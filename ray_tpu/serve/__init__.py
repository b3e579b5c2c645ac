"""ray_tpu.serve: online model serving.

TPU-native rebuild of the reference's Ray Serve (``python/ray/serve/``,
SURVEY §2.4/§3.6): a controller actor reconciles deployment replicas with
queue-depth autoscaling; handles route with power-of-two-choices; an HTTP
proxy fronts apps; ``@serve.batch`` shapes concurrent requests into MXU
batches; ``@serve.multiplexed`` LRU-caches many models per replica.
"""

from ray_tpu.serve.api import (
    HTTPOptions,
    _run,
    delete,
    get_app_handle,
    get_deployment_handle,
    ingress,
    grpc_address,
    proxy_url,
    run,
    run_config,
    shutdown,
    start,
    status,
)
from ray_tpu.serve.batching import batch
from ray_tpu.serve.deployment import Application, AutoscalingConfig, Deployment, deployment
from ray_tpu.serve.llm import LLMEngine, LLMServer
from ray_tpu.serve.openai_compat import OpenAICompatLLMServer
from ray_tpu.serve.multiplex import get_multiplexed_model_id, multiplexed
from ray_tpu.serve.replica import ReplicaContext, get_replica_context
from ray_tpu.serve.router import DeploymentHandle, DeploymentResponse

__all__ = [
    "Application",
    "LLMEngine",
    "LLMServer",
    "OpenAICompatLLMServer",
    "AutoscalingConfig",
    "Deployment",
    "DeploymentHandle",
    "DeploymentResponse",
    "HTTPOptions",
    "ReplicaContext",
    "batch",
    "delete",
    "deployment",
    "get_app_handle",
    "get_deployment_handle",
    "get_replica_context",
    "ingress",
    "get_multiplexed_model_id",
    "grpc_address",
    "multiplexed",
    "proxy_url",
    "run",
    "run_config",
    "shutdown",
    "start",
    "status",
]
