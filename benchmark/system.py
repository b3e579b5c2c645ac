"""The seam between the benchmark and the program: the few calls that build
the system under test from a configuration file. Everything else in the
benchmark is the yardstick and never imports ``ray_tpu``."""

from __future__ import annotations

import importlib
import json
import os
from typing import Any, Dict

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(relpath: str) -> Dict[str, Any]:
    with open(os.path.join(ROOT, relpath)) as f:
        return json.load(f)


def model_module(config: Dict[str, Any]):
    """The family module a configuration file names under ``"model"``."""
    return importlib.import_module(f"benchmark.models.{config['model']}")


def shrink_for_rehearsal(config: Dict[str, Any]) -> Dict[str, Any]:
    """Builder-only: the configuration at the toy sizes its file lists under
    ``"rehearsal"``, to walk the whole command on a CPU. Never a measurement."""
    small = dict(config)
    small.update(config.get("rehearsal", {}).get("config", {}))
    run = dict(config["run"])
    run.update(config.get("rehearsal", {}).get("run", {}))
    small["run"] = run
    return small


def make_params(cfg, seed: int, embed_table_scale: float = 1.0):
    """The weights, on the device, in one jitted call from the seed, in the
    type the configuration serves them in: the program's own initialiser,
    with the tied embedding table scaled by ``embed_table_scale`` (the
    configuration file says why)."""
    import jax

    from ray_tpu.models.transformer import init_params

    def make(key):
        params = init_params(cfg, key)
        params["embed"] = (params["embed"] * embed_table_scale).astype(params["embed"].dtype)
        return params

    return jax.jit(make)(jax.random.key(seed % (2**31)))


def device_info() -> Dict[str, Any]:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def memory_peak_bytes(n_chips: int) -> int:
    """Peak bytes in use on the fullest of the chips the cell used."""
    import jax

    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices()[:n_chips]
    ]
    return int(max(peaks))


def train_state_shardings(cfg, mesh, state_shapes):
    """The shardings ``make_train_step``'s own ``sharded_init`` gives the
    train state (params per ``param_specs``, AdamW's moments like their
    params, counters replicated), as a tree — so that the state can be made
    under one jit, already spread, instead of whole on one chip first."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models.transformer import param_specs

    specs = param_specs(cfg, kv_tp=cfg.kv_heads % mesh.shape.get("tp", 1) == 0)
    params_def = jax.tree.structure(state_shapes["params"])

    def params_like(node):
        return jax.tree.structure(node) == params_def

    def place(node):
        if params_like(node):
            return jax.tree.map(lambda _, spec: NamedSharding(mesh, spec), node, specs)
        return NamedSharding(mesh, P())

    return jax.tree.map(place, state_shapes, is_leaf=params_like)
