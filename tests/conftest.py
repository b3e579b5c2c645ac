"""Test fixtures.

Parity with the reference's ``python/ray/tests/conftest.py``: a
``ray_start_regular``-style fixture for a fresh single-node runtime, and a
``ray_start_cluster`` fixture that builds multi-node clusters in one process
(reference: ``python/ray/cluster_utils.py:135`` spawns extra raylets; here
extra Node objects share one control service).

JAX runs on a virtual 8-device CPU mesh so sharding/collective tests work
without TPU hardware.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# No arena pre-fault in tests: populating a 2 GB segment per rt.init steals
# ~0.5 s of the single core per test for bandwidth no test needs (the bench
# keeps it — that is where cold-page memcpy rates matter).
os.environ.setdefault("RAY_TPU_SHM_PREFAULT", "0")

import jax  # noqa: E402

# also covers a jax that a pytest plugin imported before this file ran
jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache: the suite compiles hundreds of tiny jitted
# programs (8-device mesh shardings, pallas kernels, train steps) and
# recompiling them every run is a large share of suite wall time. The
# helper leaves JAX_COMPILATION_CACHE_DIR alone when set, else uses the
# fixed <checkout>/.jax_cache.
from ray_tpu.ops.backend import use_compile_cache  # noqa: E402

use_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption(
        "--full", action="store_true", default=False,
        help="run the full tier (slow/soak tests) in addition to the smoke tier",
    )


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "full: slow/soak tests excluded from the default smoke tier "
        "(run with --full; always run before capturing BENCH/MULTICHIP artifacts)",
    )


def pytest_collection_modifyitems(config, items):
    if config.getoption("--full"):
        return
    skip_full = pytest.mark.skip(reason="full tier: run with --full")
    for item in items:
        if "full" in item.keywords:
            item.add_marker(skip_full)


@pytest.fixture
def ray_start_regular():
    import ray_tpu as rt

    rt.init(num_cpus=4)
    try:
        yield rt
    finally:
        rt.shutdown()


@pytest.fixture
def ray_start_cluster():
    """Multi-node in-process cluster; yields (rt, cluster)."""
    import ray_tpu as rt

    cluster = rt.init(num_cpus=2)
    try:
        yield rt, cluster
    finally:
        rt.shutdown()
