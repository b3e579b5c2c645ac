"""Where the package meets the jax backend: which platform, which cache.

:func:`on_tpu` is the one answer to "are we compiling for the chip?". Every
site that chooses between a compiled Pallas (Mosaic) kernel and its
plain-XLA or interpret-mode stand-in asks it — the kernels' ``interpret=``
flag, the engine's decode/prefill kernel selection, the trainer's flash
auto-select, the device plane's transfer-server probe. No other comparison
against the jax default backend exists in the package (pinned by
``tests/test_tpu_lowering.py``), so a run that records this one answer
records which path every layer took. Callers reach it as
``backend.on_tpu()`` (module attribute, not a ``from``-import) so the CPU
lowering tests can substitute the chip's answer and cross-lower the exact
programs the chip will compile.

:func:`use_compile_cache` places jax's persistent compilation cache for the
entry points (``chip_smoke.py``, ``bench.py``, ``scripts/*_bench.py``, the
test suite).
"""

from __future__ import annotations

import os

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: jax reads the variable itself and
    nothing is set in code, so whoever runs the program places the cache.
    Unset: ``<checkout>/.jax_cache`` — a fixed path, because a directory
    made from ``tempfile``, a pid or a timestamp is never found again by
    the next run.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
