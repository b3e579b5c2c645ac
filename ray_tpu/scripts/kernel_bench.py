"""On-chip kernel A/Bs: decode attention and flash block sizes.

Microsecond-scale kernels are timed by SCANNING N iterations inside ONE
jitted program — one dispatch amortized over N kernel invocations — and
every timing closes on ``block_until_ready`` plus a host read of the
result. Needs the chip: off it the kernels run in interpret mode, and an
interpreter timing is not a kernel timing.

Run: ``python -m ray_tpu.scripts.kernel_bench`` (through the chip tool).
"""

from __future__ import annotations

import time
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np


def _timed_scan(step_fn: Callable, init_carry, iters: int) -> float:
    """Seconds per iteration of step_fn, scanned inside one jit program."""

    @jax.jit
    def run(carry):
        def body(c, _):
            return step_fn(c), None

        out, _ = jax.lax.scan(body, carry, None, length=iters)
        return out

    # compile + warm
    out = run(init_carry)
    _sync(out)
    t0 = time.perf_counter()
    out = run(init_carry)
    _sync(out)
    return (time.perf_counter() - t0) / iters


def _sync(tree) -> None:
    leaf = jax.block_until_ready(jax.tree_util.tree_leaves(tree)[0])
    np.asarray(jax.device_get(leaf)).ravel()[:1]


# ---------------------------------------------------------------------------
def bench_decode(B=8, H=16, Hkv=4, D=128, S=4096, iters=50) -> Dict[str, float]:
    """Decode-attention kernel vs the dense GQA fallback, one token step."""
    from ray_tpu.ops.decode_attention import decode_attention

    key = jax.random.key(0)
    q = jax.random.normal(key, (B, H, D), jnp.float32)
    k_cache = jax.random.normal(key, (B, Hkv, S, D), jnp.float32)
    v_cache = jax.random.normal(key, (B, Hkv, S, D), jnp.float32)
    lengths = jnp.full((B,), S, jnp.int32)

    def kernel_step(q):
        out = decode_attention(q, k_cache, v_cache, lengths)
        return out.astype(q.dtype)  # carry shape = q shape

    def dense_step(q):
        n_rep = H // Hkv
        qg = q.reshape(B, Hkv, n_rep, D)
        scores = jnp.einsum("bgrd,bgsd->bgrs", qg, k_cache) / np.sqrt(D)
        mask = jnp.arange(S)[None, None, None, :] < lengths[:, None, None, None]
        scores = jnp.where(mask, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bgrs,bgsd->bgrd", probs, v_cache)
        return out.reshape(B, H, D)

    t_kernel = _timed_scan(kernel_step, q, iters)
    t_dense = _timed_scan(dense_step, q, iters)
    return {"decode_kernel_us": t_kernel * 1e6, "decode_dense_us": t_dense * 1e6,
            "speedup": t_dense / t_kernel}


def bench_flash_blocks(B=1, H=8, T=8192, D=128, iters=8) -> Dict[str, float]:
    """Flash fwd across block-size configs at T=8k (fits alongside scan)."""
    from ray_tpu.ops.attention import flash_attention

    key = jax.random.key(1)
    q = jax.random.normal(key, (B, H, T, D), jnp.bfloat16)
    k = jax.random.normal(key, (B, H, T, D), jnp.bfloat16)
    v = jax.random.normal(key, (B, H, T, D), jnp.bfloat16)

    out = {}
    for bq, bk in ((128, 128), (256, 512), (512, 1024)):
        def step(q, bq=bq, bk=bk):
            return flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk).astype(q.dtype)

        out[f"flash_{bq}x{bk}_ms"] = _timed_scan(step, q, iters) * 1e3
    return out


def main(argv=None) -> None:
    """Examples:

        python -m ray_tpu.scripts.kernel_bench                 # decode + 8k/D=128
        python -m ray_tpu.scripts.kernel_bench --T 32768 --D 64 --H 4 --iters 2
        python -m ray_tpu.scripts.kernel_bench --T 8192 --D 64 --iters 4
    """
    import argparse
    import json

    parser = argparse.ArgumentParser(description="on-chip kernel A/Bs")
    parser.add_argument("--T", type=int, default=8192)
    parser.add_argument("--D", type=int, default=128)
    parser.add_argument("--H", type=int, default=8)
    parser.add_argument("--iters", type=int, default=8)
    parser.add_argument("--skip-decode", action="store_true")
    args = parser.parse_args(argv)

    from ray_tpu.ops import backend

    dev = jax.devices()[0]
    if not backend.on_tpu():
        raise SystemExit(
            f"kernel_bench times compiled kernels and found platform "
            f"{dev.platform!r}, not a TPU"
        )
    backend.use_compile_cache()
    results = {"device": getattr(dev, "device_kind", str(dev)),
               "shape": f"T={args.T} D={args.D} H={args.H}"}
    if not args.skip_decode:
        results.update(bench_decode())
    results.update(bench_flash_blocks(H=args.H, T=args.T, D=args.D, iters=args.iters))
    print(json.dumps({k: (round(v, 2) if isinstance(v, float) else v) for k, v in results.items()}))


if __name__ == "__main__":
    main()
