"""Prove on the chip that the LLM engine and the train step still start.

    python3 chip_smoke.py            # on a TPU; anything else exits non-zero

Drives the repo's two main paths once through the entry points a user calls,
at the full widths the repo names, with random weights made from a seed:

* kernels  — every Pallas kernel the two paths use, compiled (not
  interpreted) and compared with its plain-XLA reference;
* serving  — ``rt.init`` -> ``serve.run(LLMServer)`` with the 284M bf16 GQA
  decoder (``scripts/llm_bench.serving_config``) on the default engine (paged
  KV, prefix cache): 16 requests through the handle, 8 in flight, then the
  same prompts again so the prefix cache is hit;
* training — ``make_train_step`` on the 602M job of ``bench.train_config``
  at ``bench.TRAIN_BATCH``, 4 steps on one batch;
* four_chip — only with >= 4 devices: the ring-attention train step on a
  dp1·sp2·tp2 mesh, then a tensor-parallel engine.

All legs run in THIS process: a chip belongs to one process, and the engine
is freed before the 15 GiB train step. Stdout carries two JSON lines: the
report (versions, compile cache, every leg's numbers, ``"claim": null``) and
then, LAST, the verdict the driver parses — exactly
``{"ok": true|false, "device": {"platform": ..., "kind": ..., "count": N}}``
with the device as jax reports it and no other key. The exit code is 0 only
if every leg passed. With no TPU (or a ``device_kind`` ``bench.py`` has no
peak for) nothing is printed on stdout and the exit code is non-zero — there
is no CPU fallback.

``--rehearsal`` is the one exception, for the builder and never the driver:
the same legs at toy sizes on whatever backend jax has (Pallas in interpret
mode on the CPU), to debug the script before spending chip time. Its report
says ``"rehearsal": true`` and proves nothing about the chip.
"""

from __future__ import annotations

import argparse
import faulthandler
import functools
import gc
import importlib.metadata
import json
import sys
import threading
import time
import traceback
from collections import defaultdict

# What "agrees with the reference" means below. Kernel outputs are bf16
# (8 mantissa bits: one rounding is 2^-9 ~ 0.2% relative), the references
# are f32 at "highest" matmul precision; 1% of the reference's Frobenius
# norm leaves room for a few roundings and none for a wrong mask, a wrong
# page or a dropped block. Gradients pass through two more bf16 roundings.
FWD_REL_TOL = 1e-2
BWD_REL_TOL = 2e-2

FULL = dict(
    flash=dict(B=6, H=16, T=2048, D=128),
    prefill=dict(H=16, D=64, widths=(16, 32, 64, 128, 256, 512, 1024)),
    decode=dict(B=8, H=16, Hkv=8, D=64, S=1024),
    paged=dict(B=8, H=16, Hkv=8, D=64, M=64, bs=16),
    # (name, B, H, Hkv, D): the paged decode kernel at the three served families'
    # batch and head shapes (eight 64-wide heads a unit, a query row each; a
    # 128-wide head a unit with eight query rows; six of thirty such heads, a row each)
    paged_decode=(("smollm2", 40, 32, 32, 64), ("trinity", 48, 32, 4, 128), ("olmo_hybrid", 32, 30, 30, 128)),
    # (name, H, Hkv, D, chunk width, start, real tokens, window, table pages): the
    # two served families' head shapes at their cells' chunk and capacity
    paged_prefill=(
        ("smollm2", 32, 32, 64, 512, 1536, 512, None, 256),
        ("smollm2_tail", 32, 32, 64, 512, 3584, 77, None, 256),
        ("trinity_full", 32, 4, 128, 512, 3000, 512, 0, 512),
        ("trinity_sliding", 32, 4, 128, 512, 3000, 500, 2048, 512),
    ),
    # a decode step by diffusion over blocks at the SDAR cell's shape: 48 rows
    # x 4 positions, 32 query heads over 4 KV heads of 128, ~950 cached tokens
    # a row in a table of 256 pages
    block_step=dict(B=48, H=32, Hkv=4, D=128, Bk=4, M=256, tokens=950, reps=64),
    # (name, pools, lanes a row, sequences, rows a sequence): the pools' write at the served families' rows
    paged_write=(("smollm2_decode", 2, 2048, 40, 1), ("smollm2_chunk", 2, 2048, 1, 512), ("sdar_block_step", 2, 512, 48, 4),
                 ("olmo_hybrid_decode", 2, 3840, 32, 1), ("kimi_latent_decode", 1, 640, 64, 1), ("kimi_latent_chunk", 1, 640, 1, 512)),
    # (name, tokens, a, b, layers, experts, k, live tokens): the expert layer's
    # up projection at the served MoE cells' shapes: a block step's 48 x 4
    # tokens and a prefill chunk's 512 over all 128 experts of one layer of
    # the stack, and a decode step's 48 rows when two sequences are live
    # (idle rows follow the first's experts)
    grouped=dict(reps=100, shapes=(
        ("sdar_block_step", 192, 2048, 768, 6, 128, 8, 192),
        ("trinity_chunk", 512, 2048, 1024, 4, 128, 8, 512),
        ("trinity_decode", 48, 2048, 1024, 4, 128, 8, 2),
    )),
    serve=dict(slots=8, seq=1024, requests=16, prompt=64, new=32),
    train=dict(steps=4),
    ring=dict(batch=4, steps=3),
    tp_requests=4,
)
REHEARSAL = dict(
    flash=dict(B=1, H=2, T=256, D=128),
    prefill=dict(H=2, D=64, widths=(16, 32)),
    decode=dict(B=2, H=4, Hkv=2, D=64, S=128),
    paged=dict(B=2, H=4, Hkv=2, D=64, M=4, bs=16),
    paged_decode=(("packed", 2, 4, 4, 64), ("grouped", 3, 16, 2, 128)),
    paged_prefill=(("mha", 2, 2, 64, 32, 48, 20, None, 8), ("gqa_sliding", 8, 1, 128, 32, 80, 32, 40, 8)),
    block_step=dict(B=3, H=8, Hkv=2, D=64, Bk=4, M=8, tokens=70, reps=2),
    paged_write=(("decode", 2, 256, 3, 1), ("block_step", 2, 128, 3, 4), ("latent_chunk", 1, 128, 1, 32)),
    grouped=dict(reps=2, shapes=(("all_live", 24, 128, 128, 2, 8, 2, 24), ("two_live", 12, 128, 256, 3, 8, 2, 2))),
    serve=dict(slots=4, seq=128, requests=8, prompt=32, new=8),
    train=dict(steps=4),
    ring=dict(batch=2, steps=3),
    tp_requests=2,
)


class CompileClock:
    """Seconds jax spent tracing, lowering to MLIR and in the backend compiler
    (or fetching its result from the persistent cache — the only part a warm
    cache can save), and cache hits/misses. Read from jax's own monitoring
    events, so it also sees compiles on the engine's thread."""

    PARTS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
        "/jax/core/compile/backend_compile_duration": "backend",
    }
    COUNTS = {
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/compilation_cache/cache_misses": "cache_misses",
    }

    def __init__(self):
        import jax

        self._lock = threading.Lock()
        self._totals = defaultdict(float)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event in self.PARTS:
            with self._lock:
                self._totals[self.PARTS[event]] += secs

    def _on_event(self, event, **_):
        if event in self.COUNTS:
            with self._lock:
                self._totals[self.COUNTS[event]] += 1

    def read(self):
        with self._lock:
            return dict(self._totals)


def _rel_err(got, ref):
    import jax.numpy as jnp

    got, ref = got.astype(jnp.float32), ref.astype(jnp.float32)
    return float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref))


def _check(name, got, ref, tol, out):
    import jax.numpy as jnp

    assert got.shape == ref.shape, f"{name}: shape {got.shape} != {ref.shape}"
    assert bool(jnp.isfinite(got.astype(jnp.float32)).all()), f"{name}: non-finite values"
    err = _rel_err(got, ref)
    out[name] = round(err, 5)
    assert err < tol, f"{name}: relative error {err:.4g} >= {tol}"


def _compile(jitted, *args, on_chip):
    """Lower + compile; on the chip the program must contain a Mosaic call
    (``tpu_custom_call``) — read from the lowered text, not from a flag."""
    lowered = jitted.lower(*args)
    if on_chip:
        assert "tpu_custom_call" in lowered.as_text(), "lowered without a Mosaic kernel"
    return lowered.compile()


def _needs_gib(compiled):
    """GiB per device the compiler says the program needs: arguments +
    temporaries + outputs - donated aliases."""
    m = compiled.memory_analysis()
    needs = (
        m.argument_size_in_bytes + m.temp_size_in_bytes
        + m.output_size_in_bytes - m.alias_size_in_bytes
    )
    return round(needs / 2**30, 2)


# ---------------------------------------------------------------------------
def leg_kernels(sz, on_chip):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops.attention import _reference_attention, flash_attention
    from ray_tpu.ops.decode_attention import (
        _paged_decode_xla,
        decode_attention,
        paged_decode_attention,
        paged_prefill_attention,
    )

    errs = {}
    bf16 = jnp.bfloat16

    def rand(key, shape):
        return jax.random.normal(jax.random.key(key), shape, jnp.float32).astype(bf16)

    @jax.jit
    def ref_row(q, k, v):
        with jax.default_matmul_precision("highest"):
            return _reference_attention(q, k, v, q.shape[-1] ** -0.5, True)

    def ref_attention(q, k, v):
        # one batch row at a time: the reference materializes [H, T, T] f32
        return jnp.concatenate(
            [ref_row(q[i : i + 1], k[i : i + 1], v[i : i + 1]) for i in range(q.shape[0])]
        )

    @jax.jit
    def ref_decode(q, k_pool, v_pool, tables, lengths, layer):
        B, H, D = q.shape
        Hkv = k_pool.shape[-1] // D
        with jax.default_matmul_precision("highest"):
            out = _paged_decode_xla(
                q.reshape(B, Hkv, H // Hkv, D), k_pool, v_pool, tables, lengths, layer, D ** -0.5
            )
        return out.reshape(B, H, D)

    # flash forward + backward, default_blocks, the train step's shape
    f = sz["flash"]
    shape = (f["B"], f["H"], f["T"], f["D"])
    q, k, v, w = (rand(i, shape) for i in range(4))

    def flash_loss(q, k, v):
        return (flash_attention(q, k, v).astype(jnp.float32) * w).sum()

    def ref_loss(q, k, v):
        return (ref_attention(q, k, v).astype(jnp.float32) * w).sum()

    flash = jax.jit(flash_attention)
    out = _compile(flash, q, k, v, on_chip=on_chip)(q, k, v)
    _check("flash_fwd", out, ref_attention(q, k, v), FWD_REL_TOL, errs)
    flash_grad = jax.jit(jax.grad(flash_loss, argnums=(0, 1, 2)))
    grads = _compile(flash_grad, q, k, v, on_chip=on_chip)(q, k, v)
    ref_grads = jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2)))(q, k, v)
    for name, g, rg in zip(("flash_dq", "flash_dk", "flash_dv"), grads, ref_grads):
        _check(name, g, rg, BWD_REL_TOL, errs)
    del q, k, v, w, out, grads, ref_grads

    # flash prefill: one prompt per call at every bucket width the engine
    # produces (serve/llm._bucket), head_dim of the serving model
    p = sz["prefill"]
    for T in p["widths"]:
        q, k, v = (rand(10 + i, (1, p["H"], T, p["D"])) for i in range(3))
        out = _compile(flash, q, k, v, on_chip=on_chip)(q, k, v)
        _check(f"prefill_T{T}", out, ref_attention(q, k, v), FWD_REL_TOL, errs)

    # dense decode kernel; its reference is the paged XLA math over a pool
    # of one S-token page per sequence
    d = sz["decode"]
    B, H, Hkv, D, S = d["B"], d["H"], d["Hkv"], d["D"], d["S"]
    q = rand(20, (B, H, D))
    kc, vc = rand(21, (B, Hkv, S, D)), rand(22, (B, Hkv, S, D))
    lengths = jnp.asarray(np.linspace(1, S, B).astype(np.int32))
    out = _compile(jax.jit(decode_attention), q, kc, vc, lengths, on_chip=on_chip)(q, kc, vc, lengths)
    def as_pool(c):  # one layer, one S-token page per sequence
        return jnp.swapaxes(c, 1, 2).reshape(1, B, S, Hkv * D)

    ref = ref_decode(q, as_pool(kc), as_pool(vc), jnp.arange(B, dtype=jnp.int32)[:, None], lengths, 0)
    _check("decode_dense", out, ref, FWD_REL_TOL, errs)

    # paged decode kernel over the engine's pool layout (the second of three
    # layers), shuffled tables, ragged lengths (one empty row, one full), at the
    # shape it has had here and at the three served families'
    g = sz["paged"]
    M, bs = g["M"], g["bs"]
    for name, B, H, Hkv, D in [("", g["B"], g["H"], g["Hkv"], g["D"])] + [("_" + c[0], *c[1:]) for c in sz["paged_decode"]]:
        N = B * M + 1
        q = rand(30, (B, H, D))
        kp, vp = rand(31, (3, N, bs, Hkv * D)), rand(32, (3, N, bs, Hkv * D))
        rng = np.random.default_rng(0)
        bt = jnp.asarray(rng.permutation(np.arange(1, N)).reshape(B, M).astype(np.int32))
        lengths = jnp.asarray(np.linspace(0, M * bs, B).astype(np.int32))
        layer = jnp.int32(1)
        out = _compile(jax.jit(paged_decode_attention), q, kp, vp, bt, lengths, layer, on_chip=on_chip)(
            q, kp, vp, bt, lengths, layer
        )
        ref = ref_decode(q, kp, vp, bt, lengths, layer)
        _check(f"decode_paged{name}", out[1:], ref[1:], FWD_REL_TOL, errs)
        assert not bool(jnp.any(out[0])), f"decode_paged{name}: an empty row must be zeros"
        del q, kp, vp, out, ref

    # paged prefill kernel: a chunk of queries at a start over a shuffled
    # table, against the XLA lines on the same pool. At the serving model's
    # head shape every bucket width the engine produces, in mid-sequence with
    # a padded tail; then the served families' shapes at their chunk width
    def prefill(use_kernel, q, kp, vp, bt, start, length, layer, window):
        return paged_prefill_attention(q, kp, vp, bt, start, length, layer, window=window, use_kernel=use_kernel)

    def prefill_ref(*args):
        with jax.default_matmul_precision("highest"):
            return prefill(False, *args)

    prefill_kernel, prefill_xla = jax.jit(functools.partial(prefill, True)), jax.jit(prefill_ref)
    cases = [(f"T{T}", H, Hkv, D, T, 3 * bs + T // 2, max(1, T - 3), None, M * B) for T in p["widths"]
             if 3 * bs + T // 2 + T <= M * B * bs]
    for name, H, Hkv, D, T, start, length, window, M in cases + list(sz["paged_prefill"]):
        N = M + 2
        q = rand(40, (1, T, H, D))
        kp, vp = rand(41, (2, N, bs, Hkv * D)), rand(42, (2, N, bs, Hkv * D))
        bt = jnp.asarray(np.random.default_rng(1).permutation(np.arange(1, N))[None, :M].astype(np.int32))
        args = (q, kp, vp, bt, jnp.asarray([start], jnp.int32), jnp.asarray([length], jnp.int32), jnp.int32(1),
                None if window is None else jnp.int32(window))
        out = _compile(prefill_kernel, *args, on_chip=on_chip)(*args)
        _check(f"prefill_paged_{name}", out[:, :length], prefill_xla(*args)[:, :length], FWD_REL_TOL, errs)

    # a block step's attention (generation by diffusion over blocks): every
    # row's block of Bk queries over its cached tokens and the block itself,
    # block-causal, through the prefill kernel; one idle row. Checked against
    # the XLA lines, then timed alone (``reps`` calls chained in one program)
    # at the query tile it takes (Bk queries: 32 rows a KV head) and at the
    # prefill kernel's own 16, beside the XLA lines at default precision
    b = sz["block_step"]
    B, H, Hkv, D, Bk, M = (b[k] for k in ("B", "H", "Hkv", "D", "Bk", "M"))
    rng = np.random.default_rng(2)
    starts = (rng.integers(b["tokens"] // 2, b["tokens"] * 3 // 2, size=B) // Bk * Bk).astype(np.int32)
    used = int(-(-(starts.max() + Bk) // bs))
    N = B * used + 1
    tables = np.zeros((B, M), np.int32)
    tables[:, :used] = rng.permutation(np.arange(1, N)).reshape(B, used)
    tables[-1] = 0  # an idle row: its table is the garbage page and it holds nothing
    lengths = np.full(B, Bk, np.int32)
    lengths[-1] = 0
    q = rand(50, (B, Bk, H, D))
    kp, vp = rand(51, (2, N, bs, Hkv * D)), rand(52, (2, N, bs, Hkv * D))
    args = (q, kp, vp, jnp.asarray(tables), jnp.asarray(starts), jnp.asarray(lengths), jnp.int32(1))

    def block_attn(use_kernel, q_tile, q, kp, vp, bt, starts, lengths, layer):
        return paged_prefill_attention(q, kp, vp, bt, starts, lengths, layer, block=Bk, use_kernel=use_kernel,
                                       q_tile=q_tile)

    def block_ref(*args):
        with jax.default_matmul_precision("highest"):
            return block_attn(False, None, *args)

    out = _compile(jax.jit(functools.partial(block_attn, True, None)), *args, on_chip=on_chip)(*args)
    _check("block_step", out[:-1], jax.jit(block_ref)(*args)[:-1], FWD_REL_TOL, errs)

    def chained(fn):
        def many(q, *rest):
            return jax.lax.fori_loop(0, b["reps"], lambda _, q: q + 0 * fn(q, *rest).astype(q.dtype), q)

        return jax.jit(many)

    times = {}
    for name, fn in (("kernel_tile_of_block", functools.partial(block_attn, True, None)),
                     ("kernel_tile_16", functools.partial(block_attn, True, 16)),
                     ("xla_lines", functools.partial(block_attn, False, None))):
        many = chained(fn)
        jax.block_until_ready(many(*args))
        t0 = time.perf_counter()
        jax.block_until_ready(many(*args))
        times[name] = round(1e6 * (time.perf_counter() - t0) / b["reps"], 1)
    visible = int(((starts[:-1] + Bk + bs - 1) // bs * bs).sum())

    # the expert layer's grouped product alone: the kernel against the
    # float32 product of the live layer, then kernel and ``ragged_dot`` timed
    # (``reps`` calls in one program, the group sizes riding the loop so that
    # nothing is hoisted) and read as GB/s of the live groups' weights
    from ray_tpu.ops.grouped_matmul import grouped_matmul

    def many_products(fn):
        def many(rows, w, sizes):
            def step(_, carry):
                out = fn(rows, w, carry[0])
                return carry[0] + jnp.isnan(out[0, 0]).astype(jnp.int32), out

            return jax.lax.fori_loop(0, sz["grouped"]["reps"], step, (sizes, jnp.zeros((rows.shape[0], w.shape[2]), bf16)))

        return jax.jit(many)

    grouped = {}
    for name, tokens, a, b, L, E, k, live_tokens in sz["grouped"]["shapes"]:
        rng = np.random.default_rng(41)
        p = rng.dirichlet(np.full(E, 20.0))
        picks = np.stack([rng.choice(E, size=k, replace=False, p=p) for _ in range(tokens)])
        picks[live_tokens:] = picks[0]
        sizes = np.bincount(picks.reshape(-1), minlength=E).astype(np.int32)
        layer = L // 2
        rows = rand(60, (tokens * k, a))
        w = (jax.random.normal(jax.random.key(61), (L * E, a, b), jnp.float32) * a ** -0.5).astype(bf16)
        in_stack = jnp.zeros((L, E), jnp.int32).at[layer].set(jnp.asarray(sizes)).reshape(L * E)
        out = _compile(jax.jit(grouped_matmul), rows, w, in_stack, on_chip=on_chip)(rows, w, in_stack)
        ref = jax.jit(lambda r, w, s: jax.lax.ragged_dot(r.astype(jnp.float32), w.astype(jnp.float32), s, precision="highest"))(
            rows, w[layer * E:(layer + 1) * E], jnp.asarray(sizes))
        _check(f"grouped_{name}", out, ref, FWD_REL_TOL, errs)
        live_bytes = int((sizes > 0).sum()) * a * b * 2
        grouped[name] = {"live_groups": int((sizes > 0).sum()), "rows_a_group_max": int(sizes.max()), "live_weight_bytes": live_bytes}
        for label, fn in (("kernel", grouped_matmul), ("ragged_dot", jax.lax.ragged_dot)):
            many = many_products(fn)
            jax.block_until_ready(many(rows, w, in_stack))
            t0 = time.perf_counter()
            jax.block_until_ready(many(rows, w, in_stack))
            us = 1e6 * (time.perf_counter() - t0) / sz["grouped"]["reps"]
            grouped[name][label] = {"us_a_call": round(us, 1), "live_GB_per_s": round(live_bytes / us / 1e3, 1)}
        del rows, w, out, ref

    # the pools' write (``paged_write_rows``), compiled, against the scatter it
    # replaces on the chip: a call's new rows into layer 1 of the pools, every
    # page but the garbage page 0 bit for bit. A decode call (a row a slot at a
    # random offset, the last slot idle), a block step's few rows, a chunk from
    # inside a page with a padded tail
    from ray_tpu.models.generation import _paged_write_index
    from ray_tpu.ops.decode_attention import paged_write_rows, paged_write_segments

    written = {}
    for name, n, lanes, B, T in sz["paged_write"]:
        rng = np.random.default_rng(len(written))
        M = -(-(3 * bs + 5 + T) // bs) + 1
        N = B * M + 1
        tables = rng.permutation(np.arange(1, N)).reshape(B, M).astype(np.int32)
        keep = np.full(B, T if T < bs else T - 3)
        if B > 1:
            tables[-1], keep[-1] = 0, 0  # an idle slot
        starts = rng.integers(0, 2 * bs // T, size=B) * T if T < bs else np.full(B, 3 * bs + 5)
        positions = jnp.asarray(starts[:, None] + np.arange(T)[None, :], jnp.int32)
        phys, off = _paged_write_index(jnp.asarray(tables), positions, jnp.asarray(np.arange(T)[None, :] < keep[:, None]), bs)
        pools = tuple(rand(60 + i, (2, N, bs, lanes)) for i in range(n))
        new = tuple(rand(70 + i, (B * T, lanes)) for i in range(n))

        def kernel(pools, new, phys, off, B=B):
            return paged_write_rows(pools, new, 1, paged_write_segments(phys, off, sequences=B, block_size=bs))

        got = _compile(jax.jit(kernel), pools, new, phys, off, on_chip=on_chip)(pools, new, phys, off)
        want = tuple(pool.at[1, phys, off].set(rows) for pool, rows in zip(pools, new))
        written[name] = all(bool(jnp.array_equal(g[:, 1:], w[:, 1:])) for g, w in zip(got, want))
        assert written[name], f"paged_write_{name}: the pools differ from the scatter's off the garbage page"
        del pools, new, got, want
    return {"rel_err": errs, "tolerance": {"fwd": FWD_REL_TOL, "bwd": BWD_REL_TOL},
            "block_step_us_a_call": times, "block_step_kv_bytes": visible * 2 * Hkv * D * 2,
            "grouped_product": grouped, "paged_write_is_the_scatter": written}


# ---------------------------------------------------------------------------
def _serving_model(on_chip):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import TransformerConfig, init_params
    from ray_tpu.scripts.llm_bench import serving_config

    if on_chip:
        cfg = serving_config()
    else:  # rehearsal
        cfg = TransformerConfig(
            vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
            d_ff=128, max_seq_len=128, attention="dense", dtype=jnp.bfloat16,
        )
    return cfg, init_params(cfg, jax.random.key(0))


def leg_serving(sz, on_chip):
    import ray_tpu as rt
    from ray_tpu import serve
    from ray_tpu.observability.events import global_event_manager
    from ray_tpu.serve.llm import LLMServer

    s = sz["serve"]
    cfg, params = _serving_model(on_chip)
    span = cfg.vocab_size - 2
    prompts = [[(7 * i + j) % span + 1 for j in range(s["prompt"])] for i in range(s["requests"])]

    rt.init(num_cpus=2)
    try:
        handle = serve.run(
            serve.deployment(LLMServer).bind(
                lambda: (cfg, params), max_batch_size=s["slots"], max_seq_len=s["seq"]
            ),
            route_prefix=None,
        )

        def wave():
            """Every prompt once, ``slots`` in flight at a time; returns the
            token lists and how far the requests overlapped (sum of request
            latencies over wall time: 1 = served one by one)."""
            outs, lat, t0 = [], 0.0, time.perf_counter()
            for i in range(0, len(prompts), s["slots"]):
                resps = [
                    handle.remote({"prompt": p, "max_tokens": s["new"], "temperature": 0.0})
                    for p in prompts[i : i + s["slots"]]
                ]
                for r in resps:
                    body = r.result(timeout=600)
                    outs.append(body["tokens"])
                    lat += body["latency_s"]
            return outs, lat / (time.perf_counter() - t0)

        first, overlap = wave()
        again, _ = wave()
        stats = handle.stats.remote().result(timeout=60)
        decode_text = handle.lowered_decode_text.remote().result(timeout=300)
    finally:
        serve.shutdown()
        rt.shutdown()

    for toks in first:
        assert len(toks) == s["new"], f"got {len(toks)} tokens, wanted {s['new']}"
        assert all(0 <= t < cfg.vocab_size for t in toks), "token id out of range"
    assert again == first, "the prefix-cached repeat wave is not token-identical"
    assert overlap > 2, f"requests did not overlap in the engine (overlap {overlap:.2f})"
    assert stats["kv_block_pool_size"] > 0, stats
    assert stats["prefix_cache_hits"] > 0, "the repeat wave never hit the prefix cache"
    assert stats["active_slots"] == stats["queued"] == stats["prefilling"] == 0, stats
    # quiesced engine: every page still out of the pool is a prefix-cache page
    assert stats["kv_blocks_in_use"] == stats["prefix_cache_blocks"], stats
    crashes = [
        e for e in global_event_manager().list_events(source_type="SERVE")
        if e.label == "engine_crash"
    ]
    assert not crashes, f"engine_crash flight record: {crashes[-1].message}"
    has_mosaic = "tpu_custom_call" in decode_text
    if on_chip:
        assert has_mosaic, "the engine's paged decode program has no Mosaic kernel"
    # which write of the new K and V rows the programs hold, and that the decode program bears it out
    assert stats["kv_write"] == ("kernel" if on_chip else "scatter"), stats["kv_write"]
    assert ('kernel_name = "paged_write"' in decode_text) == on_chip, "the decode program's pool write is not what stats() says"
    return {
        "requests": 2 * len(prompts),
        "in_flight": s["slots"],
        "overlap": round(overlap, 2),
        "prefix_cache_hits": stats["prefix_cache_hits"],
        "prefix_tokens_reused": stats["prefix_tokens_reused"],
        "decode_program_has_tpu_custom_call": has_mosaic,
        "kv_write": stats["kv_write"],
    }


# ---------------------------------------------------------------------------
def _train_config(on_chip):
    import jax.numpy as jnp

    from bench import TRAIN_BATCH, train_config
    from ray_tpu.models.transformer import TransformerConfig

    if on_chip:
        return train_config(), TRAIN_BATCH
    cfg = TransformerConfig(
        vocab_size=256, d_model=128, n_layers=2, n_heads=2, d_ff=256, max_seq_len=128,
        dtype=jnp.bfloat16, attention="flash", remat="dots", scan_layers=False,
    )
    return cfg, 2


def _fixed_batch(cfg, batch):
    import jax.numpy as jnp
    import numpy as np

    return jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (batch, cfg.max_seq_len)), jnp.int32
    )


def _falling(losses):
    import math

    assert all(math.isfinite(x) for x in losses), f"non-finite loss in {losses}"
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    return [round(x, 4) for x in losses]


def _wait_device_free(limit=512 << 20, timeout_s=30.0):
    """The engine's weights and KV pool must be gone before a step that
    needs ~15 of the chip's 16 GB. Serve's router thread lets go of the
    replica a second or two after ``serve.shutdown()``, so poll."""
    import jax

    deadline = time.monotonic() + timeout_s
    while True:
        gc.collect()
        held = (jax.devices()[0].memory_stats() or {}).get("bytes_in_use", 0)
        if held < limit:
            return
        assert time.monotonic() < deadline, (
            f"{held >> 20} MiB still held on the device {timeout_s:.0f} s after the last leg"
        )
        time.sleep(0.5)


def leg_training(sz, on_chip):
    import jax

    from ray_tpu.models.transformer import make_train_step

    _wait_device_free()

    cfg, batch = _train_config(on_chip)
    init_state, train_step = make_train_step(cfg)
    state = init_state(jax.random.key(0))
    tokens = _fixed_batch(cfg, batch)
    step = _compile(train_step, state, tokens, on_chip=on_chip)
    losses = []
    for _ in range(sz["train"]["steps"]):
        state, loss = step(state, tokens)
        losses.append(float(jax.block_until_ready(loss)))
    limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit", 0)
    return {
        "batch": batch, "losses": _falling(losses),
        "hbm_gib": {"step_needs": _needs_gib(step), "device_limit": round(limit / 2**30, 2)},
    }


# ---------------------------------------------------------------------------
def leg_four_chip(sz, on_chip):
    import dataclasses

    import jax
    import numpy as np
    from jax.sharding import Mesh

    from ray_tpu.models.transformer import make_train_step
    from ray_tpu.serve.llm import LLMEngine

    _wait_device_free()
    devices = jax.devices()[:4]

    # README "Distributed training in one screen" at the 602M widths
    cfg, _ = _train_config(on_chip)
    cfg = dataclasses.replace(cfg, attention="ring")
    mesh = Mesh(np.array(devices).reshape(1, 2, 2), ("dp", "sp", "tp"))
    with mesh:
        init_state, step = make_train_step(cfg, mesh=mesh)
        state = init_state(jax.random.key(0))
        tokens = step.shard_batch(_fixed_batch(cfg, sz["ring"]["batch"]))
        step = _compile(step, state, tokens, on_chip=on_chip)
        losses = []
        for _ in range(sz["ring"]["steps"]):
            state, loss = step(state, tokens)
            losses.append(float(jax.block_until_ready(loss)))
    wq = state["params"]["layers"]["wq"]
    assert len(wq.sharding.device_set) > 1 and not wq.sharding.is_fully_replicated, (
        f"wq is not laid out over the mesh: {wq.sharding}"
    )
    in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0) for d in devices]
    if on_chip:  # the CPU backend reports no memory stats
        assert all(in_use), f"a device holds nothing — state is not spread: {in_use}"
    ring = {
        "batch": sz["ring"]["batch"], "losses": _falling(losses), "step_needs_gib": _needs_gib(step),
        "bytes_in_use_gib": [round(b / 2**30, 2) for b in in_use],
    }
    del state, tokens, wq, step
    gc.collect()

    # tensor-parallel engine over all four
    scfg, params = _serving_model(on_chip)
    s = sz["serve"]
    span = scfg.vocab_size - 2
    engine = LLMEngine(
        scfg, params, max_batch_size=s["slots"], max_seq_len=s["seq"],
        mesh=Mesh(np.array(devices), ("tp",)),
    )
    try:
        prompts = [[(5 * i + j) % span + 1 for j in range(s["prompt"])] for i in range(sz["tp_requests"])]
        futures = [engine.submit(p, max_tokens=s["new"]) for p in prompts]
        outs = [f.result(timeout=600) for f in futures]
        # the same prompts again: served from the pool's cached pages
        again = [engine.submit(p, max_tokens=s["new"]).result(timeout=600) for p in prompts]
        emb = engine.runner.params["embed"]
        assert len(emb.sharding.device_set) == 4, f"embed on {len(emb.sharding.device_set)} device(s)"
        stats = engine.stats()
        # after prefills, decode steps and copy-on-write: each device still
        # holds every page, of its own KV heads (all heads where 4 does not
        # divide them)
        pool = engine.runner.cache["k"]
        shard = tuple(pool.addressable_shards[0].data.shape)
    finally:
        engine.shutdown()
    for toks in outs:
        assert len(toks) == s["new"] and all(0 <= t < scfg.vocab_size for t in toks), toks
    assert again == outs, "the prefix-cached repeat is not token-identical under the mesh"
    assert stats["prefix_cache_hits"] > 0 and stats["cow_copies"] > 0, stats
    heads = scfg.kv_heads // 4 if scfg.kv_heads % 4 == 0 else scfg.kv_heads
    assert shard == pool.shape[:3] + (heads * scfg.head_dim,), f"pool {pool.shape} lies as {shard} a device"
    return {"ring_train": ring, "tp_engine": {
        "requests": len(outs) + len(again), "kv_pool_shard": list(shard),
        "prefix_cache_hits": stats["prefix_cache_hits"]}}


def verdict(summary: dict) -> dict:
    """The last stdout line: the two keys the driver's contract names, no more."""
    return {"ok": summary["ok"], "device": summary["device"]}


LEGS = {"kernels": leg_kernels, "serving": leg_serving, "training": leg_training,
        "four_chip": leg_four_chip}


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--rehearsal", action="store_true",
        help="toy sizes on any backend, to debug this script; proves nothing about the chip",
    )
    parser.add_argument(
        "--legs", default=",".join(LEGS),
        help="comma-separated subset of %(default)s (the summary lists what ran)",
    )
    args = parser.parse_args(argv)
    selected = [leg for leg in args.legs.split(",") if leg]
    unknown = set(selected) - set(LEGS)
    if unknown:
        parser.error(f"unknown leg(s) {sorted(unknown)}; choose from {list(LEGS)}")

    # the driver allows 1200 s: past 1100 dump every thread's stack and die
    faulthandler.dump_traceback_later(1100, exit=True)

    import jax
    import jaxlib

    from bench import peak_flops
    from ray_tpu.ops import backend

    cache_dir = backend.use_compile_cache()
    # cache every program, not only those past jax's 1 s default: a smoke is
    # mostly small programs, and uncached they were ~45 s of a warm run's 53 s
    # of compiling (my chip run, PR 22)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = CompileClock()
    dev = jax.devices()[0]
    on_chip = not args.rehearsal
    if on_chip:
        try:
            peak_flops(dev)  # platform "tpu" and a device_kind with a known peak
        except RuntimeError as exc:
            print(f"chip_smoke: {exc}", file=sys.stderr)
            return 2
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:  # e.g. a CPU-only rehearsal box
        libtpu = "not installed"
    summary = {
        "ok": False,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())},
        "rehearsal": args.rehearsal,
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__, "libtpu": libtpu},
        "compile_cache": cache_dir,
        "legs": {},
    }
    print(f"chip_smoke: {json.dumps(summary['device'])} {summary['versions']}", file=sys.stderr)

    sizes = REHEARSAL if args.rehearsal else FULL
    for leg in selected:
        if leg == "four_chip" and len(jax.devices()) < 4:
            summary["four_chip"] = f"not run: {len(jax.devices())} device(s)"
            continue
        before = clock.read()
        t0 = time.perf_counter()
        try:
            result = {"status": "ok", **LEGS[leg](sizes, on_chip)}
        except Exception as exc:  # noqa: BLE001 — record the leg, run the others
            traceback.print_exc()
            result = {"status": "failed", "error": f"{type(exc).__name__}: {exc}"[:500]}
        wall = time.perf_counter() - t0
        spent = {k: v - before.get(k, 0) for k, v in clock.read().items()}
        parts = {k: round(spent.get(k, 0.0), 1) for k in CompileClock.PARTS.values()}
        compile_s = sum(spent.get(k, 0.0) for k in parts)
        result.update(
            compile_s=round(compile_s, 1), compile_parts=parts,
            run_s=round(max(0.0, wall - compile_s), 1),
            cache_hits=int(spent.get("cache_hits", 0)),
            cache_misses=int(spent.get("cache_misses", 0)),
        )
        summary["legs"][leg] = result
        print(f"chip_smoke: {leg}: {json.dumps(result)}", file=sys.stderr)

    summary["ok"] = bool(summary["legs"]) and all(
        r["status"] == "ok" for r in summary["legs"].values()
    )
    summary["claim"] = None
    faulthandler.cancel_dump_traceback_later()
    print(json.dumps(summary))
    print(json.dumps(verdict(summary)), flush=True)
    return 0 if summary["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
