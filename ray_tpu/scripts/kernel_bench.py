"""On-chip kernel A/Bs: decode attention and flash block sizes.

Microsecond-scale kernels are timed by SCANNING N iterations inside ONE
jitted program — one dispatch amortized over N kernel invocations — and
every timing closes on ``block_until_ready`` plus a host read of the
result. Needs the chip: off it the kernels run in interpret mode, and an
interpreter timing is not a kernel timing.

Run: ``python -m ray_tpu.scripts.kernel_bench`` (through the chip tool).
"""

from __future__ import annotations

import functools
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
    np.asarray(leaf.reshape(-1)[:1])  # a host read of one number, not of the array


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


def _device_ops(run: Callable) -> list:
    """The device's operations (profiler events of ``/device:TPU:0``'s ``XLA
    Ops`` line) during one ``run()``, which returns what to wait for."""
    import glob
    import tempfile

    from jax.profiler import ProfileData

    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            _sync(run())
        data = ProfileData.from_file(sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1])
    return [ev for plane in data.planes if plane.name == "/device:TPU:0" for line in plane.lines
            if line.name == "XLA Ops" for ev in line.events]


def _kernel_seconds(fn: Callable, args, iters: int) -> float:
    """Device seconds the Mosaic kernels of a jitted ``fn`` take a call: the
    custom-calls' durations in a profiler trace of ``iters`` calls (the
    kernel alone: no dispatch, no XLA operation beside it)."""
    _sync(fn(*args))  # compile + warm

    def calls():
        for _ in range(iters):
            out = fn(*args)
        return out

    ns = sum(ev.duration_ns for ev in _device_ops(calls) if " custom-call(" in ev.name)
    if not ns:
        raise SystemExit("no custom-call in the device trace: not a compiled Mosaic kernel")
    return ns / iters / 1e9


def _flash_inputs(B, H, Tq, Tk, D, Dv, dtype=jnp.bfloat16, seed=1):
    """q, k, v and the output's cotangent, from ``seed``."""
    kq, kk, kv, kg = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(kq, (B, H, Tq, D), dtype), jax.random.normal(kk, (B, H, Tk, D), dtype),
            jax.random.normal(kv, (B, H, Tk, Dv), dtype), jax.random.normal(kg, (B, H, Tq, Dv), dtype))


def bench_flash_kernels(B=1, H=16, Tq=8192, Tk=None, D=192, Dv=128, causal=True, window=None, block_q=None,
                        block_k=None, iters=10) -> Dict[str, float]:
    """The three flash kernels apart, ms a call: the forward, the queries'
    gradient and the keys' and values' (each backward program keeps one
    ``pallas_call`` and XLA drops the other; both hold the row sum ``delta``,
    an elementwise pass over ``dO`` and ``O``). Keys of ``D``, values of
    ``Dv``; ``Tk`` defaults to ``Tq``. ``tiles``: ``tile_counts`` of the call."""
    from ray_tpu.ops import attention

    Tk = Tk or Tq
    q, k, v, do = _flash_inputs(B, H, Tq, Tk, D, Dv)
    default_q, default_k = attention.default_blocks(D, q.dtype.itemsize)
    bq, bk = block_q or default_q, block_k or default_k
    scale = D ** -0.5

    # through the module, so a caller can stand another tree's kernels in their place
    def fwd(q, k, v):
        return attention._flash_forward(q, k, v, scale, causal, bq, bk, attention._use_interpret(), window=window)

    def bwd(q, k, v, out, lse, do):
        return attention._flash_backward(q, k, v, out, lse, do, scale, causal, bq, bk, attention._use_interpret(), window=window)

    out, lse = jax.jit(fwd)(q, k, v)
    res = (q, k, v, out, lse, do)
    return {
        "fwd_ms": _kernel_seconds(jax.jit(fwd), (q, k, v), iters) * 1e3,
        "dq_ms": _kernel_seconds(jax.jit(lambda *a: bwd(*a)[0]), res, iters) * 1e3,
        "dkv_ms": _kernel_seconds(jax.jit(lambda *a: bwd(*a)[1:]), res, iters) * 1e3,
        "tiles": attention.tile_counts(Tq, Tk, bq, bk, causal, window),
    }


def flash_errors(B=1, H=16, Tq=2048, Tk=None, D=192, Dv=128, causal=True, window=None, seed=1) -> Dict[str, dict]:
    """``out``, ``lse``, ``dq``, ``dk``, ``dv`` of the flash kernels at bf16
    inputs against dense float32 attention of the same inputs at
    ``Precision.HIGHEST`` (keep T <= 2048: the reference holds the scores).
    ``rel_rms``: the relative RMS error; the outputs are bf16, so ~1.6e-3 is
    their rounding alone. ``rounded_apart``: the share of numbers that differ
    from the reference rounded to bf16, which sees what ``rel_rms`` cannot:
    an error inside the kernel a hundredth of the last bit moves about that
    share of the numbers across a rounding boundary."""
    from ray_tpu.ops import attention

    Tk = Tk or Tq
    q, k, v, do = _flash_inputs(B, H, Tq, Tk, D, Dv, seed=seed)
    scale = D ** -0.5

    def dense(q, k, v):
        hi = jax.lax.Precision.HIGHEST
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=hi) * scale
        i, j = jnp.arange(Tq)[:, None], jnp.arange(Tk)[None, :]
        seen = jnp.ones((Tq, Tk), bool) if not causal else j <= i
        if window is not None:
            seen = seen & (j > i - window)
        s = jnp.where(seen, s, attention.NEG_INF)
        lse = jax.nn.logsumexp(s, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", jnp.exp(s - lse[..., None]), v, precision=hi), lse

    def flash(q, k, v):
        return attention.flash_attention_with_lse(q, k, v, scale, causal, None, None, window)

    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    (want, want_lse), pull = jax.vjp(dense, *f32)
    (got, got_lse), pull_flash = jax.vjp(flash, q, k, v)
    wants = (want, want_lse) + pull((do.astype(jnp.float32), jnp.zeros_like(want_lse)))
    gots = (got, got_lse) + pull_flash((do, jnp.zeros_like(got_lse)))
    rms = lambda x: float(jnp.sqrt(jnp.mean(jnp.square(x.astype(jnp.float32)))))  # noqa: E731
    names = ("out", "lse", "dq", "dk", "dv")
    return {"rel_rms": {n: rms(g.astype(jnp.float32) - w) / rms(w) for n, g, w in zip(names, gots, wants)},
            "rounded_apart": {n: float(jnp.mean(g != w.astype(g.dtype))) for n, g, w in zip(names, gots, wants) if n != "lse"}}


def bench_flash_blocks(B=1, H=8, T=8192, D=128, Dv=None, iters=8) -> Dict[str, float]:
    """The flash kernels across block-size configs at T=8k, fwd, dq and dkv apart."""
    out = {}
    for bq, bk in ((128, 128), (256, 512), (512, 1024)):
        got = bench_flash_kernels(B, H, T, None, D, Dv or D, block_q=bq, block_k=bk, iters=iters)
        out.update({f"flash_{bq}x{bk}_{k}": v for k, v in got.items() if k != "tiles"})
    return out


# the training cells' flash calls (B, H, Tq, Tk, D, Dv, causal): Moonlight's
# expanded latent heads, SmolLM2 at depth 8, and the ring at tp2 x sp2: its own
# zigzag shard under the causal mask and a hop against half a peer's, unmasked
FLASH_CELL_CALLS = {
    "moonlight": (1, 16, 8192, 8192, 192, 128, True),
    "train-l8": (4, 32, 2048, 2048, 64, 64, True),
    "ring4-own": (1, 16, 4096, 4096, 64, 64, True),
    "ring4-hop": (1, 16, 4096, 2048, 64, 64, False),
}


def bench_flash_cells(iters=10, calls=None) -> Dict[str, dict]:
    """Time (fwd, dq, dkv apart) and error of the flash kernels at each of the
    training cells' calls; the error at 2048 positions of the same heads."""
    out = {}
    for name in calls or FLASH_CELL_CALLS:
        B, H, Tq, Tk, D, Dv, causal = FLASH_CELL_CALLS[name]
        out[name] = bench_flash_kernels(B, H, Tq, Tk, D, Dv, causal, iters=iters)
        out[name]["error"] = flash_errors(1, H, 2048, 2048 * Tk // Tq, D, Dv, causal)
    return out


def bench_factor_product(D=128, steps=256, iters=10) -> Dict[str, dict]:
    """How the MXU takes a float32 factor: one ``P [512, 1024] . V [1024, D]``
    product a grid step from resident tiles, float32 out. ``f32``: both tiles
    float32 (what a kernel that casts its bf16 operands up hands Mosaic);
    ``terms N``: ``P`` split into N bf16 terms, a pass each, against the bf16
    ``V`` (a float32 is three such terms exactly; one term is what
    ``ops/attention.py::_dot`` does). us a product, and the error against the
    float64 product of the same numbers."""
    from jax.experimental import pallas as pl

    def dot(a, b):
        return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    kp, kv = jax.random.split(jax.random.key(2))
    p = jax.random.uniform(kp, (512, 1024), jnp.float32)
    v = jax.random.normal(kv, (1024, D), jnp.bfloat16)
    want = np.asarray(p, np.float64) @ np.asarray(v.astype(jnp.float32), np.float64)

    def run(terms):
        def kernel(p_ref, v_ref, o_ref):
            rest, v = p_ref[...], v_ref[...]
            if not terms:
                o_ref[...] = dot(rest, v.astype(jnp.float32))
                return
            term = rest.astype(jnp.bfloat16)
            out = dot(term, v)
            for _ in range(terms - 1):
                rest = rest - term.astype(jnp.float32)
                term = rest.astype(jnp.bfloat16)
                out = out + dot(term, v)
            o_ref[...] = out

        return jax.jit(pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct((steps * 512, D), jnp.float32), grid=(steps,),
            in_specs=[pl.BlockSpec((512, 1024), lambda i: (0, 0)), pl.BlockSpec((1024, D), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((512, D), lambda i: (i, 0))))

    out = {}
    for terms in (0, 1, 2, 3):
        fn = run(terms)
        err = np.asarray(fn(p, v)[:512], np.float64) - want
        out["f32" if not terms else f"terms {terms}"] = {
            "us": _kernel_seconds(fn, (p, v), iters) / steps * 1e6,
            "rel_rms_error": float(np.sqrt(np.mean(err ** 2) / np.mean(want ** 2))),
        }
    return out


# the serve cells' pool writes: (pools, lanes a row, sequences of a decode call, positions it writes a sequence: a
# block step's 4). A prefill chunk is 512 rows of one sequence in every cell
PAGED_WRITE_CALLS = {
    "smollm2": (2, 2048, 40, 1),
    "trinity-mini": (2, 512, 48, 1),
    "sdar": (2, 512, 48, 4),
    "olmo-hybrid": (2, 3840, 32, 1),
    "kimi-linear": (1, 640, 64, 1),
}
_HBM_BYTES_PER_S = 819e9  # one v5e chip, published


def _device_us(once: Callable, iters: int) -> float:
    """Device microseconds an iteration of a program that scans ``iters`` of
    them: every operation's duration in a profiler trace of one ``once()``
    (which runs the program and returns what to wait for; control flow left
    out: its time is its body's), over ``iters``."""
    _sync(once())  # compile + warm
    ns = sum(ev.duration_ns for ev in _device_ops(once)
             if not any(f" {c}(" in ev.name for c in ("while", "conditional", "call")))
    return ns / iters / 1e3


def bench_paged_write(iters=48, pages=1024, layers=4, calls=None) -> Dict[str, dict]:
    """The write of a call's new K and V rows (or latent rows) into the paged
    pools, alone, at each serve cell's shapes: a decode call (a row a slot,
    each in a page of its own at a random offset; SDAR: a block of 4), the
    same with one slot live and the others idle (bound for page 0, as most
    are in the cells) and a 512-row prefill chunk from a page boundary. us a call (device time of
    everything the write costs, index arithmetic and layout included; both
    pools) for ``scatter`` (``pool.at[l, phys, off].set``: XLA's scatter, a
    row an update), ``scatter_told`` (the same told its indices are unique
    and sorted), ``scatter_dropped`` (the idle rows out of bounds and
    dropped), for a chunk ``page_scatter`` (a page an update: only right
    where the chunk fills whole pages) and ``kernel``
    (``ops.decode_attention.paged_write_rows``); ``bytes_us`` is the rows'
    own bytes at the chip's bandwidth, ``page_bytes_us`` what moving the
    touched pages costs (read and written once; written only where a chunk
    fills them), which is what a copy engine that moves whole tiles can
    reach. The pools are donated to the timed program and ride its scan."""
    from ray_tpu.ops.decode_attention import paged_write_rows, paged_write_segments

    bs, out = 16, {}
    for name in calls or PAGED_WRITE_CALLS:
        n, lanes, slots, span = PAGED_WRITE_CALLS[name]
        out[name] = {}
        for call, (B, T) in {"decode": (slots, span), "decode_one_live": (slots, span), "chunk": (1, 512)}.items():
            rng = np.random.default_rng(B * T)
            R = B * T
            # a page a sequence and layer-step, distinct within a call; a chunk's pages in a row
            first = rng.permutation(np.arange(1, pages - T // bs - 1))[:B]
            start = np.zeros(B, np.int64) if call == "chunk" else rng.integers(0, bs // span, size=B) * span
            pos = start[:, None] + np.arange(T)[None, :]
            live = np.arange(B)[:, None] < (1 if call == "decode_one_live" else B)  # the others idle: page 0
            phys = jnp.asarray(np.where(live, first[:, None] + pos // bs, 0).reshape(-1), jnp.int32)
            off = jnp.asarray((pos % bs).reshape(-1), jnp.int32)
            rows = tuple(jax.random.normal(jax.random.key(i), (R, lanes), jnp.bfloat16) for i in range(n))

            def scatter(pools, layer, **told):
                return tuple(p.at[layer, phys, off].set(r, **told) for p, r in zip(pools, rows))

            def page_scatter(pools, layer):
                return tuple(p.at[layer, phys[::bs]].set(r.reshape(R // bs, bs, lanes)) for p, r in zip(pools, rows))

            def kernel(pools, layer):
                return paged_write_rows(pools, rows, layer, paged_write_segments(phys, off, sequences=B, block_size=bs))

            def scatter_dropped(pools, layer):  # the idle rows out of bounds and dropped, not sent to page 0
                return tuple(p.at[layer, jnp.where(phys > 0, phys, pages), off].set(r, mode="drop") for p, r in zip(pools, rows))

            ways = {"scatter": scatter, "kernel": kernel}
            if call == "decode_one_live":
                ways["scatter_dropped"] = scatter_dropped
            else:
                ways["scatter_told"] = functools.partial(scatter, unique_indices=True, indices_are_sorted=call == "chunk")
            if call == "chunk":
                ways["page_scatter"] = page_scatter
            got = {"rows": R, "lanes": lanes, "pools": n}
            some = tuple(jax.random.normal(jax.random.key(7 + i), (layers, pages, bs, lanes), jnp.bfloat16) for i in range(n))
            got["kernel_is_scatter"] = all(bool(jnp.array_equal(a[:, 1:], b[:, 1:])) for a, b in zip(
                jax.jit(kernel)(some, jnp.int32(1)), jax.jit(scatter)(some, jnp.int32(1))))
            del some
            for way, fn in ways.items():
                def run(pools, fn=fn):
                    def body(carry, i):
                        return fn(carry, i % layers), None

                    return jax.lax.scan(body, pools, jnp.arange(iters, dtype=jnp.int32))[0]

                # fresh pools a way: the timed program is given them to keep, and hands them back
                held = [tuple(jnp.zeros((layers, pages, bs, lanes), jnp.bfloat16) for _ in range(n))]
                timed = jax.jit(run, donate_argnums=(0,))

                def once(held=held, timed=timed):
                    held[0] = timed(held[0])
                    return held[0]

                got[f"{way}_us"] = _device_us(once, iters)
                held.clear()
            row_bytes = R * lanes * 2 * n
            got["us_a_row"] = {w[:-3]: v / R for w, v in got.items() if w.endswith("_us")}
            got["bytes_us"] = row_bytes / _HBM_BYTES_PER_S * 1e6
            touched = {"decode": 2 * B, "decode_one_live": 2, "chunk": R // bs}[call] * bs * lanes * 2 * n
            got["page_bytes_us"] = touched / _HBM_BYTES_PER_S * 1e6
            out[name][call] = got
    return out


def _latent_decode_variant(walk: str, work: str, span: int, *, rank: int, scale: float):
    """``latent_paged_decode`` (jitted, the same arguments) with another walk
    (``beside``: the module's; ``shared``: :func:`_walk_pages`; ``none``: no
    copy is issued) around another work a group (``pieces``: the module's;
    ``one_piece``: ``_softmax_step`` on the whole group; ``no_chain``: the two
    products, the scores standing in for the probabilities; ``nothing``)."""
    from jax.experimental import pallas as pl

    from ray_tpu.ops import decode_attention as da

    def kernel(tables_ref, lengths_ref, layer_ref, q_ref, pool_hbm, o_ref, buf, sems, m_scr, l_scr, acc_scr):
        bi = pl.program_id(0)
        bs = pool_hbm.shape[2]
        held = jnp.minimum(lengths_ref[bi], tables_ref.shape[1] * bs)
        tile = min(da._LATENT_KEY_TILE, span)

        @pl.when(bi == 0)
        def _():
            buf[...] = jnp.zeros_like(buf)

        m_scr[...] = jnp.full_like(m_scr, da.NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        one_pass = functools.partial(da._dot_pv, terms=1)

        def one_piece(g, slot):
            pos = g * span + jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)
            s = da._dot_qk(q_ref[...], buf[slot]) * scale
            da._softmax_step(jnp.where(pos < held, s, da.NEG_INF), buf[slot, :, :rank], 0, m_scr, l_scr, acc_scr,
                             dot_pv=one_pass)
            yield

        def no_chain(g, slot):
            scores = []
            for t in range(0, span, tile):
                scores.append(da._dot_qk(q_ref[...], buf[slot, t:t + tile]) * scale)
                yield
            s = jnp.concatenate(scores, axis=1)
            yield
            for n in range(0, rank, da._LANES):
                lanes = slice(n, n + da._LANES)
                acc_scr[0, :, lanes] = acc_scr[0, :, lanes] + one_pass(s, buf[slot, :, lanes])
                yield

        def nothing(g, slot):  # as many places to start copies at as the module's work has
            yield from [None] * (span // tile + 1 + rank // da._LANES)

        def pieces(g, slot):
            return da._latent_group_work(g, slot, q_ref, buf, m_scr, l_scr, acc_scr, held, sm_scale=scale, rank=rank)

        group_work = {"pieces": pieces, "one_piece": one_piece, "no_chain": no_chain, "nothing": nothing}[work]

        def whole(g, slot):
            for _ in group_work(g, slot):
                pass

        if walk == "beside":
            da._walk_latent_pages(tables_ref, bi, layer_ref[0], pool_hbm, buf, sems, bs, held, group_work)
        elif walk == "shared":
            da._walk_pages(tables_ref, bi, layer_ref[0], pool_hbm, None, buf, None, sems, bs, 0, held, whole)
        else:
            jax.lax.fori_loop(0, pl.cdiv(held, span), lambda g, c: whole(g, g % 2), None)
        o_ref[...] = da._finished(m_scr, l_scr, acc_scr, 0).astype(o_ref.dtype)

    def call(q, pool, tables, lengths, layer):
        return da._latent_call(kernel, "latent_paged_decode", [tables, lengths, layer.reshape(1)], q, pool,
                               grid=(q.shape[0],), rows=q.shape[1], q_index=lambda b, *_: (b, 0, 0), rank=rank, span=span)

    return jax.jit(call)


def bench_latent_decode(iters=8, rows=64, heads=32, lanes=640, rank=512, pages=32768, tokens=(16_500, 17_500),
                        spans=(256, 512, 1024, 2048), seed=0) -> Dict[str, dict]:
    """The latent decode kernel alone at ``doc-sessions``' shape: ``rows``
    sequences of 16.5-17.5k cached tokens, tables that name pages anywhere in
    a pool ``[2, pages, 16, lanes]`` bf16 (sequences share pages, as the
    cell's sessions share their documents'), a layer. us a call (the
    custom-call's device time) and ``bytes_share``, the share of it that the
    visited pages' bytes take at the chip's bandwidth. ``split``, at the
    module's group of cached rows: ``kernel`` (``latent_paged_decode`` as it
    is) and ``shared_walk`` (the kernel as it stood on :func:`_walk_pages` and
    ``_softmax_step``: a group's copies in loops in front of its products),
    each checked against ``_latent_xla`` (``rel_err``: the largest difference
    over the largest number); then what computes nothing to check
    (:func:`_latent_decode_variant`): the module's work on the shared walk,
    the copies alone of each walk (the work a no-op), the arithmetic alone on
    whatever the buffers hold (no copy issued; in pieces and in one piece),
    and the products without the softmax chain between them. ``spans``: both
    kernels at each group size."""
    from ray_tpu.ops import decode_attention as da

    bs, scale = 16, 0.05
    rng = np.random.default_rng(seed)
    lengths = rng.integers(tokens[0], tokens[1] + 1, size=rows)
    tables = jnp.asarray(rng.integers(1, pages, size=(rows, -(-tokens[1] // bs) + 1)), jnp.int32)
    kq, kp = jax.random.split(jax.random.key(seed))
    real = jnp.arange(lanes) < lanes - 64  # the pool's pad lanes hold zeros
    pool = jnp.where(real, jax.random.normal(kp, (2, pages, bs, lanes), jnp.bfloat16), 0).astype(jnp.bfloat16)
    q = jnp.where(real, jax.random.normal(kq, (rows, heads, lanes), jnp.bfloat16), 0).astype(jnp.bfloat16)
    lens, layer = jnp.asarray(lengths, jnp.int32), jnp.int32(1)
    args = (q, pool, tables, lens, layer)
    bytes_us = float(np.sum(-(-lengths // bs))) * bs * lanes * 2 / _HBM_BYTES_PER_S * 1e6
    variant = functools.partial(_latent_decode_variant, rank=rank, scale=scale)

    def timed(fn) -> dict:
        us = _kernel_seconds(fn, args, iters) * 1e6
        return {"us": us, "bytes_share": bytes_us / us}

    def checked(fn) -> dict:
        got, worst, top = fn(*args), 0.0, 0.0
        for b in range(0, rows, 8):  # the reference gathers a dense float32 view: eight sequences at a time
            want = da.latent_paged_decode(q[b:b + 8], pool, tables[b:b + 8], lens[b:b + 8], layer, rank=rank,
                                          sm_scale=scale, use_kernel=False).astype(jnp.float32)
            worst = max(worst, float(jnp.abs(got[b:b + 8].astype(jnp.float32) - want).max()))
            top = max(top, float(jnp.abs(want).max()))
        return {**timed(fn), "rel_err": worst / top}

    def the_kernel(span):
        return jax.jit(functools.partial(da.latent_paged_decode, rank=rank, sm_scale=scale, span=span))

    span = da._LATENT_DECODE_SPAN
    out = {"rows": int(lengths.sum()), "bytes_us": bytes_us, "span": span, "split": {
        "kernel": checked(the_kernel(span)),
        "shared_walk": checked(variant("shared", "one_piece", span)),
        "shared_walk_pieces": timed(variant("shared", "pieces", span)),
        "copies_alone": timed(variant("beside", "nothing", span)),
        "shared_walk_copies_alone": timed(variant("shared", "nothing", span)),
        "arithmetic_alone": timed(variant("none", "pieces", span)),
        "arithmetic_alone_one_piece": timed(variant("none", "one_piece", span)),
        "products_alone": timed(variant("none", "no_chain", span)),
    }}
    out["spans"] = {str(s): {"kernel": timed(the_kernel(s)), "shared_walk": timed(variant("shared", "one_piece", s))}
                    for s in spans}
    return out


def main(argv=None) -> None:
    """Examples:

        python -m ray_tpu.scripts.kernel_bench                 # decode + 8k/D=128
        python -m ray_tpu.scripts.kernel_bench --flash-cells   # the training cells' calls: time and error
        python -m ray_tpu.scripts.kernel_bench --paged-write   # the serve cells' pool writes: scatter against kernel
        python -m ray_tpu.scripts.kernel_bench --latent-decode # the latent decode kernel alone, split and group sizes
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
    parser.add_argument("--Dv", type=int, default=None, help="the values' size (default: D)")
    parser.add_argument("--skip-decode", action="store_true")
    parser.add_argument("--flash-cells", action="store_true",
                        help="the flash kernels at the training cells' calls, and the float32-factor probe; nothing else")
    parser.add_argument("--paged-write", action="store_true",
                        help="the write of a call's K and V rows into the paged pools at the serve cells' shapes; nothing else")
    parser.add_argument("--latent-decode", action="store_true",
                        help="the latent decode kernel alone at doc-sessions' shape: the split and the group sizes; nothing else")
    args = parser.parse_args(argv)

    from ray_tpu.ops import backend

    dev = jax.devices()[0]
    if not backend.on_tpu():
        raise SystemExit(
            f"kernel_bench times compiled kernels and found platform "
            f"{dev.platform!r}, not a TPU"
        )
    backend.use_compile_cache()
    results = {"device": getattr(dev, "device_kind", str(dev))}
    if args.paged_write:
        results.update(bench_paged_write())
        print(json.dumps(results))
        return
    if args.latent_decode:
        results.update(bench_latent_decode(args.iters))
        print(json.dumps(results))
        return
    if args.flash_cells:
        results.update(bench_flash_cells(args.iters), factor_product=bench_factor_product(iters=args.iters))
        print(json.dumps(results))
        return
    results["shape"] = f"T={args.T} D={args.D} Dv={args.Dv or args.D} H={args.H}"
    if not args.skip_decode:
        results.update(bench_decode())
    results.update(bench_flash_blocks(H=args.H, T=args.T, D=args.D, Dv=args.Dv, iters=args.iters))
    print(json.dumps({k: (round(v, 2) if isinstance(v, float) else v) for k, v in results.items()}))


if __name__ == "__main__":
    main()
