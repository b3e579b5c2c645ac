"""Flash attention as Pallas TPU kernels, forward and backward.

The hot op of the model stack (SURVEY §7 phase 4): blockwise online-softmax
attention that keeps the [Tq, Tk] score matrix out of HBM — scores live in
VMEM one (block_q x block_k) tile at a time, feeding the MXU per tile.

All kernels use a 3-D grid (batch*heads, outer block, inner block) with the
inner dimension streaming K/V (forward, dq) or Q (dk/dv) through VMEM one
block per step — no full-sequence operand ever resides in VMEM, so context
length is bounded by HBM, not VMEM (64k+ sequences compile where a
full-K/V-resident kernel dies at ~16k). Running max/denominator/accumulator
state lives in VMEM scratch across inner steps; outputs are written on the
last step (the standard revisited-output pattern).

A tile pays for what it holds (``_tiles``, ``_dot``, ``_resident``): operands
reach the MXU in the type they arrive in (bf16 x bf16 is one exact pass;
float32 arrivals stay float32), and a grid step whose tile is skipped names
the block already in VMEM, so the pipeline copies nothing for it.
``tile_counts`` counts a shape's tiles that run, are crossed and are skipped.

Forward saves the per-row logsumexp; backward rematerializes P blockwise in
two kernels (dq over q-blocks, dk/dv over k-blocks — the FlashAttention-2
split that avoids atomics), so both directions are linear in sequence memory.
Long-sequence training composes this with ``ray_tpu.parallel.ring``
(blockwise ring attention over an ICI axis). On non-TPU backends the kernels
run in interpret mode so tests exercise identical code paths on the virtual
CPU mesh.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import backend

NEG_INF = -1e30
_LANES = 128  # m/l scratch is lane-replicated to keep stores 2-D tileable


def _block_mask(q_start, k_start, block_q, block_k, causal, q_len, kv_len, window=None):
    """[block_q, block_k] validity mask (None when nothing is masked).

    ``window``: sliding-window (local) attention — key j is visible to
    query i iff i - window < j (combined with causal: j <= i), the
    Mistral-style local mask."""
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    valid = None
    if causal:
        valid = k_pos <= q_pos
    if window is not None:
        in_w = k_pos > q_pos - window
        valid = in_w if valid is None else jnp.logical_and(valid, in_w)
    if q_len is not None:
        in_q = q_pos < q_len
        valid = in_q if valid is None else jnp.logical_and(valid, in_q)
    if kv_len is not None:
        in_k = k_pos < kv_len
        valid = in_k if valid is None else jnp.logical_and(valid, in_k)
    return valid


_NT = (((1,), (1,)), ((), ()))  # a [m, d] . b [n, d]^T
_NN = (((1,), (0,)), ((), ()))  # a [m, n] . b [n, d]
_TN = (((0,), (0,)), ((), ()))  # a [m, n]^T . b [m, d]


def _tiles(*refs):
    """The refs' tiles in the type they arrive in where that is bf16 for all
    of them, else as float32."""
    tiles = [r[...] for r in refs]
    if all(t.dtype == jnp.bfloat16 for t in tiles):
        return tiles
    return [t.astype(jnp.float32) for t in tiles]


def _dot(a, b, dims):
    """The float32 product of two tiles. Two bf16 tiles: one pass of the MXU,
    exact (a bf16 x bf16 product is exact in float32). A float32 factor (P,
    dS) goes in the other tile's type: against a bf16 tile rounded to bf16,
    which is what Mosaic makes of a float32 operand left to itself (one pass
    of the rounded operand, the same error to the last digit; PERF.md
    section 6, PR 52)."""
    return jax.lax.dot_general(a.astype(b.dtype), b, dims, preferred_element_type=jnp.float32)


def _across(x, width):
    """A row statistic held in every lane, ``[rows, LANES]``, across ``width``
    lanes: whole lane tiles repeated, no ``[rows, 1]`` column to broadcast
    (a column costs the forward 8% of its time on the chip; PERF.md, PR 52)."""
    if width % _LANES == 0:
        return x if width == _LANES else pltpu.repeat(x, width // _LANES, axis=1)
    if width < _LANES:
        return x[:, :width]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], width))


def _tile_runs(q_start, k_start, block_q, block_k, causal, window):
    """Whether some pair of the tile at (q_start, k_start) may be visible: a
    tile above the causal diagonal, or wholly left of the sliding window, has
    none and is skipped. True where the call has neither."""
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1
    if window is not None:
        # the EARLIEST query (i = q_start) has the loosest bound j > i - window
        run = run & (k_start + block_k - 1 > q_start - window)
    return run


def _k_blocks_run(i, block_q, block_k, num_k, causal, window):
    """(first, last) K block whose tile runs for query block ``i``:
    :func:`_tile_runs` solved for the block index."""
    first, last = 0, num_k - 1
    if causal:
        last = jnp.minimum(last, (i * block_q + block_q - 1) // block_k)
    if window is not None:
        first = jnp.maximum(i * block_q - window + 1, 0) // block_k
    return first, last


def _q_blocks_run(j, block_q, block_k, num_q, causal, window):
    """(first, last) query block whose tile runs for K block ``j``."""
    first, last = 0, num_q - 1
    if causal:
        first = jnp.minimum(j * block_k // block_q, last)
    if window is not None:
        last = jnp.minimum(last, (j * block_k + block_k + window - 2) // block_q)
    return first, last


def _resident(step, first, last):
    """The streamed block a grid step names: its own where its tile runs, else
    the nearest one that does, which is the block already in VMEM, so the
    pipeline issues no copy for a step that is skipped."""
    if isinstance(first, int) and isinstance(last, int):
        return step  # the whole grid: every tile runs
    return jnp.clip(step, first, last)


def tile_counts(Tq, Tk, block_q, block_k, causal, window=None):
    """(run, crossed, skipped) tiles a head of a call's grid; static per
    shape. A tile runs by the kernels' own predicate; one that runs is crossed
    if the diagonal, the window's edge or a padded tail hides some pair of it
    (the kernels mask every tile alike: the count sizes a block shape,
    PERF.md section 7)."""
    bq, bk = min(block_q, Tq), min(block_k, Tk)
    q_start = np.arange(-(-Tq // bq))[:, None] * bq
    k_start = np.arange(-(-Tk // bk))[None, :] * bk
    run = np.broadcast_to(_tile_runs(q_start, k_start, bq, bk, causal, window), (q_start.size, k_start.size))
    clear = (q_start + bq <= Tq) & (k_start + bk <= Tk)
    if causal:
        clear = clear & (k_start + bk - 1 <= q_start)
    if window is not None:
        clear = clear & (k_start > q_start + bq - 1 - window)
    return int(run.sum()), int((run & ~clear).sum()), int((~run).sum())


def _attn_fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, sm_scale: float, causal: bool, block_q: int, block_k: int, kv_len, window=None,
):
    """Grid (bh, q_block, k_block); k innermost streams K/V through VMEM.

    q_ref: [block_q, D]; k_ref: [block_k, D], v_ref: [block_k, Dv] (this
    step's tile; the values' size is their own: latent attention's heads
    have keys of 192 and values of 128); o_ref: [block_q, Dv]; lse_ref:
    [1, block_q] (this q-block's slice — per-block mapping keeps stores
    statically aligned and Megacore-safe); scratch: m/l [block_q, LANES]
    lane-replicated, acc [block_q, Dv]. ``kv_len``: the keys' true length
    where their tail is padded, else None.
    """
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    num_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    @pl.when(_tile_runs(q_start, k_start, block_q, block_k, causal, window))
    def _step():
        q, k, v = _tiles(q_ref, k_ref, v_ref)
        if q.dtype == jnp.bfloat16:
            s = _dot(q, k, _NT) * sm_scale  # as both backward kernels recompute it
        else:
            s = _dot(q * sm_scale, k, _NT)
        valid = _block_mask(q_start, k_start, block_q, block_k, causal, None, kv_len, window=window)
        if valid is not None:
            s = jnp.where(valid, s, NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]    # [bq, LANES], every lane the row's
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(jnp.where(m_prev == NEG_INF, NEG_INF, m_prev - m_new))
        p = jnp.exp(s - _across(m_new, block_k))
        if valid is not None:
            p = jnp.where(valid, p, 0.0)
        l_scr[...] = l_prev * alpha + p.sum(axis=-1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * _across(alpha, acc_scr.shape[1]) + _dot(p, v, _NN)

    @pl.when(ki == num_k - 1)
    def _final():
        m = m_scr[:, :1]
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0, 1.0, l)
        o_ref[...] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        lse = jnp.where(l == 0, NEG_INF, m + jnp.log(l_safe))   # [bq, 1]
        lse_ref[0, :] = lse[:, 0]


def _pad_to(x, axis, multiple):
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _kv_map(block_q, block_k, num_k, causal, window):
    """The K/V index map of a (bh, q_block, k_block) grid."""
    return lambda bh, i, j: (bh, _resident(j, *_k_blocks_run(i, block_q, block_k, num_k, causal, window)), 0)


def _flash_forward(q, k, v, sm_scale: float, causal: bool, block_q: int, block_k: int, interpret: bool, window=None):
    """Returns (out [B,H,Tq,Dv], lse [B*H, 1, Tq_padded]). q, k: [B,H,T,D]; v: [B,H,Tk,Dv]."""
    B, H, Tq, D = q.shape
    Tk, Dv = k.shape[2], v.shape[3]
    bq = min(block_q, Tq)
    bk = min(block_k, Tk)
    # pad ragged tails to block multiples: padded q rows are computed then
    # sliced off; padded keys are masked in-kernel via kv_len.
    q = _pad_to(q, 2, bq)
    k = _pad_to(k, 2, bk)
    v = _pad_to(v, 2, bk)
    Tq_p, Tk_p = q.shape[2], k.shape[2]
    qf = q.reshape(B * H, Tq_p, D)
    kf = k.reshape(B * H, Tk_p, D)
    vf = v.reshape(B * H, Tk_p, Dv)

    grid = (B * H, Tq_p // bq, Tk_p // bk)
    kv_map = _kv_map(bq, bk, grid[2], causal, window)
    out, lse = pl.pallas_call(
        functools.partial(
            _attn_fwd_kernel, sm_scale=sm_scale, causal=causal,
            block_q=bq, block_k=bk, kv_len=Tk if Tk < Tk_p else None, window=window,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tq_p, Dv), q.dtype),
            jax.ShapeDtypeStruct((B * H, 1, Tq_p), jnp.float32),
        ],
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, bq, D), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((None, bk, D), kv_map),
            pl.BlockSpec((None, bk, Dv), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((None, bq, Dv), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((None, 1, bq), lambda bh, i, j: (bh, 0, i)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, Tq_p, Dv)[:, :, :Tq, :], lse


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr,
    *, sm_scale, causal, block_q, block_k, kv_len, window=None,
):
    """Grid (bh, q_block, k_block); streams K/V. dq accumulates in scratch.

    q/dq: [block_q, D]; do: [block_q, Dv]; k: [block_k, D]; v: [block_k, Dv];
    lse/delta: [1, block_q].
    """
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    num_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    @pl.when(_tile_runs(q_start, k_start, block_q, block_k, causal, window))
    def _step():
        q, k, v, do = _tiles(q_ref, k_ref, v_ref, do_ref)
        lse = lse_ref[0, :]
        delta = delta_ref[0, :]
        s = _dot(q, k, _NT) * sm_scale
        p = jnp.exp(s - lse[:, None])
        valid = _block_mask(q_start, k_start, block_q, block_k, causal, None, kv_len, window=window)
        if valid is not None:
            p = jnp.where(valid, p, 0.0)
        dp = _dot(do, v, _NT)
        ds = p * (dp - delta[:, None])
        dq_scr[...] = dq_scr[...] + _dot(ds, k, _NN)

    @pl.when(ki == num_k - 1)
    def _final():
        dq_ref[...] = (dq_scr[...] * sm_scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr,
    *, sm_scale, causal, block_q, block_k, q_len, kv_len, window=None,
):
    """Grid (bh, k_block, q_block); streams Q/dO. dk/dv accumulate in scratch.

    k/dk: [block_k, D]; v/dv: [block_k, Dv]; q: [block_q, D]; do: [block_q, Dv];
    lse/delta: [1, block_q]. ``q_len``/``kv_len``: the true length where that
    tail is padded, else None.
    """
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    num_q = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    @pl.when(_tile_runs(q_start, k_start, block_q, block_k, causal, window))
    def _step():
        qs, k, v, do = _tiles(q_ref, k_ref, v_ref, do_ref)
        lse = lse_ref[0, :]
        delta = delta_ref[0, :]
        s = _dot(qs, k, _NT) * sm_scale
        p = jnp.exp(s - lse[:, None])
        valid = _block_mask(q_start, k_start, block_q, block_k, causal, q_len, kv_len, window=window)
        if valid is not None:
            p = jnp.where(valid, p, 0.0)
        dv_scr[...] = dv_scr[...] + _dot(p, do, _TN)
        dp = _dot(do, v, _NT)
        ds = p * (dp - delta[:, None])
        dk_scr[...] = dk_scr[...] + _dot(ds, qs, _TN)

    @pl.when(qi == num_q - 1)
    def _final():
        dk_ref[...] = (dk_scr[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _flash_backward(q, k, v, out, lse, g, sm_scale, causal, block_q, block_k, interpret, g_lse=None, window=None):
    B, H, Tq, D = q.shape
    Tk, Dv = k.shape[2], v.shape[3]
    bq = min(block_q, Tq)
    bk = min(block_k, Tk)
    qp = _pad_to(q, 2, bq)
    gp = _pad_to(g, 2, bq)
    op = _pad_to(out, 2, bq)
    kp = _pad_to(k, 2, bk)
    vp = _pad_to(v, 2, bk)
    Tq_p, Tk_p = qp.shape[2], kp.shape[2]
    qf = qp.reshape(B * H, Tq_p, D)
    kf = kp.reshape(B * H, Tk_p, D)
    vf = vp.reshape(B * H, Tk_p, Dv)
    gf = gp.reshape(B * H, Tq_p, Dv)
    of = op.reshape(B * H, Tq_p, Dv)
    # delta = rowsum(dO * O): cheap elementwise, plain XLA
    delta = jnp.sum(gf.astype(jnp.float32) * of.astype(jnp.float32), axis=-1)[:, None, :]
    if g_lse is not None:
        # d lse/d s = softmax = P, so the lse cotangent folds into the same
        # P * (dP - delta) term with delta := delta - g_lse
        glp = _pad_to(g_lse.astype(jnp.float32).reshape(B * H, Tq), 1, bq)
        delta = delta - glp[:, None, :]

    q_len, kv_len = Tq if Tq < Tq_p else None, Tk if Tk < Tk_p else None
    num_q, num_k = Tq_p // bq, Tk_p // bk
    kv_map = _kv_map(bq, bk, num_k, causal, window)

    def q_block(j, i):  # of the (bh, k_block, q_block) grid
        return _resident(i, *_q_blocks_run(j, bq, bk, num_q, causal, window))

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
            block_q=bq, block_k=bk, kv_len=kv_len, window=window,
        ),
        out_shape=jax.ShapeDtypeStruct((B * H, Tq_p, D), q.dtype),
        grid=(B * H, num_q, num_k),
        in_specs=[
            pl.BlockSpec((None, bq, D), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((None, bk, D), kv_map),
            pl.BlockSpec((None, bk, Dv), kv_map),
            pl.BlockSpec((None, bq, Dv), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((None, 1, bq), lambda bh, i, j: (bh, 0, i)),
            pl.BlockSpec((None, 1, bq), lambda bh, i, j: (bh, 0, i)),
        ],
        out_specs=pl.BlockSpec((None, bq, D), lambda bh, i, j: (bh, i, 0)),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qf, kf, vf, gf, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
            block_q=bq, block_k=bk, q_len=q_len, kv_len=kv_len, window=window,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tk_p, D), k.dtype),
            jax.ShapeDtypeStruct((B * H, Tk_p, Dv), v.dtype),
        ],
        grid=(B * H, num_k, num_q),
        in_specs=[
            pl.BlockSpec((None, bq, D), lambda bh, j, i: (bh, q_block(j, i), 0)),
            pl.BlockSpec((None, bk, D), lambda bh, j, i: (bh, j, 0)),
            pl.BlockSpec((None, bk, Dv), lambda bh, j, i: (bh, j, 0)),
            pl.BlockSpec((None, bq, Dv), lambda bh, j, i: (bh, q_block(j, i), 0)),
            pl.BlockSpec((None, 1, bq), lambda bh, j, i: (bh, 0, q_block(j, i))),
            pl.BlockSpec((None, 1, bq), lambda bh, j, i: (bh, 0, q_block(j, i))),
        ],
        out_specs=[
            pl.BlockSpec((None, bk, D), lambda bh, j, i: (bh, j, 0)),
            pl.BlockSpec((None, bk, Dv), lambda bh, j, i: (bh, j, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qf, kf, vf, gf, lse, delta)

    dq = dq.reshape(B, H, Tq_p, D)[:, :, :Tq, :]
    dk = dk.reshape(B, H, Tk_p, D)[:, :, :Tk, :]
    dv = dv.reshape(B, H, Tk_p, Dv)[:, :, :Tk, :]
    return dq, dk, dv


def _reference_attention(q, k, v, sm_scale: float, causal: bool):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * sm_scale
    if causal:
        Tq, Tk = s.shape[-2], s.shape[-1]
        mask = jnp.arange(Tk)[None, :] <= jnp.arange(Tq)[:, None]
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


def default_blocks(head_dim: int, itemsize: int = 2) -> tuple:
    """(block_q, block_k) for the flash kernels: (512, 1024) at every shape of
    two-byte operands; (512, 512) for float32 ones, whose backward tiles at
    keys of 192 and values of 128 otherwise pass the kernel's VMEM by 2 MB
    (the compiler's refusal, chipless, PR 51).

    The 602M train step (T=2048, D=128) compiles and runs with these on a
    v5e (``chip_smoke.py``). No per-shape block timing taken from a compiled
    kernel is on record — ROADMAP S7 owns measuring one from a device trace
    before this grows a per-shape table.
    """
    del head_dim  # shape-independent today
    return (512, 1024) if itemsize <= 2 else (512, 512)


def flash_attention(
    q,
    k,
    v,
    sm_scale: Optional[float] = None,
    causal: bool = True,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
):
    """Blockwise flash attention. q,k,v: [B, H, T, D].

    Block sizes default to :func:`default_blocks`.

    Thin wrapper over :func:`flash_attention_with_lse` (an unused lse
    output costs a zero cotangent, which folds away in the backward).
    """
    return flash_attention_with_lse(q, k, v, sm_scale, causal, block_q, block_k)[0]


def sliding_window_attention(
    q, k, v, window: int, *, sm_scale: Optional[float] = None, causal: bool = True,
    block_q: Optional[int] = None, block_k: Optional[int] = None,
):
    """Local (sliding-window) flash attention.

    With ``causal=True`` (the Mistral-style long-context mask) query i sees
    keys in (i - window, i]; off-window blocks are skipped entirely, so
    compute is O(T * window). With ``causal=False`` the window bounds only
    the PAST — keys j > i - window, including all future positions — and
    compute stays O(T^2) on the future side.
    """
    if window < 1:
        raise ValueError(f"window must be >= 1, got {window} (0 would mask every key)")
    return flash_attention_with_lse(q, k, v, sm_scale, causal, block_q, block_k, window)[0]


def flash_attention_with_lse(
    q,
    k,
    v,
    sm_scale: Optional[float] = None,
    causal: bool = True,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    window: Optional[int] = None,
):
    """Flash attention that also returns the per-row logsumexp.

    q, k: [B,H,T,D]; v: [B,H,Tk,Dv], a size of its own (latent attention's
    heads: keys of 192, values of 128). Returns (out [B,H,Tq,Dv], lse
    [B,H,Tq] f32). The lse output is what
    makes partial-attention results combinable — ring attention merges
    per-step outputs with lse-softmax weights (``parallel/ring.py``).

    Block defaults resolve HERE, outside the custom_vjp: its fwd/bwd are
    invoked with the wrapper's original nondiff args, so a None default
    resolved inside the primal body would leak into the grad path."""
    if block_q is None or block_k is None:
        dq, dk = default_blocks(q.shape[-1], q.dtype.itemsize)
        block_q = block_q or dq
        block_k = block_k or dk
    return _flash_with_lse_cv(q, k, v, sm_scale, causal, block_q, block_k, window)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_with_lse_cv(q, k, v, sm_scale, causal, block_q: int, block_k: int, window):
    out, lse = _fwd_lse(q, k, v, sm_scale, causal, block_q, block_k, window)[0]
    return out, lse


def _fwd_lse(q, k, v, sm_scale, causal, block_q, block_k, window=None):
    B, H, Tq, D = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    out, lse = _flash_forward(q, k, v, scale, causal, block_q, block_k, _use_interpret(), window=window)
    lse_trim = lse[:, 0, :Tq].reshape(B, H, Tq)
    return (out, lse_trim), (q, k, v, out, lse)


def _bwd_lse(sm_scale, causal, block_q, block_k, window, residuals, g):
    q, k, v, out, lse = residuals
    g_out, g_lse = g
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    return _flash_backward(
        q, k, v, out, lse, g_out, scale, causal, block_q, block_k, _use_interpret(),
        g_lse=g_lse, window=window,
    )


_flash_with_lse_cv.defvjp(_fwd_lse, _bwd_lse)


def _use_interpret() -> bool:
    return not backend.on_tpu()


def mha(q, k, v, *, causal: bool = True, sm_scale: Optional[float] = None):
    """Plain-XLA reference attention (for tests and small shapes)."""
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    return _reference_attention(q, k, v, scale, causal)
