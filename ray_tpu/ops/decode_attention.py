"""Single-token (decode) attention over a KV cache as a Pallas TPU kernel.

Decode attention is the per-token hot op of serving: one query row per
sequence attends over the whole cache. It is purely HBM-bandwidth-bound —
the FLOPs are trivial; what matters is streaming K/V exactly once at full
bandwidth. The kernel:

- grids over (batch, kv_head, cache blocks) and streams K/V blocks through
  VMEM with online-softmax state in scratch (same revisited-output pattern
  as the training flash kernel in ``ray_tpu.ops.attention``);
- exploits GQA natively: the ``n_rep`` query heads of a KV group ride in
  the sublane dimension of ONE block, so K/V bytes are read once per
  GROUP, not once per query head — an n_rep-fold bandwidth saving, which
  is the whole reason GQA exists;
- masks per-sequence cache validity with an additive bias row
  (``0 / -inf``), so ragged slot positions in the serving engine's shared
  cache need no recompilation.

:func:`paged_decode_attention` is the block-pool variant (PagedAttention,
Kwon et al. 2023): K/V live in a shared pool of fixed-size pages
``[num_blocks, block_size, Hkv, D]`` and each sequence names its pages in
an ``int32[B, max_blocks]`` block table. The table rides Pallas scalar
prefetch (``PrefetchScalarGridSpec``) so the BlockSpec index maps gather
pages straight out of HBM — no materialized per-sequence cache copy.
``use_kernel=False`` is the plain-XLA reference (a ``jnp.take`` gather that
reduces to the dense math) the kernel is checked against.

No backward pass: decode is inference-only. Non-TPU backends run in
interpret mode (tests exercise the same code path on CPU).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import NEG_INF, _LANES, _use_interpret

_MIN_REP = 8  # sublane multiple: pad the n_rep query rows up to one tile


def _decode_kernel(
    q_ref, k_ref, v_ref, bias_ref, o_ref, m_scr, l_scr, acc_scr,
    *, sm_scale: float, block_s: int,
):
    """Grid (B, Hkv, S_blocks); S innermost streams the cache through VMEM.

    q_ref: [rep_p, D] (the group's query heads, sublane-padded);
    k_ref/v_ref: [block_s, D]; bias_ref: [1, block_s] (0 valid / -inf not);
    o_ref: [rep_p, D]; scratch m/l [rep_p, LANES], acc [rep_p, D].
    """
    si = pl.program_id(2)
    num_s = pl.num_programs(2)

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32) * sm_scale
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    s = s + bias_ref[0, :][None, :]  # [rep_p, block_s]

    m_prev = m_scr[:, :1]
    l_prev = l_scr[:, :1]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(jnp.where(m_prev == NEG_INF, NEG_INF, m_prev - m_new))
    p = jnp.exp(s - m_new)
    l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(si == num_s - 1)
    def _final():
        l = l_scr[:, :1]
        # A fully-masked row (lengths[b] == 0) never sees a finite score, so
        # its running max stays at the bias floor: m <= NEG_INF/2 detects it
        # (l is useless here — additive -1e30 bias absorbs in f32 and every
        # masked slot contributes p == 1). Emit zeros, not garbage-V means.
        empty = m_scr[:, :1] <= NEG_INF * 0.5
        out = jnp.where(empty, 0.0, acc_scr[...] / jnp.where(l == 0, 1.0, l))
        o_ref[...] = out.astype(o_ref.dtype)


def decode_attention(
    q: jax.Array,         # [B, H, D] one query row per sequence
    k_cache: jax.Array,   # [B, Hkv, S, D]
    v_cache: jax.Array,   # [B, Hkv, S, D]
    lengths: jax.Array,   # [B] int32: valid cache entries per sequence
    *,
    sm_scale: Optional[float] = None,
    block_s: int = 512,
) -> jax.Array:
    """Returns [B, H, D]. H must be a multiple of Hkv (GQA groups)."""
    import math

    B, H, D = q.shape
    _, Hkv, S, _ = k_cache.shape
    n_rep = H // Hkv
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    rep_p = -(-n_rep // _MIN_REP) * _MIN_REP  # round UP to a sublane multiple

    qg = q.reshape(B, Hkv, n_rep, D)
    if rep_p != n_rep:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rep_p - n_rep), (0, 0)))

    # Prefer shrinking the block to a divisor of S over padding: padding
    # copies the ENTIRE cache (the op's whole byte budget) just to round the
    # last block. A block narrower than S must be a multiple of 128 — it is
    # the sublane dim of the K/V tiles AND the lane dim of the bias row —
    # or Mosaic refuses the BlockSpec; no such divisor means pad.
    bs = min(block_s, S)
    if S % bs:
        bs = next((d for d in range(bs - bs % _LANES, 0, -_LANES) if S % d == 0), bs)
    pad_s = (-S) % bs
    if pad_s:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, 0), (0, pad_s), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, 0), (0, pad_s), (0, 0)))
    Sp = S + pad_s
    bias = jnp.where(jnp.arange(Sp)[None, :] < lengths[:, None], 0.0, NEG_INF).astype(jnp.float32)

    grid = (B, Hkv, Sp // bs)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, sm_scale=scale, block_s=bs),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rep_p, D), q.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, rep_p, D), lambda b, g, s: (b, g, 0, 0)),
            pl.BlockSpec((None, None, bs, D), lambda b, g, s: (b, g, s, 0)),
            pl.BlockSpec((None, None, bs, D), lambda b, g, s: (b, g, s, 0)),
            pl.BlockSpec((None, 1, bs), lambda b, g, s: (b, 0, s)),
        ],
        out_specs=pl.BlockSpec((None, None, rep_p, D), lambda b, g, s: (b, g, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rep_p, _LANES), jnp.float32),
            pltpu.VMEM((rep_p, _LANES), jnp.float32),
            pltpu.VMEM((rep_p, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=_use_interpret(),
    )(qg, k_cache, v_cache, bias[:, None, :])
    return out[:, :, :n_rep, :].reshape(B, H, D)


def _paged_decode_kernel(
    tables_ref, lengths_ref,  # scalar-prefetch: [B, M] int32 page ids, [B] int32
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, sm_scale: float, block_size: int,
):
    """Grid (B, M): M innermost walks the sequence's logical blocks.

    One grid step holds one whole physical page: k_ref/v_ref are
    ``[block_size, Hkv, D]`` (the page axis squeezed by the BlockSpec, whose
    index map reads ``tables_ref`` to pick the page), q_ref/o_ref are
    ``[Hkv, rep_p, D]`` and the online-softmax scratch carries a leading
    ``Hkv`` axis. The KV heads are walked inside the kernel — a block that
    squeezed the second-minor ``Hkv`` axis of the pool is not a shape Mosaic
    can tile. Per head the state machine is :func:`_decode_kernel`'s;
    validity is derived in-kernel from ``lengths_ref`` instead of a bias
    input, and logical blocks wholly past the valid prefix skip their FLOPs.
    """
    bi = pl.program_id(0)
    si = pl.program_id(1)
    num_s = pl.num_programs(1)
    length = lengths_ref[bi]
    n_kv = q_ref.shape[0]

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(si * block_size < length)
    def _accum():
        pos = si * block_size + jax.lax.broadcasted_iota(jnp.int32, (1, block_size), 1)
        bias = jnp.where(pos < length, 0.0, NEG_INF)  # [1, block_size]
        for g in range(n_kv):
            q = q_ref[g].astype(jnp.float32) * sm_scale
            k = k_ref[:, g, :].astype(jnp.float32)
            v = v_ref[:, g, :].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            s = s + bias  # [rep_p, block_size]

            m_prev = m_scr[g, :, :1]
            l_prev = l_scr[g, :, :1]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(jnp.where(m_prev == NEG_INF, NEG_INF, m_prev - m_new))
            p = jnp.exp(s - m_new)
            l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
            acc_scr[g] = acc_scr[g] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            m_scr[g] = jnp.broadcast_to(m_new, m_scr.shape[1:])
            l_scr[g] = jnp.broadcast_to(l_new, l_scr.shape[1:])

    @pl.when(si == num_s - 1)
    def _final():
        l = l_scr[:, :, :1]
        empty = m_scr[:, :, :1] <= NEG_INF * 0.5  # lengths[b] == 0: emit zeros
        out = jnp.where(empty, 0.0, acc_scr[...] / jnp.where(l == 0, 1.0, l))
        o_ref[...] = out.astype(o_ref.dtype)


def _paged_decode_xla(qg, k_pages, v_pages, block_tables, lengths, scale):
    """Plain-XLA reference: gather each sequence's pages into a dense
    [B, Hkv, M*bs, D] view and run the masked grouped einsum — the exact
    math of the dense path; the kernel is compared against it (tier-1 in
    interpret mode, ``chip_smoke.py`` compiled)."""
    g = jnp.take(k_pages, block_tables, axis=0)  # [B, M, bs, Hkv, D]
    B, M, bs, Hkv, D = g.shape
    k = jnp.transpose(g, (0, 3, 1, 2, 4)).reshape(B, Hkv, M * bs, D)
    v = jnp.transpose(
        jnp.take(v_pages, block_tables, axis=0), (0, 3, 1, 2, 4)
    ).reshape(B, Hkv, M * bs, D)
    s = jnp.einsum(
        "bgrk,bgsk->bgrs", qg.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale  # [B, Hkv, n_rep, S]
    vis = jnp.arange(M * bs)[None, :] < lengths[:, None]
    s = jnp.where(vis[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # a fully-masked row softmaxes to uniform garbage; zero it like the kernel
    p = jnp.where((lengths > 0)[:, None, None, None], p, 0.0)
    return jnp.einsum("bgrs,bgsk->bgrk", p, v.astype(jnp.float32))


def paged_decode_attention(
    q: jax.Array,             # [B, H, D] one query row per sequence
    k_pages: jax.Array,       # [num_blocks, block_size, Hkv, D] shared pool
    v_pages: jax.Array,       # [num_blocks, block_size, Hkv, D]
    block_tables: jax.Array,  # [B, M] int32 physical page per logical block
    lengths: jax.Array,       # [B] int32: valid cache entries per sequence
    *,
    sm_scale: Optional[float] = None,
    use_kernel: bool = True,
) -> jax.Array:
    """Decode attention over a paged KV pool; returns [B, H, D].

    Table entries past ``ceil(lengths[b] / block_size)`` may point anywhere
    valid (the engine points them at the reserved garbage page 0) — they are
    masked out, never normalized in. ``use_kernel=False`` is the plain-XLA
    gather reference. There is no auto-select here: callers that choose by
    platform (``models/generation.py``) ask ``ops.backend.on_tpu()`` once
    and pass the answer; off the chip the kernel runs in interpret mode,
    which is how the kernel itself is tested on CPU.
    """
    import math

    B, H, D = q.shape
    _, bs, Hkv, _ = k_pages.shape
    M = block_tables.shape[1]
    n_rep = H // Hkv
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)

    qg = q.reshape(B, Hkv, n_rep, D)
    if not use_kernel:
        out = _paged_decode_xla(qg, k_pages, v_pages, block_tables, lengths, scale)
        return out.astype(q.dtype).reshape(B, H, D)

    rep_p = -(-n_rep // _MIN_REP) * _MIN_REP
    if rep_p != n_rep:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rep_p - n_rep), (0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block_tables, lengths — usable in index maps
        grid=(B, M),
        in_specs=[
            pl.BlockSpec((None, Hkv, rep_p, D), lambda b, s, bt, ln: (b, 0, 0, 0)),
            # the paged gather: logical block s of sequence b streams from
            # physical page bt[b, s] — one DMA per page, no copy
            pl.BlockSpec((None, bs, Hkv, D), lambda b, s, bt, ln: (bt[b, s], 0, 0, 0)),
            pl.BlockSpec((None, bs, Hkv, D), lambda b, s, bt, ln: (bt[b, s], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, Hkv, rep_p, D), lambda b, s, bt, ln: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, rep_p, _LANES), jnp.float32),
            pltpu.VMEM((Hkv, rep_p, _LANES), jnp.float32),
            pltpu.VMEM((Hkv, rep_p, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, sm_scale=scale, block_size=bs),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rep_p, D), q.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=_use_interpret(),
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32), qg, k_pages, v_pages)
    return out[:, :, :n_rep, :].reshape(B, H, D)
