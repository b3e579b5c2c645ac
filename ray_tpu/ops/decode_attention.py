"""Attention over a KV cache for serving, as Pallas TPU kernels: one query a
sequence (decode), dense or paged, and a chunk of queries over a paged pool.

Decode attention is the per-token hot op of serving: one query row per
sequence attends over the whole cache. Its bytes set its floor (K and V are
read once, nothing is reused), but its time is the bytes' only if the work
around each block of keys hides under their copy: a block of scores is a few
rows tall whatever the kernel does, so the products' fixed cost and the
dependent chain max -> exp -> sum -> product are paid a block, not a FLOP. The
dense kernel:

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
Kwon et al. 2023): K/V live in a shared, layer-stacked pool of fixed-size
pages ``[L, num_blocks, block_size, Hkv*D]`` (a page row holds all KV heads
side by side on the lanes, so the pool's natural device layout is unpadded
row-major pages) and each sequence names its pages in an
``int32[B, max_blocks]`` block table. The grid is one step a sequence; the
table, the lengths and the layer index ride Pallas scalar prefetch
(``PrefetchScalarGridSpec``), the pools stay in HBM, and the body copies the
pages a sequence can see, a group of 128 tokens at a time, straight out of
the stacked pool — no per-layer slice, no materialized per-sequence cache
copy, and no work for a page or a slot that holds nothing. The body's unit
of work for a group of keys is a run of whole lane tiles of the page row,
the neighbouring KV heads whose queries fill one sublane tile of rows
(:func:`_heads_a_unit`): a row a head and query, each in its own head's
lanes with exact zeros in the others', so one ``QK^T``, one softmax step and
one ``P.V`` serve all of them. The operands go to the MXU in bf16 as the pool
stores them, ``P`` rounded to bf16 in the open; scores and softmax state are
float32. Kernel alone on a v5e (PERF.md section 5, PR 45) the page copies
cost 1.1x their bytes' time at 128-240 KiB a page and a layer and the whole
kernel 1.2-1.4x where eight or six heads share a unit; where a unit is one
GQA group (four units a group of keys, 32 KiB a page) the copies cost 1.6x
and the kernel 3.5x, the softmax chain of so few units standing in the open.
``use_kernel=False`` is the plain-XLA reference (a ``jnp.take`` gather that
reduces to the dense math) the kernel is checked against.

:func:`paged_prefill_attention` is the same page walk (:func:`_walk_pages`)
for a chunk of ``T > 1`` consecutive queries of a sequence: a grid step a
tile of 128 queries, the keys walked from the first position the tile's
first query still sees to its last query's own, causal and windowed by
position, so a prefill chunk reads the pages it can see and no
``[T, capacity]`` score tensor exists.

:func:`latent_paged_decode` and :func:`latent_paged_prefill` walk the **one**
pool of a latent attention layer (``[L, num_blocks, block_size, lanes]``: a
token's normalised latent and the key part every head shares, side by side,
zero lanes up to whole tiles): a group of cached rows is the keys of every
head (all its lanes, against queries in the absorbed form) and, in its first
``rank`` lanes, the values, so each row is copied once and the heads are the
rows of one ``QK^T`` and one ``P.V``. The prefill kernel is on
:func:`_walk_pages`; the decode kernel, whose 20-KiB pages under one block of
32 query rows cost as much to ask for as to multiply, has a walk of its own
that starts a group's copies from inside the products of the group before
(:func:`_walk_latent_pages`).

No backward pass: decode is inference-only. Non-TPU backends run in
interpret mode (tests exercise the same code path on CPU).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import NEG_INF, _LANES, _use_interpret


def block_last(q_pos, block: int):
    """The last key position the query at ``q_pos`` sees: its own under a
    causal mask (``block`` <= 1: ``q_pos`` as it is), the last of its block
    of ``block`` positions under a block-causal one
    (``TransformerConfig.block_length``)."""
    return q_pos if block <= 1 else (q_pos // block + 1) * block - 1


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
    window=None,
) -> jax.Array:
    """Returns [B, H, D]. H must be a multiple of Hkv (GQA groups).
    ``window`` (an int or a traced scalar; 0 or None: none): only the last
    ``window`` valid entries are visible (:func:`window_start`)."""
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
    pos = jnp.arange(Sp)[None, :]
    visible = pos < lengths[:, None]
    if window is not None:
        visible = visible & (pos >= window_start(lengths, window)[:, None])
    bias = jnp.where(visible, 0.0, NEG_INF).astype(jnp.float32)

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


def window_start(lengths, window):
    """First cache position a decode query at ``lengths - 1`` still sees
    under a sliding window: key ``j`` is visible iff ``j > i - window``, so
    ``lengths - window``, floored at 0; 0 where ``window`` is 0 (none)."""
    return jnp.where(window > 0, jnp.maximum(lengths - window, 0), 0)


def _walk_pages(tables_ref, row, layer, k_hbm, v_hbm, k_buf, v_buf, sems, block_size, first, held, on_group):
    """The page walk of both paged kernels: positions ``[first, held)`` of the
    sequence in table row ``row``, a group of ``span`` tokens at a time.

    k_hbm/v_hbm are the whole stacked pools ``[L, N, block_size, Hkv*D]``
    where they lie in HBM; ``k_buf``/``v_buf`` are ``[2, span, Hkv*D]`` VMEM
    (``span``: a group of ``G`` pages, one whole 128-token lane tile of
    scores where the page size divides 128). The loop runs, with a traced
    trip count, from the group that holds ``first`` to the one that holds
    ``held - 1``. Each page of a group comes by its own copy out of
    ``pool[layer, table[row, j]]`` into its rows of the group's buffer; the
    next group's copies start before ``on_group(g, slot)`` does this
    group's products on ``k_buf[slot]``/``v_buf[slot]`` (two buffers). Pages
    of a group past the last visible one, or before the first, are not
    fetched: their rows keep what an earlier group left there, and the
    caller masks them by position (scores by ``where``, so stale K cannot
    reach a sum, and a probability of exactly 0 meets stale V). ``v_hbm`` None
    (a latent pool: one row is key and value): only ``k_hbm`` is walked."""
    span = k_buf.shape[1]  # tokens a group
    group = span // block_size
    page_lo, page_hi = first // block_size, pl.cdiv(held, block_size)
    group_lo, group_hi = first // span, pl.cdiv(held, span)

    def page_copies(g, slot, act):
        """``act`` on the K and V copy of each visible page of group ``g``."""

        def one_page(page, carry):
            phys = tables_ref[row, page]
            rows = pl.ds(pl.multiple_of((page - g * group) * block_size, block_size), block_size)
            act(pltpu.make_async_copy(k_hbm.at[layer, phys], k_buf.at[slot, rows], sems.at[0, slot]))
            if v_hbm is not None:
                act(pltpu.make_async_copy(v_hbm.at[layer, phys], v_buf.at[slot, rows], sems.at[1, slot]))
            return carry

        jax.lax.fori_loop(jnp.maximum(page_lo, g * group), jnp.minimum(page_hi, (g + 1) * group), one_page, None)

    page_copies(group_lo, group_lo % 2, lambda copy: copy.start())

    def one_group(g, carry):
        slot = g % 2

        @pl.when(g + 1 < group_hi)
        def _():
            page_copies(g + 1, 1 - slot, lambda copy: copy.start())

        page_copies(g, slot, lambda copy: copy.wait())
        on_group(g, slot)
        return carry

    jax.lax.fori_loop(group_lo, group_hi, one_group, None)


def _paged_decode_kernel(
    tables_ref, lengths_ref, layer_ref,  # scalar-prefetch: [B, M] page ids, [B], [1]
    *refs, sm_scale: float, block_size: int, share: int, n_rep: int, windowed: bool,
):
    """Grid (B,): one grid step a sequence; its visible pages are walked by a
    loop inside the body (:func:`_walk_pages`), so a slot that holds nothing
    costs one grid step and a page no query may see costs nothing at all:
    the walk runs from the group that holds the window's first position (0
    without one) to the one that holds position ``length - 1``. The buffers
    are zeroed at the first grid step, so they only ever hold zeros or live
    pages of the pool.

    The unit of work for a group of keys is ``W = share*D`` lanes of the page
    row, whole lane tiles that hold ``share`` neighbouring KV heads
    (:func:`_heads_a_unit`: as many as fill one sublane tile with their
    queries). q_ref is ``[units, rows_p, W]``: row ``j*n_rep + r`` of a unit
    is its head ``j``'s query ``r`` in that head's own ``D`` lanes and exact
    zeros in its neighbours', whose K lanes so drop out of the row's scores
    as exact zeros. One ``QK^T`` (contracting all ``W`` lanes), one softmax
    step and one ``P.V`` then serve every head of the unit: the rows are
    independent through the softmax, and a row's accumulator holds its own
    head's output in that head's lanes (the other lanes are a neighbour's V
    under this head's weights, and are dropped when o_ref ``[Hkv, n_rep, D]``
    is written). K, V and q go to the MXU as they are stored
    (:func:`_dot_qk`), the scale multiplies the float32 scores, and ``P`` is
    rounded to the pool's bf16 for the one pass of ``P.V``
    (:func:`_dot_pv`); scores, max, sum and accumulator are float32.
    ``windowed``: a fourth scalar-prefetch ref ``[1]`` carries this call's
    sliding window (0: none).
    """
    if windowed:
        window_ref, *refs = refs
    q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, m_scr, l_scr, acc_scr = refs
    bi = pl.program_id(0)
    length = lengths_ref[bi]
    units, _, w = q_ref.shape
    span = k_buf.shape[1]
    first = window_start(length, window_ref[0]) if windowed else 0
    # a length past the table's capacity (a finished row's overshoot) walks
    # the table and no further
    held = jnp.minimum(length, tables_ref.shape[1] * block_size)

    @pl.when(bi == 0)
    def _clean_buffers():
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    one_pass = functools.partial(_dot_pv, terms=1)

    def one_group(g, slot):
        pos = g * span + jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)
        visible = jnp.logical_and(pos >= first, pos < held)  # [1, span]
        for u in range(units):
            lanes = slice(u * w, (u + 1) * w)
            s = _dot_qk(q_ref[u], k_buf[slot, :, lanes]) * sm_scale
            _softmax_step(jnp.where(visible, s, NEG_INF), v_buf[slot, :, lanes], u, m_scr, l_scr, acc_scr, dot_pv=one_pass)

    _walk_pages(tables_ref, bi, layer_ref[0], k_hbm, v_hbm, k_buf, v_buf, sems, block_size, first, held, one_group)
    d = w // share
    for u in range(units):
        out = _finished(m_scr, l_scr, acc_scr, u)
        for j in range(share):  # head u*share + j: its rows, its lanes
            o_ref[u * share + j] = out[j * n_rep:(j + 1) * n_rep, j * d:(j + 1) * d].astype(o_ref.dtype)


def _dot_qk(q, k):
    """``q [rows, W] . k [span, W]^T`` in float32: bf16 operands go to the MXU
    as they are stored (the product of two bf16 values is exact in float32,
    so one pass accumulated in float32 is the float32 product of the dense
    lines); anything else as float32 at full precision."""
    dims = (((1,), (1,)), ((), ()))
    if q.dtype == k.dtype == jnp.bfloat16:
        return jax.lax.dot_general(q, k, dims, preferred_element_type=jnp.float32)
    return jax.lax.dot_general(
        q.astype(jnp.float32), k.astype(jnp.float32), dims, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)


def _dot_pv(p, v, terms: int = 3):
    """``p [rows, span]`` float32 times ``v [span, W]``. ``terms`` = 3,
    nothing rounded: a float32 is three bf16 terms exactly (24 bits of
    mantissa in three eights) and each term's products with a bf16 ``v`` are
    exact in float32, so three passes of the MXU, accumulated in float32, are
    the float32 product. ``terms`` = 1, the decode kernel's: one pass on ``p``
    rounded to bf16 in the open, which is what the MXU makes of a float32
    operand left to itself (1.4x the three-term error against the float32
    lines on the chip; the full-precision product of two float32 operands
    takes six passes). A pool that is not bf16 takes the six."""
    dims = (((1,), (0,)), ((), ()))
    if v.dtype != jnp.bfloat16:
        return jax.lax.dot_general(
            p, v.astype(jnp.float32), dims, preferred_element_type=jnp.float32, precision=jax.lax.Precision.HIGHEST)
    out = None
    for _ in range(terms):
        term = p.astype(jnp.bfloat16)
        p = p - term.astype(jnp.float32)
        part = jax.lax.dot_general(term, v, dims, preferred_element_type=jnp.float32)
        out = part if out is None else out + part
    return out


def _softmax_step(s, v, h, m_scr, l_scr, acc_scr, dot_pv=_dot_pv):
    """One group's masked float32 scores ``s [rows, span]`` and values
    ``v [span, W]`` into unit ``h``'s online-softmax state (``m``/``l``
    lane-broadcast ``[units, rows, LANES]``, ``acc [units, rows, W]``; a unit
    is a KV head of the prefill kernel, a run of heads of the decode kernel:
    :func:`_heads_a_unit`). ``dot_pv``: the ``P.V`` product."""
    m_prev = m_scr[h, :, :1]
    l_prev = l_scr[h, :, :1]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(jnp.where(m_prev == NEG_INF, NEG_INF, m_prev - m_new))
    p = jnp.exp(s - m_new)
    l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
    acc_scr[h] = acc_scr[h] * alpha + dot_pv(p, v)
    m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
    l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])


def _finished(m_scr, l_scr, acc_scr, unit=slice(None)):
    """The finished state of ``unit`` (all of them: ``[units, rows, W]``) as
    float32 outputs; a row that saw no key (its running max never left the
    floor) gives zeros, not garbage-V means."""
    l = l_scr[unit, :, :1]
    empty = m_scr[unit, :, :1] <= NEG_INF * 0.5
    return jnp.where(empty, 0.0, acc_scr[unit] / jnp.where(l == 0, 1.0, l))


def _gathered(pool, layer, block_tables, Hkv, D):
    """Each sequence's pages of ``layer`` as a dense [B, Hkv, M*bs, D] view."""
    B, M = block_tables.shape
    g = pool[layer, block_tables]  # [B, M, bs, Hkv*D]
    return jnp.transpose(g.reshape(B, -1, Hkv, D), (0, 2, 1, 3))


def _paged_decode_xla(qg, k_pool, v_pool, block_tables, lengths, layer, scale, window=None):
    """Plain-XLA reference: gather each sequence's pages of ``layer`` into a
    dense [B, Hkv, M*bs, D] view and run the masked grouped einsum — the
    exact math of the dense path; the kernel is compared against it (tier-1
    in interpret mode, ``chip_smoke.py`` compiled)."""
    B, Hkv, _, D = qg.shape
    M, bs = block_tables.shape[1], k_pool.shape[2]
    k, v = (_gathered(pool, layer, block_tables, Hkv, D) for pool in (k_pool, v_pool))
    s = jnp.einsum(
        "bgrk,bgsk->bgrs", qg.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale  # [B, Hkv, n_rep, S]
    pos = jnp.arange(M * bs)[None, :]
    vis = pos < lengths[:, None]
    if window is not None:
        vis = vis & (pos >= window_start(lengths, window)[:, None])
    s = jnp.where(vis[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # a fully-masked row softmaxes to uniform garbage; zero it like the kernel
    p = jnp.where((lengths > 0)[:, None, None, None], p, 0.0)
    return jnp.einsum("bgrs,bgsk->bgrk", p, v.astype(jnp.float32))


def _heads_a_lane_tile(Hkv: int, D: int) -> int:
    """Heads narrower than a lane tile share one: ``pack`` neighbours to a
    tile of ``pack * D`` lanes, so a paged kernel never slices inside a tile
    (a 64-lane slice at a 64-lane offset cost twice the aligned load per page
    on the v5e)."""
    import math

    return math.gcd(Hkv, _LANES // D) if _LANES % D == 0 else 1


def _heads_a_unit(Hkv: int, D: int, n_rep: int) -> int:
    """Heads that share a unit of the decode kernel: whole lane tiles of
    neighbours, as many as divide ``Hkv`` and keep their ``n_rep`` queries
    each within one sublane tile of rows (eight 64-wide MHA heads, six of
    thirty 128-wide ones, one GQA group of eight queries); a lane tile's
    heads at least. The cost of a unit hardly follows its lanes (the softmax
    chain on one block of rows and the products' fixed cost set it), so fewer,
    wider units are what the kernel's time follows (PERF.md section 5)."""
    pack = _heads_a_lane_tile(Hkv, D)
    if pack * D % _LANES:
        return pack
    return max((s for s in range(pack, Hkv + 1, pack) if Hkv % s == 0 and s * n_rep <= _MIN_REP), default=pack)


def _into_own_lanes(qg: jax.Array, pack: int) -> jax.Array:
    """``qg [B, Hkv, rows, D]`` -> ``[B, Hkv, rows, pack*D]``: head ``g``'s
    query in its own ``D`` lanes of its tile, exact zeros in its neighbours'."""
    if pack == 1:
        return qg
    B, Hkv, rows, D = qg.shape
    own_lanes = jax.nn.one_hot(jnp.arange(Hkv) % pack, pack, dtype=qg.dtype)  # [Hkv, pack]
    return (qg[:, :, :, None, :] * own_lanes[None, :, None, :, None]).reshape(B, Hkv, rows, pack * D)


def _own_lanes(out: jax.Array, pack: int) -> jax.Array:
    """``out [B, Hkv, rows, pack*D]`` -> ``[B, Hkv, rows, D]``: each head's
    own lanes of its tile."""
    if pack == 1:
        return out
    B, Hkv, rows, W = out.shape
    own = jnp.arange(Hkv) % pack
    return jnp.take_along_axis(
        out.reshape(B, Hkv, rows, pack, W // pack), own[None, :, None, None, None], axis=3
    ).reshape(B, Hkv, rows, W // pack)


def _heads_share_rows(qg: jax.Array, share: int) -> jax.Array:
    """``qg [B, Hkv, n_rep, D]`` -> ``[B, Hkv/share, rows_p, share*D]``, the
    decode kernel's queries: the ``share`` heads of a unit share one block of
    rows, row ``j*n_rep + r`` head ``j``'s query ``r`` in its own ``D`` lanes
    and exact zeros in its neighbours'; the ``share*n_rep`` rows are padded
    with zero rows up to a sublane multiple."""
    B, Hkv, n_rep, D = qg.shape
    q = qg.reshape(B, Hkv // share, share, n_rep, 1, D)
    if share > 1:
        q = q * jnp.eye(share, dtype=qg.dtype)[:, None, :, None]  # [.., j, r, j', D]
    q = q.reshape(B, Hkv // share, share * n_rep, share * D)
    return jnp.pad(q, ((0, 0), (0, 0), (0, (-share * n_rep) % _MIN_REP), (0, 0)))


def _paged_call(kernel, name, scalars, qg, k_pool, v_pool, *, grid, rows, q_index, out_block=None, **compiler_params):
    """The ``pallas_call`` of both paged kernels: ``scalars`` ride scalar
    prefetch, ``qg [B, units, R, W]`` comes in blocks of ``rows`` at
    ``q_index`` and so does the output, of ``qg``'s shape or, given
    ``out_block``, ``[B, *out_block]`` a whole block a grid step; the pools
    stay in HBM (the body copies the pages it needs), and the scratch is the
    two-slot group buffers of K and V (a group of pages is one lane tile of
    scores: 128 tokens where the page size divides it), their copy semaphores
    and the online-softmax state of every unit."""
    B, units, _, W = qg.shape
    bs, row = k_pool.shape[2:]
    span = max(1, _LANES // bs) * bs
    block = pl.BlockSpec((None, units, rows, W), q_index)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=grid,
        in_specs=[block, pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=block if out_block is None else pl.BlockSpec((None, *out_block), q_index),
        scratch_shapes=[
            pltpu.VMEM((2, span, row), k_pool.dtype),
            pltpu.VMEM((2, span, row), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),  # [K | V, buffer]
            pltpu.VMEM((units, rows, _LANES), jnp.float32),
            pltpu.VMEM((units, rows, _LANES), jnp.float32),
            pltpu.VMEM((units, rows, W), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(qg.shape if out_block is None else (B, *out_block), qg.dtype),
        grid_spec=grid_spec,
        # in order: the group buffers are cleaned at the first grid step
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",) * len(grid), **compiler_params),
        interpret=_use_interpret(),
        name=name,
    )(*scalars, qg, k_pool, v_pool)


def paged_decode_attention(
    q: jax.Array,             # [B, H, D] one query row per sequence
    k_pool: jax.Array,        # [L, num_blocks, block_size, Hkv*D] shared pool
    v_pool: jax.Array,        # [L, num_blocks, block_size, Hkv*D]
    block_tables: jax.Array,  # [B, M] int32 physical page per logical block
    lengths: jax.Array,       # [B] int32: valid cache entries per sequence
    layer: jax.Array,         # int32 scalar: which layer of the pool to read
    *,
    sm_scale: Optional[float] = None,
    use_kernel: bool = True,
    window=None,
) -> jax.Array:
    """Decode attention over one layer of a paged KV pool; returns [B, H, D].

    ``window`` (an int or a traced scalar, e.g. this layer's entry riding the
    layer scan; 0: none; None: the kernel is built without it): only the
    last ``window`` valid entries of each sequence are visible.

    The pool is read where it lies: ``layer`` only steers the page DMAs, so
    a caller looping over layers hands in the same stacked buffer each time.
    ``Hkv`` is what the pool's row width and ``D`` (from ``q``) imply.
    Table entries past ``ceil(lengths[b] / block_size)``, and behind the
    window, may point anywhere valid (the engine points the former at the
    reserved garbage page 0): a page no query may see is never fetched.
    Page 0 is the pool's garbage page (``init_paged_cache``; the allocator
    never hands it out): a row whose table starts there holds no sequence —
    the engine's idle slots decode through all-zero tables at whatever
    length their last tenant left — and reads as ``lengths[b] == 0``: a
    zero row and no page visited. ``use_kernel=False`` is the plain-XLA
    gather reference. There is no auto-select here: callers that choose by
    platform (``models/generation.py``) ask ``ops.backend.on_tpu()`` once
    and pass the answer; off the chip the kernel runs in interpret mode,
    which is how the kernel itself is tested on CPU.
    """
    import math

    B, H, D = q.shape
    _, _, bs, row = k_pool.shape
    Hkv = row // D
    n_rep = H // Hkv
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    lengths = jnp.where(block_tables[:, 0] > 0, lengths, 0)  # an idle row visits nothing

    qg = q.reshape(B, Hkv, n_rep, D)
    if not use_kernel:
        out = _paged_decode_xla(qg, k_pool, v_pool, block_tables, lengths, layer, scale, window)
        return out.astype(q.dtype).reshape(B, H, D)

    share = _heads_a_unit(Hkv, D, n_rep)
    qt = _heads_share_rows(qg, share)
    windowed = window is not None
    scalars = [block_tables.astype(jnp.int32), lengths.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1)]
    if windowed:
        scalars.append(jnp.asarray(window, jnp.int32).reshape(1))

    out = _paged_call(
        functools.partial(
            _paged_decode_kernel, sm_scale=scale, block_size=bs, share=share, n_rep=n_rep, windowed=windowed),
        "paged_decode", scalars, qt, k_pool, v_pool, grid=(B,), rows=qt.shape[2], q_index=lambda b, *_: (b, 0, 0, 0),
        out_block=(Hkv, n_rep, D))
    return out.reshape(B, H, D)


_Q_TILE = 128  # queries a grid step of the prefill kernel: one group of keys on the diagonal
# q and o blocks twice, the state and the group buffers: ~12 MiB at both served
# shapes (32 heads x 128 rows, 4 x 1024), past the compiler's default for a kernel
_PREFILL_VMEM_BYTES = 48 * 1024 * 1024


def _paged_prefill_kernel(
    tables_ref, starts_ref, lengths_ref, layer_ref,  # scalar-prefetch: [B, M], [B], [B], [1]
    *refs, sm_scale: float, block_size: int, pack: int, n_rep: int, windowed: bool, block: int = 1,
):
    """Grid (B, T / tq): one grid step a tile of ``tq`` consecutive queries of
    a chunk, all heads. The tile's queries stand at ``start + qi*tq ..``; its
    keys are walked where they lie in the pool (:func:`_walk_pages`) from the
    group that holds the first position its first query still sees (0
    without a window) to the one that holds its last query's own position,
    or the chunk's last real token's if that comes first: nothing past
    ``start + length - 1`` is fetched, and a tile wholly past it walks
    nothing and emits zeros. Visibility is positional and per query: key
    ``j`` is seen by the query at ``p`` iff ``j <= p`` and, with a window,
    ``j > p - window``. ``block`` > 1 (a block-causal config): iff ``j`` is at
    or before the last position of ``p``'s block of ``block`` positions, and
    the walk runs to the end of the tile's last query's block.

    q_ref/o_ref are ``[Hkv, tq*n_rep, W]``: row ``t*n_rep + r`` is query
    ``t``'s head ``r`` of the KV group, so the ``n_rep`` heads of a group
    share one read of its K/V and one mask serves every group; lanes as in
    :func:`_paged_decode_kernel`. Scores, softmax state and ``P.V`` are
    float32 with nothing rounded on the way (:func:`_dot_qk`, :func:`_dot_pv`).
    """
    if windowed:
        window_ref, *refs = refs
    q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, m_scr, l_scr, acc_scr = refs
    bi, qi = pl.program_id(0), pl.program_id(1)
    n_kv, rows, w = q_ref.shape
    span, tq = k_buf.shape[1], rows // n_rep
    p0 = starts_ref[bi] + qi * tq  # the tile's first query's position
    held = jnp.minimum(starts_ref[bi] + lengths_ref[bi], tables_ref.shape[1] * block_size)
    last = jnp.where(p0 < held, jnp.minimum(held, block_last(p0 + tq - 1, block) + 1 if block > 1 else p0 + tq), 0)
    first = window_start(p0 + 1, window_ref[0]) if windowed else 0

    @pl.when(jnp.logical_and(bi == 0, qi == 0))
    def _clean_buffers():
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    q_pos = p0 + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // n_rep
    q_first = window_start(q_pos + 1, window_ref[0]) if windowed else None  # [rows, 1]

    def one_group(g, slot):
        pos = g * span + jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)
        visible = jnp.logical_and(pos <= block_last(q_pos, block), pos < last)  # [rows, span]
        if windowed:
            visible = jnp.logical_and(visible, pos >= q_first)
        for h in range(n_kv):
            if h % pack == 0:  # a new lane tile, shared by its heads
                lanes = slice(h // pack * w, (h // pack + 1) * w)
                k = k_buf[slot, :, lanes]
                v = v_buf[slot, :, lanes]
            s = _dot_qk(q_ref[h], k) * sm_scale
            _softmax_step(jnp.where(visible, s, NEG_INF), v, h, m_scr, l_scr, acc_scr, dot_pv=_dot_pv)

    _walk_pages(tables_ref, bi, layer_ref[0], k_hbm, v_hbm, k_buf, v_buf, sems, block_size, first, last, one_group)
    o_ref[...] = _finished(m_scr, l_scr, acc_scr).astype(o_ref.dtype)


def _paged_prefill_xla(qg, k_pool, v_pool, block_tables, starts, lengths, layer, scale, n_rep, window=None, block=1):
    """Plain-XLA reference: the dense lines of
    ``models/generation.py::paged_forward_counted`` on a gathered
    [B, Hkv, M*bs, D] view; the kernel is compared against it."""
    B, Hkv, rows, D = qg.shape
    M, bs = block_tables.shape[1], k_pool.shape[2]
    k, v = (_gathered(pool, layer, block_tables, Hkv, D) for pool in (k_pool, v_pool))
    s = jnp.einsum("bgtk,bgsk->bgts", qg.astype(jnp.float32), k.astype(jnp.float32)) * scale
    pos = jnp.arange(M * bs)[None, None, :]
    q_pos = (starts[:, None] + jnp.arange(rows)[None, :] // n_rep)[:, :, None]
    vis = (pos <= block_last(q_pos, block)) & (pos < (starts + lengths)[:, None, None])
    if window is not None:
        vis = vis & (pos >= window_start(q_pos + 1, window))
    s = jnp.where(vis[:, None], s, NEG_INF)
    p = jnp.where(vis.any(-1)[:, None, :, None], jax.nn.softmax(s, axis=-1), 0.0)
    return jnp.einsum("bgts,bgsk->bgtk", p, v.astype(jnp.float32))


def paged_prefill_attention(
    q: jax.Array,             # [B, T, H, D] a chunk of consecutive queries a sequence
    k_pool: jax.Array,        # [L, num_blocks, block_size, Hkv*D] shared pool
    v_pool: jax.Array,        # [L, num_blocks, block_size, Hkv*D]
    block_tables: jax.Array,  # [B, M] int32 physical page per logical block
    starts: jax.Array,        # [B] int32: the position of each chunk's first query
    lengths: jax.Array,       # [B] int32: real tokens of each chunk (the rest is padding)
    layer: jax.Array,         # int32 scalar: which layer of the pool to read
    *,
    sm_scale: Optional[float] = None,
    use_kernel: bool = True,
    window=None,
    block: int = 1,
    q_tile: Optional[int] = None,
) -> jax.Array:
    """Chunked-prefill attention over one layer of a paged KV pool; returns
    [B, T, H, D]. The chunk's own K/V are in the pool already.

    Query ``t`` of row ``b`` stands at position ``starts[b] + t`` and sees
    the keys at or before it (within ``window`` of it: an int or a traced
    scalar; 0: none; None: the kernel is built without it). ``block`` > 1:
    it also sees the keys after it in its own block of ``block`` positions
    (:func:`block_last`), as far as the chunk's real tokens reach; a decode
    step by diffusion over blocks is this call with ``T = block``, and its
    query tile is then the fewest queries whose heads fill whole sublane
    tiles (``q_tile`` overrides it, for the timing that chose it). The pool is read
    where it lies, a group of 128 tokens at a time, for the pages some query
    of the chunk may see and for no other: table entries past the page of
    ``starts + lengths - 1``, and behind the window of the chunk's first
    query, may point anywhere and are never fetched; no dense view is
    gathered and no ``[T, capacity]`` score tensor exists. Rows past
    ``lengths`` are padding (their K/V went to the garbage page): they see no
    key past the last real one and their outputs mean nothing. The queries
    are tiled by 128 over the grid, so a tile re-reads the K/V before it:
    with ``c`` visible tokens a chunk of ``T`` reads about
    ``(T/128) * (c - T/2)`` tokens of K and V a layer.
    ``use_kernel=False`` is the plain-XLA gather reference; the platform
    select is the caller's, as for :func:`paged_decode_attention`.
    """
    import math

    B, T, H, D = q.shape
    _, _, bs, row = k_pool.shape
    Hkv = row // D
    n_rep = H // Hkv
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    starts, lengths = starts.astype(jnp.int32), jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))

    def grouped(a):  # [B, T', H, D] -> [B, Hkv, T'*n_rep, D], a query's n_rep heads on neighbouring rows
        return jnp.transpose(a.reshape(B, -1, Hkv, n_rep, D), (0, 2, 1, 3, 4)).reshape(B, Hkv, -1, D)

    def ungrouped(a):
        return jnp.transpose(a.reshape(B, Hkv, -1, n_rep, D), (0, 2, 1, 3, 4)).reshape(B, -1, H, D)

    if not use_kernel:
        out = _paged_prefill_xla(
            grouped(q), k_pool, v_pool, block_tables, starts, lengths, layer, scale, n_rep, window, block)
        return ungrouped(out.astype(q.dtype))

    # whole sublane tiles of a 16-bit query: 16 queries, or under a block mask
    # (a decode step of ``block`` positions) the fewest whose n_rep heads a
    # KV group make a multiple of 16 rows
    unit = 16 if block <= 1 else 16 // math.gcd(16, n_rep)
    tq = q_tile or min(_Q_TILE, -(-T // unit) * unit)
    pad_t = (-T) % tq
    if pad_t:
        q = jnp.pad(q, ((0, 0), (0, pad_t), (0, 0), (0, 0)))
    pack = _heads_a_lane_tile(Hkv, D)
    qg = _into_own_lanes(grouped(q), pack)
    windowed = window is not None
    scalars = [block_tables.astype(jnp.int32), starts, lengths, jnp.asarray(layer, jnp.int32).reshape(1)]
    if windowed:
        scalars.append(jnp.asarray(window, jnp.int32).reshape(1))

    out = _paged_call(
        functools.partial(
            _paged_prefill_kernel, sm_scale=scale, block_size=bs, pack=pack, n_rep=n_rep, windowed=windowed,
            block=block),
        "paged_prefill", scalars, qg, k_pool, v_pool, grid=(B, (T + pad_t) // tq), rows=tq * n_rep,
        q_index=lambda b, i, *_: (b, 0, i, 0), vmem_limit_bytes=_PREFILL_VMEM_BYTES)
    return ungrouped(_own_lanes(out, pack))[:, :T]


# ---------------------------------------------------------------------------
# latent attention: one pool, a row is key and value
# ---------------------------------------------------------------------------
# cached rows a group of the decode walk: 64 pages of 16, two slots of 1.25 MiB at 640 lanes. The kernel alone on
# the chip, 64 rows of 17k cached tokens, a layer (``scripts/kernel_bench.py --latent-decode``; PR 55): 3.63 ms at
# 256, 2.65 at 512, 2.11 at 1024, 2.09 at 2048 (as it stood on the shared walk: 5.15 / 4.09 / 3.69 / 3.52; the
# rows' bytes at the chip's bandwidth: 1.70 ms; their copies alone 1.90; the arithmetic alone 1.10): what a group
# costs besides its bytes (the softmax step's rescale of a [32, 512] accumulator, the copies that wait for the first
# piece) is paid half as often at each doubling, and 2048 ties with 1024
_LATENT_DECODE_SPAN = 1024
_LATENT_PREFILL_ROWS = 2048  # query rows (queries x heads) a grid step of the prefill kernel
# the decode walk's copies of the group after this one: the pages started in front of the wait for this one (32 of
# 20 KiB keep the copy engines fed across it: the copies alone read 2.05 ms at 16, 1.93 at 24, 1.90 at 32 and at 64),
# then the pages a straight run between two pieces of this one's work, and the cached rows a piece of its ``QK^T``
# takes (2.11 ms at 32 / 8 / 256; 2.19 at runs of 16, 2.24 at 32; 2.13 at tiles of 512 and 2.28 with all 64 in front)
_LATENT_COPIES_AHEAD = 32
_LATENT_COPY_RUN = 8
_LATENT_KEY_TILE = 256


def _walk_latent_pages(tables_ref, row, layer, pool_hbm, buf, sems, block_size, held, work):
    """The page walk of the latent decode kernel, and of no other: positions
    ``[0, held)`` of the sequence in table row ``row``, a group of ``span``
    cached rows at a time, into the two slots of ``buf [2, span, lanes]`` as
    :func:`_walk_pages` fills them, each visible page by its own copy and no
    other page fetched. What differs is where the copies stand. A page of one
    latent pool is 20 KiB under one product of 32 query rows, so a group's 64
    descriptors and 64 waits, issued in scalar loops in front of its products,
    cost as much as the products do; here the copies of group ``g + 1`` are
    started from inside the work of group ``g``, and a group is waited for at
    once.

    ``work(g, slot)`` is a generator: the group's products and softmax step on
    ``buf[slot]``, yielding wherever copies may be started. While the group
    after ``g`` is whole (all its pages visible), its copies go out in straight
    runs, ``_LATENT_COPIES_AHEAD`` pages in front of the wait for ``g`` and
    ``_LATENT_COPY_RUN`` at each yield, in one block of code with the
    products, so that descriptors, MXU passes and the softmax chain share
    instruction bundles; and ``g``, whole too, is waited for by ONE wait for
    the slot's bytes (its copies signal one semaphore, which counts bytes). The last
    whole group and a partial one behind it (or a short sequence's only one)
    take the plain order: the next group's visible pages started and this
    one's waited for a page at a time, then the work. Rows of a slot that no
    copy of this group wrote keep what an earlier group left there; the work
    masks them by position."""
    span = buf.shape[1]
    group = span // block_size
    page_hi, group_hi, whole = pl.cdiv(held, block_size), pl.cdiv(held, span), held // span

    def copy(page, at, slot):
        """Page ``page`` of the sequence into rows ``[at, at + block_size)`` of ``buf[slot]``."""
        return pltpu.make_async_copy(
            pool_hbm.at[layer, tables_ref[row, page]], buf.at[slot, pl.ds(at, block_size)], sems.at[0, slot])

    def start_run(g, slot, pages):
        for j in pages:
            copy(g * group + j, j * block_size, slot).start()

    def visible_pages(g, slot, act):
        def one_page(page, carry):
            act(copy(page, pl.multiple_of((page - g * group) * block_size, block_size), slot))
            return carry

        jax.lax.fori_loop(g * group, jnp.minimum(page_hi, (g + 1) * group), one_page, None)

    def wait_whole(slot):  # every copy into the slot, by the bytes the slot holds
        pltpu.make_async_copy(buf.at[slot], buf.at[slot], sems.at[0, slot]).wait()

    ahead_of_wait = min(_LATENT_COPIES_AHEAD, group)
    runs = [range(0, ahead_of_wait)] + [
        range(j, min(j + _LATENT_COPY_RUN, group)) for j in range(ahead_of_wait, group, _LATENT_COPY_RUN)]

    def beside(g, carry):  # g + 1 is whole, and so is g
        slot = g % 2
        start_run(g + 1, 1 - slot, runs[0])
        wait_whole(slot)
        ahead = iter(runs[1:])
        for _ in work(g, slot):
            start_run(g + 1, 1 - slot, next(ahead, ()))
        for run in ahead:
            start_run(g + 1, 1 - slot, run)
        return carry

    def apart(g, carry):
        slot = g % 2

        @pl.when(g + 1 < group_hi)
        def _():
            visible_pages(g + 1, 1 - slot, lambda c: c.start())

        jax.lax.cond(g < whole, lambda: wait_whole(slot), lambda: visible_pages(g, slot, lambda c: c.wait()))
        for _ in work(g, slot):
            pass
        return carry

    visible_pages(0, 0, lambda c: c.start())
    last_beside = jnp.maximum(whole - 1, 0)
    jax.lax.fori_loop(0, last_beside, beside, None)
    jax.lax.fori_loop(last_beside, group_hi, apart, None)


def _latent_group_work(g, slot, q_ref, buf, m_scr, l_scr, acc_scr, held, *, sm_scale: float, rank: int):
    """Group ``g``'s work of the latent decode kernel on ``buf[slot]``, a
    generator that yields between the pieces :func:`_walk_latent_pages` starts
    copies between: ``QK^T`` a tile of ``_LATENT_KEY_TILE`` cached rows at a
    time, the softmax step on the whole group's masked scores, ``P.V`` a lane
    tile of the values at a time. The arithmetic is :func:`_softmax_step`'s
    with :func:`_dot_pv` at one term: a score and an output number are each
    one product's own, so the tiles change nothing in them."""
    span = buf.shape[1]
    tile = min(_LATENT_KEY_TILE, span)
    pos = g * span + jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)
    q = q_ref[...]
    scores = []
    for t in range(0, span, tile):
        scores.append(_dot_qk(q, buf[slot, t:t + tile]) * sm_scale)
        yield
    s = jnp.where(pos < held, jnp.concatenate(scores, axis=1), NEG_INF)
    m_prev = m_scr[0, :, :1]
    l_prev = l_scr[0, :, :1]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(jnp.where(m_prev == NEG_INF, NEG_INF, m_prev - m_new))
    p = jnp.exp(s - m_new)
    l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
    m_scr[0] = jnp.broadcast_to(m_new, m_scr.shape[1:])
    l_scr[0] = jnp.broadcast_to(l_new, l_scr.shape[1:])
    yield
    for n in range(0, rank, _LANES):
        lanes = slice(n, min(n + _LANES, rank))
        acc_scr[0, :, lanes] = acc_scr[0, :, lanes] * alpha + _dot_pv(p, buf[slot, :, lanes], terms=1)
        yield


def _latent_decode_kernel(tables_ref, lengths_ref, layer_ref, q_ref, pool_hbm, o_ref, buf, sems, m_scr, l_scr, acc_scr,
                          *, sm_scale: float, block_size: int, rank: int):
    """Grid (B,): a sequence a grid step, its cached rows walked once
    (:func:`_walk_latent_pages`). q_ref ``[rows, lanes]``: a head a row, the
    query in the absorbed form (zero rows pad the heads to a sublane tile); a
    group of cached rows ``[span, lanes]`` is every head's keys, and its first
    ``rank`` lanes every head's values: one ``QK^T``, one softmax step and one
    ``P.V`` a group serve all heads (:func:`_latent_group_work`). Operands go
    to the MXU as stored, ``P`` rounded to the pool's type for one pass
    (:func:`_dot_pv`); scores and state are float32. o_ref ``[rows, rank]``:
    ``sum_j a_j c_j`` a head."""
    bi = pl.program_id(0)
    held = jnp.minimum(lengths_ref[bi], tables_ref.shape[1] * block_size)

    @pl.when(bi == 0)
    def _clean_buffer():
        buf[...] = jnp.zeros_like(buf)

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    def work(g, slot):
        return _latent_group_work(g, slot, q_ref, buf, m_scr, l_scr, acc_scr, held, sm_scale=sm_scale, rank=rank)

    _walk_latent_pages(tables_ref, bi, layer_ref[0], pool_hbm, buf, sems, block_size, held, work)
    o_ref[...] = _finished(m_scr, l_scr, acc_scr, 0).astype(o_ref.dtype)


def _latent_prefill_kernel(tables_ref, starts_ref, lengths_ref, layer_ref, q_ref, pool_hbm, o_ref, buf, sems,
                           m_scr, l_scr, acc_scr, *, sm_scale: float, block_size: int, rank: int, heads: int):
    """Grid (B, T / tq): a tile of ``tq`` consecutive queries of a chunk a grid
    step, all heads: q_ref ``[tq * heads, lanes]``, row ``t * heads + h``
    query ``t``'s head ``h`` in the absorbed form. The tile's keys are walked
    where they lie from position 0 to its last query's own (or the chunk's
    last real token's), causal by position, as :func:`_paged_prefill_kernel`
    walks them; a cached row is key and value as in
    :func:`_latent_decode_kernel`."""
    bi, qi = pl.program_id(0), pl.program_id(1)
    rows = q_ref.shape[0]
    span, tq = buf.shape[1], rows // heads
    p0 = starts_ref[bi] + qi * tq
    held = jnp.minimum(starts_ref[bi] + lengths_ref[bi], tables_ref.shape[1] * block_size)
    last = jnp.where(p0 < held, jnp.minimum(held, p0 + tq), 0)

    @pl.when(jnp.logical_and(bi == 0, qi == 0))
    def _clean_buffer():
        buf[...] = jnp.zeros_like(buf)

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    q_pos = p0 + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // heads
    one_pass = functools.partial(_dot_pv, terms=1)

    def one_group(g, slot):
        pos = g * span + jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)
        visible = jnp.logical_and(pos <= q_pos, pos < last)  # [rows, span]
        s = _dot_qk(q_ref[...], buf[slot]) * sm_scale
        _softmax_step(jnp.where(visible, s, NEG_INF), buf[slot, :, :rank], 0, m_scr, l_scr, acc_scr, dot_pv=one_pass)

    _walk_pages(tables_ref, bi, layer_ref[0], pool_hbm, None, buf, None, sems, block_size, 0, last, one_group)
    o_ref[...] = _finished(m_scr, l_scr, acc_scr, 0).astype(o_ref.dtype)


def _latent_call(kernel, name, scalars, q, pool, *, grid, rows, q_index, rank, span, **compiler_params):
    """The ``pallas_call`` of both latent kernels: q ``[B, R, lanes]`` in
    blocks of ``rows`` at ``q_index``, the result ``[B, R, rank]`` likewise;
    the pool stays in HBM; scratch: the two-slot group buffer, its copy
    semaphores, the online-softmax state."""
    B, _, W = q.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=grid,
        in_specs=[pl.BlockSpec((None, rows, W), q_index), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, rows, rank), q_index),
        scratch_shapes=[
            pltpu.VMEM((2, span, W), pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((1, rows, _LANES), jnp.float32),
            pltpu.VMEM((1, rows, _LANES), jnp.float32),
            pltpu.VMEM((1, rows, rank), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, q.shape[1], rank), q.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",) * len(grid), **compiler_params),
        interpret=_use_interpret(),
        name=name,
    )(*scalars, q, pool)


def _latent_xla(q, pool, block_tables, starts, lengths, layer, scale, rank):
    """Plain-XLA reference of both latent kernels: each sequence's rows of
    ``layer`` gathered to a dense ``[B, M*bs, lanes]`` view, masked scores
    of every head against them, their first ``rank`` lanes as values."""
    B, T = q.shape[:2]
    rows = pool[layer, block_tables].reshape(B, -1, pool.shape[-1]).astype(jnp.float32)
    s = jnp.einsum("bthw,bsw->bhts", q.astype(jnp.float32), rows) * scale
    pos = jnp.arange(rows.shape[1])[None, None, :]
    q_pos = (starts[:, None] + jnp.arange(T)[None, :])[:, :, None]
    vis = (pos <= q_pos) & (pos < (starts + lengths)[:, None, None])       # [B, T, S]
    p = jnp.where(vis.any(-1)[:, None, :, None], jax.nn.softmax(jnp.where(vis[:, None], s, NEG_INF), axis=-1), 0.0)
    return jnp.einsum("bhts,bsr->bthr", p, rows[..., :rank])


def latent_paged_decode(
    q: jax.Array,             # [B, H, lanes] one absorbed query a head a sequence
    pool: jax.Array,          # [L, num_blocks, block_size, lanes] the latent pool
    block_tables: jax.Array,  # [B, M]
    lengths: jax.Array,       # [B] cached rows of each sequence
    layer: jax.Array,         # int32 scalar: the pool's layer
    *,
    rank: int,
    sm_scale: float,
    use_kernel: bool = True,
    span: Optional[int] = None,
) -> jax.Array:
    """Decode attention of a latent layer over its one pool: returns
    ``[B, H, rank]``, ``sum_j a_j c_j`` a head (the caller expands it to the
    head's values). ``q`` is in the absorbed form
    (``models/transformer.latent_absorb``), zero in the pool's pad lanes; a
    cached row's ``lanes`` are its key for every head, its first ``rank``
    its value. Idle rows (table starting at the garbage page 0) read as
    length 0, as in :func:`paged_decode_attention`. ``span``: cached rows a
    group of the walk (a multiple of the page and of 128)."""
    B, H, W = q.shape
    bs = pool.shape[2]
    lengths = jnp.where(block_tables[:, 0] > 0, lengths, 0).astype(jnp.int32)
    if not use_kernel:
        out = _latent_xla(q[:, None], pool, block_tables, jnp.maximum(lengths - 1, 0), jnp.minimum(lengths, 1),
                          layer, sm_scale, rank)
        return out[:, 0].astype(q.dtype)
    pad = (-H) % 16
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0))) if pad else q
    scalars = [block_tables.astype(jnp.int32), lengths, jnp.asarray(layer, jnp.int32).reshape(1)]
    out = _latent_call(
        functools.partial(_latent_decode_kernel, sm_scale=sm_scale, block_size=bs, rank=rank),
        "latent_paged_decode", scalars, qp, pool, grid=(B,), rows=H + pad, q_index=lambda b, *_: (b, 0, 0),
        rank=rank, span=span or max(1, _LATENT_DECODE_SPAN // bs) * bs)
    return out[:, :H]


def latent_paged_prefill(
    q: jax.Array,             # [B, T, H, lanes] a chunk of consecutive queries a sequence, absorbed
    pool: jax.Array,          # [L, num_blocks, block_size, lanes]
    block_tables: jax.Array,  # [B, M]
    starts: jax.Array,        # [B] the position of each chunk's first query
    lengths: jax.Array,       # [B] real tokens of each chunk
    layer: jax.Array,
    *,
    rank: int,
    sm_scale: float,
    use_kernel: bool = True,
) -> jax.Array:
    """Chunked-prefill attention of a latent layer over its one pool; returns
    ``[B, T, H, rank]``. The chunk's own rows are in the pool already; query
    ``t`` of row ``b`` stands at ``starts[b] + t`` and sees the rows at or
    before it, as far as the chunk's real tokens reach, as
    :func:`paged_prefill_attention` has it. A tile of queries re-reads the
    rows before it: 1280 B a token, a twelfth of what K and V of 32 heads of
    128 cost, so the walk is bound by its products, not its copies."""
    B, T, H, W = q.shape
    bs = pool.shape[2]
    starts, lengths = starts.astype(jnp.int32), jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))
    if not use_kernel:
        return _latent_xla(q, pool, block_tables, starts, lengths, layer, sm_scale, rank).astype(q.dtype)
    unit = 16 // math.gcd(16, H)  # the fewest queries whose heads fill whole sublane tiles of a 16-bit query
    tq = max(unit, min(_LATENT_PREFILL_ROWS // H // unit * unit, -(-T // unit) * unit))
    pad_t = (-T) % tq
    if pad_t:
        q = jnp.pad(q, ((0, 0), (0, pad_t), (0, 0), (0, 0)))
    scalars = [block_tables.astype(jnp.int32), starts, lengths, jnp.asarray(layer, jnp.int32).reshape(1)]
    out = _latent_call(
        functools.partial(_latent_prefill_kernel, sm_scale=sm_scale, block_size=bs, rank=rank, heads=H),
        "latent_paged_prefill", scalars, q.reshape(B, -1, W), pool, grid=(B, (T + pad_t) // tq), rows=tq * H,
        q_index=lambda b, i, *_: (b, i, 0), rank=rank, span=max(1, _LANES // bs) * bs,
        vmem_limit_bytes=_PREFILL_VMEM_BYTES)
    return out.reshape(B, T + pad_t, H, rank)[:, :T]


# ---------------------------------------------------------------------------
# the pools' write: a call's new rows, a page at a time
# ---------------------------------------------------------------------------
_WRITE_VMEM_BYTES = 48 * 1024 * 1024
# of it, the page buffers of the segments a grid step holds at once: every segment of every served call (a 512-row
# chunk of 3840 lanes: 33 pages of 120 KiB, twice, two pools: 15.5 MiB; 40 decode rows of 2048: 7.5 MiB); a call of
# more takes a grid step more
_WRITE_BUFFER_BYTES = 24 * 1024 * 1024


def _paged_write_kernel(layer_ref, page_ref, lo_ref, hi_ref, base_ref, *refs, pools: int, run: int, per_sequence: int):
    """A grid step: as many segments of the call as its buffers hold (all of
    them, at the served shapes). Segment ``s`` is the rows
    ``[lo[s], hi[s])`` of page ``page[s]`` of layer ``layer`` (page 0, the
    garbage page, or an empty range: nothing to do), in each of ``pools``
    pools. The pools stay in HBM, input aliased to output; what moves is
    whole pages, because a row of a page is no unit the chip can copy: a page
    of 16 rows is one tile of the pool's layout, its rows interleaved in it.

    ``run`` = 0 (runs of a page's rows or more): the source of segment ``s``
    is ``src[s]``, a whole page in HBM whose rows ``[lo, hi)`` are the new
    ones. A segment that fills its page goes as one copy from there to the
    pool; any other has its page read into VMEM, the new rows laid over it
    and the page written back. ``run`` > 0 (a sequence's ``run`` rows are
    fewer than a page's; ``per_sequence`` segments each): ``src`` is
    ``[B * run, 1, lanes]`` in VMEM, row ``t`` of the segment's sequence
    bound for row ``base[s] + t`` of the page, where that lies in
    ``[lo, hi)``. Every copy of a phase is started before any is waited for
    (the semaphores count them: the pages' reads, the sources' reads, the
    writes), so the call costs its bytes and three latencies, not a latency
    a row."""
    staged = run == 0
    srcs, pools_in, pools_out = refs[:pools], refs[pools:2 * pools], refs[2 * pools:3 * pools]
    bufs, src_bufs = refs[3 * pools:4 * pools], refs[4 * pools:5 * pools] if staged else None
    sems = refs[-1]
    layer = layer_ref[0]
    S, bs = bufs[0].shape[:2]  # segments a grid step
    s0 = pl.program_id(0) * S

    def each(fn):
        def body(s, carry):
            page, lo, hi = page_ref[s0 + s], lo_ref[s0 + s], hi_ref[s0 + s]
            live = jnp.logical_and(page > 0, hi > lo)
            whole = jnp.logical_and(live, jnp.logical_and(lo == 0, hi == bs)) if staged else False
            fn(s, page, lo, hi, jnp.logical_and(live, jnp.logical_not(whole)), whole)
            return carry

        jax.lax.fori_loop(0, S, body, None)

    def reads(s, page, act):
        for p in range(pools):
            act(pltpu.make_async_copy(pools_in[p].at[layer, page], bufs[p].at[s], sems.at[p, 0]))
            if staged:
                act(pltpu.make_async_copy(srcs[p].at[s0 + s], src_bufs[p].at[s], sems.at[p, 1]))

    def writes(s, page, from_src, act):
        for p in range(pools):
            held = srcs[p].at[s0 + s] if from_src else bufs[p].at[s]
            act(pltpu.make_async_copy(held, pools_out[p].at[layer, page], sems.at[p, 2]))

    def start_reads(s, page, lo, hi, partial, whole):
        @pl.when(partial)
        def _():
            reads(s, page, lambda copy: copy.start())

        if staged:  # a page the run fills needs no read: on its way at once
            @pl.when(whole)
            def _():
                writes(s, page, True, lambda copy: copy.start())

    def wait_reads(s, page, lo, hi, partial, whole):
        @pl.when(partial)
        def _():
            reads(s, page, lambda copy: copy.wait())

    def merge_and_write(s, page, lo, hi, partial, whole):
        @pl.when(partial)
        def _():
            for p in range(pools):
                tile = bufs[p][s]
                row = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0)
                mine = jnp.logical_and(row >= lo, row < hi)
                if staged:
                    tile = jnp.where(mine, src_bufs[p][s], tile)
                for t in range(run):
                    new = jnp.broadcast_to(srcs[p][s // per_sequence * run + t], tile.shape)
                    tile = jnp.where(jnp.logical_and(mine, row == base_ref[s0 + s] + t), new, tile)
                bufs[p][s] = tile
            writes(s, page, False, lambda copy: copy.start())

    def wait_writes(s, page, lo, hi, partial, whole):
        @pl.when(partial)
        def _():
            writes(s, page, False, lambda copy: copy.wait())

        if staged:
            @pl.when(whole)
            def _():
                writes(s, page, True, lambda copy: copy.wait())

    each(start_reads)
    each(wait_reads)
    each(merge_and_write)
    each(wait_writes)


def paged_write_segments(phys, off, *, sequences: int, block_size: int):
    """The pages a call's rows touch, from where each row goes: ``phys``,
    ``off`` ``[B*T]`` (``models/generation._paged_write_index``: the ``T``
    rows of each of the ``sequences`` at consecutive positions, the rows to
    keep a prefix of them, the others bound for page 0). Returns ``(page,
    lo, hi, base, first)``: ``[B*P]`` each but the last, rows ``[lo, hi)`` of
    page ``page`` a segment (``P`` pages a sequence: as many as ``T`` rows
    from any offset can touch), ``base`` the row of the segment's page at
    which its sequence's first row would stand (negative in a later page),
    and ``first`` ``[B]``, the offset of a sequence's first row in its page.
    The same for every layer of a call: a caller that loops over layers
    computes it once."""
    B, bs = sequences, block_size
    T = phys.shape[0] // B
    phys, off = phys.reshape(B, T).astype(jnp.int32), off.reshape(B, T).astype(jnp.int32)
    first = off[:, 0]
    P = (T + bs - 2) // bs + 1
    nth = jnp.arange(P)[None, :]
    segment = (first[:, None] + jnp.arange(T)[None, :]) // bs          # [B, T] the row's page of the P
    mine = (segment[:, None, :] == nth[:, :, None]) & (phys > 0)[:, None, :]  # [B, P, T]
    page = jnp.max(jnp.where(mine, phys[:, None, :], 0), axis=-1)
    lo = jnp.where(nth == 0, first[:, None], 0)
    hi = lo + mine.sum(-1).astype(jnp.int32)
    base = first[:, None] - nth * bs
    return page.reshape(-1), lo.reshape(-1), hi.reshape(-1), base.reshape(-1), first


def paged_write_rows(pools, rows, layer, segments):
    """Write a call's new rows into layer ``layer`` of paged pools, in place:
    ``pools`` a tuple of one (a latent pool) or two (K and V)
    ``[L, num_blocks, block_size, lanes]`` arrays, ``rows`` as many
    ``[B*T, lanes]`` arrays of the pools' type, ``segments`` what
    :func:`paged_write_segments` makes of where each row goes. Returns the
    pools, byte for byte what ``pool.at[layer, phys, off].set(rows)`` gives
    on every page but page 0.

    The contract is the index's own: the ``T`` rows of a sequence stand at
    consecutive positions; the rows to keep are a prefix of them and the rest
    (a chunk's padded tail, an idle slot, a position past a table) are bound
    for the garbage page 0, which this call never touches; no two sequences
    write into one page. One Mosaic call (``paged_write``) serves every pool,
    row width and row count: see :func:`_paged_write_kernel`. Runs shorter
    than a page (a decode call's one row a sequence, a block step's few) ride
    into VMEM with the call; longer runs are first laid out by XLA as the
    whole pages they will be (``[B, P * block_size, lanes]``, the rows at
    their offset), so that a page the run fills is one copy from there."""
    page, lo, hi, base, first = segments
    n = len(pools)
    bs, lanes = pools[0].shape[2:]
    B, S = first.shape[0], page.shape[0]
    T = rows[0].shape[0] // B
    P = S // B  # pages a sequence
    staged = T >= bs
    # sequences a grid step: their segments' page buffers (and the sources', read beside them or riding in twice) fit
    held = n * bs * lanes * pools[0].dtype.itemsize * (2 if staged else 3)
    per_step = max(1, min(B, _WRITE_BUFFER_BYTES // (held * P)))
    steps = -(-B // per_step)
    pad = steps * per_step - B
    if pad:  # whole grid steps: the segments added are bound for page 0
        page, lo, hi, base = (jnp.pad(a, (0, pad * P)) for a in (page, lo, hi, base))
        first = jnp.pad(first, (0, pad))
        rows = [jnp.pad(r, ((0, pad * T), (0, 0))) for r in rows]
    if staged:
        def lay_out(r):  # [B*T, lanes] -> [B*P, bs, lanes]: each sequence's rows from their offset in whole pages
            def place(run, at):
                return jax.lax.dynamic_update_slice(jnp.zeros((P * bs, lanes), r.dtype), run, (at, 0))

            runs = r.reshape(-1, T, lanes)
            pages = place(runs[0], first[0])[None] if runs.shape[0] == 1 else jax.vmap(place)(runs, first)
            return pages.reshape(-1, bs, lanes)

        srcs = [lay_out(r) for r in rows]
        src_spec = pl.BlockSpec(memory_space=pl.ANY)
    else:
        srcs = [r[:, None, :] for r in rows]
        src_spec = pl.BlockSpec((per_step * T, 1, lanes), lambda i, *_: (i, 0, 0))
    buffers = [pltpu.VMEM((per_step * P, bs, lanes), pool.dtype) for pool in pools]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(steps,),
        in_specs=[src_spec] * n + [pl.BlockSpec(memory_space=pl.ANY)] * n,
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * n,
        scratch_shapes=buffers * (2 if staged else 1) + [pltpu.SemaphoreType.DMA((n, 3))],
    )
    out = pl.pallas_call(
        functools.partial(_paged_write_kernel, pools=n, run=0 if staged else T, per_sequence=P),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype) for pool in pools],
        grid_spec=grid_spec,
        input_output_aliases={5 + n + p: p for p in range(n)},  # the pools, counted with the five prefetched scalars
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=_WRITE_VMEM_BYTES),
        interpret=_use_interpret(),
        name="paged_write",
    )(jnp.asarray(layer, jnp.int32).reshape(1), page, lo, hi, base, *srcs, *pools)
    return tuple(out)
