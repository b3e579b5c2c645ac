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
Kwon et al. 2023): K/V live in a shared, layer-stacked pool of fixed-size
pages ``[L, num_blocks, block_size, Hkv*D]`` (a page row holds all KV heads
side by side on the lanes, so the pool's natural device layout is unpadded
row-major pages) and each sequence names its pages in an
``int32[B, max_blocks]`` block table. The grid is one step a sequence; the
table, the lengths and the layer index ride Pallas scalar prefetch
(``PrefetchScalarGridSpec``), the pools stay in HBM, and the body copies the
pages a sequence can see, a group of 128 tokens at a time, straight out of
the stacked pool — no per-layer slice, no materialized per-sequence cache
copy, and no work for a page or a slot that holds nothing.
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


def _paged_decode_kernel(
    tables_ref, lengths_ref, layer_ref,  # scalar-prefetch: [B, M] page ids, [B], [1]
    *refs, sm_scale: float, block_size: int, pack: int, windowed: bool,
):
    """Grid (B,): one grid step a sequence; its visible pages are walked by a
    loop inside the body, so a slot that holds nothing costs one grid step
    and a page no query may see costs nothing at all.

    k_hbm/v_hbm are the whole stacked pools ``[L, N, block_size, Hkv*D]``
    where they lie in HBM. The body loops, with a traced trip count, over
    the row's visible span in groups of ``G`` pages (``k_buf``/``v_buf``:
    ``[2, G*block_size, Hkv*D]`` VMEM, one whole 128-token lane tile of
    scores where the page size divides 128), from the group that holds the
    window's first position (0 without one) to the one that holds position
    ``length - 1``. Each page of a group comes by its own copy out of
    ``pool[layer, table[b, j]]`` into its rows of the group's buffer; the
    next group's copies start before this group's products (two buffers).
    Pages of a group past the last live one, or before the window's first,
    are not fetched: their rows keep what an earlier group left there and
    are masked by position (scores by ``where``, so stale K cannot reach a
    sum, and a probability of exactly 0 meets stale V). The buffers are
    zeroed at the first grid step, so they only ever hold zeros or live
    pages of the pool.

    The KV heads are walked in static lane tiles of ``W = pack*D`` lanes,
    ``pack`` neighbouring heads to a tile, so a 64-wide head still loads
    whole 128-lane tiles: q_ref is ``[Hkv, rep_p, W]`` with head ``g``'s
    query in its own ``D`` lanes of its tile and zeros in its neighbours'
    (their K lanes drop out of the scores as exact zeros), and o_ref/acc
    carry ``W`` lanes of which the caller keeps head ``g``'s own. Per head
    the state machine is :func:`_decode_kernel`'s, a group at a time.
    ``windowed``: a fourth scalar-prefetch ref ``[1]`` carries this call's
    sliding window (0: none).
    """
    if windowed:
        window_ref, *refs = refs
    q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, m_scr, l_scr, acc_scr = refs
    bi = pl.program_id(0)
    length = lengths_ref[bi]
    layer = layer_ref[0]
    n_kv, _, w = q_ref.shape
    span = k_buf.shape[1]  # tokens a group
    group = span // block_size
    first = window_start(length, window_ref[0]) if windowed else 0
    # a length past the table's capacity (a finished row's overshoot) walks
    # the table and no further
    held = jnp.minimum(length, tables_ref.shape[1] * block_size)
    page_lo, page_hi = first // block_size, pl.cdiv(held, block_size)
    group_lo, group_hi = first // span, pl.cdiv(held, span)

    @pl.when(bi == 0)
    def _clean_buffers():
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    def page_copies(g, slot, act):
        """``act`` on the K and V copy of each visible page of group ``g``."""

        def one_page(page, carry):
            phys = tables_ref[bi, page]
            rows = pl.ds(pl.multiple_of((page - g * group) * block_size, block_size), block_size)
            act(pltpu.make_async_copy(k_hbm.at[layer, phys], k_buf.at[slot, rows], sems.at[0, slot]))
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
        pos = g * span + jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)
        visible = jnp.logical_and(pos >= first, pos < held)  # [1, span]
        for h in range(n_kv):
            if h % pack == 0:  # a new lane tile: widened once for its heads
                lanes = slice(h // pack * w, (h // pack + 1) * w)
                k = k_buf[slot, :, lanes].astype(jnp.float32)
                v = v_buf[slot, :, lanes].astype(jnp.float32)
            q = q_ref[h].astype(jnp.float32) * sm_scale
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            s = jnp.where(visible, s, NEG_INF)  # [rep_p, span]

            m_prev = m_scr[h, :, :1]
            l_prev = l_scr[h, :, :1]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(jnp.where(m_prev == NEG_INF, NEG_INF, m_prev - m_new))
            p = jnp.exp(s - m_new)
            l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
            l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])
        return carry

    jax.lax.fori_loop(group_lo, group_hi, one_group, None)

    l = l_scr[:, :, :1]
    empty = m_scr[:, :, :1] <= NEG_INF * 0.5  # lengths[b] == 0: emit zeros
    out = jnp.where(empty, 0.0, acc_scr[...] / jnp.where(l == 0, 1.0, l))
    o_ref[...] = out.astype(o_ref.dtype)


def _paged_decode_xla(qg, k_pool, v_pool, block_tables, lengths, layer, scale, window=None):
    """Plain-XLA reference: gather each sequence's pages of ``layer`` into a
    dense [B, Hkv, M*bs, D] view and run the masked grouped einsum — the
    exact math of the dense path; the kernel is compared against it (tier-1
    in interpret mode, ``chip_smoke.py`` compiled)."""
    B, Hkv, _, D = qg.shape
    M, bs = block_tables.shape[1], k_pool.shape[2]

    def dense(pool):
        g = pool[layer, block_tables]  # [B, M, bs, Hkv*D]
        return jnp.transpose(g.reshape(B, M * bs, Hkv, D), (0, 2, 1, 3))

    k, v = dense(k_pool), dense(v_pool)
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

    rep_p = -(-n_rep // _MIN_REP) * _MIN_REP
    if rep_p != n_rep:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rep_p - n_rep), (0, 0)))
    # heads narrower than a lane tile share one: `pack` neighbours to a tile
    # of W lanes, so the kernel never slices inside a tile (a 64-lane slice
    # at a 64-lane offset cost twice the aligned load per page on the v5e)
    pack = math.gcd(Hkv, _LANES // D) if _LANES % D == 0 else 1
    W = pack * D
    own = jnp.arange(Hkv) % pack  # which D lanes of its tile are head g's own
    if pack > 1:
        # q into its own lanes, exact zeros in the neighbours'
        own_lanes = jax.nn.one_hot(own, pack, dtype=qg.dtype)  # [Hkv, pack]
        qg = (qg[:, :, :, None, :] * own_lanes[None, :, None, :, None]).reshape(B, Hkv, rep_p, W)
    windowed = window is not None
    scalars = [block_tables.astype(jnp.int32), lengths.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1)]
    if windowed:
        scalars.append(jnp.asarray(window, jnp.int32).reshape(1))

    # a group of pages is one lane tile of scores: 128 tokens where the page
    # size divides it
    span = max(1, _LANES // bs) * bs
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),  # block_tables, lengths, layer(, window)
        grid=(B,),
        in_specs=[
            pl.BlockSpec((None, Hkv, rep_p, W), lambda b, *_: (b, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),  # the pools stay in HBM: the body
            pl.BlockSpec(memory_space=pl.ANY),  # copies the pages it needs
        ],
        out_specs=pl.BlockSpec((None, Hkv, rep_p, W), lambda b, *_: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, span, row), k_pool.dtype),
            pltpu.VMEM((2, span, row), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),  # [K | V, buffer]
            pltpu.VMEM((Hkv, rep_p, _LANES), jnp.float32),
            pltpu.VMEM((Hkv, rep_p, _LANES), jnp.float32),
            pltpu.VMEM((Hkv, rep_p, W), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, sm_scale=scale, block_size=bs, pack=pack, windowed=windowed),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rep_p, W), q.dtype),
        grid_spec=grid_spec,
        # in order: the group buffers are cleaned at the first grid step
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=_use_interpret(),
        name="paged_decode",
    )(*scalars, qg, k_pool, v_pool)
    out = out[:, :, :n_rep]
    if pack > 1:  # keep each head's own lanes of its tile
        out = jnp.take_along_axis(
            out.reshape(B, Hkv, n_rep, pack, D), own[None, :, None, None, None], axis=3
        )
    return out.reshape(B, H, D)
