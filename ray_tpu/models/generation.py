"""KV-cache autoregressive decoding for the flagship transformer.

The reference has no inference engine in core (Serve wraps user callables;
its LLM examples delegate to vLLM). Here decoding is first-class and
TPU-first:

- **Static shapes everywhere**: the cache is either a preallocated ring of
  ``[n_layers, B, kv_heads, max_len, head_dim]`` buffers (:func:`init_cache`,
  :func:`forward_with_cache`) or a paged pool ``[n_layers, pages, page_size,
  kv_heads*head_dim]`` named by per-sequence block tables
  (:func:`init_paged_cache`, :func:`paged_forward_with_cache`: the serving
  engine's path); prefill and every decode step are fixed-shape XLA programs,
  so the whole generate loop jits to one compiled executable (``lax.scan``
  over steps — no per-token Python).
- **One block**: both loops call the block functions of
  ``models/transformer.py`` (projections, query/key norms, RoPE, output gate,
  sandwich norms, dense or expert FFN), so they compute ``forward``'s function
  of the same parameter tree. A layer's kind (sliding window or full, RoPE or
  none) rides the layer scan as per-layer values; the window reaches the
  dense-view mask and the decode kernels as a scalar beside ``lengths``.
- **Ragged batches without ragged shapes**: per-sequence write offsets go
  through a vmapped ``dynamic_update_slice`` (lowers to an in-place scatter)
  and visibility is a ``key_pos <= query_pos`` mask — the padded tail of a
  short prompt is simply never visible and is overwritten as decoding
  proceeds.
- GQA (``n_kv_heads < n_heads``) shrinks the cache by the group factor —
  decode is HBM-bandwidth-bound, so cache bytes are the speed of light here.

Used by ``ray_tpu.serve.llm`` (continuous batching) and directly via
``generate()``.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.transformer import (
    TransformerConfig,
    block_attn_out,
    block_ffn,
    block_qkv,
    block_last,
    conv_mixer,
    embed_tokens,
    flash_by_kind,
    full_kind,
    hybrid_scan,
    in_window,
    latent_absorb,
    latent_out,
    latent_qkv,
    latent_scale,
    layer_kinds,
    layer_stacks,
    linear_inputs,
    linear_out,
    pre_norm,
    scanned_leaves,
    unembed,
)
from ray_tpu.ops import backend
from ray_tpu.ops.gated_delta import (
    gated_delta_chunked,
    gated_delta_decode,
    lane_group,
    pack_state,
    unpack_state,
)

KVCache = Dict[str, jax.Array]


def _refuse_ring_for_hybrid(cfg: TransformerConfig, what: str) -> None:
    if cfg.hybrid:
        raise ValueError(f'{what} keeps keys and values only: a config with "linear" layers (recurrent state a '
                         'sequence), "conv" layers (a convolution tail a sequence) or "latent" layers (one latent '
                         "row a token) is served through the paged path, init_paged_cache(..., slots=) and "
                         "paged_forward_with_cache(..., slots=)")


def _refuse_latent_stack(cfg: TransformerConfig, what: str) -> None:
    if cfg.latent_layers and not cfg.hybrid:
        raise ValueError(f'{what} does not serve an all-latent stack ("latent" in every layer, the shared key '
                         "part rotated): it is trained (forward, make_train_step); the latent pool caches an "
                         "unrotated row behind linear layers only")


def init_cache(cfg: TransformerConfig, batch: int, max_len: int, dtype=None) -> KVCache:
    """Preallocated KV cache: {"k","v"}: [L, B, Hkv, max_len, Dh]."""
    _refuse_latent_stack(cfg, "init_cache")
    _refuse_ring_for_hybrid(cfg, "init_cache (the ring cache of forward_with_cache and generate)")
    dt = dtype or cfg.dtype
    shape = (cfg.n_layers, batch, cfg.kv_heads, max_len, cfg.head_dim)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def init_paged_cache(
    cfg: TransformerConfig, num_blocks: int, block_size: int, dtype=None, slots: int = 0
) -> KVCache:
    """Paged KV pool: {"k","v"}: [L, num_blocks, block_size, Hkv*Dh].

    A config with "latent" layers keeps **one** pool in their place,
    ``{"latent": [L_lat, num_blocks, block_size, lanes]}``: a token's row is
    the normalised latent and the key part every head shares, side by side
    (``cfg.latent_row`` numbers, zero lanes up to ``cfg.latent_row_lanes``:
    whole 128-lane tiles, which is what the chip's layout of the minor axis
    occupies either way), read as keys and, in its first ``latent_rank``
    lanes, as values (``ops/decode_attention.py``, ``latent_paged_*``).

    A config with "linear" layers keeps pages for its full layers only (``L``
    is ``cfg.kv_layers``) and, beside them, what its linear layers carry a
    sequence, for ``slots`` sequences: ``"state"`` ``[L_lin, slots, H / g,
    dk, g * dv]`` float32 (the recurrent state, ``g`` heads side by side on
    the minor axis: ``ops/gated_delta.py``) and ``"conv"`` ``[L_lin, slots,
    (width - 1) * channels]`` (the convolution's last ``width - 1`` inputs,
    oldest first, side by side: with an axis of 3 of its own XLA lays the
    array out with that axis on the 128 lanes inside the layer loop, 1.13 GB
    for 28 MB at the benchmark's sizes). A sequence names its pages by its
    block table and its state by its slot. A config with "conv" layers (a
    gated short convolution as the whole mixer) keeps pages for its full
    layers and, a sequence, ``"conv"`` ``[L_conv, slots, (width - 1) * d]``
    alone: the tails of its conv layers and no recurrent matrix.

    Unlike :func:`init_cache` there is no batch axis — sequences own sets
    of pages named by an ``int32[B, max_blocks]`` block table, so HBM is
    proportional to tokens actually cached, not ``B * max_len``. Page 0 is
    reserved by convention as the garbage page (all-zero table entries and
    masked writes land there).

    A token's KV heads lie side by side on the minor axis: the TPU's natural
    layout of ``[.., block_size, Hkv*Dh]`` is unpadded row-major pages, which
    is what the paged decode kernel DMAs, so the serve programs update the
    pool in place and read it where it lies. (With ``Dh`` alone minor-most,
    a 64-wide head pads to 128 lanes and XLA puts the page axis on the lanes
    instead — every layer then pays a layout conversion of its whole slice.)
    """
    _refuse_latent_stack(cfg, "init_paged_cache")
    dt = dtype or cfg.dtype
    if cfg.latent_layers:
        # one pool, no K and no V: a token's row is its key for every head and, in its first lanes, its value
        cache = {"latent": jnp.zeros((cfg.latent_layers, num_blocks, block_size, cfg.latent_row_lanes), dt)}
    else:
        shape = (cfg.kv_layers, num_blocks, block_size, cfg.kv_heads * cfg.head_dim)
        cache = {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
    if cfg.hybrid:
        if slots < 1:
            raise ValueError('a config with "linear" or "conv" layers keeps a state a sequence: '
                             "init_paged_cache needs slots >= 1")
        cache.update(init_sequence_state(cfg, slots, dt))
    return cache


def init_sequence_state(cfg: TransformerConfig, slots: int, dtype=None) -> KVCache:
    """What ``slots`` sequences carry beside their pages, zero: the per-slot
    arrays of :func:`init_paged_cache`, and the layout of a pool of snapshots
    of them (``serve/model_runner.py``). A config with "linear" layers: the
    recurrent ``"state"`` and the ``"conv"`` tails of its convolution over q,
    k, v; a config with "conv" layers: the ``"conv"`` tails of its mixers
    alone, ``[L_conv, slots, (conv_width - 1) * d_model]``. Every function of
    a sequence's state walks the keys it finds (:data:`STATE_KEYS`)."""
    if cfg.conv_layers:
        return {"conv": jnp.zeros((cfg.conv_layers, slots, (cfg.conv_width - 1) * cfg.d_model), dtype or cfg.dtype)}
    H, dk, dv = cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim
    g = lane_group(H, dv)
    return {"state": jnp.zeros((cfg.linear_layers, slots, H // g, dk, g * dv), jnp.float32),
            "conv": jnp.zeros((cfg.linear_layers, slots, (cfg.linear_conv_width - 1) * cfg.linear_channels),
                              dtype or cfg.dtype)}


STATE_KEYS = ("state", "conv")  # a cache's arrays whose axis 1 is the sequence's slot


def copy_sequence_state(dst: KVCache, src: KVCache, dst_slot, src_slot) -> KVCache:
    """``dst``'s state of sequence ``dst_slot`` <- ``src``'s of ``src_slot``
    (every layer that keeps one; the slots may be traced): a snapshot taken
    (``dst`` the snapshot pool) or restored (``dst`` the cache). Other keys of
    ``dst`` are passed through."""
    out = dict(dst)
    for name in STATE_KEYS:
        if name in src:
            row = jax.lax.dynamic_slice_in_dim(src[name], src_slot, 1, axis=1)
            out[name] = jax.lax.dynamic_update_slice_in_dim(dst[name], row.astype(dst[name].dtype), dst_slot, axis=1)
    return out


def zero_sequence_state(cache: KVCache, slot) -> KVCache:
    """Sequence ``slot`` starts: what it carries beside its pages is zero."""
    out = dict(cache)
    for name in STATE_KEYS:
        if name in cache:
            zero = jnp.zeros_like(jax.lax.dynamic_slice_in_dim(cache[name], 0, 1, axis=1))
            out[name] = jax.lax.dynamic_update_slice_in_dim(cache[name], zero, slot, axis=1)
    return out


PAGE_POOLS = ("k", "v", "latent")  # a cache's arrays whose axis 1 is the page


def page_pools(cache: KVCache):
    """The paged pools a cache holds: K and V, or the one latent pool."""
    return [name for name in PAGE_POOLS if name in cache]


def paged_cache_spec(heads_axis: Optional[str]):
    """PartitionSpec of a paged pool sharded over KV heads: the heads are
    the leading factor of the minor axis, so an even split of that axis over
    ``heads_axis`` gives each shard whole heads. (The dense cache's spec,
    ``P(None, None, heads_axis, None, None)``, names axis 2 — here that is
    the page's token axis.)"""
    from jax.sharding import PartitionSpec as P

    return P(None, None, None, heads_axis)


def _paged_write_index(block_tables, positions, valid, block_size):
    """(page, offset) of each written token, flattened to ``[B*T]``: the
    paged analog of the dense path's per-row write offset. Rows marked
    invalid (bucket padding) and positions past a row's table (post-finish
    decode overshoot walks into all-zero table entries) land in the reserved
    garbage page 0, so a write can never corrupt another sequence's pages."""
    M = block_tables.shape[1]
    blk = jnp.clip(positions // block_size, 0, M - 1)  # [B, T] logical block
    phys = jnp.take_along_axis(block_tables, blk, axis=1)  # [B, T] physical page
    if valid is not None:
        # padded positions may exceed the table capacity entirely, where the
        # clip above would alias the LAST real block — route them to page 0
        phys = jnp.where(valid, phys, 0)
    return phys.reshape(-1), (positions % block_size).reshape(-1)


def copy_paged_page(cache: KVCache, src, dst) -> KVCache:
    """Copy one physical page — every layer's K and V rows, or its latent
    rows — from ``src`` to ``dst`` in a paged pool (:func:`init_paged_cache`
    layout); what a cache keeps a sequence, not a page, is passed through.

    This is the engine's copy-on-write primitive: a sequence about to write
    into a page it shares with the prefix cache (or another sequence) gets
    its own copy first, then swaps its block-table entry, so shared pages
    are only ever read. ``src``/``dst`` may be traced scalars — under
    ``jit`` every copy shares one compile. Page 0 must never be a
    destination (the garbage page's contents are sacrificial, but a COW
    into it would alias every masked write)."""
    return {**cache, **{kk: cache[kk].at[:, dst].set(cache[kk][:, src]) for kk in page_pools(cache)}}


def export_paged_page(cfg: TransformerConfig, cache: KVCache, page) -> jax.Array:
    """One physical page as a migration block ``[2, L, block_size, Hkv, Dh]``
    (k then v): the format replicas exchange names the heads, whatever the
    pool's own row layout is. Indexing materializes NEW buffers, so the block
    survives later donated steps."""
    if "latent" in cache:  # one pool, no heads: [1, L, block_size, 1, lanes]
        return cache["latent"][:, page][None, :, :, None, :]
    block = jnp.stack([cache["k"][:, page], cache["v"][:, page]])
    return block.reshape(*block.shape[:3], cfg.kv_heads, cfg.head_dim)


def write_paged_pages(cache: KVCache, blocks, pages) -> KVCache:
    """Land migration blocks ``[N, 2, L, block_size, Hkv, Dh]``
    (:func:`export_paged_page`'s format) in the pool, block ``n`` at physical
    page ``pages[n]``, in one scatter per pool. Duplicate ``(block, page)``
    pairs are idempotent (identical bytes to the same page). A latent pool's
    blocks are ``[N, 1, L, block_size, 1, lanes]``."""
    rows = jnp.swapaxes(blocks.reshape(*blocks.shape[:4], -1), 0, 2)  # [L, 2, N, bs, Hkv*Dh]
    return {**cache, **{kk: cache[kk].at[:, pages].set(rows[:, i]) for i, kk in enumerate(page_pools(cache))}}


def _write_kv(cache_layer: jax.Array, new: jax.Array, starts: jax.Array) -> jax.Array:
    """cache_layer [B,Hkv,S,Dh] <- new [B,T,Hkv,Dh] at per-row offset starts[B]."""
    upd = jnp.transpose(new, (0, 2, 1, 3))  # [B, Hkv, T, Dh]
    return jax.vmap(
        lambda c, u, s: jax.lax.dynamic_update_slice(c, u.astype(c.dtype), (0, s, 0))
    )(cache_layer, upd, starts)


def forward_with_cache(
    cfg: TransformerConfig,
    params: Dict[str, Any],
    cache: KVCache,
    tokens: jax.Array,     # [B, T] int32 (T = prompt len for prefill, 1 for decode)
    positions: jax.Array,  # [B, T] int32 absolute positions (contiguous per row)
    *,
    use_decode_kernel: Optional[bool] = None,
    use_prefill_kernel: Optional[bool] = None,
    layer_scales: Optional[Dict[str, jax.Array]] = None,
) -> Tuple[jax.Array, KVCache]:
    """One cached forward pass. Writes this call's K/V into the cache at
    ``positions`` and attends over everything up to them. Returns
    (logits [B, T, V] f32, updated cache).

    ``use_decode_kernel``: route single-token steps through the Pallas
    decode-attention kernel (``ray_tpu.ops.decode_attention``); default
    auto — ``ops.backend.on_tpu()`` (off the chip: the plain-XLA grouped
    einsum).

    ``use_prefill_kernel``: ONLY valid when every row's positions start at
    0 (the :func:`prefill` contract) — then attention sees just this
    call's own K/V, which is exactly causal flash attention over T tokens,
    and the Pallas kernel skips the [T, S] masked einsum against the whole
    cache (quadratic in cache size). Default OFF here (a T>1 call at
    nonzero positions, e.g. speculative verification, would be wrong);
    :func:`prefill` turns it on automatically on TPU.

    ``layer_scales``: dequantization scales matching ``params['layers']``
    (int8 weight-only serving). They ride the layer scan as xs, so each
    layer dequantizes IN the scan body — only one layer's weights ever
    exist at full precision, instead of a whole-tree f32 copy per step.
    Unquantized leaves carry broadcast-ones scales."""
    _refuse_ring_for_hybrid(cfg, "forward_with_cache")
    B, T = tokens.shape
    S = cache["k"].shape[3]
    h_heads, hkv = cfg.n_heads, cfg.kv_heads
    n_rep = h_heads // hkv
    scale = 1.0 / math.sqrt(cfg.head_dim)

    x = embed_tokens(cfg, params, tokens)
    starts = positions[:, 0]
    kv_pos = jnp.arange(S)
    # key s visible to query t iff s <= position(t): causal over the cache
    vis = kv_pos[None, None, None, :] <= positions[:, None, :, None]  # [B,1,T,S]
    if use_decode_kernel is None:
        use_decode_kernel = backend.on_tpu()
    decode_kernel = use_decode_kernel and T == 1
    prefill_kernel = bool(use_prefill_kernel) and T > 1
    _refuse_scales_on_two_stacks(cfg, layer_scales)

    def layer_fn(stack, x, layer_xs):
        if layer_scales is not None:
            layer_q, lsc, kind, index, kc, vc = layer_xs
            layer = _dequantized(cfg, layer_q, lsc)
        else:
            layer, kind, index, kc, vc = layer_xs
        window = None if kind is None else kind["window"]
        h = pre_norm(cfg, layer, "attn_norm", x)
        q, k, v = block_qkv(cfg, layer, h, positions, kind)
        kc = _write_kv(kc, k, starts)
        vc = _write_kv(vc, v, starts)
        if decode_kernel:
            from ray_tpu.ops.decode_attention import decode_attention

            o = decode_attention(q[:, 0], kc, vc, starts + 1, sm_scale=scale, window=window)[:, None]
            o = o.astype(x.dtype)
        elif prefill_kernel:
            # positions start at 0 for every row (prefill contract): the
            # visible keys are exactly this call's own K/V — causal flash
            # over T tokens, no [T, S] cache-wide mask
            kr = jnp.repeat(k, n_rep, axis=2) if n_rep > 1 else k
            vr = jnp.repeat(v, n_rep, axis=2) if n_rep > 1 else v
            qt, kt, vt = (jnp.transpose(a, (0, 2, 1, 3)) for a in (q, kr, vr))
            o = jnp.transpose(flash_by_kind(cfg, qt, kt, vt, scale, window), (0, 2, 1, 3)).astype(x.dtype)
        else:
            # grouped-query attention against the whole cache
            seen = vis if window is None else vis & in_window(
                kv_pos[None, None, None, :], positions[:, None, :, None], window)
            qg = q.reshape(B, T, hkv, n_rep, cfg.head_dim)
            s_ = jnp.einsum(
                "btgrk,bgsk->bgrts", qg.astype(jnp.float32), kc.astype(jnp.float32)
            ) * scale  # [B, Hkv, n_rep, T, S]
            s_ = jnp.where(seen[:, :, None], s_, -1e30)
            p = jax.nn.softmax(s_, axis=-1)
            o = jnp.einsum("bgrts,bgsk->btgrk", p, vc.astype(jnp.float32))
            o = o.reshape(B, T, h_heads, cfg.head_dim).astype(x.dtype)
        x = block_attn_out(cfg, layer, x, h, o)
        x, _ = block_ffn(cfg, layer, x, stack=stack, index=index, kernel=use_decode_kernel)
        return x, (kc, vc)

    stacks = layer_stacks(cfg, params)
    ks, vs = [], []
    for stack, first, last in stacks:
        whole = len(stacks) == 1  # the one stack scans the cache as it is, unsliced
        kc, vc = (cache["k"], cache["v"]) if whole else (cache["k"][first:last], cache["v"][first:last])
        xs = (scanned_leaves(cfg, stack), layer_kinds(cfg, first, last), jnp.arange(last - first), kc, vc)
        if layer_scales is not None:
            xs = (stack, layer_scales) + xs[1:]
        x, (k_out, v_out) = jax.lax.scan(partial(layer_fn, stack), x, xs)
        ks.append(k_out)
        vs.append(v_out)
    ks, vs = (a[0] if len(a) == 1 else jnp.concatenate(a) for a in (ks, vs))
    return unembed(cfg, params, x), {"k": ks, "v": vs}


def _dequantized(cfg: TransformerConfig, layer_q, scales):
    return {k: (layer_q[k].astype(jnp.float32) * scales[k]).astype(cfg.param_dtype) for k in layer_q}


def _refuse_scales_on_two_stacks(cfg: TransformerConfig, layer_scales) -> None:
    if layer_scales is not None and (cfg.dense_stack or cfg.dropless):
        raise ValueError(
            "layer_scales (int8 weight-only serving) cover one stack of layers whose weights ride the scan: a "
            "config with num_dense_layers > 0 or dropless expert layers is not quantized by this path"
        )


def paged_forward_counted(
    cfg: TransformerConfig,
    params: Dict[str, Any],
    cache: KVCache,            # paged pool from init_paged_cache
    block_tables: jax.Array,   # [B, M] int32 physical page per logical block
    tokens: jax.Array,         # [B, T] int32 (T = chunk len for prefill, 1 for decode)
    positions: jax.Array,      # [B, T] int32 absolute positions (contiguous per row)
    *,
    valid: Optional[jax.Array] = None,  # [B, T] bool: False = pad, don't cache
    use_decode_kernel: Optional[bool] = None,
    layer_scales: Optional[Dict[str, jax.Array]] = None,
    with_logits: bool = True,
    slots: Optional[jax.Array] = None,  # [B] int32: each row's sequence slot (a config with linear or conv layers)
    routes: bool = False,
) -> Tuple[jax.Array, KVCache, Dict[str, jax.Array]]:
    """:func:`forward_with_cache` over a paged pool instead of dense rows:
    (logits, cache, the expert layers' counts).

    A config with "linear" layers (``cfg.hybrid``) takes, with the block
    tables, the rows' ``slots``: its full layers write and read pages as
    below (the pool's layer axis counts them alone), its linear layers read
    and write row ``b``'s recurrent state and convolution tail at
    ``cache["state"][:, slots[b]]`` / ``cache["conv"][:, slots[b]]``: a
    single-token call through :func:`~ray_tpu.ops.gated_delta.gated_delta_decode`
    (on the chip the kernel, the state updated where it lies), a chunk
    through :func:`~ray_tpu.ops.gated_delta.gated_delta_chunked` from the
    state the slot holds. Positions ``valid`` marks False advance nothing: a
    row without a valid token (an idle decode row) keeps its state. A
    config with "conv" layers likewise: a conv layer reads row ``b``'s tail
    at ``cache["conv"][:, slots[b]]``, convolves tail and call, and writes
    back the last ``conv_width - 1`` inputs up to the row's real tokens.

    Writes this call's K/V into the pool through the block tables and
    attends over every cached position up to ``positions``. With
    ``use_decode_kernel`` (default: ``ops.backend.on_tpu()`` — the ONE
    select for the paged kernels; the ops themselves have none) attention
    goes through the Pallas paged kernels, the decode kernel for
    single-token calls and the prefill kernel for chunks: the block table
    rides scalar prefetch and the pages a query can see stream from HBM
    where they lie, with no gather copy and no capacity-wide scores, and
    an expert layer's grouped products go through ``ops/grouped_matmul.py``.
    Otherwise the pool is gathered to a dense view and the attention lines
    are IDENTICAL to the dense path's, which is what makes paged serving
    byte-equal to the dense cache under ``JAX_PLATFORMS=cpu``.

    ``valid`` masks bucket-padded tail tokens out of the cache write (their
    K/V routes to the garbage page 0); their logits still compute and are
    simply never read. Chunked prefill is just this function called with
    ``positions`` starting mid-sequence — visibility is positional, so a
    chunk sees all previously cached chunks plus its own causal prefix.

    The stacked pool is the layer loop's carry: layer ``l`` writes its K/V
    rows into ``pool[l]`` in place and attention reads them from there, so a
    donated cache is never sliced, re-laid-out or copied. With
    ``use_decode_kernel`` the write is
    :func:`~ray_tpu.ops.decode_attention.paged_write_rows` (whole pages, one
    call a layer for both pools; a row bound for the garbage page 0 is not
    written at all), else a scatter a pool, the plain reference: the pools
    agree bit for bit on every page but page 0.

    A block-causal config (``cfg.block_length`` > 1) sees, per query, the keys
    to the end of the query's block as far as the call's real tokens reach
    (:func:`~ray_tpu.ops.decode_attention.block_last`): a chunk's tokens, or
    the ``block_length`` positions of a block step (:func:`paged_block_step`),
    are written first and then attended through the pool like any others, and
    every such call goes through the prefill kernel (or the dense lines).
    ``with_logits=False`` skips the final norm and the head (logits: None): no
    token comes from such a config's prefill.

    The counts are what the dropless expert layers did with this call's
    ``valid`` tokens (all, where ``valid`` is None): ``{"assignments":
    int32[E], "pairs_hit": int32}``, the (token, choice) pairs each expert
    got summed over the expert layers, and how many (layer, expert) pairs got
    at least one; zeros for any other config. A config that holds a share of
    its experts (``cfg.experts_held``) counts the experts held, and adds
    ``"routed"``: the pairs the routers chose over all the experts. A caller
    that drops them pays nothing: they fall out of the compiled program.
    ``routes``: in their place ``{"routes": int32[expert layers, B * T, k]}``,
    the experts each dropless layer ran each token through, in layer order
    (:func:`~ray_tpu.models.transformer.moe_ffn_dropless`): a comparison hands
    them to its reference, whose own router breaks a near-tie its own way.
    """
    B, T = tokens.shape
    M = block_tables.shape[1]
    bs = cache["latent" if cfg.latent_layers else "k"].shape[2]
    cap = M * bs
    h_heads, hkv = cfg.n_heads, cfg.kv_heads
    n_rep = h_heads // hkv
    scale = 1.0 / math.sqrt(cfg.head_dim)

    x = embed_tokens(cfg, params, tokens)
    starts = positions[:, 0]
    kv_pos = jnp.arange(cap)
    vis = kv_pos[None, None, None, :] <= block_last(positions, cfg.block)[:, None, :, None]  # [B,1,T,cap]
    if use_decode_kernel is None:
        use_decode_kernel = backend.on_tpu()
    # a chunk's real tokens: the padded tail (``valid`` False) wrote to the
    # garbage page and is no key of the prefill kernel's
    lengths = T if valid is None else jnp.broadcast_to(valid, (B, T)).sum(-1)
    if cfg.block > 1:
        # a query sees past itself inside its block, but nothing past the call's last real token
        vis = vis & (kv_pos < jnp.broadcast_to(starts + lengths, (B,))[:, None])[:, None, None, :]
    _refuse_scales_on_two_stacks(cfg, layer_scales)
    if routes and (cfg.num_experts <= 0 or cfg.moe_capacity_factor > 0 or (cfg.hybrid and not cfg.split_ffn)):
        raise ValueError("routes: the config has no dropless expert layer whose selection could be handed out")

    phys, off = _paged_write_index(block_tables, positions, valid, bs)
    if use_decode_kernel:
        from ray_tpu.ops.decode_attention import paged_write_rows, paged_write_segments

        segments = paged_write_segments(phys, off, sequences=B, block_size=bs)  # once a call, not a layer

    def write_rows(l, pools, rows):
        """This call's new ``rows`` (``[B, T, ..]`` a pool) into layer ``l``
        of ``pools``: on the chip whole pages by one Mosaic call for all the
        pools, else (the plain reference) a scatter a pool."""
        rows = [r.reshape(B * T, -1).astype(p.dtype) for p, r in zip(pools, rows)]
        if use_decode_kernel:
            return paged_write_rows(pools, rows, l, segments)
        return tuple(p.at[l, phys, off].set(r) for p, r in zip(pools, rows))

    def dense_view(pool, l):
        # [B, Hkv, cap, Dh] view of layer l through the block tables, so the
        # attention lines below are verbatim the dense path's. Transpose, then
        # merge pages: splitting the heads off a merged [cap] axis first is
        # the same view, but XLA:CPU then fuses the einsum differently and the
        # logits leave the dense cache's by ~1e-6
        g = pool[l, block_tables].reshape(B, M, bs, hkv, cfg.head_dim)
        return jnp.transpose(g, (0, 3, 1, 2, 4)).reshape(B, hkv, cap, cfg.head_dim)

    def layer_fn(stack, first, carry, layer_xs):
        x, kc, vc = carry
        if layer_scales is not None:
            layer_q, lsc, kind, l = layer_xs
            layer = _dequantized(cfg, layer_q, lsc)
        else:
            layer, kind, l = layer_xs
        window = None if kind is None else kind["window"]
        x, kc, vc = paged_attention_block(x, kc, vc, layer, kind, l, window)
        x, counts = block_ffn(cfg, layer, x, valid, stack=stack, index=l - first, kernel=use_decode_kernel,
                              routes=routes)
        return (x, kc, vc), counts

    def paged_attention_block(x, kc, vc, layer, kind, l, window):
        """One attention branch against the pool: write this call's K/V into
        layer ``l`` of it, attend, output gate, ``wo``, residual."""
        h = pre_norm(cfg, layer, "attn_norm", x)
        q, k, v = block_qkv(cfg, layer, h, positions, kind)
        kc, vc = write_rows(l, (kc, vc), (k, v))
        if use_decode_kernel and T == 1 and cfg.block <= 1:
            from ray_tpu.ops.decode_attention import paged_decode_attention

            o = paged_decode_attention(
                q[:, 0], kc, vc, block_tables, starts + 1, l, sm_scale=scale, window=window
            )[:, None]
            o = o.astype(x.dtype)
        elif use_decode_kernel:
            from ray_tpu.ops.decode_attention import paged_prefill_attention

            o = paged_prefill_attention(
                q, kc, vc, block_tables, starts, lengths, l, sm_scale=scale, window=window, block=cfg.block
            ).astype(x.dtype)
        else:
            # masked positions contribute exactly-0.0 weight, so page-0
            # garbage never reaches the output
            seen = vis if window is None else vis & in_window(
                kv_pos[None, None, None, :], positions[:, None, :, None], window)
            kd, vd = dense_view(kc, l), dense_view(vc, l)
            qg = q.reshape(B, T, hkv, n_rep, cfg.head_dim)
            s_ = jnp.einsum(
                "btgrk,bgsk->bgrts", qg.astype(jnp.float32), kd.astype(jnp.float32)
            ) * scale  # [B, Hkv, n_rep, T, cap]
            s_ = jnp.where(seen[:, :, None], s_, -1e30)
            p = jax.nn.softmax(s_, axis=-1)
            o = jnp.einsum("bgrts,bgsk->btgrk", p, vd.astype(jnp.float32))
            o = o.reshape(B, T, h_heads, cfg.head_dim).astype(x.dtype)
        return block_attn_out(cfg, layer, x, h, o), kc, vc

    if cfg.hybrid:
        if slots is None:
            raise ValueError('a config with "linear" or "conv" layers keeps a state a sequence: pass the rows\' slots')
        if layer_scales is not None:
            raise ValueError('layer_scales (int8 weight-only serving) do not cover a config with "linear" or '
                             '"conv" layers')
        G = lane_group(cfg.linear_heads, cfg.linear_value_dim) if cfg.linear_layers else 0
        real = None if valid is None else jnp.broadcast_to(valid, (B, T))
        live = jnp.ones((B,), bool) if valid is None else lengths > 0

        def linear_fn(carry, x, layer, li):
            kc, vc, st, cv = carry
            tail = cv[li, slots].reshape(B, cfg.linear_conv_width - 1, cfg.linear_channels)
            h = pre_norm(cfg, layer, "attn_norm", x)
            # a row without real tokens reads its own tail back: nothing of it moves
            q, k, v, g, beta, tail = linear_inputs(cfg, layer, h, tail, None if valid is None else lengths)
            if T == 1:
                o, st = gated_delta_decode(st, li, slots, live, q[:, 0], k[:, 0], v[:, 0], jnp.exp(g[:, 0]),
                                           beta[:, 0], kernel=use_decode_kernel)
                o = o[:, None]
            else:
                o, S = gated_delta_chunked(unpack_state(st[li, slots], G), q, k, v, g, beta, real)
                st = st.at[li, slots].set(pack_state(S, G))
            cv = cv.at[li, slots].set(tail.reshape(B, -1).astype(cv.dtype))
            return (kc, vc, st, cv), linear_out(cfg, layer, x, h, o)

        def conv_fn(carry, x, layer, ci):
            kc, vc, st, cv = carry
            tail = cv[ci, slots].reshape(B, cfg.conv_width - 1, cfg.d_model)
            h = pre_norm(cfg, layer, "attn_norm", x)
            # a row without real tokens reads its own tail back: nothing of it moves
            x, tail = conv_mixer(cfg, layer, x, h, tail, None if valid is None else lengths)
            cv = cv.at[ci, slots].set(tail.reshape(B, -1).astype(cv.dtype))
            return (kc, vc, st, cv), x

        def full_fn(carry, x, layer, fi):
            kc, vc, st, cv = carry
            x, kc, vc = paged_attention_block(x, kc, vc, layer, full_kind(cfg), fi, None)
            return (kc, vc, st, cv), x

        def latent_fn(carry, x, layer, fi):
            """A latent layer's attention branch against its one pool (the
            carry's first place; no V): the call's rows written, the queries
            absorbed, the walk over the rows they can see."""
            from ray_tpu.ops.decode_attention import latent_paged_decode, latent_paged_prefill

            lc, _, st, cv = carry
            h = pre_norm(cfg, layer, "attn_norm", x)
            q, row = latent_qkv(cfg, layer, h)
            lanes = lc.shape[-1]
            row = jnp.pad(row, ((0, 0), (0, 0), (0, lanes - row.shape[-1])))
            lc, = write_rows(fi, (lc,), (row,))
            qa = latent_absorb(cfg, layer, q, lanes)
            if use_decode_kernel and T == 1:
                o = latent_paged_decode(qa[:, 0], lc, block_tables, starts + 1, fi, rank=cfg.latent_rank,
                                        sm_scale=latent_scale(cfg))[:, None]
            else:
                o = latent_paged_prefill(qa, lc, block_tables, starts, lengths, fi, rank=cfg.latent_rank,
                                         sm_scale=latent_scale(cfg), use_kernel=bool(use_decode_kernel))
            return (lc, None, st, cv), latent_out(cfg, layer, x, o.astype(x.dtype))

        latent = cfg.attn_kind == "latent"
        carry = (cache["latent"], None) if latent else (cache["k"], cache["v"])
        mixers = {"linear": linear_fn, "conv": conv_fn, "full": full_fn, "latent": latent_fn}
        (ks, vs, st, cv), x, counts, extra = hybrid_scan(
            cfg, params, carry + (cache.get("state"), cache["conv"]), x, mixers, valid=valid, kernel=use_decode_kernel,
            routes=routes)
        logits = unembed(cfg, params, x) if with_logits else None
        pools = {"latent": ks} if latent else {"k": ks, "v": vs}
        if routes:
            # layer order: the expert layers before the scanned periods, the periods' (a place that
            # was a dense layer's in its period reads -1 and is no expert layer), those behind
            nd, (lead, P, R) = cfg.num_dense_layers, cfg.plan
            before = max(lead - nd, 0)
            scanned = counts.reshape(R * P, B * T, -1)[max(nd - lead, 0):]
            outside = extra if extra is not None else scanned[:0]
            moe = {"routes": jnp.concatenate([outside[:before], scanned, outside[before:]])}
        elif counts is None:
            moe = {"assignments": jnp.zeros((1,), jnp.int32), "pairs_hit": jnp.zeros((), jnp.int32)}
        else:  # [periods, layers a period, experts held] of the valid tokens
            moe = {"assignments": counts.sum((0, 1)), "pairs_hit": jnp.sum(counts > 0).astype(jnp.int32)}
            if extra is not None:  # the expert layers before and behind the scanned periods
                moe = {"assignments": moe["assignments"] + extra.sum(0),
                       "pairs_hit": moe["pairs_hit"] + jnp.sum(extra > 0).astype(jnp.int32)}
            if cfg.experts_held is not None:
                # (token, choice) pairs routed over all the experts, here or elsewhere: the counts' denominator
                tokens_ = B * T if valid is None else jnp.broadcast_to(valid, (B, T)).sum()
                moe["routed"] = jnp.asarray(tokens_ * cfg.expert_top_k * cfg.expert_layers, jnp.int32)
        return logits, {**pools, **({} if st is None else {"state": st}), "conv": cv}, moe

    carry = (x, cache["k"], cache["v"])
    assignments = jnp.zeros((max(cfg.num_experts, 1),), jnp.int32)
    pairs_hit = jnp.zeros((), jnp.int32)
    chosen = []
    for stack, first, last in layer_stacks(cfg, params):
        xs = (scanned_leaves(cfg, stack), layer_kinds(cfg, first, last), jnp.arange(first, last, dtype=jnp.int32))
        if layer_scales is not None:
            xs = (stack, layer_scales) + xs[1:]
        carry, counts = jax.lax.scan(partial(layer_fn, stack, first), carry, xs)
        if routes:
            chosen += [] if counts is None else [counts]
        elif counts is not None:  # a dropless expert stack: [layers, E] assignments of the valid tokens
            assignments = assignments + counts.sum(0)
            pairs_hit = pairs_hit + jnp.sum(counts > 0).astype(jnp.int32)
    x, ks, vs = carry
    logits = unembed(cfg, params, x) if with_logits else None
    if routes:
        return logits, {"k": ks, "v": vs}, {"routes": jnp.concatenate(chosen)}
    return logits, {"k": ks, "v": vs}, {"assignments": assignments, "pairs_hit": pairs_hit}


def paged_forward_with_cache(*args, **kwargs) -> Tuple[jax.Array, KVCache]:
    """:func:`paged_forward_counted` without the counts: (logits, cache)."""
    return paged_forward_counted(*args, **kwargs)[:2]


def paged_decode_step(
    cfg: TransformerConfig,
    params: Dict[str, Any],
    cache: KVCache,
    tokens: jax.Array,        # [B] the previously sampled token per row
    positions: jax.Array,     # [B] the absolute position to write it at
    block_tables: jax.Array,  # [B, M]
    **fw_kwargs,
) -> Tuple[jax.Array, KVCache]:
    """One paged decode step: (logits [B, V], cache)."""
    logits, cache = paged_forward_with_cache(
        cfg, params, cache, block_tables, tokens[:, None], positions[:, None], **fw_kwargs
    )
    return logits[:, 0], cache


BlockState = Dict[str, jax.Array]


def open_blocks(cfg: TransformerConfig, steps: jax.Array, known: Optional[jax.Array] = None,
                known_tokens: Optional[jax.Array] = None) -> BlockState:
    """The state a row's block starts in: the first ``known[b]`` positions
    hold ``known_tokens[b]`` (the tail of a prompt whose length is no
    multiple of the block), the rest ``cfg.mask_token_id`` and are masked;
    ``steps[b]`` denoising steps lie ahead. Which positions are masked is
    state of its own, never a comparison of ids: a prompt may hold the mask
    id as an ordinary token.

    ``{"toks": int32[B, Bk], "masked": bool[B, Bk], "unmasked_at":
    int32[B, Bk] (the denoising step, from 1, at which a position took its
    token; 0: known from the start), "steps": int32[B], "steps_left":
    int32[B]}``."""
    B, Bk = steps.shape[0], cfg.block
    masked = jnp.ones((B, Bk), bool) if known is None else jnp.arange(Bk)[None, :] >= known[:, None]
    toks = jnp.full((B, Bk), cfg.mask_token_id, jnp.int32)
    if known_tokens is not None:
        toks = jnp.where(masked, toks, known_tokens.astype(jnp.int32))
    steps = steps.astype(jnp.int32)
    return {"toks": toks, "masked": masked, "unmasked_at": jnp.zeros((B, Bk), jnp.int32),
            "steps": steps, "steps_left": steps}


def select_rows(rows: jax.Array, new: BlockState, old: BlockState) -> BlockState:
    """``new``'s state in the rows ``rows`` [B] marks, ``old``'s elsewhere."""
    return {k: jnp.where(rows.reshape((-1,) + (1,) * (old[k].ndim - 1)), new[k], old[k]) for k in old}


def unmask_step(cfg: TransformerConfig, logits: jax.Array, state: BlockState, sample=None) -> BlockState:
    """One denoising step's unmasking (``low_confidence_static``): at each
    masked position the candidate is ``sample(logits [B*Bk, V])`` (default:
    the arg-max) over the vocabulary without the mask id, its confidence the
    softmax probability of the candidate; the ``ceil(masked left / steps
    left)`` masked positions of highest confidence (ties: the lowest
    position) take their candidates and never change again. A row with no
    mask left is left as it is."""
    B, Bk, V = logits.shape
    logits = logits.at[..., cfg.mask_token_id].set(-1e30)  # a candidate is never a mask
    flat = logits.reshape(B * Bk, V)
    cand = (jnp.argmax(flat, -1) if sample is None else sample(flat)).astype(jnp.int32).reshape(B, Bk)
    picked = jnp.take_along_axis(logits, cand[..., None], axis=-1)[..., 0]
    conf = jnp.exp(picked - jax.nn.logsumexp(logits, axis=-1))
    masked = state["masked"]
    left = masked.sum(-1).astype(jnp.int32)
    n = -(-left // jnp.maximum(state["steps_left"], 1))
    # rank 0: the most confident masked position, the lowest of equals (a stable sort)
    order = jnp.argsort(-jnp.where(masked, conf, -1.0), axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1)
    take = masked & (rank < n[:, None])
    step_no = state["steps"] - state["steps_left"] + 1
    return {
        "toks": jnp.where(take, cand, state["toks"]),
        "masked": masked & ~take,
        "unmasked_at": jnp.where(take, step_no[:, None], state["unmasked_at"]),
        "steps": state["steps"],
        "steps_left": jnp.maximum(state["steps_left"] - (left > 0), 0),
    }


def paged_block_step(
    cfg: TransformerConfig,
    params: Dict[str, Any],
    cache: KVCache,
    block_tables: jax.Array,  # [B, M]
    state: BlockState,        # :func:`open_blocks`
    positions: jax.Array,     # [B] the position of each row's block's first token
    *,
    live: Optional[jax.Array] = None,  # [B] bool: False = an idle row, nothing written or counted
    sample=None,
    **fw_kwargs,
) -> Tuple[jax.Array, KVCache, BlockState, Dict[str, jax.Array], Dict[str, jax.Array]]:
    """One decode step of generation by diffusion over blocks: a forward of
    every row's ``cfg.block_length`` positions as they stand (mask ids among
    them) over everything committed before them plus the block itself, then
    :func:`unmask_step`. Returns (logits [B, Bk, V], cache, the state after
    the step, what the step finished, the expert layers' counts).

    The step writes the block's K/V through the table before it attends, as a
    prefill chunk does. While a mask is left those rows are tentative: the
    next step of the same block overwrites them. A row that came in with no
    mask left runs the **commit**: the same forward on the finished block,
    whose K/V are therefore final; its logits decide nothing, its tokens are
    handed back (``{"committed": bool[B], "toks": int32[B, Bk], "unmasked_at":
    int32[B, Bk]}``) and its state reopens all masked for the block that
    follows. Nothing before a block's first position is ever written."""
    B, Bk = state["toks"].shape
    commit = ~state["masked"].any(-1)
    pos = positions[:, None] + jnp.arange(Bk, dtype=positions.dtype)[None, :]
    valid = None if live is None else jnp.broadcast_to(live[:, None], (B, Bk))
    logits, cache, moe = paged_forward_counted(cfg, params, cache, block_tables, state["toks"], pos, valid=valid, **fw_kwargs)
    done = {"committed": commit, "toks": state["toks"], "unmasked_at": state["unmasked_at"]}
    nxt = select_rows(commit, open_blocks(cfg, state["steps"]), unmask_step(cfg, logits, state, sample))
    return logits, cache, nxt, done, moe


def _single_device_params(params) -> bool:
    """True iff on TPU and the embed param is a CONCRETE single-device
    array (tracers and multi-device shardings return False)."""
    if not backend.on_tpu():
        return False
    emb = params.get("embed") if isinstance(params, dict) else None
    if not isinstance(emb, jax.Array) or isinstance(emb, jax.core.Tracer):
        return False
    try:
        return len(emb.sharding.device_set) == 1
    except Exception:
        return False


def prefill(
    cfg: TransformerConfig,
    params: Dict[str, Any],
    cache: KVCache,
    tokens: jax.Array,          # [B, Tp] right-padded prompts
    lengths: jax.Array,         # [B] true prompt lengths (>= 1)
    **fw_kwargs,
) -> Tuple[jax.Array, KVCache]:
    """Fill the cache from position 0 and return the last real token's
    logits per row: (logits [B, V], cache)."""
    B, Tp = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(Tp)[None, :], (B, Tp))
    if "use_prefill_kernel" not in fw_kwargs:
        # positions provably start at 0 here, so the flash path is safe —
        # but ONLY auto-enable when params are concretely single-device
        # (a pallas_call can't lower against GSPMD-sharded operands; under
        # jit tracing or multi-device shardings, stay on the einsum path)
        fw_kwargs["use_prefill_kernel"] = _single_device_params(params)
    logits, cache = forward_with_cache(cfg, params, cache, tokens, positions, **fw_kwargs)
    last = jnp.take_along_axis(logits, (lengths - 1)[:, None, None], axis=1)[:, 0]
    return last, cache


def decode_step(
    cfg: TransformerConfig,
    params: Dict[str, Any],
    cache: KVCache,
    tokens: jax.Array,     # [B] the previously sampled token per row
    positions: jax.Array,  # [B] the absolute position to write it at
    **fw_kwargs,
) -> Tuple[jax.Array, KVCache]:
    """One decode step: (logits [B, V], cache)."""
    logits, cache = forward_with_cache(
        cfg, params, cache, tokens[:, None], positions[:, None], **fw_kwargs
    )
    return logits[:, 0], cache


def filter_top_k_top_p(
    logits: jax.Array, top_k: Optional[int] = None, top_p: Optional[float] = None
) -> jax.Array:
    """Mask logits outside the top-k set / top-p nucleus to -inf. Shared by
    :func:`sample_logits` and the serving engine so the two sampling paths
    can't drift."""
    if top_k is not None and top_k < logits.shape[-1]:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -1e30, logits)
    if top_p is not None and top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest prefix with cumulative mass >= top_p (always >= 1 token)
        keep = cum - probs < top_p
        cutoff = jnp.min(jnp.where(keep, sorted_logits, jnp.inf), axis=-1, keepdims=True)
        logits = jnp.where(logits < cutoff, -1e30, logits)
    return logits


def sample_logits(
    logits: jax.Array,  # [B, V] f32
    key: jax.Array,
    *,
    temperature: float = 1.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
) -> jax.Array:
    """Greedy (temperature == 0) or temperature/top-k/top-p sampling. The
    knobs are Python statics, so each configuration is its own jit cache
    entry — the decode loop stays branch-free."""
    if temperature == 0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = filter_top_k_top_p(logits / temperature, top_k, top_p)
    return jax.random.categorical(key, logits).astype(jnp.int32)


def generate(
    cfg: TransformerConfig,
    params: Dict[str, Any],
    prompt: jax.Array,                       # [B, Tp] right-padded
    prompt_lengths: Optional[jax.Array] = None,  # [B]; defaults to full rows
    *,
    max_new_tokens: int,
    key: Optional[jax.Array] = None,
    temperature: float = 1.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    eos_id: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Batched autoregressive generation; jit-compatible end to end.

    Returns (tokens [B, Tp + max_new_tokens] with each row = prompt followed
    by its generated continuation, lengths [B] = prompt + generated counts).
    Rows that hit ``eos_id`` stop counting (the eos itself is included) and
    pad with ``eos_id`` thereafter.
    """
    B, Tp = prompt.shape
    if prompt_lengths is None:
        prompt_lengths = jnp.full((B,), Tp, jnp.int32)
    if key is None:
        key = jax.random.key(0)
    total = Tp + max_new_tokens
    cache = init_cache(cfg, B, total)
    last_logits, cache = prefill(cfg, params, cache, prompt, prompt_lengths)
    pad_tok = eos_id if eos_id is not None else 0
    keys = jax.random.split(key, max_new_tokens)

    def _sample(logits, k, done):
        tok = sample_logits(logits, k, temperature=temperature, top_k=top_k, top_p=top_p)
        tok = jnp.where(done, pad_tok, tok)
        new_done = done | (tok == eos_id) if eos_id is not None else done
        return tok, new_done

    # first token comes straight from the prefill logits; each scan step then
    # decodes exactly one forward per sampled token (no trailing wasted step)
    tok0, done0 = _sample(last_logits, keys[0], jnp.zeros((B,), bool))

    def body(carry, step_key):
        cache, tok, pos, done = carry
        logits, cache = decode_step(cfg, params, cache, tok, pos)
        nxt, new_done = _sample(logits, step_key, done)
        return (cache, nxt, pos + 1, new_done), (nxt, done)

    init = (cache, tok0, prompt_lengths, done0)
    if max_new_tokens > 1:
        (_, _, _, _), (rest, rest_was_done) = jax.lax.scan(body, init, keys[1:])
        toks = jnp.concatenate([tok0[None], rest], axis=0).T          # [B, max_new]
        was_done = jnp.concatenate(
            [jnp.zeros((1, B), bool), rest_was_done], axis=0
        ).T
    else:
        toks = tok0[:, None]
        was_done = jnp.zeros((B, 1), bool)
    gen_counts = jnp.sum(~was_done, axis=1).astype(jnp.int32)

    out = jnp.zeros((B, total), jnp.int32)
    out = jax.lax.dynamic_update_slice(out, prompt.astype(jnp.int32), (0, 0))
    # place each row's continuation right after its true prompt
    out = jax.vmap(lambda o, t, s: jax.lax.dynamic_update_slice(o, t, (s,)))(
        out, toks, prompt_lengths
    )
    return out, prompt_lengths + gen_counts
