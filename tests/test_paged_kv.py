"""Paged KV cache + chunked prefill tests.

Five contracts:
- allocator: typed exhaustion shed, no fragmentation across churn, double
  frees raise (leak checks must see corruption, not absorb it)
- ops: paged_decode_attention over one layer of the stacked pool == dense
  decode_attention through a shuffled block table (XLA fallback and
  interpret-mode Pallas kernel; MHA, GQA, head_dim 64 and 128)
- runner: the paged pool carried through the layer loop gives the dense
  cache's logits bit for bit on the CPU
- engine identity: the engine is token-identical to one-shot ``generate()``
  under greedy decoding, and chunked prefill is token-identical to one-shot
  for every chunk width
- leak checks: every release path (finish, eos, deadline shed, disconnect
  evict, prefill crash, loop crash) returns ALL blocks to the pool
"""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_reference import greedy_reference
from ray_tpu.exceptions import DeadlineExceededError, OverloadedError
from ray_tpu.models import TransformerConfig, init_params
from ray_tpu.serve.kv_blocks import BlockAllocator
from ray_tpu.serve.llm import LLMEngine

CFG = TransformerConfig(
    vocab_size=89, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=64,
    attention="dense", dtype=jnp.float32,
)
_reference = functools.partial(greedy_reference, CFG)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.key(11))


def _paged(params, **kw):
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("max_seq_len", 64)
    return LLMEngine(CFG, params, **kw)


def _wait(pred, timeout=60):
    deadline = time.time() + timeout
    while not pred() and time.time() < deadline:
        time.sleep(0.005)
    assert pred()


def _assert_no_leak(eng):
    """Quiesced-engine leak check under prefix caching: every held page is
    accounted for by the prefix cache, and flushing it empties the pool."""
    st = eng.stats()
    assert st["kv_blocks_in_use"] == st["prefix_cache_blocks"]
    eng.store.flush_prefix_cache()
    st = eng.stats()
    assert st["kv_blocks_in_use"] == 0 and st["prefix_cache_blocks"] == 0


# --------------------------------------------------------------------------
# BlockAllocator
# --------------------------------------------------------------------------
def test_allocator_page_zero_reserved():
    a = BlockAllocator(8)
    assert a.capacity == 7
    got = a.alloc(7)
    assert 0 not in got and sorted(got) == list(range(1, 8))
    assert a.free_blocks == 0 and a.used_blocks == 7
    a.free(got)
    assert a.free_blocks == 7 and a.used_blocks == 0


def test_allocator_too_small_raises():
    with pytest.raises(ValueError):
        BlockAllocator(1)


def test_allocator_exhaustion_is_typed_shed():
    a = BlockAllocator(4)
    held = a.alloc(2)
    with pytest.raises(OverloadedError) as exc:
        a.alloc(2)
    assert exc.value.layer == "engine" and exc.value.reason == "kv_blocks"
    assert exc.value.retry_after_s > 0
    # the failed alloc took nothing
    assert a.free_blocks == 1 and a.used_blocks == 2
    a.free(held)


def test_allocator_double_free_raises():
    a = BlockAllocator(4)
    got = a.alloc(1)
    a.free(got)
    with pytest.raises(ValueError):
        a.free(got)
    with pytest.raises(ValueError):
        a.free([0])  # the garbage page is never held


def test_allocator_no_fragmentation_across_churn():
    """1k admit/release cycles of varying sizes: the pool always refills to
    capacity and a full-capacity alloc still succeeds afterwards (pages are
    interchangeable, so there is nothing to fragment)."""
    a = BlockAllocator(17)
    rng = np.random.default_rng(7)
    for i in range(1000):
        sizes = []
        holds = []
        while a.free_blocks > 0:
            n = min(int(rng.integers(1, 5)), a.free_blocks)
            holds.append(a.alloc(n))
            sizes.append(n)
        for h in rng.permutation(len(holds)):
            a.free(holds[h])
        assert a.free_blocks == a.capacity, f"leak after cycle {i}"
    full = a.alloc(a.capacity)
    assert len(full) == a.capacity
    a.free(full)


# --------------------------------------------------------------------------
# paged_decode_attention op: one layer of a stacked, lane-dense pool
# --------------------------------------------------------------------------
# (H, Hkv, D): the cells' MHA shape, two GQA groupings, an aligned head
OP_SHAPES = {"mha_d64": (4, 4, 64), "gqa8_2_d64": (8, 2, 64), "gqa4_1_d128": (4, 1, 128)}
op_shapes = pytest.mark.parametrize("shape", list(OP_SHAPES.values()), ids=list(OP_SHAPES))


def _paged_op_case(shape, seed=0, S=64, bs=16, layers=3):
    """q, a 3-layer pool ``[L, N, bs, Hkv*D]`` whose layers hold different
    data, a shuffled table, lengths with an empty row, a partial last page
    and a full row — and, per layer, the dense decode kernel's answer over
    that layer's gathered pages."""
    from ray_tpu.ops.decode_attention import decode_attention

    H, Hkv, D = shape
    rng = np.random.default_rng(seed)
    lengths = jnp.asarray([5, S, 17, 0], jnp.int32)
    B, M = len(lengths), S // bs
    N = B * M + 1
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    k_pool = jnp.asarray(rng.normal(size=(layers, N, bs, Hkv * D)), jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=(layers, N, bs, Hkv * D)), jnp.float32)
    # shuffled table: physical placement must not matter
    perm = rng.permutation(np.arange(1, N))
    bt = jnp.asarray(perm[: B * M].reshape(B, M).astype(np.int32))

    def dense(pool, layer):
        return jnp.transpose(pool[layer][bt].reshape(B, S, Hkv, D), (0, 2, 1, 3))

    refs = [decode_attention(q, dense(k_pool, l), dense(v_pool, l), lengths) for l in range(layers)]
    return q, k_pool, v_pool, bt, lengths, refs


@op_shapes
def test_paged_decode_attention_matches_dense_xla(shape):
    from ray_tpu.ops.decode_attention import paged_decode_attention

    q, kp, vp, bt, lengths, refs = _paged_op_case(shape)
    out = paged_decode_attention(q, kp, vp, bt, lengths, 1, use_kernel=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(refs[1]), atol=1e-5)


@op_shapes
def test_paged_decode_attention_kernel_interpret(shape):
    from ray_tpu.ops.decode_attention import paged_decode_attention

    q, kp, vp, bt, lengths, refs = _paged_op_case(shape, seed=3)
    out = paged_decode_attention(q, kp, vp, bt, lengths, 1, use_kernel=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(refs[1]), atol=1e-5)
    xla = paged_decode_attention(q, kp, vp, bt, lengths, 1, use_kernel=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(xla), atol=1e-5)
    assert not np.asarray(out[3]).any()  # lengths == 0: zeros, not garbage-V means


@pytest.mark.parametrize("use_kernel", [True, False], ids=["kernel", "xla"])
@pytest.mark.parametrize("layer", [0, 1, 2])
def test_paged_decode_attention_reads_the_named_layer(layer, use_kernel):
    """The layer index steers the page DMAs: every layer of the pool gives
    its own answer (traced, as the runner's layer loop passes it), and no
    other layer's."""
    from ray_tpu.ops.decode_attention import paged_decode_attention

    q, kp, vp, bt, lengths, refs = _paged_op_case(OP_SHAPES["gqa8_2_d64"], seed=5)
    fn = jax.jit(lambda l: paged_decode_attention(q, kp, vp, bt, lengths, l, use_kernel=use_kernel))
    out = np.asarray(fn(jnp.int32(layer)))
    np.testing.assert_allclose(out, np.asarray(refs[layer]), atol=1e-5)
    for other in set(range(3)) - {layer}:
        assert np.abs(out - np.asarray(refs[other]))[:3].max() > 1e-2


# the kernel walks a row's visible pages in groups of 128 tokens: lengths at
# every edge of a page and of a group, the full table, and an empty row
EDGE_LENGTHS = (0, 1, 15, 16, 17, 127, 128, 129, 256)
EDGE_SHAPES = {"mha_d64": (4, 4, 64), "gqa32_4_d128": (32, 4, 128)}
edge_shapes = pytest.mark.parametrize("shape", list(EDGE_SHAPES.values()), ids=list(EDGE_SHAPES))


def _edge_case(shape, window, seed, *, poison=False):
    """A row a length of ``EDGE_LENGTHS`` over 16 pages of 16 tokens; the
    last two rows' first 8 pages are the row before theirs' (a shared
    prefix). ``poison``: every table entry a query at that length and
    window may not see names a page of NaN, where the clean table keeps the
    row's own page."""
    from ray_tpu.ops.decode_attention import window_start

    H, Hkv, D = shape
    bs, M, B = 16, 16, len(EDGE_LENGTHS)
    rng = np.random.default_rng(seed)
    N = B * M + 2
    k_pool, v_pool = (rng.normal(size=(2, N, bs, Hkv * D)).astype(np.float32) for _ in range(2))
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    bt = rng.permutation(np.arange(1, N - 1))[: B * M].reshape(B, M).astype(np.int32)
    bt[-2:, :8] = bt[-3, :8]
    lengths = np.asarray(EDGE_LENGTHS, np.int32)
    if poison:
        k_pool[:, N - 1] = v_pool[:, N - 1] = np.nan
        first = np.asarray(window_start(lengths, window or 0)) // bs
        page = np.arange(M)[None]
        bt = np.where((page < first[:, None]) | (page >= -(-lengths // bs)[:, None]), N - 1, bt).astype(np.int32)
    return q, jnp.asarray(k_pool), jnp.asarray(v_pool), jnp.asarray(bt), jnp.asarray(lengths)


# no window argument; none (0); one inside the first group, inside a page, for
# the long rows (129 -> position 29, 256 -> 156) and longer than the short ones;
# one on a page's edge; one longer than every row
@pytest.mark.parametrize("window", [None, 0, 100, 112, 1000])
@edge_shapes
def test_paged_decode_kernel_at_page_and_group_edges(shape, window):
    from ray_tpu.ops.decode_attention import paged_decode_attention

    q, kp, vp, bt, lengths = _edge_case(shape, window, seed=11)
    kw = {} if window is None else {"window": jnp.int32(window)}
    got = paged_decode_attention(q, kp, vp, bt, lengths, jnp.int32(1), use_kernel=True, **kw)
    want = paged_decode_attention(q, kp, vp, bt, lengths, jnp.int32(1), use_kernel=False, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    assert not np.asarray(got[0]).any()  # lengths == 0
    if window == 100:
        full = paged_decode_attention(q, kp, vp, bt, lengths, jnp.int32(1), use_kernel=True)
        assert np.abs(np.asarray(full) - np.asarray(got))[5:].max() > 1e-3  # the long rows lost keys
        np.testing.assert_array_equal(np.asarray(full)[:5], np.asarray(got)[:5])  # the short rows none


@pytest.mark.parametrize("window", [None, 100])
@edge_shapes
def test_paged_decode_kernel_never_computes_on_a_page_no_query_may_see(shape, window):
    """Every table entry past the row's last page and behind its window
    names a page of NaN: the kernel's answer is finite and is the
    reference's on the clean table, so those pages are neither fetched nor
    multiplied by a zero weight."""
    from ray_tpu.ops.decode_attention import paged_decode_attention

    kw = {} if window is None else {"window": jnp.int32(window)}
    q, kp, vp, poisoned, lengths = _edge_case(shape, window, seed=13, poison=True)
    clean = _edge_case(shape, window, seed=13)[3]
    assert (np.asarray(poisoned) != np.asarray(clean)).sum() > 60
    got = np.asarray(paged_decode_attention(q, kp, vp, poisoned, lengths, jnp.int32(0), use_kernel=True, **kw))
    assert np.isfinite(got).all()
    want = paged_decode_attention(q, kp, vp, clean, lengths, jnp.int32(0), use_kernel=False, **kw)
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("use_kernel", [True, False], ids=["kernel", "xla"])
def test_a_row_whose_table_starts_at_the_garbage_page_reads_as_empty(use_kernel):
    """The engine's idle slots decode through all-zero tables at whatever
    length their last tenant left: such a row gives zeros and leaves its
    neighbours' answers alone."""
    from ray_tpu.ops.decode_attention import paged_decode_attention

    q, kp, vp, bt, lengths, refs = _paged_op_case(OP_SHAPES["mha_d64"], seed=7)
    idle = bt.at[1].set(0)  # the full row: 64 stale tokens on page 0
    out = np.asarray(paged_decode_attention(q, kp, vp, idle, lengths, 1, use_kernel=use_kernel))
    assert not out[1].any()
    np.testing.assert_allclose(out[[0, 2, 3]], np.asarray(refs[1])[[0, 2, 3]], atol=1e-5)


def test_a_length_past_the_tables_capacity_walks_the_table_and_no_further():
    """A finished row's position may run past its table (the engine's
    post-finish overshoot): the kernel reads the table's pages, as the
    reference does, and no entry beyond the row."""
    from ray_tpu.ops.decode_attention import paged_decode_attention

    q, kp, vp, bt, lengths, _ = _paged_op_case(OP_SHAPES["gqa8_2_d64"], seed=9)
    over = lengths.at[1].set(64 + 300).at[3].set(64 + 1)  # capacity: 4 pages of 16
    got = paged_decode_attention(q, kp, vp, bt, over, 2, use_kernel=True)
    want = paged_decode_attention(q, kp, vp, bt, over, 2, use_kernel=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    at_capacity = paged_decode_attention(q, kp, vp, bt, over.at[1].set(64).at[3].set(64), 2, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(at_capacity))


# --------------------------------------------------------------------------
# the model runner: paged pool == dense cache, in logits
# --------------------------------------------------------------------------
def _runner_cfg(shape):
    H, Hkv, D = shape
    return TransformerConfig(
        vocab_size=61, d_model=H * D, n_layers=3, n_heads=H, n_kv_heads=Hkv, d_ff=64,
        attention="dense", dtype=jnp.float32,
    )


def _dense_and_paged_logits(cfg, params, *, use_decode_kernel=False, layer_scales=None):
    """Two ragged prompts prefilled in 8-wide pieces (the last one padded; the
    paged side masks it with ``valid``) and decoded 3 tokens, call for call
    through the dense cache and through a shuffled pool of 4-token pages."""
    from ray_tpu.models.generation import (
        forward_with_cache, init_cache, init_paged_cache, paged_decode_step,
        paged_forward_with_cache,
    )

    rng = np.random.default_rng(1)
    chunk, steps, bs = 8, 3, 4
    lens = [11, 6]
    B, S = len(lens), 32
    M = S // bs
    prompts = [rng.integers(1, cfg.vocab_size, n) for n in lens]
    nxt = jnp.asarray(rng.integers(1, cfg.vocab_size, (steps, B)), jnp.int32)
    pos = jnp.asarray(lens, jnp.int32)
    kw = {"layer_scales": layer_scales}
    dense, dense_out = init_cache(cfg, B, S), []
    pool, paged_out = init_paged_cache(cfg, B * M + 1, bs), []
    bt = jnp.asarray(rng.permutation(np.arange(1, B * M + 1)).reshape(B, M).astype(np.int32))

    for b, prompt in enumerate(prompts):
        row = init_cache(cfg, 1, S)
        for start in range(0, len(prompt), chunk):
            piece = prompt[start:start + chunk]
            toks = np.zeros((1, chunk), np.int64)
            toks[0, : len(piece)] = piece
            positions = (start + jnp.arange(chunk))[None]
            d_logits, row = forward_with_cache(cfg, params, row, jnp.asarray(toks), positions, **kw)
            p_logits, pool = paged_forward_with_cache(
                cfg, params, pool, bt[b:b + 1], jnp.asarray(toks), positions,
                valid=(jnp.arange(chunk) < len(piece))[None], use_decode_kernel=False, **kw)
        dense = {kk: dense[kk].at[:, b].set(row[kk][:, 0]) for kk in dense}
        dense_out.append(d_logits[0, len(piece) - 1])
        paged_out.append(p_logits[0, len(piece) - 1])
    for t in range(steps):
        d_logits, dense = forward_with_cache(
            cfg, params, dense, nxt[t][:, None], (pos + t)[:, None], use_decode_kernel=False, **kw)
        p_logits, pool = paged_decode_step(
            cfg, params, pool, nxt[t], pos + t, bt, use_decode_kernel=use_decode_kernel, **kw)
        dense_out.append(d_logits[:, 0])
        paged_out.append(p_logits)
    assert np.asarray(pool["k"][:, 0]).any()  # the padded tail's writes went to the garbage page
    return dense_out, paged_out


@op_shapes
def test_paged_runner_bit_identical_to_dense_cache(shape):
    """Chunked prefill (with a masked, padded tail) and decode through the
    carried pool give the dense cache's logits bit for bit on the CPU."""
    cfg = _runner_cfg(shape)
    dense, paged = _dense_and_paged_logits(cfg, init_params(cfg, jax.random.key(2)))
    for d, p in zip(dense, paged):
        np.testing.assert_array_equal(np.asarray(d), np.asarray(p))


@op_shapes
def test_paged_runner_decode_kernel_matches_dense_cache(shape):
    """The same with single-token steps through the (interpret-mode) Pallas
    kernel reading the stacked pool at the loop's layer index."""
    cfg = _runner_cfg(shape)
    dense, paged = _dense_and_paged_logits(
        cfg, init_params(cfg, jax.random.key(2)), use_decode_kernel=True)
    for d, p in zip(dense, paged):
        np.testing.assert_allclose(np.asarray(d), np.asarray(p), atol=2e-4, rtol=2e-4)


def _chunked_prefill(cfg, params, *, use_decode_kernel, layer_scales=None):
    """A 27-token prompt in 8-wide chunks (the last padded, masked by
    ``valid``) at traced starts through one jitted program, over a shuffled
    pool of 4-token pages: every chunk's logits and the pool they leave."""
    from ray_tpu.models.generation import init_paged_cache, paged_forward_with_cache

    rng = np.random.default_rng(4)
    chunk, bs, M = 8, 4, 8
    prompt = rng.integers(1, cfg.vocab_size, 27)
    pool = init_paged_cache(cfg, 2 * M + 1, bs)
    bt = jnp.asarray(rng.permutation(np.arange(1, 2 * M + 1))[None, :M].astype(np.int32))

    @jax.jit
    def prefill(pool, toks, start, length):
        return paged_forward_with_cache(
            cfg, params, pool, bt, toks, start + jnp.arange(chunk)[None], valid=(jnp.arange(chunk) < length)[None],
            use_decode_kernel=use_decode_kernel, layer_scales=layer_scales)

    out = []
    for start in range(0, len(prompt), chunk):
        piece = prompt[start:start + chunk]
        toks = np.zeros((1, chunk), np.int32)
        toks[0, : len(piece)] = piece
        logits, pool = prefill(pool, jnp.asarray(toks), jnp.int32(start), jnp.int32(len(piece)))
        out.append(logits[0, : len(piece)])
    return jnp.concatenate(out), pool, prefill


@op_shapes
def test_paged_runner_prefill_kernel_matches_the_dense_lines(shape):
    """``use_decode_kernel=True`` sends a chunk (T > 1) through the
    (interpret-mode) paged prefill kernel: the dense lines' logits, and the
    pool it leaves holds the first layer's K/V bit for bit (they do not pass
    through attention) and the deeper layers' to rounding."""
    cfg = _runner_cfg(shape)
    params = init_params(cfg, jax.random.key(2))
    want, dense_pool, _ = _chunked_prefill(cfg, params, use_decode_kernel=False)
    got, kernel_pool, _ = _chunked_prefill(cfg, params, use_decode_kernel=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4, rtol=2e-4)
    for name in ("k", "v"):
        # but for the garbage page: the scatter sent the padded tail's rows there, the kernel's write
        # (``paged_write_rows``) copies no row bound for it, and what a padded row attends over means nothing
        np.testing.assert_array_equal(np.asarray(kernel_pool[name][0, 1:]), np.asarray(dense_pool[name][0, 1:]))
        np.testing.assert_allclose(np.asarray(kernel_pool[name][:, 1:]), np.asarray(dense_pool[name][:, 1:]), atol=2e-4, rtol=2e-4)
        assert np.asarray(dense_pool[name][:, 0]).any() and not np.asarray(kernel_pool[name][:, 0]).any()


def test_paged_runner_prefill_kernel_with_int8_layer_scales():
    """The int8 path's attention branch is the same: scales ride the layer
    loop's xs, the kernel reads the carried pool."""
    from ray_tpu.ops.quantization import quantize_layers

    cfg = _runner_cfg(OP_SHAPES["gqa8_2_d64"])
    params = init_params(cfg, jax.random.key(2))
    layers_q, scales = quantize_layers(params["layers"])
    qparams = {**params, "layers": layers_q}
    want, _, _ = _chunked_prefill(cfg, qparams, use_decode_kernel=False, layer_scales=scales)
    got, _, _ = _chunked_prefill(cfg, qparams, use_decode_kernel=True, layer_scales=scales)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("use", [False, True, None], ids=["False", "True", "default_off_the_chip"])
def test_use_decode_kernel_is_the_one_select_for_a_chunk_too(use):
    """``False`` keeps the dense lines for T > 1 (the benchmark's own calls
    pass it by keyword; the engine under a mesh does), ``True`` puts one
    Pallas call a layer stack into the chunk's program and no
    capacity-wide score tensor; the default asks ``on_tpu()``: off here."""
    cfg = _runner_cfg(OP_SHAPES["mha_d64"])
    _, _, prefill = _chunked_prefill(cfg, init_params(cfg, jax.random.key(2)), use_decode_kernel=use)
    from ray_tpu.models.generation import init_paged_cache

    text = str(jax.make_jaxpr(prefill)(init_paged_cache(cfg, 17, 4), jnp.zeros((1, 8), jnp.int32), jnp.int32(8), jnp.int32(8)))
    capacity_wide_scores = "f32[1,4,1,8,32]"  # [B, Hkv, n_rep, T, M * block_size]
    assert ("pallas_call" in text) == bool(use)
    assert (capacity_wide_scores in text) == (not use)


def test_paged_runner_int8_layer_scales_bit_identical_to_dense_cache():
    """The int8 path's scales ride the layer loop's xs beside the layers and
    the layer index; the pool still rides the carry."""
    from ray_tpu.ops.quantization import quantize_layers

    cfg = _runner_cfg(OP_SHAPES["gqa8_2_d64"])
    params = init_params(cfg, jax.random.key(2))
    layers_q, scales = quantize_layers(params["layers"])
    dense, paged = _dense_and_paged_logits(cfg, {**params, "layers": layers_q}, layer_scales=scales)
    for d, p in zip(dense, paged):
        np.testing.assert_array_equal(np.asarray(d), np.asarray(p))


def test_migration_blocks_round_trip_through_the_pool():
    """Exported pages keep the ticket's block format (heads named) and land
    in another pool's pages byte for byte, duplicates of the bucket padding
    included."""
    from ray_tpu.models.generation import export_paged_page, init_paged_cache, write_paged_pages

    cfg = _runner_cfg(OP_SHAPES["gqa8_2_d64"])
    bs, rng = 4, np.random.default_rng(4)
    shape = init_paged_cache(cfg, 6, bs)["k"].shape
    src = {kk: jnp.asarray(rng.normal(size=shape), jnp.float32) for kk in ("k", "v")}
    blocks = [export_paged_page(cfg, src, page) for page in (3, 1, 5)]
    assert blocks[0].shape == (2, cfg.n_layers, bs, cfg.kv_heads, cfg.head_dim)
    # head 1 of the block is the second Dh-wide run of the pool's row
    np.testing.assert_array_equal(
        np.asarray(blocks[0][0, :, :, 1]), np.asarray(src["k"][:, 3, :, cfg.head_dim:]))
    dst = write_paged_pages(
        init_paged_cache(cfg, 6, bs), jnp.stack(blocks + blocks[-1:]), jnp.asarray([2, 4, 1, 1]))
    for kk in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(dst[kk][:, [2, 4, 1]]), np.asarray(src[kk][:, [3, 1, 5]]))
        assert not np.asarray(dst[kk][:, [0, 3, 5]]).any()
    for got, want in zip((export_paged_page(cfg, dst, page) for page in (2, 4, 1)), blocks):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_paged_cache_spec_shards_heads_not_page_tokens():
    """Under a mesh the pool splits over KV heads (the leading factor of its
    minor axis), each device holding whole heads of every page; the runner
    keeps that sharding on the cache it returns and computes the same
    logits as unsharded."""
    from jax.sharding import Mesh, NamedSharding

    from ray_tpu.models.generation import (
        init_paged_cache, paged_cache_spec, paged_forward_with_cache)

    cfg = _runner_cfg(OP_SHAPES["gqa8_2_d64"])
    params = init_params(cfg, jax.random.key(2))
    bs, M = 4, 4
    pool = init_paged_cache(cfg, M + 1, bs)
    bt = jnp.arange(1, M + 1, dtype=jnp.int32)[None]
    toks = jnp.arange(1, 9, dtype=jnp.int32)[None]
    pos = jnp.arange(8)[None]
    ref_logits, ref_pool = paged_forward_with_cache(cfg, params, pool, bt, toks, pos)

    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    sharding = NamedSharding(mesh, paged_cache_spec("tp"))
    sharded = {kk: jax.device_put(v, sharding) for kk, v in pool.items()}
    shard = sharded["k"].addressable_shards[0].data.shape
    assert shard == (cfg.n_layers, M + 1, bs, cfg.head_dim)  # one of two heads; whole pages
    logits, out = jax.jit(
        lambda c: paged_forward_with_cache(cfg, params, c, bt, toks, pos), out_shardings=(None, sharding)
    )(sharded)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits), atol=1e-5)
    for kk in ("k", "v"):
        np.testing.assert_allclose(np.asarray(out[kk]), np.asarray(ref_pool[kk]), atol=1e-5)
        # device 0 holds head 0 of every token of every page
        np.testing.assert_allclose(
            np.asarray(out[kk].addressable_shards[0].data),
            np.asarray(ref_pool[kk][..., : cfg.head_dim]), atol=1e-5)


# --------------------------------------------------------------------------
# engine identity: engine == generate(), chunked == one-shot
# --------------------------------------------------------------------------
PROMPTS = [[3, 5, 7, 11, 13], [2] * 17, list(range(1, 31)), [8, 9]]


def test_paged_engine_token_identical_to_generate(params):
    paged = _paged(params)
    try:
        ref = [_reference(params, p, 8) for p in PROMPTS]
        got = [f.result(timeout=120) for f in
               [paged.submit(p, max_tokens=8) for p in PROMPTS]]
        assert got == ref
        _assert_no_leak(paged)
    finally:
        paged.shutdown()


@pytest.mark.parametrize("chunk", [16, 7, 64])  # 1 block, odd, full prompt
def test_chunked_prefill_token_identical_to_one_shot(params, chunk):
    oneshot = _paged(params, prefill_chunk_tokens=0)
    chunked = _paged(params, prefill_chunk_tokens=chunk)
    try:
        ref = [f.result(timeout=120) for f in
               [oneshot.submit(p, max_tokens=8) for p in PROMPTS]]
        got = [f.result(timeout=120) for f in
               [chunked.submit(p, max_tokens=8) for p in PROMPTS]]
        assert got == ref
        assert chunked.stats()["prefill_chunks"] >= len(PROMPTS)
        _assert_no_leak(chunked)
    finally:
        oneshot.shutdown()
        chunked.shutdown()


def test_paged_prefix_reuse_token_identical(params):
    """A repeated prompt admits through the prefix cache: the warm run reuses
    every full prompt block (plus COW on the tail) and the tokens match the
    cold run bit-for-bit."""
    eng = _paged(params, kv_block_size=8)
    try:
        prompt = list(range(40, 7, -1))  # 33 tokens -> 4 full blocks of 8
        a = eng.generate(prompt, max_tokens=6)
        assert eng.stats()["prefix_cache_misses"] == 1
        b = eng.generate(prompt, max_tokens=6)
        assert a == b
        st = eng.stats()
        assert st["prefix_cache_hits"] == 1
        assert st["prefix_tokens_reused"] >= 32
        _assert_no_leak(eng)
    finally:
        eng.shutdown()


def test_paged_never_fitting_prompt_is_value_error(params):
    # pool of 2 usable blocks (32 positions) but max_seq_len still 64: the
    # block check fires where the seq-len check cannot
    eng = _paged(params, kv_num_blocks=3)
    try:
        with pytest.raises(ValueError, match="never be admitted"):
            eng.submit([1] * 30, max_tokens=10)
        assert eng.stats()["kv_blocks_in_use"] == 0
    finally:
        eng.shutdown()


def test_bucket_cap_contract():
    from ray_tpu.serve.llm import _bucket

    assert _bucket(100, cap=128) == 128
    assert _bucket(100, cap=100) == 100  # clamped, not grown past the cache
    assert _bucket(64, cap=64) == 64
    with pytest.raises(ValueError):
        _bucket(65, cap=64)


# --------------------------------------------------------------------------
# leak checks: every release path returns ALL blocks
# --------------------------------------------------------------------------
def test_blocks_released_on_finish_and_eos(params):
    eng = _paged(params)
    try:
        out = eng.generate([4, 5, 6], max_tokens=8)
        eos = out[2]
        eng.generate([4, 5, 6], max_tokens=8, eos_id=eos)  # early eos stop
        _assert_no_leak(eng)
    finally:
        eng.shutdown()


def test_blocks_released_on_disconnect_evict(params):
    eng = _paged(params, max_batch_size=1)
    try:
        stream = eng.submit_stream([4, 2], max_tokens=50)
        next(stream)
        _wait(lambda: eng.stats()["active_slots"] == 1)
        assert eng.stats()["kv_blocks_in_use"] > 0
        stream.close()
        _wait(lambda: eng.stats()["active_slots"] == 0)
        _wait(lambda: eng.stats()["kv_blocks_in_use"] == 0)
        # the freed pages still serve new work
        assert len(eng.generate([4, 2], max_tokens=3)) == 3
        _assert_no_leak(eng)
    finally:
        eng.shutdown()


def test_blocks_released_on_deadline_shed(params):
    eng = _paged(params, max_batch_size=1)
    try:
        blocker = eng.submit([2, 7, 1], max_tokens=40)
        doomed = eng.submit([2, 7, 1], max_tokens=2,
                            deadline_ts=time.time() + 0.05)
        with pytest.raises(DeadlineExceededError):
            doomed.result(timeout=120)
        blocker.result(timeout=120)
        _assert_no_leak(eng)
    finally:
        eng.shutdown()


def test_blocks_released_on_prefill_crash(params):
    eng = _paged(params, prefill_chunk_tokens=8)
    try:
        real = eng.runner._prefill_chunk
        eng.runner._prefill_chunk = lambda *a, **k: (_ for _ in ()).throw(
            RuntimeError("injected prefill fault")
        )
        fut = eng.submit([1, 2, 3, 4, 5], max_tokens=4)
        with pytest.raises(RuntimeError, match="prefill failed"):
            fut.result(timeout=120)
        _wait(lambda: eng.stats()["kv_blocks_in_use"] == 0)
        eng.runner._prefill_chunk = real
        # pool intact: the engine keeps serving
        assert len(eng.generate([1, 2, 3], max_tokens=3)) == 3
        _assert_no_leak(eng)
    finally:
        eng.shutdown()


class _Poisoned:
    """A step's tokens that fail when the host reads them."""

    def __array__(self, *a, **k):
        raise RuntimeError("injected decode fault")


@pytest.mark.parametrize("fault", ["dispatch", "dispatch_with_a_step_in_flight", "collect"])
def test_blocks_released_on_loop_crash(params, fault):
    """A decode step that fails where it is dispatched (the first, or one
    dispatched while the step before is unread) or where it is read: the
    engine fails every request it holds, drops the step in flight with them,
    resets the pool and serves the next request."""
    eng = _paged(params)
    try:
        real, calls = eng.runner._decode_k_paged, []

        def program(*a, **k):
            calls.append(1)
            if fault == "dispatch" or (fault == "dispatch_with_a_step_in_flight" and len(calls) >= 2):
                raise RuntimeError("injected decode fault")
            out = real(*a, **k)
            return (_Poisoned(), *out[1:]) if fault == "collect" else out

        eng.runner._decode_k_paged = program
        futs = [eng.submit([1, 2, 3], max_tokens=8), eng.submit([9, 8, 7, 6], max_tokens=8)]
        for fut in futs:
            with pytest.raises(RuntimeError):
                fut.result(timeout=120)
        _wait(lambda: eng.stats()["kv_blocks_in_use"] == 0)
        assert eng._flight is None and eng.stats()["active_slots"] == 0
        eng.runner._decode_k_paged = real
        # _fail_inflight + _reset_cache recovered the engine
        assert eng.generate([1, 2, 3], max_tokens=6) == _reference(params, [1, 2, 3], 6)
        _assert_no_leak(eng)
    finally:
        eng.shutdown()


@pytest.mark.parametrize("K", [1, 4])
def test_eos_read_one_step_late_drops_the_row_and_frees_its_pages(params, K):
    """The step after an EOS is on the device before the host reads the
    EOS: its row is dropped whole and counted, exactly the tokens up to the
    EOS are returned, the pool goes back to its baseline, and both a later
    request over the finished one's prefix and one that is given its freed
    pages (the pool holds no others) get the reference's tokens."""
    from llm_reference import late_eos_case

    prompt, out, j = late_eos_case(CFG, params)
    # 3 + 40 - 1 positions in pages of 4: 11 pages, and the pool has exactly 11
    eng = _paged(params, max_batch_size=2, decode_chunk=K, kv_block_size=4, kv_num_blocks=12)
    try:
        assert eng.store.kv_free_blocks() == 11
        assert eng.generate(prompt, max_tokens=40, eos_id=out[j]) == out[: j + 1]
        # the future resolves where the EOS is read; the step behind it is read next
        _wait(lambda: eng._flight is None and eng.stats()["decode_row_steps_discarded"] > 0)
        st = eng.stats()
        assert st["decode_row_steps_discarded"] == K  # one dispatch past the EOS, none past that
        assert st["active_slots"] == 0 and st["kv_blocks_shared"] == 0
        # what is held is the cache's: the full pages of prompt + generated[:-1], once each
        assert st["kv_blocks_in_use"] == st["prefix_cache_blocks"] == (len(prompt) + j) // 4
        longer = prompt + out[:j] + [11, 12, 13]
        assert eng.generate(longer, max_tokens=6) == _reference(params, longer, 6)
        assert eng.stats()["prefix_tokens_reused"] >= 4 * ((len(prompt) + j) // 4)
        # a request that needs every page of the pool, the dropped step's targets among them
        other = [31, 32, 33]
        assert eng.generate(other, max_tokens=40) == _reference(params, other, 40)
        _assert_no_leak(eng)
        assert eng.store.kv_free_blocks() == 11
    finally:
        eng.shutdown()


def test_stream_cancelled_with_a_step_in_flight(params):
    """A consumer that goes away mid-decode: the step in flight for its row
    is dropped by the request's identity (the slot may be another's by
    then), nothing more is emitted, the pages return, and the slot's next
    owner gets the reference's tokens."""
    eng = _paged(params, max_batch_size=1, max_seq_len=1024)
    try:
        want, nxt = _reference(params, [4, 2], 3), [9, 3, 7]
        want_nxt = _reference(params, nxt, 6)
        stream = eng.submit_stream([4, 2], max_tokens=1000)  # far from done when the consumer leaves
        assert [next(stream) for _ in range(3)] == want
        req = stream._req
        stream.close()
        _wait(lambda: eng.stats()["active_slots"] == 0)
        _wait(lambda: eng.stats()["kv_blocks_in_use"] == 0)
        _wait(lambda: eng._flight is None)
        st = eng.stats()
        assert st["decode_row_steps_discarded"] >= 1 and st["slots_evicted"] == 1
        emitted = len(req.generated)
        assert 3 <= emitted < 1000
        assert eng.generate(nxt, max_tokens=6) == want_nxt
        assert len(req.generated) == emitted and not req.stream_queue.qsize() > emitted
        _assert_no_leak(eng)
    finally:
        eng.shutdown()


def test_head_of_line_waits_for_blocks_no_leak(params):
    """Pool fits one max-size request: the second is HELD (not shed, not
    reordered) until the first releases, then admits and completes."""
    eng = _paged(params, max_batch_size=2, kv_num_blocks=5)  # 4 usable blocks
    try:
        a = eng.submit([1] * 40, max_tokens=20)  # needs all 4 blocks
        _wait(lambda: eng.stats()["kv_blocks_in_use"] == 4)
        b = eng.submit([2] * 40, max_tokens=20)  # must wait for the pool
        _wait(lambda: eng.admission_snapshot()["waiting_for_blocks"] == 1)
        assert len(a.result(timeout=120)) == 20
        assert len(b.result(timeout=120)) == 20
        _wait(lambda: eng.stats()["kv_blocks_in_use"]
              == eng.stats()["prefix_cache_blocks"])
        _assert_no_leak(eng)
    finally:
        eng.shutdown()


def test_paged_snapshot_and_metrics_registered(params):
    from ray_tpu.observability import metric_defs
    from ray_tpu.runtime import admission

    names = {m.name for m in metric_defs.ALL_METRICS}
    for family in (
        "llm_kv_block_pool_size",
        "llm_kv_blocks_in_use",
        "llm_prefill_chunks_total",
        "llm_prefill_kv_tokens_visited_total",
        "llm_prefill_kv_tokens_capacity_total",
        "llm_decode_stall_seconds",
    ):
        assert family in names
    eng = _paged(params)
    try:
        snap = [s for s in admission.sources_snapshot()
                if s.get("layer") == "engine"][-1]
        assert snap["kv_block_pool_size"] == eng.store.allocator.capacity
        assert snap["kv_blocks_in_use"] == 0
        assert snap["kv_block_occupancy"] == 0.0
    finally:
        eng.shutdown()


@pytest.mark.parametrize("family", ["dense", "block", "hybrid", "latent"])
def test_the_kernels_write_leaves_the_scatters_logits_and_pools(family):
    """``paged_forward_counted`` with ``use_decode_kernel=True`` (every kernel
    interpreted; the new rows go into the pools by ``paged_write_rows``)
    against ``use_decode_kernel=False`` (the scatter), for a dense config, one
    that decodes by blocks of 4, a hybrid one (its full layers' pools beside
    the linear layers' state) and a latent one (one pool): a chunk from inside
    a page with a padded tail, then a decode call, an idle row in both. The
    logits of the real tokens, and the pools on every page but page 0."""
    import importlib

    from ray_tpu.models.generation import init_paged_cache, paged_forward_counted

    cfg = CFG if family == "dense" else importlib.import_module(
        {"block": "test_block_diffusion", "hybrid": "test_hybrid_state", "latent": "test_kimi_linear"}[family]).CFG
    p = init_params(cfg, jax.random.key(1))
    B, bs, M, step = 3, 16, 4, cfg.block
    rng = np.random.default_rng(5)
    tables = rng.permutation(np.arange(1, 1 + B * M)).reshape(B, M).astype(np.int32)
    tables[2] = 0  # an idle slot
    bt = jnp.asarray(tables)
    at = {"slots": jnp.arange(B, dtype=jnp.int32)} if cfg.hybrid else {}
    calls = [  # (rows a sequence, first positions, rows to keep)
        (24, [0, 20, 0], [24, 16, 0]),
        (step, [24, 36, 0], [step, step, 0]),
    ]
    tokens = [jnp.asarray(rng.integers(1, cfg.vocab_size - 1, (B, T)), jnp.int32) for T, _, _ in calls]
    out = {}
    for kernel in (False, True):
        cache = init_paged_cache(cfg, 1 + B * M, bs, **({"slots": B} if cfg.hybrid else {}))
        logits = []
        for toks, (T, starts, keep) in zip(tokens, calls):
            positions = jnp.asarray(np.asarray(starts)[:, None] + np.arange(T)[None, :], jnp.int32)
            valid = jnp.asarray(np.arange(T)[None, :] < np.asarray(keep)[:, None])
            lg, cache, _ = paged_forward_counted(cfg, p, cache, bt, toks, positions, valid=valid, use_decode_kernel=kernel, **at)
            logits.append(np.asarray(lg)[np.asarray(valid)])
        out[kernel] = (logits, cache)
    for got, want in zip(*(out[k][0] for k in (True, False))):
        np.testing.assert_allclose(got, want, atol=3e-4, rtol=3e-4)
    got, want = (out[k][1] for k in (True, False))
    assert set(got) == set(want)
    for name in want:
        pages = slice(1, None) if name in ("k", "v", "latent") else slice(None)
        np.testing.assert_allclose(np.asarray(got[name][:, pages]), np.asarray(want[name][:, pages]), atol=3e-4, rtol=3e-4)
    first = "latent" if "latent" in want else "k"  # the first pool layer's rows pass through no attention: bit for bit
    if family in ("dense", "block"):
        np.testing.assert_array_equal(np.asarray(got[first][0, 1:]), np.asarray(want[first][0, 1:]))
    assert np.asarray(want[first][:, 1:]).any()
