"""Decode-attention kernel tests (interpret mode on CPU): vs reference
einsum over ragged lengths, GQA groups, multi-block streaming, and the
forward_with_cache integration."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.decode_attention import decode_attention


def _reference(q, kc, vc, lengths):
    B, H, D = q.shape
    Hkv, S = kc.shape[1], kc.shape[2]
    n_rep = H // Hkv
    keys = jnp.repeat(kc, n_rep, axis=1).astype(jnp.float32)   # [B, H, S, D]
    vals = jnp.repeat(vc, n_rep, axis=1).astype(jnp.float32)
    s = jnp.einsum("bhd,bhsd->bhs", q.astype(jnp.float32), keys) / math.sqrt(D)
    mask = jnp.arange(S)[None, None, :] < lengths[:, None, None]
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhs,bhsd->bhd", p, vals)


@pytest.mark.parametrize("n_rep", [1, 4])
@pytest.mark.parametrize("block_s", [64, 128])
def test_matches_reference(n_rep, block_s):
    rng = np.random.default_rng(0)
    B, Hkv, S, D = 3, 2, 200, 32  # S not a block multiple: exercises padding
    H = Hkv * n_rep
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
    lengths = jnp.asarray([1, 77, 200], jnp.int32)
    out = decode_attention(q, kc, vc, lengths, block_s=block_s)
    ref = _reference(q, kc, vc, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("n_rep", [1, 4])
@pytest.mark.parametrize("block_size", [16, 128])
def test_paged_kernel_matches_reference_on_the_same_cache(n_rep, block_size):
    """The dense cache cut into shuffled pages (groups of 8 pages and of
    one): the paged kernel gives this file's reference and the dense
    kernel's answer."""
    from ray_tpu.ops.decode_attention import paged_decode_attention

    rng = np.random.default_rng(2)
    B, Hkv, S, D = 3, 2, 256, 32
    M = S // block_size
    q = jnp.asarray(rng.standard_normal((B, Hkv * n_rep, D)), jnp.float32)
    kc, vc = (jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32) for _ in range(2))
    lengths = jnp.asarray([1, 129, 256], jnp.int32)
    bt = rng.permutation(np.arange(1, B * M + 1)).reshape(B, M).astype(np.int32)

    def pool(cache):  # [1, pages, block_size, Hkv*D], page 0 the garbage page
        pages = jnp.transpose(cache, (0, 2, 1, 3)).reshape(B * M, block_size, Hkv * D)
        return jnp.zeros((1, B * M + 1, block_size, Hkv * D), jnp.float32).at[0, bt.reshape(-1)].set(pages)

    out = paged_decode_attention(q, pool(kc), pool(vc), jnp.asarray(bt), lengths, jnp.int32(0))
    np.testing.assert_allclose(np.asarray(out), np.asarray(_reference(q, kc, vc, lengths)), rtol=2e-5, atol=2e-5)
    dense = decode_attention(q, kc, vc, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), rtol=2e-5, atol=2e-5)


def test_bf16_cache():
    rng = np.random.default_rng(1)
    B, Hkv, S, D = 2, 2, 64, 16
    q = jnp.asarray(rng.standard_normal((B, 4, D)), jnp.bfloat16)
    kc = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.bfloat16)
    vc = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.bfloat16)
    lengths = jnp.asarray([5, 64], jnp.int32)
    out = decode_attention(q, kc, vc, lengths)
    ref = _reference(q, kc, vc, lengths)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=3e-2, atol=3e-2
    )


def test_forward_with_cache_kernel_path_matches_einsum():
    from ray_tpu.models import TransformerConfig, init_params
    from ray_tpu.models.generation import forward_with_cache, init_cache

    cfg = TransformerConfig(
        vocab_size=53, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=64,
        attention="dense", dtype=jnp.float32,
    )
    params = init_params(cfg, jax.random.key(2))
    cache = init_cache(cfg, 2, 24)
    # prefill via the einsum path
    toks = jnp.asarray(np.random.default_rng(3).integers(0, 53, (2, 6)), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(6)[None, :], (2, 6))
    _, cache = forward_with_cache(cfg, params, cache, toks, pos)
    # one decode step, both paths, same cache
    tok = jnp.asarray([[7], [9]], jnp.int32)
    dpos = jnp.asarray([[6], [6]], jnp.int32)
    l_kernel, _ = forward_with_cache(cfg, params, cache, tok, dpos, use_decode_kernel=True)
    l_einsum, _ = forward_with_cache(cfg, params, cache, tok, dpos, use_decode_kernel=False)
    np.testing.assert_allclose(
        np.asarray(l_kernel), np.asarray(l_einsum), rtol=2e-4, atol=2e-4
    )


def test_generate_with_kernel_matches():
    """Full generate loop with the kernel forced on equals the einsum loop."""
    import functools

    from ray_tpu.models import TransformerConfig, init_params
    from ray_tpu.models.generation import decode_step, init_cache, prefill

    cfg = TransformerConfig(
        vocab_size=41, d_model=16, n_layers=1, n_heads=2, d_ff=32,
        attention="dense", dtype=jnp.float32,
    )
    params = init_params(cfg, jax.random.key(4))
    prompt = jnp.asarray([[3, 5, 8]], jnp.int32)
    outs = {}
    for use in (True, False):
        cache = init_cache(cfg, 1, 8)
        logits, cache = prefill(cfg, params, cache, prompt, jnp.asarray([3], jnp.int32))
        toks = []
        pos = jnp.asarray([3], jnp.int32)
        for _ in range(4):
            t = jnp.argmax(logits, -1).astype(jnp.int32)
            toks.append(int(t[0]))
            logits, cache = __import__("ray_tpu.models.generation", fromlist=["forward_with_cache"]).forward_with_cache(
                cfg, params, cache, t[:, None], pos[:, None], use_decode_kernel=use
            )
            logits = logits[:, 0]
            pos = pos + 1
        outs[use] = toks
    assert outs[True] == outs[False]


def test_large_n_rep_sublane_rounding():
    """n_rep > 8 and not a multiple of 8 (rounds up to 16 sublanes)."""
    rng = np.random.default_rng(5)
    B, Hkv, n_rep, S, D = 2, 2, 12, 64, 16
    q = jnp.asarray(rng.standard_normal((B, Hkv * n_rep, D)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
    lengths = jnp.asarray([10, 64], jnp.int32)
    out = decode_attention(q, kc, vc, lengths)
    ref = _reference(q, kc, vc, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_block_shrinks_to_divisor_instead_of_padding():
    """S=600 with block_s=512 -> block shrinks to 300 (divisor), no pad."""
    rng = np.random.default_rng(6)
    B, Hkv, S, D = 2, 1, 600, 32
    q = jnp.asarray(rng.standard_normal((B, 2, D)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
    lengths = jnp.asarray([600, 123], jnp.int32)
    out = decode_attention(q, kc, vc, lengths, block_s=512)
    ref = _reference(q, kc, vc, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_zero_length_rows_yield_zeros():
    """An empty slot (lengths == 0) must emit zeros, not garbage-V means."""
    rng = np.random.default_rng(7)
    B, Hkv, S, D = 3, 2, 64, 16
    q = jnp.asarray(rng.standard_normal((B, 4, D)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
    lengths = jnp.asarray([0, 5, 0], jnp.int32)
    out = np.asarray(decode_attention(q, kc, vc, lengths))
    assert (out[0] == 0).all() and (out[2] == 0).all()
    ref = _reference(q, kc, vc, lengths)
    np.testing.assert_allclose(out[1], np.asarray(ref[1]), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# the paged kernel's unit is a run of heads that share one block of query rows:
# the served head shapes and the ones that stress the layout, against the
# plain-XLA lines on the same pool
# ---------------------------------------------------------------------------
_PAGE, _TABLE = 16, 10  # 160 tokens a row: two groups of 128 keys, the second part full


def _paged_case(Hkv, D, n_rep, seed=0, dtype=jnp.bfloat16):
    """Three layers of a shuffled pool and six rows: empty, one token, a length
    that ends mid-page, a full table, two tokens into the second group, and an
    idle slot (an all-zero table at the length its last tenant left)."""
    rng = np.random.default_rng(seed)
    B, N = 6, 6 * _TABLE + 1
    q = jnp.asarray(rng.standard_normal((B, Hkv * n_rep, D)), dtype)
    kp, vp = (jnp.asarray(rng.standard_normal((3, N, _PAGE, Hkv * D)), dtype) for _ in range(2))
    bt = rng.permutation(np.arange(1, N)).reshape(B, _TABLE).astype(np.int32)
    bt[5] = 0
    lengths = jnp.asarray([0, 1, 37, _PAGE * _TABLE, 130, 77], jnp.int32)
    return q, kp, vp, jnp.asarray(bt), lengths


def _paged_both(q, kp, vp, bt, lengths, **kw):
    from ray_tpu.ops.decode_attention import paged_decode_attention

    return [
        np.asarray(paged_decode_attention(q, kp, vp, bt, lengths, jnp.int32(1), use_kernel=k, **kw), np.float32)
        for k in (True, False)
    ]


# (Hkv, D, n_rep): SmolLM2 (eight heads a unit, a row each), Trinity-Mini (a
# head a unit, eight real rows), Olmo-Hybrid (six of thirty heads a unit, a
# row each), two heads x four queries (eight rows exactly), two heads x eight
# (sixteen rows: past one sublane tile), and an odd head count at 64 lanes (a
# head a unit of 64 lanes)
_HEAD_SHAPES = [(32, 64, 1), (4, 128, 8), (30, 128, 1), (8, 64, 4), (2, 64, 8), (3, 64, 2)]


@pytest.mark.parametrize("window", [None, 40], ids=["full", "window40"])
@pytest.mark.parametrize("Hkv,D,n_rep", _HEAD_SHAPES, ids=[f"kv{h}_d{d}_rep{r}" for h, d, r in _HEAD_SHAPES])
def test_paged_kernel_matches_xla_at_every_head_layout(Hkv, D, n_rep, window):
    q, kp, vp, bt, lengths = _paged_case(Hkv, D, n_rep, seed=Hkv + n_rep)
    got, want = _paged_both(q, kp, vp, bt, lengths, window=window)
    assert got.shape == (6, Hkv * n_rep, D)
    assert not got[0].any() and not got[5].any(), "an empty row and an idle slot are zeros"
    assert np.abs(want[1:5]).max() > 0.5
    # P meets V in bf16 (one pass of the MXU) and the result is bf16
    np.testing.assert_allclose(got, want, rtol=1e-2, atol=1e-2)


def test_paged_kernel_matches_xla_on_a_float32_pool():
    """A pool that is not bf16 keeps full-precision products."""
    q, kp, vp, bt, lengths = _paged_case(8, 64, 4, seed=3, dtype=jnp.float32)
    got, want = _paged_both(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("Hkv,D,n_rep", [(8, 64, 4), (32, 64, 1), (30, 128, 1)], ids=["two_a_unit", "eight_a_unit", "six_a_unit"])
def test_a_loud_neighbour_in_the_unit_changes_nothing(Hkv, D, n_rep):
    """The heads of a unit share lane tiles and a block of query rows. With
    the odd heads' K and V a thousand times louder, the even heads' outputs
    are bit-for-bit what they were: their rows hold exact zeros in their
    neighbours' lanes, and only their own lanes of the accumulator are kept."""
    from ray_tpu.ops.decode_attention import _heads_a_unit

    assert _heads_a_unit(Hkv, D, n_rep) > 1
    q, kp, vp, bt, lengths = _paged_case(Hkv, D, n_rep, seed=11)
    odd = jnp.tile(jnp.repeat(jnp.asarray([1.0, 1e3], kp.dtype), D), Hkv // 2)  # [Hkv*D]: x1 | x1000 a pair of heads
    quiet, _ = _paged_both(q, kp, vp, bt, lengths)
    loud, want = _paged_both(q, kp * odd, vp * odd, bt, lengths)
    heads = lambda a: a.reshape(6, Hkv, n_rep, D)
    np.testing.assert_array_equal(heads(loud)[:, 0::2], heads(quiet)[:, 0::2])
    np.testing.assert_allclose(heads(loud)[:, 0::2], heads(want)[:, 0::2], rtol=1e-2, atol=1e-2)
    # the loud heads are themselves right, at their own scale
    np.testing.assert_allclose(heads(loud)[:, 1::2] / 1e3, heads(want)[:, 1::2] / 1e3, rtol=1e-2, atol=1e-2)


@pytest.mark.parametrize("shape,share", [((32, 64, 1), 8), ((4, 128, 8), 1), ((30, 128, 1), 6), ((8, 64, 4), 2),
                                         ((2, 64, 8), 2), ((3, 64, 2), 1), ((16, 128, 2), 4), ((5, 96, 1), 1)])
def test_heads_a_unit_fills_one_sublane_tile_of_rows_with_whole_lane_tiles(shape, share):
    from ray_tpu.ops.decode_attention import _heads_a_unit

    assert _heads_a_unit(*shape) == share


# ---------------------------------------------------------------------------
# the pools' write: paged_write_rows against the scatter, byte for byte
# ---------------------------------------------------------------------------
def _write_call(rng, B, T, starts, lengths, tables, bs):
    """(phys, off) of a call as ``models/generation._paged_write_index`` gives them."""
    from ray_tpu.models.generation import _paged_write_index

    positions = jnp.asarray(np.asarray(starts)[:, None] + np.arange(T)[None, :], jnp.int32)
    valid = jnp.asarray(np.arange(T)[None, :] < np.asarray(lengths)[:, None])
    return _paged_write_index(jnp.asarray(tables), positions, valid, bs)


# (pools, lanes, sequences, rows a sequence, first positions, rows to keep, layer): tables of 4 pages of 16
_WRITE_CASES = {
    "decode_two_pools_some_idle": (2, 512, 5, 1, [3, 17, 0, 40, 63], [1, 1, 0, 1, 1], 1),
    "decode_latent_one_pool": (1, 640, 4, 1, [3, 31, 9, 15], [1, 0, 0, 1], 2),
    "decode_2048_lanes": (2, 2048, 3, 1, [0, 15, 16], [1, 1, 1], 0),
    "decode_3840_lanes_layer_2": (2, 3840, 2, 1, [33, 7], [1, 1], 2),
    "decode_past_the_table": (2, 512, 3, 1, [64, 5, 200], [1, 1, 1], 1),
    "chunk_from_a_page_boundary": (2, 512, 1, 32, [16], [32], 1),
    "chunk_from_inside_a_page": (2, 512, 1, 32, [5], [32], 1),
    "chunk_with_a_padded_tail": (2, 2048, 1, 32, [16], [21], 0),
    "chunk_latent_inside_a_page_padded": (1, 640, 1, 48, [7], [30], 1),
    "chunk_all_padding": (2, 512, 1, 32, [0], [0], 1),
    "block_steps_of_four": (2, 512, 4, 4, [4, 12, 0, 60], [4, 4, 0, 4], 2),
    "two_chunks_a_call": (1, 512, 2, 32, [7, 32], [20, 3], 0),
    "short_runs_across_two_pages": (2, 512, 2, 4, [14, 30], [4, 3], 1),
    "short_runs_latent_of_eight": (1, 640, 2, 8, [14, 9], [8, 3], 0),
}


@pytest.mark.parametrize("case", list(_WRITE_CASES), ids=list(_WRITE_CASES))
def test_paged_write_rows_is_the_scatter_on_every_page_but_the_garbage_page(case):
    """``paged_write_rows`` (interpreted) against ``pool.at[l, phys, off].set``:
    one pool and two, rows of 512 to 3840 lanes, a decode call with idle rows
    (all-zero tables), chunks from a page boundary and from inside a page,
    with a padded tail and all padding, runs shorter than a page that cross
    into the next, a layer that is not 0, positions past
    a row's table (an all-zero table's, or clipped onto the last page with
    ``valid`` False: page 0 either way). Every page but page 0 byte for byte,
    the other layers with them."""
    from ray_tpu.ops.decode_attention import paged_write_rows, paged_write_segments

    n, lanes, B, T, starts, lengths, layer = _WRITE_CASES[case]
    rng = np.random.default_rng(sorted(_WRITE_CASES).index(case))
    L, bs, M = 3, 16, 4
    N = 1 + B * M
    pools = tuple(jnp.asarray(rng.standard_normal((L, N, bs, lanes)), jnp.bfloat16) for _ in range(n))
    rows = tuple(jnp.asarray(rng.standard_normal((B * T, lanes)), jnp.bfloat16) for _ in range(n))
    tables = rng.permutation(np.arange(1, N)).reshape(B, M).astype(np.int32)
    tables[np.asarray(lengths) == 0] = 0  # an idle slot's table
    if case == "decode_past_the_table":
        lengths = [1, 1, 0]  # the row at 200 is past its table: not valid, or it would alias the last page
        tables[0] = 0        # and the row at 64 walks an all-zero table, as a finished row does
    phys, off = _write_call(rng, B, T, starts, lengths, tables, bs)
    got = paged_write_rows(pools, rows, layer, paged_write_segments(phys, off, sequences=B, block_size=bs))
    want = tuple(p.at[layer, phys, off].set(r) for p, r in zip(pools, rows))
    assert len(got) == n
    for g, w, before in zip(got, want, pools):
        g, w = (np.asarray(a.view(jnp.uint16)) for a in (g, w))
        np.testing.assert_array_equal(g[:, 1:], w[:, 1:])
        if any(lengths):
            assert (w[layer] != np.asarray(before.view(jnp.uint16))[layer]).any()  # the call wrote something


def test_paged_write_rows_never_touches_the_garbage_page():
    """A row bound for page 0 (an idle slot, a padded tail) is not copied at
    all: page 0 comes back as it went in, where the scatter leaves the last
    such row in it."""
    from ray_tpu.ops.decode_attention import paged_write_rows, paged_write_segments

    rng = np.random.default_rng(3)
    pool = jnp.asarray(rng.standard_normal((2, 6, 16, 256)), jnp.bfloat16)
    rows = jnp.asarray(rng.standard_normal((3, 256)), jnp.bfloat16)
    phys, off = jnp.asarray([0, 4, 0], jnp.int32), jnp.asarray([2, 9, 2], jnp.int32)
    got, = paged_write_rows((pool,), (rows,), 1, paged_write_segments(phys, off, sequences=3, block_size=16))
    np.testing.assert_array_equal(np.asarray(got[:, 0].view(jnp.uint16)), np.asarray(pool[:, 0].view(jnp.uint16)))
    np.testing.assert_array_equal(np.asarray(got[1, 4, 9].view(jnp.uint16)), np.asarray(rows[1].view(jnp.uint16)))


def test_a_call_too_large_for_one_grid_steps_buffers_takes_more(monkeypatch):
    """The page buffers a grid step holds are bounded: a call of more
    sequences than fit takes more grid steps (the last padded with segments
    bound for page 0) and writes the same pools."""
    from ray_tpu.ops import decode_attention as da

    rng = np.random.default_rng(9)
    n, lanes, B, bs, M = 2, 256, 7, 16, 2
    monkeypatch.setattr(da, "_WRITE_BUFFER_BYTES", 3 * (3 * n * bs * lanes * 2))  # three rows' buffers a step
    for T, starts in ((1, [3, 17, 0, 31, 8, 20, 9]), (16, [5, 0, 16, 3, 9, 1, 2])):
        pools = tuple(jnp.asarray(rng.standard_normal((2, 1 + B * M, bs, lanes)), jnp.bfloat16) for _ in range(n))
        rows = tuple(jnp.asarray(rng.standard_normal((B * T, lanes)), jnp.bfloat16) for _ in range(n))
        tables = rng.permutation(np.arange(1, 1 + B * M)).reshape(B, M).astype(np.int32)
        phys, off = _write_call(rng, B, T, starts, [T] * B, tables, bs)
        got = da.paged_write_rows(pools, rows, 1, da.paged_write_segments(phys, off, sequences=B, block_size=bs))
        for g, pool, r in zip(got, pools, rows):
            want = pool.at[1, phys, off].set(r)
            np.testing.assert_array_equal(np.asarray(g.view(jnp.uint16))[:, 1:], np.asarray(want.view(jnp.uint16))[:, 1:])
