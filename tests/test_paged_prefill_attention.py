"""The paged prefill kernel (interpret mode on CPU) against the dense lines:
a chunk of queries at a traced start over a shuffled pool, a query at a time
in numpy here and through the op's own gather reference; chunks at every place
the engine puts one, windows on either side of the chunk's width, both served
head shapes, and pages no query may see filled with NaN."""

import math

import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.decode_attention import paged_prefill_attention

BS = 16  # tokens a page
SHAPES = {"mha_d64": (2, 2, 64), "gqa8_d128": (8, 1, 128)}  # H, Hkv, D: n_rep 1 (two heads a lane tile) and 8
# (T, start, length): a first chunk; one behind two cached chunks; a start on a
# page's edge that is no chunk's (a prefix hit of three pages); a last chunk
# whose tail is padding; one whose every query sits past the first group
CHUNKS = {"first": (32, 0, 32), "mid": (32, 64, 32), "prefix_hit": (32, 48, 32), "padded_tail": (32, 96, 13),
          "second_group": (32, 160, 32)}
# no window argument; none; smaller than the context; smaller than the chunk
WINDOWS = [None, 0, 40, 8]


def _case(shape, chunk, window, seed, *, poison=False, dtype=jnp.float32):
    """One sequence of ``start + length`` tokens on shuffled pages of a
    two-layer pool (read: layer 1; layer 0 is NaN throughout), and the chunk's
    queries. ``poison``: every page no query of the chunk may see (past the
    last real token's, behind the first query's window, the garbage page 0,
    the table's spare entries) holds NaN."""
    H, Hkv, D = shape
    T, start, length = chunk
    M = 16
    rng = np.random.default_rng(seed)
    seq_k, seq_v = (rng.normal(size=(M * BS, Hkv, D)).astype(np.float32) for _ in range(2))
    q = rng.normal(size=(1, T, H, D)).astype(np.float32)
    N = 2 * M + 2
    bt = rng.permutation(np.arange(1, N - 1))[:M].astype(np.int32)  # out of order; pages 0 and N - 1 in no table
    pools = []
    for seq in (seq_k, seq_v):
        pool = np.full((2, N, BS, Hkv * D), np.nan if poison else 0.5, np.float32)
        pool[1, bt] = seq.reshape(M, BS, Hkv * D)
        pools.append(pool)
    if poison:
        first = max(0, start - window + 1) if window else 0
        pages = np.arange(M)
        hidden = (pages < first // BS) | (pages > (start + length - 1) // BS)
        assert hidden.sum() >= 3
        bt = np.where(hidden, rng.choice([0, N - 1], size=M), bt).astype(np.int32)
    args = (jnp.asarray(q, dtype), jnp.asarray(pools[0], dtype), jnp.asarray(pools[1], dtype), jnp.asarray(bt[None]),
            jnp.asarray([start], jnp.int32), jnp.asarray([length], jnp.int32), jnp.int32(1))
    return args, (q[0], seq_k, seq_v)


def _a_query_at_a_time(q, seq_k, seq_v, start, length, window):
    """[length, H, D]: query ``t`` over the keys ``j <= start + t`` (and
    ``j > start + t - window``), in float64."""
    T, H, D = q.shape
    n_rep = H // seq_k.shape[1]
    out = np.zeros((length, H, D))
    for t in range(length):
        p = start + t
        lo = max(0, p - window + 1) if window else 0
        for h in range(H):
            k, v = seq_k[lo : p + 1, h // n_rep].astype(np.float64), seq_v[lo : p + 1, h // n_rep].astype(np.float64)
            s = k @ q[t, h].astype(np.float64) / math.sqrt(D)
            w = np.exp(s - s.max())
            out[t, h] = (w / w.sum()) @ v
    return out


def _kw(window):
    return {} if window is None else {"window": jnp.int32(window)}


@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("chunk", list(CHUNKS.values()), ids=list(CHUNKS))
@pytest.mark.parametrize("shape", list(SHAPES.values()), ids=list(SHAPES))
def test_prefill_kernel_matches_the_dense_lines(shape, chunk, window):
    args, (q, seq_k, seq_v) = _case(shape, chunk, window, seed=3)
    T, start, length = chunk
    got = np.asarray(paged_prefill_attention(*args, **_kw(window)))
    assert got.shape == (1, T) + shape[::2] and np.isfinite(got).all()  # the padded rows too
    want = _a_query_at_a_time(q, seq_k, seq_v, start, length, window)
    np.testing.assert_allclose(got[0, :length], want, rtol=2e-5, atol=2e-5)
    xla = np.asarray(paged_prefill_attention(*args, use_kernel=False, **_kw(window)))
    np.testing.assert_allclose(got[0, :length], xla[0, :length], rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("chunk", [CHUNKS["prefix_hit"], CHUNKS["padded_tail"], CHUNKS["second_group"]],
                         ids=["prefix_hit", "padded_tail", "second_group"])
@pytest.mark.parametrize("shape", list(SHAPES.values()), ids=list(SHAPES))
def test_prefill_kernel_never_computes_on_a_page_no_query_may_see(shape, chunk, window):
    """Pages past the last real token's, behind the first query's window, the
    garbage page, the other layer: all NaN, and named by every table entry
    the chunk cannot see. The answer is finite, padded rows included, and is
    the clean pool's: those pages are neither fetched nor met by a zero weight."""
    args, (q, seq_k, seq_v) = _case(shape, chunk, window, seed=5, poison=True)
    T, start, length = chunk
    got = np.asarray(paged_prefill_attention(*args, **_kw(window)))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(
        got[0, :length], _a_query_at_a_time(q, seq_k, seq_v, start, length, window), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("T,start,length", [(20, 30, 20), (7, 121, 5), (256, 0, 256), (160, 96, 150)],
                         ids=["ragged_width", "narrower_than_a_tile", "two_tiles", "padded_to_two_tiles"])
def test_prefill_kernel_tiles_any_chunk_width(T, start, length):
    """A width that is no multiple of the query tile is padded to one; past
    128 queries the grid has several tiles, each walking to its own last key."""
    args, (q, seq_k, seq_v) = _case(SHAPES["mha_d64"], (T, start, length), 100, seed=7)
    got = np.asarray(paged_prefill_attention(*args, window=jnp.int32(100)))
    assert got.shape[:2] == (1, T)
    np.testing.assert_allclose(
        got[0, :length], _a_query_at_a_time(q, seq_k, seq_v, start, length, 100), rtol=2e-5, atol=2e-5)


def test_prefill_kernel_rows_walk_their_own_tables():
    """Two sequences a call, at their own starts and lengths; the second
    holds nothing (length 0, a table of the garbage page, which is NaN
    here): zeros, and no page visited."""
    (q, kp, vp, bt, starts, lengths, layer), (q0, seq_k, seq_v) = _case(SHAPES["gqa8_d128"], (32, 48, 20), None, seed=9)
    kp, vp = kp.at[:, 0].set(jnp.nan), vp.at[:, 0].set(jnp.nan)
    got = np.asarray(paged_prefill_attention(
        jnp.concatenate([q, q]), kp, vp, jnp.concatenate([bt, jnp.zeros_like(bt)]),
        jnp.asarray([48, 0], jnp.int32), jnp.asarray([20, 0], jnp.int32), layer))
    np.testing.assert_allclose(got[0, :20], _a_query_at_a_time(q0, seq_k, seq_v, 48, 20, None), rtol=2e-5, atol=2e-5)
    assert not got[1].any()


def test_prefill_kernel_reads_a_bf16_pool_as_it_is_stored():
    """bf16 queries and pool go to the score product as they are (their
    products are exact in float32); the rest is float32, so the answer is
    the float32 lines' on the same rounded values up to its own rounding."""
    args, _ = _case(SHAPES["mha_d64"], CHUNKS["mid"], None, seed=11, dtype=jnp.bfloat16)
    got = paged_prefill_attention(*args)
    assert got.dtype == jnp.bfloat16
    q, kp, vp = (np.asarray(a.astype(jnp.float32)) for a in args[:3])
    bt = np.asarray(args[3])[0]
    H, Hkv, D = SHAPES["mha_d64"]
    seq_k, seq_v = (p[1, bt].reshape(-1, Hkv, D) for p in (kp, vp))
    want = _a_query_at_a_time(q[0], seq_k, seq_v, 64, 32, None)
    np.testing.assert_allclose(np.asarray(got.astype(jnp.float32))[0], want, rtol=1e-2, atol=1e-2)
