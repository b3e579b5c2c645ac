"""The flash kernels' tile body (``ops/attention.py``): operands in the type
they arrive in, no copy for a tile that is skipped. Interpret mode, small
blocks; the compiled kernels at the cells' shapes are
``tests/test_tpu_lowering.py``'s and the chip's.

Every case holds the kernels to a dense float32 reference AND to themselves
with the index maps unclamped, bit for bit: which block a skipped step names
changes no number.
"""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention
from ray_tpu.ops.attention import NEG_INF, flash_attention_with_lse, tile_counts

BLOCKS = (16, 32)
WINDOW = 24  # no multiple of either block: the window's edge crosses tiles
HEADS = {"d64": (64, 64), "latent192x128": (192, 128)}
MASKS = {"causal": (True, None), "unmasked": (False, None), "window": (True, WINDOW)}
LENGTHS = {"even": (64, 64), "ragged": (75, 75), "longer_q": (90, 64), "longer_k": (48, 96)}
# against the float32 reference: bf16 outputs round at 2^-9 of their size, and
# the gradients read the rounded output back (delta = rowsum(dO * O))
LIMITS = {"float32": 2e-5, "bfloat16": 3e-2}


def _seen(Tq, Tk, causal, window):
    i, j = np.arange(Tq)[:, None], np.arange(Tk)[None, :]
    seen = np.ones((Tq, Tk), bool) if not causal else j <= i
    return seen if window is None else seen & (j > i - window)


def _dense(q, k, v, scale, causal, window):
    """(out, lse) in float32; a row that sees no key gives zeros and the floor."""
    seen = jnp.asarray(_seen(q.shape[2], k.shape[2], causal, window))
    s = jnp.where(seen, jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") * scale, NEG_INF)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.where(seen, jnp.exp(s - m), 0.0)
    l = p.sum(axis=-1, keepdims=True)
    safe = jnp.where(l == 0, 1.0, l)
    out = jnp.einsum("bhqk,bhkd->bhqd", p / safe, v, precision="highest")
    return out, jnp.where(l == 0, NEG_INF, m + jnp.log(safe))[..., 0]


def _all_five(fn, q, k, v, g_out, g_lse):
    (out, lse), pull = jax.vjp(fn, q, k, v)
    return (out, lse) + pull((g_out.astype(out.dtype), g_lse))


@pytest.fixture
def every_tile_copied(monkeypatch):
    """The kernels with every grid step naming its own block, run or skipped."""
    monkeypatch.setattr(attention, "_resident", lambda step, first, last: step)


CASES = list(itertools.product(HEADS, MASKS, LENGTHS, LIMITS))


@pytest.mark.parametrize("heads,mask,lengths,dtype", CASES, ids=["-".join(c) for c in CASES])
def test_a_tile_pays_for_what_it_holds_and_changes_no_number(heads, mask, lengths, dtype, request):
    (D, Dv), (causal, window), (Tq, Tk) = HEADS[heads], MASKS[mask], LENGTHS[lengths]
    keys = jax.random.split(jax.random.key(len(request.node.name)), 5)
    shapes = ((1, 2, Tq, D), (1, 2, Tk, D), (1, 2, Tk, Dv), (1, 2, Tq, Dv), (1, 2, Tq))
    q, k, v, g_out, g_lse = (jax.random.normal(key, s, jnp.float32) for key, s in zip(keys, shapes))
    q, k, v, g_out = (x.astype(dtype) for x in (q, k, v, g_out))
    scale = D ** -0.5

    def flash(q, k, v):
        return flash_attention_with_lse(q, k, v, scale, causal, *BLOCKS, window)

    got = _all_five(flash, q, k, v, g_out, g_lse)
    want = _all_five(lambda *a: _dense(*a, scale, causal, window), *(x.astype(jnp.float32) for x in (q, k, v)), g_out, g_lse)
    for name, g, w in zip(("out", "lse", "dq", "dk", "dv"), got, want):
        assert g.shape == w.shape and bool(jnp.isfinite(g).all()), name
        size = max(1.0, float(jnp.abs(jnp.where(w == NEG_INF, 0.0, w)).max()))
        assert float(jnp.abs(g.astype(jnp.float32) - w).max()) <= LIMITS[dtype] * size, name
    assert got[0].dtype == q.dtype and got[1].dtype == jnp.float32

    request.getfixturevalue("every_tile_copied")
    for name, g, b in zip(("out", "lse", "dq", "dk", "dv"), got, _all_five(flash, q, k, v, g_out, g_lse)):
        g, b = (np.asarray(x.astype(jnp.float32)) for x in (g, b))
        if dtype == "float32":
            np.testing.assert_array_equal(g, b, err_msg=name)
        else:  # XLA's CPU fusions may sum a row in another order: a last bit, of P in bf16 too
            np.testing.assert_allclose(g, b, rtol=2 ** -7, atol=2 ** -9 * max(1.0, np.abs(b).max()), err_msg=name)


GRIDS = [
    (64, 64, 16, 32, True, None), (75, 75, 16, 32, True, None), (90, 64, 16, 32, True, WINDOW),
    (48, 96, 16, 32, True, None), (96, 48, 16, 32, False, WINDOW), (75, 50, 16, 32, False, None),
    (200, 200, 32, 16, True, 40), (64, 64, 512, 1024, True, None), (130, 70, 16, 32, True, 7),
]


@pytest.mark.parametrize("Tq,Tk,bq,bk,causal,window", GRIDS)
def test_tile_counts_are_a_brute_force_count_of_visible_pairs(Tq, Tk, bq, bk, causal, window):
    """Pair by pair over the padded grid: a tile runs if a pair of it passes
    the diagonal and the window, and is crossed if it runs and some pair of it
    is hidden by them or by a padded tail; the blocks a skipped step is
    clamped into are the ones whose tiles run."""
    bq, bk = min(bq, Tq), min(bk, Tk)
    nq, nk = -(-Tq // bq), -(-Tk // bk)
    live = _seen(nq * bq, nk * bk, causal, window)
    real = live & (np.arange(nq * bq) < Tq)[:, None] & (np.arange(nk * bk) < Tk)[None, :]
    tiles = lambda m: m.reshape(nq, bq, nk, bk).transpose(0, 2, 1, 3).reshape(nq, nk, -1)  # noqa: E731
    run = tiles(live).any(-1)
    crossed = run & ~tiles(real).all(-1)
    assert tile_counts(Tq, Tk, bq, bk, causal, window) == (run.sum(), crossed.sum(), nq * nk - run.sum())
    for i in range(nq):
        first, last = (int(x) for x in attention._k_blocks_run(i, bq, bk, nk, causal, window))
        assert [j for j in range(nk) if run[i, j]] == list(range(first, last + 1))
    for j in range(nk):
        first, last = (int(x) for x in attention._q_blocks_run(j, bq, bk, nq, causal, window))
        ran = [i for i in range(nq) if run[i, j]]
        assert ran == list(range(first, last + 1)) or (not ran and first == nq - 1)


@pytest.mark.parametrize("call,counts", [
    ((8192, 8192, True), (72, 16, 56)),    # Moonlight's 8192 positions: 128 steps a head
    ((2048, 2048, True), (6, 4, 2)),       # SmolLM2 at depth 8: 8 steps
    ((4096, 4096, True), (20, 8, 12)),     # the ring's own 4096-row shard: 32 steps
    ((4096, 2048, False), (16, 0, 0)),     # a ring hop, unmasked: all run, none crossed
])
def test_tile_counts_at_the_training_cells_calls(call, counts):
    Tq, Tk, causal = call
    assert tile_counts(Tq, Tk, *attention.default_blocks(64), causal) == counts
