"""``ops/grouped_matmul.py``: the expert layer's grouped product as a Pallas
kernel, run here in interpret mode, held to ``jax.lax.ragged_dot`` and to a
plain float32 loop over the groups. The tile sizes the cases name are the
kernel's own: a product's window of 32 or 128 rows, a row tile of 512."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import transformer
from ray_tpu.ops import backend
from ray_tpu.ops import grouped_matmul as gm

F32, BF16 = jnp.float32, jnp.bfloat16
SMALL, LARGE = gm._WINDOWS
TILE = gm._ROW_TILE


def _operands(sizes, R, a, b, dtype, seed=0):
    rng = np.random.default_rng(seed)
    rows = jnp.asarray(rng.normal(size=(R, a)), dtype)
    weights = jnp.asarray(rng.normal(size=(len(sizes), a, b)) / np.sqrt(a), dtype)
    return rows, weights, jnp.asarray(sizes, jnp.int32)


def _loop_over_groups(rows, weights, sizes):
    """The product as the docstring states it, in float32 on the host."""
    rows, weights = np.asarray(rows, np.float32), np.asarray(weights, np.float32)
    out = np.zeros((rows.shape[0], weights.shape[2]), np.float32)
    start = 0
    for g, size in enumerate(np.asarray(sizes)):
        out[start:start + size] = rows[start:start + size] @ weights[g]
        start += size
    return out


def _rel(got, want):
    return float(np.linalg.norm(np.asarray(got, np.float32) - want) / np.linalg.norm(want))


CASES = [
    # name, group sizes, rows (None: their sum), a, b, dtype
    ("every group empty but one", [0, 0, 0, 40, 0, 0], None, 64, 128, F32),
    ("empty groups between live ones", [0, 5, 0, 0, 20, 0, 7, 0], None, 64, 128, F32),
    ("a group of one row", [1, 30, 1, 1, 15], None, 64, 128, F32),
    ("a group of a small window exactly", [SMALL, 3, SMALL], None, 64, 128, F32),
    ("a group of a small window and a row", [SMALL + 1, 3, SMALL + 1, 11], None, 64, 128, F32),
    ("a group of a large window exactly", [5, LARGE, 3, LARGE], None, 64, 128, F32),
    ("a group of a large window and a row", [5, LARGE + 1, 2], None, 64, 128, F32),
    ("a group of a row tile exactly", [TILE, 16, TILE], None, 32, 128, F32),
    ("a group of a row tile and a row", [3, TILE + 1, 12], None, 32, 128, F32),
    ("groups that straddle row tiles", [500, 24, 0, 1000, 12, 70, 442], None, 32, 128, F32),
    ("one group over three row tiles", [100, 1300, 30], None, 32, 128, F32),
    ("rows no multiple of the row tile", [0, 500, 0, 0, 20, 1, 179, 0, 400], None, 64, 128, F32),
    ("rows no multiple of a sublane tile", [7, 0, 19, 11], None, 64, 128, F32),
    ("fewer rows than a sublane tile", [2, 0, 3], None, 64, 128, F32),
    ("rows past the last group are zeros", [0, 5, 20, 1, 14], 64, 64, 128, F32),
    ("the result in tiles of b", [12, 0, 30, 9], None, 4096, 512, F32),
    ("a block step's rows an expert, bfloat16", [12, 9, 0, 17, 14, 11, 13, 20], None, 256, 128, BF16),
    ("a chunk's rows an expert, bfloat16", [32, 51, 18, 0, 40, 27], None, 256, 128, BF16),
    ("row tiles straddled, bfloat16", [500, 24, 0, 1000, 12], None, 128, 256, BF16),
]


@pytest.mark.parametrize("name,sizes,R,a,b,dtype", CASES, ids=[c[0] for c in CASES])
def test_the_kernel_is_the_grouped_product(name, sizes, R, a, b, dtype):
    rows, weights, group_sizes = _operands(sizes, R or sum(sizes), a, b, dtype)
    got = jax.jit(gm.grouped_matmul)(rows, weights, group_sizes)
    assert got.shape == (rows.shape[0], b) and got.dtype == dtype
    plain = _loop_over_groups(rows, weights, sizes)
    xla = jax.lax.ragged_dot(rows, weights, group_sizes)
    if dtype == F32:
        np.testing.assert_allclose(np.asarray(got), plain, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(xla), rtol=2e-5, atol=2e-5)
    else:  # one rounding of a float32 sum: no further from float32 than ragged_dot is
        assert _rel(got, plain) <= 1.05 * _rel(xla, plain) + 1e-4
        assert _rel(got, plain) < 4e-3
    assert not np.asarray(got, np.float32)[sum(sizes):].any()


def test_a_weight_tile_holds_the_whole_contraction():
    assert gm._b_tile(2048, 768, 2) == 768 and gm._b_tile(2048, 1024, 2) == 1024 and gm._b_tile(768, 2048, 2) == 2048
    assert gm._b_tile(4096, 14336, 2) == 512  # 4 MiB of [4096, 512]
    assert gm._b_tile(4096, 1000, 4) == 1000  # no lane-tiled divisor: all of it


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
def test_a_layer_stack_is_read_in_place_at_a_traced_index_inside_a_scan(dtype):
    """``moe_ffn_dropless``'s use: ``L x E`` groups of which only layer
    ``index``'s hold rows, ``index`` the scan's counter."""
    L, E, R, d = 3, 8, 96, 128
    rng = np.random.default_rng(3)
    stack = jnp.asarray(rng.normal(size=(L, E, d, d)) / np.sqrt(d), dtype)
    sizes = jnp.asarray(rng.multinomial(R, np.ones(E) / E, size=L), jnp.int32)
    sizes = sizes.at[1, 2].add(sizes[1, 5]).at[1, 5].set(0)  # an empty expert in the middle layer
    x = jnp.asarray(rng.normal(size=(R, d)), dtype)

    def through(product):
        def layer(x, index):
            in_stack = jnp.zeros((L, E), jnp.int32).at[index].set(sizes[index]).reshape(L * E)
            return product(x, stack.reshape(L * E, d, d), in_stack), None

        return jax.jit(lambda x: jax.lax.scan(layer, x, jnp.arange(L))[0])(x)

    got, want = through(gm.grouped_matmul), through(jax.lax.ragged_dot)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               rtol=2e-5 if dtype == F32 else 3e-2, atol=2e-5 if dtype == F32 else 3e-2)


def test_the_gradient_is_ragged_dots():
    rows, weights, group_sizes = _operands([0, 9, 0, 33, 6], 48, 64, 128, F32, seed=5)
    cot = jnp.asarray(np.random.default_rng(6).normal(size=(48, 128)), F32)

    def loss(product):
        return lambda r, w: (product(r, w, group_sizes) * cot).sum()

    got = jax.grad(loss(gm.grouped_matmul), argnums=(0, 1))(rows, weights)
    want = jax.grad(loss(jax.lax.ragged_dot), argnums=(0, 1))(rows, weights)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5, atol=1e-5)


def test_off_the_chip_the_expert_layer_keeps_ragged_dot(monkeypatch):
    """``transformer.grouped_matmul`` asks the one platform predicate: the
    kernel on the chip, XLA's own product elsewhere (the tier-1 run does not
    pay for interpret mode in every engine test)."""
    rows, weights, group_sizes = _operands([4, 0, 20, 8], 32, 64, 128, F32)
    called = []
    monkeypatch.setattr(transformer, "grouped_matmul_kernel", lambda *args: (called.append(1), jax.lax.ragged_dot(*args))[1])
    transformer.grouped_matmul(rows, weights, group_sizes)
    assert not called
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    transformer.grouped_matmul(rows, weights, group_sizes)
    assert called


def test_the_tile_takes_the_larger_budget_only_where_the_rows_outnumber_the_groups_tile_columns():
    """A served step's products (a few thousand rows over a stack's hundreds
    of groups) keep the 4 MiB tile they had; a train step's (49 152 rows over
    a layer's 8 groups of 2048 x 1408) take the whole width: one pass over the
    rows instead of eleven."""
    for R, G, a, b in ((4096, 5 * 128, 2048, 1024), (96, 6 * 128, 2048, 768), (4096, 8 * 32, 2304, 1024),
                       (256, 640, 2048, 1408)):
        assert gm._tile_for(R, G, a, b, 2) == gm._b_tile(a, b, 2), (R, G, a, b)
    assert gm._b_tile(2048, 1408, 2) == 128
    assert gm._tile_for(49152, 8, 2048, 1408, 2) == 1408 and gm._tile_for(49152, 8, 1408, 2048, 2) == 2048
    assert gm._tile_for(49152, 8, 4096, 14336, 2) == 1024     # 8 MiB of [4096, 1024]
