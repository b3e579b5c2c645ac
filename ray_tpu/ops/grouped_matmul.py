"""The expert layer's grouped product as one Mosaic kernel.

``grouped_matmul(rows [R, a], weights [G, a, b], group_sizes [G]) -> [R, b]``:
``rows[start_g : start_g + group_sizes[g]] @ weights[g]`` for every group, the
rows sorted by group, as ``jax.lax.ragged_dot`` computes it. What the kernel
is for is few rows a group (a block step's 12, a prefill chunk's 32): there
the product costs what its weights cost to read, so each group that holds rows
has its ``[a, b-tile]`` of weights copied out of HBM exactly once, in one
piece, the next groups' copies in flight meanwhile, and a group without rows
costs one scalar comparison: no copy, no product. ``G`` may be a whole layer
stack's ``L x E`` groups of which one layer's hold rows; the weights stay
where they lie. In a train step the same kernel is the rows' gradient (the
cotangent against the transposed weights); the weights' gradient is XLA's
``ragged_dot`` (``_bwd``).

Grid ``(b tiles, row tiles)``. The rows and the result ride BlockSpecs in
tiles of ``_ROW_TILE``; the weights stay in HBM and the body copies them. The
first grid step walks ``group_sizes`` (scalar prefetch) once and lists the
groups that hold rows with their first row in SMEM; a row tile's step then
runs the listed groups that reach into it, in order, each against the window
of rows that covers it: ``_WINDOWS[0]`` rows where that holds the group's
rows (the MXU is paid by the weight tile, so a window straddling ten groups
would multiply ten groups' weights for a tenth of its rows each), else as
many of ``_WINDOWS[1]`` as it takes. A window starts on a sublane tile, not
on the group's first row, and is stored under a mask of the group's rows.

Arithmetic: operands as stored (the weights cast to the rows' dtype in VMEM
if they differ), float32 accumulation over the whole contraction (``a`` is
never tiled), one rounding to the rows' dtype at the store. Rows past
``sum(group_sizes)`` are zeros.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import _use_interpret

_ALIGN = 16                   # rows of a bf16 sublane tile: where a window may start
_ROW_TILE = 512               # rows of a BlockSpec tile of the rows and the result
_WINDOWS = (32, 128)          # rows of one product: a small group's, a large group's pieces
_WEIGHT_TILE_BYTES = 4 << 20  # the most one group's [a, b-tile] may hold ...
# ... and where the rows outnumber the groups' tile columns (a train step's hundreds of rows a group): there the
# product is paid by the rows' passes, one a b-tile, not by the weights' copies, so the tile is the whole width
# where that fits (2048 x 1408 bf16 is 5.8 MB: 1 pass over the rows instead of 11 tiles of 128)
_TALL_WEIGHT_TILE_BYTES = 8 << 20
_SLOTS = 3                    # weight buffers: two copies in flight behind the one in use


def _b_tile(a: int, b: int, itemsize: int, budget: int = _WEIGHT_TILE_BYTES) -> int:
    """The widest lane-tiled divisor of ``b`` whose ``[a, tile]`` fits the
    budget; all of ``b`` where it fits or has no such divisor."""
    if a * b * itemsize <= budget or b % 128:
        return b
    fits = [t for t in range(128, b, 128) if b % t == 0 and a * t * itemsize <= budget]
    return max(fits, default=128)


def _tile_for(R: int, G: int, a: int, b: int, itemsize: int) -> int:
    """The b-tile of a product of ``R`` rows over ``G`` groups, read off the
    static shapes: every b-tile passes over all the rows (``R x a x b / tile``
    numbers in all) and copies each live group's weights once (at most ``G x
    a x b``), so where ``R > G x tile`` the rows' passes are what the product
    pays for and the tile takes the larger budget."""
    tile = _b_tile(a, b, itemsize)
    return _b_tile(a, b, itemsize, _TALL_WEIGHT_TILE_BYTES) if R > G * tile else tile


def _kernel(sizes_ref, rows_ref, w_hbm, out_ref, wbuf, sems, live_g, live_s, st, *, tn, windows):
    """st: [next listed group to run, next whose weights are awaited, groups listed]."""
    n, m = pl.program_id(0), pl.program_id(1)
    TM = rows_ref.shape[0]
    t0 = m * TM

    @pl.when((n == 0) & (m == 0))
    def _list_live_groups():
        def visit(g, carry):
            k, start = carry
            size = sizes_ref[g]
            live_g[k] = g          # overwritten by the next group unless this one holds rows
            live_s[k] = start
            return k + (size > 0).astype(jnp.int32), start + size

        st[2] = jax.lax.fori_loop(0, sizes_ref.shape[0], visit, (jnp.int32(0), jnp.int32(0)))[0]

    listed = st[2]

    def weights_of(k):
        slot = k % _SLOTS
        return pltpu.make_async_copy(w_hbm.at[live_g[k], :, pl.ds(n * tn, tn)], wbuf.at[slot], sems.at[slot])

    @pl.when(m == 0)
    def _open():
        st[0] = 0
        st[1] = 0
        for k in range(_SLOTS - 1):
            @pl.when(k < listed)
            def _():
                weights_of(k).start()

    out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    def end_of(k):
        return live_s[k] + sizes_ref[live_g[k]]

    def run(k):
        @pl.when(k == st[1])
        def _first_touch():
            weights_of(k).wait()
            st[1] = k + 1

            @pl.when(k + _SLOTS - 1 < listed)
            def _():
                weights_of(k + _SLOTS - 1).start()   # into the buffer group k - 1 is done with

        lo = jnp.maximum(live_s[k] - t0, 0)
        hi = jnp.minimum(end_of(k) - t0, TM)
        first = lo // _ALIGN * _ALIGN
        slot = k % _SLOTS

        def product(start, W):
            s = pl.multiple_of(jnp.minimum(start, TM - W), _ALIGN)
            x = rows_ref[pl.ds(s, W), :]
            y = jnp.dot(x, wbuf[slot].astype(x.dtype), preferred_element_type=jnp.float32)
            r = s + jax.lax.broadcasted_iota(jnp.int32, (W, 1), 0)
            mine = (r >= lo) & (r < hi)
            out_ref[pl.ds(s, W), :] = jnp.where(mine, y, out_ref[pl.ds(s, W), :].astype(jnp.float32)).astype(out_ref.dtype)

        small, large = windows

        @pl.when(hi - first <= small)
        def _():
            product(first, small)

        @pl.when(hi - first > small)
        def _():
            jax.lax.fori_loop(0, pl.cdiv(hi - first, large), lambda c, _: product(first + c * large, large), None)

        return k + 1

    # the group before the next one may reach into this tile from the last
    k0 = st[0]
    behind = jnp.maximum(k0 - 1, 0)
    k0 = jnp.where((k0 > 0) & (end_of(behind) > t0), behind, k0)
    st[0] = jax.lax.while_loop(lambda k: (k < listed) & (live_s[k] < t0 + TM), run, k0)


def _forward(rows, weights, group_sizes):
    R, a = rows.shape
    G, _, b = weights.shape
    padded = -(-R // _ALIGN) * _ALIGN
    if padded != R:
        rows = jnp.pad(rows, ((0, padded - R), (0, 0)))
    TM = min(_ROW_TILE, padded)
    windows = tuple(min(w, TM) for w in _WINDOWS)
    tn = _tile_for(padded, G, a, b, weights.dtype.itemsize)
    listable = min(G, padded) + 1
    need = 2 * TM * (a + tn) * rows.dtype.itemsize + _SLOTS * a * tn * weights.dtype.itemsize
    out = pl.pallas_call(
        functools.partial(_kernel, tn=tn, windows=windows),
        out_shape=jax.ShapeDtypeStruct((padded, b), rows.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b // tn, pl.cdiv(padded, TM)),
            in_specs=[pl.BlockSpec((TM, a), lambda n, m, sizes: (m, 0)), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((TM, tn), lambda n, m, sizes: (m, n)),
            scratch_shapes=[
                pltpu.VMEM((_SLOTS, a, tn), weights.dtype),
                pltpu.SemaphoreType.DMA((_SLOTS,)),
                pltpu.SMEM((listable,), jnp.int32),
                pltpu.SMEM((listable,), jnp.int32),
                pltpu.SMEM((3,), jnp.int32),
            ],
        ),
        # in order: the list, the copies in flight and the place in the list pass from step to step
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=need + (16 << 20)),
        interpret=_use_interpret(),
        name="grouped_matmul",
    )(group_sizes.astype(jnp.int32), rows, weights)
    return out[:R]


@jax.custom_vjp
def grouped_matmul(rows, weights, group_sizes):
    return _forward(rows, weights, group_sizes)


def _fwd(rows, weights, group_sizes):
    return _forward(rows, weights, group_sizes), (rows, weights, group_sizes)


def _bwd(residuals, g):
    """The rows' gradient is itself a grouped product, against the transposed
    weights: the kernel again. The weights' is ``jax.lax.ragged_dot``'s own (a
    product ragged in the contracted dimension). Rows past ``sum(group_sizes)``
    belong to no group (a share's dropped assignments): their cotangent
    reaches nothing, and is zeroed first, because XLA's ragged products on the
    chip leave such rows of their result as they find them. The scope names
    both in a profile."""
    rows, weights, group_sizes = residuals
    with jax.named_scope("grouped_matmul_bwd"):
        grouped = (jnp.arange(rows.shape[0]) < jnp.sum(group_sizes))[:, None]
        g = jnp.where(grouped, g, jnp.zeros((), g.dtype))
        d_rows = _forward(g, jnp.swapaxes(weights, 1, 2), group_sizes)
        _, vjp = jax.vjp(lambda w: jax.lax.ragged_dot(rows, w.astype(rows.dtype), group_sizes), weights)
        return d_rows, *vjp(g), None


grouped_matmul.defvjp(_fwd, _bwd)
