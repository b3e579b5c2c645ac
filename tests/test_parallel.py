"""Parallelism layer tests on the virtual 8-device CPU mesh.

Covers mesh construction, SPMD collectives, actor collective groups, ring /
Ulysses attention numerics vs dense reference, the GPipe pipeline, and the
Pallas flash-attention kernel (interpret mode on CPU).
"""

import functools
import math
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ray_tpu.parallel import (
    MeshManager,
    collective,
    pipeline_sharded,
    ring_attention_sharded,
    ring_layout,
    ring_order,
    shard_array,
    ulysses_attention_sharded,
)
from ray_tpu.ops.attention import flash_attention, mha


@pytest.fixture(scope="module")
def mesh8():
    return MeshManager().create_mesh({"dp": 8})


@pytest.fixture(scope="module")
def mesh_sp():
    return MeshManager().create_mesh({"sp": 8})


def test_mesh_construction_and_inference():
    mm = MeshManager()
    mesh = mm.create_mesh({"dp": 2, "tp": -1}, name="train")
    assert mesh.shape["dp"] == 2 and mesh.shape["tp"] == 4
    assert mm.get_mesh("train") is mesh
    with pytest.raises(ValueError):
        mm.create_mesh({"dp": 3})


def test_canonical_axis_order():
    mm = MeshManager()
    mesh = mm.create_mesh({"tp": 2, "dp": 2, "sp": 2})
    assert mesh.axis_names == ("dp", "sp", "tp")


def test_spmd_allreduce_allgather(mesh8):
    x = jnp.arange(8.0)
    xs = shard_array(x, mesh8, "dp")

    def f(shard):
        return collective.allreduce(shard.sum(), "dp")

    total = shard_map(f, mesh=mesh8, in_specs=P("dp"), out_specs=P())(xs)
    assert float(total) == 28.0

    def g(shard):
        return collective.allgather(shard, "dp")

    gathered = shard_map(g, mesh=mesh8, in_specs=P("dp"), out_specs=P("dp"))(xs)
    assert gathered.shape == (64,)


def test_spmd_reducescatter_broadcast(mesh8):
    x = jnp.ones((8, 4))
    xs = shard_array(x, mesh8, "dp")

    def rs(shard):
        return collective.reducescatter(jnp.broadcast_to(shard, (8, 4)), "dp")

    out = shard_map(rs, mesh=mesh8, in_specs=P("dp"), out_specs=P("dp"))(xs)
    assert out.shape == (8, 4)
    np.testing.assert_allclose(np.asarray(out), 8.0)

    def bc(shard):
        return collective.broadcast(shard, "dp", root=3)

    x2 = shard_array(jnp.arange(8.0), mesh8, "dp")
    out2 = shard_map(bc, mesh=mesh8, in_specs=P("dp"), out_specs=P("dp"))(x2)
    np.testing.assert_allclose(np.asarray(out2), 3.0)


def test_send_recv_ring(mesh8):
    x = shard_array(jnp.arange(8.0), mesh8, "dp")

    def shift(shard):
        return collective.send_recv(shard, "dp", shift=1)

    out = shard_map(shift, mesh=mesh8, in_specs=P("dp"), out_specs=P("dp"))(x)
    np.testing.assert_allclose(np.asarray(out), np.roll(np.arange(8.0), 1))


def test_actor_collective_group():
    collective.init_collective_group(world_size=4, rank=0, group_name="g1")
    results = {}

    def participant(rank):
        collective.init_collective_group(4, rank, group_name="g1")
        out = collective.allreduce_tensor(np.full((4,), float(rank + 1)), rank, "g1")
        results[rank] = np.asarray(out)

    threads = [threading.Thread(target=participant, args=(r,)) for r in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    for r in range(4):
        np.testing.assert_allclose(results[r], 10.0)  # 1+2+3+4
    collective.destroy_collective_group("g1")


def test_actor_collective_broadcast_and_gather():
    name = "g2"
    results = {}

    def participant(rank):
        collective.init_collective_group(3, rank, group_name=name)
        results[rank] = (
            collective.broadcast_tensor(rank * 10, rank, src_rank=1, group_name=name),
            collective.allgather_tensor(rank, rank, group_name=name),
        )

    threads = [threading.Thread(target=participant, args=(r,)) for r in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    for r in range(3):
        assert results[r][0] == 10
        assert results[r][1] == [0, 1, 2]
    collective.destroy_collective_group(name)


# ------------------------------------------------------------------ attention
def _qkv(B=2, H=8, T=128, D=32, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    return tuple(jax.random.normal(k, (B, H, T, D), dtype) for k in keys)


def _ring_mesh(n):
    return MeshManager().create_mesh({"sp": n}, devices=jax.devices()[:n])


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("n", [2, 4, 8])
@pytest.mark.parametrize("layout", ["contiguous", "zigzag"])
def test_ring_attention_matches_dense(layout, n, causal):
    """Forward and gradients of the ring, over a sequence placed as
    ``ring_layout`` says, against ``mha`` on the un-permuted sequence. A
    causal ring is zigzag where a rank's shard cuts in two (T = 8n) and
    contiguous where it does not (T = 9n); without a mask the ring takes
    any placement, zigzag too."""
    T = (8 if layout == "zigzag" else 9) * n
    assert ring_layout(T, n, causal) == (layout if causal else "contiguous")
    order = ring_order(T, n) if layout == "zigzag" else np.arange(T)
    inverse = np.argsort(order)
    mesh = _ring_mesh(n)
    q, k, v = _qkv(B=1, H=2, T=T, D=16)

    def ring(q, k, v):
        placed = (x[:, :, order] for x in (q, k, v))
        return ring_attention_sharded(*placed, mesh, "sp", causal=causal)[:, :, inverse]

    def out_and_grads(attend):
        def loss(q, k, v):
            out = attend(q, k, v)
            return jnp.sum(out.astype(jnp.float32) ** 2), out

        (_, out), grads = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return out, grads

    got, got_grads = out_and_grads(ring)
    want, want_grads = out_and_grads(functools.partial(mha, causal=causal))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    for a, b in zip(got_grads, want_grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def _eqns(jaxpr, name):
    """Every equation of primitive ``name`` in a jaxpr, its sub-jaxprs included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _eqns(sub, name)
    return found


def _ring_jaxprs(n, T, causal=True, **blocks):
    """Jaxprs of an n-way ring over T positions: forward, and forward with its pullback."""
    mesh = _ring_mesh(n)
    q, k, v = _qkv(B=1, H=2, T=T, D=16)

    def ring(q, k, v):
        return ring_attention_sharded(q, k, v, mesh, "sp", causal=causal, **blocks)

    def both(q, k, v, ct):
        return jax.vjp(ring, q, k, v)[1](ct)

    return jax.make_jaxpr(ring)(q, k, v).jaxpr, jax.make_jaxpr(both)(q, k, v, q).jaxpr


@pytest.mark.parametrize("n", [2, 4, 8])
@pytest.mark.parametrize("T_local", [8, 9])
def test_ring_kv_make_n_minus_1_hops(n, T_local):
    """K and V each hop n-1 times forward, and dK and dV n-1 times in the
    gradient: no rotation back to the start, no hop of zero cotangents, and
    no loop around them (zigzag and contiguous alike)."""
    forward, with_pullback = _ring_jaxprs(n, T_local * n)
    assert len(_eqns(forward, "ppermute")) == 2 * (n - 1)
    assert len(_eqns(with_pullback, "ppermute")) == 2 * (n - 1) + 2 * (n - 1)
    for jaxpr in (forward, with_pullback):
        assert not _eqns(jaxpr, "while") and not _eqns(jaxpr, "scan")


def _branch_tiles(jaxpr):
    """Per ``cond`` of a ring's jaxpr that picks between flash calls (the
    kernels' own ``pl.when`` bodies hold none): the tiles in each branch."""
    def tiles(branch):
        return sum(math.prod(e.params["grid_mapping"].grid) for e in _eqns(branch.jaxpr, "pallas_call"))

    per_cond = [[tiles(b) for b in eqn.params["branches"]] for eqn in _eqns(jaxpr, "cond")]
    return [t for t in per_cond if any(t)]


@pytest.mark.parametrize("n", [2, 4])
def test_zigzag_steps_have_no_empty_branch_and_equal_tiles(n):
    """Zigzag: at every step after the first, K/V from an earlier rank and
    from a later one cost the same tiles and neither costs none; contiguous
    (a shard that does not cut into the blocks' halves): one of them is the
    empty branch, which is the imbalance."""
    blocks = dict(block_q=8, block_k=16)
    zigzag = _branch_tiles(_ring_jaxprs(n, 32 * n, **blocks)[0])
    assert len(zigzag) == n - 1
    for earlier, later in zigzag:
        assert earlier == later == 2 * 4  # heads x (4 x 1 tiles of all of q, or 2 x 2 of its late half)
    contiguous = _branch_tiles(_ring_jaxprs(n, 33 * n, **blocks)[0])
    assert len(contiguous) == n - 1 and all(later == 0 < earlier for earlier, later in contiguous)


def test_ulysses_matches_dense(mesh_sp):
    q, k, v = _qkv()
    ref = mha(q, k, v, causal=True)
    spec = (None, None, "sp", None)
    qs, ks, vs = (shard_array(x, mesh_sp, *spec) for x in (q, k, v))
    out = ulysses_attention_sharded(qs, ks, vs, mesh_sp, "sp", causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_dense(causal):
    q, k, v = _qkv(T=256, D=64)
    ref = mha(q, k, v, causal=causal)
    out = flash_attention(q, k, v, None, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("T", [100, 192, 200])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_ragged_lengths(T, causal):
    """Sequence lengths that are not block multiples (tail-block regression)."""
    q, k, v = _qkv(T=T, D=32)
    ref = mha(q, k, v, causal=causal)
    out = flash_attention(q, k, v, None, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_attention_grads():
    q, k, v = _qkv(T=128, D=32)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, None, True) ** 2).sum()

    def loss_ref(q, k, v):
        return (mha(q, k, v, causal=True) ** 2).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


# ------------------------------------------------------------------ pipeline
def test_pipeline_matches_sequential(mesh8):
    mm = MeshManager()
    mesh = mm.create_mesh({"pp": 4}, devices=mesh8.devices.flatten()[:4])
    S, M, Bm, F = 4, 8, 4, 16
    keys = jax.random.split(jax.random.PRNGKey(0), S)
    ws = jnp.stack([jax.random.normal(k, (F, F)) * 0.3 for k in keys])
    xs = jax.random.normal(jax.random.PRNGKey(1), (M, Bm, F))

    def stage_fn(w, x):
        return jnp.tanh(x @ w)

    out = pipeline_sharded(stage_fn, ws, xs, mesh, "pp")

    expected = xs
    for s in range(S):
        expected = jnp.tanh(expected @ ws[s])
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=1e-5)


def test_pipeline_gradients_flow():
    """Training through the pipeline: jax autodiff reverses the microbatch
    schedule (backward hops ride the same ICI ring), so grads w.r.t. every
    stage's params must be nonzero and match a single-device reference."""
    from jax.sharding import Mesh

    n = 4
    devices = np.array(jax.devices()[:n])
    mesh = Mesh(devices, ("pp",))
    d = 8
    rng = np.random.default_rng(0)
    stacked = {"w": jnp.asarray(rng.standard_normal((n, d, d)) * 0.3, jnp.float32)}
    mb = jnp.asarray(rng.standard_normal((4, 2, d)), jnp.float32)

    def stage(p, x):
        return jnp.tanh(x @ p["w"])

    def loss_pipe(params):
        out = pipeline_sharded(stage, params, mb, mesh)
        return jnp.sum(out ** 2)

    def loss_ref(params):
        x = mb
        for i in range(n):
            x = jnp.tanh(x @ params["w"][i])
        return jnp.sum(x ** 2)

    g_pipe = jax.grad(loss_pipe)(stacked)
    g_ref = jax.grad(loss_ref)(stacked)
    np.testing.assert_allclose(np.asarray(g_pipe["w"]), np.asarray(g_ref["w"]), rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(g_pipe["w"]).sum()) > 0


# ------------------------------------------------------------- multi-host
def test_multihost_mesh_layout():
    """The DCN axis must own whole host blocks: device order within each
    dcn slice stays contiguous (inner axes ride ICI)."""
    from ray_tpu.parallel.distributed import multihost_mesh

    mesh = multihost_mesh(("dp", "tp"), (2, -1), dcn_axis="dp")
    assert mesh.shape == {"dp": 2, "tp": 4}
    devs = mesh.devices
    ids = np.vectorize(lambda d: d.id)(devs)
    # row 0 = first host's 4 devices, row 1 = second host's
    assert sorted(ids[0].tolist()) == [0, 1, 2, 3]
    assert sorted(ids[1].tolist()) == [4, 5, 6, 7]


def test_rendezvous_via_cluster_kv():
    import ray_tpu

    ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
    try:
        from ray_tpu.parallel.distributed import rendezvous_via_cluster

        addr0, ws, r0 = rendezvous_via_cluster(0, 2)
        addr1, _, r1 = rendezvous_via_cluster(1, 2)
        assert addr0 == addr1 and ":" in addr0
        assert (r0, r1) == (0, 1)
    finally:
        ray_tpu.shutdown()


def test_multihost_mesh_three_axes_dcn_not_first():
    """3-axis layout with the DCN axis in the middle: shape must be right
    AND the dcn axis must own contiguous host blocks (moveaxis regression)."""
    from ray_tpu.parallel.distributed import multihost_mesh

    mesh = multihost_mesh(("a", "dp", "b"), (2, 2, 2), dcn_axis="dp")
    assert dict(mesh.shape) == {"a": 2, "dp": 2, "b": 2}
    ids = np.vectorize(lambda d: d.id)(mesh.devices)
    # fixing a,b and varying dp must jump by a whole host block (4 devices)
    for a in range(2):
        for b in range(2):
            assert abs(int(ids[a, 1, b]) - int(ids[a, 0, b])) == 4


@pytest.mark.parametrize("T,window,causal", [(256, 64, True), (300, 100, True), (256, 32, False)])
def test_sliding_window_attention_matches_dense(T, window, causal):
    """Local attention: off-window blocks are skipped; result and grads
    must match a densely-masked reference."""
    from ray_tpu.ops.attention import NEG_INF, sliding_window_attention

    q, k, v = _qkv(T=T, D=32)

    def dense_window(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
        qpos, kpos = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
        mask = kpos > qpos - window
        if causal:
            mask &= kpos <= qpos
        s = jnp.where(mask[None, None], s, NEG_INF)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)

    out = sliding_window_attention(q, k, v, window, causal=causal, block_q=128, block_k=128)
    ref = dense_window(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    g1 = jax.grad(
        lambda a, b, c: jnp.sum(
            sliding_window_attention(a, b, c, window, causal=causal, block_q=128, block_k=128) ** 2
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    g2 = jax.grad(lambda a, b, c: jnp.sum(dense_window(a, b, c) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)
