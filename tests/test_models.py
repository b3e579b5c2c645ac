"""Model-layer tests: forward shapes, training convergence, sharded train
step over the virtual 8-device mesh (dp/sp/tp + ep), pipeline dryrun."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import (
    MLPConfig,
    TransformerConfig,
    forward,
    init_params,
    loss_fn,
    make_train_step,
    mlp_apply,
    mlp_init,
)

TINY = TransformerConfig(vocab_size=128, d_model=32, n_layers=2, n_heads=2, d_ff=64, attention="dense")


def test_forward_shape():
    params = init_params(TINY, jax.random.key(0))
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = forward(TINY, params, tokens)
    assert logits.shape == (2, 16, 128)
    assert logits.dtype == jnp.float32
    assert np.isfinite(np.asarray(logits)).all()


def test_init_is_not_a_confident_token_copier():
    """Tied-embedding init regression (a std-1 embedding made diag logits
    ~|E_t|^2 ~ d): init logits must be O(1), random-token loss must sit
    near the uniform baseline ln(V) (the bug measured ~26), and
    repeated-token loss must not be ~zero (the bug measured 8e-6 — a
    CONFIDENT copier).  A mild copy preference in the argmax is inherent
    to tied embeddings + residual streams and is fine."""
    from ray_tpu.models.transformer import loss_fn

    params = init_params(TINY, jax.random.key(0))
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 128, (2, 32)), jnp.int32)
    logits = np.asarray(forward(TINY, params, tokens))
    # O(1) logits at init (the copier produced ~d-scale diagonals)
    assert np.abs(logits).max() < 25.0, np.abs(logits).max()
    loss = float(loss_fn(TINY, params, tokens))
    assert 0.5 * np.log(128) < loss < 2.5 * np.log(128), loss
    # repeated tokens are predictable-but-not-free: a confident copier
    # scores ~0 here
    ones_loss = float(loss_fn(TINY, params, jnp.ones((2, 32), jnp.int32)))
    assert ones_loss > 0.05, f"near-zero repeated-token loss {ones_loss} (copier init)"


def test_loss_decreases():
    init_state, step = make_train_step(TINY, learning_rate=1e-2)
    state = init_state(jax.random.key(0))
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 128, (4, 17)), jnp.int32)
    first = None
    for _ in range(10):
        state, loss = step(state, tokens)
        if first is None:
            first = float(loss)
    assert float(loss) < first


def test_moe_forward():
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=1, n_heads=2, d_ff=64,
        num_experts=4, expert_top_k=2, attention="dense",
    )
    params = init_params(cfg, jax.random.key(1))
    logits = forward(cfg, params, jnp.zeros((2, 8), jnp.int32))
    assert np.isfinite(np.asarray(logits)).all()


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
def test_sharded_train_step():
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 2, 2), ("dp", "sp", "tp"))
    cfg = TransformerConfig(
        vocab_size=128, d_model=32, n_layers=2, n_heads=4, d_ff=64,
        num_experts=4, attention="dense",
    )
    with mesh:
        init_state, step = make_train_step(cfg, mesh=mesh, ep="dp")
        state = init_state(jax.random.key(0))
        tokens = step.shard_batch(
            jnp.asarray(np.random.default_rng(0).integers(0, 128, (4, 16)), jnp.int32)
        )
        state, loss = step(state, tokens)
        assert np.isfinite(float(loss))
        # param shardings actually landed on the tp axis
        wq = state["params"]["layers"]["wq"]
        assert "tp" in str(wq.sharding.spec)


def test_sharded_matches_single_device():
    """Same seed/batch: the sharded loss must equal the unsharded loss."""
    from jax.sharding import Mesh

    cfg = TINY
    tokens = jnp.asarray(np.random.default_rng(2).integers(0, 128, (4, 16)), jnp.int32)
    params = init_params(cfg, jax.random.key(3))
    ref = float(loss_fn(cfg, params, tokens))
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 2, 2), ("dp", "sp", "tp"))
    with mesh:
        from ray_tpu.models.transformer import shard_params
        from jax.sharding import NamedSharding, PartitionSpec as P

        sp = shard_params(params, mesh, cfg)
        toks = jax.device_put(tokens, NamedSharding(mesh, P("dp", None)))
        got = float(jax.jit(lambda p, t: loss_fn(cfg, p, t))(sp, toks))
    # bf16 matmuls: collective reduction order differs across shardings
    assert abs(got - ref) / abs(ref) < 1e-3


def test_mlp():
    cfg = MLPConfig(in_dim=8, hidden=16, depth=2, out_dim=4)
    params = mlp_init(cfg, jax.random.key(0))
    out = mlp_apply(params, jnp.ones((3, 8)))
    assert out.shape == (3, 4)


@pytest.mark.full
def test_graft_entry_hooks():
    import __graft_entry__ as g

    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    assert out.shape[-1] == 2048
    g.dryrun_multichip(8)


@pytest.mark.full
def test_ring_attention_mode_matches_dense():
    """attention="ring" (sp-sharded ring attention in the model) must agree
    with the dense einsum path on loss and gradients."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from ray_tpu.models.transformer import TransformerConfig, make_train_step

    devices = np.array(jax.devices()[:4]).reshape(2, 2, 1)
    mesh = Mesh(devices, ("dp", "sp", "tp"))
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 128, (4, 32)), jnp.int32
    )

    losses = {}
    params_after = {}
    for mode in ("dense", "ring"):
        cfg = TransformerConfig(
            vocab_size=128, d_model=32, n_layers=2, n_heads=2, d_ff=64,
            max_seq_len=64, attention=mode, remat=False,
        )
        with mesh:
            init_state, step = make_train_step(cfg, mesh=mesh)
            state = init_state(jax.random.key(0))
            state, loss = step(state, step.shard_batch(tokens))
            losses[mode] = float(loss)
            params_after[mode] = jax.tree.map(np.asarray, state["params"])
    assert losses["ring"] == pytest.approx(losses["dense"], rel=1e-3)
    # the backward pass must agree too, not just the forward loss
    flat_d = jax.tree.leaves(params_after["dense"])
    flat_r = jax.tree.leaves(params_after["ring"])
    for a, b in zip(flat_d, flat_r):
        np.testing.assert_allclose(a, b, rtol=2e-2, atol=2e-3)


# --------------------------------------------------------------------------
# attention="ring": the sequence's placement (parallel/ring.py)
# --------------------------------------------------------------------------
def _ring_tiny(attention):
    return TransformerConfig(
        vocab_size=128, d_model=32, n_layers=2, n_heads=2, d_ff=64, max_seq_len=64,
        attention=attention, remat=False, dtype=jnp.float32)


@pytest.mark.parametrize("mesh_shape, T, layout", [
    ((1, 2, 2), 16, "zigzag"),        # dp1 x sp2 x tp2, as the four-chip training cell
    ((1, 2, 2), 18, "contiguous"),    # a shard of 9 does not cut in two
    ((1, 2, 2), 15, "contiguous"),    # nor does one padded for an odd length
    ((1, 4, 1), 16, "zigzag"),
    ((1, 4, 1), 20, "contiguous"),
], ids=lambda x: "x".join(map(str, x)) if isinstance(x, tuple) else str(x))
def test_ring_mode_equals_dense_in_either_placement(mesh_shape, T, layout):
    """Loss and gradients of ``attention="ring"`` under a mesh equal the
    dense path's whichever placement the length gives; the train step names
    the placement it traced; ``forward`` from outside takes and returns
    natural order."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(mesh_shape), ("dp", "sp", "tp"))
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 128, (2, T)), jnp.int32)
    dense, ring = _ring_tiny("dense"), _ring_tiny("ring")
    params = init_params(dense, jax.random.key(0))
    act = NamedSharding(mesh, P("dp", "sp", None))
    want = jax.jit(jax.value_and_grad(lambda p: loss_fn(dense, p, tokens)))(params)
    got = jax.jit(jax.value_and_grad(lambda p: loss_fn(ring, p, tokens, act_spec=act, mesh=mesh, sp_axis="sp")))(params)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
    logits = jax.jit(lambda p, t: forward(ring, p, t, act_spec=act, mesh=mesh, sp_axis="sp"))(params, tokens)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(jax.jit(lambda p, t: forward(dense, p, t))(params, tokens)), atol=2e-4)

    for cfg, traced in ((ring, layout), (dense, None)):
        init_state, step = make_train_step(cfg, mesh=mesh)
        assert step.ring_layout is None
        step.lower(jax.eval_shape(init_state, jax.random.key(0)), jax.ShapeDtypeStruct(tokens.shape, tokens.dtype))
        assert step.ring_layout == traced


def test_without_a_mesh_the_loss_is_the_program_it_was():
    """The one-chip training path (``smollm2-1.7b-train-l8``) places nothing:
    with no mesh ``loss_fn`` lowers to the same text as the loss written
    without the placement (the parent's body), ``attention="ring"`` included."""
    from ray_tpu.models.transformer import ring_placement
    from ray_tpu.parallel._compat import spmd_roll

    def natural_loss(cfg, params, tokens):
        B, T = tokens.shape
        logits = forward(cfg, params, tokens)
        targets = spmd_roll(tokens, -1, axis=1)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        mask = (jnp.arange(T) < T - 1).astype(nll.dtype)[None, :]
        return jnp.sum(nll * mask) / (B * (T - 1))

    tokens = jnp.zeros((2, 16), jnp.int32)
    for cfg in (TINY, _ring_tiny("ring")):
        assert ring_placement(cfg, None, None, 16) is None
        params = init_params(cfg, jax.random.key(0))
        texts = [jax.jit(jax.value_and_grad(lambda p, f=f: f(cfg, p, tokens))).lower(params).as_text()
                 for f in (loss_fn, natural_loss)]
        assert texts[0] == texts[1]


# --------------------------------------------------------------------------
# ViT (image model family)
# --------------------------------------------------------------------------
def _vit_tiny():
    from ray_tpu.models import ViTConfig

    return ViTConfig(
        image_size=16, patch_size=4, channels=3, num_classes=10,
        d_model=32, n_layers=2, n_heads=2, d_ff=64, attention="dense", remat=False,
    )


def test_vit_forward_shape_and_patchify():
    from ray_tpu.models import init_vit_params, patchify, vit_forward

    cfg = _vit_tiny()
    params = init_vit_params(cfg, jax.random.key(0))
    images = jnp.asarray(np.random.default_rng(0).standard_normal((2, 16, 16, 3)), jnp.float32)
    patches = patchify(cfg, images)
    assert patches.shape == (2, 16, 48)
    # patchify must preserve pixel content (first patch == top-left block)
    first = np.asarray(images[0, :4, :4, :]).reshape(-1)
    np.testing.assert_allclose(np.asarray(patches[0, 0]), first, rtol=1e-6)
    logits = vit_forward(cfg, params, images)
    assert logits.shape == (2, 10) and logits.dtype == jnp.float32
    assert np.isfinite(np.asarray(logits)).all()


def test_vit_trains():
    from ray_tpu.models import make_vit_train_step

    cfg = _vit_tiny()
    init_state, step = make_vit_train_step(cfg, learning_rate=1e-2)
    state = init_state(jax.random.key(0))
    rng = np.random.default_rng(0)
    images = jnp.asarray(rng.standard_normal((8, 16, 16, 3)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 10, (8,)), jnp.int32)
    first = None
    for _ in range(30):
        state, loss = step(state, images, labels)
        if first is None:
            first = float(loss)
    assert float(loss) < first * 0.5  # memorizes the tiny batch


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 virtual devices")
def test_vit_sharded_train_step():
    from jax.sharding import Mesh

    from ray_tpu.models import make_vit_train_step

    cfg = _vit_tiny()
    devices = np.array(jax.devices()[:4]).reshape(2, 2)
    mesh = Mesh(devices, ("dp", "tp"))
    with mesh:
        init_state, step = make_vit_train_step(cfg, mesh=mesh)
        state = init_state(jax.random.key(0))
        rng = np.random.default_rng(0)
        images, labels = step.shard_batch(
            jnp.asarray(rng.standard_normal((4, 16, 16, 3)), jnp.float32),
            jnp.asarray(rng.integers(0, 10, (4,)), jnp.int32),
        )
        state, loss = step(state, images, labels)
        assert np.isfinite(float(loss))


def test_shard_params_typo_axis_raises():
    from jax.sharding import Mesh

    from ray_tpu.models.transformer import shard_params

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=1, n_heads=2, d_ff=64)
    params = init_params(cfg, jax.random.key(0))
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    with pytest.raises(ValueError, match="not a mesh axis"):
        shard_params(params, mesh, cfg, tp="model")  # typo'd axis name


def test_shard_params_moe_on_dp_less_mesh():
    """Implicit ep->dp default must not raise on a tp-only mesh."""
    from jax.sharding import Mesh

    from ray_tpu.models.transformer import shard_params

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=1, n_heads=2, d_ff=64,
        num_experts=2, expert_top_k=1,
    )
    params = init_params(cfg, jax.random.key(0))
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    sharded = shard_params(params, mesh, cfg, tp="tp")
    assert sharded["layers"]["we1"].shape == params["layers"]["we1"].shape


def test_moe_capacity_matches_dense_when_ample():
    """With capacity >= every assignment, the GShard dispatch equals the
    dense-dispatch formulation bit-for-bit-ish."""
    base = dict(
        vocab_size=64, d_model=32, n_layers=1, n_heads=2, d_ff=64,
        num_experts=4, expert_top_k=2, attention="dense", dtype=jnp.float32,
    )
    dense_cfg = TransformerConfig(**base)
    cap_cfg = TransformerConfig(**base, moe_capacity_factor=8.0)  # no drops
    params = init_params(dense_cfg, jax.random.key(0))
    toks = jnp.asarray(np.random.default_rng(1).integers(0, 64, (2, 12)), jnp.int32)
    a = forward(dense_cfg, params, toks)
    b = forward(cap_cfg, params, toks)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)


def test_moe_capacity_tight_still_finite_and_trains():
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=1, n_heads=2, d_ff=64,
        num_experts=4, expert_top_k=2, attention="dense",
        moe_capacity_factor=1.0,  # tight: some tokens drop
    )
    init_state, step = make_train_step(cfg, learning_rate=1e-2)
    state = init_state(jax.random.key(2))
    toks = jnp.asarray(np.random.default_rng(3).integers(0, 64, (4, 13)), jnp.int32)
    first = None
    for _ in range(8):
        state, loss = step(state, toks)
        first = first if first is not None else float(loss)
    assert np.isfinite(float(loss)) and float(loss) < first


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
def test_moe_capacity_sharded_train_step():
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 2, 2), ("dp", "sp", "tp"))
    cfg = TransformerConfig(
        vocab_size=128, d_model=32, n_layers=2, n_heads=4, d_ff=64,
        num_experts=4, expert_top_k=2, attention="dense",
        moe_capacity_factor=2.0,
    )
    with mesh:
        init_state, step = make_train_step(cfg, mesh=mesh, ep="dp")
        state = init_state(jax.random.key(0))
        toks = step.shard_batch(
            jnp.asarray(np.random.default_rng(0).integers(0, 128, (4, 16)), jnp.int32)
        )
        state, loss = step(state, toks)
        assert np.isfinite(float(loss))


@pytest.mark.full
def test_unrolled_and_dots_remat_match_scan():
    """The headline TPU bench runs remat="dots" + scan_layers=False; this
    CPU parity check pins that exact configuration to the default scan
    path: identical logits and loss gradients."""
    import dataclasses

    tokens = jnp.asarray(np.random.default_rng(3).integers(0, 128, (2, 16)), jnp.int32)
    params = init_params(TINY, jax.random.key(1))
    ref_logits = np.asarray(forward(TINY, params, tokens))
    ref_grad = jax.grad(lambda p: loss_fn(TINY, p, tokens))(params)

    # bf16 activations: scan vs unrolled reassociates fusions, so agreement
    # is bounded by bf16 rounding (~4e-3 relative), not float32 epsilon
    for remat, scan in ((False, False), ("dots", False), ("dots", True), (True, False)):
        cfg = dataclasses.replace(TINY, remat=remat, scan_layers=scan)
        np.testing.assert_allclose(
            np.asarray(forward(cfg, params, tokens)), ref_logits, rtol=0.05, atol=0.02
        )
        g = jax.grad(lambda p: loss_fn(cfg, p, tokens))(params)
        for a, b in zip(jax.tree_util.tree_leaves(ref_grad), jax.tree_util.tree_leaves(g)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0.05, atol=0.02)

    with pytest.raises(ValueError):
        dataclasses.replace(TINY, remat="Dots")  # typo must not silently full-remat
