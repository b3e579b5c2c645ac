"""An all-latent stack that rotates its shared key part, with a dense layer and
routed + shared expert layers of which a share is held (``deepseek_v3`` as
Moonlight-16B-A3B has it), TRAINED, against the plain float32 reference
``benchmark/models/moonlight.py``: forward, loss and every leaf's gradient,
the flash kernel with a value size of its own, the share of the experts under
a cotangent, the routers' load rule, and the refusals that remain. Toy size
(layer 0 dense + 3 expert layers, 16 experts of which 4 held, 2 shared, 4
heads of 16 + 8 / 16, rank 32), float32, CPU.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.models import moonlight  # noqa: E402
from ray_tpu.models import transformer  # noqa: E402
from ray_tpu.models.generation import init_cache, init_paged_cache  # noqa: E402
from ray_tpu.models.transformer import (TransformerConfig, forward, forward_and_load, init_params,  # noqa: E402
                                        latent_attention_expanded, loss_fn, make_train_step, moe_ffn_dropless,
                                        param_specs)
from ray_tpu.ops.attention import flash_attention_with_lse, mha  # noqa: E402

C = dict(model="moonlight", vocab_size=256, hidden_size=64, intermediate_size=128, num_hidden_layers=4,
         num_attention_heads=4, num_key_value_heads=4, hidden_act="silu", max_position_embeddings=128,
         rms_norm_eps=1e-5, rope_theta=50000, tie_word_embeddings=False, attention_bias=False, first_k_dense_replace=1,
         kv_lora_rank=32, q_lora_rank=None, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
         moe_intermediate_size=32, moe_layer_freq=1, n_group=1, topk_group=1, n_routed_experts=4, experts_routed=16,
         experts_held=[4, 8], n_shared_experts=2, norm_topk_prob=True, num_experts_per_tok=3,
         routed_scaling_factor=2.446, scoring_func="sigmoid", topk_method="noaux_tc", num_nextn_predict_layers=0)
F32 = dict(dtype="float32", param_dtype="float32", max_seq_len=128, scan_layers=False)
CFG = moonlight.program_config(C, attention="dense", **F32)
FLASH = moonlight.program_config(C, attention="flash", **F32)
GAMMA = 1e-3


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64).reshape(np.shape(a))
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


@pytest.fixture(scope="module")
def params():
    p = init_params(CFG, jax.random.key(5))
    return {**p, "embed": p["embed"] * 8.0}  # rows of O(1) entries, as a trained table's


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.key(6), (2, 48), 0, C["vocab_size"], jnp.int32)


@pytest.fixture(scope="module")
def reference():
    return moonlight.make_reference(C)


def test_the_tree_is_an_all_latent_stack_with_a_dense_and_an_expert_stack(params):
    assert set(params) == {"embed", "head", "final_norm", "dense_layers", "layers"}
    mixer = {"lat_wq", "lat_wkva", "lat_norm", "lat_wkvb", "lat_wo", "attn_norm", "ffn_norm"}
    assert set(params["dense_layers"]) == mixer | {"w1", "w2", "w3"}
    assert set(params["layers"]) == mixer | {"router", "router_bias", "we1", "we2", "we3", "ws1", "ws2", "ws3"}
    assert params["layers"]["we1"].shape == (3, 4, 64, 32) and params["layers"]["router"].shape == (3, 64, 16)
    assert params["layers"]["ws1"].shape == (3, 64, 64) and params["layers"]["lat_wq"].shape == (3, 64, 4, 24)
    n = sum(int(np.prod(a.shape)) for path, a in jax.tree_util.tree_leaves_with_path(params)
            if a.ndim > 1 and "router_bias" not in str(path) and "norm" not in str(path))
    assert n == moonlight.n_params(C)


@pytest.mark.parametrize("cfg", [CFG, FLASH], ids=["dense", "flash"])
def test_the_forward_and_the_counts_are_the_references(cfg, params, tokens, reference):
    logits, load = forward_and_load(cfg, params, tokens)
    for b in range(tokens.shape[0]):
        want = reference.logits(params, tokens[b])
        assert rel(logits[b], want) < 2e-5
    assert load.shape == (3, 16) and load.dtype == jnp.int32
    np.testing.assert_array_equal(load, sum(np.asarray(reference.counts(params, tokens[b])) for b in range(2)))
    np.testing.assert_array_equal(load.sum(-1), tokens.size * C["num_experts_per_tok"])
    # without the counts: the same logits
    np.testing.assert_array_equal(forward(cfg, params, tokens), logits)


@pytest.mark.parametrize("cfg", [CFG, FLASH], ids=["dense", "flash"])
def test_the_loss_and_every_leafs_gradient_are_the_references(cfg, params, tokens, reference):
    loss, grads = jax.value_and_grad(lambda p: loss_fn(cfg, p, tokens))(params)
    seen = []

    def sink(stack, index, leaf, g):
        got = grads[stack] if leaf is None else grads[stack][leaf][index]
        seen.append((stack, index, leaf))
        assert rel(got, g) < 5e-5, (stack, index, leaf, rel(got, g))

    want = reference.loss_and_grads(params, tokens, sink)
    assert abs(float(loss) - want) / want < 1e-6
    assert abs(reference.loss(params, tokens) - want) < 1e-9
    leaves = {(s, i, k) for s in ("dense_layers", "layers") for k, a in params[s].items() if k != "router_bias"
              for i in range(a.shape[0])} | {("embed", None, None), ("head", None, None), ("final_norm", None, None)}
    assert set(seen) == leaves and len(seen) == len(leaves)
    assert not np.any(np.asarray(grads["layers"]["router_bias"]))  # the bias selects: no gradient reaches it


@pytest.mark.parametrize("control", moonlight.CONTROLS)
def test_each_control_of_the_reference_is_another_function(control, params, tokens, reference):
    other = moonlight.make_reference(C, control)
    # (a router in bfloat16 moves a choice only where two scores lie close: few tokens at this size)
    floor = 1e-5 if control == "bf16_router" else 1e-3
    assert rel(other.logits(params, tokens[0]), reference.logits(params, tokens[0])) > floor


@pytest.mark.parametrize("T", [64, 40, 7])
def test_rotated_latent_attention_through_the_flash_kernel_is_the_expanded_form(T, params):
    layer = jax.tree.map(lambda a: a[1], params["layers"])
    kx, kg = jax.random.split(jax.random.key(T))
    x, g = jax.random.normal(kx, (2, T, 64)), jax.random.normal(kg, (2, T, 64))
    pos = jnp.broadcast_to(jnp.arange(T)[None], (2, T))

    def branch(flash):
        # blocks of 32: T = 64 is two whole blocks, 40 one and a ragged tail, 7 under one
        def f(layer, x):
            return latent_attention_expanded(FLASH, layer, x, transformer._rms_norm(x, layer["attn_norm"]), pos, flash)
        out, vjp = jax.vjp(f, layer, x)
        return out, vjp(g)

    real = transformer.flash_attention_with_lse
    transformer.flash_attention_with_lse = lambda q, k, v, s, c: real(q, k, v, s, c, 32, 32)
    try:
        (o_f, (dl_f, dx_f)), (o_e, (dl_e, dx_e)) = branch(True), branch(False)
    finally:
        transformer.flash_attention_with_lse = real
    assert rel(o_f, o_e) < 1e-5 and rel(dx_f, dx_e) < 1e-5
    for k in ("lat_wq", "lat_wkva", "lat_norm", "lat_wkvb", "lat_wo"):
        assert rel(dl_f[k], dl_e[k]) < 1e-5, k
    # and the rotation is there: without positions' turn the branch is another function
    still = latent_attention_expanded(FLASH, layer, x, transformer._rms_norm(x, layer["attn_norm"]), jnp.zeros_like(pos))
    assert rel(still, o_e) > 1e-3 or T == 1


def test_the_flash_kernel_at_equal_sizes_is_unchanged_and_a_value_size_of_its_own_agrees_with_it():
    ks = jax.random.split(jax.random.key(0), 4)
    q, k = jax.random.normal(ks[0], (1, 2, 48, 24)), jax.random.normal(ks[1], (1, 2, 48, 24))
    v, g = jax.random.normal(ks[2], (1, 2, 48, 16)), jax.random.normal(ks[3], (1, 2, 48, 16))
    pad = lambda a: jnp.pad(a, ((0, 0),) * 3 + ((0, 8),))  # noqa: E731

    def run(q, k, v, g):
        (out, lse), vjp = jax.vjp(lambda q, k, v: flash_attention_with_lse(q, k, v, 0.2, True, 16, 32, None), q, k, v)
        return out, lse, vjp((g, jnp.ones_like(lse)))

    # a size of its own: what the equal-size kernel gives on values zero-padded to the keys' size
    # (PR 51 also pinned the equal-size program's text, letter for letter; PR 52 rewrote the tile
    # bodies, so equal sizes are held to the dense lines instead)
    own, lse, (dq, dk, dv) = run(q, k, v, g)
    eq, lse_eq, (dq_eq, dk_eq, dv_eq) = run(q, k, pad(v), pad(g))
    np.testing.assert_allclose(eq, mha(q, k, pad(v), causal=True, sm_scale=0.2), rtol=1e-5, atol=1e-5)
    assert own.shape == (1, 2, 48, 16) and dv.shape == v.shape and dq.shape == q.shape
    for a, b in ((own, eq[..., :16]), (lse, lse_eq), (dq, dq_eq), (dk, dk_eq), (dv, dv_eq[..., :16])):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    assert not np.any(np.asarray(eq[..., 16:]))


def test_the_share_test_for_training(params):
    """One input and one cotangent: the four shares' routed parts plus the
    shared expert once are the uncut layer, forward and backward."""
    uncut = moonlight.program_config({**C, "n_routed_experts": 16, "experts_held": [0, 16]}, attention="dense", **F32)
    full = jax.tree.map(lambda a: a[0], init_params(uncut, jax.random.key(9))["layers"])
    kx, kg = jax.random.split(jax.random.key(10))
    x, g = jax.random.normal(kx, (2, 24, 64)), jax.random.normal(kg, (2, 24, 64))
    weights = ("we1", "we2", "we3")

    def layer_vjp(cfg, layer):
        out, vjp = jax.vjp(lambda layer, x: moe_ffn_dropless(cfg, layer, x)[0], layer, x)
        return out, *vjp(g)

    out, dl, dx = layer_vjp(uncut, full)
    shared_only = {"w1": full["ws1"], "w3": full["ws3"], "w2": full["ws2"]}
    s_out, s_vjp = jax.vjp(lambda w, x: transformer._dense_ffn(w, x), shared_only, x)
    s_dl, s_dx = s_vjp(g)
    routed_out, routed_dx, router_grad = out - s_out, dx - s_dx, dl["router"]
    parts = []
    for s in range(4):
        cfg = moonlight.program_config({**C, "experts_held": [4 * s, 4 * s + 4], "n_shared_experts": 0},
                                       attention="dense", **F32)
        mine = {k: (v[4 * s: 4 * s + 4] if k in weights else v) for k, v in full.items() if not k.startswith("ws")}
        o, d, dxs = layer_vjp(cfg, mine)
        for k in weights:   # each share's expert-weight gradients are the uncut layer's for those experts
            assert rel(d[k], dl[k][4 * s: 4 * s + 4]) < 1e-5, (s, k)
        assert not np.any(np.asarray(d["router_bias"]))
        parts.append((o, d["router"], dxs))
    assert rel(sum(p[0] for p in parts), routed_out) < 1e-5
    assert rel(sum(p[1] for p in parts), router_grad) < 1e-5     # the shared experts do not touch the router
    assert rel(sum(p[2] for p in parts), routed_dx) < 1e-5
    for k, ws in (("w1", "ws1"), ("w3", "ws3"), ("w2", "ws2")):
        assert rel(s_dl[k], dl[ws]) < 1e-5


def test_the_bias_moves_by_load_and_by_nothing_else(params, tokens):
    assert transformer.ROUTER_BIAS_RATE == GAMMA
    init_state, step = make_train_step(CFG, learning_rate=1e-2)
    state = jax.jit(init_state)(jax.random.key(5))
    assert set(state) == {"params", "opt", "step", "expert_load"}
    assert state["expert_load"].shape == (3, 16) and state["expert_load"].dtype == jnp.uint32
    assert not np.any(np.asarray(state["expert_load"]))
    # the bias is no leaf of the optimizer: two moments a trained leaf and the count, and none for it
    trained = len(jax.tree.leaves(state["params"])) - 1
    assert len(jax.tree.leaves(state["opt"])) == 2 * trained + 1
    seen = np.zeros((3, 16), np.int64)
    for i in range(3):
        before = np.asarray(state["params"]["layers"]["router_bias"])
        router = np.asarray(state["params"]["layers"]["router"])
        state, loss = step(state, tokens)
        load = np.asarray(state["expert_load"]).astype(np.int64) - seen
        seen += load
        np.testing.assert_array_equal(load.sum(-1), tokens.size * C["num_experts_per_tok"])   # tokens x k a layer
        after = np.asarray(state["params"]["layers"]["router_bias"])
        np.testing.assert_array_equal(after, moonlight.bias_step(before, load, GAMMA))
        moved = np.sign(after - before)
        np.testing.assert_array_equal(moved, np.sign(load.mean(-1, keepdims=True) - load))   # toward the mean load
        np.testing.assert_allclose(np.abs(after - before)[moved != 0], GAMMA, rtol=1e-3)
        assert np.abs(np.asarray(state["params"]["layers"]["router"]) - router).max() > 1e-4  # AdamW trains the router
        assert np.isfinite(float(loss))
    assert int(state["step"]) == 3
    # at a large weight decay the bias would shrink by lr x wd x b a step under AdamW: it does not
    assert np.abs(after).max() > 0


def test_a_config_without_the_bias_or_latent_layers_trains_as_it_did():
    dense = TransformerConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64, max_seq_len=32,
                              dtype=jnp.float32, attention="dense", scan_layers=False)
    routed = TransformerConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64, max_seq_len=32,
                               dtype=jnp.float32, attention="dense", num_experts=4, expert_top_k=2)
    toks = jnp.zeros((2, 16), jnp.int32)
    for cfg in (dense, routed):
        init_state, step = make_train_step(cfg)
        state = jax.eval_shape(init_state, jax.random.key(0))
        assert set(state) == {"params", "opt", "step"}
        assert len(jax.tree.leaves(state["opt"])) == 2 * len(jax.tree.leaves(state["params"])) + 1
        text = step.lower(state, toks).as_text()
        assert "ui32" not in text     # no counter rides the step
        # no bias for a load rule to move: the one path returns no counts, and the step's program has none
        assert jax.eval_shape(lambda p: forward_and_load(cfg, p, toks), state["params"])[1] is None


STACK = dict(vocab_size=64, d_model=32, n_layers=4, n_heads=4, d_ff=64, layer_types=("latent",) * 4,
             rope_full_layers=True, latent_rank=16, latent_nope_dim=8, latent_rope_dim=4, latent_value_dim=8)


@pytest.mark.parametrize("bad,named", [
    ({"rope_full_layers": False}, 'without "linear" layers'),
    ({"latent_rope_dim": 0}, "latent_rope_dim"),
    ({"latent_rope_dim": 3}, "the rotation takes pairs"),
    ({"layer_types": ("latent", "latent", "full", "latent")}, '"full" or "sliding" layers beside it'),
    ({"attention": "ring"}, "the ring kernel takes one head size"),
    ({"block_length": 4}, "block_length > 1"),
    ({"qk_norm": True}, "qk_norm"),
    ({"attn_gate": True}, "attn_gate"),
    ({"layer_types": ("linear",) * 3 + ("latent",), "linear_heads": 4, "linear_key_dim": 8, "linear_value_dim": 8},
     "rotates nothing"),
])
def test_the_config_still_refuses_by_name_what_is_not_built(bad, named):
    with pytest.raises(ValueError, match=named):
        TransformerConfig(**{**STACK, **bad})


def test_serving_a_mesh_and_a_hybrid_train_step_refuse_the_stack_by_name():
    with pytest.raises(ValueError, match="all-latent stack"):
        init_cache(CFG, 1, 32)
    with pytest.raises(ValueError, match="all-latent stack"):
        init_paged_cache(CFG, 8, 16)
    with pytest.raises(ValueError, match='"latent" layers'):
        param_specs(CFG)
    kimi = TransformerConfig(vocab_size=64, d_model=32, n_layers=4, n_heads=4, d_ff=64, rope_full_layers=False,
                             layer_types=("linear",) * 3 + ("latent",), linear_heads=4, linear_key_dim=8,
                             linear_value_dim=8, linear_gate="channel", linear_gate_rank=8, latent_rank=16,
                             latent_nope_dim=8, latent_rope_dim=4, latent_value_dim=8, num_experts=4, expert_top_k=2,
                             num_dense_layers=1, router_score="sigmoid", router_bias=True)
    with pytest.raises(ValueError, match='router_bias beside "linear" layers'):
        make_train_step(kimi)
