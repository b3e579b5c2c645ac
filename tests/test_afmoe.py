"""The block beyond Llama's (PR 29): sliding and full layers in one layer
scan, query/key norms, an output gate, sandwich norms, a dropless routed +
shared expert layer behind leading dense layers, an untied head. The three
layer loops are held to the benchmark's own plain reference
(``benchmark/models/afmoe.py``: no second copy of the equations here), at a
toy size, in float32, on seeded weights with every norm gain perturbed."""

from __future__ import annotations

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.models import afmoe  # noqa: E402
from ray_tpu.models import transformer  # noqa: E402
from ray_tpu.models.generation import (  # noqa: E402
    decode_step,
    forward_with_cache,
    init_cache,
    init_paged_cache,
    paged_decode_step,
    paged_forward_with_cache,
)
from ray_tpu.models.transformer import forward, init_params, moe_ffn_dropless  # noqa: E402

TOY = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "intermediate_size": 96, "moe_intermediate_size": 48, "num_hidden_layers": 5, "num_dense_layers": 1,
    "layer_types": ["sliding_attention", "sliding_attention", "sliding_attention", "full_attention",
                    "sliding_attention"],
    "sliding_window": 8, "num_experts": 8, "num_experts_per_tok": 2, "num_shared_experts": 1,
    "score_func": "sigmoid", "route_norm": True, "route_scale": 2.826, "n_group": 1, "topk_group": 1,
    "rms_norm_eps": 1e-5, "rope_theta": 10000, "rope_scaling": None, "hidden_act": "silu",
    "tie_word_embeddings": False, "mup_enabled": True, "vocab_size": 256, "max_position_embeddings": 512,
}
T = 45        # five windows and a bit: every sliding layer hides keys
TOL = 1e-4    # relative Frobenius error of float32 logits


def toy_cfg(**over):
    return afmoe.program_config(TOY, dtype="float32", param_dtype="float32", attention="dense",
                                max_seq_len=64, **over)


def seeded(cfg, seed=0):
    """The program's own initialiser, with every norm gain moved off 1 and the
    router bias made large enough to change the selection."""
    params = init_params(cfg, jax.random.key(seed))
    keys = iter(jax.random.split(jax.random.key(seed + 1), 64))

    def stir(path, leaf):
        name = path[-1].key
        if name.endswith("norm"):
            return leaf * (1.0 + 0.2 * jax.random.normal(next(keys), leaf.shape, leaf.dtype))
        if name == "router_bias":
            return 0.2 * jax.random.normal(next(keys), leaf.shape, leaf.dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(stir, params)


@pytest.fixture(scope="module")
def world():
    cfg = toy_cfg()
    params = seeded(cfg)
    tokens = np.random.default_rng(3).integers(1, TOY["vocab_size"], size=T).astype(np.int32)
    ref_logits, ref_loss = afmoe.make_reference(TOY)
    want = np.asarray(ref_logits(params, jnp.asarray(tokens)))
    return cfg, params, tokens, want, ref_loss


def rel(got, want):
    return float(np.linalg.norm(np.asarray(got) - want) / np.linalg.norm(want))


def via_forward(cfg, params, tokens):
    return forward(cfg, params, jnp.asarray(tokens)[None])[0]


def via_dense_cache(cfg, params, tokens, split=29):
    """Prefill ``split`` tokens, then decode the rest one by one."""
    cache = init_cache(cfg, 1, 64)
    row = np.zeros((1, 32), np.int32)
    row[0, :split] = tokens[:split]
    pos = jnp.arange(32)[None]
    logits, cache = forward_with_cache(cfg, params, cache, jnp.asarray(row), pos, use_decode_kernel=False)
    out = [logits[0, :split]]
    for t in range(split, len(tokens)):
        lg, cache = decode_step(cfg, params, cache, jnp.asarray(tokens[t : t + 1]), jnp.asarray([t]),
                                use_decode_kernel=True)  # the Pallas dense decode kernel, interpreted
        out.append(lg)
    return jnp.concatenate(out)


def via_paged(cfg, params, tokens, split=37, chunk=16, page=4, prefill_kernel=False):
    """Chunked prefill at a traced start (the engine's ``_prefill_chunk``;
    ``prefill_kernel``: through the paged prefill kernel, as on the chip),
    then decode through the paged kernel in interpret mode; chunks, pages and
    the window all end at different places."""
    M = 64 // page
    cache = init_paged_cache(cfg, M + 1, page)
    bt = jnp.asarray(np.arange(1, M + 1, dtype=np.int32)[None])

    @jax.jit
    def prefill_chunk(cache, toks, start, length):
        valid = (jnp.arange(chunk) < length)[None]
        return paged_forward_with_cache(cfg, params, cache, bt, toks, start + jnp.arange(chunk)[None],
                                        valid=valid, use_decode_kernel=prefill_kernel)

    out = []
    for start in range(0, split, chunk):
        piece = tokens[start : min(start + chunk, split)]
        toks = np.zeros((1, chunk), np.int32)
        toks[0, : len(piece)] = piece
        logits, cache = prefill_chunk(cache, jnp.asarray(toks), jnp.int32(start), jnp.int32(len(piece)))
        out.append(logits[0, : len(piece)])
    for t in range(split, len(tokens)):
        lg, cache = paged_decode_step(cfg, params, cache, jnp.asarray(tokens[t : t + 1]), jnp.asarray([t]), bt,
                                      use_decode_kernel=True)
        out.append(lg)
    return jnp.concatenate(out)


PATHS = {"forward": via_forward, "forward_with_cache": via_dense_cache, "paged_forward_with_cache": via_paged,
         "paged_prefill_kernel": functools.partial(via_paged, prefill_kernel=True)}


@pytest.mark.parametrize("path", sorted(PATHS))
def test_each_layer_loop_computes_the_reference(world, path):
    cfg, params, tokens, want, _ = world
    assert rel(PATHS[path](cfg, params, tokens), want) < TOL


def test_unrolled_layers_and_the_loss_agree_with_the_reference(world):
    cfg, params, tokens, want, ref_loss = world
    unrolled = dataclasses.replace(cfg, scan_layers=False)
    assert rel(via_forward(unrolled, params, tokens), want) < TOL
    batch = jnp.asarray(np.stack([tokens[:32], tokens[13:]]))
    assert abs(float(transformer.loss_fn(cfg, params, batch)) / ref_loss(params, batch) - 1) < 1e-5


def _bias_in_weights(cfg, layer, x2):
    logits = x2.astype(jnp.float32) @ layer["router"].astype(jnp.float32)
    choose = jax.nn.sigmoid(logits) + layer["router_bias"]
    weights, experts = jax.lax.top_k(choose, cfg.expert_top_k)
    weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return experts.astype(jnp.int32), weights * cfg.route_scale


def _norm_before_branch(cfg, layer, x, h, o):
    o = o * jax.nn.sigmoid(jnp.einsum("btd,dhk->bthk", h, layer["wg"]))
    moved = transformer._rms_norm(x, layer["post_attn_norm"], cfg.norm_eps)  # the norm on the residual stream
    return moved + jnp.einsum("bthk,hkd->btd", o, layer["wo"])


# each fault is the program computing a neighbouring function of the SAME weights
FAULTS = {
    "no window on sliding layers": dict(cfg=dict(sliding_window=10**6)),
    "RoPE on a full layer": dict(cfg=dict(rope_full_layers=True)),
    "softmax for sigmoid": dict(cfg=dict(router_score="softmax")),
    "no route_norm": dict(cfg=dict(route_norm=False)),
    "no route_scale": dict(cfg=dict(route_scale=1.0)),
    "the bias added to the weights": dict(patch=("route", _bias_in_weights)),
    "no output gate": dict(cfg=dict(attn_gate=False)),
    "no query/key norm": dict(cfg=dict(qk_norm=False)),
    "no shared expert": dict(cfg=dict(num_shared_experts=0)),
    "post-norm moved before the branch": dict(patch=("block_attn_out", _norm_before_branch)),
    "a tied head": dict(cfg=dict(tie_embeddings=True)),
}
# every fault through forward(); those a cached loop could make on its own (the
# mask, the per-layer kinds, the block's wiring) through the cached loops too
CASES = [(f, "forward") for f in FAULTS] + [
    ("no window on sliding layers", "paged_forward_with_cache"),
    ("no window on sliding layers", "forward_with_cache"),
    ("no window on sliding layers", "paged_prefill_kernel"),
    ("RoPE on a full layer", "paged_prefill_kernel"),
    ("RoPE on a full layer", "paged_forward_with_cache"),
    ("no output gate", "paged_forward_with_cache"),
    ("the bias added to the weights", "paged_forward_with_cache"),
]


@pytest.mark.parametrize("fault,path", CASES)
def test_a_neighbouring_function_fails_the_tolerance(world, monkeypatch, fault, path):
    cfg, params, tokens, want, _ = world
    spec = FAULTS[fault]
    if "patch" in spec:
        name, fn = spec["patch"]
        monkeypatch.setattr(transformer, name, fn)
        from ray_tpu.models import generation

        if hasattr(generation, name):
            monkeypatch.setattr(generation, name, fn)
    wrong = dataclasses.replace(cfg, **spec.get("cfg", {}))
    assert rel(PATHS[path](wrong, params, tokens), want) > 10 * TOL


# --- (c) the expert layer alone -------------------------------------------------------------
def _expert_layer_reference(cfg, layer, x2):
    """Every expert on every token, times the router's weight for it (zero unless chosen)."""
    w = {"router": layer["router"], "expert_bias": layer["router_bias"]}
    weights = afmoe._route(x2, w, k=cfg.expert_top_k, score_func=cfg.router_score, route_norm=cfg.route_norm,
                           route_scale=cfg.route_scale)
    out = afmoe._mlp(x2, {"gate": layer["ws3"], "up": layer["ws1"], "down": layer["ws2"]})
    for e in range(cfg.num_experts):
        out = out + weights[:, e : e + 1] * afmoe._mlp(
            x2, {"gate": layer["we3"][e], "up": layer["we1"][e], "down": layer["we2"][e]})
    return out, weights


@pytest.mark.parametrize("favoured", [[5], [1, 6]], ids=["one expert takes all", "k experts, the rest empty"])
def test_the_expert_layer_drops_nothing_at_any_imbalance(world, favoured):
    cfg, params, _, _, _ = world
    layer = jax.tree.map(lambda a: a[0], params["layers"])
    bias = jnp.full((cfg.num_experts,), -50.0).at[jnp.asarray(favoured)].set(50.0)
    if len(favoured) < cfg.expert_top_k:  # the runner-up is the same for every token too
        bias = bias.at[0].set(20.0)
    layer = dict(layer, router_bias=bias)
    x = jax.random.normal(jax.random.key(9), (3, 11, cfg.d_model))
    got, counts = moe_ffn_dropless(cfg, layer, x)
    want, weights = _expert_layer_reference(cfg, layer, x.reshape(-1, cfg.d_model))
    assert rel(got.reshape(-1, cfg.d_model), np.asarray(want)) < 1e-5
    hit = sorted(int(e) for e in np.flatnonzero(np.asarray(counts)))
    assert hit == sorted(set(favoured) | ({0} if len(favoured) < cfg.expert_top_k else set()))
    assert int(counts.sum()) == 33 * cfg.expert_top_k and int(counts.max()) == 33  # nothing dropped
    assert np.count_nonzero(np.asarray(weights)) == 33 * cfg.expert_top_k


def test_the_grouped_products_take_t_times_k_rows(world, monkeypatch):
    cfg, params, _, _, _ = world
    layer = jax.tree.map(lambda a: a[0], params["layers"])
    seen = []
    real = transformer.grouped_matmul
    monkeypatch.setattr(transformer, "grouped_matmul", lambda rows, w, g: (seen.append(rows.shape), real(rows, w, g))[1])
    x = jax.random.normal(jax.random.key(2), (2, 7, cfg.d_model))
    _, counts = moe_ffn_dropless(cfg, layer, x, valid=jnp.asarray([[True] * 7, [True] * 3 + [False] * 4]))
    rows = 14 * cfg.expert_top_k  # the FLOPs follow k, not E
    assert seen == [(rows, cfg.d_model), (rows, cfg.d_model), (rows, cfg.expert_width)]
    assert int(counts.sum()) == 10 * cfg.expert_top_k  # padding computes, it is not counted


# --- (d) the window in the paged decode kernel ----------------------------------------------
@pytest.mark.parametrize("window", [0, 5, 16, 23, 40, 47])
def test_paged_decode_kernel_window_matches_its_xla_reference(window):
    from ray_tpu.ops.decode_attention import paged_decode_attention

    B, H, Hkv, D, bs, M, L = 5, 8, 2, 32, 8, 6, 2
    rng = np.random.default_rng(window)
    kp, vp = (jnp.asarray(rng.normal(size=(L, B * M + 1, bs, Hkv * D)), jnp.float32) for _ in range(2))
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    bt = jnp.asarray(rng.permutation(np.arange(1, B * M + 1)).reshape(B, M).astype(np.int32))
    lengths = jnp.asarray([3, 23, 24, 41, 48], jnp.int32)  # under, at and over the windows; 23 is no multiple of 8
    kw = dict(sm_scale=0.2, window=jnp.int32(window))
    got = paged_decode_attention(q, kp, vp, bt, lengths, jnp.int32(1), use_kernel=True, **kw)
    want = paged_decode_attention(q, kp, vp, bt, lengths, jnp.int32(1), use_kernel=False, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    if window:
        full = paged_decode_attention(q, kp, vp, bt, lengths, jnp.int32(1), use_kernel=False, sm_scale=0.2)
        assert np.abs(np.asarray(full) - np.asarray(want))[3:].max() > 1e-3  # the long rows lost keys
        np.testing.assert_allclose(np.asarray(full)[0], np.asarray(want)[0], rtol=1e-6)  # the short row none
    else:
        unwindowed = paged_decode_attention(q, kp, vp, bt, lengths, jnp.int32(1), use_kernel=True, sm_scale=0.2)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(unwindowed))


def test_dense_decode_kernel_window_matches_the_masked_einsum(world):
    from ray_tpu.ops.decode_attention import decode_attention

    rng = np.random.default_rng(1)
    B, H, Hkv, D, S = 3, 4, 2, 32, 128
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(B, Hkv, S, D)), jnp.float32) for _ in range(2))
    lengths = jnp.asarray([4, 50, 128], jnp.int32)
    got = decode_attention(q, k, v, lengths, window=jnp.int32(9))
    pos = jnp.arange(S)[None]
    vis = (pos < lengths[:, None]) & (pos >= lengths[:, None] - 9)
    s = jnp.einsum("bgrd,bgsd->bgrs", q.reshape(B, Hkv, 2, D), k) / np.sqrt(D)
    p = jax.nn.softmax(jnp.where(vis[:, None, None], s, -1e30), -1)
    want = jnp.einsum("bgrs,bgsd->bgrd", p, v).reshape(B, H, D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


# --- sharding -------------------------------------------------------------------------------
def test_the_new_tree_shards_over_ep_and_gives_the_unsharded_logits(world):
    cfg, params, tokens, want, _ = world
    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip("needs 4 host devices")
    from jax.sharding import Mesh

    from ray_tpu.models.transformer import param_specs, shard_params

    mesh = Mesh(np.array(devs[:4]).reshape(2, 2), ("dp", "tp"))
    specs = param_specs(cfg)
    assert jax.tree.structure(specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)) == jax.tree.structure(params)
    sharded = shard_params(params, mesh, cfg)  # ep folds into dp: 8 experts over 2
    assert sharded["layers"]["we1"].sharding.spec[1] == "dp"
    assert not sharded["layers"]["we1"].is_fully_replicated
    got = jax.jit(lambda p, t: forward(cfg, p, t))(sharded, jnp.asarray(tokens)[None])[0]
    assert rel(got, want) < TOL


# --- no silent path -------------------------------------------------------------------------
def test_switches_of_the_dropless_layer_are_refused_elsewhere():
    from ray_tpu.models.transformer import TransformerConfig

    with pytest.raises(ValueError, match="dropless"):
        TransformerConfig(num_experts=4, router_score="sigmoid", moe_capacity_factor=1.0)
    with pytest.raises(ValueError, match="dropless"):
        TransformerConfig(num_experts=4, num_shared_experts=1, moe_capacity_factor=1.0)
    with pytest.raises(ValueError, match="sliding_window"):
        TransformerConfig(n_layers=2, layer_types=("sliding", "full"))
    with pytest.raises(ValueError, match="layer_types"):
        TransformerConfig(n_layers=3, layer_types=("full", "full"))


def test_defaults_keep_the_old_tree_and_head_dim():
    from ray_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64)
    assert cfg.head_dim == 8 and cfg.layer_windows is None and cfg.dense_stack == 0
    params = init_params(cfg, jax.random.key(0))
    assert sorted(params) == ["embed", "final_norm", "layers"]
    assert sorted(params["layers"]) == ["attn_norm", "ffn_norm", "w1", "w2", "w3", "wk", "wo", "wq", "wv"]


def test_arithmetic_counts_the_program_tree(world):
    cfg, params, _, _, _ = world
    assert afmoe.n_params(TOY) == sum(x.size for x in jax.tree.leaves(params))
    published = dict(TOY, hidden_size=2048, num_attention_heads=32, num_key_value_heads=4, head_dim=128,
                     intermediate_size=6144, moe_intermediate_size=1024, num_experts=128, num_experts_per_tok=8,
                     vocab_size=200192, num_hidden_layers=32, num_dense_layers=2,
                     layer_types=(["sliding_attention"] * 3 + ["full_attention"]) * 8)
    assert 26.0e9 < afmoe.n_params(published) < 26.2e9           # "26B"
    assert 3.0e9 < afmoe.active_params_per_token(published) + 200192 * 2048 < 3.6e9   # "A3B", both tables counted
    one = afmoe.expert_step_bytes(published, experts_hit=1)
    assert one == 3 * 2048 * 1024 * 2
