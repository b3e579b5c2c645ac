"""A config with "conv" layers (LFM2: a gated short convolution as a layer's
whole mixer beside a few GQA layers, each over a dense or an expert FFN, in a
layer order that is no (k x linear, full) period): the layer plan read from
``layer_types``, ``forward``, chunked paged prefill + decode and ``LLMEngine``
against the plain float32 reference of ``benchmark/models/lfm2_moe.py`` at toy
widths, tails included; the router's published ``+ 1e-6``; the two older
hybrids through the generalised loop, bit for bit what the parent's loop
gave; and every combination not built for the kind refused by name.

Limits: everything here is float32 on the CPU, where program and reference
differ by the order of their sums only: logits and tails agree to ~5e-7
(measured), the limit 1e-4 leaves two hundred times that and is a thousand
times under what any fault below moves them by (a lost tail: order one).
"""

from __future__ import annotations

import dataclasses
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import system  # noqa: E402
from benchmark.models import lfm2_moe  # noqa: E402
from ray_tpu.models import generation, transformer  # noqa: E402
from ray_tpu.models.generation import (copy_sequence_state, init_paged_cache, init_sequence_state,  # noqa: E402
                                       paged_forward_counted, zero_sequence_state)
from ray_tpu.models.transformer import TransformerConfig, forward, init_params, plan_layers  # noqa: E402
from ray_tpu.serve.llm import LLMEngine  # noqa: E402

PUBLISHED = ("conv conv full_attention conv conv conv full_attention conv conv conv full_attention conv conv conv "
             "full_attention conv conv conv full_attention conv conv full_attention conv conv").split()
TOL = 1e-4
BS = 16


def file_of(layers: int, **more):
    """A configuration file at toy widths: the published order's first ``layers``."""
    return {"model": "lfm2_moe", "conv_L_cache": 3, "conv_bias": False, "hidden_size": 32, "intermediate_size": 64,
            "layer_types": PUBLISHED[:layers], "max_position_embeddings": 256, "moe_intermediate_size": 16,
            "norm_eps": 1e-5, "norm_topk_prob": True, "num_attention_heads": 4, "num_dense_layers": 2, "num_experts": 8,
            "num_experts_per_tok": 4, "num_hidden_layers": layers, "num_key_value_heads": 2, "rope_theta": 1000000,
            "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 128, "tie_embedding": True, **more}


def built(layers: int, seed: int = 0):
    c = file_of(layers)
    cfg = lfm2_moe.program_config(c, dtype="float32", param_dtype="float32")
    return c, cfg, init_params(cfg, jax.random.key(seed))


@pytest.fixture(scope="module")
def cut():
    return built(14)


def rel(a, b):
    return float(jnp.linalg.norm(jnp.asarray(a) - jnp.asarray(b)) / jnp.linalg.norm(jnp.asarray(b)))


def prompt_of(n, seed=0, vocab=128):
    return np.random.default_rng(seed).integers(1, vocab, size=n).tolist()


# ---------------------------------------------------------------------------
# the plan, read from the list
# ---------------------------------------------------------------------------
KINDS = {"conv": "conv", "full_attention": "full"}


@pytest.mark.parametrize("kinds,dense,plan", [
    (tuple(KINDS[t] for t in PUBLISHED), 2, (1, 4, 5)),          # conv | (conv full conv conv) x 5 | full conv conv
    (tuple(KINDS[t] for t in PUBLISHED[:14]), 2, (2, 4, 3)),     # conv conv | (full conv conv conv) x 3
    (tuple(KINDS[t] for t in PUBLISHED[:14]), 0, (0, 4, 3)),     # no dense layer to keep out: (conv conv full conv) x 3 | conv conv
    (("linear",) * 3 + ("full",), 0, (0, 4, 1)),
    ((("linear",) * 3 + ("full",)) * 4, 0, (0, 4, 4)),           # Olmo-Hybrid's
    ((("linear",) * 3 + ("latent",)) * 2, 1, (0, 4, 2)),         # Kimi-Linear's: its first place dense once
    (("linear", "full") * 2, 0, (0, 2, 2)),
    (("conv", "conv", "full", "conv", "full", "conv", "conv"), 2, (2, 2, 2)),
    (("conv", "full"), 0, (0, 2, 1)),
], ids=["published-24", "cut-14", "cut-14-no-dense", "one-period", "olmo", "kimi", "pairs", "rehearsal", "no-repeat"])
def test_the_plan_is_read_from_the_list(kinds, dense, plan):
    assert plan_layers(kinds, dense) == plan
    lead, period, repeats = plan
    body = kinds[lead : lead + period]
    assert set(body) == set(kinds) and kinds[lead : lead + period * repeats] == body * repeats


def test_the_config_builds_the_published_order_and_its_cut(cut):
    whole = lfm2_moe.program_config(file_of(24), dtype="float32", param_dtype="float32")
    assert whole.plan == (1, 4, 5) and (whole.conv_layers, whole.kv_layers, whole.linear_layers) == (18, 6, 0)
    _, cfg, params = cut
    assert cfg.plan == (2, 4, 3) and (cfg.conv_layers, cfg.kv_layers) == (11, 3) and cfg.hybrid and cfg.split_ffn
    assert [len(params[k]) for k in ("lead_layers", "period_layers", "tail_layers")] == [2, 4, 0]
    assert params["period_layers"][0]["wq"].shape == (3, 32, 4, 8) and params["period_layers"][1]["conv_in"].shape == (3, 32, 96)
    assert params["dense_ffn"]["w1"].shape == (2, 32, 64) and params["expert_ffn"]["we1"].shape == (12, 8, 32, 16)
    assert sum(a.size for a in jax.tree.leaves(params)) == lfm2_moe.n_params(file_of(14))
    state = init_sequence_state(cfg, 5)
    assert set(state) == {"conv"} and state["conv"].shape == (11, 5, 2 * 32)   # a tail a slot and no recurrent matrix


# ---------------------------------------------------------------------------
# forward against the reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("layers", [24, 14])
def test_forward_is_the_references(layers):
    c, cfg, params = built(layers)
    ref_logits, ref_loss = lfm2_moe.make_reference(c)
    tokens = jnp.asarray([prompt_of(40, 1), prompt_of(40, 2)])
    got = forward(cfg, params, tokens)
    for b in range(2):
        assert rel(got[b], ref_logits(params, tokens[b])) < TOL
    assert abs(float(transformer.loss_fn(cfg, params, tokens)) - ref_loss(params, tokens)) < 1e-4


def test_a_fault_in_the_mixer_or_the_order_fails_the_comparison(cut):
    """What the 1e-4 is worth: the taps reversed, the gates swapped, the
    leading layers taken for a period's, each moves the logits by tenths."""
    c, cfg, params = cut
    ref_logits, _ = lfm2_moe.make_reference(c)
    tokens = jnp.asarray(prompt_of(40, 3))
    want = ref_logits(params, tokens)

    def flipped(layer):
        return {**layer, "conv_w": layer["conv_w"][..., ::-1, :]} if "conv_w" in layer else layer

    for broken in ({**params, "lead_layers": [flipped(l) for l in params["lead_layers"]]},
                   {**params, "period_layers": [flipped(l) for l in params["period_layers"]]},
                   {**params, "lead_layers": params["lead_layers"][::-1]}):
        assert rel(forward(cfg, broken, tokens[None])[0], want) > 0.05


# ---------------------------------------------------------------------------
# chunked paged prefill, then decode, rows of different lengths in one batch
# ---------------------------------------------------------------------------
def paged_walk(cfg, params, prompts, chunk_ends, steps=3, fault=None):
    """Each prompt prefilled in chunks that end at ``chunk_ends`` (inside a
    page), then ``steps`` greedy decode steps of all rows in one batch.
    Returns (logits behind every chunk and decode step a row, tokens fed, cache)."""
    n, C = len(prompts), 32
    M = 8
    cache = init_paged_cache(cfg, n * M + 1, BS, slots=n + 1)
    tables = np.arange(1, n * M + 1, dtype=np.int32).reshape(n, M)
    logits = [[] for _ in prompts]
    for i, p in enumerate(prompts):
        pos = 0
        for end in [e for e in chunk_ends if e < len(p)] + [len(p)]:
            m = end - pos
            toks = np.zeros((1, C), np.int32)
            toks[0, :m] = p[pos:end]
            valid = (jnp.arange(C) < m)[None, :]
            lg, cache, _ = paged_forward_counted(cfg, params, cache, jnp.asarray(tables[i : i + 1]), jnp.asarray(toks),
                                                 pos + jnp.arange(C)[None, :], valid=valid, slots=jnp.asarray([i], jnp.int32))
            logits[i].append(lg[0, m - 1])
            pos = end
            if fault == "zero" and end == chunk_ends[0]:
                cache = zero_sequence_state(cache, i)
            if fault == "foreign" and end == chunk_ends[0]:
                cache = copy_sequence_state(cache, cache, i, (i + 1) % n)
    toks = jnp.asarray([int(jnp.argmax(l[-1])) for l in logits], jnp.int32)
    pos = jnp.asarray([len(p) for p in prompts], jnp.int32)
    fed = []
    for _ in range(steps):
        fed.append(np.asarray(toks))
        lg, cache, _ = paged_forward_counted(cfg, params, cache, jnp.asarray(tables), toks[:, None], pos[:, None],
                                             slots=jnp.arange(n, dtype=jnp.int32))
        for i in range(n):
            logits[i].append(lg[i, 0])
        toks, pos = jnp.argmax(lg[:, 0], -1).astype(jnp.int32), pos + 1
    return logits, fed, cache


def test_chunked_prefill_and_decode_through_the_cache_are_the_references_tails_included(cut):
    c, cfg, params = cut
    ref_logits, _ = lfm2_moe.make_reference(c)
    prompts = [prompt_of(75, 4), prompt_of(41, 5), prompt_of(58, 6)]
    ends = [21, 22, 23, 53]    # a cut inside a page, the two positions right behind it, another cut inside a page
    logits, fed, cache = paged_walk(cfg, params, prompts, ends)
    for i, p in enumerate(prompts):
        history = p + [int(f[i]) for f in fed]
        at = [e - 1 for e in ends if e < len(p)] + [len(p) - 1 + j for j in range(4)]
        want, tails = ref_logits(params, jnp.asarray(history), jnp.asarray(at), tails_after=len(history))
        assert rel(jnp.stack(logits[i]), want) < TOL, i
        held = cache["conv"][:, i].reshape(11, 2, 32)
        assert rel(held, tails) < TOL, i                       # each slot's tails: the reference's last two u rows
    assert float(jnp.abs(cache["conv"][:, 3]).max()) == 0.0   # a slot no row names does not move


@pytest.mark.parametrize("fault", ["zero", "foreign"])
def test_a_lost_or_foreign_tail_shows_right_behind_the_boundary_and_fades(cut, fault):
    c, cfg, params = cut
    ref_logits, _ = lfm2_moe.make_reference(c)
    prompts = [prompt_of(75, 4), prompt_of(41, 5)]
    ends = [21, 22, 23, 53]
    logits, _, _ = paged_walk(cfg, params, prompts, ends, steps=0, fault=fault)
    want = ref_logits(params, jnp.asarray(prompts[0]), jnp.asarray([20, 21, 22, 52, 74]))
    errs = [rel(logits[0][j], want[j]) for j in range(5)]
    assert errs[0] < TOL and errs[1] > 0.1 and errs[2] > 0.01     # sound up to the boundary, wrong right behind it
    assert errs[4] < errs[1]                                       # 52 tokens on it has faded (attention keeps a trace)


# ---------------------------------------------------------------------------
# through the engine
# ---------------------------------------------------------------------------
def engine_of(cfg, params, **kw):
    kw = {"max_batch_size": 2, "max_seq_len": 128, "kv_block_size": BS, "kv_num_blocks": 40, "prefill_chunk_tokens": 32,
          "state_snapshots": 6, **kw}
    return LLMEngine(cfg, params, **kw)


def greedy(c, params, prompt, n):
    ref_logits, _ = lfm2_moe.make_reference(c)
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(jnp.argmax(ref_logits(params, jnp.asarray(seq), jnp.asarray([len(seq) - 1]))[0])))
    return seq[len(prompt):]


def test_the_engine_serves_the_references_tokens_and_a_slot_another_sequence_left_starts_from_zero(cut):
    c, cfg, params = cut
    eng = engine_of(cfg, params, max_batch_size=1, prefix_cache=False, state_snapshots=0)
    try:
        for seed, n in ((7, 45), (8, 20), (9, 70)):   # one slot, three occupants: each must find zeros, not its predecessor's tails
            p = prompt_of(n, seed)
            assert eng.generate(p, max_tokens=6) == greedy(c, params, p, 6)
        s = eng.stats()
        assert (s["state_zeroed"], s["state_restores"], s["conv_layers"]) == (3, 0, 11)
        assert s["conv_tail_bytes_per_slot"] == s["state_bytes_per_slot"] == 11 * 2 * 32 * 4 and s["state_reset_s"] > 0
    finally:
        eng.shutdown()


def test_a_second_turn_restored_from_a_snapshot_is_a_cold_prefill_of_the_whole_history(cut):
    c, cfg, params = cut
    first = prompt_of(50, 10)
    warm, cold = engine_of(cfg, params), engine_of(cfg, params, prefix_cache=False, state_snapshots=0)
    try:
        reply = warm.generate(first, max_tokens=14)            # 64 tokens cached: four whole pages and a snapshot behind them
        history = first + reply + prompt_of(9, 11)
        before = warm.stats()
        again = warm.generate(history, max_tokens=8)
        after = warm.stats()
        assert after["state_restores"] - before["state_restores"] == 1
        assert after["prefix_tokens_reused"] - before["prefix_tokens_reused"] >= 48   # pages AND a snapshot: a hit
        assert again == cold.generate(history, max_tokens=8) == greedy(c, params, history, 8)
        snap = warm.store.state_snapshot(history + again)
        assert snap is not None and snap["state"].shape == (11, 2, 32) and snap["tokens"] % BS == 0
        ref_logits, _ = lfm2_moe.make_reference(c)
        _, tails = ref_logits(params, jnp.asarray(history + again), jnp.asarray([0]), tails_after=snap["tokens"])
        assert rel(snap["state"], tails) < TOL                 # the engine's own snapshot: the reference's tails at that token
    finally:
        warm.shutdown()
        cold.shutdown()


def test_an_idle_slots_tails_do_not_move_and_a_discarded_row_steps_snapshot_is_dropped(cut):
    c, cfg, params = cut
    eng = engine_of(cfg, params)
    try:
        eng.generate(prompt_of(30, 12), max_tokens=4)
        deadline = time.time() + 10
        while eng.stats()["active_slots"] and time.time() < deadline:
            time.sleep(0.02)
        tails = np.asarray(eng.runner.cache["conv"])
        used = [i for i in range(2) if np.abs(tails[:, i]).max() > 0]
        assert len(used) == 1                                   # one slot was ever live
        eng.generate(prompt_of(35, 13), max_tokens=20)          # the free slot, or the same one again: the other stays put
        after = np.asarray(eng.runner.cache["conv"])
        moved = [i for i in range(2) if not np.array_equal(after[:, i], tails[:, i])]
        assert len(moved) == 1
        # an EOS seen a step late: the row-step behind it is discarded, and with it the snapshot taken behind it
        p = prompt_of(20, 14)
        reply = greedy(c, params, p, 16)
        stop = reply[11]                                        # the token that ends the page (20 + 12 = 32)
        before = eng.stats()
        out = eng.generate(p, max_tokens=16, eos_id=stop)
        assert out == reply[: reply.index(stop) + 1]
        s = eng.stats()
        assert s["state_snapshots_in_use"] <= s["state_snapshot_pool_size"] and s["kv_blocks_in_use"] >= 0
        assert s["state_snapshots_taken"] >= before["state_snapshots_taken"]
    finally:
        eng.shutdown()
    s = eng.stats()
    assert s["active_slots"] == 0


# ---------------------------------------------------------------------------
# the router: the bias selects and never weighs, the published 1e-6 in the sum
# ---------------------------------------------------------------------------
def test_route_selects_by_the_bias_weighs_by_the_scores_and_adds_the_configs_eps(cut):
    _, cfg, _ = cut
    assert cfg.route_norm_eps == 1e-6 and TransformerConfig().route_norm_eps == 1e-20
    x = jax.random.normal(jax.random.key(1), (6, 32))
    layer = {"router": jax.random.normal(jax.random.key(2), (32, 8)),
             "router_bias": jnp.asarray([5.0, 0, 0, 0, 0, 0, 0, -5.0])}     # expert 0 always chosen, 7 never
    experts, weights = transformer.route(cfg, layer, x)
    scores = jax.nn.sigmoid(x @ layer["router"])
    assert bool((experts == 0).any(-1).all()) and not bool((experts == 7).any())
    picked = jnp.take_along_axis(scores, experts, axis=-1)
    want = picked / (picked.sum(-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(np.asarray(weights), np.asarray(want), rtol=1e-6)
    assert float(jnp.abs(weights.sum(-1) - 1.0).max()) > 1e-8               # the 1e-6 is in the sum
    old = dataclasses.replace(cfg, route_norm_eps=1e-20)
    assert float(jnp.abs(transformer.route(old, layer, x)[1].sum(-1) - 1.0).max()) < 1e-6


@pytest.mark.parametrize("layers", [24, 14])
def test_the_paged_forward_hands_out_each_expert_layers_selection_in_layer_order(layers):
    """``routes=True``: the experts every expert layer ran each token through.
    In float32 they are the reference's own, layer for layer (the published
    24: a place of the scanned period that is a dense layer in its first
    period and leaves no row), and a reference handed them back (``on_router``)
    gives the logits it gives alone; handed another selection it does not."""
    c, cfg, params = built(layers)
    ref_logits, _ = lfm2_moe.make_reference(c)
    p = prompt_of(29, 11)
    toks = np.zeros((1, 32), np.int32)
    toks[0, :29] = p
    cache = init_paged_cache(cfg, 9, BS, slots=1)
    lg, _, moe = paged_forward_counted(cfg, params, cache, jnp.arange(1, 9, dtype=jnp.int32)[None], jnp.asarray(toks),
                                       jnp.arange(32)[None, :], valid=(jnp.arange(32) < 29)[None, :],
                                       slots=jnp.zeros((1,), jnp.int32), routes=True)
    assert set(moe) == {"routes"} and moe["routes"].shape == (layers - 2, 32, 4)
    own = {}

    def theirs(layer, h, gate, bias):
        own[layer] = ref_logits.route(h, gate, bias)[1]

    want = ref_logits(params, jnp.asarray(p), on_router=theirs)
    assert sorted(own) == list(range(2, layers))
    for layer, chosen in own.items():
        np.testing.assert_array_equal(np.sort(np.asarray(moe["routes"][layer - 2, :29]), -1), np.sort(np.asarray(chosen), -1))
    assert rel(lg[0, :29], want) < TOL
    handed = ref_logits(params, jnp.asarray(p), on_router=lambda layer, *_: moe["routes"][layer - 2, :29])
    assert rel(handed, want) < 1e-6
    other = ref_logits(params, jnp.asarray(p), on_router=lambda layer, *_: (moe["routes"][layer - 2, :29] + 1) % 8)
    assert rel(other, want) > 0.05


def test_a_plain_expert_stack_hands_out_its_selections_too_and_they_are_what_it_counted():
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=3, n_heads=4, n_kv_heads=2, d_ff=64, max_seq_len=64,
                            num_experts=8, expert_top_k=2, num_dense_layers=1, expert_d_ff=16, moe_capacity_factor=0.0,
                            dtype=jnp.float32, param_dtype=jnp.float32)
    params = init_params(cfg, jax.random.key(0))
    args = (cfg, params, init_paged_cache(cfg, 5, BS), jnp.arange(1, 5, dtype=jnp.int32)[None], jnp.arange(1, 17)[None],
            jnp.arange(16)[None])
    lg, _, moe = paged_forward_counted(*args, routes=True)
    lg0, _, counted = paged_forward_counted(*args)
    assert moe["routes"].shape == (2, 16, 2) and float(jnp.abs(lg - lg0).max()) == 0.0
    np.testing.assert_array_equal(np.bincount(np.asarray(moe["routes"]).ravel(), minlength=8), np.asarray(counted["assignments"]))
    dense = dataclasses.replace(cfg, num_experts=0, num_dense_layers=0)
    with pytest.raises(ValueError, match="routes: the config has no dropless expert layer"):
        paged_forward_counted(dense, init_params(dense, jax.random.key(0)), *args[2:], routes=True)


@pytest.mark.parametrize("name", ["trinity-mini-serve-l5", "sdar-30b-a3b-serve-l6", "kimi-linear-48b-a3b-serve-l8",
                                  "moonlight-16b-a3b-train-ep8"])
def test_the_older_configurations_route_as_they_did(name):
    """Their routing is the parent's to the bit: the same function with the
    sum's 1e-20 written out, on the same inputs."""
    config = system.shrink_for_rehearsal(system.load_json(f"benchmark/configs/{name}.json"))
    run = config["run"]
    cfg = system.model_module(config).program_config(config, dtype=run["dtype"], param_dtype=run["param_dtype"])
    assert cfg.route_norm_eps == 1e-20
    x = jax.random.normal(jax.random.key(3), (64, cfg.d_model))
    layer = {"router": jax.random.normal(jax.random.key(4), (cfg.d_model, cfg.num_experts)) / 8,
             "router_bias": 0.01 * jax.random.normal(jax.random.key(5), (cfg.num_experts,))}

    def parents(cfg, layer, x2):
        logits = jnp.dot(x2.astype(jnp.float32), layer["router"].astype(jnp.float32), precision="highest")
        scores = jax.nn.sigmoid(logits) if cfg.router_score == "sigmoid" else jax.nn.softmax(logits, axis=-1)
        choose = scores + layer["router_bias"].astype(jnp.float32) if cfg.router_bias else scores
        _, experts = jax.lax.top_k(choose, cfg.expert_top_k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
        if cfg.route_norm:
            weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
        return experts.astype(jnp.int32), weights * cfg.route_scale

    for got, want in zip(transformer.route(cfg, layer, x), parents(cfg, layer, x)):
        assert np.array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# the two older hybrids through the generalised loop: the parent's loop, to the bit
# ---------------------------------------------------------------------------
def parents_hybrid_scan(cfg, params, carry, x, mixers, valid=None, kernel=True, routes=False):
    """PR 53's ``hybrid_scan`` and ``split_ffn`` as they stood (k linear
    layers, then one full or latent layer, a period), behind this PR's call."""
    k = cfg.layer_types.index(cfg.attn_kind)
    nd = cfg.num_dense_layers
    linear_fn, full_fn = mixers["linear"], mixers[cfg.attn_kind]

    def split(x, i, j):
        def at(stack, index):
            return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, index, 0, keepdims=False), stack)

        def dense(x):
            layer = at(params["dense_ffn"], 0 if nd == 1 else jnp.clip(i, 0, nd - 1))
            return transformer.block_ffn(cfg, layer, x)[0], jnp.zeros((cfg.experts_here,), jnp.int32)

        def routed(x):
            stack = params["expert_ffn"]
            index = jnp.maximum(i - nd, 0)
            return transformer.block_ffn(cfg, at(transformer.scanned_leaves(cfg, stack), index), x, valid, stack=stack,
                                         index=index, kernel=kernel)

        return routed(x) if j >= nd else jax.lax.cond(i < nd, dense, routed, x)

    def period(state, xs):
        lin, full, p = xs
        counts = []
        for j in range(k + 1):
            layer = lin[j] if j < k else full
            carry, x = linear_fn(*state, layer, p * k + j) if j < k else full_fn(*state, layer, p)
            x, c = split(x, p * (k + 1) + j, j) if cfg.split_ffn else transformer.block_ffn(cfg, layer, x)
            state = (carry, x)
            counts.append(c)
        return state, jnp.stack(counts) if cfg.split_ffn else None

    (carry, x), counts = jax.lax.scan(
        period, (carry, x), (tuple(params["linear_layers"]), params["layers"], jnp.arange(cfg.n_layers // (k + 1), dtype=jnp.int32)))
    return carry, x, counts, None


@pytest.mark.parametrize("name", ["olmo-hybrid-7b-serve-l16", "kimi-linear-48b-a3b-serve-l8"])
def test_the_older_hybrids_rehearsal_logits_are_the_parents_loops_to_the_bit(monkeypatch, name):
    config = system.shrink_for_rehearsal(system.load_json(f"benchmark/configs/{name}.json"))
    run = config["run"]
    cfg = system.model_module(config).program_config(config, max_seq_len=run["max_seq_len"], dtype=run["dtype"],
                                                     param_dtype=run["param_dtype"])
    assert cfg.plan == (0, cfg.linear_per_period + 1, cfg.periods) and cfg.periods == 2
    params = system.make_params(cfg, 3, float(run["weights"]["embed_table_scale"]))
    tokens = jnp.asarray([prompt_of(48, 20, cfg.vocab_size), prompt_of(48, 21, cfg.vocab_size)])

    def both():
        full = forward(cfg, params, tokens)
        cache = init_paged_cache(cfg, 9, BS, slots=2)
        bt = jnp.asarray(np.arange(1, 9, dtype=np.int32).reshape(2, 4))
        pos = jnp.broadcast_to(jnp.arange(48)[None, :], (2, 48))
        chunk, cache, moe = paged_forward_counted(cfg, params, cache, bt, tokens, pos, slots=jnp.arange(2, dtype=jnp.int32))
        step, cache, _ = paged_forward_counted(cfg, params, cache, bt, tokens[:, :1], jnp.full((2, 1), 48),
                                               slots=jnp.arange(2, dtype=jnp.int32))
        return [np.asarray(a) for a in (full, chunk, step, moe["assignments"], *[cache[k] for k in sorted(cache)])]

    mine = both()
    monkeypatch.setattr(transformer, "hybrid_scan", parents_hybrid_scan)
    monkeypatch.setattr(generation, "hybrid_scan", parents_hybrid_scan)
    for got, want in zip(mine, both()):
        assert np.array_equal(got, want)


# ---------------------------------------------------------------------------
# every combination not built for the kind raises a sentence that names it
# ---------------------------------------------------------------------------
CONV = dict(vocab_size=64, d_model=32, n_layers=4, n_heads=2, d_ff=64, layer_types=("conv", "full", "conv", "conv"))


@pytest.mark.parametrize("bad,named", [
    ({"layer_types": ("conv", "linear", "full", "conv"), "linear_heads": 2, "linear_key_dim": 8, "linear_value_dim": 8},
     '"linear" layers in one config'),
    ({"layer_types": ("conv", "latent", "full", "conv")}, '"latent" layers in one config'),
    ({"layer_types": ("conv", "sliding", "full", "conv"), "sliding_window": 8}, '"sliding" layers in one config'),
    ({"layer_types": ("conv",) * 4}, 'no "full" layer'),
    ({"conv_width": 1}, "conv_width < 2"),
    ({"attention": "ring"}, 'attention="ring"'),
    ({"block_length": 4}, "block_length > 1"),
    ({"num_experts": 4, "moe_capacity_factor": 1.0}, "moe_capacity_factor"),
    ({"num_experts": 4, "experts_held": (0, 2)}, "experts_held"),
    ({"layer_types": ("conv", "full", "conv", "gated")}, '"conv" for each'),
])
def test_the_config_refuses_by_name_what_a_conv_layer_is_not_built_for(bad, named):
    TransformerConfig(**CONV)
    with pytest.raises(ValueError, match=named):
        TransformerConfig(**{**CONV, **bad})


def test_training_a_mesh_the_ring_cache_and_the_engines_other_modes_refuse_the_kind_by_name():
    cfg = TransformerConfig(**CONV, dtype=jnp.float32)
    params = init_params(cfg, jax.random.key(0))
    with pytest.raises(ValueError, match='"conv" layers is served, not trained'):
        transformer.make_train_step(cfg)
    with pytest.raises(ValueError, match='"conv" layers'):
        transformer.param_specs(cfg)
    with pytest.raises(ValueError, match="no mesh"):
        forward(cfg, params, jnp.ones((1, 8), jnp.int32), act_spec=object())
    with pytest.raises(ValueError, match="convolution tail a sequence"):
        generation.init_cache(cfg, 1, 32)
    with pytest.raises(ValueError, match="slots >= 1"):
        init_paged_cache(cfg, 8, BS)
    with pytest.raises(ValueError, match="pass the rows' slots"):
        paged_forward_counted(cfg, params, init_paged_cache(cfg, 8, BS, slots=1), jnp.ones((1, 2), jnp.int32),
                              jnp.ones((1, 4), jnp.int32), jnp.arange(4)[None, :])
    kw = dict(max_batch_size=2, max_seq_len=64, kv_block_size=BS)
    for more, named in (({"decode_chunk": 4}, "decode_chunk > 1"), ({"quantize": True}, "quantize=True"),
                        ({"mesh": jax.sharding.Mesh(np.array(jax.devices()[:1]), ("tp",))}, "mesh")):
        with pytest.raises(ValueError, match=named) as err:
            LLMEngine(cfg, params, **kw, **more)
        assert '"conv": a convolution tail' in str(err.value)
    eng = LLMEngine(cfg, params, **kw)
    try:
        for call in (lambda: eng.prefill_export([1, 2, 3], mig_id="m"),
                     lambda: eng.adopt_migration({"prompt": [1, 2, 3], "tok0": 1}, {})):
            with pytest.raises(ValueError, match="convolution tails"):
                call()
    finally:
        eng.shutdown()
    plain = TransformerConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=2, d_ff=64, dtype=jnp.float32)
    with pytest.raises(ValueError, match='"linear" or "conv" layers'):
        LLMEngine(plain, init_params(plain, jax.random.key(0)), **kw, state_snapshots=4)
