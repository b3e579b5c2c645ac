"""A config with Kimi Delta Attention layers (the gated delta rule with a
decay a key channel), latent attention over ONE pool of latent rows, and
expert layers that hold a share of their experts (``kimi_linear``) against the
plain float32 reference ``benchmark/models/kimi_linear.py``: the three forms
of the channel-decay recurrence, the absorbed against the expanded latent
attention, the forward, chunked paged prefill and decode through latent pages
and per-slot state, the engine's reuse by snapshot over shared latent pages,
the share of the experts, the one-pool cache's helpers, and the refusals.
Toy size (two periods of 3 KDA + 1 latent layer, layer 1's FFN dense, 16
experts of which 4 held, 4 heads), float32, CPU: no near-ties, so tokens are
compared one for one.
"""

import hashlib
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import system  # noqa: E402
from benchmark.models import kimi_linear  # noqa: E402
from ray_tpu.models import transformer  # noqa: E402
from ray_tpu.models.generation import (copy_paged_page, export_paged_page, init_cache, init_paged_cache,  # noqa: E402
                                       paged_forward_counted, write_paged_pages)
from ray_tpu.models.transformer import (TransformerConfig, forward, init_params, latent_absorb,  # noqa: E402
                                        latent_attention_expanded, latent_out, latent_qkv, latent_scale,
                                        moe_ffn_dropless, param_specs, pre_norm)
from ray_tpu.ops import gated_delta as gd  # noqa: E402
from ray_tpu.ops.decode_attention import latent_paged_decode, latent_paged_prefill  # noqa: E402
from ray_tpu.serve.llm import LLMEngine  # noqa: E402

C = dict(model="kimi_linear", vocab_size=512, hidden_size=64, intermediate_size=128, num_hidden_layers=8,
         num_attention_heads=4, num_key_value_heads=4, head_dim=16, hidden_act="silu", model_max_length=512,
         rms_norm_eps=1e-5, tie_word_embeddings=False, first_k_dense_replace=1, kv_lora_rank=32, q_lora_rank=None,
         qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, mla_use_nope=True, moe_intermediate_size=32,
         moe_layer_freq=1, moe_renormalize=True, moe_router_activation_func="sigmoid", num_expert_group=1, topk_group=1,
         num_experts=4, experts_routed=16, experts_held=[4, 8], num_experts_per_token=4, num_shared_experts=1,
         routed_scaling_factor=2.446, num_nextn_predict_layers=0,
         linear_attn_config=dict(kda_layers=[1, 2, 3, 5, 6, 7], full_attn_layers=[4, 8], num_heads=4, head_dim=32,
                                 short_conv_kernel_size=4))
CFG = kimi_linear.program_config(C, dtype="float32", param_dtype="float32", max_seq_len=256)
BS, CHUNK = 16, 32


@pytest.fixture(scope="module")
def params():
    p = init_params(CFG, jax.random.key(3))
    return {**p, "embed": p["embed"] * 8.0}  # rows of O(1) entries, as a trained table's


@pytest.fixture(scope="module")
def reference():
    return kimi_linear.make_reference(C)


def engine_of(params, **kw):
    kw = {"max_batch_size": 4, "max_seq_len": 256, "kv_block_size": BS, "kv_num_blocks": 80,
          "prefill_chunk_tokens": CHUNK, **kw}
    return LLMEngine(CFG, params, **kw)


def prompt_of(n, seed=0):
    return np.random.default_rng([seed, n]).integers(1, 512, size=n).tolist()


def settle(eng, timeout=10.0):
    t0 = time.time()
    while time.time() - t0 < timeout:
        s = eng.stats()
        if s["active_slots"] == 0 and s["kv_blocks_in_use"] == s["prefix_cache_blocks"]:
            return s
        time.sleep(0.02)
    raise AssertionError(f"engine did not settle: {eng.stats()}")


# ---------------------------------------------------------------------------
# the recurrence with a decay a key channel
# ---------------------------------------------------------------------------
def _inputs(T, B=2, H=3, dk=16, dv=32, seed=0, fast=True):
    ks = jax.random.split(jax.random.key(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (B, T, H, dk))) / np.sqrt(dk)
    k = unit(jax.random.normal(ks[1], (B, T, H, dk)))
    v = jax.random.normal(ks[2], (B, T, H, dv))
    # log alpha a channel: log-uniform from alpha ~ 0.9999 down to alpha = 1e-3 a token (g = -6.9) where ``fast``
    lo, hi = np.log(1e-4), np.log(6.9 if fast else 0.1)
    g = -jnp.exp(jax.random.uniform(ks[3], (B, T, H, dk), minval=lo, maxval=hi))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    S0 = jax.random.normal(ks[5], (B, H, dk, dv))
    return q, k, v, g, beta, S0


def _sequential(S, q, k, v, g, beta, valid=None):
    outs = []
    for t in range(q.shape[1]):
        o, Sn = gd.gated_delta_step(S, q[:, t], k[:, t], v[:, t], jnp.exp(g[:, t]), beta[:, t])
        if valid is not None:
            Sn = jnp.where(valid[:, t, None, None, None], Sn, S)
        S = Sn
        outs.append(o)
    return jnp.stack(outs, axis=1), S


@pytest.mark.parametrize("fast", [True, False], ids=["fast-forgetting", "slow"])
@pytest.mark.parametrize("start", ["zero", "nonzero"])
@pytest.mark.parametrize("T", [64, 128, 1, 37, 150])
def test_the_channel_decay_chunked_recurrence_is_the_sequential_one(T, start, fast):
    q, k, v, g, beta, S0 = _inputs(T, seed=T, fast=fast)
    if fast and T >= 37:
        assert float(jnp.exp(g).min()) < 3e-3  # channels that forget within a token are among them
    S0 = jnp.zeros_like(S0) if start == "zero" else S0
    with jax.default_matmul_precision("highest"):
        want_o, want_S = _sequential(S0, q, k, v, g, beta)
        got_o, got_S = jax.jit(gd.gated_delta_chunked)(S0, q, k, v, g, beta)
    assert bool(jnp.isfinite(got_o).all()) and bool(jnp.isfinite(got_S).all())
    np.testing.assert_allclose(got_o, want_o, atol=2e-5)
    np.testing.assert_allclose(got_S, want_S, atol=2e-5)


@pytest.mark.parametrize("T,real", [(128, 100), (96, 64), (70, 1)])
def test_a_padded_chunk_with_a_channel_decay_advances_the_state_by_its_real_tokens_only(T, real):
    q, k, v, g, beta, S0 = _inputs(T, seed=7)
    valid = jnp.broadcast_to(jnp.arange(T)[None, :] < real, (2, T))
    with jax.default_matmul_precision("highest"):
        want_o, want_S = _sequential(S0, q, k, v, g, beta, valid)
        got_o, got_S = gd.gated_delta_chunked(S0, q, k, v, g, beta, valid)
    np.testing.assert_allclose(got_o[:, :real], want_o[:, :real], atol=2e-5)
    np.testing.assert_allclose(got_S, want_S, atol=2e-5)


@pytest.mark.parametrize("form", ["step", "chunked", "decode"])
def test_a_decay_a_head_gives_what_it_gave_and_a_channel_decay_of_equal_channels_gives_the_same(form):
    """The scalar form is untouched (``g`` ``[.., H]`` takes the lines it took);
    the channel form fed one value on all of a head's channels is that function."""
    q, k, v, g, beta, S0 = _inputs(70, seed=11, fast=False)
    gh = g[..., 0]                                     # a decay a head
    gc = jnp.broadcast_to(gh[..., None], g.shape)      # the same, written a channel
    with jax.default_matmul_precision("highest"):
        if form == "step":
            a = gd.gated_delta_step(S0, q[:, 0], k[:, 0], v[:, 0], jnp.exp(gh[:, 0]), beta[:, 0])
            b = gd.gated_delta_step(S0, q[:, 0], k[:, 0], v[:, 0], jnp.exp(gc[:, 0]), beta[:, 0])
        elif form == "chunked":
            a = gd.gated_delta_chunked(S0, q, k, v, gh, beta)
            b = gd.gated_delta_chunked(S0, q, k, v, gc, beta)
            want = _sequential(S0, q, k, v, gh, beta)
            np.testing.assert_allclose(a[0], want[0], atol=2e-5)
        else:
            state = jnp.zeros((2, 4, 3, 16, 32)).at[1, jnp.array([2, 0])].set(S0)
            args = (jnp.int32(1), jnp.array([2, 0]), jnp.array([True, True]), q[:, 0], k[:, 0], v[:, 0])
            a = gd.gated_delta_decode(state, *args, jnp.exp(gh[:, 0]), beta[:, 0], kernel=True)
            b = gd.gated_delta_decode(state, *args, jnp.exp(gc[:, 0]), beta[:, 0], kernel=True)
    for x, y in zip(a, b):
        np.testing.assert_allclose(x, y, atol=2e-5)


def test_the_decode_kernel_with_a_channel_decay_is_the_step_and_leaves_idle_rows_alone():
    q, k, v, g, beta, S0 = _inputs(1, B=3, H=4, dk=8, dv=32, seed=5)
    G = gd.lane_group(4, 32)
    state = jnp.zeros((2, 5, 4 // G, 8, G * 32)).at[1, jnp.array([4, 0, 2])].set(gd.pack_state(S0, G))
    slots, live = jnp.array([4, 0, 2]), jnp.array([True, False, True])
    alpha = jnp.exp(g[:, 0])
    want_o, want_S = gd.gated_delta_step(S0, q[:, 0], k[:, 0], v[:, 0], alpha, beta[:, 0])
    for kernel in (True, False):
        o, new = gd.gated_delta_decode(state, jnp.int32(1), slots, live, q[:, 0], k[:, 0], v[:, 0], alpha, beta[:, 0],
                                       kernel=kernel)
        got = gd.unpack_state(new[1, slots], G)
        np.testing.assert_allclose(o[live], want_o[live], atol=1e-5)
        np.testing.assert_allclose(got[0], want_S[0], atol=1e-5)
        np.testing.assert_allclose(got[1], S0[1], atol=0)         # the idle row's state is left as it is
        assert not bool(new[0].any())                              # and so is every other layer's


# ---------------------------------------------------------------------------
# latent attention
# ---------------------------------------------------------------------------
def test_absorbed_latent_attention_is_the_expanded_form(params):
    """``q' . c + q_pe . k_pe`` against cached rows and ``W_kvb[V] (sum a_j
    c_j)`` is the published form's ``softmax(q k^T) v`` with every head's
    keys and values expanded: through the dense lines and both kernels."""
    layer = jax.tree.map(lambda a: a[1], params["layers"])
    B, T = 2, 40
    x = jax.random.normal(jax.random.key(1), (B, T, 64))
    h = pre_norm(CFG, layer, "attn_norm", x)
    want = latent_attention_expanded(CFG, layer, x, h)
    q, row = latent_qkv(CFG, layer, h)
    lanes = CFG.latent_row_lanes
    assert (CFG.latent_row, lanes) == (40, 128)
    pool = jnp.zeros((2, 9, BS, lanes)).at[1, jnp.arange(1, 7).reshape(2, 3)].set(
        jnp.pad(row, ((0, 0), (0, 8), (0, lanes - 40))).reshape(2, 3, BS, lanes))
    bt = jnp.arange(1, 7, dtype=jnp.int32).reshape(2, 3)
    qa = latent_absorb(CFG, layer, q, lanes)
    starts, lengths = jnp.zeros((B,), jnp.int32), jnp.full((B,), T, jnp.int32)
    for kernel in (False, True):
        o = latent_paged_prefill(qa, pool, bt, starts, lengths, jnp.int32(1), rank=32, sm_scale=latent_scale(CFG),
                                 use_kernel=kernel)
        np.testing.assert_allclose(latent_out(CFG, layer, x, o), want, atol=2e-5)
        last = latent_paged_decode(qa[:, -1], pool, bt, lengths, jnp.int32(1), rank=32, sm_scale=latent_scale(CFG),
                                   use_kernel=kernel)
        np.testing.assert_allclose(latent_out(CFG, layer, x[:, -1:], last[:, None]), want[:, -1:], atol=2e-5)


@pytest.mark.parametrize("span", [128, 256])
def test_the_latent_decode_kernel_walks_only_live_rows_of_the_one_pool(span):
    ks = jax.random.split(jax.random.key(0), 3)
    pool = jax.random.normal(ks[0], (2, 40, BS, 128)).at[..., 40:].set(0)
    bt = jnp.asarray(np.random.default_rng(0).permutation(np.arange(1, 40))[:30].reshape(3, 10), jnp.int32).at[2].set(0)
    lengths = jnp.array([150, 37, 55])
    q = jax.random.normal(ks[1], (3, 4, 128)).at[..., 40:].set(0)
    got = latent_paged_decode(q, pool, bt, lengths, jnp.int32(1), rank=32, sm_scale=0.3, span=span)
    want = latent_paged_decode(q, pool, bt, lengths, jnp.int32(1), rank=32, sm_scale=0.3, use_kernel=False)
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert not bool(got[2].any())  # a table that starts at the garbage page holds no sequence


# what a walk that fetches whole groups ahead of their products can get wrong: lengths a row by the group's rows
# ``span``, then rows whose table is all page 0 (idle), then whether the tables name consecutive pages
_WALK_CASES = {
    "whole_groups": (lambda s: (2 * s, s), (), False),
    "one_row_past_a_group": (lambda s: (2 * s + 1, s + 1), (), False),  # a last group of one row in one page
    "under_a_page": (lambda s: (5, 1), (), False),
    "an_idle_row_between_live_ones": (lambda s: (s + 37, 3 * s, 48), (1,), False),
    "a_long_row_then_a_short_one": (lambda s: (2 * s + 40, 7, s - 1), (), False),  # the buffers still hold the long one's
    "consecutive_pages": (lambda s: (s + 300, 2 * s - 1), (), True),
}


@pytest.mark.parametrize("span", [256, 512, 1024])
@pytest.mark.parametrize("case", list(_WALK_CASES))
def test_the_latent_decode_walk_at_the_edges_of_its_groups(case, span):
    lengths, idle, consecutive = _WALK_CASES[case]
    lengths = np.asarray(lengths(span))
    B, M = len(lengths), -(-int(lengths.max()) // BS) + 1
    ks = jax.random.split(jax.random.key(span), 2)
    pool = jax.random.normal(ks[0], (2, B * M + 1, BS, 128)).at[..., 40:].set(0)  # page 0 holds garbage too
    pages = np.arange(1, B * M + 1)
    bt = (pages if consecutive else np.random.default_rng(span).permutation(pages)).reshape(B, M)
    bt[list(idle)] = 0
    # a table entry past a row's last page may point anywhere: at the garbage page, as the engine's do
    bt = jnp.asarray(np.where(np.arange(M)[None, :] * BS < lengths[:, None], bt, 0), jnp.int32)
    q = jax.random.normal(ks[1], (B, 4, 128)).at[..., 40:].set(0)
    args = (q, pool, bt, jnp.asarray(lengths, jnp.int32), jnp.int32(1))
    got = latent_paged_decode(*args, rank=32, sm_scale=0.3, span=span)
    want = latent_paged_decode(*args, rank=32, sm_scale=0.3, use_kernel=False)
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert np.isfinite(got).all() and not np.asarray(got)[list(idle)].any()


@pytest.mark.parametrize("walk,work", [("shared", "one_piece"), ("shared", "pieces"), ("beside", "pieces")])
def test_the_kernel_benchs_stand_ins_that_compute_a_result_compute_the_kernels(walk, work):
    """``scripts/kernel_bench.py --latent-decode`` times the kernel as it stood on
    the shared walk, and the module's work on either walk: each is the kernel."""
    from ray_tpu.scripts.kernel_bench import _latent_decode_variant

    ks = jax.random.split(jax.random.key(5), 2)
    pool = jax.random.normal(ks[0], (2, 60, BS, 128)).at[..., 40:].set(0)
    bt = jnp.asarray(np.random.default_rng(5).permutation(np.arange(1, 60))[:58].reshape(2, 29), jnp.int32)
    q = jax.random.normal(ks[1], (2, 8, 128)).at[..., 40:].set(0)
    args = (q, pool, bt, jnp.array([450, 300], jnp.int32), jnp.int32(1))
    got = _latent_decode_variant(walk, work, 128, rank=32, scale=0.3)(*args)
    np.testing.assert_allclose(got, latent_paged_decode(*args, rank=32, sm_scale=0.3, use_kernel=False), atol=2e-6)


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------
def test_the_parameter_tree_keeps_mixers_and_ffns_in_stacks_of_their_own_and_counts_as_the_reference(params):
    assert CFG.split_ffn and (CFG.periods, CFG.linear_per_period, CFG.latent_layers, CFG.kv_layers) == (2, 3, 2, 0)
    assert set(params) == {"embed", "head", "final_norm", "linear_layers", "layers", "dense_ffn", "expert_ffn"}
    assert params["dense_ffn"]["w1"].shape == (1, 64, 128) and params["expert_ffn"]["we1"].shape == (7, 4, 64, 32)
    assert params["expert_ffn"]["router"].shape == (7, 64, 16)      # the router scores all 16, the tree holds 4
    assert "w1" not in params["layers"] and "w1" not in params["linear_layers"][0]
    assert params["linear_layers"][0]["dt_bias"].shape == (2, 4, 32) and params["linear_layers"][0]["A_log"].shape == (2, 4)
    assert sum(x.size for x in jax.tree.leaves(params)) == kimi_linear.n_params(C)


def test_forward_is_the_references(params, reference):
    toks = jnp.asarray(prompt_of(100))
    np.testing.assert_allclose(forward(CFG, params, toks[None])[0], reference[0](params, toks), atol=5e-5)


def _prefill(params, cache, prompt, bt_row, slot, start=0, kernel=False):
    logits = None
    for pos in range(start, len(prompt), CHUNK):
        n = min(CHUNK, len(prompt) - pos)
        toks = np.zeros((1, CHUNK), np.int32)
        toks[0, :n] = prompt[pos : pos + n]
        logits, cache, _ = paged_forward_counted(
            CFG, params, cache, bt_row[None], jnp.asarray(toks), pos + jnp.arange(CHUNK)[None],
            valid=(jnp.arange(CHUNK) < n)[None], slots=jnp.asarray([slot], jnp.int32), use_decode_kernel=kernel)
        logits = logits[0, n - 1]
    return logits, cache


@pytest.mark.parametrize("kernel", [False, True])
def test_chunked_prefill_then_decode_through_latent_pages_and_state_is_the_references(params, reference, kernel):
    prompt = prompt_of(75)
    cache = init_paged_cache(CFG, 16, BS, slots=3)
    assert set(cache) == {"latent", "state", "conv"} and cache["latent"].shape == (2, 16, BS, 128)
    bt = jnp.arange(1, 9, dtype=jnp.int32)
    logits, cache = _prefill(params, cache, prompt, bt, 2, kernel=kernel)
    seq, got = list(prompt), [logits]
    for _ in range(3):
        seq.append(int(jnp.argmax(got[-1])))
        lg, cache, moe = paged_forward_counted(
            CFG, params, cache, bt[None], jnp.asarray([[seq[-1]]]), jnp.asarray([[len(seq) - 1]]),
            valid=jnp.asarray([[True]]), slots=jnp.asarray([2], jnp.int32), use_decode_kernel=kernel)
        got.append(lg[0, 0])
        assert int(moe["routed"]) == 7 * 4 and 0 <= int(moe["assignments"].sum()) <= 28
    want = reference[0](params, jnp.asarray(seq), jnp.arange(len(prompt) - 1, len(seq)))
    np.testing.assert_allclose(jnp.stack(got), want, atol=1e-4)


def test_a_follow_up_from_a_restored_snapshot_over_shared_latent_pages_is_the_cold_engines(params):
    """The engine itself: a follow-up turn whose admission restores a state
    snapshot and shares the first turn's latent pages gives the tokens of an
    engine without a prefix cache, which prefills the whole history."""
    first, more = prompt_of(70), prompt_of(13, seed=1)
    cold = engine_of(params, prefix_cache=False)
    warm = engine_of(params, state_snapshots=8)
    try:
        outs = {}
        for name, eng in (("cold", cold), ("warm", warm)):
            reply = eng.submit(first, max_tokens=12).result(timeout=120)
            settle(eng)
            outs[name] = (reply, eng.submit(first + reply + more, max_tokens=10).result(timeout=120))
        assert outs["warm"] == outs["cold"]
        s = settle(warm)
        assert s["state_restores"] >= 1 and s["prefix_tokens_reused"] >= 64
        assert s["latent_layers"] == 2 and s["kv_bytes_per_token"] == 2 * 128 * 4
        assert s["moe_experts_held"] == 4 and len(s["moe_expert_assignments"]) == 4
        assert 0 < s["moe_assignments_local"] < s["moe_assignments"] and s["moe_assignments"] % (7 * 4) == 0
        assert "latent_layers" not in cold.admission_snapshot()
    finally:
        cold.shutdown()
        warm.shutdown()


# ---------------------------------------------------------------------------
# a share of the experts
# ---------------------------------------------------------------------------
def _uncut(**kw):
    """The toy config's expert layer holding all 16 experts, and the same with a share."""
    base = dict(vocab_size=64, d_model=64, n_layers=2, n_heads=4, d_ff=128, num_experts=16, expert_top_k=4,
                expert_d_ff=32, num_shared_experts=1, router_score="sigmoid", router_bias=True, route_scale=2.446,
                dtype=jnp.float32)
    return TransformerConfig(**{**base, **kw})


def test_the_four_shares_routed_parts_and_the_shared_expert_once_are_the_uncut_layer(reference):
    """The share test: each of four chips routes over all 16 experts,
    normalises over all 4 chosen and adds the terms of the 4 experts it
    holds; the four routed parts and the shared expert, counted once, are
    the uncut layer's output, and that is the reference's expert branch."""
    whole = _uncut()
    layer = jax.tree.map(lambda a: a[0], init_params(whole, jax.random.key(5))["layers"])
    x = jax.random.normal(jax.random.key(6), (2, 9, 64))
    want, counts = moe_ffn_dropless(whole, layer, x)
    shared = (jax.nn.silu(x @ layer["ws3"]) * (x @ layer["ws1"])) @ layer["ws2"]
    total, local = jnp.zeros_like(x), []
    for lo in range(0, 16, 4):
        cut = _uncut(experts_held=(lo, lo + 4))
        mine = {**layer, **{k: layer[k][lo : lo + 4] for k in ("we1", "we3", "we2")}}
        out, c = moe_ffn_dropless(cut, mine, x)
        total = total + (out - shared)
        local.append(c)
    np.testing.assert_allclose(total + shared, want, atol=2e-5)
    np.testing.assert_array_equal(jnp.concatenate(local), counts)      # every choice landed on exactly one share
    assert int(counts.sum()) == 2 * 9 * 4
    # and the uncut layer is the reference's (its file's experts_held spanning all it routes)
    c = {**C, "num_experts": 16, "experts_held": [0, 16], "num_hidden_layers": 8}
    h = x.reshape(-1, 64)
    chosen, w = kimi_linear._ref_route(h, layer["router"], layer["router_bias"], top_k=4, scale=2.446, renormalize=True,
                                       score="sigmoid")
    y = sum(jnp.sum(jnp.where(chosen == e, w, 0.0), -1, keepdims=True)
            * kimi_linear._mlp(h, layer["we3"][e], layer["we1"][e], layer["we2"][e]) for e in range(16))
    np.testing.assert_allclose((y + shared.reshape(-1, 64)).reshape(x.shape), want, atol=2e-5)
    assert kimi_linear.held(c) == (0, 16)


def test_dropped_assignments_never_reach_the_grouped_product(monkeypatch):
    """The rows of experts held elsewhere sort behind every group: the group
    sizes the products get count the experts held alone, so those rows are
    never multiplied (they come back zeros) and no absent expert's weights
    are asked for."""
    cut = _uncut(experts_held=(8, 12))
    layer = jax.tree.map(lambda a: a[0], init_params(cut, jax.random.key(5))["layers"])
    assert layer["we1"].shape == (4, 64, 32) and layer["router"].shape == (64, 16)
    x = jax.random.normal(jax.random.key(6), (1, 11, 64))
    seen = []
    real = transformer._ragged_dot

    def spy(rows, weights, sizes):
        seen.append((np.asarray(rows), np.asarray(sizes)))
        return real(rows, weights, sizes)

    monkeypatch.setattr(transformer, "grouped_matmul", spy)
    experts, _ = transformer.route(cut, layer, x.reshape(-1, 64))
    here = int(((experts >= 8) & (experts < 12)).sum())
    _, counts = moe_ffn_dropless(cut, layer, x)
    assert 0 < here < 44 and int(counts.sum()) == here and len(seen) == 3
    for rows, sizes in seen:
        assert sizes.shape == (4,) and int(sizes.sum()) == here   # L x E_held groups: nothing for an expert held elsewhere
    assert not np.asarray(seen[2][0])[here:].any()                # the dropped rows reach the down-projection as zeros


# ---------------------------------------------------------------------------
# the one-pool cache's helpers, and the engine's reset
# ---------------------------------------------------------------------------
def test_copy_on_write_export_and_landing_on_the_one_pool_cache():
    cache = init_paged_cache(CFG, 6, BS, slots=2)
    cache = {**cache, "latent": jax.random.normal(jax.random.key(0), cache["latent"].shape),
             "state": cache["state"] + 1.0}
    copied = copy_paged_page(cache, jnp.int32(2), jnp.int32(4))
    np.testing.assert_array_equal(copied["latent"][:, 4], cache["latent"][:, 2])
    np.testing.assert_array_equal(copied["latent"][:, 3], cache["latent"][:, 3])
    np.testing.assert_array_equal(copied["state"], cache["state"])       # what a sequence keeps is passed through
    block = export_paged_page(CFG, cache, jnp.int32(2))
    assert block.shape == (1, 2, BS, 1, 128)
    landed = write_paged_pages(cache, jnp.stack([block, block]), jnp.asarray([5, 1]))
    np.testing.assert_array_equal(landed["latent"][:, 5], cache["latent"][:, 2])
    np.testing.assert_array_equal(landed["latent"][:, 1], cache["latent"][:, 2])
    assert set(landed) == {"latent", "state", "conv"}


def test_a_cache_reset_rebuilds_the_one_pool_and_frees_both_kinds(params):
    eng = engine_of(params, state_snapshots=4)
    try:
        eng.submit(prompt_of(40), max_tokens=4).result(timeout=120)
        settle(eng)
        assert eng.stats()["state_snapshots_in_use"] >= 1
        eng._fail_inflight(RuntimeError("test"))
        eng._reset_cache()
        assert set(eng.runner.cache) == {"latent", "state", "conv"} and not bool(eng.runner.cache["latent"].any())
        assert eng.stats()["state_snapshots_in_use"] == 0 and eng.stats()["kv_blocks_in_use"] == 0
        assert len(eng.submit(prompt_of(40), max_tokens=4).result(timeout=120)) == 4
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# refusals, and what stays as it was
# ---------------------------------------------------------------------------
KIMI = dict(vocab_size=64, d_model=32, n_layers=4, n_heads=4, d_ff=64, layer_types=("linear",) * 3 + ("latent",),
            rope_full_layers=False, linear_heads=4, linear_key_dim=8, linear_value_dim=8, latent_rank=16,
            latent_nope_dim=8, latent_rope_dim=4, latent_value_dim=8)


@pytest.mark.parametrize("bad,named", [
    ({"rope_full_layers": True}, "rotates nothing"),
    ({"layer_types": ("latent",) * 4}, 'without "linear" layers'),
    ({"layer_types": ("linear", "linear", "full", "latent")}, '"full" or "sliding" layers beside it'),
    ({"latent_rank": 0}, "latent_rank"),
    ({"qk_norm": True}, "qk_norm"),
    ({"num_experts": 4, "expert_top_k": 2, "experts_held": (2, 6)}, "experts_held"),
    ({"experts_held": (0, 2)}, "experts_held"),
    ({"linear_gate": "row"}, "linear_gate"),
    ({"linear_gate": "channel", "linear_gate_rank": 0}, "linear_gate_rank must be > 0"),
    ({"num_experts": 4, "moe_capacity_factor": 1.0}, "moe_capacity_factor"),
    ({"layer_types": ("linear",) * 3 + ("full",), "num_experts": 4, "rope_full_layers": True}, 'beside "full" layers'),
])
def test_the_config_refuses_by_name_what_these_layers_cannot_run(bad, named):
    with pytest.raises(ValueError, match=named):
        TransformerConfig(**{**KIMI, **bad})


def test_a_mesh_the_ring_cache_and_migration_refuse_the_config_by_name(params):
    with pytest.raises(ValueError, match='"latent" layers'):
        param_specs(CFG)
    with pytest.raises(ValueError, match="experts_held"):
        param_specs(_uncut(experts_held=(0, 4)))
    with pytest.raises(ValueError, match='"latent" layers'):
        init_cache(CFG, 1, 32)
    eng = engine_of(params)
    try:
        with pytest.raises(ValueError, match="recurrent state"):
            eng.prefill_export(prompt_of(20), mig_id="m")
        with pytest.raises(ValueError, match="recurrent state"):
            eng.adopt_migration({"prompt": prompt_of(20), "tok0": 1}, {})
    finally:
        eng.shutdown()
    for kw, named in (({"decode_chunk": 4}, "decode_chunk"), ({"quantize": True}, "quantize=True")):
        with pytest.raises(ValueError, match=named):
            engine_of(params, **kw)


# what the five served configurations' trees and cache shapes hash to at the
# parent of the change that brought the fields above (``init_params`` and
# ``init_paged_cache`` under ``jax.eval_shape``: every leaf's path, shape and type)
BEFORE = {"smollm2-1.7b-serve": "377cee5cb08d1196", "trinity-mini-serve-l5": "7f4c62df4801adef",
          "sdar-30b-a3b-serve-l6": "ba0e9dcd3c37272b", "olmo-hybrid-7b-serve-l16": "967bad57f0959823",
          "smollm2-1.7b-train-l8": "0c0b23d004dae30d"}


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_a_config_without_the_new_fields_builds_the_tree_and_cache_it_built_before(name):
    c = system.load_json(f"benchmark/configs/{name}.json")
    run = c["run"]
    cfg = system.model_module(c).program_config(c, max_seq_len=run.get("max_seq_len", 2048), dtype=run["dtype"],
                                                param_dtype=run["param_dtype"])
    assert cfg.experts_held is None and not cfg.latent_layers and not cfg.split_ffn and cfg.linear_gate == "head"
    tree = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    cache = jax.eval_shape(lambda: init_paged_cache(cfg, 8, 16, **({"slots": 4} if cfg.hybrid else {})))
    desc = sorted((jax.tree_util.keystr(p), tuple(x.shape), str(x.dtype))
                  for p, x in jax.tree_util.tree_leaves_with_path({"params": tree, "cache": cache}))
    assert hashlib.sha256(json.dumps(desc).encode()).hexdigest()[:16] == BEFORE[name]
    if not cfg.hybrid:
        assert set(cache) == {"k", "v"}


def test_stats_and_counts_carry_the_new_keys_only_for_a_config_that_has_the_mechanism():
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64, num_experts=4, expert_top_k=2,
                            dtype=jnp.float32)
    eng = LLMEngine(cfg, init_params(cfg, jax.random.key(0)), max_batch_size=2, max_seq_len=64, kv_block_size=16,
                    kv_num_blocks=16, prefill_chunk_tokens=32)
    try:
        eng.submit([1, 2, 3], max_tokens=2).result(timeout=120)
        s = eng.stats()
        assert s["moe_assignments"] == sum(s["moe_expert_assignments"]) > 0
        assert not {"moe_assignments_local", "moe_experts_held", "latent_layers", "kv_bytes_per_token"} & set(s)
    finally:
        eng.shutdown()
