"""A config with "linear" layers (Gated DeltaNet beside full attention:
``TransformerConfig.layer_types`` naming ``"linear"``, Olmo Hybrid) against
the plain float32 reference ``benchmark/models/olmo_hybrid.py``: the two forms
of the recurrence, the forward, chunked paged prefill and decode through pages
and per-slot state, and the engine's two kinds of sequence state in one
manager: slots zeroed or restored at admission, snapshots on radix nodes,
their pool's exhaustion and eviction. Toy size, float32, CPU: no near-ties,
so tokens are compared one for one.
"""

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.models import olmo_hybrid  # noqa: E402
from ray_tpu.models import generation  # noqa: E402
from ray_tpu.models.generation import (copy_sequence_state, init_paged_cache, init_sequence_state,  # noqa: E402
                                       paged_decode_step, paged_forward_with_cache)
from ray_tpu.models.transformer import TransformerConfig, forward, init_params, loss_fn, param_specs  # noqa: E402
from ray_tpu.ops import gated_delta as gd  # noqa: E402
from ray_tpu.serve.kv_blocks import SnapshotPool  # noqa: E402
from ray_tpu.serve.llm import LLMEngine  # noqa: E402
from ray_tpu.serve.prefix_cache import PrefixCache  # noqa: E402

C = dict(model="olmo_hybrid", vocab_size=512, hidden_size=64, intermediate_size=128, num_hidden_layers=8,
         num_attention_heads=4, num_key_value_heads=4, hidden_act="silu", max_position_embeddings=512,
         attention_bias=False, rms_norm_eps=1e-6, tie_word_embeddings=False,
         layer_types=(["linear_attention"] * 3 + ["full_attention"]) * 2,
         linear_num_key_heads=4, linear_num_value_heads=4, linear_key_head_dim=8, linear_value_head_dim=32,
         linear_conv_kernel_dim=4, linear_allow_neg_eigval=True, rope_parameters={"rope_theta": None})
CFG = olmo_hybrid.program_config(C, dtype="float32", param_dtype="float32", max_seq_len=256)
BS, CHUNK = 16, 32


@pytest.fixture(scope="module")
def params():
    p = init_params(CFG, jax.random.key(3))
    return {**p, "embed": p["embed"] * 8.0}  # rows of O(1) entries: there is no norm on the first layer's input


@pytest.fixture(scope="module")
def reference():
    return olmo_hybrid.make_reference(C)


def engine_of(params, **kw):
    kw = {"max_batch_size": 4, "max_seq_len": 256, "kv_block_size": BS, "kv_num_blocks": 80,
          "prefill_chunk_tokens": CHUNK, **kw}
    return LLMEngine(CFG, params, **kw)


@pytest.fixture(scope="module")
def cold(params):
    """The engine without a prefix cache: every request prefills its whole prompt from a zero state."""
    eng = engine_of(params, prefix_cache=False)
    yield eng
    eng.shutdown()


def prompt_of(n, seed=0):
    return np.random.default_rng([seed, n]).integers(1, 512, size=n).tolist()


def whole(eng):
    """Both pools' counts add up: pages in use are the cache's, snapshot entries held are the nodes'."""
    s = eng.stats()
    return (s["kv_blocks_in_use"] == s["prefix_cache_blocks"] and s["state_snapshots_in_use"] == eng.store.prefix.snapshots
            and s["active_slots"] == 0)


def settle(eng, timeout=10.0):
    t0 = time.time()
    while time.time() - t0 < timeout:
        if whole(eng):
            return True
        time.sleep(0.02)
    return False


# ---------------------------------------------------------------------------
# the recurrence
# ---------------------------------------------------------------------------
def _inputs(T, B=2, H=4, dk=8, dv=32, seed=0):
    rng = np.random.default_rng([seed, T])
    unit = lambda x: x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)  # noqa: E731
    q = (unit(rng.normal(size=(B, T, H, dk))) / np.sqrt(dk)).astype(np.float32)
    k = unit(rng.normal(size=(B, T, H, dk))).astype(np.float32)
    v = rng.normal(size=(B, T, H, dv)).astype(np.float32)
    g = (-0.5 * np.abs(rng.normal(size=(B, T, H)))).astype(np.float32)
    beta = (2.0 / (1.0 + np.exp(-rng.normal(size=(B, T, H))))).astype(np.float32)
    S0 = rng.normal(size=(B, H, dk, dv)).astype(np.float32)
    return S0, q, k, v, g, beta


def _sequential(S0, q, k, v, g, beta, valid):
    S, outs = jnp.asarray(S0), []
    for t in range(q.shape[1]):
        o, Sn = gd.gated_delta_step(S, q[:, t], k[:, t], v[:, t], jnp.exp(g[:, t]), beta[:, t])
        S = jnp.where(valid[:, t][:, None, None, None], Sn, S)
        outs.append(o)
    return jnp.stack(outs, 1), S


@pytest.mark.parametrize("start", ["zero", "nonzero"])
@pytest.mark.parametrize("T", [64, 128, 1, 37, 150])
def test_the_chunked_recurrence_is_the_sequential_one(T, start):
    S0, q, k, v, g, beta = _inputs(T)
    if start == "zero":
        S0 = np.zeros_like(S0)
    valid = np.ones((2, T), bool)
    want_o, want_S = _sequential(S0, q, k, v, g, beta, valid)
    got_o, got_S = jax.jit(gd.gated_delta_chunked)(S0, q, k, v, g, beta)
    assert float(jnp.max(jnp.abs(got_o - want_o))) < 2e-5
    assert float(jnp.max(jnp.abs(got_S - want_S))) < 2e-5


@pytest.mark.parametrize("T,real", [(128, 100), (96, 64), (70, 1)])
def test_a_padded_chunk_advances_the_state_by_its_real_tokens_only(T, real):
    S0, q, k, v, g, beta = _inputs(T, seed=1)
    valid = np.ones((2, T), bool)
    valid[1, real:] = False
    want_o, want_S = _sequential(S0, q, k, v, g, beta, valid)
    got_o, got_S = jax.jit(gd.gated_delta_chunked)(S0, q, k, v, g, beta, valid)
    assert float(jnp.max(jnp.abs(got_S - want_S))) < 2e-5
    assert float(jnp.max(jnp.abs(got_o[0] - want_o[0]))) < 2e-5
    assert float(jnp.max(jnp.abs(got_o[1, :real] - want_o[1, :real]))) < 2e-5
    # and the same state as the real tokens alone give
    alone = gd.gated_delta_chunked(S0[1:], q[1:, :real], k[1:, :real], v[1:, :real], g[1:, :real], beta[1:, :real])[1]
    assert float(jnp.max(jnp.abs(got_S[1] - alone[0]))) < 2e-5


@pytest.mark.parametrize("H,dv,group", [(4, 32, 4), (2, 64, 2), (4, 128, 1), (3, 16, 1), (30, 192, 2)])
def test_heads_share_a_row_of_the_state_where_that_fills_the_lanes(H, dv, group):
    assert gd.lane_group(H, dv) == group
    S = np.random.default_rng(0).normal(size=(2, 3, H, 8, dv)).astype(np.float32)
    packed = gd.pack_state(jnp.asarray(S), group)
    assert packed.shape == (2, 3, H // group, 8, group * dv)
    np.testing.assert_array_equal(np.asarray(gd.unpack_state(packed, group)), S)
    # head h lies at group h // g, lanes [(h % g) dv, (h % g + 1) dv)
    h = H - 1
    np.testing.assert_array_equal(np.asarray(packed[0, 0, h // group, :, (h % group) * dv:(h % group + 1) * dv]), S[0, 0, h])


def test_the_decode_kernel_is_the_step_and_leaves_idle_rows_alone():
    S0, q, k, v, g, beta = _inputs(1, B=3)
    group = gd.lane_group(4, 32)
    state = jnp.asarray(np.random.default_rng(1).normal(size=(3, 6, 4 // group, 8, group * 32)).astype(np.float32))
    slots, live = jnp.asarray([4, 0, 2], jnp.int32), jnp.asarray([True, False, True])
    args = (q[:, 0], k[:, 0], v[:, 0], jnp.exp(g[:, 0]), beta[:, 0])
    want_o, want = gd.gated_delta_decode(state, jnp.int32(1), slots, live, *args, kernel=False)
    got_o, got = gd.gated_delta_decode(state, jnp.int32(1), slots, live, *args, kernel=True)  # interpret mode here
    assert float(jnp.max(jnp.abs(got_o - want_o)[jnp.asarray([0, 2])])) < 1e-5
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    changed = np.abs(np.asarray(got) - np.asarray(state)).max(axis=(2, 3, 4)) > 0  # [layers, slots]
    assert changed.tolist() == [[False] * 6, [False, False, True, False, True, False], [False] * 6]


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------
def test_the_parameter_tree_is_a_period_a_scan_step_and_counts_as_the_reference_counts(params):
    assert len(params["linear_layers"]) == 3  # the j-th linear layer of every period is one stack
    lin, full = params["linear_layers"][1], params["layers"]
    assert lin["lin_wq"].shape == (2, 64, 4, 8) and lin["lin_wv"].shape == (2, 64, 4, 32)
    assert lin["conv_w"].shape == (2, 4, 4 * (2 * 8 + 32)) and lin["A_log"].shape == (2, 4)
    assert full["wq"].shape == (2, 64, 4, 16) and full["q_norm"].shape == (2, 64)  # gains over the whole projection
    assert "attn_norm" not in full and "attn_norm" not in lin and "post_attn_norm" in lin  # no norm on a branch's input
    assert sum(a.size for a in jax.tree.leaves(params)) == olmo_hybrid.n_params(C)
    specs = param_specs(CFG)
    assert [set(x) for x in specs["linear_layers"]] == [set(lin)] * 3


def test_forward_is_the_references(params, reference):
    toks = np.asarray([prompt_of(150), prompt_of(150, 1)])
    got = jax.jit(lambda p, t: forward(CFG, p, t))(params, jnp.asarray(toks))
    want = jnp.stack([reference[0](params, jnp.asarray(t)) for t in toks])
    assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) < 1e-4
    assert abs(float(loss_fn(CFG, params, jnp.asarray(toks))) - reference[1](params, jnp.asarray(toks))) < 1e-4


def _prefill(params, cache, prompt, bt_row, slot, start=0):
    """``prompt[start:]`` in chunks of CHUNK through the paged forward, as the engine's program does."""
    @jax.jit
    def chunk(params, cache, toks, bt, slot, start, length):
        valid = (jnp.arange(CHUNK) < length)[None, :]
        lg, cache = paged_forward_with_cache(CFG, params, cache, bt, toks, start + jnp.arange(CHUNK)[None, :],
                                             valid=valid, slots=slot, use_decode_kernel=False)
        return lg[0, length - 1], cache

    lg = None
    for s in range(start, len(prompt), CHUNK):
        piece = prompt[s:s + CHUNK]
        toks = np.zeros((1, CHUNK), np.int32)
        toks[0, :len(piece)] = piece
        lg, cache = chunk(params, cache, jnp.asarray(toks), bt_row, jnp.asarray([slot], jnp.int32),
                          jnp.int32(s), jnp.int32(len(piece)))
    return lg, cache


@pytest.mark.parametrize("kernel", [False, True])
def test_chunked_prefill_then_decode_through_pages_and_state_is_the_references(params, reference, kernel):
    lens, k, M = [150, 97], 3, 12
    prompts = [prompt_of(n) for n in lens]
    cache = init_paged_cache(CFG, 2 * M + 1, BS, slots=4)
    assert cache["k"].shape == (2, 2 * M + 1, BS, 64) and cache["state"].shape[:2] == (6, 4)
    bt = jnp.asarray(np.arange(1, 2 * M + 1, dtype=np.int32).reshape(2, M))
    first = []
    for i, p in enumerate(prompts):
        lg, cache = _prefill(params, cache, p, bt[i:i + 1], slot=i + 1)
        first.append(lg)
    got = [jnp.stack(first)]
    toks, pos = jnp.argmax(got[0], -1).astype(jnp.int32), jnp.asarray(lens, jnp.int32)
    decode = jax.jit(lambda p, c, t, pos: paged_decode_step(CFG, p, c, t, pos, bt, slots=jnp.asarray([1, 2], jnp.int32),
                                                            use_decode_kernel=kernel))
    generated = []
    for _ in range(k):
        generated.append(np.asarray(toks))
        lg, cache = decode(params, cache, toks, pos)
        got.append(lg)
        toks, pos = jnp.argmax(lg, -1).astype(jnp.int32), pos + 1
    got = jnp.stack(got, 1)
    want = []
    for i, p in enumerate(prompts):
        seq = p + [int(g[i]) for g in generated]
        want.append(reference[0](params, jnp.asarray(seq), jnp.arange(len(p) - 1, len(p) + k)))
    want = jnp.stack(want)
    assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) < 1e-4
    assert float(jnp.abs(cache["state"][:, jnp.asarray([0, 3])]).max()) == 0.0  # no other slot was touched


def test_a_follow_up_prefilled_from_a_restored_snapshot_has_the_full_prefills_logits(params, reference):
    turn1, more, M = prompt_of(64), prompt_of(23, 1), 8
    cache = init_paged_cache(CFG, 2 * M + 1, BS, slots=2)
    bt = jnp.asarray(np.arange(1, 2 * M + 1, dtype=np.int32).reshape(2, M))
    _, cache = _prefill(params, cache, turn1, bt[:1], slot=0)
    snaps = copy_sequence_state(init_sequence_state(CFG, 3), cache, 2, 0)          # taken: entry 2 <- slot 0
    _, cache = _prefill(params, cache, prompt_of(40, 2), bt[1:], slot=1)            # a foreign sequence in between
    cache = copy_sequence_state(cache, snaps, 1, 2)                                  # restored: slot 1 <- entry 2
    shared = jnp.concatenate([bt[:1, :4], bt[1:, 4:]], axis=1)                      # turn 1's four pages, then its own
    got, cache = _prefill(params, cache, turn1 + more, shared, slot=1, start=64)
    want = reference[0](params, jnp.asarray(turn1 + more), jnp.asarray([len(turn1 + more) - 1]))[0]
    assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) < 1e-4


def test_the_ring_cache_refuses_a_config_with_linear_layers_by_name(params):
    with pytest.raises(ValueError, match="linear"):
        generation.init_cache(CFG, 1, 32)
    with pytest.raises(ValueError, match="linear"):
        generation.generate(CFG, params, jnp.ones((1, 8), jnp.int32), max_new_tokens=2)
    with pytest.raises(ValueError, match="slots"):
        init_paged_cache(CFG, 8, BS)


# ---------------------------------------------------------------------------
# the engine: two kinds of sequence state in one manager
# ---------------------------------------------------------------------------
def test_a_follow_up_turn_from_a_restored_snapshot_is_the_cold_engines(params, reference, cold):
    eng = engine_of(params)
    try:
        system = prompt_of(48)
        assert eng.generate(system, max_tokens=1) == cold.generate(system, max_tokens=1)
        s = eng.stats()
        assert (s["state_snapshots_taken"], s["state_snapshots_in_use"], s["state_zeroed"]) == (1, 1, 1)  # after the prompt's pages
        turn1 = system + prompt_of(21, 1)
        r1 = eng.generate(turn1, max_tokens=20)
        assert r1 == cold.generate(turn1, max_tokens=20)
        turn2 = turn1 + r1 + prompt_of(9, 2)
        r2 = eng.generate(turn2, max_tokens=12)
        assert r2 == cold.generate(turn2, max_tokens=12)
        s = eng.stats()
        # turn 1 skipped the system prompt's 48 tokens, turn 2 the 80 of turn 1's prompt and reply that fill pages
        assert (s["state_restores"], s["prefix_tokens_reused"], s["prefix_tokens_matched"]) == (2, 48 + 80, 48 + 80)
        want = reference[0](params, jnp.asarray(turn2 + r2), jnp.arange(len(turn2) - 1, len(turn2) + 11))
        assert np.asarray(jnp.argmax(want, -1)).tolist() == r2
        assert settle(eng)
        assert eng.store.flush_prefix_cache() > 0 and eng.stats()["state_snapshots_in_use"] == 0  # node freed -> snapshot freed
    finally:
        eng.shutdown()


def test_a_page_match_deeper_than_its_snapshot_skips_only_to_the_snapshot(params, cold):
    eng = engine_of(params)
    try:
        system = prompt_of(48)
        eng.generate(system, max_tokens=1)
        turn1 = system + prompt_of(21, 1)
        r1 = eng.generate(turn1, max_tokens=20)
        assert settle(eng)
        with eng._lock:  # the deeper snapshot goes, as when the pool is full; its pages stay
            deep = max(eng.store.prefix._snap_nodes.values(), key=lambda nd: nd.seq)
            eng.store.snap_pool.free(eng.store.prefix._detach(deep))
        before = eng.stats()
        turn2 = turn1 + r1 + prompt_of(9, 2)
        assert eng.generate(turn2, max_tokens=12) == cold.generate(turn2, max_tokens=12)
        s = eng.stats()
        assert s["prefix_tokens_matched"] - before["prefix_tokens_matched"] == 80   # five pages matched
        assert s["prefix_tokens_reused"] - before["prefix_tokens_reused"] == 48     # the system prompt's snapshot
        assert s["state_restores"] - before["state_restores"] == 1
        assert settle(eng)
    finally:
        eng.shutdown()


def test_a_shared_prefix_whose_lone_first_session_aged_its_snapshot_out_of_a_full_pool_gets_it_back(params, cold):
    """A document is sent once, then ONE session holds two turns on it while
    the snapshot pool is full: the document's snapshot, above a single child
    and restored from once, ages and goes. The next reader prefills the
    document again, cuts a chunk at its end and leaves a snapshot there; the
    reader after that restores."""
    eng = engine_of(params, state_snapshots=3)
    try:
        doc = prompt_of(96)  # six pages, three chunks
        eng.generate(doc, max_tokens=1)
        turn1 = doc + prompt_of(21, 1)
        r1 = eng.generate(turn1, max_tokens=20)
        turn2 = turn1 + r1 + prompt_of(9, 2)
        r2 = eng.generate(turn2, max_tokens=12)
        assert (r1, r2) == (cold.generate(turn1, max_tokens=20), cold.generate(turn2, max_tokens=12))
        assert settle(eng)
        # three entries, all held by nodes: the document's (aged), turn 1's (aged) and turn 2's
        assert eng.store.prefix.snapshot_at(doc) == (eng.store.prefix.snapshot_at(doc)[0], 96) and eng.store.prefix.snapshots == 3
        other = prompt_of(40, 7)  # its snapshot needs an entry: the oldest goes, which is the document's
        eng.generate(other, max_tokens=1)
        assert settle(eng) and eng.store.prefix.snapshot_at(doc) == (-1, 0)
        before = eng.stats()
        a = doc + prompt_of(30, 3)
        assert eng.generate(a, max_tokens=20) == cold.generate(a, max_tokens=20)
        s = eng.stats()
        assert s["state_restores"] == before["state_restores"] and s["state_zeroed"] - before["state_zeroed"] == 1
        assert s["prefill_chunks"] - before["prefill_chunks"] == 4  # 32 + 32 + 32 to the document's end, then the rest
        assert settle(eng) and eng.store.prefix.snapshot_at(doc)[1] == 96  # left at the branch, while the prompt was prefilled
        before = eng.stats()
        b = doc + prompt_of(30, 4)
        assert eng.generate(b, max_tokens=20) == cold.generate(b, max_tokens=20)
        s = eng.stats()
        assert s["state_restores"] - before["state_restores"] == 1
        assert s["prefix_tokens_reused"] - before["prefix_tokens_reused"] == 96
        assert s["prefill_chunks"] - before["prefill_chunks"] == 1
        assert settle(eng)
    finally:
        eng.shutdown()


def test_a_prefix_whose_first_reader_ran_on_past_it_gets_a_snapshot_from_the_second(params, cold):
    """Nobody sent the document alone: the first request is document + question, so its snapshots lie
    past the document's end. The second reader finds pages and no state, and leaves one at the branch."""
    eng = engine_of(params)
    try:
        doc = prompt_of(96)
        for seed, restores in ((1, 0), (2, 0), (3, 1)):
            q = doc + prompt_of(25, seed)
            before = eng.stats()["state_restores"]
            assert eng.generate(q, max_tokens=8) == cold.generate(q, max_tokens=8)
            assert eng.stats()["state_restores"] - before == restores
            assert settle(eng)
        assert eng.store.prefix.snapshot_at(doc)[1] == 96
    finally:
        eng.shutdown()


def test_a_slot_reused_after_a_foreign_occupant_gives_the_fresh_result(params, cold):
    eng = engine_of(params, max_batch_size=1, prefix_cache=False)
    try:
        eng.generate(prompt_of(70, 5), max_tokens=9)
        p = prompt_of(41, 6)
        assert eng.generate(p, max_tokens=9) == cold.generate(p, max_tokens=9)
        assert eng.stats()["state_zeroed"] == 2 and eng.stats()["state_snapshots_taken"] == 0
    finally:
        eng.shutdown()


def test_a_discarded_row_step_never_reaches_a_snapshot(params, cold):
    """An EOS is read a step late: the step dispatched meanwhile fed the EOS
    to the slot's state. Here that step ends a page, so a snapshot was taken
    right behind it; it must go with the step."""
    tp, s = 43, 4  # decode step s + 1 (discarded) writes position tp + s = 47: the end of page 2
    prompt = prompt_of(tp)
    free_run = cold.generate(prompt, max_tokens=12)
    eos = free_run[s]
    assert eos not in free_run[:s]
    eng = engine_of(params)
    try:
        out = eng.generate(prompt, max_tokens=12, eos_id=eos)
        assert out == free_run[:s + 1]
        assert settle(eng)
        st = eng.stats()
        assert st["decode_row_steps_discarded"] == 1
        # what was published: the prompt's two whole pages, the second with the snapshot taken during
        # prefill; nothing at 48 tokens, where the discarded step's state stood
        carries = [nd.snapshot >= 0 for nd in eng.store.prefix._chain(prompt + out, 3)]
        assert carries == [False, True] and st["state_snapshots_taken"] == 2 and st["state_snapshots_in_use"] == 1
        follow = prompt + out + prompt_of(11, 3)
        assert eng.generate(follow, max_tokens=6) == cold.generate(follow, max_tokens=6)
        assert eng.stats()["prefix_tokens_reused"] == 32
    finally:
        eng.shutdown()


def test_snapshot_pool_exhaustion_and_eviction_fail_no_request_and_keep_the_counts_whole(params, cold):
    eng = engine_of(params, state_snapshots=1)
    try:
        a1, b1 = prompt_of(40, 7), prompt_of(52, 8)
        ra = eng.generate(a1, max_tokens=30)
        rb = eng.generate(b1, max_tokens=30)      # its snapshot takes the one entry: a's is detached, a's pages stay
        assert (ra, rb) == (cold.generate(a1, max_tokens=30), cold.generate(b1, max_tokens=30))
        assert settle(eng)
        s = eng.stats()
        assert (s["state_snapshots_in_use"], s["state_snapshots_evicted"]) == (1, 1)
        a2 = a1 + ra + prompt_of(7, 9)
        assert eng.generate(a2, max_tokens=8) == cold.generate(a2, max_tokens=8)
        t = eng.stats()
        assert t["prefix_tokens_matched"] - s["prefix_tokens_matched"] == 64  # the pages were there
        assert t["prefix_tokens_reused"] == s["prefix_tokens_reused"]           # no snapshot left on them: from zero
        assert t["state_zeroed"] - s["state_zeroed"] == 1
        # four at once on a pool of one: whoever finds no entry goes without
        futs = [eng.submit(prompt_of(33 + i, 10 + i), max_tokens=20, eos_id=511) for i in range(4)]
        for i, f in enumerate(futs):
            assert f.result(timeout=120) == cold.generate(prompt_of(33 + i, 10 + i), max_tokens=20, eos_id=511)
        assert settle(eng)
        assert eng.stats()["state_snapshots_in_use"] <= 1
    finally:
        eng.shutdown()
    assert eng.store.allocator.used_blocks == len(eng.store.prefix) and eng.store.snap_pool.in_use == eng.store.prefix.snapshots


def test_the_engine_reads_out_the_state_a_served_turn_left_and_it_is_the_references(params, reference):
    """``state_snapshot``: what a check holds the engine's own state by. Two
    turns through the engine with a foreign sequence decoding beside them;
    the snapshot the second turn left covers its last whole page and is the
    reference recurrence's state after exactly those tokens. An engine whose
    admission loses the restore leaves another state there."""
    def served(eng):
        turn1 = prompt_of(48) + prompt_of(21, 1)
        other = eng.submit(prompt_of(30, 9), max_tokens=60, eos_id=511)   # a live neighbour in another slot
        r1 = eng.generate(turn1, max_tokens=20)
        turn2 = turn1 + r1 + prompt_of(9, 2)
        r2 = eng.generate(turn2, max_tokens=12)
        other.result(timeout=120)
        assert settle(eng)
        got = eng.store.state_snapshot(turn2 + r2)
        assert eng.store.state_snapshot(prompt_of(64, 5)) is None                # no node on that path
        ticks = (eng.store.prefix._tick, eng.stats()["state_restores"])
        assert eng.store.state_snapshot(turn2 + r2)["tokens"] == got["tokens"] and ticks == (eng.store.prefix._tick, eng.stats()["state_restores"])
        _, want = reference[0](params, jnp.asarray(turn2 + r2), jnp.asarray([0]), states_after=got["tokens"])
        assert got["tokens"] == (len(turn2) + 12 - 1) // BS * BS and got["state"].shape == want.shape
        return float(jnp.linalg.norm(got["state"] - want) / jnp.linalg.norm(want))

    eng = engine_of(params)
    try:
        assert served(eng) < 1e-4
    finally:
        eng.shutdown()
    eng = engine_of(params)
    eng._restore_state = lambda cache, snaps, slot, entry: eng._zero_state(cache, slot)   # the fault: a slot never restored
    try:
        assert served(eng) > 0.05
    finally:
        eng.shutdown()


def test_without_a_snapshot_pool_every_request_prefills_from_zero(params, cold):
    eng = engine_of(params, state_snapshots=0)
    try:
        p = prompt_of(50, 11)
        r = eng.generate(p, max_tokens=20)
        q = p + r + prompt_of(5, 12)
        assert eng.generate(q, max_tokens=5) == cold.generate(q, max_tokens=5)
        s = eng.stats()
        assert (s["state_snapshots_taken"], s["prefix_tokens_reused"], s["state_zeroed"]) == (0, 0, 2)
        assert s["prefix_tokens_matched"] == 64 and s["kv_blocks_shared"] == 0
    finally:
        eng.shutdown()


def test_a_cancel_and_a_cache_reset_free_both_kinds(params):
    eng = engine_of(params)
    try:
        stream = eng.submit_stream(prompt_of(60, 13), max_tokens=150, eos_id=511)
        got = [next(stream) for _ in range(40)]  # past a page boundary: the request holds a snapshot by now
        assert len(got) == 40
        stream.close()
        assert settle(eng)
        s = eng.stats()
        assert (s["kv_blocks_in_use"], s["state_snapshots_in_use"], s["slots_evicted"]) == (0, 0, 1)
        assert s["state_snapshots_taken"] >= 2
        eng.generate(prompt_of(48, 14), max_tokens=1)
        assert eng.stats()["state_snapshots_in_use"] == 1
        # the loop's own recovery (``_fail_inflight`` + ``_reset_cache`` on the engine thread): a step that raises
        eng.runner._decode_k_paged = lambda *a, **k: (_ for _ in ()).throw(RuntimeError("the device pool is gone"))
        with pytest.raises(RuntimeError):
            eng.submit(prompt_of(30, 15), max_tokens=200, eos_id=511).result(timeout=30)
        assert settle(eng)
        s = eng.stats()
        assert (s["kv_blocks_in_use"], s["prefix_cache_blocks"], s["state_snapshots_in_use"]) == (0, 0, 0)
        deadline = time.time() + 10  # the request fails before the loop has made the pools anew
        while float(jnp.abs(eng.runner.cache["state"]).max()) and time.time() < deadline:
            time.sleep(0.02)
        assert float(jnp.abs(eng.runner.cache["state"]).max()) == 0.0
    finally:
        eng.shutdown()


@pytest.mark.parametrize("kw,named", [({"decode_chunk": 4}, "decode_chunk"), ({"quantize": True}, "quantize=True")])
def test_the_engine_refuses_by_name_what_the_slots_state_cannot_follow(params, kw, named):
    with pytest.raises(ValueError, match=named):
        engine_of(params, **kw)


def test_a_mesh_is_refused_for_a_config_with_linear_layers(params):
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    with pytest.raises(ValueError, match="mesh"):
        engine_of(params, mesh=mesh)
    with pytest.raises(ValueError, match="no mesh"):
        forward(CFG, params, jnp.ones((1, 8), jnp.int32), act_spec=object())


@pytest.mark.parametrize("call", ["prefill_export", "adopt_migration"])
def test_migration_is_refused_for_a_config_with_linear_layers(params, cold, call):
    with pytest.raises(ValueError, match="recurrent state"):
        if call == "prefill_export":
            cold.prefill_export(prompt_of(20), mig_id="m")
        else:
            cold.adopt_migration({"prompt": prompt_of(20), "tok0": 1}, {})


def test_a_config_without_linear_layers_takes_no_snapshot_pool_and_reports_no_state():
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=2, d_ff=64, max_seq_len=64,
                            dtype=jnp.float32)
    p = init_params(cfg, jax.random.key(0))
    with pytest.raises(ValueError, match="state_snapshots"):
        LLMEngine(cfg, p, max_batch_size=2, max_seq_len=64, state_snapshots=4)
    eng = LLMEngine(cfg, p, max_batch_size=2, max_seq_len=64, state_snapshots=0)
    try:
        eng.generate([1, 2, 3], max_tokens=3)
        assert not [k for k in eng.stats() if k.startswith("state_") or k == "prefix_tokens_matched"]
        assert set(eng.runner.cache) == {"k", "v"} and eng.runner.snaps is None
    finally:
        eng.shutdown()


@pytest.mark.parametrize("kw", [
    {}, {"n_kv_heads": 1, "head_dim": 24, "tie_embeddings": False},
    {"qk_norm": True, "attn_gate": True, "post_norms": True},
    {"layer_types": ("sliding", "full"), "sliding_window": 8, "rope_full_layers": False},
    {"num_experts": 4, "num_dense_layers": 1, "num_shared_experts": 1, "router_bias": True, "router_score": "sigmoid"},
])
def test_a_config_without_linear_layers_builds_the_tree_and_cache_it_built_before(kw):
    """The switches this PR adds default to what was: the same leaves with
    the same shapes, drawn from the same keys, and the pool's layer axis is
    every layer."""
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=2, d_ff=64, max_seq_len=64, **kw)
    assert not cfg.hybrid and cfg.kv_layers == 2 and cfg.pre_norms and not cfg.qk_norm_whole
    p = init_params(cfg, jax.random.key(0))
    dh = cfg.head_dim
    want = {"attn_norm": (32,), "ffn_norm": (32,), "wq": (32, 2, dh), "wk": (32, cfg.kv_heads, dh),
            "wv": (32, cfg.kv_heads, dh), "wo": (2, dh, 32)}
    if cfg.qk_norm:
        want.update(q_norm=(dh,), k_norm=(dh,))
    if cfg.attn_gate:
        want["wg"] = (32, 2, dh)
    if cfg.post_norms:
        want.update(post_attn_norm=(32,), post_ffn_norm=(32,))
    dense = {"w1": (32, 64), "w3": (32, 64), "w2": (64, 32)}
    experts = {"router": (32, 4), "we1": (4, 32, 64), "we3": (4, 32, 64), "we2": (4, 64, 32), "router_bias": (4,),
               "ws1": (32, 64), "ws3": (32, 64), "ws2": (64, 32)}
    stacks = {"layers": (2 - cfg.dense_stack, {**want, **(experts if cfg.num_experts else dense)})}
    if cfg.dense_stack:
        stacks["dense_layers"] = (1, {**want, **dense})
    assert set(p) == {"embed", "final_norm", *stacks} | ({"head"} if not cfg.tie_embeddings else set())
    for name, (n, leaves) in stacks.items():
        assert {k: v.shape for k, v in p[name].items()} == {k: (n, *s) for k, s in leaves.items()}
    # the first layer's wq is still the first of eight splits of the first layer key
    layer_key = jax.random.split(jax.random.split(jax.random.key(0), 3)[1], 2)[0]
    from ray_tpu.models.common import dense_init

    first = p["dense_layers" if cfg.dense_stack else "layers"]["wq"][0]
    np.testing.assert_array_equal(np.asarray(first),
                                  np.asarray(dense_init(jax.random.split(layer_key, 8)[0], (32, 2, dh), 32, cfg.param_dtype)))
    cache = init_paged_cache(cfg, 9, 16)
    assert set(cache) == {"k", "v"} and cache["k"].shape == (2, 9, 16, cfg.kv_heads * dh)


@pytest.mark.parametrize("bad,named", [
    ({"layer_types": ("linear", "full", "full", "linear")}, "period"),
    ({"layer_types": ("linear",) * 4}, "period"),
    ({"linear_heads": 0}, "linear_heads"),
    ({"num_experts": 4}, "num_experts"),
    ({"pre_norms": False, "post_norms": False}, "pre_norms"),
    ({"qk_norm": False, "qk_norm_whole": True}, "qk_norm"),
])
def test_the_config_refuses_by_name_what_a_linear_layer_cannot_run(bad, named):
    kw = dict(vocab_size=64, d_model=32, n_layers=4, n_heads=2, d_ff=64, layer_types=("linear", "full") * 2,
              linear_heads=2, linear_key_dim=8, linear_value_dim=16)
    TransformerConfig(**kw)
    with pytest.raises(ValueError, match=named):
        TransformerConfig(**{**kw, **bad})


# ---------------------------------------------------------------------------
# the index and the pool on their own
# ---------------------------------------------------------------------------
def test_the_snapshot_pool_is_a_free_list_that_refuses_a_double_free():
    pool = SnapshotPool(2)
    a, b = pool.alloc(), pool.alloc()
    assert {a, b} == {0, 1} and pool.alloc() == -1 and pool.in_use == 2
    pool.free(a)
    assert pool.in_use == 1 and pool.alloc() == a
    with pytest.raises(ValueError):
        pool.free(7)
    assert SnapshotPool(0).alloc() == -1


def test_match_snapshot_returns_the_deepest_snapshot_on_the_path_within_the_limit():
    cache = PrefixCache(4)
    toks = list(range(40))
    cache.insert(toks, list(range(1, 11)), lambda p: True)
    assert cache.match_snapshot(toks, 39) == (list(range(1, 11)), 40, -1, 0)
    assert cache.attach_snapshot(toks, 8, 5) and cache.attach_snapshot(toks, 24, 6)
    assert not cache.attach_snapshot(toks, 24, 7)         # the node carries one already
    assert not cache.attach_snapshot(toks, 10, 7)         # no whole number of blocks
    assert not cache.attach_snapshot(toks + [1] * 8, 48, 7)  # no such node
    assert cache.snapshots == 2
    assert cache.match_snapshot(toks, 39)[2:] == (6, 24)
    assert cache.match_snapshot(toks, 23)[2:] == (5, 8)    # the deeper one lies past the limit
    assert cache.match_snapshot(toks[:16] + [99] * 8, 23) == ([1, 2, 3, 4], 16, 5, 8)
    assert cache.match_snapshot([99] * 8, 7) == ([], 0, -1, 0)


def test_snapshot_at_reads_the_deepest_snapshot_on_a_path_and_moves_no_clock():
    cache = PrefixCache(4)
    a, b = list(range(24)), list(range(100, 116))
    cache.insert(a, [1, 2, 3, 4, 5, 6], lambda p: True)
    cache.insert(b, [7, 8, 9, 10], lambda p: True)
    assert cache.attach_snapshot(a, 8, 0) and cache.attach_snapshot(a, 16, 1) and cache.attach_snapshot(b, 16, 2)
    clocks = (cache._tick, [(nd.last_used, nd.snap_used) for nd in cache._nodes.values()])
    assert cache.snapshot_at(a) == (1, 16) and cache.snapshot_at(a[:12]) == (0, 8) and cache.snapshot_at(a[:7]) == (-1, 0)
    assert cache.snapshot_at(b + [5] * 9) == (2, 16) and cache.snapshot_at([99] * 8) == (-1, 0)
    assert clocks == (cache._tick, [(nd.last_used, nd.snap_used) for nd in cache._nodes.values()])
    assert cache.evict_snapshot() == 0                      # a read-out is no use: the oldest attached still goes first


def test_a_snapshot_goes_with_its_node_and_the_least_recently_used_goes_when_the_pool_is_full():
    cache = PrefixCache(4)
    a, b = list(range(16)), list(range(100, 116))
    cache.insert(a, [1, 2, 3, 4], lambda p: True)
    cache.insert(b, [5, 6, 7, 8], lambda p: True)
    assert cache.attach_snapshot(a, 16, 0) and cache.attach_snapshot(b, 16, 1) and cache.attach_snapshot(b, 8, 2)
    cache.match_snapshot(a, 100)                             # a's is the most recently used now
    # by use, not by depth: b's two were attached after a's, and a's was restored from since
    assert cache.evict_snapshot() == 1 and cache.snapshot_evictions == 1 and len(cache) == 8  # pages stay
    assert cache.evict_snapshot() == 2 and cache.evict_snapshot() == 0 and cache.evict_snapshot() == -1
    assert cache.attach_snapshot(a, 16, 3) and cache.attach_snapshot(a, 8, 4)
    assert cache.evict(4, lambda p: True) == [8, 7, 6, 5] and cache.take_freed_snapshots() == []  # b's cold chain
    assert cache.evict(1, lambda p: True) == [4] and cache.take_freed_snapshots() == [3]          # a's leaf, and its snapshot
    cache.drain()
    assert cache.take_freed_snapshots() == [4] and cache.snapshots == 0 and cache.take_freed_snapshots() == []


def test_a_snapshot_more_than_one_request_restored_from_does_not_age_and_keys_stand_for_tokens():
    from ray_tpu.serve.prefix_cache import chain_keys

    cache = PrefixCache(4)
    doc, s1 = list(range(8)), list(range(8)) + [50] * 8
    keys = tuple(chain_keys(s1, 4, 4))
    cache.insert(s1, [1, 2, 3, 4], lambda p: True, keys)
    assert cache.attach_snapshot(doc, 8, 0, keys) and cache.attach_snapshot(s1, 12, 1, keys)  # 0 above one child: aged
    assert cache.match_snapshot(s1, 15, keys) == ([1, 2, 3, 4], 16, 1, 12)
    assert cache.match_snapshot(doc + [60] * 8, 15)[2:] == (0, 8)
    cache.restored(0)
    cache.restored(0)                                        # two sessions were admitted on the document's
    assert cache.attach_snapshot(s1, 16, 2, keys)            # 1 ages (one child, never restored from); 0 does not
    assert [cache.evict_snapshot() for _ in range(3)] == [1, 0, 2]
    assert cache.attach_snapshot(doc, 8, 0)
    cache.restored(0)
    assert cache.attach_snapshot(s1, 16, 2)                  # one restore is a conversation's next turn: 0 ages
    assert cache.evict_snapshot() == 0


def test_the_branch_point_is_the_deepest_node_where_another_requests_tokens_part():
    cache = PrefixCache(4)
    doc = list(range(16))
    s1, s2 = doc + [50] * 8, doc + [60] * 8
    cache.insert(s1, list(range(1, 7)), lambda p: True)
    assert cache.branch_point(s2, 23) == 16                  # the document's last node has a child that is not s2's
    assert cache.branch_point(s1, 23) == 0                   # s1's own chain: every child is on its path
    assert cache.branch_point(s1[:20] + [9] * 4, 23) == 20   # parts from s1 inside its question
    assert cache.branch_point(s2, 15) == 0                   # the limit: short of the prompt's last token
    cache.insert(s2, [1, 2, 3, 4, 7, 8], lambda p: True)
    assert cache.branch_point(s1, 23) == 16 and cache.branch_point([7] * 8, 7) == 0


def test_attaching_below_ages_the_snapshots_above_on_single_child_nodes():
    cache = PrefixCache(4)
    system, s1, s2 = list(range(8)), list(range(8)) + [50] * 8, list(range(8)) + [60] * 8
    cache.insert(s1, [1, 2, 3, 4], lambda p: True)
    cache.insert(s2, [1, 2, 5, 6], lambda p: True)
    assert cache.attach_snapshot(system, 8, 0)               # two sessions branch off it: it stays as used as it is
    assert cache.attach_snapshot(s1, 12, 1)
    assert cache.attach_snapshot(s1, 16, 2)                  # below 1 on the only path: 1 ages at once
    assert cache.evict_snapshot() == 1
    assert cache.evict_snapshot() == 0                       # then by use: the system prompt's is the oldest left
