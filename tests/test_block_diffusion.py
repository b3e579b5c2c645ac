"""Generation by diffusion over blocks (``TransformerConfig.block_length`` > 1,
SDAR) against the plain float32 reference ``benchmark/models/sdar_moe.py``:
the block-causal forward, chunked paged prefill (dense lines and the kernel
in interpret mode), every denoising step and the commit of the program's
block step, and the engine's tokens through its normal path. Toy size,
float32, CPU: no near-ties, so tokens are compared one for one.
"""

import dataclasses
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.models import sdar_moe  # noqa: E402
from ray_tpu.models.generation import (init_paged_cache, open_blocks, paged_block_step,  # noqa: E402
                                       paged_forward_counted, select_rows, unmask_step)
from ray_tpu.models.transformer import TransformerConfig, forward, init_params  # noqa: E402
from ray_tpu.serve.llm import LLMEngine, TokenBlock  # noqa: E402
from ray_tpu.serve.openai_compat import OpenAICompatLLMServer  # noqa: E402

BK, MASK = 4, 500
C = dict(hidden_size=64, intermediate_size=96, moe_intermediate_size=48, num_attention_heads=4,
         num_key_value_heads=2, head_dim=32, vocab_size=512, num_experts=8, num_experts_per_tok=2,
         num_hidden_layers=2, max_position_embeddings=256, rope_theta=1e6, rms_norm_eps=1e-6,
         tie_word_embeddings=False, norm_topk_prob=True, block_length=BK, mask_token_id=MASK,
         denoising_steps=4, hidden_act="silu", rope_scaling=None)
CFG = sdar_moe.program_config(C, dtype="float32", param_dtype="float32", max_seq_len=256)
BS, CHUNK = 16, 32


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.key(0))


@pytest.fixture(scope="module")
def reference():
    return sdar_moe.make_reference(C)


@pytest.fixture(scope="module")
def engine(params):
    eng = LLMEngine(CFG, params, max_batch_size=4, max_seq_len=256, kv_block_size=BS, prefill_chunk_tokens=CHUNK)
    yield eng
    eng.shutdown()


def prompt_of(n, seed=0):
    return np.random.default_rng([seed, n]).integers(1, 512, size=n).tolist()


def close(a, b, tol=2e-4):
    return float(jnp.max(jnp.abs(jnp.asarray(a) - jnp.asarray(b)))) < tol


# ---------------------------------------------------------------------------
# the forward pass and chunked paged prefill
# ---------------------------------------------------------------------------
def test_forward_under_the_block_mask_is_the_references(params, reference):
    toks = jnp.asarray(prompt_of(30))
    assert close(forward(CFG, params, toks[None])[0], reference[0](params, toks))
    causal = dataclasses.replace(CFG, block_length=0)
    assert not close(forward(causal, params, toks[None])[0], reference[0](params, toks), tol=1e-2)


@pytest.mark.parametrize("kernel", [False, True], ids=["dense_lines", "kernel_interpreted"])
@pytest.mark.parametrize("n", [72, 75], ids=["whole_blocks", "a_tail_of_3"])
def test_chunked_paged_prefill_is_the_references(params, reference, kernel, n):
    """Chunks of the prompt's whole blocks at running starts: each chunk's
    logits are the reference's at its positions (what follows a block changes
    nothing in it), and so is what the chunks left in the pool: the block that
    follows, opened by the prompt's tail, reads it."""
    p = prompt_of(n)
    fill = n - n % BK
    M = 8
    cache = init_paged_cache(CFG, M + 1, BS)
    bt = jnp.arange(1, M + 1, dtype=jnp.int32)[None]
    want = reference[0](params, jnp.asarray(p[:fill]))
    for start in range(0, fill, CHUNK):
        piece = p[start : min(start + CHUNK, fill)]
        toks = np.zeros((1, CHUNK), np.int32)
        toks[0, : len(piece)] = piece
        valid = (np.arange(CHUNK) < len(piece))[None]
        logits, cache, _ = paged_forward_counted(
            CFG, params, cache, bt, jnp.asarray(toks), start + jnp.arange(CHUNK)[None], valid=jnp.asarray(valid),
            use_decode_kernel=kernel)
        assert close(logits[0, : len(piece)], want[start : start + len(piece)])
    state = open_blocks(CFG, jnp.asarray([4]), jnp.asarray([n - fill]), jnp.asarray([(p[fill:] + [0] * BK)[:BK]]))
    logits, *_ = paged_block_step(CFG, params, cache, bt, state, jnp.asarray([fill]), use_decode_kernel=kernel)
    block = p[fill:] + [MASK] * (BK - (n - fill))
    assert close(logits[0], reference[0](params, jnp.asarray(p[:fill] + block), jnp.arange(fill, fill + BK)))


# ---------------------------------------------------------------------------
# the block step: every denoising step, the commit, rows in different phases
# ---------------------------------------------------------------------------
def run_blocks(params, prompts, steps, n_blocks, *, kernel=False, on_step=None, skip_commit=False):
    """The program's own block step in a plain loop (no engine): prefill the
    prompts' whole blocks, then ``n_blocks`` blocks a row. Returns the tokens
    each row committed. ``skip_commit`` (a mutation): the commit forward is
    not run, so a block's K/V stay what its last denoising step wrote."""
    n, M = len(prompts), 8
    cache = init_paged_cache(CFG, n * M + 1, BS)
    bt = jnp.asarray(np.arange(1, n * M + 1, dtype=np.int32).reshape(n, M))
    fills = [len(p) - len(p) % BK for p in prompts]
    for i, p in enumerate(prompts):
        toks = np.zeros((1, 96), np.int32)
        toks[0, : fills[i]] = p[: fills[i]]
        _, cache, _ = paged_forward_counted(
            CFG, params, cache, bt[i : i + 1], jnp.asarray(toks), jnp.arange(96)[None],
            valid=jnp.asarray((np.arange(96) < fills[i])[None]), use_decode_kernel=kernel, with_logits=False)
    known = np.asarray([len(p) - f for p, f in zip(prompts, fills)], np.int32)
    tails = np.asarray([(p[f:] + [0] * BK)[:BK] for p, f in zip(prompts, fills)], np.int32)
    state = open_blocks(CFG, jnp.asarray(steps, jnp.int32), jnp.asarray(known), jnp.asarray(tails))
    pos = np.asarray(fills, np.int32)
    out = [[] for _ in prompts]
    blocks_left = [n_blocks] * n
    while any(blocks_left):
        live = jnp.asarray([b > 0 for b in blocks_left])
        went_in = jax.device_get(state)
        if skip_commit:
            finished = ~went_in["masked"].any(-1)
            if finished.any():  # hand the finished blocks back and reopen them, without the forward that writes them
                for i in np.nonzero(finished)[0]:
                    if blocks_left[i]:
                        out[i] += went_in["toks"][i, known[i]:].tolist()
                        pos[i], blocks_left[i], known[i] = pos[i] + BK, blocks_left[i] - 1, 0
                state = select_rows(jnp.asarray(finished), open_blocks(CFG, state["steps"]), state)
                continue
        logits, cache, state, done, _ = paged_block_step(
            CFG, params, cache, bt, state, jnp.asarray(pos), live=live, use_decode_kernel=kernel)
        done = jax.device_get(done)
        if on_step is not None:
            on_step(went_in, logits, pos.copy(), [list(o) for o in out], list(blocks_left))
        for i in range(n):
            if done["committed"][i] and blocks_left[i]:
                assert (done["toks"][i] == went_in["toks"][i]).all()
                out[i] += done["toks"][i, known[i]:].tolist()
                pos[i], blocks_left[i], known[i] = pos[i] + BK, blocks_left[i] - 1, 0
    return out


@pytest.mark.parametrize("kernel", [False, True], ids=["dense_lines", "kernel_interpreted"])
def test_every_denoising_step_and_the_commit_are_the_references(params, reference, kernel):
    """Three rows in different phases of one batch (prompts with and without
    a tail, 4, 2 and 1 steps a block): every step's ``[Bk, V]`` logits are
    the reference's forward of committed + block as it went in, commit
    forwards included, and what the rows commit is the reference's loop's."""
    prompts, steps = [prompt_of(30), prompt_of(48), prompt_of(21)], [4, 2, 1]
    seen = {"steps": 0}

    def on_step(went_in, logits, pos, committed, blocks_left):
        for i, p in enumerate(prompts):
            if not blocks_left[i]:
                continue  # the row has committed all its blocks
            seq = (p + committed[i])[: pos[i]]  # before the first commit the prompt's tail is in the block
            want = reference[0](params, jnp.asarray(seq + went_in["toks"][i].tolist()), jnp.arange(pos[i], pos[i] + BK))
            assert close(logits[i], want)
            seen["steps"] += 1

    got = run_blocks(params, prompts, steps, 3, kernel=kernel, on_step=on_step)
    assert seen["steps"] >= 3 * (2 + 1)
    for p, s, g in zip(prompts, steps, got):
        assert g == reference[1](params, p, len(g), s)


def test_unmasking_takes_the_most_confident_and_breaks_ties_low():
    V = 512
    logits = np.zeros((2, BK, V), np.float32)
    logits[0, :, 7] = [1.0, 3.0, 3.0, 2.0]      # positions 1 and 2 tie: 1 goes first
    logits[0, :, MASK] = 50.0                   # the mask id is never a candidate
    logits[1, :, 9] = [5.0, 1.0, 1.0, 1.0]
    state = open_blocks(CFG, jnp.asarray([4, 2]), jnp.asarray([0, 1]), jnp.asarray([[0] * BK, [11, 0, 0, 0]]))
    after = jax.device_get(unmask_step(CFG, jnp.asarray(logits), state))
    assert after["masked"][0].tolist() == [True, False, True, True] and after["toks"][0, 1] == 7
    # row 1: position 0 is known; 3 masked over 2 steps: ceil(3/2) = 2 of them, the lowest of equals
    assert after["masked"][1].tolist() == [False, False, False, True] and after["toks"][1].tolist() == [11, 9, 9, MASK]
    assert after["unmasked_at"][1].tolist() == [0, 1, 1, 0] and after["steps_left"].tolist() == [3, 1]


# ---------------------------------------------------------------------------
# the engine's normal path
# ---------------------------------------------------------------------------
CASES = [(30, 10, 4), (32, 8, 2), (45, 7, 1), (3, 9, 4), (64, 12, 4), (33, 1, 2)]


@pytest.mark.parametrize("n,max_tokens,steps", CASES, ids=[f"p{n}_t{m}_s{s}" for n, m, s in CASES])
def test_the_engine_generates_the_references_tokens(engine, params, reference, n, max_tokens, steps):
    p = prompt_of(n, seed=n)
    assert engine.generate(p, max_tokens=max_tokens, denoising_steps=steps) == reference[1](params, p, max_tokens, steps)


def mutated_loop(logits, prompt, max_tokens, steps, mutation):
    """The reference's generation loop with one thing changed: ``"shifted"``
    (a position's candidate comes from the logits of the one before it),
    ``"left_to_right"`` (the lowest masked positions are unmasked, whatever
    the confidence), ``"stale_kv"`` (what later blocks attend to of a block
    is its state at its last denoising step: K/V written on a denoise step
    and kept, no commit). ``logits``: the forward (another mask is another
    mutation)."""
    n = len(prompt)
    committed, stale, block = list(prompt[: n - n % BK]), list(prompt[: n - n % BK]), list(prompt[n - n % BK :])
    out = []
    while len(out) < max_tokens:
        known = len(block)
        block, masked, left = block + [MASK] * (BK - known), [i >= known for i in range(BK)], steps
        while any(masked):
            context = stale if mutation == "stale_kv" else committed
            lg = np.array(logits(jnp.asarray(context + block), jnp.arange(len(context) - (mutation == "shifted"),
                                                                         len(context) + BK - (mutation == "shifted"))))
            lg[:, MASK] = -np.inf
            cand = lg.argmax(-1)
            conf = 1.0 / np.exp(lg - lg.max(-1)[:, None]).sum(-1)
            todo = [i for i in range(BK) if masked[i]]
            todo = todo if mutation == "left_to_right" else sorted(todo, key=lambda i: (-conf[i], i))
            last_in = list(block)
            for i in todo[: -(-len(todo) // left)]:
                block[i], masked[i] = int(cand[i]), False
            left -= 1
        out += block[known:]
        committed, stale, block = committed + block, stale + last_in, []
    return out[:max_tokens]


def test_each_neighbouring_mutation_of_the_loop_serves_other_tokens(engine, params, reference):
    """Five things an implementation could get wrong and still emit tokens:
    a causal mask inside the block, logits shifted by one, K/V written on a
    denoise step and kept, unmasking left to right, the commit skipped. The
    engine's tokens are the sound loop's and none of theirs."""
    p, T, S = prompt_of(30, seed=5), 24, 4
    served = engine.generate(p, max_tokens=T, denoising_steps=S)
    sound = lambda toks, positions: reference[0](params, toks, positions)  # noqa: E731
    causal_ref = sdar_moe.make_reference({**C, "block_length": 1})[0]
    assert mutated_loop(sound, p, T, S, None) == served == reference[1](params, p, T, S)
    mutants = {
        "causal_mask_inside_the_block": mutated_loop(lambda t, pos: causal_ref(params, t, pos), p, T, S, None),
        "shifted_logits": mutated_loop(sound, p, T, S, "shifted"),
        "kv_written_on_a_denoise_step": mutated_loop(sound, p, T, S, "stale_kv"),
        "left_to_right_unmasking": mutated_loop(sound, p, T, S, "left_to_right"),
        # on the program's side: the same block step without its commit forward
        "commit_skipped": run_blocks(params, [p], [S], -(-(T + len(p) % BK) // BK), skip_commit=True)[0][:T],
    }
    for name, tokens in mutants.items():
        assert tokens != served, name
    # skipping the commit IS keeping the last denoise step's K/V: the two sides agree on what that serves
    assert mutants["commit_skipped"] == mutants["kv_written_on_a_denoise_step"]
    assert run_blocks(params, [p], [S], -(-(T + len(p) % BK) // BK))[0][:T] == served


def test_rows_in_different_phases_share_a_batch(engine, params, reference):
    jobs = [(prompt_of(n, seed=100 + n), m, s) for n, m, s in [(30, 14, 4), (17, 9, 1), (40, 11, 2), (5, 16, 3), (52, 6, 4)]]
    futures = [engine.submit(p, max_tokens=m, denoising_steps=s) for p, m, s in jobs]
    for (p, m, s), f in zip(jobs, futures):
        assert f.result(timeout=300) == reference[1](params, p, m, s)


def test_a_prompt_may_hold_the_mask_id_as_an_ordinary_token(engine, params, reference):
    p = prompt_of(22, seed=9)
    p[3], p[20], p[21] = MASK, MASK, MASK  # one in the prefilled part, two in the tail that opens the first block
    assert engine.generate(p, max_tokens=9) == reference[1](params, p, 9)


def test_a_prefix_hit_then_generation_equals_the_cold_run(params, reference):
    eng = LLMEngine(CFG, params, max_batch_size=2, max_seq_len=256, kv_block_size=BS, prefill_chunk_tokens=CHUNK)
    try:
        p = prompt_of(32, seed=21)
        first = eng.generate(p, max_tokens=22)
        s = eng.stats()
        # 54 tokens committed; the last block (positions 52-55) was cut at max_tokens, so its page is not full
        assert s["prefix_cache_blocks"] == 3 and s["tokens_emitted"] == 22 and s["tokens_unmasked"] == 24
        longer = p + first[:16] + prompt_of(7, seed=22)
        warm = eng.generate(longer, max_tokens=10)
        s = eng.stats()
        assert s["prefix_cache_hits"] + s["prefix_cache_partial"] == 1 and s["prefix_tokens_reused"] == 48
        assert warm == reference[1](params, longer, 10)
        # a prompt of whole cached pages: nothing to prefill, nothing copied
        again = eng.generate(p, max_tokens=22)
        s = eng.stats()
        assert again == first and s["cow_copies"] == 0 and s["prefix_tokens_reused"] == 48 + 32
    finally:
        eng.shutdown()


def test_no_page_is_published_before_its_blocks_commit_and_a_cancel_drops_the_block(params):
    eng = LLMEngine(CFG, params, max_batch_size=2, max_seq_len=256, kv_block_size=BS, prefill_chunk_tokens=CHUNK)
    try:
        stream = eng.submit_stream(prompt_of(30, seed=31), max_tokens=200, blocks=True)
        first = next(stream)
        assert isinstance(first, TokenBlock) and len(first.tokens) == 2 and len(first.unmasked_at) == 2
        second = next(stream)
        assert len(second.tokens) == BK and set(second.unmasked_at) <= {1, 2, 3, 4}
        # mid-generation: pages holding committed blocks are this row's own, none is in the cache yet
        assert eng.stats()["prefix_cache_blocks"] == 0
        stream.close()
        deadline = time.time() + 30
        while eng.stats()["active_slots"] and time.time() < deadline:
            time.sleep(0.01)
        s = eng.stats()
        assert s["active_slots"] == 0 and s["blocks_dropped"] == 1 and s["slots_evicted"] == 1
        assert s["prefix_cache_blocks"] == 0 and s["kv_blocks_in_use"] == 0  # a cancelled row publishes nothing
    finally:
        eng.shutdown()


def test_sampling_never_serves_the_mask_id(params):
    eng = LLMEngine(CFG, params, max_batch_size=2, max_seq_len=256, kv_block_size=BS, prefill_chunk_tokens=CHUNK)
    try:
        out = eng.generate(prompt_of(19, seed=41), max_tokens=40, temperature=5.0)
        assert len(out) == 40 and MASK not in out and all(0 <= t < 512 for t in out)
        assert eng.generate(prompt_of(8, seed=42), max_tokens=12, eos_id=out[0], temperature=0.0)  # an EOS cuts inside a block
    finally:
        eng.shutdown()


def test_the_block_counters_count_forwards_and_tokens(params):
    eng = LLMEngine(CFG, params, max_batch_size=2, max_seq_len=256, kv_block_size=BS, prefill_chunk_tokens=CHUNK)
    try:
        eng.generate(prompt_of(32, seed=51), max_tokens=16)
        s = eng.stats()
        assert (s["block_steps"], s["block_row_forwards"], s["block_commits"], s["tokens_emitted"]) == (20, 20, 4, 16)
        assert s["block_steps"] == s["decode_steps"] and s["block_length"] == BK
        eng.generate(prompt_of(32, seed=52), max_tokens=16, denoising_steps=1)
        s = eng.stats()
        assert s["block_row_forwards"] == 20 + 8 and s["tokens_unmasked"] == 32
        assert eng.admission_snapshot()["latency"]["inter_token"]["count"] == 2 * 15
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# what block steps cannot honour yet is refused by name
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kw,named", [
    (dict(decode_chunk=2), "decode_chunk > 1"),
    (dict(quantize=True), "quantize=True"),
    (dict(kv_block_size=6), "kv_block_size 6"),
    (dict(max_seq_len=254), "max_seq_len 254"),
], ids=["decode_chunk", "quantize", "page_of_broken_blocks", "max_seq_len"])
def test_the_engine_refuses_by_name_what_block_steps_cannot_honour(params, kw, named):
    with pytest.raises(ValueError, match="generation by diffusion over blocks") as e:
        LLMEngine(CFG, params, **{**dict(max_batch_size=2, max_seq_len=256), **kw})
    assert named in str(e.value)


def test_a_mesh_is_refused_for_a_diffusion_config(params):
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    with pytest.raises(ValueError, match="mesh"):
        LLMEngine(CFG, params, max_batch_size=2, max_seq_len=256, mesh=mesh)


def test_submit_refuses_what_a_diffusion_config_cannot_honour(engine, params):
    with pytest.raises(ValueError, match="prefill_export / adopt_migration"):
        engine.prefill_export(prompt_of(20), mig_id="m1")
    with pytest.raises(ValueError, match="prefill_export / adopt_migration"):
        engine.adopt_migration({"prompt": prompt_of(20), "mig_id": "m1", "tok0": 1}, {})
    for bad in (0, 5):
        with pytest.raises(ValueError, match="denoising_steps must be 1 to the config's block_length 4"):
            engine.submit(prompt_of(20), denoising_steps=bad)
    causal = dataclasses.replace(CFG, block_length=0)
    ar = LLMEngine(causal, params, max_batch_size=2, max_seq_len=64)
    try:
        with pytest.raises(ValueError, match="denoising_steps belongs to a config that generates by diffusion"):
            ar.submit(prompt_of(20), denoising_steps=2)
    finally:
        ar.shutdown()


def test_the_config_refuses_a_block_mask_it_cannot_run():
    with pytest.raises(ValueError, match="sliding"):
        TransformerConfig(n_layers=2, block_length=4, layer_types=("sliding", "full"), sliding_window=8)
    with pytest.raises(ValueError, match="ring"):
        TransformerConfig(block_length=4, attention="ring")
    with pytest.raises(ValueError, match="mask_token_id"):
        TransformerConfig(block_length=4, vocab_size=100, mask_token_id=100)


def test_the_openai_adapter_refuses_by_name_what_rests_on_a_next_token_distribution(params):
    server = OpenAICompatLLMServer(lambda: (CFG, params), max_batch_size=2, max_seq_len=256)
    try:
        body = {"model": "m", "prompt": prompt_of(12), "max_tokens": 4, "temperature": 0.0}
        out = server(dict(body))
        assert len(out["choices"][0]["token_ids"]) == 4
        for extra, named in ((dict(logprobs=1), "logprobs"), (dict(n=2), "n > 1"), (dict(logit_bias={"5": 1.0}), "logit_bias")):
            with pytest.raises(ValueError, match="generates by diffusion over blocks of 4") as e:
                server({**body, **extra})
            assert named in str(e.value)
        events = list(server({"prompt": prompt_of(13), "max_tokens": 6, "stream": True, "denoising_steps": 2}))
        assert [len(ev["tokens"]) for ev in events[:-1]] == [3, 3] and events[-1] == {"done": True, "num_generated": 6}
        assert all(set(ev["unmasked_at"]) <= {1, 2} for ev in events[:-1])
        chunks = list(server({**body, "stream": True}))  # the OpenAI stream still delivers a token a chunk
        assert [c["choices"][0].get("token_ids") for c in chunks[:-1]] == [[t] for t in out["choices"][0]["token_ids"]]
    finally:
        server.engine.shutdown()


# ---------------------------------------------------------------------------
# an autoregressive config lowers to what it did
# ---------------------------------------------------------------------------
def test_a_causal_configs_programs_do_not_know_about_blocks(params):
    """``block_length`` 0 and 1 are one program, the token-a-step decode with
    its ``[B]`` tokens and none of a block step's state or counters. (That
    both lower, kernels included, to what they did before ``block_length``
    existed was checked against the parent commit when it was added: the
    programs' StableHLO and the kernels' jaxprs were byte-equal.)"""
    texts = []
    for block_length in (0, 1):
        cfg = dataclasses.replace(CFG, block_length=block_length)
        eng = LLMEngine(cfg, params, max_batch_size=2, max_seq_len=64, prefill_chunk_tokens=CHUNK)
        try:
            abstract = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
            p, cache = jax.tree.map(abstract, (eng.runner.params, eng.runner.cache))
            i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
            chunk = eng.runner._prefill_chunk.lower(p, cache, i32(1, CHUNK), i32(1, 4), i32(), i32()).as_text()
            texts.append((eng.runner.lowered_decode_text(), chunk))
            assert eng.runner.dev_toks.shape == (2,) and "block_steps" not in eng.stats()
        finally:
            eng.shutdown()
    assert texts[0] == texts[1]
