"""The cell ``sdar-30b-a3b-serve-l6.fixed-length-gen`` (PR 40): its manifest
entries, its files, its schedule, its four readers on recorded data and on a
run without their counters, the reference module's arithmetic, and the
replay that decides ``correct`` at toy size (sound tokens pass it; tokens
unmasked left to right and a foreign token fail it)."""

from __future__ import annotations

import json
import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import manifest, run as runner, system, trace_reduce  # noqa: E402
from benchmark.kinds import block_requests, open_loop_requests  # noqa: E402
from benchmark.models import sdar_moe  # noqa: E402

CELL = "sdar-30b-a3b-serve-l6.fixed-length-gen"
NEW = {
    "block_forwards_per_token": ("forwards/token", "lower", "program_counter", "admission and scheduler", "output_tokens_per_s"),
    "block_attn_roofline_share": ("%", "higher", "device_trace", "kernels, serving", "itl_mean_ms"),
    "block_expert_ffn_time_share": ("%", "lower", "device_trace", "expert layer", "itl_mean_ms"),
    "block_gap_p99_ms.watch": ("ms", "lower", "host_clock", "served path, watched", "itl_mean_ms"),
}
GAINED = ("batch_occupancy", "decode_step_dev_ms", "decode_kernel_time_share", "decode_overlap_share",
          "moe_load_max_over_mean", "moe_experts_hit_share", "kv_pool_in_use_share", "backlog_at_close")


@pytest.fixture(scope="module")
def files():
    m = manifest.load()
    cell, config, traffic = runner.load_cell(m, CELL, rehearsal=False)
    return m, cell, config, traffic


def test_the_manifest_is_sound_with_the_new_entries(files):
    m, cell, _, _ = files
    assert manifest.problems(m, ROOT) == []
    assert cell == m["workloads"][-1] and cell["chips"] == 1 and len(m["workloads"]) == 7
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    assert m["configs"][-1]["name"] == cell["config"] and m["configs"][-1]["reduced"] == ["num_hidden_layers"]
    e2e = {x["name"] for x in manifest.metrics_of(m, "end_to_end", CELL)}
    assert e2e == {"output_tokens_per_s", "itl_mean_ms", "setup_s"}  # no tail and no first-token time: PERF.md section 4
    layer = {x["name"] for x in manifest.metrics_of(m, "per_layer", CELL)}
    assert layer == set(NEW) | set(GAINED)
    assert [x["name"] for x in m["per_layer"][-4:]] == list(NEW)
    for x in m["per_layer"][-4:]:
        unit, better, source, layer_name, moves = NEW[x["name"]]
        assert x == {"name": x["name"], "unit": unit, "better": better, "source": source, "layer": layer_name,
                     "moves": moves, "workloads": [CELL]}
    for name in GAINED + ("output_tokens_per_s", "itl_mean_ms"):
        entry = [x for x in m["per_layer"] + m["end_to_end"] if x["name"] == name][0]
        assert entry["workloads"][-1] == CELL and entry["workloads"].count(CELL) == 1


def test_the_configuration_keeps_every_published_number_but_the_depth(files):
    _, _, config, _ = files
    assert config["reduced"] == ["num_hidden_layers"] and config["published"]["num_hidden_layers"] == 48
    assert 48 % config["num_hidden_layers"] == 0 and config["num_hidden_layers"] >= 4
    for key in ("block_length", "denoising_steps", "mask_token_id", "remasking", "no_shift", "commit", "qk_norm"):
        assert key in config["assumed"]
    assert config["run"]["kv_block_size"] % config["block_length"] == 0
    assert sdar_moe.n_params(config) == pytest.approx(4361e6, rel=2e-3)  # ISSUE 40: 6 x 623.1M + 2 x 311.2M
    catalog = os.path.join(os.sep, "opt", "skills", "guides", "model-configs", "architectures.jsonl")
    if not os.path.isfile(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "SDAR-30B-A3B-Chat")
    assert config["source"] == row["source_url"]
    assert sorted(k for k, v in row["config"].items() if config.get(k, "absent") != v) == ["num_hidden_layers"]


def test_the_program_config_is_the_qwen3_moe_layer_under_a_block_mask(files):
    _, _, config, _ = files
    cfg = sdar_moe.program_config(config, max_seq_len=4096, dtype="bfloat16", param_dtype="bfloat16")
    assert (cfg.block_length, cfg.mask_token_id, cfg.block) == (4, 151669, 4)
    assert (cfg.qk_norm, cfg.router_score, cfg.route_norm, cfg.num_shared_experts, cfg.num_dense_layers,
            cfg.tie_embeddings, cfg.embed_scale, cfg.dropless) == (True, "softmax", True, 0, 0, False, 1.0, True)
    assert (cfg.num_experts, cfg.expert_top_k, cfg.expert_width, cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (128, 8, 768, 32, 4, 128)
    with pytest.raises(ValueError, match="block_length"):
        sdar_moe.program_config({**config, "block_length": 1})
    small = system.shrink_for_rehearsal(config)
    assert sdar_moe.program_config(small, dtype="float32", param_dtype="float32").mask_token_id == 500


def test_the_schedule_holds_the_stated_requests_of_512_output_tokens(files):
    _, _, _, traffic = files
    plan = open_loop_requests.schedule(traffic, 51.0)
    scored = [r for r in plan if r["scored"]]
    assert len(scored) == round(traffic["rate"] * 51) and len(plan) - len(scored) == round(traffic["rate"] * traffic["ramp_s"])
    assert {r["max_tokens"] for r in plan} == {512}
    lens = [r["prompt_len"] for r in scored]
    assert min(lens) >= 128 and max(lens) <= 2048 and 400 <= float(np.median(lens)) <= 640
    assert traffic["kind"] == "block_requests" and traffic["backlog"] == "expected"
    assert plan == open_loop_requests.schedule(traffic, 51.0)  # one realisation for every seed
    lengths = block_requests.served_check_prompts({"prefill_chunk_tokens": 512, "correctness": {"max_prompt": 1283}}, 4)
    assert [n % 4 for n in lengths] == [1, 2, 3] and lengths[0] < 512 < lengths[1] < 1024 < lengths[2]


def test_the_attention_byte_count_counts_what_is_visible():
    c = {"num_hidden_layers": 6, "num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 128, "block_length": 4}
    # a 16-token page of one layer: K and V of 4 heads of 128 in bf16 = 32 KiB
    assert sdar_moe.block_attention_bytes(c, 1.0, 0) == 6 * 32 * 1024
    assert sdar_moe.block_attention_bytes(c, 0.0, 48) == 6 * 48 * 4 * 2 * 32 * 128 * 2
    assert sdar_moe.block_attention_bytes(c, 2880.0, 48) == pytest.approx(0.576e9, rel=0.02)  # 48 rows x 60 pages


# ---------------------------------------------------------------------------
# the readers: a number on a recorded run, None where there is nothing to read
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(ROOT, "benchmark", "testdata", "recorded_trace.json")) as f:
        return json.load(f)["events"]


def _run(events, samples, config, open_stats=None, close_stats=None, turns=()):
    sampler = types.SimpleNamespace(samples=[(0.5, s) for s in samples])
    probe = types.SimpleNamespace(sampler=sampler, stats_open=open_stats and (0.0, open_stats),
                                  stats_close=close_stats and (1.0, close_stats))
    return {"ctx": types.SimpleNamespace(config=config), "events": events, "window": (0.0, 1.0), "probe": probe,
            "peak": {"hbm_bytes_per_s": 819e9}, "turns": list(turns)}


def _reader(name):
    return runner.load_reader(name, manifest.load()["paths"])


def test_block_forwards_per_token_reads_the_engines_counters():
    read = _reader("block_forwards_per_token")
    a = {"block_row_forwards": 1000, "tokens_emitted": 800}
    b = {"block_row_forwards": 3500, "tokens_emitted": 2800}
    assert read(_run([], [], {}, a, b)) == pytest.approx(1.25)
    assert read(_run([], [], {}, {"decode_steps": 1}, {"decode_steps": 9})) is None  # the parent's engine: no such counter
    assert read(_run([], [], {}, a, a)) is None and read(_run([], [], {})) is None


def test_block_attn_roofline_share_divides_the_visible_bytes_by_the_kernels_time(recorded):
    read = _reader("block_attn_roofline_share")
    # the recorded decode steps' kernel is ``custom-call bf16[64,32,8,64]``, once a layer: the reader finds it by
    # ``[slots, kv heads, ..]``, whatever the head shape
    config = {"model": "sdar_moe", "block_length": 4, "num_hidden_layers": 24, "num_attention_heads": 32,
              "num_key_value_heads": 32, "head_dim": 64, "run": {"max_batch_size": 64, "kv_block_size": 16}}
    plane = trace_reduce.device_planes(recorded)[0]
    steps = len(trace_reduce.program_runs(recorded, plane)["jit__decode_k_paged"])
    ns = sum(e[4] for e in trace_reduce.ops_inside(recorded, plane, "jit__decode_k_paged")
             if e[2].endswith("custom-call bf16[64,32,8,64]"))
    live = [{"kv_live_pages": 300.0, "active_slots": 40}, {"kv_live_pages": 500.0, "active_slots": 48}]
    want = 100.0 * sdar_moe.block_attention_bytes(config, 400.0, 44.0) / (ns / 1e9 / steps) / 819e9
    assert read(_run(recorded, live, config)) == pytest.approx(want) and want > 0
    assert read(_run(recorded, live, {**config, "block_length": 0})) is None      # a token a step
    assert read(_run(recorded, live, {**config, "model": "smollm2"})) is None     # no byte count of its own
    assert read(_run(recorded, [{"active_slots": 3}], config)) is None            # the parent: no counter
    assert read(_run([], live, config)) is None                                   # no trace
    assert read({**_run(recorded, live, config), "ctx": None}) is None


def test_block_expert_ffn_time_share_finds_the_grouped_products_by_their_rows(recorded):
    read = _reader("block_expert_ffn_time_share")
    config = {"num_experts": 8, "block_length": 4, "num_experts_per_tok": 8, "run": {"max_batch_size": 2}}
    share = read(_run(recorded, [], config))  # results of 2 x 4 x 8 = 64 rows: the recorded steps have such operations
    assert 0 < share < 100
    assert read(_run(recorded, [], {**config, "run": {"max_batch_size": 3}})) == 0.0
    assert read(_run(recorded, [], {**config, "block_length": 0})) is None
    assert read(_run(recorded, [], {**config, "num_experts": 0})) is None
    assert read(_run([], [], config)) is None


def test_block_gap_p99_takes_the_gaps_from_block_to_block():
    read = _reader("block_gap_p99_ms.watch")
    times = [0.100] * 4 + [0.120] * 4 + [0.150] * 4 + [1.5] * 4  # blocks of 4 tokens at one stamp each; the last past the window
    turn = types.SimpleNamespace(token_times=times)
    assert read(_run([], [], {}, turns=[turn])) == pytest.approx(20.0 + 0.99 * 10.0)
    assert read(_run([], [], {}, turns=[types.SimpleNamespace(token_times=[0.1] * 4)])) is None


# ---------------------------------------------------------------------------
# the replay that decides ``correct``, at toy size
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def toy():
    import jax

    config = system.shrink_for_rehearsal(system.load_json("benchmark/configs/sdar-30b-a3b-serve-l6.json"))
    cfg = sdar_moe.program_config(config, max_seq_len=256, dtype="float32", param_dtype="float32")
    params = system.make_params(cfg, 3)
    served = types.SimpleNamespace(cfg=cfg, params=params, config=config, run=config["run"])
    return config, served, sdar_moe.make_reference(config), jax


def _served_like_the_engine(generate, params, prompt, n, steps, mutate=None):
    """The tokens and unmasking steps a sound engine streams: the reference's
    own loop, recording at which step each position took its token."""
    record = []

    def on_step(context, block, masked, lg):
        record.append((len(context), list(masked)))

    tokens = generate(params, prompt, n, steps, on_step=on_step)
    Bk = 4
    fill = len(prompt) - len(prompt) % Bk
    unmasked_at = {}
    by_block = {}
    for start, masked in record:
        by_block.setdefault(start, []).append(masked)
    for start, states in by_block.items():
        for s, masked in enumerate(states, 1):
            after = states[s] if s < len(states) else [False] * Bk
            for j in range(Bk):
                if masked[j] and not after[j]:
                    unmasked_at[start + j] = s
    steps_of = [unmasked_at[len(prompt) + i] for i in range(n)]
    return tokens, steps_of, fill


def test_the_replay_passes_sound_tokens_and_fails_a_wrong_order_and_a_foreign_token(toy):
    config, served, (ref_logits, generate), _ = toy
    cc = config["run"]["correctness"]
    prompt = np.random.default_rng(5).integers(1, 512, size=30).tolist()
    tokens, steps_of, _ = _served_like_the_engine(generate, served.params, prompt, 14, 4)
    sound = block_requests.replay_blocks(served, ref_logits, prompt, tokens, steps_of)
    verdict = block_requests.replay_verdict([sound], cc, 0.0)
    assert verdict["ok"] and verdict["tokens"] == 14 and verdict["worst_deficit_sd"] == 0.0
    assert verdict["worst_confidence_gap"] <= 0.0 and verdict["mean_confidence_gap"] < cc["confidence_margin"] < 0.0
    assert verdict["left_to_right_mean_gap"] > verdict["mean_confidence_gap"]
    # the same tokens said to have been unmasked left to right: the chosen positions were not the most confident
    wrong_order = [i % 4 + 1 if i >= 2 else i + 3 for i in range(14)]
    ltr = block_requests.replay_verdict(
        [block_requests.replay_blocks(served, ref_logits, prompt, tokens, wrong_order)], cc, 0.0)
    assert not ltr["ok"]
    # a token from nowhere fails by its deficit
    foreign = list(tokens)
    foreign[5] = (foreign[5] + 1) % 500
    bad = block_requests.replay_verdict(
        [block_requests.replay_blocks(served, ref_logits, prompt, foreign, steps_of)], cc, 0.0)
    assert not bad["ok"] and bad["worst_deficit_sd"] > cc["near_tie_sd"]
    # only the blocks asked for are replayed; a reply with no whole block has nothing to hold
    some = block_requests.replay_blocks(served, ref_logits, prompt, tokens, steps_of, only=[0, 2])
    assert len(some["deficits"]) == 2 + 4
    assert not block_requests.replay_verdict(
        [block_requests.replay_blocks(served, ref_logits, prompt[:28], tokens[:3], steps_of[:3])], cc, 0.0)["ok"]


def test_the_runner_check_holds_every_step_to_the_reference_and_a_bf16_program_fails_it(toy):
    config, served, _, jax = toy
    out = block_requests.check_blocks_against_reference(served.cfg, served.params, config, 3)
    assert out["ok"] and out["rel_err"] < 1e-5 and out["rel_err_same_routing"] < 1e-5 and out["swap_margin_max_sd"] == 0.0
    assert out["steps"] >= 2 * 2 * 2 and out["prompt_lengths"][0] == 75 and out["prompt_lengths"][1] % 4 == 0
    # the lower-precision control: the same walk in bfloat16 is not correct by the toy limits
    low = sdar_moe.program_config(config, max_seq_len=256, dtype="bfloat16", param_dtype="bfloat16")
    assert not block_requests.check_blocks_against_reference(low, system.make_params(low, 3), config, 3)["ok"]
