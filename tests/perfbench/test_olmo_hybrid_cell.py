"""The cell ``olmo-hybrid-7b-serve-l16.session-turns`` (PR 44): its manifest
entries, its files, its schedule, its six readers on recorded inputs and on
a run without their counters, the reference module's arithmetic, and the
``--rehearsal`` walk of the cell on the CPU. The cell is looked for *among*
a metric's workloads, never at their end: the next cell is appended after it."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import manifest, readers_state, run as runner, system  # noqa: E402
from benchmark.kinds import sessions  # noqa: E402
from benchmark.models import olmo_hybrid  # noqa: E402

CELL = "olmo-hybrid-7b-serve-l16.session-turns"
CONFIG = "olmo-hybrid-7b-serve-l16"
NEW = {
    "state_restore_share": ("share", "higher", "program_counter", "KV block manager", "output_tokens_per_s"),
    "state_snapshot_pool_in_use_share": ("share", "higher", "program_counter", "KV block manager", "output_tokens_per_s"),
    "state_update_time_share": ("%", "lower", "device_trace", "kernels, serving", "itl_mean_ms"),
    "state_update_roofline_share": ("%", "higher", "device_trace", "kernels, serving", "itl_mean_ms"),
    "prefill_scan_time_share": ("%", "lower", "device_trace", "kernels, serving", "output_tokens_per_s"),
    "prefill_chunks_per_admission": ("chunks", "lower", "program_counter", "KV block manager", "output_tokens_per_s"),
}
GAINED = ("batch_occupancy", "decode_step_dev_ms", "decode_kernel_time_share", "decode_overlap_share",
          "paged_kernel_us_per_live_page", "kv_pool_in_use_share", "backlog_at_close", "compiles_in_window.serve")
NOT_JOINED = ("loop_host_ms_per_step", "decode_dry_share")  # their lists are pinned by tests that pass: PERF.md section 7


@pytest.fixture(scope="module")
def files():
    m = manifest.load()
    cell, config, traffic = runner.load_cell(m, CELL, rehearsal=False)
    return m, cell, config, traffic


def test_the_manifest_is_sound_and_holds_the_new_entries_letter_for_letter(files):
    m, cell, _, _ = files
    assert manifest.problems(m, ROOT) == []
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "session-turns", "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "1.3 x knee" in cell["why"]
    entry = manifest.config_entry(m, CONFIG)
    assert entry["source"] == "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json"
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json" and entry["reduced"] == ["num_hidden_layers", "layer_types"]
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    e2e = {x["name"] for x in manifest.metrics_of(m, "end_to_end", CELL)}
    assert e2e == {"output_tokens_per_s", "itl_mean_ms", "itl_p99_ms", "setup_s"}  # above the knee: no first-token time
    layer = {x["name"] for x in manifest.metrics_of(m, "per_layer", CELL)}
    assert layer == set(NEW) | set(GAINED)
    by_name = {x["name"]: x for x in m["per_layer"] + m["end_to_end"]}
    for name, (unit, better, source, layer_name, moves) in NEW.items():
        assert by_name[name] == {"name": name, "unit": unit, "better": better, "source": source, "layer": layer_name,
                                 "moves": moves, "workloads": by_name[name]["workloads"]}
        assert CELL in by_name[name]["workloads"]
        assert manifest.layer_metric_file(name, m["paths"], ROOT) is not None
    for name in GAINED + ("output_tokens_per_s", "itl_mean_ms", "itl_p99_ms"):
        assert by_name[name]["workloads"].count(CELL) == 1
    for name in NOT_JOINED:
        assert CELL not in by_name[name]["workloads"]


def test_the_configuration_keeps_every_published_number_but_the_depth(files):
    _, _, config, _ = files
    assert config["reduced"] == ["num_hidden_layers", "layer_types"]
    assert config["published"]["num_hidden_layers"] == 32 and len(config["published"]["layer_types"]) == 32
    assert config["layer_types"] == config["published"]["layer_types"][:16] == (["linear_attention"] * 3 + ["full_attention"]) * 4
    for key in ("norm_placement", "qk_norm", "rope", "linear_layer", "state_dtype", "A_log_dt_bias"):
        assert key in config["assumed"]
    run = config["run"]
    assert config["deployment"] and config["rehearsal"] and run["sizing"] and run["correctness"]["why"]
    assert (run["max_batch_size"], run["kv_block_size"], run["prefill_chunk_tokens"], run["decode_chunk"]) == (32, 16, 512, 1)
    assert (run["max_seq_len"], run["kv_num_blocks"], run["state_snapshots"]) == (4096, 3072, 64)   # ISSUE 44's pools
    cc = run["correctness"]
    assert cc["engine_conversations"] > run["max_batch_size"] and cc["state_bf16_exact_max"] == 0.5
    # ISSUE 44's count: a linear layer 215.6M, a full layer 185.8M, whole 7.43B, this stage 4100.8M
    assert olmo_hybrid.layer_params(config, "linear_attention") == pytest.approx(215.57e6, rel=1e-4)
    assert olmo_hybrid.layer_params(config, "full_attention") == pytest.approx(185.81e6, rel=1e-4)
    assert olmo_hybrid.n_params(config) == pytest.approx(4100.8e6, rel=1e-4)
    whole = {**config, "num_hidden_layers": 32, "layer_types": config["published"]["layer_types"]}
    assert olmo_hybrid.n_params(whole) == pytest.approx(7.43e9, rel=1e-3)
    catalog = os.path.join(os.sep, "opt", "skills", "guides", "model-configs", "architectures.jsonl")
    if not os.path.isfile(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Olmo-Hybrid-7B")
    assert config["source"] == row["source_url"]
    assert sorted(k for k, v in row["config"].items() if config.get(k, "absent") != v) == ["layer_types", "num_hidden_layers"]


def test_the_program_config_is_the_hybrid_block(files):
    _, _, config, _ = files
    cfg = olmo_hybrid.program_config(config, max_seq_len=4096, dtype="bfloat16", param_dtype="bfloat16")
    assert cfg.hybrid and (cfg.periods, cfg.linear_per_period, cfg.linear_layers, cfg.kv_layers) == (4, 3, 12, 4)
    assert (cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim, cfg.linear_conv_width, cfg.linear_channels) == (30, 96, 192, 4, 11520)
    assert (cfg.pre_norms, cfg.post_norms, cfg.qk_norm, cfg.qk_norm_whole, cfg.rope_full_layers, cfg.tie_embeddings,
            cfg.embed_scale, cfg.linear_allow_neg_eigval) == (False, True, True, True, False, False, 1.0, True)
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.d_ff, cfg.vocab_size) == (30, 30, 128, 11008, 100352)
    with pytest.raises(ValueError, match="rope_theta"):
        olmo_hybrid.program_config({**config, "rope_parameters": {"rope_theta": 10000.0}})
    with pytest.raises(ValueError, match="linear_num_key_heads"):
        olmo_hybrid.program_config({**config, "linear_num_key_heads": 15})
    small = system.shrink_for_rehearsal(config)
    toy = olmo_hybrid.program_config(small, dtype="float32", param_dtype="float32")
    assert (toy.periods, toy.linear_per_period, toy.linear_heads) == (2, 3, 4)


def test_state_update_bytes_by_hand_for_one_row(files):
    _, _, config, _ = files
    state = 2 * 30 * 96 * 192 * 4               # read and written, float32
    tail = 2 * 3 * 11520 * 2                    # the convolution's three last inputs, bf16, read and written
    vectors = (2 * 30 * 96 + 2 * 30 * 192 + 2 * 30) * 4  # q, k; v, o; the two gates
    assert olmo_hybrid.state_update_bytes(config, 1) == 12 * (state + tail + vectors) == 55575360
    assert olmo_hybrid.state_update_bytes(config, 32) == 32 * 55575360
    assert olmo_hybrid.state_bytes_per_sequence(config) == 12 * (30 * 96 * 192 * 4 + 3 * 11520 * 2) == 27371520
    kv_token = 2 * 4 * 30 * 128 * 2             # four full layers only
    assert olmo_hybrid.decode_step_bytes(config, 1000, rows=0) - olmo_hybrid.decode_step_bytes(config, 0, rows=0) == 1000 * kv_token


def test_the_schedule_is_sessions_own_above_the_knee(files):
    _, _, _, traffic = files
    assert traffic["kind"] == "state_sessions" and traffic["backlog"] == "expected"
    assert (traffic["agents"], traffic["system_prompt_tokens"], traffic["turns"], traffic["ramp_s"], traffic["trace_s"]) == (4, 1024, 4, 40, 4)
    assert traffic["new_tokens"] == {"dist": "uniform", "lo": 64, "hi": 256}
    assert traffic["output_tokens"] == {"dist": "uniform", "lo": 96, "hi": 160}
    assert traffic["think_s"] == {"dist": "uniform", "lo": 0.5, "hi": 2.0}
    plan = sessions.schedule(traffic, 51.0)
    assert sum(s["in_window"] for s in plan) == round(traffic["rate"] * 51)
    assert [s["agent"] for s in plan[:8]] == [0, 1, 2, 3, 0, 1, 2, 3]
    assert all(len(s["new_tokens"]) == 4 and 96 <= min(s["output_tokens"]) and max(s["output_tokens"]) <= 160 for s in plan)
    assert plan == sessions.schedule(traffic, 51.0)  # one realisation for every seed
    # the longest history still fits the engine's 4096 positions
    assert 1024 + 4 * (256 + 160) <= 4096


# ---------------------------------------------------------------------------
# the readers: a number on a recorded run, None where there is nothing to read
# ---------------------------------------------------------------------------
PLANE = "/device:TPU:0"
STATE_KERNEL = "custom-call.7 custom-call (f32[32,15,384], f32[12,32,15,96,384])"
PAGED_KERNEL = "custom-call.3 custom-call bf16[32,30,1,128]"


def _events():
    """Two decode steps and one prefill chunk as ``trace_reduce.read_xplane`` gives them."""
    ev = []
    t = 0
    for _ in range(2):
        ev.append([PLANE, "XLA Modules", "jit__decode_k_paged(123)", t, 1000])
        for name, dur in ((STATE_KERNEL, 100), ("fusion.1 fusion bf16[32,11008]", 500), (PAGED_KERNEL, 50),
                          (STATE_KERNEL, 100), ("fusion.9 fusion f32[32,100352]", 250)):
            ev.append([PLANE, "XLA Ops", name, t, dur])
            t += dur
    ev.append([PLANE, "XLA Modules", "jit__prefill_chunk(77)", t, 2000])
    for name, dur in (("fusion.20 fusion f32[8,1,30,64,64]", 300), ("fusion.21 fusion bf16[1,512,11008]", 1200),
                      ("fusion.22 fusion f32[1,30,96,192]", 100), ("fusion.23 fusion f32[12,32,15,96,384]", 100),
                      ("fusion.24 fusion bf16[1,512,30,128]", 300)):
        ev.append([PLANE, "XLA Ops", name, t, dur])
        t += dur
    return ev


def _run(events, samples, config, open_stats=None, close_stats=None):
    sampler = types.SimpleNamespace(samples=[(0.5, s) for s in samples])
    probe = types.SimpleNamespace(sampler=sampler, stats_open=open_stats and (0.0, open_stats),
                                  stats_close=close_stats and (1.0, close_stats))
    return {"ctx": types.SimpleNamespace(config=config), "events": events, "window": (0.0, 1.0), "probe": probe,
            "peak": {"hbm_bytes_per_s": 819e9}, "turns": []}


def _reader(name):
    return runner.load_reader(name, manifest.load()["paths"])


def test_state_restore_share_is_reused_over_matched(files):
    read = _reader("state_restore_share")
    a = {"prefix_tokens_matched": 1000, "prefix_tokens_reused": 1000}
    b = {"prefix_tokens_matched": 11000, "prefix_tokens_reused": 10000}
    assert read(_run([], [], {}, a, b)) == pytest.approx(0.9)
    assert read(_run([], [], {}, {"prefix_tokens_reused": 5}, {"prefix_tokens_reused": 9})) is None  # the parent: no such counter
    assert read(_run([], [], {}, a, a)) is None and read(_run([], [], {})) is None


def test_prefill_chunks_per_admission_is_chunks_over_restored_and_zeroed_slots():
    read = _reader("prefill_chunks_per_admission")
    a = {"prefill_chunks": 100, "state_restores": 90, "state_zeroed": 2}
    b = {"prefill_chunks": 400, "state_restores": 280, "state_zeroed": 12}
    assert read(_run([], [], {}, a, b)) == pytest.approx(300 / 200)
    assert read(_run([], [], {}, {"prefill_chunks": 1}, {"prefill_chunks": 9})) is None  # the parent: no such counters
    assert read(_run([], [], {}, a, a)) is None and read(_run([], [], {})) is None


def test_state_snapshot_pool_in_use_share_is_the_windows_mean():
    read = _reader("state_snapshot_pool_in_use_share")
    samples = [{"state_snapshots_in_use": 32, "state_snapshot_pool_size": 64},
               {"state_snapshots_in_use": 64, "state_snapshot_pool_size": 64}]
    assert read(_run([], samples, {})) == pytest.approx(0.75)
    assert read(_run([], [{"active_slots": 3}], {})) is None and read(_run([], [], {})) is None


def test_the_state_operations_are_told_by_the_states_dimensions(files):
    _, _, config, _ = files
    match = readers_state.state_op(config)
    assert match(STATE_KERNEL) and match("fusion.2 fusion f32[32,30,96,192]") and match("scatter.1 scatter f32[12,33,15,96,384]")
    assert not match(PAGED_KERNEL) and not match("fusion.1 fusion bf16[32,30,96,192]") and not match("fusion.1 fusion f32[32,30,96]")
    chunked = readers_state.state_op(config, chunked=True)
    assert chunked("fusion.20 fusion f32[8,1,30,64,64]") and chunked("fusion.5 fusion f32[8,1,30,64,192]")
    assert not chunked("fusion.24 fusion bf16[1,512,30,128]") and not chunked("fusion.3 fusion f32[8,30,640]")
    assert readers_state.state_op({"num_hidden_layers": 24}) is None
    assert readers_state.lane_group(30, 192) == 2 and readers_state.lane_group(4, 32) == 4 and readers_state.lane_group(3, 16) == 1
    from ray_tpu.ops import gated_delta

    assert readers_state.CHUNK == gated_delta.CHUNK  # the yardstick's copy of the program's chunk length


def test_state_update_time_share_and_roofline_share_read_the_decode_steps(files):
    _, _, config, _ = files
    time_share, roofline = _reader("state_update_time_share"), _reader("state_update_roofline_share")
    samples = [{"active_slots": 32}, {"active_slots": 30}]
    assert time_share(_run(_events(), samples, config)) == pytest.approx(100.0 * 200 / 1000)
    want = 100.0 * olmo_hybrid.state_update_bytes(config, 31.0) / (200e-9) / 819e9
    assert roofline(_run(_events(), samples, config)) == pytest.approx(want)
    smollm = {"model": "smollm2", "num_hidden_layers": 24, "run": {"max_batch_size": 40}}
    for read in (time_share, roofline):
        assert read(_run(_events(), samples, smollm)) is None      # no linear layers
        assert read(_run([], samples, config)) is None             # no trace
        assert read({**_run(_events(), samples, config), "ctx": None}) is None
    assert roofline(_run(_events(), [], config)) is None           # no counter
    quiet = [e for e in _events() if e[2] != STATE_KERNEL]
    assert roofline(_run(quiet, samples, config)) is None and time_share(_run(quiet, samples, config)) == 0.0


def test_prefill_scan_time_share_reads_the_chunks(files):
    _, _, config, _ = files
    read = _reader("prefill_scan_time_share")
    assert read(_run(_events(), [], config)) == pytest.approx(100.0 * 500 / 2000)
    assert read(_run(_events(), [], {"num_hidden_layers": 24})) is None and read(_run([], [], config)) is None


# ---------------------------------------------------------------------------
# the whole command at toy size
# ---------------------------------------------------------------------------
def test_the_rehearsal_walks_the_cell_on_the_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELL, "--seed", "2147483999",
                          "--seconds", "4", "--trace", "1", "--rehearsal"], capture_output=True, text=True, env=env,
                         cwd=ROOT, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal_host_only"] and line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    compared = line["compared"]
    for key in ("state_rel_err", "restored_rel_err", "restored_state_rel_err", "engine_state_rel_err",
                "state_bf16_exact_share", "served_worst_deficit_sd", "window_worst_deficit_sd", "failed_requests"):
        assert key in compared and compared[key]["value"] <= compared[key]["limit"]
    assert compared["restored_rel_err"]["value"] < 1e-3 and compared["engine_state_rel_err"]["value"] < 1e-3
    assert compared["state_bf16_exact_share"]["value"] < 0.01   # the engine's own snapshots hold float32
    assert {"state_restore_share", "state_snapshot_pool_in_use_share", "batch_occupancy", "kv_pool_in_use_share"} <= set(line["metric_names"])
