"""ISSUE 46's cell ``kimi-linear-48b-a3b-serve-l8.doc-sessions``: the manifest's
new entries letter for letter, the configuration against the catalog, the
program's config, the arithmetic by hand, the schedule, the five new
per-layer readers on recorded inputs, and the ``--rehearsal`` walk of the
whole command on the CPU."""

import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import manifest, readers_latent, run as runner, system  # noqa: E402
from benchmark.kinds import sessions  # noqa: E402
from benchmark.models import kimi_linear  # noqa: E402

CONFIG = "kimi-linear-48b-a3b-serve-l8"
CELL = CONFIG + ".doc-sessions"
SOURCE = "https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json"
NEW = {  # name: unit, better, source, layer, moves
    "latent_attn_time_share": ("%", "lower", "device_trace", "kernels, serving", "itl_mean_ms"),
    "latent_attn_roofline_share": ("%", "higher", "device_trace", "kernels, serving", "itl_mean_ms"),
    "latent_prefill_time_share": ("%", "lower", "device_trace", "kernels, serving", "output_tokens_per_s"),
    "expert_local_share": ("share", "lower", "program_counter", "expert layer", "itl_mean_ms"),
    "held_expert_ffn_time_share": ("%", "lower", "device_trace", "expert layer", "itl_mean_ms"),
}
GAINED = ("batch_occupancy", "decode_step_dev_ms", "decode_kernel_time_share", "decode_overlap_share",
          "kv_pool_in_use_share", "backlog_at_close", "state_restore_share", "state_snapshot_pool_in_use_share",
          "state_update_time_share", "state_update_roofline_share", "prefill_scan_time_share",
          "prefill_chunks_per_admission", "moe_load_max_over_mean", "moe_experts_hit_share")
NOT_JOINED = ("loop_host_ms_per_step", "decode_dry_share", "first16_mean_ms", "itl_p99_ms", "compiles_in_window.serve",
              "paged_kernel_us_per_live_page", "expert_ffn_time_share", "kv_read_share")


@pytest.fixture(scope="module")
def files():
    m = manifest.load()
    cell, config, traffic = runner.load_cell(m, CELL, rehearsal=False)
    return m, cell, config, traffic


def test_the_manifest_is_sound_and_holds_the_new_entries_letter_for_letter(files):
    m, cell, _, _ = files
    assert manifest.problems(m, ROOT) == []
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "doc-sessions", "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "1.3 x knee" in cell["why"] and "64 of 256 experts held" in cell["why"]
    entry = manifest.config_entry(m, CONFIG)
    assert entry["source"] == SOURCE and entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["reduced"] == ["num_hidden_layers", "linear_attn_config", "num_experts"] and len(entry["why"]) <= 200
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    e2e = {x["name"] for x in manifest.metrics_of(m, "end_to_end", CELL)}
    assert e2e == {"output_tokens_per_s", "itl_mean_ms", "setup_s"}  # above the knee: no first-token time
    layer = {x["name"] for x in manifest.metrics_of(m, "per_layer", CELL)}
    assert layer == set(NEW) | set(GAINED)
    by_name = {x["name"]: x for x in m["per_layer"] + m["end_to_end"]}
    for name, (unit, better, source, layer_name, moves) in NEW.items():
        assert by_name[name] == {"name": name, "unit": unit, "better": better, "source": source, "layer": layer_name,
                                 "moves": moves, "workloads": by_name[name]["workloads"]}
        assert CELL in by_name[name]["workloads"]
        assert manifest.layer_metric_file(name, m["paths"], ROOT) is not None
    for name in GAINED + ("output_tokens_per_s", "itl_mean_ms"):
        assert by_name[name]["workloads"].count(CELL) == 1     # among them, wherever later cells stand
    for name in NOT_JOINED:
        assert CELL not in by_name[name]["workloads"]


def test_the_configuration_keeps_every_published_number_but_the_depth_and_the_share(files):
    _, _, config, _ = files
    assert config["reduced"] == ["num_hidden_layers", "linear_attn_config", "num_experts"]
    pub = config["published"]
    assert pub["num_hidden_layers"] == 27 and pub["num_experts"] == 256
    assert len(pub["linear_attn_config"]["kda_layers"]) == 20 and pub["linear_attn_config"]["full_attn_layers"][-1] == 27
    lac = config["linear_attn_config"]
    assert lac["kda_layers"] == pub["linear_attn_config"]["kda_layers"][:6] == [1, 2, 3, 5, 6, 7]
    assert lac["full_attn_layers"] == pub["linear_attn_config"]["full_attn_layers"][:2] == [4, 8]
    assert {k: v for k, v in lac.items() if not k.endswith("_layers")} == \
        {k: v for k, v in pub["linear_attn_config"].items() if not k.endswith("_layers")}      # no width inside the group moved
    assert (config["num_experts"], config["experts_routed"], config["experts_held"]) == (64, 256, [0, 64])
    for key in ("norm_placement", "kda_layer", "mla_layer", "state_dtype", "A_log_dt_bias", "router_bias"):
        assert key in config["assumed"]
    run = config["run"]
    assert config["deployment"] and config["rehearsal"] and config["sizing"] and run["correctness"]["why"]
    assert "four" in config["deployment"].lower() and "first of four" in config["deployment"]
    assert (run["max_batch_size"], run["kv_block_size"], run["prefill_chunk_tokens"], run["decode_chunk"]) == (64, 16, 512, 1)
    assert (run["max_seq_len"], run["kv_num_blocks"]) == (18432, 32768)
    cc = run["correctness"]
    assert cc["max_prompt"] >= 6000 and cc["engine_conversations"] > run["max_batch_size"] and cc["state_bf16_exact_max"] == 0.5
    # ISSUE 46's count: KDA mixer 39.5M, MLA mixer 29.1M, an expert 7.08M, this chip 4338.6M, whole 49.1B
    assert kimi_linear.mixer_params(config, "kda") == pytest.approx(39.5e6, rel=2e-3)
    assert kimi_linear.mixer_params(config, "mla") == pytest.approx(29.1e6, rel=2e-3)
    assert kimi_linear.expert_params(config) == 3 * 2304 * 1024
    assert kimi_linear.n_params(config) == pytest.approx(4338.6e6, rel=1e-4)
    whole = {**config, **{k: pub[k] for k in config["reduced"]}, "experts_held": [0, 256]}
    assert kimi_linear.n_params(whole) == pytest.approx(49.1e9, rel=2e-3)
    catalog = os.path.join(os.sep, "opt", "skills", "guides", "model-configs", "architectures.jsonl")
    if not os.path.isfile(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    assert config["source"] == row["source_url"]
    assert sorted(k for k, v in row["config"].items() if config.get(k, "absent") != v) == sorted(config["reduced"])


def test_the_program_config_is_the_period_of_three_kda_and_one_latent_layer(files):
    _, _, config, _ = files
    cfg = kimi_linear.program_config(config, max_seq_len=18432, dtype="bfloat16", param_dtype="bfloat16")
    assert cfg.hybrid and cfg.split_ffn and (cfg.periods, cfg.linear_per_period, cfg.linear_layers) == (2, 3, 6)
    assert (cfg.latent_layers, cfg.kv_layers, cfg.latent_row, cfg.latent_row_lanes) == (2, 0, 576, 640)
    assert (cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim, cfg.linear_conv_width, cfg.linear_channels) == (32, 128, 128, 4, 12288)
    assert (cfg.linear_gate, cfg.linear_gate_rank, cfg.linear_out_gate, cfg.linear_allow_neg_eigval) == ("channel", 128, "sigmoid", False)
    assert (cfg.latent_rank, cfg.latent_nope_dim, cfg.latent_rope_dim, cfg.latent_value_dim) == (512, 128, 64, 128)
    assert (cfg.num_experts, cfg.experts_held, cfg.experts_here, cfg.expert_top_k, cfg.num_dense_layers) == (256, (0, 64), 64, 8, 1)
    assert (cfg.router_score, cfg.route_norm, cfg.route_scale, cfg.router_bias, cfg.num_shared_experts) == ("sigmoid", True, 2.446, True, 1)
    assert (cfg.pre_norms, cfg.post_norms, cfg.rope_full_layers, cfg.tie_embeddings, cfg.embed_scale, cfg.norm_eps) == \
        (True, False, False, False, 1.0, 1e-5)
    for bad, named in (({"q_lora_rank": 1536}, "q_lora_rank"), ({"mla_use_nope": False}, "mla_use_nope"),
                       ({"num_expert_group": 8}, "expert groups"), ({"experts_held": [0, 32]}, "experts_held")):
        with pytest.raises(ValueError, match=named):
            kimi_linear.program_config({**config, **bad})
    toy = kimi_linear.program_config(system.shrink_for_rehearsal(config), dtype="float32", param_dtype="float32")
    assert (toy.periods, toy.linear_per_period, toy.linear_heads, toy.experts_held, toy.num_experts) == (2, 3, 4, (4, 8), 16)


def test_latent_attn_work_and_state_update_bytes_by_hand_for_one_row(files):
    _, _, config, _ = files
    state = 2 * 32 * 128 * 128 * 4              # read and written, float32
    tail = 2 * 3 * 12288 * 2                    # the convolution's three last inputs, bf16, read and written
    vectors = (2 * 32 * 128 + 2 * 32 * 128 + 32 * 128 + 32) * 4  # q, k; v, o; the decay a channel; beta a head
    assert kimi_linear.state_update_bytes(config, 1) == 6 * (state + tail + vectors) == 26542848
    assert kimi_linear.state_update_bytes(config, 64) == 64 * 26542848
    assert kimi_linear.state_bytes_per_sequence(config) == 6 * (32 * 128 * 128 * 4 + 3 * 12288 * 2) == 13025280
    # one row at 17 000 cached tokens, two MLA layers: 576 numbers a token read once, and a head's two products over them
    by, fl = kimi_linear.latent_attn_work(config, 17000, 1)
    assert by == 2 * (17000 * 576 + 32 * (2 * 512 + 64)) * 2 == 39307264
    assert fl == 2 * 17000 * 2 * 32 * (576 + 512) == 2 * 17000 * 69632
    assert fl / by == pytest.approx(60.2, abs=0.1)   # ISSUE 46: 60 FLOP a byte against the chip's ~240
    token = 2 * 576 * 2                              # a cached token, both MLA layers
    assert kimi_linear.decode_step_bytes(config, 1000) - kimi_linear.decode_step_bytes(config, 0) == 1000 * token
    one_expert = 2 * kimi_linear.expert_params(config)
    assert kimi_linear.decode_step_bytes(config, 0, experts_hit=10) - kimi_linear.decode_step_bytes(config, 0, experts_hit=9) == one_expert


def test_the_schedule_is_sessions_own_over_long_documents_above_the_knee(files):
    _, _, config, traffic = files
    assert traffic["kind"] == "latent_sessions" and traffic["backlog"] == "expected"
    assert (traffic["agents"], traffic["system_prompt_tokens"], traffic["turns"], traffic["trace_s"]) == (8, 16384, 3, 2)
    assert traffic["new_tokens"] == {"dist": "uniform", "lo": 64, "hi": 256}
    assert traffic["output_tokens"] == {"dist": "uniform", "lo": 192, "hi": 320}
    assert traffic["think_s"] == {"dist": "uniform", "lo": 0.5, "hi": 2.0}
    plan = sessions.schedule(traffic, 10.0)
    assert sum(s["in_window"] for s in plan) == round(traffic["rate"] * 10)
    assert [s["agent"] for s in plan[:9]] == [0, 1, 2, 3, 4, 5, 6, 7, 0]
    assert all(len(s["new_tokens"]) == 3 and 192 <= min(s["output_tokens"]) and max(s["output_tokens"]) <= 320 for s in plan)
    assert plan == sessions.schedule(traffic, 10.0)  # one realisation for every seed
    # the longest history fits the engine's positions, and the pool holds the documents and every turn the system
    # can answer in ramp and window (the knee's 2.5 sessions/s of 3 turns, a turn's 160 new + 256 served tokens in
    # the mean): the rate is above the knee, so what it offers beyond that waits and takes no page
    run = config["run"]
    assert 16384 + 3 * (256 + 320) <= run["max_seq_len"]
    assert traffic["rate"] == 3.25 == 1.3 * 2.5 and traffic["ramp_s"] == 48
    turns_answered = 2.55 * 3 * (traffic["ramp_s"] + manifest.load()["run_seconds"])
    assert 8 * 1024 + turns_answered * ((160 + 256) // 16 + 1) < run["kv_num_blocks"]
    rehearsal = traffic["rehearsal"]
    assert rehearsal["turns"] == 2 and rehearsal["system_prompt_tokens"] > 2 * system.shrink_for_rehearsal(config)["run"]["prefill_chunk_tokens"]


# ---------------------------------------------------------------------------
# the readers: a number on a recorded run, None where there is nothing to read
# ---------------------------------------------------------------------------
PLANE = "/device:TPU:0"
LATENT_DECODE = "custom-call.3 custom-call bf16[64,32,512]"
LATENT_PREFILL = "custom-call.9 custom-call bf16[1,16384,512]"
STATE_KERNEL = "custom-call.7 custom-call (f32[64,32,128], f32[6,64,32,128,128])"
GROUPED = "custom-call.11 custom-call bf16[512,1024]"


def _events():
    """Two decode steps and one prefill chunk as ``trace_reduce.read_xplane`` gives them."""
    ev, t = [], 0
    for _ in range(2):
        ev.append([PLANE, "XLA Modules", "jit__decode_k_paged(123)", t, 1000])
        for name, dur in ((STATE_KERNEL, 100), (GROUPED, 150), ("fusion.4 fusion bf16[64,1024]", 50), (LATENT_DECODE, 200),
                          ("custom-call.12 custom-call bf16[512,2304]", 100), ("fusion.9 fusion f32[64,163840]", 400)):
            ev.append([PLANE, "XLA Ops", name, t, dur])
            t += dur
    ev.append([PLANE, "XLA Modules", "jit__prefill_chunk(77)", t, 2000])
    for name, dur in ((LATENT_PREFILL, 500), ("fusion.21 fusion bf16[1,512,9216]", 1200), ("fusion.22 fusion f32[8,1,32,64,64]", 300)):
        ev.append([PLANE, "XLA Ops", name, t, dur])
        t += dur
    return ev


def _run(events, samples, config, open_stats=None, close_stats=None):
    sampler = types.SimpleNamespace(samples=[(0.5, s) for s in samples])
    probe = types.SimpleNamespace(sampler=sampler, stats_open=open_stats and (0.0, open_stats),
                                  stats_close=close_stats and (1.0, close_stats))
    return {"ctx": types.SimpleNamespace(config=config), "events": events, "window": (0.0, 1.0), "probe": probe,
            "peak": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}, "turns": []}


def _reader(name):
    return runner.load_reader(name, manifest.load()["paths"])


SMOLLM = {"model": "smollm2", "num_hidden_layers": 24, "num_attention_heads": 32, "run": {"max_batch_size": 40}}


def test_the_latent_kernels_are_told_by_their_results(files):
    _, _, config, _ = files
    decode, prefill = readers_latent.decode_kernel(config), readers_latent.prefill_kernel(config)
    assert decode(LATENT_DECODE) and not decode(STATE_KERNEL) and not decode(GROUPED) and not decode(LATENT_PREFILL)
    assert not decode("fusion.3 fusion bf16[64,32,512]")                   # XLA's own operation of that shape is not the kernel
    assert prefill(LATENT_PREFILL) and not prefill(LATENT_DECODE) and not prefill("custom-call.2 custom-call bf16[1,16384,128]")
    assert readers_latent.decode_kernel(SMOLLM) is None and readers_latent.prefill_kernel({}) is None
    # kv_live_pages averages over all 8 layers, of which 2 walk pages: 64 rows of 1063 pages
    pages = 2 * 64 * 1063 / 8
    assert readers_latent.live_tokens(config, pages, 64) == 64 * 1063 * 16 - 64 * 8


def test_latent_attn_time_share_and_roofline_share_read_the_decode_steps(files):
    _, _, config, _ = files
    share, roofline = _reader("latent_attn_time_share"), _reader("latent_attn_roofline_share")
    samples = [{"active_slots": 64, "kv_live_pages": 2 * 64 * 1063 / 8}, {"active_slots": 62, "kv_live_pages": 2 * 62 * 1063 / 8}]
    assert share(_run(_events(), samples, config)) == pytest.approx(100.0 * 200 / 1000)
    tokens = readers_latent.live_tokens(config, 2 * 63 * 1063 / 8, 63.0)
    by, fl = kimi_linear.latent_attn_work(config, tokens, 63.0)
    assert by / 819e9 > fl / 197e12                                         # the bytes come first on this chip
    assert roofline(_run(_events(), samples, config)) == pytest.approx(100.0 * (by / 819e9) / 200e-9)
    for read in (share, roofline):
        assert read(_run(_events(), samples, SMOLLM)) is None              # no latent attention
        assert read(_run([], samples, config)) is None                     # no trace
        assert read({**_run(_events(), samples, config), "ctx": None}) is None
    assert roofline(_run(_events(), [], config)) is None                   # no counter
    quiet = [e for e in _events() if e[2] != LATENT_DECODE]
    assert roofline(_run(quiet, samples, config)) is None and share(_run(quiet, samples, config)) == 0.0


def test_latent_prefill_time_share_reads_the_chunks(files):
    _, _, config, _ = files
    read = _reader("latent_prefill_time_share")
    assert read(_run(_events(), [], config)) == pytest.approx(100.0 * 500 / 2000)
    assert read(_run(_events(), [], SMOLLM)) is None and read(_run([], [], config)) is None


def test_expert_local_share_is_local_over_routed():
    read = _reader("expert_local_share")
    a = {"moe_assignments": 1000, "moe_assignments_local": 260}
    b = {"moe_assignments": 9000, "moe_assignments_local": 2260}
    assert read(_run([], [], {}, a, b)) == pytest.approx(0.25)
    assert read(_run([], [], {}, {"moe_assignments": 5}, {"moe_assignments": 9})) is None   # no share: no such counter
    assert read(_run([], [], {}, a, a)) is None and read(_run([], [], {})) is None


def test_held_expert_ffn_time_share_reads_the_grouped_products_and_the_shared_expert(files):
    _, _, config, _ = files
    read = _reader("held_expert_ffn_time_share")
    assert read(_run(_events(), [], config)) == pytest.approx(100.0 * (150 + 50 + 100) / 1000)
    assert read(_run(_events(), [], SMOLLM)) is None and read(_run([], [], config)) is None
    assert read(_run(_events(), [], {k: v for k, v in config.items() if k != "experts_held"})) is None


# ---------------------------------------------------------------------------
# the latent pool's own comparison
# ---------------------------------------------------------------------------
def test_the_latent_pool_is_held_to_the_references_rows_and_an_8_bit_pool_fails_it(files):
    from benchmark.kinds.latent_sessions import check_latent_pool
    from benchmark.tools.latent_precision_control import latent_rows_in_8_bits

    config = system.shrink_for_rehearsal(files[2])
    run = config["run"]
    cfg = system.model_module(config).program_config(
        config, max_seq_len=run["max_seq_len"], dtype=run["dtype"], param_dtype=run["param_dtype"])
    params = system.make_params(cfg, 5, float(run["weights"]["embed_table_scale"]))
    sound = check_latent_pool(cfg, params, config, 5)
    assert sound["ok"] and sound["latent_rows_rel_err"] < 1e-4 and sound["latent_8bit_exact_share"] < 0.01
    assert sound["pad_lanes_zero"] and sound["tokens"] == 75 + 21 and len(sound["by_layer"]) == 2
    # the rows the single-token calls wrote, and the worst page's median: sound; two read-back pages exchanged: not
    assert sound["decode_rows"] == 21 and sound["latent_decode_rows_rel_err"] < 1e-4 and sound["latent_page_rel_err"] < 1e-4
    assert sound["wrong_page_control"] > 1.0 > sound["latent_page_rel_tol"]
    rounded = latent_rows_in_8_bits(lambda: check_latent_pool(cfg, params, config, 5))
    assert not rounded["ok"] and rounded["latent_8bit_exact_share"] == 1.0
    assert 0.01 < rounded["latent_rows_rel_err"] < 0.1   # three mantissa bits: ~2^-4 / sqrt(3) a value


# ---------------------------------------------------------------------------
# the whole command at toy size
# ---------------------------------------------------------------------------
def test_the_rehearsal_walks_the_cell_on_the_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELL, "--seed", "3000000011",
                          "--seconds", "4", "--trace", "1", "--rehearsal"], capture_output=True, text=True, env=env,
                         cwd=ROOT, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal_host_only"] and line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    compared = line["compared"]
    for key in ("state_rel_err", "restored_rel_err", "restored_state_rel_err", "engine_state_rel_err",
                "state_bf16_exact_share", "served_worst_deficit_sd", "window_worst_deficit_sd", "failed_requests",
                "latent_rows_rel_err", "latent_decode_rows_rel_err", "latent_page_rel_err", "latent_8bit_exact_share"):
        assert key in compared and compared[key]["value"] <= compared[key]["limit"]
    assert compared["restored_rel_err"]["value"] < 1e-3 and compared["engine_state_rel_err"]["value"] < 1e-3
    assert compared["state_bf16_exact_share"]["value"] < 0.01   # the engine's own snapshots hold float32
    assert compared["latent_rows_rel_err"]["value"] < 1e-3 and compared["latent_8bit_exact_share"]["value"] < 0.01
    assert {"state_restore_share", "state_snapshot_pool_in_use_share", "batch_occupancy", "kv_pool_in_use_share",
            "expert_local_share", "moe_experts_hit_share", "prefill_chunks_per_admission"} <= set(line["metric_names"])
