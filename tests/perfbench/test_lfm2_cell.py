"""The cell ``lfm2-8b-a1b-serve-l14.short-chat-full-batch`` (PR 54): its
manifest entries, its files, its schedule, its four readers on events written
by hand and on a run without what they read, the reference module's
arithmetic, the runner check's controls at toy size, and the ``--rehearsal``
walk of the cell on the CPU. Everything is looked for BY NAME: the cell among
a metric's workloads, a metric among the manifest's, never a place or a count."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import manifest, readers_conv, run as runner, system  # noqa: E402
from benchmark.kinds import conv_requests  # noqa: E402
from benchmark.models import lfm2_moe  # noqa: E402

CELL = "lfm2-8b-a1b-serve-l14.short-chat-full-batch"
CONFIG = "lfm2-8b-a1b-serve-l14"
NEW = {
    "conv_mixer_time_share": ("%", "lower", "device_trace", "kernels, serving", "itl_mean_ms"),
    "conv_mixer_roofline_share": ("%", "higher", "device_trace", "kernels, serving", "itl_mean_ms"),
    "conv_prefill_time_share": ("%", "lower", "device_trace", "kernels, serving", "output_tokens_per_s"),
    "state_reset_us_per_admission": ("us", "lower", "program_span", "KV block manager", "output_tokens_per_s"),
}
GAINED = ("batch_occupancy", "decode_step_dev_ms", "decode_kernel_time_share", "paged_kernel_us_per_live_page",
          "decode_overlap_share", "kv_pool_in_use_share", "backlog_at_close", "moe_load_max_over_mean",
          "moe_experts_hit_share", "expert_ffn_time_share", "state_snapshot_pool_in_use_share",
          "prefill_chunks_per_admission")
NOT_JOINED = ("loop_host_ms_per_step", "decode_dry_share")  # their lists are pinned by tests that pass: PERF.md section 7


@pytest.fixture(scope="module")
def files():
    m = manifest.load()
    cell, config, traffic = runner.load_cell(m, CELL, rehearsal=False)
    return m, cell, config, traffic


def test_the_manifest_is_sound_and_holds_the_new_entries_by_name(files):
    m, cell, _, _ = files
    assert manifest.problems(m, ROOT) == []
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "short-chat-full-batch", "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200
    for said in ("1.3x knee", "128 rows", "16 rows an expert", "11 conv tails + 3 KV layers", "slots bind, not pages",
                 "no prefix hit", "14 of 24 layers", "host share unmeasured"):
        assert said in cell["why"], said
    entry = manifest.config_entry(m, CONFIG)
    assert entry["source"] == "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json"
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json" and entry["reduced"] == ["num_hidden_layers", "layer_types"]
    assert [w["name"] for w in m["workloads"] if w["config"] == CONFIG] == [CELL]      # one cell, no second
    e2e = {x["name"] for x in manifest.metrics_of(m, "end_to_end", CELL)}
    assert e2e == {"output_tokens_per_s", "itl_mean_ms", "setup_s"}    # above the knee: no first-token time and no tail
    layer = {x["name"] for x in manifest.metrics_of(m, "per_layer", CELL)}
    assert layer == set(NEW) | set(GAINED)
    by_name = {x["name"]: x for x in m["per_layer"] + m["end_to_end"]}
    for name, (unit, better, source, layer_name, moves) in NEW.items():
        assert by_name[name] == {"name": name, "unit": unit, "better": better, "source": source, "layer": layer_name,
                                 "moves": moves, "workloads": [CELL]}
        assert manifest.layer_metric_file(name, m["paths"], ROOT) is not None
        assert layer_name in {x["layer"] for x in m["per_layer"] if x["name"] not in NEW}   # a layer already named
    for name in GAINED + ("output_tokens_per_s", "itl_mean_ms"):
        assert by_name[name]["workloads"].count(CELL) == 1
    for name in NOT_JOINED:
        assert CELL not in by_name[name]["workloads"]


def test_the_configuration_keeps_every_published_number_but_the_depth(files):
    _, _, config, _ = files
    assert config["model"] == "lfm2_moe" and config["reduced"] == ["num_hidden_layers", "layer_types"]
    published = config["published"]
    assert published["num_hidden_layers"] == 24 and len(published["layer_types"]) == 24
    assert config["num_hidden_layers"] == 14 and config["layer_types"] == published["layer_types"][:14]
    assert config["layer_types"] == ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 3
    for key in ("in_proj_order", "no_activation", "conv_bias_covers_projections", "qk_layernorm", "rope_half_split",
                "tie_embedding", "no_embedding_multiplier", "router_sum_eps", "tail_dtype"):
        assert key in config["assumed"], key
    run = config["run"]
    assert config["deployment"] and config["rehearsal"] and config["sizing"] and run["correctness"]["why"] and run["weights"]["why"]
    assert (run["max_batch_size"], run["kv_block_size"], run["prefill_chunk_tokens"], run["decode_chunk"]) == (128, 16, 512, 1)
    assert (run["max_seq_len"], run["kv_num_blocks"], run["state_snapshots"]) == (4096, 16384, 512)
    assert (run["dtype"], run["param_dtype"]) == ("bfloat16", "bfloat16")
    catalog = os.path.join(os.sep, "opt", "skills", "guides", "model-configs", "architectures.jsonl")
    if not os.path.isfile(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "LFM2-8B-A1B")
    assert config["source"] == row["source_url"]
    assert sorted(k for k, v in row["config"].items() if config.get(k, "absent") != v) == ["layer_types", "num_hidden_layers"]
    assert published["layer_types"] == row["config"]["layer_types"]


def test_the_arithmetic_is_the_issues_count(files):
    _, _, config, _ = files
    d = 2048
    assert lfm2_moe.expert_params(config) == 3 * d * 1792 == 11010048
    assert lfm2_moe.mixer_params(config, "conv") == d * 3 * d + d * d + 3 * d + d == 16785408      # 16.78M
    assert lfm2_moe.mixer_params(config, "full_attention") == 2 * d * d + 2 * d * 512 + 2 * 64 + d == 10487936   # 10.49M
    assert lfm2_moe.n_params(config) == pytest.approx(4667e6, rel=1e-3)                              # 9.33 GB in bf16
    whole = {**config, "num_hidden_layers": 24, "layer_types": config["published"]["layer_types"]}
    assert lfm2_moe.n_params(whole) == pytest.approx(8340e6, rel=1e-3)                               # published: 8.3B
    assert lfm2_moe.n_params({**whole, "tie_embedding": False}) == pytest.approx(8474e6, rel=1e-3)
    assert lfm2_moe.kv_bytes_per_token(config) == 2 * 3 * 8 * 64 * 2 == 6144
    assert lfm2_moe.tail_bytes_per_sequence(config) == 11 * 2 * d * 2 == 90112                       # 90 KB a sequence
    weights = d * 3 * d + 3 * d + d * d
    row = d + 6 * d + 4 * d + 2 * d
    assert lfm2_moe.conv_mixer_bytes(config, 0) == 11 * weights * 2
    assert lfm2_moe.conv_mixer_bytes(config, 128) == 11 * (weights + 128 * row) * 2
    without = lfm2_moe.conv_mixer_bytes(config, 128, out_proj=False)
    assert without == 11 * (d * 3 * d + 3 * d + 128 * (row - 2 * d)) * 2 and without < lfm2_moe.conv_mixer_bytes(config, 128)
    every = lfm2_moe.decode_step_bytes(config, 0)
    assert every == lfm2_moe.n_params(config) * 2
    assert every - lfm2_moe.decode_step_bytes(config, 0, experts_hit=12 * 32 - 10) == 10 * 11010048 * 2
    assert lfm2_moe.decode_step_bytes(config, 1000) - every == 1000 * 6144


def test_the_program_config_is_the_conv_block_and_refuses_what_it_cannot_honour(files):
    _, _, config, _ = files
    cfg = lfm2_moe.program_config(config, max_seq_len=4096, dtype="bfloat16", param_dtype="bfloat16")
    assert cfg.hybrid and cfg.split_ffn and cfg.plan == (2, 4, 3) and (cfg.conv_layers, cfg.kv_layers, cfg.linear_layers) == (11, 3, 0)
    assert (cfg.conv_width, cfg.route_norm_eps, cfg.router_score, cfg.router_bias, cfg.route_norm, cfg.route_scale) == \
        (3, 1e-6, "sigmoid", True, True, 1.0)
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.d_ff, cfg.expert_width, cfg.vocab_size) == (32, 8, 64, 7168, 1792, 65536)
    assert (cfg.num_experts, cfg.expert_top_k, cfg.num_dense_layers, cfg.num_shared_experts) == (32, 4, 2, 0)
    assert (cfg.tie_embeddings, cfg.embed_scale, cfg.qk_norm, cfg.qk_norm_whole, cfg.norm_eps, cfg.rope_theta) == \
        (True, 1.0, True, False, 1e-5, 1e6)
    for bad, named in (({"conv_bias": True}, "conv_bias"), ({"use_expert_bias": False}, "use_expert_bias"),
                       ({"layer_types": ["conv", "sliding_attention"] * 7}, "layer_types"),
                       ({"num_dense_layers": 14}, "num_dense_layers"), ({"tie_embedding": False}, "tie_embedding"),
                       ({"rope_scaling": {"type": "yarn"}}, "rope_scaling")):
        with pytest.raises(ValueError, match=named):
            lfm2_moe.program_config({**config, **bad})
    toy = lfm2_moe.program_config(system.shrink_for_rehearsal(config), dtype="float32", param_dtype="float32")
    assert toy.plan == (2, 2, 2) and toy.n_layers == 7 and (toy.conv_layers, toy.kv_layers) == (5, 2)   # a tail of one


def test_the_schedule_is_open_loops_own_above_the_knee(files):
    _, _, config, traffic = files
    assert traffic["kind"] == "conv_requests" and traffic["backlog"] == "expected" and traffic["rate_why"] and traffic["ramp_why"]
    assert traffic["prompt_tokens"] == {"dist": "lognormal", "median": 256, "sigma": 0.8, "lo": 32, "hi": 2048}
    assert traffic["output_tokens"] == {"dist": "lognormal", "median": 256, "sigma": 0.7, "lo": 32, "hi": 1024}
    assert traffic["trace_s"] == 4
    plan = conv_requests.schedule(traffic, 51.0)
    scored = [r for r in plan if r["scored"]]
    assert len(scored) == round(traffic["rate"] * 51) and len(plan) - len(scored) == round(traffic["rate"] * traffic["ramp_s"])
    assert plan == conv_requests.schedule(traffic, 51.0)      # one realisation for every seed
    assert 32 <= min(r["prompt_len"] for r in plan) and max(r["prompt_len"] for r in plan) <= 2048
    assert 32 <= min(r["max_tokens"] for r in plan) and max(r["max_tokens"] for r in plan) <= 1024
    run = config["run"]
    assert max(r["prompt_len"] + r["max_tokens"] for r in plan) <= run["max_seq_len"]
    # the slots bind, not the pages: 128 of the largest requests together ask for less than the pool holds
    pages = sorted((-(-(r["prompt_len"] + r["max_tokens"]) // run["kv_block_size"]) for r in plan), reverse=True)
    assert sum(pages[: run["max_batch_size"]]) < run["kv_num_blocks"] - 1
    assert len(plan) <= run["max_queued_requests"]            # the whole backlog may queue: nothing is shed


# ---------------------------------------------------------------------------
# the readers: a number on events written by hand, None where there is nothing to read
# ---------------------------------------------------------------------------
PLANE = "/device:TPU:0"
IN_PROJ = "fusion.11 fusion bf16[128,6144]"
TAILS = "fusion.12 fusion (bf16[128,2048], bf16[128,4096])"
POOL = "scatter.3 scatter bf16[11,128,4096]"


def _events():
    """Two decode steps and one prefill chunk as ``trace_reduce.read_xplane`` gives them."""
    ev, t = [], 0
    for _ in range(2):
        ev.append([PLANE, "XLA Modules", "jit__decode_k_paged(123)", t, 1000])
        for name, dur in ((IN_PROJ, 100), (TAILS, 30), (POOL, 20), ("fusion.1 fusion bf16[128,2048]", 250),
                          ("custom-call.3 custom-call bf16[512,1792]", 400), ("fusion.9 fusion f32[128,65536]", 200)):
            ev.append([PLANE, "XLA Ops", name, t, dur])
            t += dur
    ev.append([PLANE, "XLA Modules", "jit__prefill_chunk(77)", t, 2000])
    for name, dur in (("fusion.20 fusion bf16[1,512,6144]", 300), ("fusion.21 fusion bf16[1,4096]", 100),
                      ("fusion.22 fusion bf16[2048,1792]", 1200), ("fusion.24 fusion bf16[1,512,2048]", 400)):
        ev.append([PLANE, "XLA Ops", name, t, dur])
        t += dur
    return ev


def _run(events, samples, config, open_stats=None, close_stats=None):
    sampler = types.SimpleNamespace(samples=[(0.5, s) for s in samples])
    probe = types.SimpleNamespace(sampler=sampler, stats_open=open_stats and (0.0, open_stats),
                                  stats_close=close_stats and (1.0, close_stats), trace_started=0.0)
    return {"ctx": types.SimpleNamespace(config=config), "events": events, "window": (0.0, 1.0), "probe": probe,
            "peak": {"hbm_bytes_per_s": 819e9}, "turns": []}


def _reader(name):
    return runner.load_reader(name, manifest.load()["paths"])


SMOLLM = {"model": "smollm2", "num_hidden_layers": 24, "hidden_size": 2048, "run": {"max_batch_size": 40}}


def test_the_conv_operations_are_told_by_the_widths_only_a_conv_mixer_has(files):
    _, _, config, _ = files
    match = readers_conv.conv_op(config)
    assert match(IN_PROJ) and match(TAILS) and match(POOL) and match("fusion.2 fusion bf16[1,512,6144]")
    assert match("dynamic-update-slice.1 dynamic-update-slice bf16[11,640,4096]")                  # the snapshot pool's entries
    for other in ("fusion.1 fusion bf16[128,2048]", "custom-call.3 custom-call bf16[512,1792]", "fusion.9 fusion f32[128,65536]",
                  "fusion.4 fusion bf16[128,7168]", "custom-call.5 custom-call bf16[128,8,1,64]", "fusion.6 fusion bf16[6144,2048]"):
        assert not match(other), other
    assert readers_conv.conv_op(SMOLLM) is None and readers_conv.conv_op({}) is None
    assert readers_conv.conv_layers(config) == 11


def test_the_three_trace_readers_read_the_steps_and_the_chunk(files):
    _, _, config, _ = files
    time_share, roofline, prefill = (_reader(n) for n in ("conv_mixer_time_share", "conv_mixer_roofline_share", "conv_prefill_time_share"))
    samples = [{"active_slots": 128}, {"active_slots": 124}]
    assert time_share(_run(_events(), samples, config)) == pytest.approx(100.0 * 150 / 1000)
    assert prefill(_run(_events(), samples, config)) == pytest.approx(100.0 * 400 / 2000)
    want = 100.0 * lfm2_moe.conv_mixer_bytes(config, 126.0, out_proj=False) / (150e-9) / 819e9
    assert roofline(_run(_events(), samples, config)) == pytest.approx(want)
    for read in (time_share, roofline, prefill):
        assert read(_run(_events(), samples, SMOLLM)) is None       # no conv layers
        assert read(_run([], samples, config)) is None              # no trace
        assert read({**_run(_events(), samples, config), "ctx": None}) is None
    assert roofline(_run(_events(), [], config)) is None            # no counter
    quiet = [e for e in _events() if "6144" not in e[2] and "4096" not in e[2]]
    assert roofline(_run(quiet, samples, config)) is None and time_share(_run(quiet, samples, config)) == 0.0


def test_state_reset_us_per_admission_is_the_spans_seconds_over_the_admissions():
    read = _reader("state_reset_us_per_admission")
    a = {"state_reset_s": 0.010, "state_zeroed": 100, "state_restores": 0}
    b = {"state_reset_s": 0.034, "state_zeroed": 250, "state_restores": 50}
    assert read(_run([], [], {}, a, b)) == pytest.approx(1e6 * 0.024 / 200)
    assert read(_run([], [], {}, {"state_zeroed": 1, "state_restores": 0}, {"state_zeroed": 9, "state_restores": 0})) is None  # the parent: no such clock
    assert read(_run([], [], {}, a, a)) is None and read(_run([], [], {})) is None


# ---------------------------------------------------------------------------
# the runner's check at toy size: the stated program passes, each control fails by its own limit
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def toy(files):
    import jax

    _, _, config, _ = files
    small = system.shrink_for_rehearsal(config)
    run = small["run"]
    cfg = lfm2_moe.program_config(small, max_seq_len=run["max_seq_len"], dtype=run["dtype"], param_dtype=run["param_dtype"])
    return small, cfg, jax.block_until_ready(system.make_params(cfg, 5, 1.0))


def test_the_stated_program_passes_every_limit_of_the_runners_check(toy):
    small, cfg, params = toy
    out = conv_requests.check_conv_against_reference(cfg, params, small, 5)
    assert out["ok"], out
    for name, limit in conv_requests.LIMITS:
        assert out[name] < out[limit], name
    assert out["cuts"] == [c for c in out["cuts"] if c % 16 == 5] and all(s % 16 == 0 for s in out["snapshots_at"])


@pytest.mark.parametrize("fault,caught_by", [("zeroed_tail", "boundary_rel_err"), ("wrong_slot", "boundary_rel_err"),
                                             ("idle_moves", "tails_rel_err")])
def test_a_lost_or_foreign_tail_fails_the_check_right_behind_the_boundary_and_a_moved_one_in_the_tails(toy, fault, caught_by):
    small, cfg, params = toy
    out = conv_requests.check_conv_against_reference(cfg, params, small, 5, fault=fault)
    assert not out["ok"] and out[caught_by] > 0.3, out
    if fault == "idle_moves":   # the idle slots' tails moved and nothing else did: no logit sees it
        assert max(out["rel_err"], out["restored_rel_err"], out["boundary_rel_err"]) < out["rel_tol"], out


def test_the_reference_follows_the_programs_selections_and_counts_where_its_own_would_differ(toy):
    """The check hands the reference the experts the program ran each token
    through. At toy size in float32 the two routers agree, so following
    changes nothing; a program whose router rounds its scores to bfloat16
    chooses otherwise at near-ties, which ``near_tie_share`` counts, and the
    logits of the reference that follows it stay close where the reference
    routing for itself leaves by several times as much."""
    from benchmark.tools import conv_precision_control as control, precision_control as pc
    from ray_tpu.models import transformer

    small, cfg, params = toy
    sound = conv_requests.check_conv_against_reference(cfg, params, small, 5)
    alone = conv_requests.check_conv_against_reference(cfg, params, small, 5, matched=False)
    assert sound["routes_matched"] and not alone["routes_matched"] and sound["near_tie_share"] == alone["near_tie_share"] == 0.0
    assert abs(sound["rel_err"] - alone["rel_err"]) < 1e-6
    assert len(set(sound["slots"])) == len(sound["slots"]) and sound["unnamed_slots_max_abs"] == 0.0
    with pc.swapped_in(transformer, "route", control.route_in_bf16):
        followed = conv_requests.check_conv_against_reference(cfg, params, small, 5)
        own = conv_requests.check_conv_against_reference(cfg, params, small, 5, matched=False)
    assert followed["near_tie_share"] > 0 and own["near_tie_share"] > 0
    assert own["rel_err"] > 3 * followed["rel_err"], (own, followed)


def test_lowered_weights_fail_the_check_in_the_tails_no_expert_layer_lies_before(toy):
    from benchmark.tools import precision_control as pc

    small, cfg, params = toy
    lowered = pc.weights_through_int8(system.make_params(cfg, 5, 1.0))
    out = conv_requests.check_conv_against_reference(cfg, lowered, small, 5, reference_params=lambda: params)
    assert not out["ok"] and out["lead_tails_rel_err"] > 10 * out["lead_tails_rel_tol"], out


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
    for key in ("conv_rel_err", "restored_rel_err", "boundary_rel_err", "tails_rel_err", "lead_tails_rel_err",
                "router_swap_share", "expert_rel_err", "served_worst_deficit_sd", "window_worst_deficit_sd", "failed_requests"):
        assert key in compared and compared[key]["value"] <= compared[key]["limit"], key
    # what reads counters reports on any backend; what reads the device trace is left out here, and nothing raises
    for name in ("batch_occupancy", "state_reset_us_per_admission", "prefill_chunks_per_admission",
                 "state_snapshot_pool_in_use_share", "moe_experts_hit_share"):
        assert name in line["metric_names"], name
