"""The cell ``trinity-mini-serve-l5.mixed-lengths`` (PR 29): its manifest
entries, its files, the engine's expert-layer counters that its readers
read, its rehearsal walk, and its lower-precision control at toy size."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import manifest, run as runner, stratify, system  # noqa: E402

CELL = "trinity-mini-serve-l5.mixed-lengths"
NEW_READERS = ("moe_load_max_over_mean", "moe_experts_hit_share", "kv_read_share", "expert_ffn_time_share")


@pytest.fixture(scope="module")
def files():
    m = manifest.load()
    cell, config, traffic = runner.load_cell(m, CELL, rehearsal=False)
    return m, cell, config, traffic


def test_the_manifest_is_sound_and_the_cell_reports_what_the_issue_names(files):
    m, cell, _, _ = files
    assert manifest.problems(m, ROOT) == []
    assert cell["chips"] == 1 and sum(w["chips"] == 4 for w in m["workloads"]) <= len(m["workloads"]) // 4
    e2e = {x["name"] for x in manifest.metrics_of(m, "end_to_end", CELL)}
    assert e2e == {"itl_p99_ms", "itl_mean_ms", "first16_mean_ms", "setup_s"}
    layer = {x["name"] for x in manifest.metrics_of(m, "per_layer", CELL)}
    assert set(NEW_READERS) <= layer
    assert not layer & {"kv_pool_in_use_share", "backlog_at_close", "prefix_token_hit_share"}
    for name in NEW_READERS:
        assert [x for x in m["per_layer"] if x["name"] == name][0]["workloads"] == [CELL]


def test_the_configuration_keeps_every_published_number_but_the_two_reduced(files):
    _, _, config, _ = files
    catalog = os.path.join(os.sep, "opt", "skills", "guides", "model-configs", "architectures.jsonl")
    if not os.path.isfile(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Trinity-Mini")
    assert config["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if config.get(k, "absent") != v)
    assert differs == sorted(config["reduced"]) == ["num_dense_layers", "num_hidden_layers"]
    L, nd = config["num_hidden_layers"], config["num_dense_layers"]
    kinds = config["layer_types"][:L]
    assert kinds[nd:].count("full_attention") * 3 == kinds[nd:].count("sliding_attention")  # one whole 3:1 period
    assert L - nd >= 4 and config["num_experts"] == 128 and config["vocab_size"] == 200192
    run = config["run"]
    assert run["correctness"]["max_prompt"] >= config["sliding_window"] + run["prefill_chunk_tokens"]


def test_the_traffic_is_the_issues_mix_and_fits_the_engine(files):
    _, _, config, traffic = files
    assert traffic["kind"] == "open_loop_requests" and traffic["ramp_s"] >= 45
    assert traffic["prompt_tokens"] == {"dist": "lognormal", "median": 1024, "sigma": 1.0, "lo": 128, "hi": 7168}
    assert traffic["output_tokens"] == {"dist": "lognormal", "median": 160, "sigma": 0.7, "lo": 16, "hi": 512}
    assert traffic["prompt_tokens"]["hi"] + traffic["output_tokens"]["hi"] <= config["run"]["max_seq_len"]
    assert round(traffic["rate"] / 0.05) * 0.05 == pytest.approx(traffic["rate"])
    n = int(round(traffic["rate"] * 51))
    assert n >= 40, "at least 40 scored requests a window"
    from benchmark.kinds import open_loop_requests

    asked, pool = 0, config["run"]["kv_num_blocks"] - 1  # the window opens on a pool full of retained prefixes
    for r in open_loop_requests.schedule(traffic, 51):
        asked += -(-(r["prompt_len"] + r["max_tokens"]) // config["run"]["kv_block_size"])
        if asked >= pool:
            break
    assert r["due"] < -3.0, "the ramp's arrivals alone must have asked for the whole pool seconds before the window opens"
    sizes = stratify.stratified_sizes(traffic["prompt_tokens"], n)
    past = [s for s in sizes if s > config["sliding_window"]]
    assert 0.2 < len(past) / n < 0.3 and sum(past) > 0.5 * sum(sizes)  # a quarter of the requests, over half the tokens


# --- (e) the engine's counters -----------------------------------------------------------------
@pytest.fixture(scope="module")
def toy():
    import jax

    from ray_tpu.models.transformer import init_params

    config = system.shrink_for_rehearsal(system.load_json("benchmark/configs/trinity-mini-serve-l5.json"))
    cfg = system.model_module(config).program_config(config, max_seq_len=256, dtype="float32", param_dtype="float32")
    return config, cfg, init_params(cfg, jax.random.key(0))


def test_expert_counters_add_up_and_kv_read_share_is_the_closed_form(toy):
    from ray_tpu.serve.llm import LLMEngine

    _, cfg, params = toy
    eng = LLMEngine(cfg, params, max_batch_size=3, max_seq_len=256, kv_block_size=16, kv_num_blocks=64,
                    prefill_chunk_tokens=32)
    try:
        rng = np.random.default_rng(0)
        jobs = [(rng.integers(1, cfg.vocab_size, size=n).tolist(), k) for n, k in ((70, 5), (9, 3), (40, 1))]
        futures = [eng.submit(p, max_tokens=k, temperature=0.0) for p, k in jobs]
        assert [len(f.result(timeout=300)) for f in futures] == [k for _, k in jobs]
        s = eng.stats()
        tokens = sum(len(p) + k - 1 for p, k in jobs)  # every prompt token once, every reply token but the last
        assert s["moe_assignments"] == tokens * cfg.expert_top_k * cfg.expert_layers
        assert sum(s["moe_expert_assignments"]) == s["moe_assignments"] and len(s["moe_expert_assignments"]) == 8
        assert 0 < s["moe_experts_hit_decode"] <= s["decode_steps"] * cfg.expert_layers * cfg.num_experts
        assert s["moe_experts_hit"] > s["moe_experts_hit_decode"] and s["moe_expert_layers"] == 4
        assert s["kv_read_share"] == 1.0  # nothing live
        assert eng.admission_snapshot()["moe_assignments"] == s["moe_assignments"]
    finally:
        eng.shutdown()
    # two live sequences of 5 and 40 tokens; windows 16, 16, 16, none, 16
    eng._active[:2], eng._pos[:2] = True, [5, 40]
    assert eng.kv_read_share() == pytest.approx((4 * (5 + 16) + 45) / (5 * 45))


def test_a_config_without_windows_or_experts_reports_neither(toy):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig, init_params
    from ray_tpu.serve.llm import LLMEngine

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64, dtype=jnp.float32, max_seq_len=64)
    eng = LLMEngine(cfg, init_params(cfg, jax.random.key(0)), max_batch_size=2, max_seq_len=64, kv_num_blocks=16)
    try:
        assert len(eng.generate([1, 2, 3], max_tokens=4, temperature=0.0)) == 4
        s = eng.stats()
        assert s["decode_steps"] == 3 and s["kv_read_share"] == 1.0
        assert not [k for k in s if k.startswith("moe_")]
    finally:
        eng.shutdown()
    with pytest.raises(ValueError, match="quantize=True does not cover"):
        LLMEngine(toy[1], toy[2], max_batch_size=2, max_seq_len=64, quantize=True)


# --- (f) the walk and the readers --------------------------------------------------------------
def _run(*args, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "run.py"), *args],
                          cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout)


def test_rehearsal_walks_the_new_cell_and_its_counter_readers_read(files):
    p = _run("--workload", CELL, "--seed", str(2**31 + 1365), "--seconds", "3", "--trace", "1", "--rehearsal")
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["rehearsal_host_only"] is True and line["correct"] is True, p.stderr[-3000:]
    assert line["attempted"] > 0 and line["failed"] == 0 and "metrics" not in line
    assert {"moe_load_max_over_mean", "moe_experts_hit_share", "kv_read_share"} <= set(line["metric_names"])
    assert line["compared"]["paged_rel_err"]["value"] < 1e-4  # float32 at toy size: a check, not only a walk


def _reader(name):
    return runner.load_reader(name, manifest.load()["paths"])


def test_the_counter_readers_compute_what_they_say_and_read_nothing_from_a_program_without_counters():
    def probe(open_, close, samples=()):
        sampler = types.SimpleNamespace(samples=[(0.5, s) for s in samples])
        return {"probe": types.SimpleNamespace(stats_open=(0.0, open_), stats_close=(1.0, close), sampler=sampler),
                "window": (0.0, 1.0)}

    a = {"moe_expert_assignments": [10, 10, 10, 10], "moe_experts_hit_decode": 5, "decode_steps": 10, "moe_expert_layers": 2}
    b = {"moe_expert_assignments": [20, 40, 10, 10], "moe_experts_hit_decode": 45, "decode_steps": 20, "moe_expert_layers": 2}
    run = probe(a, b, samples=[{"kv_read_share": 0.5}, {"kv_read_share": 0.7}])
    assert _reader("moe_load_max_over_mean")(run) == pytest.approx(30 / 10)
    assert _reader("moe_experts_hit_share")(run) == pytest.approx(40 / (10 * 2 * 4))
    assert _reader("kv_read_share")(run) == pytest.approx(0.6)
    parent = probe({"active_slots": 1}, {"active_slots": 2}, samples=[{"active_slots": 1}])
    for name in ("moe_load_max_over_mean", "moe_experts_hit_share", "kv_read_share"):
        assert _reader(name)(parent) is None


def test_the_trace_reader_finds_operations_by_the_expert_layers_shapes():
    with open(os.path.join(ROOT, "benchmark", "testdata", "recorded_trace.json")) as f:
        events = json.load(f)["events"]

    def run(slots, k, shared):
        config = {"num_experts": 8, "num_experts_per_tok": k, "num_shared_experts": 1,
                  "moe_intermediate_size": shared, "run": {"max_batch_size": slots}}
        return {"ctx": types.SimpleNamespace(config=config), "events": events}

    read = _reader("expert_ffn_time_share")
    assert 0 < read(run(slots=32, k=2, shared=7)) < 100     # the recorded decode step has 64-row operations
    assert read(run(slots=13, k=3, shared=7)) == 0.0        # and none of 39 rows
    assert read({"ctx": types.SimpleNamespace(config={"run": {}}), "events": events}) is None  # no expert layer
    assert read(dict(run(32, 2, 7), events=[])) is None     # no device trace


# --- (g) the lower-precision controls, where they can fail at toy size, and what the check is made of ---
@pytest.mark.parametrize("lowered", ["router scores in bfloat16", "bfloat16 weights and activations",
                                     "weights through int8"])
def test_a_lower_precision_fails_the_logits_check(toy, lowered):
    import jax

    from benchmark import serving
    from benchmark.tools import precision_control
    from ray_tpu.models.transformer import init_params

    config, cfg, params = toy
    limit = config["run"]["correctness"]["rel_tol"]
    assert serving.check_paged_against_reference(cfg, params, config, seed=2)["ok"]
    if lowered.startswith("router"):
        got = precision_control.with_router_in_bf16(
            lambda: serving.check_paged_against_reference(cfg, params, config, seed=2))
    elif lowered.startswith("weights through"):
        got = precision_control.check_with_weights_through_int8(cfg, config, 2)
    else:
        low = system.model_module(config).program_config(config, max_seq_len=256, dtype="bfloat16", param_dtype="bfloat16")
        got = serving.check_paged_against_reference(low, init_params(low, jax.random.key(0)), config, seed=2)
    assert not got["ok"] and got["rel_err"] > 5 * limit


def test_with_the_reference_on_the_programs_routing_the_error_is_the_arithmetics(toy):
    """In float32 the two routers agree and following changes nothing; in
    bfloat16 a swapped expert of two moves a logit vector by 20-50%, and with
    the routing held equal what is left is bfloat16's own ~1.4%: every swap a
    near-tie in the reference's float32 scores."""
    import jax

    from benchmark import serving
    from benchmark.tools import precision_control
    from ray_tpu.models.transformer import init_params

    config, cfg, params = toy
    stated = serving.check_paged_against_reference(cfg, params, config, seed=2)
    same = precision_control.check_with_same_routing(cfg, params, config, seed=2)
    assert same["swapped_share"] == 0 and same["pairs"] > 0 and same["rel_err"] == stated["rel_err"]

    low = system.model_module(config).program_config(config, max_seq_len=256, dtype="bfloat16", param_dtype="bfloat16")
    low_params = init_params(low, jax.random.key(4))
    own = serving.check_paged_against_reference(low, low_params, config, seed=4)
    same = precision_control.check_with_same_routing(low, low_params, config, seed=4)
    assert own["rel_err"] > 0.05 and same["rel_err"] < 0.03
    assert 0 < same["swapped_share"] < 0.1 and 0 < same["swapped_margin_max_sd"] < 0.2
