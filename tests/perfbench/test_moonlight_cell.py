"""ISSUE 51's cell ``moonlight-16b-a3b-train-ep8.steps``: the manifest's new
entries letter for letter, the configuration against the catalog, the
program's config, the FLOP counts by hand, the four new per-layer readers on
recorded inputs, the load rule's check, and the ``--rehearsal`` walk of the
whole command on the CPU."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import manifest, readers_routed, run as runner, system  # noqa: E402
from benchmark.kinds import routed_train_steps  # noqa: E402
from benchmark.models import moonlight  # noqa: E402

CONFIG = "moonlight-16b-a3b-train-ep8"
CELL = CONFIG + ".steps"
SOURCE = "https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json"
NEW = {  # name: unit, better, source, layer
    "routed_ffn_time_share.train": ("%", "lower", "device_trace", "expert layer, training"),
    "routed_ffn_mxu_share.train": ("%", "higher", "device_trace", "expert layer, training"),
    "latent_flash_mxu_share.train": ("%", "higher", "device_trace", "kernels, training"),
    "held_expert_load_max_over_mean.train": ("ratio", "lower", "program_counter", "router"),
}
GAINED = ("step_ms", "mfu", "compiles_in_window.train")
NOT_JOINED = ("attn_kernel_time_share", "collective_time_share", "exposed_comm_share")


@pytest.fixture(scope="module")
def files():
    m = manifest.load()
    cell, config, traffic = runner.load_cell(m, CELL, rehearsal=False)
    return m, cell, config, traffic


def test_the_manifest_is_sound_and_holds_the_new_entries_letter_for_letter(files):
    m, cell, _, traffic = files
    assert manifest.problems(m, ROOT) == []
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "routed-train-steps", "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "8192-token" in cell["why"] and "8 of 64 experts" in cell["why"]
    assert traffic["kind"] == "routed_train_steps" and (traffic["warm_steps"], traffic["trace_s"]) == (2, 4)
    entry = manifest.config_entry(m, CONFIG)
    assert entry["source"] == SOURCE and entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"] and len(entry["why"]) <= 200
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    e2e = {x["name"] for x in manifest.metrics_of(m, "end_to_end", CELL)}
    assert e2e == {"tokens_per_s", "setup_s"}
    layer = {x["name"] for x in manifest.metrics_of(m, "per_layer", CELL)}
    assert layer == set(NEW) | set(GAINED)
    by_name = {x["name"]: x for x in m["per_layer"] + m["end_to_end"]}
    for name, (unit, better, source, layer_name) in NEW.items():
        assert by_name[name] == {"name": name, "unit": unit, "better": better, "source": source, "layer": layer_name,
                                 "moves": "tokens_per_s", "workloads": by_name[name]["workloads"]}
        assert CELL in by_name[name]["workloads"]
        assert manifest.layer_metric_file(name, m["paths"], ROOT) is not None
    for name in GAINED + ("tokens_per_s",):
        assert by_name[name]["workloads"].count(CELL) == 1     # among them, wherever later cells stand
    for name in NOT_JOINED:
        assert CELL not in by_name[name]["workloads"]


def test_the_configuration_keeps_every_published_number_but_the_depth_and_the_two_shares(files):
    _, _, config, _ = files
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert config["published"]["num_hidden_layers"] == 27 and config["published"]["n_routed_experts"] == 64
    assert config["published"]["vocab_size"] == 163840 == 8 * config["vocab_size"]
    assert (config["num_hidden_layers"], config["n_routed_experts"], config["experts_routed"], config["experts_held"]) == \
        (6, 8, 64, [0, 8])
    for key in ("router_bias_rate", "rope_pairing", "norm_placement"):
        assert key in config["assumed"]
    assert {"optimizer", "aux_loss"} <= set(config["departures"])
    assert "eight" in config["deployment"] and "first of five" in config["deployment"]
    run = config["run"]
    assert config["rehearsal"] and config["sizing"] and run["correctness"]["why"]
    assert (run["seq_len"], run["attention"], run["dtype"], run["param_dtype"], run["scan_layers"]) == \
        (8192, "flash", "bfloat16", "float32", False)
    assert run["batch"] in (1, 2) and run["remat"] in ("dots", "full") and run["router_bias_rate"] == 0.001
    assert set(run["correctness"]["grad_rel_tol"]) >= {"router", "lat_wq", "lat_wkva", "we1", "we2", "shared", "head", "other"}
    # ISSUE 51's count: mixer 13.76M, layer 0 83.0M, an expert 8.65M, a layer here 100.4M, this chip 668.9M, whole 15.96B
    assert moonlight.mixer_params(config) == pytest.approx(13.76e6, rel=1e-3)
    assert moonlight.expert_params(config) == 3 * 2048 * 1408
    assert moonlight.n_params(config) == pytest.approx(668.9e6, rel=1e-4)
    whole = {**config, **{k: config["published"][k] for k in config["reduced"]}, "experts_held": [0, 64]}
    assert moonlight.n_params(whole) == pytest.approx(15.96e9, rel=1e-3)
    catalog = os.path.join(os.sep, "opt", "skills", "guides", "model-configs", "architectures.jsonl")
    if not os.path.isfile(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Moonlight-16B-A3B")
    assert config["source"] == row["source_url"]
    assert sorted(k for k, v in row["config"].items() if config.get(k, "absent") != v) == sorted(config["reduced"])


def test_the_program_config_is_an_all_latent_stack_that_rotates_and_holds_a_share(files):
    _, _, config, _ = files
    cfg = moonlight.program_config(config, max_seq_len=8192, dtype="bfloat16", param_dtype="float32", attention="flash")
    assert not cfg.hybrid and cfg.layer_types == ("latent",) * 6 and cfg.rope_full_layers and cfg.latent_layers == 6
    assert (cfg.latent_rank, cfg.latent_nope_dim, cfg.latent_rope_dim, cfg.latent_value_dim) == (512, 128, 64, 128)
    assert (cfg.num_experts, cfg.experts_held, cfg.experts_here, cfg.expert_top_k, cfg.num_dense_layers) == (64, (0, 8), 8, 6, 1)
    assert (cfg.router_score, cfg.route_norm, cfg.route_scale, cfg.router_bias, cfg.num_shared_experts) == ("sigmoid", True, 2.446, True, 2)
    assert (cfg.expert_width, cfg.d_ff, cfg.rope_theta, cfg.norm_eps, cfg.tie_embeddings, cfg.embed_scale) == \
        (1408, 11264, 50000.0, 1e-5, False, 1.0)
    for bad, named in (({"q_lora_rank": 1536}, "q_lora_rank"), ({"n_group": 8}, "expert groups"),
                       ({"experts_held": [0, 4]}, "experts_held"), ({"topk_method": "greedy"}, "noaux_tc"),
                       ({"rope_scaling": {"type": "yarn"}}, "rope_scaling")):
        with pytest.raises(ValueError, match=named):
            moonlight.program_config({**config, **bad})
    toy = moonlight.program_config(system.shrink_for_rehearsal(config), dtype="float32", param_dtype="float32")
    assert (toy.n_layers, toy.experts_held, toy.num_experts, toy.expert_top_k, toy.latent_rank) == (4, (4, 8), 16, 3, 32)


def test_the_flop_counts_by_hand(files):
    _, _, config, _ = files
    T = 8192
    # one layer's causal attention: of T*T/2 visible pairs a head, a 192-product and a 128-product forward, twice that backward
    assert moonlight.latent_flash_flops(config, T) == 3 * 2 * (192 + 128) * 16 * T * T / 2 == pytest.approx(1.0308e12, rel=1e-3)
    assert moonlight.latent_flash_flops(config, T, 2) == 2 * moonlight.latent_flash_flops(config, T)
    # the grouped products: three projections x (forward + two backward products) x 2 x 2048 x 1408 a row
    assert moonlight.routed_ffn_flops(config, 1) == 9 * 2 * 2048 * 1408
    assert moonlight.routed_ffn_flops(config, 6144) == pytest.approx(3.189e11, rel=1e-3)
    # a token: 6 x (layer 0: mixer + dense FFN; 5 x (mixer + router + 2 shared + 0.75 routed experts); the head) + attention
    mixer, expert = moonlight.mixer_params(config), moonlight.expert_params(config)
    matmul = (mixer + 3 * 2048 * 11264) + 5 * (mixer + 2048 * 64 + 2.75 * expert) + 20480 * 2048
    assert moonlight.train_flops_per_token(config, T) == pytest.approx(6 * matmul + 6 * 3 * 2 * 320 * 16 * T / 2, rel=1e-9)
    assert 8192 * moonlight.train_flops_per_token(config, T) == pytest.approx(21.59e12, rel=0.001)   # a step: 15.4 TF of products, 6.2 of attention


# ---------------------------------------------------------------------------
# the readers: a number on a recorded run, None where there is nothing to read
# ---------------------------------------------------------------------------
PLANE = "/device:TPU:0"
# names as the chip's trace gave them (my chip runs, PR 51)
FLASH_FWD = "latent_flash_attention.18 custom-call (bf16[16,8192,128], f32[16,1,8192])"
FLASH_REFWD = "jvp_latent_flash_attention_.6 custom-call (bf16[16,8192,128], f32[16,1,8192])"
FLASH_DQ = "latent_flash_attention.19 custom-call bf16[16,8192,192]"
FLASH_DKV = "latent_flash_attention.20 custom-call (bf16[16,8192,192], bf16[16,8192,128])"
GROUPED = "grouped_matmul.24 custom-call bf16[12288,1408]"
GROUPED_DOWN = "jvp_grouped_matmul_.17 custom-call bf16[12288,2048]"
GROUPED_BWD = "grouped_matmul_bwd.3 custom-call bf16[12288,2048]"
RAGGED_DW = "ragged-dot-none.14 custom-call bf16[8,2048,1408]"
OTHERS = ("fusion.44 fusion bf16[12288,2048]", "fusion.100 fusion (f32[5,8,2048,1408], f32[5,8,2048,1408], f32[5,8,2048,1408])",
          "fusion.1901 fusion (bf16[8192], bf16[8192,20480])")


def _events(steps=2):
    ev, t = [], 0
    for _ in range(steps):
        ev.append([PLANE, "XLA Modules", "jit_train_step(99)", t, 10_000])
        for name, dur in ((FLASH_FWD, 1000), (GROUPED, 300), (GROUPED_DOWN, 200), (OTHERS[0], 500), (FLASH_REFWD, 1000),
                          (FLASH_DQ, 1200), (FLASH_DKV, 1300), (GROUPED_BWD, 400), (RAGGED_DW, 600), (OTHERS[1], 2000),
                          (OTHERS[2], 1500)):
            ev.append([PLANE, "XLA Ops", name, t, dur])
            t += dur
    return ev


def _run(config, events, load=None, steps=4):
    return {"ctx": types.SimpleNamespace(config=config), "events": events, "peak": {"bf16_flops": 197e12},
            "expert_load_window": load, "expert_load_steps": steps}


def _reader(name):
    return runner.load_reader(name, ("benchmark",))


def test_the_four_readers_on_a_recorded_run(files):
    _, _, config, _ = files
    load = np.zeros((5, 64), np.int64)
    load[:, :8] = [[3000, 3100, 2900, 3000, 3200, 2800, 3000, 3000]] * 5   # four steps: 750 rows a held expert a step
    load[2, 3] = 4500
    run = _run(config, _events(), load)
    grouped_ns, flash_ns, whole_ns = 300 + 200 + 400 + 600, 1000 + 1000 + 1200 + 1300, 10_000
    assert _reader("routed_ffn_time_share.train")(run) == pytest.approx(100 * grouped_ns / whole_ns)
    rows = load[:, :8].sum() / 4
    assert readers_routed.held_rows_per_step(run) == rows
    want = 100 * moonlight.routed_ffn_flops(config, rows) / (grouped_ns * 1e-9) / 197e12
    assert _reader("routed_ffn_mxu_share.train")(run) == pytest.approx(want)
    want = 100 * 6 * moonlight.latent_flash_flops(config, 8192) / (flash_ns * 1e-9) / 197e12
    assert _reader("latent_flash_mxu_share.train")(run) == pytest.approx(want)
    assert _reader("held_expert_load_max_over_mean.train")(run) == pytest.approx(4500 / load[2, :8].mean())
    # told by shape too, whatever the kernels are called
    match = readers_routed.grouped_product(config)
    assert match("custom-call.7 custom-call bf16[49152,1408]") and match("custom-call.8 custom-call bf16[8,1408,2048]")
    assert not match(OTHERS[0]) and not match(FLASH_DQ) and not match("custom-call.9 custom-call bf16[16,8192,128]")
    flash = readers_routed.flash_kernel(config)
    assert all(flash(n) for n in (FLASH_FWD, FLASH_REFWD, FLASH_DQ, FLASH_DKV)) and not flash(GROUPED) and not flash(RAGGED_DW)


def test_the_readers_return_none_where_there_is_nothing_to_read(files):
    _, _, config, _ = files
    names = list(NEW)
    for run in (_run(config, []), _run({"model": "smollm2", "run": {"seq_len": 2048, "batch": 4}}, _events(), None),
                {"events": _events()}):
        for name in names:
            assert _reader(name)(run) is None, name
    # a trace without the counter: the time share reads, the shares that need rows do not
    run = _run(config, _events(), None)
    assert _reader("routed_ffn_time_share.train")(run) is not None and _reader("latent_flash_mxu_share.train")(run) is not None
    assert _reader("routed_ffn_mxu_share.train")(run) is None and _reader("held_expert_load_max_over_mean.train")(run) is None


def test_the_load_rules_check_counts_what_is_off(files):
    _, _, config, _ = files
    bias = np.zeros((5, 64), np.float32)
    load = np.full((5, 64), 768, np.int64)
    load[0, 0], load[0, 1] = 800, 736
    after = moonlight.bias_step(bias, load, 0.001)
    assert after[0, 0] == np.float32(-0.001) and after[0, 1] == np.float32(0.001) and not after[1:].any()
    assert routed_train_steps.load_rule_faults(moonlight, bias, after, load, 8192, 6, 0.001) == (0, 0)
    wrong = after.copy()
    wrong[0, 0] = 0.001                                   # moved against its load
    wrong[3, 5] = 0.0005                                  # moved by something else (a decayed or trained bias)
    assert routed_train_steps.load_rule_faults(moonlight, bias, wrong, load, 8192, 6, 0.001) == (2, 0)
    load[4, 9] += 1
    assert routed_train_steps.load_rule_faults(moonlight, bias, moonlight.bias_step(bias, load, 0.001), load, 8192, 6, 0.001) == (0, 1)


# ---------------------------------------------------------------------------
# the comparison that decides ``correct``: the stated program passes it, each control in its place does not
# ---------------------------------------------------------------------------
def test_the_warm_up_runs_step_n_at_n_over_warmup_steps_of_the_peak(files):
    _, _, config, _ = files
    run = config["run"]
    assert (run["learning_rate"], run["warmup_steps"]) == (3e-4, 2000)      # ISSUE 51's rate is the peak
    schedule = routed_train_steps.learning_rate(run)
    for step in (1, 2, 125, 2000, 5000):
        assert float(schedule(step - 1)) == pytest.approx(moonlight.warmup_rate(run, step), rel=1e-4)
    assert moonlight.warmup_rate(run, 1) == pytest.approx(1.5e-7) and moonlight.warmup_rate(run, 2000) == 3e-4
    assert routed_train_steps.learning_rate({"learning_rate": 1e-3}) == 1e-3


def test_the_references_adamw_step_is_the_optimizers_first_update():
    import jax
    import jax.numpy as jnp
    import optax

    p = jax.random.normal(jax.random.key(0), (64, 32)) * 0.02
    g = jax.random.normal(jax.random.key(1), (64, 32)) * 1e-6
    g = g.at[:8].set(0.0)                               # rows no token touched: only the decay moves them
    opt = optax.adamw(2e-3)
    updates, _ = opt.update(g, opt.init(p), p)
    want = optax.apply_updates(p, updates)
    got = moonlight.adamw_first_step(p, g, 2e-3)
    assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want - p)) < 1e-4
    assert float(jnp.linalg.norm(got - p)) > 0


def test_swaps_are_half_the_counts_difference():
    a = np.array([[5, 5, 5, 5], [3, 7, 5, 5]])
    b = np.array([[5, 6, 4, 5], [3, 7, 5, 5]])
    assert routed_train_steps.swaps(a, a) == 0 and routed_train_steps.swaps(a, b) == 1


CONTROL_FAILS = {   # the control in the program's place: the comparison of the kind that has to refuse it
    None: (),
    "reference:bf16_params": ("f32.grad_rel_err.",),
    "reference:no_rotation": ("f32.grad_rel_err.", "f32.loss_rel_err"),
    "unchanged_state": ("param_change_rel_err",),
    "bf16_state": ("param_change_rel_err",),
}


@pytest.mark.parametrize("variant", list(CONTROL_FAILS), ids=lambda v: v or "stated")
def test_each_control_in_the_programs_place_is_refused_by_a_limit_of_the_kind(files, variant):
    _, _, config, traffic = files
    held = routed_train_steps.held_before_the_window(system.shrink_for_rehearsal(config), traffic, 3, lambda m: None, variant)
    assert bool(held["reasons"]) == (variant is not None), held["reasons"]
    for named in CONTROL_FAILS[variant]:
        assert any(r.startswith(named) for r in held["reasons"]), (named, held["reasons"])
    if variant in ("unchanged_state", "bf16_state"):
        assert held["compared"]["param_change_rel_err"][0] > 0.9      # a state left as it was reads 1
    if variant == "unchanged_state":                                  # ... and nothing else is out
        assert len(held["reasons"]) == 1


# ---------------------------------------------------------------------------
# the whole command at toy size
# ---------------------------------------------------------------------------
def test_the_rehearsal_walks_the_cell_on_the_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELL, "--seed", "3000000011",
                          "--seconds", "3", "--trace", "1", "--rehearsal"], capture_output=True, text=True, env=env,
                         cwd=ROOT, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal_host_only"] and line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    compared = line["compared"]
    groups = ("router", "lat_wq", "lat_wkva", "we1", "we2", "we3", "shared", "head", "other")
    for key in ("first_loss_rel_err", "f32.loss_rel_err", "param_change_rel_err") + tuple(
            f"{p}grad_rel_err.{g}" for g in groups for p in ("", "f32.")):
        assert key in compared and compared[key]["value"] < compared[key]["limit"]
        assert compared[key]["value"] < 1e-3           # float32 at toy size: the reference's function, not near it
    assert compared["f32.routing_swaps"]["value"] == 0
    for key in ("bias_off_the_rule", "layers_whose_counts_do_not_sum", "nonfinite_losses"):
        assert compared[key] == {"value": 0, "limit": 0}
    assert {"step_ms", "mfu", "compiles_in_window.train", "held_expert_load_max_over_mean.train"} <= set(line["metric_names"])
