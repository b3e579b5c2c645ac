"""The benchmark's pure parts, on the CPU: traffic, arithmetic, the trace
reduction, the manifest, the reference. No TPU topology is described here,
at import or later; the command itself is walked once at toy sizes through
its builder-only rehearsal."""

from __future__ import annotations

import copy
import json
import os
import statistics
import subprocess
import sys
from collections import Counter

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest, stratify, trace_reduce, yardstick  # noqa: E402
from benchmark.kinds import open_loop_requests, sessions  # noqa: E402

MANIFEST = manifest.load(os.path.join(ROOT, "BENCHMARK.json"))
SECONDS = MANIFEST["run_seconds"]
SEEDS = (1, 2, 3_000_000_019)


def _traffic(name):
    with open(os.path.join(ROOT, "benchmark", "traffic", name + ".json")) as f:
        return json.load(f)


CHAT = _traffic("chat-steady")
AGENT = _traffic("agent-prefix")


# ---------------------------------------------------------------------------
# traffic: one fixed schedule, fixed count, stratified lengths, 8 a block
# ---------------------------------------------------------------------------
def _realisation(monkeypatch, kind, traffic, schedule_seed):
    """Another realisation of the same stated process than the committed one."""
    monkeypatch.setattr(stratify, "SCHEDULE_SEED", schedule_seed)
    return kind.schedule(traffic, SECONDS)


def test_open_loop_schedule_is_the_same_in_every_run(monkeypatch):
    committed = open_loop_requests.schedule(CHAT, SECONDS)
    assert committed == open_loop_requests.schedule(CHAT, SECONDS)
    assert "schedule_seed" not in CHAT and "burst" not in CHAT, "the realisation is a constant, not an option"
    assert _realisation(monkeypatch, open_loop_requests, CHAT, 1) != committed


def test_open_loop_window_holds_the_fixed_count_after_a_ramp():
    plan = open_loop_requests.schedule(CHAT, SECONDS)
    scored = [r for r in plan if r["scored"]]
    ramp = [r for r in plan if not r["scored"]]
    assert len(scored) == round(CHAT["rate"] * SECONDS)
    assert len(ramp) == round(CHAT["rate"] * CHAT["ramp_s"])
    assert all(0 <= r["due"] < SECONDS for r in scored)
    assert all(-CHAT["ramp_s"] <= r["due"] < 0 for r in ramp), "the ramp comes before the window"


@pytest.mark.parametrize("field,dist", [("prompt_len", "prompt_tokens"), ("max_tokens", "output_tokens")])
@pytest.mark.parametrize("schedule_seed", [1, 2])
def test_open_loop_every_realisation_offers_the_same_multiset_of_lengths(field, dist, schedule_seed, monkeypatch):
    """Whatever orders them, the lengths are the distribution's own quantiles."""
    a = open_loop_requests.schedule(CHAT, SECONDS)
    b = _realisation(monkeypatch, open_loop_requests, CHAT, schedule_seed)
    for scored in (True, False):
        ca = Counter(r[field] for r in a if r["scored"] == scored)
        cb = Counter(r[field] for r in b if r["scored"] == scored)
        assert ca == cb
        assert sorted(ca.elements()) == stratify.stratified_sizes(CHAT[dist], sum(ca.values()))
    assert [r[field] for r in a] != [r[field] for r in b]


@pytest.mark.parametrize("seed", SEEDS)
def test_the_seed_decides_the_tokens_and_nothing_else(seed):
    import numpy as np

    a = np.random.default_rng([seed, 2]).integers(1, 49152, size=64)
    b = np.random.default_rng([seed, 2]).integers(1, 49152, size=64)
    c = np.random.default_rng([seed + 1, 2]).integers(1, 49152, size=64)
    assert (a == b).all() and not (a == c).all()


@pytest.mark.parametrize("schedule_seed", [0, 1, 2])
def test_open_loop_blocks_hold_exactly_eight_arrivals(schedule_seed, monkeypatch):
    plan = [r for r in _realisation(monkeypatch, open_loop_requests, CHAT, schedule_seed) if r["scored"]]
    block_s = stratify.BLOCK / CHAT["rate"]
    per_block = Counter(int(r["due"] // block_s) for r in plan)
    full_blocks = len(plan) // stratify.BLOCK
    assert all(per_block[k] == stratify.BLOCK for k in range(full_blocks))
    assert sum(per_block.values()) == len(plan)


def test_open_loop_lengths_follow_the_stated_distribution():
    plan = [r for r in open_loop_requests.schedule(CHAT, SECONDS) if r["scored"]]
    prompts = sorted(r["prompt_len"] for r in plan)
    d = CHAT["prompt_tokens"]
    assert d["lo"] <= prompts[0] and prompts[-1] <= d["hi"]
    assert abs(statistics.median(prompts) - d["median"]) <= 0.05 * d["median"]
    outs = [r["max_tokens"] for r in plan]
    assert CHAT["output_tokens"]["lo"] <= min(outs) and max(outs) <= CHAT["output_tokens"]["hi"]


def test_every_scored_request_is_long_enough_for_its_first_sixteen_tokens():
    from benchmark import serving

    assert min(r["max_tokens"] for r in open_loop_requests.schedule(CHAT, SECONDS)) >= serving.FIRST_K
    assert min(n for s in sessions.schedule(AGENT, SECONDS) for n in s["output_tokens"]) >= serving.FIRST_K


def test_sessions_schedule_is_the_same_in_every_run_and_counts_are_fixed():
    a, b = sessions.schedule(AGENT, SECONDS), sessions.schedule(AGENT, SECONDS)
    assert a == b
    inside = [s for s in a if s["in_window"]]
    assert len(inside) == round(AGENT["rate"] * SECONDS)
    assert all(s["start"] < 0 for s in a if not s["in_window"]), "the ramp comes before the window"
    assert all(len(s["new_tokens"]) == AGENT["turns"] and len(s["think_s"]) == AGENT["turns"] - 1 for s in a)


@pytest.mark.parametrize("field,dist,per", [
    ("new_tokens", "new_tokens", 0), ("output_tokens", "output_tokens", 0), ("think_s", "think_s", 1)])
def test_sessions_every_realisation_offers_the_same_multisets(field, dist, per, monkeypatch):
    a = sessions.schedule(AGENT, SECONDS)
    b = _realisation(monkeypatch, sessions, AGENT, 3)
    flat = lambda plan: Counter(x for s in plan if s["in_window"] for x in s[field])  # noqa: E731
    assert flat(a) == flat(b)
    n = round(AGENT["rate"] * SECONDS) * (AGENT["turns"] - per)
    want = stratify.stratified_sizes({**AGENT[dist], "round": field != "think_s"}, n)
    assert sorted(flat(a).elements()) == pytest.approx(want)


def test_sessions_agents_go_round_robin_over_arrival_order():
    plan = sessions.schedule(AGENT, SECONDS)
    assert [s["agent"] for s in plan] == [i % AGENT["agents"] for i in range(len(plan))]
    assert [s["start"] for s in plan] == sorted(s["start"] for s in plan)


def test_sessions_longest_context_fits_the_engine():
    cfg = json.load(open(os.path.join(ROOT, "benchmark/configs/smollm2-1.7b-serve.json")))
    longest = AGENT["system_prompt_tokens"] + AGENT["turns"] * (AGENT["new_tokens"]["hi"] + AGENT["output_tokens"]["hi"])
    assert longest <= cfg["run"]["max_seq_len"]
    chat_longest = CHAT["prompt_tokens"]["hi"] + CHAT["output_tokens"]["hi"]
    assert chat_longest <= cfg["run"]["max_seq_len"]


@pytest.mark.parametrize("n", [1, 7, 8, 9, 153])
def test_stratified_arrivals_count_and_order(n):
    import numpy as np

    times = stratify.stratified_arrivals(np.random.default_rng(0), n, 3.0, -2.0)
    assert len(times) == n and all(-2.0 <= t <= -2.0 + n / 3.0 + 1e-9 for t in times)


@pytest.mark.parametrize("dist", [
    {"dist": "lognormal", "median": 100, "sigma": 0.5, "lo": 10, "hi": 1000},
    {"dist": "uniform", "lo": 64, "hi": 256},
])
def test_stratified_sizes_are_the_inverse_cdf_at_even_quantiles(dist):
    xs = stratify.stratified_sizes(dist, 101)
    assert xs == sorted(xs) and len(xs) == 101
    mid = dist.get("median", (dist["lo"] + dist["hi"]) / 2)
    assert xs[50] == round(mid)


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4], 0.5, 2.5),
    ([1, 2, 3, 4, 5], 0.9, 4.6),
    ([10], 0.99, 10),
    ([3, 1, 2], 0.0, 1),
    ([3, 1, 2], 1.0, 3),
    (list(range(101)), 0.99, 99.0),
])
def test_quantile_is_linear_interpolated(values, q, want):
    assert yardstick.quantile(values, q) == pytest.approx(want)
    import numpy as np

    assert yardstick.quantile(values, q) == pytest.approx(float(np.quantile(values, q)))


def test_quantile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        yardstick.quantile([], 0.5)


def test_iqr_share_is_the_drivers_spread():
    xs = [100, 101, 102, 103, 104, 105]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert yardstick.iqr_share(xs) == pytest.approx((q3 - q1) / statistics.median(xs))


@pytest.mark.parametrize("steps,window,want_rate,want_n", [
    ([(0.0, 1.0), (1.0, 2.0), (2.0, 3.0)], (0.0, 3.0), 100.0, 3),
    ([(0.0, 1.0), (1.0, 2.0), (2.0, 3.5)], (0.0, 3.0), 100.0, 2),  # the cut step does not count
    ([(-0.5, 0.5), (0.5, 1.5), (1.5, 2.5)], (0.0, 3.0), 100.0, 2),  # nor one begun before the window
    ([(0.0, 4.0)], (0.0, 3.0), None, 0),
    ([(0.1, 0.6), (0.6, 1.1)], (0.0, 3.0), 200.0, 2),  # over the steps' own time, not the window's length
])
def test_tokens_per_s_is_over_whole_steps(steps, window, want_rate, want_n):
    rate, n = yardstick.whole_steps_rate(steps, window, 100)
    assert n == want_n
    assert rate == (None if want_rate is None else pytest.approx(want_rate))


def test_gaps_count_where_they_end():
    assert yardstick.gaps_ending_in([0.5, 1.5, 2.5, 3.5], (1.0, 3.0)) == [1.0, 1.0]


def test_mfu_and_flops_per_token():
    from benchmark.models import smollm2

    cfg = json.load(open(os.path.join(ROOT, "benchmark/configs/smollm2-1.7b-train-l8.json")))
    assert smollm2.n_params(cfg) == 8 * (4 * 2048 * 2048 + 3 * 2048 * 8192 + 2 * 2048) + 49152 * 2048 + 2048
    full = json.load(open(os.path.join(ROOT, "benchmark/configs/smollm2-1.7b-serve.json")))
    assert 1.70e9 < smollm2.n_params(full) < 1.72e9, "SmolLM2-1.7B has 1.71B parameters"
    f = smollm2.train_flops_per_token(cfg, 2048)
    assert f == pytest.approx(6 * (smollm2.n_params(cfg) - 17 * 2048) + 6 * 8 * 2048 * 2048)
    # the ledger's PR 23 reading: 24 791 tokens/s was an MFU of 0.507
    assert yardstick.mfu_percent(f, 24791, 1, 197e12) == pytest.approx(50.7, abs=0.1)


@pytest.mark.parametrize("kind,ok", [("TPU v5 lite", True), ("TPU v5e", True), ("cpu", False), ("TPU v9", False)])
def test_peaks_table_knows_the_chip_and_refuses_the_rest(kind, ok):
    if ok:
        assert yardstick.peaks(kind)["bf16_flops"] == 197e12
        assert yardstick.peaks(kind)["hbm_bytes_per_s"] == 819e9
    else:
        with pytest.raises(LookupError):
            yardstick.peaks(kind)


# ---------------------------------------------------------------------------
# the trace reduction
# ---------------------------------------------------------------------------
P0 = "/device:TPU:0"
TINY = [  # [plane, line, name, start_ns, dur_ns]
    [P0, "XLA Modules", "jit_a(1)", 0, 100],
    [P0, "XLA Modules", "jit_b(2)", 150, 100],
    [P0, "XLA Modules", "jit_a(1)", 300, 100],
    [P0, "XLA Ops", "fusion.1", 0, 40],
    [P0, "XLA Ops", "custom-call.2", 50, 50],
    [P0, "XLA Ops", "all-reduce.3", 150, 60],
    [P0, "XLA Ops", "fusion.4", 180, 70],
    [P0, "XLA Ops", "fusion.1", 300, 100],
    [P0, "XLA Ops", "while.9 while (s32[], bf16[2])", 0, 400],  # control flow: its time is its children's
    [P0, "Async XLA Ops", "all-reduce-start.5 all-reduce-start f32[8]", 260, 60],  # in flight beside the core
    ["/host:CPU", "python", "noise", 0, 1000],
]


def test_busy_is_the_union_of_operation_intervals():
    assert trace_reduce.busy(TINY, P0) == [(0, 40), (50, 100), (150, 250), (300, 400)]
    assert trace_reduce.window_of(TINY) == (0, 400)
    busy_s, window_s = trace_reduce.busy_and_window_s(TINY)
    assert busy_s == pytest.approx(290e-9) and window_s == pytest.approx(400e-9)
    assert trace_reduce.idle_share(TINY) == pytest.approx(1 - 290 / 400)


def test_program_time_by_name():
    assert trace_reduce.program_runs(TINY, P0) == {"jit_a": [100, 100], "jit_b": [100]}
    assert trace_reduce.program_name("jit__decode_k_paged(7a9f)") == "jit__decode_k_paged"


def test_kernel_share_inside_a_program():
    share = trace_reduce.time_share(TINY, P0, "jit_a", trace_reduce.is_custom_call)
    assert share == pytest.approx(50 / 190)
    assert trace_reduce.time_share(TINY, P0, "jit_missing", trace_reduce.is_custom_call) is None


def test_gaps_are_named_by_what_surrounds_them():
    gaps = dict(trace_reduce.idle_gaps(TINY, P0))
    assert gaps == {"inside jit_a": pytest.approx(10e-9), "jit_a -> jit_b": pytest.approx(50e-9),
                    "jit_b -> jit_a": pytest.approx(50e-9)}
    assert sum(gaps.values()) == pytest.approx(110e-9)


def test_collective_and_exposed_time():
    coll, exposed = trace_reduce.collective_and_exposed_s(TINY, P0)
    # [150, 210) on the core (30 of it beside fusion.4) and [260, 320) in flight (40 of it beside nothing)
    assert coll == pytest.approx(120e-9) and exposed == pytest.approx(70e-9)


@pytest.mark.parametrize("name,is_coll", [
    ("all-reduce.3", True), ("all-reduce-start.1", True), ("collective-permute-done.7", True),
    ("reduce-scatter", True), ("fusion.12", False), ("copy.3", False),
    ("collective-permute-start.2 collective-permute-start (bf16[1,16])", True),
    ("copy.61 copy bf16[1,1536,16,32,64]", False),
])
def test_collective_names(name, is_coll):
    assert trace_reduce.is_collective(name) == is_coll


def test_hlo_text_is_cut_to_instruction_opcode_shape():
    text = "%copy.61 = bf16[1,1536,16,32,64]{1,4,3,2,0:T(8,128)(2,1)} copy(bf16[1,1536,16,32,64]{4,3,2,1,0} %x)"
    assert trace_reduce.short_name(text) == "copy.61 copy bf16[1,1536,16,32,64]"
    assert trace_reduce.opcode(trace_reduce.short_name(text)) == "copy"
    assert trace_reduce.short_name("%w = (s32[]{:T(128)}, bf16[2]{0}) while((s32[], bf16[2]) %t), body=%b") == \
        "w while (s32[], bf16[2])"
    assert trace_reduce.is_custom_call("closed_call.14 custom-call bf16[64,32,8,64]")


def test_top_ops_rank_by_total_time():
    assert trace_reduce.top_ops(TINY, P0, 2) == [["fusion.1", pytest.approx(140e-9)], ["fusion.4", pytest.approx(70e-9)]]


@pytest.mark.parametrize("a,b,want", [
    ([(0, 10)], [(2, 4), (6, 12)], [(0, 2), (4, 6)]),
    ([(0, 10), (20, 30)], [], [(0, 10), (20, 30)]),
    ([(0, 10)], [(0, 10)], []),
    ([(5, 6)], [(0, 10)], []),
])
def test_interval_subtraction(a, b, want):
    assert trace_reduce.subtract(a, b) == want


def _recorded():
    with open(os.path.join(ROOT, "benchmark", "testdata", "recorded_trace.json")) as f:
        return json.load(f)


def test_recorded_trace_reduces():
    """A slice of a real profiler trace of the chat cell on a TPU v5 lite,
    recorded by this benchmark (PR 24): the reduction finds the engine's
    programs by name, and its parts add up."""
    rec = _recorded()
    events = rec["events"]
    planes = trace_reduce.device_planes(events)
    assert planes == ["/device:TPU:0"]
    runs = trace_reduce.program_runs(events, planes[0])
    assert "jit__decode_k_paged" in runs
    busy_s, window_s = trace_reduce.busy_and_window_s(events)
    assert 0 < busy_s <= window_s
    gaps = trace_reduce.idle_gaps(events, planes[0], n=10**6)
    assert sum(s for _, s in gaps) == pytest.approx(window_s - busy_s, rel=1e-9)
    assert busy_s == pytest.approx(rec["expected"]["busy_s"], rel=1e-12)
    assert statistics.median(runs["jit__decode_k_paged"]) == rec["expected"]["decode_median_ns"]
    share = trace_reduce.time_share(events, planes[0], "jit__decode_k_paged", trace_reduce.is_custom_call)
    assert 0 < share < 1
    bd = trace_reduce.breakdown(events)
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


# ---------------------------------------------------------------------------
# the manifest
# ---------------------------------------------------------------------------
def test_the_manifest_is_sound_and_its_files_exist():
    assert manifest.problems(MANIFEST, ROOT) == []


def test_the_manifest_names_what_the_issue_names():
    e2e = {x["name"] for x in MANIFEST["end_to_end"]}
    assert {"itl_p99_ms", "tokens_per_s", "setup_s"} <= e2e
    assert {"smollm2-1.7b-serve", "smollm2-1.7b-train-l8", "smollm2-1.7b-train-ring4"} <= {
        c["name"] for c in MANIFEST["configs"]}, "a later PR adds configurations; it takes none away"


def _broken(edit):
    m = copy.deepcopy(MANIFEST)
    edit(m)
    return manifest.problems(m)


def _set(path, value):
    def edit(m):
        node = m
        for k in path[:-1]:
            node = node[k]
        node[path[-1]] = value
    return edit


@pytest.mark.parametrize("edit,needle", [
    (_set(("workloads", 0, "name"), "has space"), "workload name"),
    (_set(("workloads", 0, "name"), "x" * 65), "workload name"),
    (_set(("end_to_end", 0, "unit"), "tokens per second"), "unit"),
    (_set(("end_to_end", 0, "unit"), "µs"), "unit"),
    (_set(("end_to_end", 0, "bound"), 0.2), "bound"),
    (_set(("end_to_end", 0, "better"), "faster"), "better"),
    (_set(("end_to_end", 0, "source"), "program_counter"), "source is host_clock or device_trace"),
    (_set(("per_layer", 0, "moves"), "nothing"), "not an end-to-end metric"),
    (_set(("per_layer", 0, "moves"), "tokens_per_s"), "which does not report"),
    (_set(("per_layer", 0, "why"), "no such key"), "keys must be"),
    (_set(("run_seconds",), 52), "run_seconds"),
    (_set(("run_seconds",), 10.5), "run_seconds"),
    (_set(("workloads", 0, "chips"), 2), "chips is 1 or 4"),
    (_set(("workloads", 0, "config"), "unknown"), "unknown config"),
    (_set(("configs", 0, "file"), "ray_tpu/x.json"), "not under paths"),
    (_set(("configs", 0, "file"), "benchmark/../x.json"), "not under paths"),
    (_set(("configs", 0, "reduced"), ["hidden_size"]), "may not name the width"),
    (_set(("configs", 0, "reduced"), ["kv_lora_rank"]), "may not name the width"),
    (_set(("command",), ["python3", "/abs/run.py"]), "leaves the repo"),
    (_set(("extra",), 1), "top-level keys"),
    (lambda m: m["end_to_end"].pop(), "setup_s"),
    (lambda m: m["workloads"].append(dict(m["workloads"][0], name="twin")), "appear twice"),
    (lambda m: [w.update(chips=4) for w in m["workloads"][:2]], "at most 25%"),
    (lambda m: m["end_to_end"].append(dict(m["end_to_end"][0])), "appears twice"),
])
def test_a_broken_manifest_is_refused(edit, needle):
    errs = _broken(edit)
    assert any(needle in e for e in errs), errs


@pytest.mark.parametrize("metric", [x["name"] for x in MANIFEST["per_layer"]])
def test_every_per_layer_metric_has_a_reader_that_returns_nothing_from_nothing(metric):
    from benchmark import run as runner

    read = runner.load_reader(metric, MANIFEST["paths"])

    class NoProbe:
        sampler = stats_open = stats_close = None

    empty = {"values": {}, "turns": [], "events": [], "steps": [], "window": (0.0, 1.0),
             "probe": NoProbe(), "compiles_in_window": 0, "chips": 1,
             "peak": {"bf16_flops": 197e12}, "flops_per_token": 1.0, "tokens_per_step": 1}
    got = read(empty)
    assert got is None or got == 0


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_every_cell_finds_its_files_by_name(cell):
    w = manifest.cell(MANIFEST, cell)
    cfg = json.load(open(os.path.join(ROOT, manifest.config_entry(MANIFEST, w["config"])["file"])))
    assert cfg["name"] == w["config"] and len(cfg["source"]) <= 200
    assert set(cfg["reduced"]) == set(manifest.config_entry(MANIFEST, w["config"])["reduced"])
    traffic = json.load(open(manifest.traffic_file(w["traffic"], ROOT)))
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "kinds", traffic["kind"] + ".py"))
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "models", cfg["model"] + ".py"))
    assert len(manifest.metrics_of(MANIFEST, "end_to_end", cell)) >= 2
    assert manifest.metrics_of(MANIFEST, "per_layer", cell)


# ---------------------------------------------------------------------------
# the served numbers and the verdict, from made-up client records
# ---------------------------------------------------------------------------
def _turn(due, first, gap, n, max_tokens=None, scored=True, late=0.001):
    from benchmark import serving

    t = serving.Turn(due, [1, 2, 3], max_tokens or n, scored)
    t.sent = due + late
    t.token_times = [due + first + gap * i for i in range(n)]
    t.tokens = [5] * n
    return t


def test_first16_is_the_time_from_due_to_the_sixteenth_token():
    from benchmark import serving

    turns = [_turn(10.0, 0.4, 0.1, 40), _turn(20.0, 0.2, 0.2, 16), _turn(1.0, 9.0, 9.0, 40, scored=False)]
    m = serving.serve_metrics(turns, (5.0, 60.0))
    assert m["first16_mean_ms"] == pytest.approx(((400 + 15 * 100) + (200 + 15 * 200)) / 2)
    assert m["ttft_mean_ms"] == pytest.approx(300.0)
    assert m["ttft_p50_ms"] == pytest.approx(300.0)
    assert m["itl_mean_ms"] == pytest.approx((39 * 100 + 15 * 200 + 5 * 9000) / 59)


GOOD = {"paged runner": {"ok": True}, "served path": {"ok": True}}


@pytest.mark.parametrize("lates_ms,p99", [
    ([1.0] * 65, 1.0),
    ([1.0] * 64 + [2000.0], 1.0 + 0.36 * 1999.0),  # one send stalled: it shows in the number, and only there
    ([25.0] * 65, 25.0),
    ([19.0] * 65, 19.0),
])
def test_the_generators_lateness_is_reported_and_decides_nothing(lates_ms, p99):
    from benchmark import serving

    turns = [_turn(float(i), 0.3, 0.1, 20, late=ms / 1e3) for i, ms in enumerate(lates_ms)]
    m = serving.serve_metrics(turns, (0.0, 100.0))
    assert m["loadgen_late_p99_ms"] == pytest.approx(p99)
    assert m["first16_mean_ms"] == pytest.approx(300.0 + 15 * 100.0)  # counted from due, so lateness is inside it
    v = serving.judge(turns, 100, GOOD)
    assert v["reasons"] == [] and v["attempted"] == len(turns) and v["failed"] == 0


@pytest.mark.parametrize("spoil,failed,why", [
    (lambda t: None, 0, None),
    (lambda t: setattr(t, "error", "Shed: queue full"), 1, "Shed"),
    (lambda t: (setattr(t, "token_times", t.token_times[:15]), setattr(t, "tokens", t.tokens[:15]),
                setattr(t, "cancelled", True)), 1, "no first 16 tokens"),
    (lambda t: (setattr(t, "token_times", t.token_times[:18]), setattr(t, "tokens", t.tokens[:18])), 1, "failed"),
    (lambda t: (setattr(t, "token_times", t.token_times[:18]), setattr(t, "tokens", t.tokens[:18]),
                setattr(t, "cancelled", True)), 0, None),  # cut by the end of the run after its first 16: scored
    (lambda t: setattr(t, "tokens", [101] * 20), 0, "outside the vocabulary"),
])
def test_what_counts_as_a_failed_request(spoil, failed, why):
    from benchmark import serving

    turns = [_turn(float(i), 0.3, 0.1, 20) for i in range(4)]
    spoil(turns[2])
    v = serving.judge(turns, 100, GOOD)
    assert v["failed"] == failed and v["attempted"] == 4
    assert bool(v["reasons"]) == (why is not None)
    if why:
        assert why in " ".join(v["reasons"])


@pytest.mark.parametrize("check", ["paged runner", "served path"])
def test_a_disagreement_with_the_reference_is_not_correct(check):
    from benchmark import serving

    bad = {**GOOD, check: {"ok": False, "worst_deficit_sd": 3.2}}
    v = serving.judge([_turn(0.0, 0.3, 0.1, 20)], 100, bad)
    assert v["failed"] == 0 and check in v["reasons"][0]


@pytest.mark.parametrize("raises", [None, RuntimeError, KeyboardInterrupt])
def test_one_window_is_measured_and_the_system_is_taken_down(monkeypatch, raises):
    from benchmark import serving

    class Served:
        closed = False

        def __init__(self, *a):
            pass

        def close(self):
            Served.closed = True

    monkeypatch.setattr(serving, "Served", Served)
    windows = []

    def drive(ctx, served, p, seconds):
        windows.append(seconds)
        if raises:
            raise raises("the window broke")
        return {"values": {"loadgen_late_p99_ms": 2000.0}}

    class Ctx:
        config = traffic = seed = None
        seconds = 1.0
        log = staticmethod(lambda msg: None)

    if raises:
        with pytest.raises(raises):
            serving.run_served(Ctx, drive)
    else:
        assert serving.run_served(Ctx, drive)["values"]["loadgen_late_p99_ms"] == 2000.0
    assert windows == [1.0] and Served.closed


# ---------------------------------------------------------------------------
# the reference against the program, tiny, float32, on the CPU
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny_model():
    import jax
    import jax.numpy as jnp

    from benchmark import system
    from ray_tpu.models.transformer import init_params

    config = system.shrink_for_rehearsal(system.load_json("benchmark/configs/smollm2-1.7b-train-l8.json"))
    model = system.model_module(config)
    cfg = model.program_config(config, max_seq_len=64, dtype=jnp.float32, param_dtype=jnp.float32,
                               attention="dense", scan_layers=False)
    params = init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 64), 0, cfg.vocab_size)
    return config, model, cfg, params, tokens


def test_reference_logits_agree_with_the_program(tiny_model):
    import jax
    import numpy as np

    from ray_tpu.models.transformer import forward

    config, model, cfg, params, tokens = tiny_model
    ref_logits, _ = model.make_reference(config)
    with jax.default_matmul_precision("highest"):
        got = forward(cfg, params, tokens)
    for b in range(tokens.shape[0]):
        np.testing.assert_allclose(np.asarray(got[b]), np.asarray(ref_logits(params, tokens[b])),
                                   rtol=2e-4, atol=2e-4)


def test_reference_loss_agrees_with_the_program(tiny_model):
    import jax

    from ray_tpu.models.transformer import loss_fn

    config, model, cfg, params, tokens = tiny_model
    _, ref_loss = model.make_reference(config)
    with jax.default_matmul_precision("highest"):
        got = float(loss_fn(cfg, params, tokens))
    assert ref_loss(params, tokens) == pytest.approx(got, rel=1e-5)


def test_reference_departures_are_what_the_file_says(tiny_model):
    """Without the two stated departures the reference computes the
    published model, which is not what the program computes."""
    import numpy as np

    config, model, cfg, params, tokens = tiny_model
    published = dict(config, departures={})
    a = model.make_reference(config)[0](params, tokens[0])
    b = model.make_reference(published)[0](params, tokens[0])
    assert not np.allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-3)


def test_paged_runner_check_passes_and_would_catch_a_wrong_model(tiny_model):
    import jax
    import jax.numpy as jnp

    from benchmark import serving, system
    from ray_tpu.models.transformer import init_params

    config = system.shrink_for_rehearsal(system.load_json("benchmark/configs/smollm2-1.7b-serve.json"))
    cfg = system.model_module(config).program_config(
        config, max_seq_len=config["run"]["max_seq_len"], dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    params = init_params(cfg, jax.random.key(3))
    good = serving.check_paged_against_reference(cfg, params, config, 3)
    assert good["ok"] and good["rel_err"] < 0.02
    wrong = dict(config, rope_theta=10000.0)  # the reference of another model
    bad = serving.check_paged_against_reference(cfg, params, wrong, 3)
    assert not bad["ok"]


class _GreedyServed:
    """Stands where ``serving.Served`` does: answers greedily from the
    reference itself, seeing the whole prompt or (a broken cache) another
    prefix in its place."""

    def __init__(self, config, params, break_prefix=False):
        from benchmark import system

        self.config, self.run, self.params, self.break_prefix = config, config["run"], params, break_prefix
        self.ref = system.model_module(config).make_reference(config)[0]
        self.vocab = config["vocab_size"]

    def stream(self, turn, stop, traced=False):
        import jax.numpy as jnp
        import numpy as np

        seen = list(turn.prompt)
        if self.break_prefix:  # the first half of the context is somebody else's
            half = len(seen) // 2
            seen[:half] = np.random.default_rng(9).integers(1, self.vocab, size=half).tolist()
        for _ in range(turn.max_tokens):
            lg = self.ref(self.params, jnp.asarray(seen), jnp.asarray([len(seen) - 1]))
            turn.tokens.append(int(np.argmax(np.asarray(lg[0]))))
            seen.append(turn.tokens[-1])


@pytest.fixture(scope="module")
def tiny_served():
    import jax
    import jax.numpy as jnp

    from benchmark import system

    config = system.shrink_for_rehearsal(system.load_json("benchmark/configs/smollm2-1.7b-serve.json"))
    cfg = system.model_module(config).program_config(
        config, max_seq_len=config["run"]["max_seq_len"], dtype=jnp.float32, param_dtype=jnp.float32)
    params = jax.block_until_ready(system.make_params(cfg, 3, config["run"]["weights"]["embed_table_scale"]))
    return config, cfg, params


def _conversations(vocab):
    import numpy as np

    rng = np.random.default_rng(4)
    return [[rng.integers(1, vocab, size=n).tolist() for n in pieces] for pieces in ((40,), (56, 9))]


def test_served_path_check_passes_what_the_reference_would_say(tiny_served):
    from benchmark import serving

    config, cfg, params = tiny_served
    got = serving.check_served_against_reference(_GreedyServed(config, params), _conversations(cfg.vocab_size))
    assert got["ok"] and got["worst_deficit_sd"] == 0.0
    assert got["tokens"] == 3 * config["run"]["correctness"]["served_tokens"]


def test_served_path_check_catches_a_prefix_that_is_not_the_requests_own(tiny_served):
    from benchmark import serving

    config, cfg, params = tiny_served
    got = serving.check_served_against_reference(_GreedyServed(config, params, break_prefix=True),
                                                 _conversations(cfg.vocab_size))
    assert not got["ok"] and got["worst_deficit_sd"] > 1.0, got


def test_as_initialised_the_model_copies_its_last_token_whatever_the_context(tiny_served):
    """Why the benchmark scales the embedding table: with the program's own
    initialisation the served tokens say nothing about the KV cache."""
    import jax
    import numpy as np

    from benchmark import serving, system

    config, cfg, _ = tiny_served
    params = jax.block_until_ready(system.make_params(cfg, 3))  # unscaled
    served = _GreedyServed(config, params, break_prefix=True)
    turn = serving.Turn(0.0, np.random.default_rng(5).integers(1, cfg.vocab_size, size=40).tolist(), 4, False)
    served.stream(turn, None)
    assert turn.tokens == [turn.prompt[-1]] * 4
    blind = serving.check_served_against_reference(served, _conversations(cfg.vocab_size))
    assert blind["ok"], "a broken prefix goes unseen when every token is a copy"


# ---------------------------------------------------------------------------
# the command
# ---------------------------------------------------------------------------
def _run(*args, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "run.py"), *args],
                          cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout)


def test_the_command_refuses_to_run_without_a_tpu():
    p = _run("--workload", "smollm2-1.7b-train-l8.steps", "--seed", "1", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == "", "no result line without a chip"
    assert "no accelerator" in p.stderr


@pytest.mark.parametrize("cell,trace", [
    ("smollm2-1.7b-serve.chat-steady", "0"),
    ("smollm2-1.7b-serve.agent-prefix", "1"),
    ("smollm2-1.7b-train-l8.steps", "1"),
])
def test_rehearsal_walks_the_whole_command_and_names_no_time(cell, trace):
    p = _run("--workload", cell, "--seed", str(2**31 + 11), "--seconds", "2", "--trace", trace, "--rehearsal")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["rehearsal_host_only"] is True and line["correct"] is True, p.stderr[-2000:]
    assert line["device"]["platform"] == "cpu"
    assert "metrics" not in line, "a rehearsal never prints a time under a metric's name"
    assert line["attempted"] > 0 and line["failed"] == 0
