"""The four per-layer metrics that read the engine loop's own clock (PR 42):
their manifest entries, their readers on ``stats()`` written by hand (the
parent's has neither key: a reader must leave its metric out, not raise),
and ``host_spans``, which puts the device's idle time down to the loop's
phases, on a recorded slice of a real v5e trace."""

from __future__ import annotations

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import host_spans, manifest, run as runner, trace_reduce  # noqa: E402

SERVE = ["smollm2-1.7b-serve.chat-steady", "smollm2-1.7b-serve.agent-prefix", "smollm2-1.7b-serve.chat-saturated",
         "trinity-mini-serve-l5.mixed-lengths", "sdar-30b-a3b-serve-l6.fixed-length-gen"]
BELOW_THE_KNEE = ["smollm2-1.7b-serve.chat-steady", "smollm2-1.7b-serve.agent-prefix", "trinity-mini-serve-l5.mixed-lengths"]
ENTRIES = [
    {"name": "loop_host_ms_per_step", "unit": "ms", "better": "lower", "source": "program_span",
     "layer": "admission and scheduler", "moves": "itl_mean_ms", "workloads": SERVE},
    {"name": "decode_dry_share", "unit": "share", "better": "lower", "source": "program_counter",
     "layer": "admission and scheduler", "moves": "itl_mean_ms", "workloads": SERVE},
    {"name": "admit_host_ms_per_request", "unit": "ms", "better": "lower", "source": "program_span",
     "layer": "admission and scheduler", "moves": "first16_mean_ms", "workloads": BELOW_THE_KNEE},
    {"name": "first_token_join_ms", "unit": "ms", "better": "lower", "source": "program_span",
     "layer": "model runner", "moves": "first16_mean_ms", "workloads": BELOW_THE_KNEE},
]
PHASES = ("evict", "admit", "prefill_enqueue", "dispatch_rows", "dispatch_enqueue", "collect_wait",
          "collect_counts", "emit", "prefill_wait", "prefill_counts", "first_token", "idle")


@pytest.mark.parametrize("entry", ENTRIES, ids=[e["name"] for e in ENTRIES])
def test_the_entry_is_in_the_manifest_letter_for_letter_and_its_cells_report_what_it_moves(entry):
    m = manifest.load()
    assert manifest.problems(m, ROOT) == []
    assert [x for x in m["per_layer"] if x["name"] == entry["name"]] == [entry]
    assert entry["layer"] in {x["layer"] for x in m["per_layer"] if x["name"] != entry["name"]}  # a layer already named
    for cell in entry["workloads"]:
        assert entry["moves"] in {x["name"] for x in manifest.metrics_of(m, "end_to_end", cell)}
        assert entry["name"] in {x["name"] for x in manifest.metrics_of(m, "per_layer", cell)}
    for cell in ("smollm2-1.7b-train-l8.steps", "smollm2-1.7b-train-ring4.steps"):
        assert entry["name"] not in {x["name"] for x in manifest.metrics_of(m, "per_layer", cell)}


def stats(steps, forwards, dispatches=None, **phase_s):
    """A ``stats()`` as the engine gives it (``phase_s``: seconds of the named phases, 0 for the rest)."""
    out = {"decode_steps": steps, "prefill_forwards": forwards}
    if dispatches is not None:
        out["decode_dispatches"] = dict(zip(("queued", "dry", "cold"), dispatches))
        out["loop_phase_s"] = {p: float(phase_s.get(p, 0.0)) for p in PHASES}
    return out


def _run(opened, closed, samples=(), trace_started=0.0):
    probe = types.SimpleNamespace(stats_open=opened and (0.0, opened), stats_close=closed and (1.0, closed),
                                  sampler=types.SimpleNamespace(samples=list(samples)), trace_started=trace_started)
    return {"probe": probe}


OPENED = stats(100, 10, (80, 15, 5), admit=0.5, first_token=1.0, emit=2.0, dispatch_enqueue=3.0, collect_wait=40.0,
               prefill_wait=7.0, idle=50.0)
CLOSED = stats(300, 20, (200, 75, 25), admit=0.52, first_token=1.05, emit=2.2, dispatch_enqueue=3.5, evict=0.03,
               collect_wait=60.0, prefill_wait=9.0, idle=70.0)
# what each reader makes of OPENED -> CLOSED: the host path is admit + first_token + emit + dispatch_enqueue + evict
# = 0.02 + 0.05 + 0.2 + 0.5 + 0.03 = 0.8 s over 200 steps; 60 dry of 60 + 120; 0.02 s and 0.05 s over 10 requests
WANT = {"loop_host_ms_per_step": 4.0, "decode_dry_share": 1 / 3, "admit_host_ms_per_request": 2.0, "first_token_join_ms": 5.0}
CASES = {
    "ratio": (OPENED, CLOSED, WANT),
    # nothing decoded and nothing prefilled inside the window; only cold steps
    "zero_denominator": (OPENED, stats(100, 10, (80, 15, 9), admit=0.9, idle=90.0), dict.fromkeys(WANT)),
    # the parent: steps and prefills are counted, nothing is timed
    "parent": (stats(100, 10), stats(300, 20), dict.fromkeys(WANT)),
    "no_open": (None, CLOSED, dict.fromkeys(WANT)),
    "no_close": (OPENED, None, dict.fromkeys(WANT)),
}


@pytest.mark.parametrize("metric", sorted(WANT))
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_reader_divides_the_windows_deltas_or_leaves_the_metric_out(metric, case):
    opened, closed, want = CASES[case]
    got = runner.load_reader(metric, manifest.load()["paths"])(_run(opened, closed))
    assert got is None if want[metric] is None else got == pytest.approx(want[metric])


# a traced run: the sampler read CLOSED just before the profiler session opened at 0.25; from there to the
# window's close the host ran slower (the session, then its export), which the readers must not take in
LATER = stats(900, 30, (300, 500, 30), admit=5.0, first_token=6.0, emit=7.0, dispatch_enqueue=9.0, evict=1.0,
              collect_wait=70.0, prefill_wait=9.5, idle=71.0)


@pytest.mark.parametrize("metric", sorted(WANT))
def test_a_traced_runs_reader_takes_the_part_before_the_profiler_session(metric):
    read = runner.load_reader(metric, manifest.load()["paths"])
    samples = [(0.1, stats(200, 15, (140, 45, 15), admit=0.51)), (0.2, CLOSED), (0.3, LATER), (0.6, LATER)]
    assert read(_run(OPENED, LATER, samples, trace_started=0.25)) == pytest.approx(WANT[metric])
    # no reading before the session (or no session: trace_started 0.0): the whole window
    whole = read(_run(OPENED, LATER))
    assert read(_run(OPENED, LATER, samples[2:], trace_started=0.25)) == pytest.approx(whole) != pytest.approx(WANT[metric])
    assert read(_run(OPENED, LATER, samples)) == pytest.approx(whole)
    probe = types.SimpleNamespace(stats_open=(0.0, OPENED), stats_close=(1.0, LATER), sampler=None, trace_started=0.25)
    assert read({"probe": probe}) == pytest.approx(whole)


def test_the_engines_stats_hold_what_the_readers_divide():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig, init_params
    from ray_tpu.serve.llm import LLMEngine

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64, dtype=jnp.float32, max_seq_len=64)
    eng = LLMEngine(cfg, init_params(cfg, jax.random.key(0)), max_batch_size=2, max_seq_len=64, kv_block_size=16)
    try:
        before = eng.stats()
        assert len(eng.generate([3, 1, 4], max_tokens=30)) == 30
        after = eng.stats()
    finally:
        eng.shutdown()
    assert tuple(after["loop_phase_s"]) == PHASES
    paths = manifest.load()["paths"]
    values = {name: runner.load_reader(name, paths)(_run(before, after)) for name in WANT}
    host = sum(after["loop_phase_s"][p] - before["loop_phase_s"][p] for p in PHASES
               if p not in ("collect_wait", "prefill_wait", "idle"))
    assert values["loop_host_ms_per_step"] == pytest.approx(1e3 * host / 29) and host > 0
    assert 0.0 <= values["decode_dry_share"] <= 1.0  # 28 steps behind one in flight, one cold
    assert sum(after["decode_dispatches"].values()) == 29 and after["decode_dispatches"]["cold"] == 1
    assert values["admit_host_ms_per_request"] > 0 and values["first_token_join_ms"] > 0


# -- host_spans --------------------------------------------------------------
PLANE = "/device:TPU:0"


def op(start, dur, name="fusion.1 fusion bf16[8]"):
    return [PLANE, trace_reduce.OP_LINE, name, start, dur]


def module(start, dur, name):
    return [PLANE, trace_reduce.MODULE_LINE, f"{name}(1)", start, dur]


def span(phase, start, dur, thread="python3#0"):
    return [thread, "llm::" + phase, start, dur]


def test_a_gap_goes_to_the_phase_that_covers_most_of_it_and_to_no_span_where_none_does():
    # busy 0-100, idle 100-400, busy 400-500, idle 500-600, busy 600-700, idle 700-900, busy 900-1000
    events = [module(0, 100, "jit__decode_k_paged"), op(0, 100), module(400, 100, "jit__prefill_chunk"), op(400, 100),
              module(600, 100, "jit__decode_k_paged"), op(600, 100), module(900, 100, "jit__decode_k_paged"), op(900, 100)]
    spans = [span("emit", 50, 100), span("idle", 150, 200), span("evict", 350, 30), span("admit", 380, 100),
             span("dispatch_enqueue", 700, 250)]
    gaps = host_spans.idle_intervals(events, PLANE)
    assert gaps == [(100, 400), (500, 600), (700, 900)]
    # 100-400: emit 50, idle 200, evict 30, admit 20; 500-600: nothing; 700-900: dispatch_enqueue 200
    assert host_spans.owners(gaps, spans) == ["idle", "no span", "dispatch_enqueue"]
    assert host_spans.gaps_by_span(events, spans) == [["idle", 300e-9], ["dispatch_enqueue", 200e-9], ["no span", 100e-9]]
    by = host_spans.gaps_by_programs_and_span(events, spans)
    assert by == {"jit__decode_k_paged -> jit__prefill_chunk": [["idle", 300e-9]],
                  "jit__decode_k_paged -> jit__decode_k_paged": [["dispatch_enqueue", 200e-9]],
                  "jit__prefill_chunk -> jit__decode_k_paged": [["no span", 100e-9]]}
    # and the names are trace_reduce's own
    assert {name for name, _ in trace_reduce.idle_gaps(events, PLANE)} == set(by)
    assert host_spans.gaps_by_span([], spans) == [] and host_spans.gaps_by_span(events, []) == [["no span", 600e-9]]
    # the decode run at 900 follows a gap and lies inside dispatch_enqueue (700-950); the one at 600 follows
    # a gap that no dispatch_enqueue span touches
    assert host_spans.launches_inside(events, spans, "jit__decode_k_paged", "dispatch_enqueue", min_gap_ns=50, slack_ns=0) == (1, 2)


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(ROOT, "benchmark", "testdata", "recorded_host_spans.json")) as f:
        return json.load(f)


def test_the_recorded_slice_reduces_to_the_totals_written_in_it(recorded):
    events, spans, want = recorded["events"], recorded["spans"], recorded["expected"]
    assert {s[1] for s in spans} <= {"llm::" + p for p in PHASES} and len({s[0] for s in spans}) == 1
    # flat and without a hole on the recorded thread
    ordered = sorted(spans, key=lambda s: s[2])
    assert all(a[2] + a[3] <= b[2] and b[2] - (a[2] + a[3]) < 50_000 for a, b in zip(ordered, ordered[1:]))
    got = host_spans.gaps_by_span(events, spans)
    assert [name for name, _ in got] == [name for name, _ in want["idle_by_span"]]
    for (_, a), (_, b) in zip(got, want["idle_by_span"]):
        assert a == pytest.approx(b, abs=1e-9)
    busy_s, window_s = trace_reduce.busy_and_window_s(events)
    assert sum(s for _, s in got) == pytest.approx(window_s - busy_s, abs=1e-9)
    assert busy_s == pytest.approx(want["busy_s"], abs=1e-9) and window_s == pytest.approx(want["window_s"], abs=1e-9)
    by = host_spans.gaps_by_programs_and_span(events, spans)
    assert {k: [[p, pytest.approx(s, abs=1e-9)] for p, s in v] for k, v in by.items()} == want["idle_by_programs_and_span"]
    assert list(host_spans.launches_inside(events, spans, "jit__decode_k_paged", "dispatch_enqueue")) == want["decode_launches"]
    named = sum(s for name, s in got if name != "no span")
    assert named >= 0.9 * (window_s - busy_s)
