"""The engine loop names its own time (``observability/tracing.py::LoopClock``
through ``serve/llm.py::_loop``): the twelve ``llm::`` phases in a
``jax.profiler`` trace and in ``stats()["loop_phase_s"]``, the counter of
what a decode step found on the device when it was enqueued
(``decode_dispatches``), the two Prometheus families published from them,
and ``llm_decode_stall_seconds``. Toy sizes, CPU.
"""

import glob
import json
import os
import pickle
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.models import sdar_moe  # noqa: E402
from ray_tpu.models.transformer import TransformerConfig, init_params  # noqa: E402
from ray_tpu.observability import metric_defs  # noqa: E402
from ray_tpu.serve import llm  # noqa: E402
from ray_tpu.serve.llm import LLMEngine  # noqa: E402

PHASES = ("evict", "admit", "prefill_enqueue", "dispatch_rows", "dispatch_enqueue", "collect_wait",
          "collect_counts", "emit", "prefill_wait", "prefill_counts", "first_token", "idle")
ENQUEUES = {"admit", "prefill_enqueue", "dispatch_enqueue", "first_token"}
CFG = TransformerConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64, dtype=jnp.float32, max_seq_len=128)
BLOCK_C = dict(hidden_size=64, intermediate_size=96, moe_intermediate_size=48, num_attention_heads=4,
               num_key_value_heads=2, head_dim=32, vocab_size=512, num_experts=8, num_experts_per_tok=2,
               num_hidden_layers=2, max_position_embeddings=256, rope_theta=1e6, rms_norm_eps=1e-6,
               tie_word_embeddings=False, norm_topk_prob=True, block_length=4, mask_token_id=500,
               denoising_steps=4, hidden_act="silu", rope_scaling=None)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.key(0))


def engine_of(params, **kw):
    return LLMEngine(CFG, params, **{"max_batch_size": 2, "max_seq_len": 128, "kv_block_size": 16,
                                     "prefill_chunk_tokens": 16, **kw})


def host_lines(trace_dir):
    """{line name: [(name, start_ns, end_ns), ...]} of the profile's host plane."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))[-1]
    plane = [p for p in ProfileData.from_file(path).planes if p.name == "/host:CPU"][0]
    return {line.name: [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns)) for e in line.events]
            for line in plane.lines}


def test_the_phases_are_the_twelve_and_stats_always_has_them(params):
    assert llm.LOOP_PHASES == PHASES
    eng = engine_of(params)
    try:
        s = eng.stats()
        assert tuple(s["loop_phase_s"]) == PHASES and set(s["decode_dispatches"]) == {"queued", "dry", "cold"}
        assert s["decode_dispatches"] == {"queued": 0, "dry": 0, "cold": 0}
    finally:
        eng.shutdown()


def test_a_profiler_session_holds_every_instant_of_the_loop_in_one_phase(params, tmp_path):
    eng = engine_of(params)
    try:
        eng.generate([3, 1, 4], max_tokens=4)  # compiled before the session
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1  # as benchmark/run.py sets it
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            futs = [eng.submit([5, 9, 2, 6] * 10, max_tokens=12), eng.submit([2, 7], max_tokens=6)]
            assert [len(f.result(timeout=120)) for f in futs] == [12, 6]
            time.sleep(0.2)  # the loop idles inside the session
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.shutdown()
    lines = {name: evs for name, evs in host_lines(str(tmp_path)).items() if any(e[0].startswith("llm::") for e in evs)}
    assert len(lines) == 1  # one thread
    events = sorted(next(iter(lines.values())), key=lambda e: e[1])
    spans = [e for e in events if e[0].startswith("llm::")]
    assert {e[0] for e in spans} == {"llm::" + p for p in PHASES}
    # flat and without a hole: a phase opens where the one before it ended
    # (the annotation's own exit and enter take the microseconds between)
    assert all(a[2] <= b[1] for a, b in zip(spans, spans[1:]))
    assert sum(e[2] - e[1] for e in spans) >= 0.98 * (spans[-1][2] - spans[0][1])
    # the backend's launches on that thread lie in the phases that enqueue
    launches = [e for e in events if e[0] == "PjRtCpuExecutable::Execute" and spans[0][1] <= e[1] < spans[-1][2]]
    assert len(launches) >= 18  # one a decode step at the least
    inside = set()
    for _, start, _ in launches:
        owner = [s[0] for s in spans if s[1] <= start < s[2]]
        assert len(owner) == 1
        inside.add(owner[0][len("llm::"):])
    assert "dispatch_enqueue" in inside and "prefill_enqueue" in inside and "first_token" in inside
    assert inside <= ENQUEUES


def test_without_a_session_no_annotation_is_built_and_the_seconds_add_up(params, monkeypatch):
    built = []

    class Counted(jax.profiler.TraceAnnotation):
        def __init__(self, *a, **kw):
            built.append(a)
            super().__init__(*a, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counted)
    eng = engine_of(params)
    try:
        assert eng._clock._annotation is Counted
        def reading_while_a_row_decodes():
            # the open phase is added when it ends: read while phases are short (an idle wait is 50 ms)
            fut = eng.submit([3, 1, 4], max_tokens=100)
            while eng.stats()["active_slots"] < 1:
                time.sleep(0.001)
            return time.perf_counter(), eng.stats(), fut

        eng.generate([3, 1, 4], max_tokens=4)
        t0, s0, first = reading_while_a_row_decodes()
        end = time.perf_counter() + 1.5
        while time.perf_counter() < end:
            eng.generate([3, 1, 4, 1, 5] * 4, max_tokens=8)
            time.sleep(0.02)
        t1, s1, last = reading_while_a_row_decodes()
        assert len(first.result(timeout=120)) == len(last.result(timeout=120)) == 100
        grown = {p: s1["loop_phase_s"][p] - s0["loop_phase_s"][p] for p in PHASES}
        assert all(v > 0 for v in grown.values()), grown
        assert sum(grown.values()) == pytest.approx(t1 - t0, rel=0.05)
    finally:
        eng.shutdown()
    assert built == []


class _Unready:
    """A step's output that never says it is ready (the device still runs)."""

    def __init__(self, out):
        self.out = out

    def is_ready(self):
        return False

    def __array__(self, *a, **kw):
        return np.asarray(self.out)


def test_a_step_is_cold_after_an_empty_batch_and_queued_behind_a_running_one(params):
    eng = engine_of(params)
    program = eng.runner._decode_k_paged
    eng.runner._decode_k_paged = lambda *a: (lambda out, *rest: (_Unready(out), *rest))(*program(*a))
    try:
        assert len(eng.generate([3, 1, 4], max_tokens=10)) == 10
        assert eng.stats()["decode_dispatches"] == {"queued": 8, "dry": 0, "cold": 1}
        assert len(eng.generate([3, 1, 4], max_tokens=5)) == 5
        assert eng.stats()["decode_dispatches"] == {"queued": 11, "dry": 0, "cold": 2}
    finally:
        eng.shutdown()


def test_a_step_is_dry_when_the_one_in_flight_had_finished_but_never_behind_a_chunk(params):
    eng = engine_of(params)
    admit, note = eng._admit, eng._note_dispatch
    seen = []

    def slow_admit():
        time.sleep(0.03)  # the toy step in flight is long done when the next is enqueued
        admit()

    def noted(behind_chunk):
        before = dict(eng._dispatches)
        note(behind_chunk)
        seen.append((behind_chunk, [k for k in before if eng._dispatches[k] != before[k]]))

    try:
        eng.generate([8, 8, 8] * 11, max_tokens=3)  # both programs compiled
        warm = sum(eng.stats()["decode_dispatches"].values())
        eng._admit, eng._note_dispatch = slow_admit, noted
        first = eng.submit([3, 1, 4], max_tokens=24)
        while eng.stats()["decode_steps"] < 4:
            time.sleep(0.005)
        second = eng.submit([5, 9, 2, 6] * 12, max_tokens=4)  # three chunks, each ahead of a step of the first
        assert len(first.result(timeout=120)) == 24 and len(second.result(timeout=120)) == 4
        d = eng.stats()["decode_dispatches"]
    finally:
        eng.shutdown()
    assert all(len(kinds) == 1 for _, kinds in seen) and sum(d.values()) - warm == len(seen)
    assert seen[0] == (False, ["cold"])
    behind = [kinds[0] for chunk, kinds in seen if chunk]
    assert len(behind) >= 3 and set(behind) == {"queued"}
    alone = [kinds[0] for chunk, kinds in seen[1:] if not chunk]
    assert alone.count("dry") >= 0.9 * len(alone) and d["dry"] >= alone.count("dry")


def test_a_block_step_is_counted_and_timed_too():
    cfg = sdar_moe.program_config(BLOCK_C, dtype="float32", param_dtype="float32", max_seq_len=256)
    eng = LLMEngine(cfg, init_params(cfg, jax.random.key(0)), max_batch_size=2, max_seq_len=256,
                    kv_block_size=16, prefill_chunk_tokens=32)
    try:
        prompt = np.random.default_rng(0).integers(1, 400, size=40).tolist()
        assert len(eng.generate(prompt, max_tokens=8)) == 8
        s = eng.stats()
        assert s["decode_dispatches"]["cold"] == 1 and sum(s["decode_dispatches"].values()) == s["block_steps"] == 10
        assert all(s["loop_phase_s"][p] > 0 for p in PHASES if p != "first_token")  # no token comes from its prefill
    finally:
        eng.shutdown()


def test_stats_pickle_and_the_new_keys_only_grow(params):
    eng = engine_of(params)
    try:
        fut = eng.submit([3, 1, 4, 1, 5, 9] * 6, max_tokens=40)
        samples = []
        while not fut.done():
            samples.append(pickle.loads(pickle.dumps(eng.stats())))
            time.sleep(0.002)
        samples.append(eng.stats())
    finally:
        eng.shutdown()
    assert len(samples) > 3
    for a, b in zip(samples, samples[1:]):
        assert b["decode_steps_overlapped"] >= a["decode_steps_overlapped"]
        assert all(b["loop_phase_s"][p] >= a["loop_phase_s"][p] for p in PHASES)
        assert all(b["decode_dispatches"][k] >= a["decode_dispatches"][k] for k in a["decode_dispatches"])
    assert samples[-1]["decode_dispatches"]["cold"] == 1 and sum(samples[-1]["decode_dispatches"].values()) == 39
    assert samples[-1]["decode_steps_overlapped"] == 38 and "loop_iterations" not in samples[-1]  # nothing read it


def test_the_two_families_follow_the_engines_totals_and_the_four_mirrors_are_gone(params):
    names = {m.name for m in metric_defs.ALL_METRICS}
    assert {"llm_loop_phase_seconds_total", "llm_decode_dispatches_total"} <= names
    assert not names & {"llm_moe_assignments_total", "llm_moe_experts_hit_total",
                        "llm_decode_steps_overlapped_total", "llm_decode_row_steps_discarded_total"}
    phase = lambda p: metric_defs.LLM_LOOP_PHASE_SECONDS.get({"phase": p})  # noqa: E731
    device = lambda k: metric_defs.LLM_DECODE_DISPATCHES.get({"device": k})  # noqa: E731
    before = {**{p: phase(p) for p in PHASES}, **{k: device(k) for k in ("queued", "dry", "cold")}}
    eng = engine_of(params)
    try:
        assert len(eng.generate([3, 1, 4], max_tokens=12)) == 12
    finally:
        eng.shutdown()  # the loop publishes once more as it ends
    s = eng.stats()
    for p in PHASES:
        assert phase(p) - before[p] == pytest.approx(s["loop_phase_s"][p], abs=1e-9)
    for k, n in s["decode_dispatches"].items():
        assert device(k) - before[k] == n
    assert sum(s["decode_dispatches"].values()) == 11


def test_a_stall_is_the_wait_for_a_chunk_that_ran_beside_live_rows(params):
    stall = metric_defs.LLM_DECODE_STALL
    count = lambda: sum(stall._totals.values())  # noqa: E731
    total = lambda: sum(stall._sums.values())  # noqa: E731
    eng = engine_of(params)
    try:
        eng.generate([7, 1, 8, 2] * 8, max_tokens=2)  # chunks with no row live: no stall
        n0, sum0, wait0 = count(), total(), eng.stats()["loop_phase_s"]["prefill_wait"]
        first = eng.submit([3, 1, 4], max_tokens=120)
        while eng.stats()["active_slots"] < 1:
            time.sleep(0.001)
        n1 = count()
        second = eng.submit([5, 9, 2, 6] * 8, max_tokens=2)  # two chunks beside the first's steps
        second.result(timeout=120), first.result(timeout=120)
        waited = eng.stats()["loop_phase_s"]["prefill_wait"] - wait0
    finally:
        eng.shutdown()
    assert n1 - n0 <= 1  # the first's own one chunk ran beside nothing
    assert count() - n1 == 2
    assert 0 < total() - sum0 <= waited + 1e-9


def test_a_crash_is_recorded_with_the_iterations_phase_times(params):
    from ray_tpu.observability.events import global_event_manager

    eng = engine_of(params)
    token = str(eng._admission_token)
    admit = eng._admit

    def crash_once():
        eng._admit = admit
        time.sleep(0.02)
        raise RuntimeError("boom in the loop clock's own test")

    try:
        eng._admit = crash_once
        time.sleep(0.3)
        assert len(eng.generate([3, 1, 4], max_tokens=3)) == 3  # the loop recovered
    finally:
        eng.shutdown()
    events = [e for e in global_event_manager().list_events(source_type="SERVE")
              if e.label == "engine_crash" and "the loop clock's own test" in e.message
              and e.custom_fields.get("engine") == token]  # a token is given again once its engine is gone
    assert len(events) == 1
    ms = json.loads(events[0].custom_fields["state"])["loop_phase_ms"]
    assert tuple(ms) == tuple(sorted(PHASES)) and ms["admit"] >= 20 and ms["idle"] == 0
