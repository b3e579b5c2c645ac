"""Builder-only: what the host was doing while the chip idled. Runs one cell
exactly as ``benchmark/run.py --trace 1`` does (its ``main``, its probe, its
profiler session) with ``trace_reduce.read_xplane`` wrapped at run time, so
that the same xplane's host plane is reduced (``host_spans``) before the
harness deletes the directory. It goes when the harness reads the host
plane itself (PERF.md section 7, PR 42 a). After ``run.py``'s own line it
prints one JSON line:

``idle_by_span``               the traced window's idle seconds by loop phase
``idle_by_programs_and_span``  ``breakdown.idle_gaps``'s names, each divided among the phases
``decode_launches``            the clocks against each other: of the decode runs that begin at an idle gap of 0.1 ms,
                               [those inside ``llm::dispatch_enqueue`` or within 0.5 ms of its end, all]
``device_leads_by_ms``         the least time by which the device's stamps are early against the host's in this
                               trace (a run cannot begin before the call that launches it; the offset is a
                               session's own, ``tools/clock_probe.py``); ``idle_by_span_shifted`` and
                               ``decode_launches_shifted`` are the two above with the spans moved earlier by it
``loop``                       the loop clock from ``stats()``: ms a decode step by phase before, in and after the
                               profiler session (between the sampler's reads that bracket it; ``before`` is the
                               part the per-layer readers take) with what each part's steps found on the device,
                               and the phases' sum over the window's wall time. With ``--no-profiler`` the same
                               parts of a run with no session: what the instrumentation costs while one is on
``inside_phase``               of three phases' time, the runtime's own host events nested in them, by name

    python3 benchmark/tools/host_gaps.py --workload <cell> --seed <n> [--seconds 51] [--no-profiler] [--rehearsal]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def phase_ms_per_step(opened, closed):
    """ms a decode step by phase between two ``stats()`` readings."""
    steps = closed["decode_steps"] - opened["decode_steps"]
    if not steps or "loop_phase_s" not in closed:
        return None
    return {p: 1e3 * (s - opened["loop_phase_s"][p]) / steps for p, s in closed["loop_phase_s"].items()}


def nested_seconds(thread_events, phase: str, top: int = 12):
    """Seconds of the loop thread's other events that begin inside spans of
    ``phase``, by event name, largest first."""
    spans = sorted((e[2], e[2] + e[3]) for e in thread_events if e[1] == "llm::" + phase)
    by_name, j = {}, 0
    for _, name, start, dur in thread_events:
        if name.startswith("llm::"):
            continue
        while j < len(spans) and spans[j][1] <= start:
            j += 1
        if j < len(spans) and spans[j][0] <= start:
            by_name[name] = by_name.get(name, 0) + dur
    return [[n, ns / 1e9] for n, ns in sorted(by_name.items(), key=lambda kv: -kv[1])[:top]]


def device_lead_ns(events, thread_events) -> int:
    """The least time by which this trace's device stamps are early against
    its host stamps. A run cannot begin before the call that launches it:
    over the ``dispatch_enqueue`` spans in which a decode run begins at an
    idle gap of 0.1 ms, the mean of the jit call's host event's start less
    the run's start, 0 where the runs come after their calls."""
    from benchmark import host_spans, trace_reduce

    plane = trace_reduce.device_planes(events)[0]
    gaps = [g for g in host_spans.idle_intervals(events, plane) if g[1] - g[0] >= 100_000]
    starts = sorted(m[3] for m in trace_reduce.module_events(events, plane)
                    if trace_reduce.program_name(m[2]) == "jit__decode_k_paged" and any(a <= m[3] <= b for a, b in gaps))
    calls = sorted(e[2] for e in thread_events if e[1] == "PjitFunction(_decode_k_paged)")
    early = []
    for _, name, s0, dur in thread_events:
        if name != "llm::dispatch_enqueue":
            continue
        call = next((c for c in calls if s0 <= c < s0 + dur), None)
        dev = next((t for t in starts if s0 <= t < s0 + dur), None)
        if call is not None and dev is not None:
            early.append(call - dev)
    return max(0, int(sum(early) / len(early))) if early else 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--no-profiler", action="store_true", help="the same run with no profiler session: not `correct`, no idle_by_span")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()

    from benchmark import host_spans, run as runner, trace_reduce

    seen = {}
    read_xplane, make_probe = trace_reduce.read_xplane, runner.Ctx.probe

    def read_both(trace_dir):
        seen["events"], seen["thread_events"] = read_xplane(trace_dir), host_spans.loop_thread_events(trace_dir)
        return seen["events"]

    def keep_probe(ctx, served, window):
        seen["probe"] = make_probe(ctx, served, window)
        return seen["probe"]

    trace_reduce.read_xplane, runner.Ctx.probe = read_both, keep_probe
    if args.no_profiler:
        def no_session(probe):  # the probe's timeline as it is, with no session opened
            probe.trace_started = time.perf_counter()

        runner.Probe.start_trace = no_session
    code = runner.main(["--workload", args.workload, "--seed", str(args.seed), "--seconds", str(args.seconds),
                        "--trace", "1"] + (["--rehearsal"] if args.rehearsal else []))
    probe = seen.get("probe")
    if code or probe is None or probe.stats_open is None or probe.stats_close is None:
        return code or 1
    line = {"workload": args.workload, "seed": args.seed, "profiler": not args.no_profiler}
    (t0, opened), (t1, closed) = probe.stats_open, probe.stats_close
    if "loop_phase_s" in closed:
        a, b = probe.trace_started, probe.trace_started + float(probe.ctx.traffic["trace_s"])
        before = [s for t, s in probe.sampler.samples if t0 < t < a]
        after = [s for t, s in probe.sampler.samples if t >= b]
        parts = {"before": (opened, before[-1]), "in": (before[-1], after[0]), "after": (after[0], closed)} if before and after else {}
        line["loop"] = {
            "phases_sum_over_wall": (sum(closed["loop_phase_s"].values()) - sum(opened["loop_phase_s"].values())) / (t1 - t0),
            **{k: {"phase_ms_per_step": phase_ms_per_step(x, y), "decode_steps": y["decode_steps"] - x["decode_steps"],
                   "prefill_forwards": y["prefill_forwards"] - x["prefill_forwards"],
                   "decode_dispatches": {d: n - x["decode_dispatches"][d] for d, n in y["decode_dispatches"].items()}}
               for k, (x, y) in parts.items()},
        }
    events, thread_events = seen.get("events") or [], seen.get("thread_events") or []
    spans = [e for e in thread_events if e[1].startswith(host_spans.PREFIX)]
    if events and spans:
        thread = max({s[0] for s in spans}, key=lambda t: sum(s[0] == t for s in spans))
        spans = [s for s in spans if s[0] == thread]
        thread_events = [e for e in thread_events if e[0] == thread]
        lead = device_lead_ns(events, thread_events)
        shifted = [[t, n, s - lead, d] for t, n, s, d in spans]
        launches = lambda sp: list(host_spans.launches_inside(events, sp, "jit__decode_k_paged", "dispatch_enqueue"))  # noqa: E731
        line["idle_by_span"] = host_spans.gaps_by_span(events, spans)
        line["idle_by_programs_and_span"] = host_spans.gaps_by_programs_and_span(events, spans)
        line["decode_launches"] = launches(spans)
        line["device_leads_by_ms"] = lead / 1e6
        line["idle_by_span_shifted"] = host_spans.gaps_by_span(events, shifted)
        line["decode_launches_shifted"] = launches(shifted)
        line["inside_phase"] = {p: nested_seconds(thread_events, p) for p in ("dispatch_enqueue", "collect_counts", "admit")}
        busy_s, window_s = trace_reduce.busy_and_window_s(events)
        line["traced"] = {"busy_s": busy_s, "window_s": window_s, "spans": len(spans), "thread": thread}
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
