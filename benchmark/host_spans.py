"""The engine loop's phases in a profiler trace, and the device's idle time
put down to them.

Since PR 42 the loop of ``serve/llm.py`` writes each of its twelve phases as
a ``jax.profiler.TraceAnnotation`` named ``llm::<phase>`` while a profiler
session is on. They land in the xplane's ``/host:CPU`` plane, on the line
of the loop's thread and on the clock the device planes use, flat and
without a hole. :func:`read` takes them out of the newest trace under a
directory as ``[thread, name, start_ns, dur_ns]``; :func:`gaps_by_span`
takes the idle intervals of the first device plane, exactly as
``trace_reduce.idle_gaps`` finds them (``window_of``, ``busy``,
``subtract``: called, not copied), and puts each down to the phase that
covers most of it. Where ``idle_gaps`` says *when* the chip idled (between
which two programs), this says what the host was doing meanwhile.

``benchmark/run.py::Probe.read_trace`` keeps the device planes only and
deletes the trace directory, so no reader under ``layer_metrics/`` can see
the host plane yet: ``tools/host_gaps.py`` is the builder's way in, and the
wiring is the next ``benchmark`` issue's (PERF.md section 7). Checked on
``testdata/recorded_host_spans.json``, a slice of a real v5e trace.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

from benchmark import trace_reduce

Span = Sequence  # [thread, name, start_ns, dur_ns]
HOST_PLANE = "/host:CPU"
PREFIX = "llm::"
NO_SPAN = "no span"


def loop_thread_events(trace_dir: str) -> List[list]:
    """Every host event of the threads that wrote ``llm::`` events, from the
    newest trace under ``trace_dir``, in order of start; ``thread`` is the
    line's name and its place among the plane's lines (two threads may share
    a name). Besides the loop's phases these are the runtime's own events
    nested in them (``PjitFunction(..)``, transfers), at ``host_tracer_level`` 1."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        return []
    out = []
    for plane in ProfileData.from_file(files[-1]).planes:
        if plane.name != HOST_PLANE:
            continue
        for i, line in enumerate(plane.lines):
            events = [[f"{line.name}#{i}", ev.name, int(ev.start_ns), int(ev.duration_ns)] for ev in line.events]
            if any(e[1].startswith(PREFIX) for e in events):
                out += events
    return sorted(out, key=lambda e: e[2])


def read(trace_dir: str) -> List[list]:
    """The ``llm::`` events of the host plane of the newest trace under
    ``trace_dir``, in order of start."""
    return [e for e in loop_thread_events(trace_dir) if e[1].startswith(PREFIX)]


def idle_intervals(events: List[trace_reduce.Event], plane: str) -> List[trace_reduce.Interval]:
    """The parts of the traced window in which no operation ran on ``plane``."""
    win = trace_reduce.window_of(events)
    return [] if win is None else trace_reduce.subtract([win], trace_reduce.busy(events, plane))


def owners(gaps: List[trace_reduce.Interval], spans: List[Span]) -> List[str]:
    """For each gap (sorted, disjoint) the phase whose spans cover most of
    it, without the prefix; ``"no span"`` where none touches it."""
    spans = sorted(spans, key=lambda s: s[2])
    out, j = [], 0
    for s, e in gaps:
        while j < len(spans) and spans[j][2] + spans[j][3] <= s:
            j += 1
        covered: Dict[str, int] = defaultdict(int)
        k = j
        while k < len(spans) and spans[k][2] < e:
            covered[spans[k][1]] += min(e, spans[k][2] + spans[k][3]) - max(s, spans[k][2])
            k += 1
        best = max(covered.items(), key=lambda kv: kv[1], default=(NO_SPAN, 0))
        out.append(best[0][len(PREFIX):] if best[1] > 0 else NO_SPAN)
    return out


def gaps_by_span(events: List[trace_reduce.Event], spans: List[Span]) -> List[List]:
    """The idle time of the first device plane's window by the loop phase
    that covers most of each gap: ``[[phase, seconds], ...]``, longest total
    first. The spans of one thread: the engine loop's."""
    planes = trace_reduce.device_planes(events)
    if not planes:
        return []
    gaps = idle_intervals(events, planes[0])
    by_name: Dict[str, int] = defaultdict(int)
    for (s, e), phase in zip(gaps, owners(gaps, spans)):
        by_name[phase] += e - s
    return [[name, ns / 1e9] for name, ns in sorted(by_name.items(), key=lambda kv: -kv[1])]


def gaps_by_programs_and_span(events: List[trace_reduce.Event], spans: List[Span]) -> Dict[str, List[List]]:
    """``trace_reduce.idle_gaps``'s names (the programs on either side of a
    gap), each divided among the phases its gaps were put down to:
    ``{"<before> -> <after>": [[phase, seconds], ...]}``. The names are
    ``idle_gaps``'s own: it is called once a phase on the trace with every
    other phase's gaps filled in."""
    planes = trace_reduce.device_planes(events)
    if not planes:
        return {}
    plane = planes[0]
    gaps = idle_intervals(events, plane)
    phases = owners(gaps, spans)
    out: Dict[str, Dict[str, int]] = defaultdict(dict)
    for phase in set(phases):
        filled = [[plane, trace_reduce.OP_LINE, "filled", s, e - s] for (s, e), p in zip(gaps, phases) if p != phase]
        for name, seconds in trace_reduce.idle_gaps(list(events) + filled, plane, n=1 << 30):
            out[name][phase] = seconds
    return {name: [[p, s] for p, s in sorted(by.items(), key=lambda kv: -kv[1])]
            for name, by in sorted(out.items(), key=lambda kv: -sum(kv[1].values()))}


def launches_inside(events: List[trace_reduce.Event], spans: List[Span], program: str, phase: str,
                    min_gap_ns: int = 100_000, slack_ns: int = 500_000) -> Tuple[int, int]:
    """Whether the two clocks are one: of the runs of ``program`` on the
    first device plane that begin in or at the end of an idle gap of at least
    ``min_gap_ns`` (the device was waiting for that launch), how many start
    inside a span of ``phase`` or within ``slack_ns`` of its end:
    ``(inside, all)``."""
    planes = trace_reduce.device_planes(events)
    if not planes:
        return 0, 0
    gaps = [(s, e) for s, e in idle_intervals(events, planes[0]) if e - s >= min_gap_ns]
    marks = [(s[2], s[2] + s[3] + slack_ns) for s in spans if s[1] == PREFIX + phase]
    inside = total = 0
    for ev in trace_reduce.module_events(events, planes[0]):
        if trace_reduce.program_name(ev[2]) == program and any(s <= ev[3] <= e for s, e in gaps):
            total += 1
            inside += any(a <= ev[3] <= b for a, b in marks)
    return inside, total
