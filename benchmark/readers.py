"""What the per-layer readers (``layer_metrics/<name>.py``) share: each of
them takes the finished run (a dict: the client's records, the engine's
counters, the profiler's events, the steps) and returns a number, or None
where there is nothing to read — the harness then leaves the metric out."""

from __future__ import annotations

import statistics
from typing import Any, Dict, List, Optional

from benchmark import trace_reduce, yardstick


def first_plane(run) -> Optional[str]:
    planes = trace_reduce.device_planes(run.get("events") or [])
    return planes[0] if planes else None


def mark_gaps_ms(run, start: Optional[str], end: str) -> List[float]:
    """Per scored request with a trace: time from mark ``start`` (None: the
    client's send) to mark ``end``, in ms, on the host clock."""
    out = []
    for t in run.get("turns", []):
        tr = t.trace
        if tr is None or not t.scored:
            continue
        b = tr.mark_offset(end)
        a = (t.sent - tr.t0) if start is None else tr.mark_offset(start)
        if a is not None and b is not None:
            out.append(1e3 * (b - a))
    return out


def quantile_or_none(values, q):
    return yardstick.quantile(values, q) if values else None


def stats_in_window(run) -> List[Dict[str, Any]]:
    """The engine's ``stats()`` as the sampler read them inside the window
    (twice a second); empty where the run sampled nothing."""
    sampler = run["probe"].sampler
    if sampler is None:
        return []
    w0, w1 = run["window"]
    return [s for t, s in sampler.samples if w0 <= t <= w1]


def mean_or_none(values):
    return sum(values) / len(values) if values else None


def counter_delta(run, key: str) -> Optional[float]:
    p = run["probe"]
    if p.stats_open is None or p.stats_close is None:
        return None
    return p.stats_close[1][key] - p.stats_open[1][key]


def prompt_tokens_sent_in_window(run) -> int:
    w0, w1 = run["window"]
    return sum(t.prompt_len for t in run.get("turns", []) if t.sent is not None and w0 <= t.sent <= w1)


def program_ms(run, program: str) -> List[float]:
    plane = first_plane(run)
    if plane is None:
        return []
    return [ns / 1e6 for ns in trace_reduce.program_runs(run["events"], plane).get(program, [])]


def median_or_none(values):
    return statistics.median(values) if values else None


def kernel_share_percent(run, program: Optional[str]) -> Optional[float]:
    plane = first_plane(run)
    if plane is None:
        return None
    share = trace_reduce.time_share(run["events"], plane, program, trace_reduce.is_custom_call)
    return None if share is None else 100.0 * share


def collective_shares_percent(run) -> Optional[Dict[str, float]]:
    """Averaged over the chips: collective time over busy time, and exposed
    collective time over the traced window, in percent."""
    events = run.get("events") or []
    planes = trace_reduce.device_planes(events)
    win = trace_reduce.window_of(events)
    if not planes or win is None:
        return None
    coll = exposed = busy = 0.0
    for p in planes:
        c, x = trace_reduce.collective_and_exposed_s(events, p)
        coll, exposed = coll + c, exposed + x
        busy += trace_reduce.total(trace_reduce.busy(events, p)) / 1e9
    if busy <= 0:
        return None
    window_s = (win[1] - win[0]) / 1e9 * len(planes)
    return {"collective": 100.0 * coll / busy, "exposed": 100.0 * exposed / window_s}
