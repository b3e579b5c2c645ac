"""What the readers of the engine loop's own clock share. ``stats()`` holds
``loop_phase_s`` (seconds of the loop thread by phase, ``serve/llm.py::
LOOP_PHASES``: they add up to its wall time) and ``decode_dispatches`` (what
each decode step found on the device when it was enqueued) since PR 42; a
program without them gives every reader None.

**Which part of the window.** Per-layer metrics are read in traced runs, and
there the host is slower from the profiler session's opening to the
window's close (the session itself, then its export beside the engine:
PERF.md section 5). So these readers subtract the window's opening from the
sampler's last reading *before* ``Probe.trace_started``, the quarter of the
window no session touched, numerator and denominator alike; where there is
no such reading (no sampler, no session) they take the whole window."""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

#: phases in which the loop waits (for the device, for a request); the rest is the host path
WAITS = ("collect_wait", "prefill_wait", "idle")


def readings(run) -> Optional[Tuple[Dict[str, Any], Dict[str, Any]]]:
    """The two ``stats()`` a reader subtracts: the window's opening and the
    last reading before the profiler session (else the window's close)."""
    p = run["probe"]
    if p.stats_open is None or p.stats_close is None:
        return None
    t0, opened = p.stats_open
    samples = p.sampler.samples if p.sampler is not None else ()
    before = [s for t, s in samples if t0 < t < p.trace_started]
    return opened, (before[-1] if before else p.stats_close[1])


def window_delta(run, key: str) -> Optional[Dict[str, float]]:
    """Later minus earlier of a ``stats()`` value that is a dict of totals."""
    r = readings(run)
    if r is None or key not in r[0] or key not in r[1]:
        return None
    return {k: v - r[0][key].get(k, 0) for k, v in r[1][key].items()}


def count_delta(run, key: str) -> Optional[float]:
    """Later minus earlier of a ``stats()`` counter, over the same part."""
    r = readings(run)
    return None if r is None else r[1][key] - r[0][key]


def phase_ms_per(run, phase: str, per: str) -> Optional[float]:
    """The part's seconds in ``phase`` over its count of ``per``, in ms."""
    seconds, n = window_delta(run, "loop_phase_s"), count_delta(run, per)
    return 1e3 * seconds[phase] / n if seconds is not None and n else None
