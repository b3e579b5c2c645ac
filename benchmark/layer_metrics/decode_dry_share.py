"""Of the decode steps enqueued behind a step in flight, the share that found
that step finished and no prefill chunk ahead: the device had nothing queued
and idled until the enqueue (``stats()`` deltas of ``decode_dispatches`` over
the part of the window before the profiler session, ``loop_phases.readings``:
``dry`` over ``dry + queued``; ``cold`` steps, after an empty batch, are left
out). What ``decode_overlap_share`` cannot say: that one counts steps the
host dispatched ahead, this one steps that came too late.

A watch, not a yardstick: ``is_ready()`` learns of a finished step as late as
a readback does, so the share is a lower bound. It read 0.00-0.03 in every
cell's part before the session, where the host path is under the step, and
0.42 behind ``mixed-lengths``' session, where the profiler's export makes it
5.8 ms against a 3.3-ms step (PERF.md section 5): it tells a host that
outlasts the step from one that does not, and the measure of a ``perf_opt``
on the host path is ``loop_host_ms_per_step``. None where ``stats()`` lacks
the counter or no such step was enqueued."""
from benchmark import loop_phases


def read(run):
    d = loop_phases.window_delta(run, "decode_dispatches")
    if d is None or not d["dry"] + d["queued"]:
        return None
    return d["dry"] / (d["dry"] + d["queued"])
