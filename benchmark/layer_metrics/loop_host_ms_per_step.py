"""Host path of the engine loop a decode step: the seconds in every phase of
``stats()``'s ``loop_phase_s`` but the waits (``collect_wait`` and
``prefill_wait``, blocked on the device; ``idle``, an empty batch) over the
``decode_steps`` of the same part of the window, in ms: the part before the
profiler session opens (``loop_phases.readings``), since the session and its
export slow the host for the rest. Beside ``decode_step_dev_ms``: where it
is the larger, the host sets the pace. None where ``stats()`` has no loop clock."""
from benchmark import loop_phases


def read(run):
    seconds, steps = loop_phases.window_delta(run, "loop_phase_s"), loop_phases.count_delta(run, "decode_steps")
    if seconds is None or not steps:
        return None
    return 1e3 * sum(s for phase, s in seconds.items() if phase not in loop_phases.WAITS) / steps
