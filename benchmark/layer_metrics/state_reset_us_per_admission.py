"""What giving a slot its state costs the engine loop an admission, in
microseconds of the host clock: the seconds the engine spent inside the
span the runner opens around zeroing a slot's state or restoring it from a snapshot
(``llm::state_restore``, timed by the scheduler around it; the time to enqueue the program behind the step in
flight, not its few microseconds on the device), ``stats()``'s
``state_reset_s``, over the admissions of the same part of the window
(``state_zeroed`` + ``state_restores``: a configuration whose layers keep a
state a sequence counts every admission as one or the other). The part before
the profiler session opens, as ``loop_host_ms_per_step``
(``loop_phases.readings``). None where ``stats()`` has no such clock (a
program, or a configuration, without per-slot state) or nothing was admitted."""
from benchmark import loop_phases

KEYS = ("state_reset_s", "state_zeroed", "state_restores")


def read(run):
    r = loop_phases.readings(run)
    if r is None or any(k not in r[0] or k not in r[1] for k in KEYS):
        return None
    admitted = sum(loop_phases.count_delta(run, k) for k in KEYS[1:])
    return 1e6 * loop_phases.count_delta(run, KEYS[0]) / admitted if admitted else None
