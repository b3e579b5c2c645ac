"""The loop's ``admit`` phase (fair-queue pop, prefix match over the whole
prompt, page reservation, the copy-on-write page copy) a prefilled request:
delta of ``stats()``'s ``loop_phase_s["admit"]`` over that of
``prefill_forwards``, in ms, both over the part of the window before the
profiler session (``loop_phases.readings``). The phase runs every iteration,
so the empty passes between arrivals are in it. None where ``stats()`` has no
loop clock."""
from benchmark import loop_phases


def read(run):
    return loop_phases.phase_ms_per(run, "admit", "prefill_forwards")
