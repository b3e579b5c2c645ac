"""The loop's ``first_token`` phase a prefilled request: after a prompt's
last chunk the eager key split, the sampler and its readback, which wait
behind the decode step in flight, and the join into the batch (ROADMAP S6 b);
delta of ``stats()``'s ``loop_phase_s["first_token"]`` over that of
``prefill_forwards``, in ms, both over the part of the window before the
profiler session (``loop_phases.readings``). None where ``stats()`` has no loop clock."""
from benchmark import loop_phases


def read(run):
    return loop_phases.phase_ms_per(run, "first_token", "prefill_forwards")
