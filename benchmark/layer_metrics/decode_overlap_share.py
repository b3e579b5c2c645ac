"""Of the decode steps the engine read inside the window, the share it had
dispatched while the step before was still unread (``stats()`` deltas of
``decode_steps_overlapped`` over ``decode_steps``): how much of the host's
work between two steps ran beside the device and not between its programs.
None where ``stats()`` has no such counter (a program that reads every step
before it dispatches the next) or no step was read."""
from benchmark import readers

KEY = "decode_steps_overlapped"


def read(run):
    p = run["probe"]
    if p.stats_close is None or KEY not in p.stats_close[1]:
        return None
    overlapped, steps = readers.counter_delta(run, KEY), readers.counter_delta(run, "decode_steps")
    return overlapped / steps if overlapped is not None and steps else None
