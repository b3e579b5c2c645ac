"""Mean ``active_slots`` of the engine's ``stats()``, sampled twice a
second inside the window."""
from benchmark import readers


def read(run):
    return readers.mean_or_none([s["active_slots"] for s in readers.stats_in_window(run)])
