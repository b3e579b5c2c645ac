"""Mean of ``kv_read_share`` in the engine's ``stats()``, sampled twice a
second inside the window: of the live sequences' cached tokens, the share a
decode step must read (a sliding layer sees ``min(len, window)``, a full
layer ``len``). The pool still holds every page."""
from benchmark import readers


def read(run):
    return readers.mean_or_none([s["kv_read_share"] for s in readers.stats_in_window(run) if "kv_read_share" in s])
