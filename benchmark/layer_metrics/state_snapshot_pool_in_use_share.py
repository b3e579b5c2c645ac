"""Mean of ``state_snapshots_in_use`` over ``state_snapshot_pool_size`` in
the engine's ``stats()``, sampled twice a second inside the window: beside
``kv_pool_in_use_share`` it says which of the two pools is full and
evicting. None where ``stats()`` has no such pool."""
from benchmark import readers


def read(run):
    return readers.mean_or_none([s["state_snapshots_in_use"] / s["state_snapshot_pool_size"]
                                 for s in readers.stats_in_window(run) if s.get("state_snapshot_pool_size")])
