"""Mean of ``kv_blocks_in_use`` over ``kv_block_pool_size`` in the engine's
``stats()``, sampled twice a second inside the window: beside
``batch_occupancy`` it says whether the pool or the slots bind."""
from benchmark import readers


def read(run):
    return readers.mean_or_none([s["kv_blocks_in_use"] / s["kv_block_pool_size"]
                                 for s in readers.stats_in_window(run) if s.get("kv_block_pool_size")])
