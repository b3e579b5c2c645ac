"""Client send -> the engine's ``engine_submit`` mark (handle, router,
replica), median over scored requests; reqtrace marks, host clock."""
from benchmark import readers


def read(run):
    return readers.quantile_or_none(readers.mark_gaps_ms(run, None, "engine_submit"), 0.5)
