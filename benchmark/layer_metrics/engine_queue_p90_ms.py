"""``engine_submit`` -> ``admitted`` (fair queue + wait for KV pages), 90th
percentile over scored requests; reqtrace marks, host clock."""
from benchmark import readers


def read(run):
    return readers.quantile_or_none(readers.mark_gaps_ms(run, "engine_submit", "admitted"), 0.9)
