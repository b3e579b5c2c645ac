"""How late the load generator sent, 99th percentile over every request of
the run, from the generator's own clock (send time minus due time)."""


def read(run):
    return run["values"].get("loadgen_late_p99_ms")
