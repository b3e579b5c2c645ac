"""Of the (token, choice) pairs the routers chose over all the experts in the
window, the share that landed on the experts this chip holds: the window's
delta of ``moe_assignments_local`` over that of ``moe_assignments`` (the
engine's ``stats()``; a configuration that holds a share of its experts). 0.25
if routing is even over four chips' shares; what the grouped products' rows
follow. None without the counters."""


def read(run):
    p = run["probe"]
    if p.stats_open is None or p.stats_close is None:
        return None
    a, b = p.stats_open[1], p.stats_close[1]
    if "moe_assignments_local" not in b or "moe_assignments_local" not in a:
        return None
    routed = b["moe_assignments"] - a["moe_assignments"]
    return (b["moe_assignments_local"] - a["moe_assignments_local"]) / routed if routed > 0 else None
