"""Over the window, the busiest expert's assignments over the mean expert's
(``moe_expert_assignments`` of the engine's ``stats()``, close minus open,
summed over the expert layers): 1.0 is an even load; the grouped products'
time follows the rows, so what imbalance costs on one chip is the experts
hit, not the busiest one (PERF.md section 3)."""


def read(run):
    p = run["probe"]
    if p.stats_open is None or p.stats_close is None:
        return None
    a, b = (s[1].get("moe_expert_assignments") for s in (p.stats_open, p.stats_close))
    if not a or not b:
        return None
    per = [y - x for x, y in zip(a, b)]
    return max(per) / (sum(per) / len(per)) if sum(per) > 0 else None
