"""(layer, expert) pairs that got a token in a decode step, over expert
layers x experts, averaged over the window's decode steps (``stats()``
deltas of ``moe_experts_hit_decode`` and ``decode_steps``): the share of the
routed experts' weights a decode step has to read."""


def read(run):
    p = run["probe"]
    if p.stats_open is None or p.stats_close is None:
        return None
    a, b = p.stats_open[1], p.stats_close[1]
    if "moe_experts_hit_decode" not in b or "decode_steps" not in b:
        return None
    steps = b["decode_steps"] - a["decode_steps"]
    pairs = b["moe_expert_layers"] * len(b["moe_expert_assignments"])
    return (b["moe_experts_hit_decode"] - a["moe_experts_hit_decode"]) / (steps * pairs) if steps > 0 else None
