"""The linear layers' state update's share of the device time of the
operations inside ``jit__decode_k_paged``, in percent: the operations whose
result holds the per-slot recurrent state, told by its dimensions and by
nothing else (``benchmark/readers_state.py``: ``[.., slots, heads, key size,
value size]`` float32, or the same with the heads that share a row of 128
lanes folded into the minor axis), whatever implements them: a Mosaic kernel
that updates the state in place, or XLA's own gather, products and scatter.
None without a trace or for a configuration without linear layers."""
from benchmark import readers_state


def read(run):
    return readers_state.state_ops_share_percent(run, "jit__decode_k_paged")
