"""The expert layer's share of the device time of the operations inside
``jit__decode_k_paged`` where that program is a block step, in percent: the
three grouped products a layer (``jax.lax.ragged_dot``: on the TPU a
custom-call), found by the shape only they have: a result of ``slots x
block_length x experts_per_tok`` rows (every assignment of a block step is a
row of the grouped products, whatever the routing). None for a configuration
that decodes a token a step (``expert_ffn_time_share`` reads those)."""
import re

from benchmark import readers, trace_reduce


def read(run):
    c = getattr(run.get("ctx"), "config", None) or {}
    plane = readers.first_plane(run)
    if not c.get("num_experts") or not c.get("block_length") or plane is None:
        return None
    rows = c["run"]["max_batch_size"] * c["block_length"] * c["num_experts_per_tok"]
    grouped = re.compile(rf"\[{rows},\d+\]")
    share = trace_reduce.time_share(run["events"], plane, "jit__decode_k_paged", lambda name: bool(grouped.search(name)))
    return None if share is None else 100.0 * share
