"""The expert layer's share of the device time of the operations inside
``jit__decode_k_paged``, in percent: the three grouped products a layer
(one operation each, ``jax.lax.ragged_dot``: on the TPU a custom-call) and
the shared expert's two up-projections. The trace gives only an operation's
instruction, opcode and result shape, so they are found by the shapes only
the expert layer has: a result of ``slots x experts_per_tok`` rows (every
assignment of a decode step is a row of the grouped products, whatever the
routing), and ``[slots, shared width]``. The shared expert's down-projection
has the hidden size like many others and is not counted (0.25% of an expert
layer's weights)."""
import re

from benchmark import readers, trace_reduce


def read(run):
    c = getattr(run.get("ctx"), "config", None) or {}
    plane = readers.first_plane(run)
    if not c.get("num_experts") or plane is None:
        return None
    slots = c["run"]["max_batch_size"]
    rows = slots * c["num_experts_per_tok"]
    shared = c.get("num_shared_experts", 0) * c["moe_intermediate_size"]
    grouped = re.compile(rf"\[{rows},\d+\]")
    shared_up = re.compile(rf"\[{slots},{shared}\]$")

    def expert_layer(name: str) -> bool:
        return bool(grouped.search(name) or (shared and shared_up.search(name)))

    share = trace_reduce.time_share(run["events"], plane, "jit__decode_k_paged", expert_layer)
    return None if share is None else 100.0 * share
