"""The expert layer's share of the device time of the operations inside
``jit__decode_k_paged``, in percent, for a configuration that holds a share
of its experts: the three grouped products a layer and the shared expert's
two up-projections, told by the shapes only the expert layer has at this
configuration's keys (``expert_ffn_time_share`` reads another family's): a
result of ``slots x num_experts_per_token`` rows (every choice of a decode
step is a row of the grouped products, dropped or not: the rows of experts
held elsewhere stay zeros) and ``[slots, shared width]``. The shared
expert's down-projection has the hidden size like many others and is not
counted. None without a trace or for a configuration without ``experts_held``."""
import re

from benchmark import readers, trace_reduce


def read(run):
    c = getattr(run.get("ctx"), "config", None) or {}
    plane = readers.first_plane(run)
    if not c.get("experts_held") or plane is None or "run" not in c:
        return None
    slots = c["run"]["max_batch_size"]
    rows = slots * c["num_experts_per_token"]
    shared = c.get("num_shared_experts", 0) * c["moe_intermediate_size"]
    grouped = re.compile(rf"\[{rows},\d+\]")
    shared_up = re.compile(rf"\[{slots},{shared}\]$")

    def expert_layer(name: str) -> bool:
        return bool(grouped.search(name) or (shared and shared_up.search(name)))

    share = trace_reduce.time_share(run["events"], plane, "jit__decode_k_paged", expert_layer)
    return None if share is None else 100.0 * share
