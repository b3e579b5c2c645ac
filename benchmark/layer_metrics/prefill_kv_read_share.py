"""Of the capacity a sequence's block table spans, the share a prefill
chunk's attention has to visit, over the window's chunks (``stats()`` deltas
of ``prefill_kv_tokens_visited`` over ``prefill_kv_tokens_capacity``: a chunk
adds its start + its new tokens, less what a sliding layer's window hides
below its first query, averaged over the layers, to the first, and the
table's pages x the page size to the second). What a paged prefill kernel
reads of what a gather over the whole capacity read; what is left is the
ceiling of any further gain from a chunk's attention. None where ``stats()``
has no such counter (a program that gathers the capacity for every chunk) or
no chunk ran."""
from benchmark import readers

KEY = "prefill_kv_tokens_visited"


def read(run):
    p = run["probe"]
    if p.stats_close is None or KEY not in p.stats_close[1]:
        return None
    visited, capacity = readers.counter_delta(run, KEY), readers.counter_delta(run, "prefill_kv_tokens_capacity")
    return visited / capacity if visited is not None and capacity else None
