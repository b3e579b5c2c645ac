"""Device time of the paged decode kernel for one page it has to visit, in
microseconds: the kernel's time inside ``jit__decode_k_paged`` per run of
that program, over the mean of ``kv_live_pages`` in the engine's ``stats()``
(sampled twice a second inside the window: summed over the live sequences,
the pages a decode step's attention visits in a layer, averaged over the
layers) times the layers. It says whether the kernel's cost follows the live
pages: 128 KiB of K and V a page a layer (SmolLM2) is 0.16 us at 819 GB/s.
The trace gives only an operation's instruction, opcode and result shape, so
the kernel is told from XLA's own custom-calls (``ragged-dot``) by its
result, ``[slots, kv heads, ..]``. None without the counter or a trace."""
import re

from benchmark import readers, trace_reduce

PROGRAM = "jit__decode_k_paged"


def read(run):
    c = getattr(run.get("ctx"), "config", None) or {}
    plane = readers.first_plane(run)
    live = readers.mean_or_none([s["kv_live_pages"] for s in readers.stats_in_window(run) if "kv_live_pages" in s])
    if plane is None or not live or "run" not in c:
        return None
    kernel = re.compile(rf" custom-call \w+\[{c['run']['max_batch_size']},{c['num_key_value_heads']},\d+,\d+\]")
    steps = len(trace_reduce.program_runs(run["events"], plane).get(PROGRAM, []))
    ns = sum(e[4] for e in trace_reduce.ops_inside(run["events"], plane, PROGRAM) if kernel.search(e[2]))
    if not steps or not ns:
        return None
    return ns / 1e3 / steps / (live * c["num_hidden_layers"])
