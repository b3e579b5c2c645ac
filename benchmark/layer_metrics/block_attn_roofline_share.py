"""The block step's attention kernel against the memory roofline, in
percent: the bytes its attention has to read and write a step (the
configuration's own function, ``benchmark/models/<model>.py``
``block_attention_bytes``: K and V of the pages the live rows' queries can
see, ``kv_live_pages`` of the engine's ``stats()`` averaged over the window,
and the rows' queries and outputs; it counts what is visible, not what an
implementation reads) over the kernel's device time a run of
``jit__decode_k_paged`` times the chip's published bandwidth. The kernel is
told from XLA's own custom-calls by its result, ``[slots, kv heads, ..]``,
as ``paged_kernel_us_per_live_page`` does. None without the counter, a trace
or a configuration that decodes by blocks."""
import re

from benchmark import readers, system, trace_reduce

PROGRAM = "jit__decode_k_paged"


def read(run):
    c = getattr(run.get("ctx"), "config", None) or {}
    plane = readers.first_plane(run)
    samples = readers.stats_in_window(run)
    live = readers.mean_or_none([s["kv_live_pages"] for s in samples if "kv_live_pages" in s])
    rows = readers.mean_or_none([s["active_slots"] for s in samples])
    if plane is None or not live or not rows or "run" not in c or not c.get("block_length"):
        return None
    count = getattr(system.model_module(c), "block_attention_bytes", None)
    if count is None:
        return None
    kernel = re.compile(rf" custom-call \w+\[{c['run']['max_batch_size']},{c['num_key_value_heads']},\d+,\d+\]")
    steps = len(trace_reduce.program_runs(run["events"], plane).get(PROGRAM, []))
    ns = sum(e[4] for e in trace_reduce.ops_inside(run["events"], plane, PROGRAM) if kernel.search(e[2]))
    if not steps or not ns:
        return None
    must = count(c, live, rows, page_tokens=c["run"]["kv_block_size"])
    return 100.0 * must / (ns / 1e9 / steps) / run["peak"]["hbm_bytes_per_s"]
