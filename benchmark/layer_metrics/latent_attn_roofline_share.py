"""The latent decode kernel against its roofline, in percent: the work latent
attention has to do a decode step whatever implements it (the
configuration's own function, ``benchmark/models/<model>.py``
``latent_attn_work``: the cached rows of the live sequences read once, and
for every head one dot product over a row and one weighted sum over its
latent; live tokens from ``kv_live_pages`` of the engine's ``stats()``, rows
from ``active_slots``, both averaged over the window) as the LARGER of its
bytes over the chip's published bandwidth and its FLOPs over the chip's bf16
peak (60 FLOP a byte: the bytes come first, but with 32 query rows a product
the MXU's time is of the same order), over the kernel's device time a run of
``jit__decode_k_paged``. The kernel is told as ``latent_attn_time_share``
tells it. None without the counter, a trace or latent attention."""
from benchmark import readers, readers_latent, system, trace_reduce


def read(run):
    c = getattr(run.get("ctx"), "config", None) or {}
    plane = readers.first_plane(run)
    kernel = readers_latent.decode_kernel(c)
    samples = readers.stats_in_window(run)
    pages = readers.mean_or_none([s["kv_live_pages"] for s in samples if "kv_live_pages" in s])
    rows = readers.mean_or_none([s["active_slots"] for s in samples])
    if plane is None or kernel is None or not pages or not rows:
        return None
    count = getattr(system.model_module(c), "latent_attn_work", None)
    steps = len(trace_reduce.program_runs(run["events"], plane).get(readers_latent.DECODE, []))
    ns = sum(e[4] for e in trace_reduce.ops_inside(run["events"], plane, readers_latent.DECODE) if kernel(e[2]))
    if count is None or not steps or not ns:
        return None
    bytes_, flops = count(c, readers_latent.live_tokens(c, pages, rows), rows)
    floor_s = max(bytes_ / run["peak"]["hbm_bytes_per_s"], flops / run["peak"]["bf16_flops"])
    return 100.0 * floor_s / (ns / 1e9 / steps)
