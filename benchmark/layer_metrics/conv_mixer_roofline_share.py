"""The convolution mixers against the memory roofline, in percent: the bytes a
decode step's conv layers have to move for the rows that are live (the
configuration's own function, ``benchmark/models/<model>.py``
``conv_mixer_bytes``, without the output projection, which the trace cannot
tell from other layers' outputs: the input projection's and the taps' weights
once a layer, and per live row and layer ``h``, the ``3 x hidden``-wide
projection written and read, the tail read and written; rows: the mean of
``active_slots`` in the engine's ``stats()`` over the window) over the device
time a run of ``jit__decode_k_paged`` spends in the operations
``conv_mixer_time_share`` tells, times the chip's published bandwidth. The
mixer is bound by its weights' bytes (2 x 128 rows of FLOPs a weight byte,
against the chip's 240). The same work whatever implements it; an
implementation that also moves idle rows' tails, or copies the pool of them,
reads lower. None without the counter, a trace or a configuration with conv
layers."""
from benchmark import readers, readers_conv, trace_reduce


def read(run):
    c = getattr(run.get("ctx"), "config", None) or {}
    plane = readers.first_plane(run)
    rows = readers.mean_or_none([s["active_slots"] for s in readers.stats_in_window(run)])
    match = readers_conv.conv_op(c)
    if plane is None or match is None or not rows:
        return None
    bytes_ = readers_conv.mixer_bytes(c, rows)
    steps = len(trace_reduce.program_runs(run["events"], plane).get(readers_conv.DECODE, []))
    ns = sum(e[4] for e in trace_reduce.ops_inside(run["events"], plane, readers_conv.DECODE) if match(e[2]))
    if bytes_ is None or not steps or not ns:
        return None
    return 100.0 * bytes_ / (ns / 1e9 / steps) / run["peak"]["hbm_bytes_per_s"]
