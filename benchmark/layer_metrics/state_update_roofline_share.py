"""The linear layers' state update against the memory roofline, in percent:
the bytes a decode step's linear layers have to move for the rows that are
live (the configuration's own function, ``benchmark/models/<model>.py``
``state_update_bytes``: per row and layer the float32 state read and
written, the convolution tail, q, k, v, the gates and the output; rows: the
mean of ``active_slots`` in the engine's ``stats()`` over the window) over
the device time a run of ``jit__decode_k_paged`` spends in the operations
that hold the state (``state_update_time_share`` has how they are told)
times the chip's published bandwidth. The same work whatever implements it;
an implementation that also moves idle rows' state, or pads it, reads lower.
None without the counter, a trace or a configuration with linear layers."""
from benchmark import readers, readers_state, system, trace_reduce

PROGRAM = "jit__decode_k_paged"


def read(run):
    c = getattr(run.get("ctx"), "config", None) or {}
    plane = readers.first_plane(run)
    rows = readers.mean_or_none([s["active_slots"] for s in readers.stats_in_window(run)])
    match = readers_state.state_op(c)
    if plane is None or match is None or not rows:
        return None
    count = getattr(system.model_module(c), "state_update_bytes", None)
    steps = len(trace_reduce.program_runs(run["events"], plane).get(PROGRAM, []))
    ns = sum(e[4] for e in trace_reduce.ops_inside(run["events"], plane, PROGRAM) if match(e[2]))
    if count is None or not steps or not ns:
        return None
    return 100.0 * count(c, rows) / (ns / 1e9 / steps) / run["peak"]["hbm_bytes_per_s"]
