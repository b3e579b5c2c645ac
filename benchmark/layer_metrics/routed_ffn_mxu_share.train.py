"""The grouped products against the MXU's peak, in percent: the FLOPs they
need for the rows the held experts REALLY got (the configuration's own
function, ``benchmark/models/<model>.py`` ``routed_ffn_flops``: three
projections, forward and two backward products each, 2 x hidden x width a
row; rows from the window's increment of the train state's ``expert_load``
in the held range, a step) over their device time a step
(``routed_ffn_time_share.train``'s operations) over the chip's bf16 peak. The
products' roofline share: with ~770 rows a group the weights' bytes are far
under the FLOPs' time, so the peak is the MXU's. None without the counter, a
trace, or the function."""
from benchmark import readers_routed, system


def read(run):
    c = getattr(run.get("ctx"), "config", None) or {}
    got = readers_routed.step_runs_and_ns(run, readers_routed.grouped_product(c))
    rows = readers_routed.held_rows_per_step(run)
    if got is None or rows is None or not got[1]:
        return None
    count = getattr(system.model_module(c), "routed_ffn_flops", None)
    if count is None:
        return None
    steps, ns, _ = got
    return 100.0 * count(c, rows) / (ns / 1e9 / steps) / run["peak"]["bf16_flops"]
