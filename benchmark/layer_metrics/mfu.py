"""Model FLOP/s utilisation in percent: the FLOPs forward and backward need
per token (recomputation not counted) times the tokens of one step, over the
median time of the window's whole steps, over chips times the chip's
published bf16 peak. From the median step so that the profiler's own start
and stop, which fall between steps of a traced run, do not count."""
import statistics

from benchmark import yardstick


def read(run):
    steps = [e - s for s, e in run.get("steps", [])]
    if not steps:
        return None
    rate = run["tokens_per_step"] / statistics.median(steps)
    return yardstick.mfu_percent(run["flops_per_token"], rate, run["chips"], run["peak"]["bf16_flops"])
