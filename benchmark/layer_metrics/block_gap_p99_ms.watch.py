"""p99 of the client-side gaps between a request's consecutive tokens that
are over 1 ms and end inside the window: where a stream delivers a committed
block a time, the gaps from block to block (the tokens of one block arrive
together). Recorded, judged by nothing: its 99th percentile sits on a step
(a gap holds 0 to 3 prefill chunks). None where no such gap was seen."""
from benchmark import readers, yardstick


def read(run):
    gaps = [1e3 * g for t in run.get("turns", []) for g in yardstick.gaps_ending_in(t.token_times, run["window"])]
    return readers.quantile_or_none([g for g in gaps if g > 1.0], 0.99)
