"""Median time of the whole train steps inside the window, host clock closed
on the loss read back (``block_until_ready`` and a transfer)."""
from benchmark import readers


def read(run):
    return readers.median_or_none([1e3 * (e - s) for s, e in run.get("steps", [])])
