"""Forwards of a live row (denoise and commit) the engine spent for each
token it emitted inside the window (``stats()`` deltas of
``block_row_forwards`` over ``tokens_emitted``): 5 forwards for a block of 4
tokens = 1.25 at 4 denoising steps; a first block the prompt's tail opened
and a last one cut at ``max_tokens`` move it by under 0.02. What a fused
commit, or fewer steps a block, would lower. None where ``stats()`` has no
such counters (a program that decodes a token a step)."""
from benchmark import readers


def read(run):
    p = run["probe"]
    if p.stats_close is None or "block_row_forwards" not in p.stats_close[1]:
        return None
    forwards, tokens = readers.counter_delta(run, "block_row_forwards"), readers.counter_delta(run, "tokens_emitted")
    return forwards / tokens if forwards is not None and tokens else None
