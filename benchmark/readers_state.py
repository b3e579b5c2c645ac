"""What the readers of the recurrent state's per-layer metrics share: how an
operation of a linear (gated delta rule) layer is told in a trace. An event
carries the instruction, the opcode and the result shape; the state's
dimensions come from the configuration file's ``linear_*`` keys alone."""

from __future__ import annotations

import re
from typing import Any, Callable, Dict, Optional

from benchmark import readers, trace_reduce

CHUNK = 64  # positions a chunk of the recurrence's WY form holds (``ray_tpu/ops/gated_delta.py`` ``CHUNK``)


def lane_group(heads: int, dv: int) -> int:
    """Heads that may share a row of the state so that it fills whole
    128-lane tiles: the fewest, a divisor of ``heads``; 1 where none does."""
    for g in range(1, heads + 1):
        if heads % g == 0 and (g * dv) % 128 == 0:
            return g
    return 1


def state_op(c: Dict[str, Any], chunked: bool = False) -> Optional[Callable[[str], bool]]:
    """A predicate on an operation's (short) name: its result holds the
    recurrent state, ``[.., heads, dk, dv]`` float32 or the lane-folded
    ``[.., heads / g, dk, g * dv]``; with ``chunked`` also a result with
    ``heads, CHUNK`` adjacent. None for a configuration without linear layers."""
    if not c.get("linear_num_value_heads"):
        return None
    H, dk, dv = c["linear_num_value_heads"], c["linear_key_head_dim"], c["linear_value_head_dim"]
    g = lane_group(H, dv)
    shapes = [rf"f32\[(?:\d+,)*{H},{dk},{dv}\]", rf"f32\[(?:\d+,)*{H // g},{dk},{g * dv}\]"]
    if chunked:
        shapes.append(rf"\[(?:\d+,)*{H},{CHUNK}(?:,\d+)*\]")
    pattern = re.compile("|".join(shapes))
    return lambda name: bool(pattern.search(name))


def state_ops_share_percent(run, program: str, chunked: bool = False) -> Optional[float]:
    c = getattr(run.get("ctx"), "config", None) or {}
    plane = readers.first_plane(run)
    match = state_op(c, chunked)
    if plane is None or match is None:
        return None
    share = trace_reduce.time_share(run["events"], plane, program, match)
    return None if share is None else 100.0 * share
