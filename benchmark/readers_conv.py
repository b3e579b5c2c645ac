"""What the readers of the convolution mixers' per-layer metrics share: how
an operation only a conv layer's mixer has is told in a trace. An event
carries the instruction, the opcode and the result shape; the widths come
from the configuration file's published keys alone (``hidden_size``,
``conv_L_cache``, ``layer_types``).

Two results no other layer of such a model has: the ``3 x hidden``-wide
input projection ``[.., rows, 3 * hidden]``, and whatever holds a slot's tail,
``[.., (conv_L_cache - 1) * hidden]`` (the tails read at the rows' slots, the
new tails, the pool of them written back; a fusion with several results shows
all of them, so the elementwise chain counts where XLA fuses it with the new
tail). What they cannot tell apart: a result of ``[rows, hidden]`` (the
mixer's gated output where it is a fusion of its own, and the output
projection) looks like every other layer's, so the output projection's time
is in none of these metrics, and :func:`mixer_bytes` leaves its bytes out to
match."""

from __future__ import annotations

import re
from typing import Any, Callable, Dict, Optional

from benchmark import readers, system, trace_reduce

DECODE, PREFILL = "jit__decode_k_paged", "jit__prefill_chunk"


def conv_layers(c: Dict[str, Any]) -> int:
    return list(c.get("layer_types", ())[: c.get("num_hidden_layers", 0)]).count("conv")


def conv_op(c: Dict[str, Any]) -> Optional[Callable[[str], bool]]:
    """A predicate on an operation's (short) name: a result whose minor axis
    is the input projection's ``3 x hidden`` or a tail's ``(L - 1) x hidden``.
    None for a configuration without conv layers."""
    if not conv_layers(c) or not c.get("conv_L_cache"):
        return None
    d, L = c["hidden_size"], c["conv_L_cache"]
    pattern = re.compile(rf"\[(?:\d+,)*(?:{3 * d}|{(L - 1) * d})\]")
    return lambda name: bool(pattern.search(name))


def ops_share_percent(run, program: str) -> Optional[float]:
    c = getattr(run.get("ctx"), "config", None) or {}
    plane = readers.first_plane(run)
    match = conv_op(c)
    if plane is None or match is None:
        return None
    share = trace_reduce.time_share(run["events"], plane, program, match)
    return None if share is None else 100.0 * share


def mixer_bytes(c: Dict[str, Any], rows: float) -> Optional[float]:
    """The bytes the operations :func:`conv_op` tells have to move a decode
    step: the configuration's own ``conv_mixer_bytes`` without the output
    projection (its weights, ``y`` and the output)."""
    count = getattr(system.model_module(c), "conv_mixer_bytes", None)
    return None if count is None else count(c, rows, out_proj=False)
