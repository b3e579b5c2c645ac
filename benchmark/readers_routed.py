"""What the readers of a trained expert layer's per-layer metrics share: how
the grouped products and the flash kernels are told in a trace of a train
step, and the rows the held experts got in the window. An event carries the
instruction, the opcode and the result shape; the sizes come from the
configuration file's published keys and its ``run`` group alone."""

from __future__ import annotations

import re
from typing import Any, Callable, Dict, Optional

from benchmark import readers, trace_reduce

TRAIN_STEP = "jit_train_step"


def _sizes(c: Dict[str, Any]):
    run = c.get("run") or {}
    if not c.get("experts_held") or "seq_len" not in run or "moe_intermediate_size" not in c:
        return None
    lo, hi = c["experts_held"]
    return hi - lo, c["hidden_size"], c["moe_intermediate_size"]


def grouped_product(c: Dict[str, Any]) -> Optional[Callable[[str], bool]]:
    """A predicate on an operation's (short) name: one of the expert layer's
    grouped products of a train step, all of them custom-calls. By name: the
    Mosaic kernel, which the program calls ``grouped_matmul`` (forward, its
    recomputation ``jvp_grouped_matmul_``, and since the rows' gradient is the
    same kernel ``grouped_matmul_bwd``), and XLA's ``ragged-dot`` (the
    weights' gradient). By shape, whatever they are called: a two-dimensional
    result of sorted assignments by the hidden or the expert width (no other
    custom-call of the step has one), or the held experts' ``[held, hidden,
    width]`` or its transpose. None for a configuration without a share of
    experts or a ``run`` group."""
    s = _sizes(c)
    if s is None:
        return None
    held, d, f = s
    shape = re.compile(rf" \w+\[(?:\d+,(?:{d}|{f})|{held},{d},{f}|{held},{f},{d})\]$")

    def match(name: str) -> bool:
        if trace_reduce.opcode(name) != "custom-call":
            return False
        instruction = name.split(" ", 1)[0]
        return "grouped_matmul" in instruction or instruction.startswith("ragged-dot") or bool(shape.search(name))

    return match


def flash_kernel(c: Dict[str, Any]) -> Optional[Callable[[str], bool]]:
    """The flash kernels of expanded latent attention in a train step:
    custom-calls whose results are ``[sequences x heads, T, key size or value
    size]`` (forward: values and the row sums; backward: the queries' and
    the keys' and values' gradients)."""
    run = c.get("run") or {}
    if "seq_len" not in run or "qk_nope_head_dim" not in c:
        return None
    bh, T = run["batch"] * c["num_attention_heads"], run["seq_len"]
    dk, dv = c["qk_nope_head_dim"] + c["qk_rope_head_dim"], c["v_head_dim"]
    shape = re.compile(rf"\[{bh},{T},(?:{dk}|{dv})\]")
    return lambda name: trace_reduce.opcode(name) == "custom-call" and bool(shape.search(name))


def step_runs_and_ns(run, match) -> Optional[tuple]:
    """(train steps in the traced part, device ns of the operations ``match``
    accepts inside them, device ns of all their operations)."""
    plane = readers.first_plane(run)
    if plane is None or match is None:
        return None
    events = run["events"]
    steps = len(trace_reduce.program_runs(events, plane).get(TRAIN_STEP, []))
    ops = trace_reduce.ops_inside(events, plane, TRAIN_STEP)
    whole = sum(e[4] for e in ops)
    if not steps or whole <= 0:
        return None
    return steps, sum(e[4] for e in ops if match(e[2])), whole


def held_rows_per_step(run) -> Optional[float]:
    """Assignments the held experts got a step, over all expert layers: the
    window's increment of the train state's ``expert_load`` in the held
    range, over the steps it covers."""
    c = getattr(run.get("ctx"), "config", None) or {}
    load, steps = run.get("expert_load_window"), run.get("expert_load_steps")
    if load is None or not steps or not c.get("experts_held"):
        return None
    lo, hi = c["experts_held"]
    return float(load[:, lo:hi].sum()) / steps
