"""From a profiler trace to numbers. The benchmark's own reduction: every PR
computes the same number in the same way.

A trace is reduced in two stages. :func:`read_xplane` turns jax's
``.xplane.pb`` into plain events ``[plane, line, name, start_ns, dur_ns]``
(the only part that needs jax); everything after works on that list, so it is
tested on a small recorded trace (``benchmark/testdata/``) with no chip.

On a TPU the device planes are named ``/device:TPU:<n>``. Their line
``XLA Modules`` holds one event per run of a jitted program, named
``jit_<function>(<fingerprint>)``; ``XLA Ops`` holds one event per HLO
operation, named by the instruction's whole text (``%copy.3 = bf16[...]{...}
copy(...)``), which :func:`short_name` cuts to ``copy.3 copy bf16[...]``:
instruction, opcode, result shape. Control flow (``while``, ``conditional``,
``call``) shows as one event around its body's events and is left out:
its time is its children's. A Mosaic (Pallas) kernel is a ``custom-call``.
``Async XLA Ops`` holds what runs beside the core (DMA copies, collectives
in flight); it counts toward collective time, not toward busy time.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Sequence  # [plane, line, name, start_ns, dur_ns]
Interval = Tuple[int, int]

MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
CONTAINERS = ("while", "conditional", "call")
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_HLO = re.compile(r"^%(\S+) = (\(.*?\)|\S+) ([\w\-]+)\(")
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|collective-broadcast"
    r"|send|recv)(-.*)?$"
)


def short_name(hlo_text: str) -> str:
    """``%copy.3 = bf16[1,8]{1,0:T(8,128)} copy(...)`` -> ``copy.3 copy bf16[1,8]``."""
    m = _HLO.match(hlo_text)
    if not m:
        return hlo_text[:120]
    shape = re.sub(r"\{[^}]*\}", "", m.group(2))
    return f"{m.group(1)} {m.group(3)} {shape[:80]}"


def opcode(name: str) -> str:
    """The opcode of a (short) operation name; a bare ``fusion.12`` is its own."""
    parts = name.split(" ")
    return parts[1] if len(parts) > 1 else re.sub(r"[.\d]+$", "", parts[0])


def is_collective(name: str) -> bool:
    return bool(COLLECTIVE.match(opcode(name)))


def read_xplane(trace_dir: str) -> List[list]:
    """Every event of every device plane in the newest trace under
    ``trace_dir``, with a ``long`` name where the profiler gives one (the
    operation with its category and shapes)."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        return []
    data = ProfileData.from_file(files[-1])
    events = []
    for plane in data.planes:
        if not _DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name if line.name == MODULE_LINE else short_name(ev.name)
                events.append([plane.name, line.name, name, int(ev.start_ns), int(ev.duration_ns)])
    return events


def device_planes(events: Iterable[Event]) -> List[str]:
    return sorted({e[0] for e in events if _DEVICE_PLANE.match(e[0])},
                  key=lambda p: int(p.rsplit(":", 1)[1]))


def program_name(module_event_name: str) -> str:
    """``jit__decode_k_paged(1234...)`` -> ``jit__decode_k_paged``."""
    return module_event_name.split("(", 1)[0].strip()


def op_events(events: Iterable[Event], plane: str) -> List[Event]:
    """The operations that ran on the core of ``plane``, control flow left out."""
    return [e for e in events if e[0] == plane and e[1] == OP_LINE and opcode(e[2]) not in CONTAINERS]


def module_events(events: Iterable[Event], plane: str) -> List[Event]:
    return sorted((e for e in events if e[0] == plane and e[1] == MODULE_LINE), key=lambda e: e[3])


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    merged: List[List[int]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def total(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The parts of ``a`` (merged) that no interval of ``b`` (merged) covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def busy(events: Iterable[Event], plane: str) -> List[Interval]:
    """The union of the intervals in which an operation ran on ``plane``."""
    return union((e[3], e[3] + e[4]) for e in op_events(events, plane))


def window_of(events: Iterable[Event]) -> Optional[Interval]:
    """The traced window on the device clock: first operation's start to the
    last one's end, over all device planes."""
    ops = [e for e in events if e[1] == OP_LINE and _DEVICE_PLANE.match(e[0])]
    if not ops:
        return None
    return min(e[3] for e in ops), max(e[3] + e[4] for e in ops)


def busy_and_window_s(events: List[Event]) -> Tuple[float, float]:
    """(busy seconds averaged over the device planes present, window seconds)."""
    win = window_of(events)
    planes = device_planes(events)
    if win is None or not planes:
        return 0.0, 0.0
    per_plane = [total(busy(events, p)) for p in planes]
    return sum(per_plane) / len(per_plane) / 1e9, (win[1] - win[0]) / 1e9


def idle_share(events: List[Event]) -> Optional[float]:
    b, w = busy_and_window_s(events)
    return None if w <= 0 else 1.0 - b / w


def program_runs(events: List[Event], plane: str) -> Dict[str, List[int]]:
    """Device durations (ns) of each jitted program's runs on ``plane``."""
    runs: Dict[str, List[int]] = defaultdict(list)
    for e in module_events(events, plane):
        runs[program_name(e[2])].append(e[4])
    return dict(runs)


def ops_inside(events: List[Event], plane: str, program: str) -> List[Event]:
    """The operations that ran inside runs of ``program`` on ``plane``."""
    spans = [(e[3], e[3] + e[4]) for e in module_events(events, plane) if program_name(e[2]) == program]
    if not spans:
        return []
    spans.sort()
    out, j = [], 0
    for op in sorted(op_events(events, plane), key=lambda e: e[3]):
        while j < len(spans) and spans[j][1] <= op[3]:
            j += 1
        if j < len(spans) and spans[j][0] <= op[3] < spans[j][1]:
            out.append(op)
    return out


def time_share(events: List[Event], plane: str, program: Optional[str], match) -> Optional[float]:
    """Share of the operations' device time (inside ``program``, or in all of
    the plane if None) spent in operations whose name ``match`` accepts."""
    ops = ops_inside(events, plane, program) if program else op_events(events, plane)
    whole = sum(e[4] for e in ops)
    if whole <= 0:
        return None
    return sum(e[4] for e in ops if match(e[2])) / whole


def is_custom_call(name: str) -> bool:
    """A Mosaic (Pallas) kernel shows as a custom-call in the XLA Ops line."""
    return opcode(name) == "custom-call"


def collective_and_exposed_s(events: List[Event], plane: str) -> Tuple[float, float]:
    """(seconds in which a collective operation was running or in flight,
    seconds of them during which no other operation ran on that chip's core)."""
    ops = op_events(events, plane)
    in_flight = [e for e in events if e[0] == plane and e[1] == ASYNC_LINE and is_collective(e[2])]
    coll = union((e[3], e[3] + e[4]) for e in ops + in_flight if is_collective(e[2]))
    comp = union((e[3], e[3] + e[4]) for e in ops if not is_collective(e[2]))
    return total(coll) / 1e9, total(subtract(coll, comp)) / 1e9


def top_ops(events: List[Event], plane: str, n: int = 10) -> List[List]:
    """The operations that took most device time: [[name, seconds], ...]."""
    by_name: Dict[str, int] = defaultdict(int)
    for e in op_events(events, plane):
        by_name[e[2]] += e[4]
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def idle_gaps(events: List[Event], plane: str, n: int = 10) -> List[List]:
    """The idle time of the window, by what surrounded it: a gap between two
    program runs is named ``<before> -> <after>``, a gap inside a run
    ``inside <program>``; [[name, seconds], ...], longest total first."""
    win = window_of(events)
    if win is None:
        return []
    gaps = subtract([win], busy(events, plane))
    mods = [(e[3], e[3] + e[4], program_name(e[2])) for e in module_events(events, plane)]
    by_name: Dict[str, int] = defaultdict(int)
    for s, e in gaps:
        inside = next((m for m in mods if m[0] <= s and e <= m[1]), None)
        if inside is not None:
            by_name[f"inside {inside[2]}"] += e - s
            continue
        before = max((m for m in mods if m[1] <= s + 1), key=lambda m: m[1], default=None)
        after = min((m for m in mods if m[0] >= e - 1), key=lambda m: m[0], default=None)
        name = f"{before[2] if before else 'start'} -> {after[2] if after else 'end'}"
        by_name[name] += e - s
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def breakdown(events: List[Event]) -> Optional[Dict[str, List]]:
    planes = device_planes(events)
    if not planes:
        return None
    return {"device_ops": top_ops(events, planes[0]), "idle_gaps": idle_gaps(events, planes[0])}
