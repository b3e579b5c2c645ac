"""``BENCHMARK.json``: loading, validation, and finding a cell's files by the
names in it. Pure Python. Nothing here lists cells, configurations or
metrics: a new one is a new entry in the manifest plus new files."""

from __future__ import annotations

import json
import os
import re
from typing import Any, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
MAX_RUN_SECONDS = 51
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}


def load(path: str = None) -> Dict[str, Any]:
    with open(path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(text, what, errors):
    if not isinstance(text, str) or not 1 <= len(text) <= 200 or "\n" in text or "\t" in text:
        errors.append(f"{what}: 1 to 200 characters on one line, no tab")


def reports(metric: Dict[str, Any], cell: str) -> bool:
    """Whether ``cell`` reports ``metric`` (no ``workloads`` key: every cell)."""
    return "workloads" not in metric or cell in metric["workloads"]


def problems(m: Dict[str, Any], root: str = None) -> List[str]:
    """Every way the manifest breaks the contract; empty if it is sound.
    ``root``: also check that the files it names exist under it."""
    errors: List[str] = []
    if set(m) != TOP_KEYS:
        errors.append(f"top-level keys must be exactly {sorted(TOP_KEYS)}")
        return errors
    paths = m["paths"]
    if not (1 <= len(paths) <= 16) or not all(isinstance(p, str) and PATH.match(p) for p in paths):
        errors.append("paths: 1 to 16 relative directories of letters, digits, _ . - /")
        return errors

    def under_paths(f):
        return (isinstance(f, str) and PATH.match(f) and not f.startswith("/") and ".." not in f.split("/")
                and any(f.startswith(p.rstrip("/") + "/") for p in paths))

    if not (1 <= len(m["command"]) <= 32):
        errors.append("command: 1 to 32 strings")
    for word in m["command"]:
        _line(word, f"command word {word!r}", errors)
        if isinstance(word, str) and (word.startswith("/") or ".." in word.split("/")):
            errors.append(f"command word {word!r} leaves the repo")
    rs = m["run_seconds"]
    if not isinstance(rs, int) or isinstance(rs, bool) or not 1 <= rs <= MAX_RUN_SECONDS:
        errors.append(f"run_seconds: a whole number from 1 to {MAX_RUN_SECONDS}")

    def names(entries, what, keys, optional=()):
        seen = set()
        for e in entries:
            n = e.get("name")
            if not isinstance(n, str) or not NAME.match(n):
                errors.append(f"{what} name {n!r}: letters, digits, _ . - ; at most 64")
            if n in seen:
                errors.append(f"{what} name {n!r} appears twice")
            seen.add(n)
            extra = set(e) - set(keys) - set(optional)
            missing = set(keys) - set(e)
            if extra or missing:
                errors.append(f"{what} {n!r}: keys must be {sorted(keys)} (+{sorted(optional)}); "
                              f"extra {sorted(extra)}, missing {sorted(missing)}")
        return seen

    configs, cells = m["configs"], m["workloads"]
    if not 1 <= len(configs) <= 24:
        errors.append("configs: 1 to 24")
    if not 1 <= len(cells) <= 24:
        errors.append("workloads: 1 to 24")
    config_names = names(configs, "config", ("name", "source", "file", "reduced", "why"))
    cell_names = names(cells, "workload", ("name", "config", "traffic", "chips", "why"))
    files = set()
    for c in configs:
        _line(c.get("source"), f"config {c.get('name')!r} source", errors)
        _line(c.get("why"), f"config {c.get('name')!r} why", errors)
        f = c.get("file")
        if not under_paths(f):
            errors.append(f"config file {f!r} is not under paths")
        elif f in files:
            errors.append(f"config file {f!r} is used twice")
        elif root is not None and not os.path.isfile(os.path.join(root, f)):
            errors.append(f"config file {f!r} does not exist")
        files.add(f)
        red = c.get("reduced", [])
        if len(red) > 16 or not all(isinstance(k, str) and NAME.match(k) for k in red):
            errors.append(f"config {c.get('name')!r}: reduced is at most 16 names")
        for k in red:
            if k.endswith(("_dim", "_rank")) or re.search(
                    r"hidden_size|intermediate|latent|state_size|proj|head_dim|expansion|experts_per_tok", k):
                errors.append(f"config {c.get('name')!r}: reduced may not name the width {k!r}")
    pairs = set()
    for w in cells:
        _line(w.get("why"), f"workload {w.get('name')!r} why", errors)
        if w.get("config") not in config_names:
            errors.append(f"workload {w.get('name')!r}: unknown config {w.get('config')!r}")
        t = w.get("traffic")
        if not isinstance(t, str) or not NAME.match(t):
            errors.append(f"workload {w.get('name')!r}: traffic {t!r} is not a name")
        elif root is not None and traffic_file(t, root) is None:
            errors.append(f"workload {w.get('name')!r}: no traffic file for {t!r}")
        if w.get("chips") not in (1, 4):
            errors.append(f"workload {w.get('name')!r}: chips is 1 or 4")
        pair = (w.get("config"), t)
        if pair in pairs:
            errors.append(f"config and traffic {pair} appear twice")
        pairs.add(pair)
    unused = config_names - {w.get("config") for w in cells}
    if unused:
        errors.append(f"configs used by no cell: {sorted(unused)}")
    four = sum(1 for w in cells if w.get("chips") == 4)
    if four > max(1, len(cells) // 4):
        errors.append(f"{four} of {len(cells)} cells ask for 4 chips; at most 25% (one always may)")

    e2e, layer = m["end_to_end"], m["per_layer"]
    if not 1 <= len(e2e) <= 16:
        errors.append("end_to_end: 1 to 16 metrics")
    if not 1 <= len(layer) <= 128:
        errors.append("per_layer: 1 to 128 metrics")
    e2e_names = names(e2e, "end_to_end metric", ("name", "unit", "better", "bound", "source"), ("workloads",))
    layer_names = names(layer, "per_layer metric",
                        ("name", "unit", "better", "source", "layer", "moves"), ("workloads",))
    if e2e_names & layer_names:
        errors.append(f"metric names used twice: {sorted(e2e_names & layer_names)}")
    if "setup_s" not in e2e_names:
        errors.append("end_to_end must include setup_s")
    for x in e2e + layer:
        n = x.get("name")
        if not isinstance(x.get("unit"), str) or not UNIT.match(x.get("unit", "")):
            errors.append(f"metric {n!r}: unit {x.get('unit')!r} is 1 to 16 of letters, digits, _ / % . -")
        if x.get("better") not in ("lower", "higher"):
            errors.append(f"metric {n!r}: better is lower or higher")
        if x.get("source") not in SOURCES:
            errors.append(f"metric {n!r}: source is one of {SOURCES}")
        for c in x.get("workloads", []):
            if c not in cell_names:
                errors.append(f"metric {n!r} lists unknown workload {c!r}")
    for x in e2e:
        b = x.get("bound")
        if not isinstance(b, (int, float)) or isinstance(b, bool) or not 0 < b <= 0.1:
            errors.append(f"metric {x.get('name')!r}: bound is a share in (0, 0.1]")
        if x.get("source") not in ("host_clock", "device_trace"):
            errors.append(f"end-to-end metric {x.get('name')!r}: source is host_clock or device_trace")
        if x.get("name") == "setup_s" and "workloads" in x:
            errors.append("setup_s is reported by every cell")
    by_name = {x.get("name"): x for x in e2e}
    for x in layer:
        _line(x.get("layer"), f"metric {x.get('name')!r} layer", errors)
        moved = by_name.get(x.get("moves"))
        if moved is None:
            errors.append(f"metric {x.get('name')!r} moves {x.get('moves')!r}, not an end-to-end metric")
            continue
        for c in sorted(cell_names):
            if reports(x, c) and not reports(moved, c):
                errors.append(f"metric {x.get('name')!r} is reported in {c!r}, "
                              f"which does not report {moved['name']!r}")
    for c in sorted(cell_names):
        if sum(1 for x in e2e if reports(x, c)) < 2:
            errors.append(f"cell {c!r} reports no end-to-end metric besides setup_s")
        if not any(reports(x, c) for x in layer):
            errors.append(f"cell {c!r} reports no per-layer metric")
    if root is not None:
        for x in layer:
            if x.get("name") and layer_metric_file(x["name"], paths, root) is None:
                errors.append(f"per-layer metric {x['name']!r} has no reader file")
    if len(json.dumps(m)) > 64 * 1024:
        errors.append("BENCHMARK.json is over 64 KiB")
    return errors


def validate(m: Dict[str, Any], root: str = None) -> None:
    errs = problems(m, root)
    if errs:
        raise ValueError("BENCHMARK.json: " + "; ".join(errs))


def cell(m: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in m["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has {[w['name'] for w in m['workloads']]}")


def config_entry(m: Dict[str, Any], name: str) -> Dict[str, Any]:
    return next(c for c in m["configs"] if c["name"] == name)


def traffic_file(traffic: str, root: str = ROOT):
    """A traffic mix is ``benchmark/traffic/<traffic>.json``."""
    p = os.path.join(root, "benchmark", "traffic", traffic + ".json")
    return p if os.path.isfile(p) else None


def layer_metric_file(name: str, paths=("benchmark",), root: str = ROOT):
    """A per-layer metric's reader is ``<path>/layer_metrics/<name>.py``."""
    for p in paths:
        f = os.path.join(root, p, "layer_metrics", name + ".py")
        if os.path.isfile(f):
            return f
    return None


def metrics_of(m: Dict[str, Any], group: str, cell_name: str) -> List[Dict[str, Any]]:
    return [x for x in m[group] if reports(x, cell_name)]
