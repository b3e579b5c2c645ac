"""The benchmark's command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process. It validates ``BENCHMARK.json``, refuses to start without a TPU
whose ``device_kind`` is in the peaks table (and without as many chips as
the cell asks for), builds the cell's system from the seed, checks it
against the plain reference, warms the cell's own shapes, measures for
``--seconds`` and prints, last, one JSON line with the contract's keys.
``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` its
per-layer metrics, with a profiler trace of a few seconds of the window.
Everything else it prints goes to stderr.

``--rehearsal`` is the builder's: the same command at the toy sizes each
file lists under ``"rehearsal"``, on whatever backend jax has. It proves
nothing about the chip, reports every time under ``rehearsal_host_only`` and
no device metric at all; the driver never passes it.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import manifest, trace_reduce, yardstick  # noqa: E402


def log(msg: str) -> None:
    print(f"[benchmark +{time.perf_counter() - _T_START:7.1f}s] {msg}", file=sys.stderr, flush=True)


class Probe:
    """What a run observes besides its own clock: the engine's counters at
    the window's ends and in between, compilations, and (traced runs) a
    profiler trace of part of the window."""

    def __init__(self, ctx, served, window):
        self.ctx, self.served, self.window = ctx, served, window
        self.tracing = self.traced = False
        self.trace_started = 0.0
        self.trace_dir = None
        self.events = []
        self.stats_open = self.stats_close = None
        self.sampler = None
        self._timer = None
        if served is not None and ctx.trace:
            from benchmark import serving

            self.sampler = serving.Sampler(served)
            self._timer = threading.Thread(target=self._serve_timeline, daemon=True)
            self._timer.start()

    def _serve_timeline(self):
        from benchmark import serving

        w0, w1 = self.window
        serving.sleep_until(w0)
        self.stats_open = (serving.now(), self.served.stats())
        self.sampler.start()
        serving.sleep_until(w0 + 0.25 * (w1 - w0))
        self.start_trace()
        serving.sleep_until(min(w1, serving.now() + float(self.ctx.traffic["trace_s"])))
        self.stop_trace()

    def start_trace(self):
        import jax

        self.trace_dir = tempfile.mkdtemp(prefix="benchmark-trace-")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self.trace_started = time.perf_counter()
        self.tracing = True

    def stop_trace(self):
        import jax

        if not self.tracing:
            return
        jax.profiler.stop_trace()
        self.tracing, self.traced = False, True

    def window_closed(self):
        if self.served is not None and self.ctx.trace:
            self.stats_close = (time.perf_counter(), self.served.stats())
            self.sampler.stop()
        if self._timer is not None:
            self._timer.join(timeout=60)
        self.stop_trace()

    def read_trace(self):
        if self.trace_dir is None:
            return
        try:
            self.events = trace_reduce.read_xplane(self.trace_dir)
        finally:
            shutil.rmtree(self.trace_dir, ignore_errors=True)


class Ctx:
    def __init__(self, **kw):
        self.__dict__.update(kw)
        self.log = log

    def probe(self, served, window):
        return Probe(self, served, window)


def load_reader(name: str, paths):
    """The reader of one per-layer metric: ``<path>/layer_metrics/<name>.py``
    with a function ``read(run) -> number or None``."""
    f = manifest.layer_metric_file(name, paths, ROOT)
    spec = importlib.util.spec_from_file_location("benchmark_layer_metric_" + name.replace(".", "_").replace("-", "_"), f)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_cell(m, name: str, rehearsal: bool):
    """The cell's entry, its configuration and its traffic mix, found by the
    names in the manifest (at toy sizes for a rehearsal)."""
    from benchmark import system

    cell = manifest.cell(m, name)
    config = system.load_json(manifest.config_entry(m, cell["config"])["file"])
    with open(manifest.traffic_file(cell["traffic"], ROOT)) as f:
        traffic = json.load(f)
    if rehearsal:
        config = system.shrink_for_rehearsal(config)
        traffic = {**traffic, **traffic.get("rehearsal", {})}
    return cell, config, traffic


def configure_jax() -> str:
    """Place the persistent compile cache and keep every program in it."""
    import jax

    from ray_tpu.ops import backend

    cache_dir = backend.use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearsal", action="store_true", help="builder-only: toy sizes, any backend")
    args = ap.parse_args(argv)

    m = manifest.load()
    manifest.validate(m, ROOT)
    from benchmark import system

    cell, config, traffic = load_cell(m, args.workload, args.rehearsal)
    if args.rehearsal:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        if cell["chips"] > 1:
            os.environ.setdefault("XLA_FLAGS", f"--xla_force_host_platform_device_count={cell['chips']}")

    cache_dir = configure_jax()
    device = system.device_info()
    log(f"device {device}; compile cache {cache_dir}; cell {cell['name']} seed {args.seed}")
    if not args.rehearsal:
        if device["platform"] != "tpu":
            log(f"no accelerator: jax reports platform {device['platform']!r}; there is no CPU fallback")
            return 2
        try:
            peak = yardstick.peaks(device["kind"])
        except LookupError as exc:
            log(str(exc))
            return 2
    else:
        peak = {"bf16_flops": float("nan"), "hbm_bytes_per_s": float("nan")}
    if device["count"] < cell["chips"]:
        log(f"the cell needs {cell['chips']} chips; jax has {device['count']}")
        return 2

    from benchmark import serving

    watch = serving.CompileWatch()
    kind = importlib.import_module(f"benchmark.kinds.{traffic['kind']}")
    ctx = Ctx(manifest=m, cell=cell, config=config, traffic=traffic, seed=args.seed,
              seconds=args.seconds, trace=bool(args.trace), rehearsal=args.rehearsal,
              t_start=_T_START, peak=peak, device=device)
    run = kind.run(ctx)
    probe = run["probe"]
    window = run["window"]
    run["setup_s"] = window[0] - _T_START
    run["compiles_in_window"] = watch.count_in(*window)
    run.update(ctx=ctx, peak=peak, chips=cell["chips"])
    probe.read_trace()
    run["events"] = probe.events

    device["memory_peak_bytes"] = run.get("memory_peak_bytes") or system.memory_peak_bytes(cell["chips"])
    metrics = {}
    if not args.trace:
        values = dict(run["values"], setup_s=run["setup_s"])
        for x in manifest.metrics_of(m, "end_to_end", cell["name"]):
            v = values.get(x["name"])
            if v is None:
                run["reasons"].append(f"end-to-end metric {x['name']} could not be measured")
                continue
            metrics[x["name"]] = {"value": v, "unit": x["unit"]}
    else:
        for x in manifest.metrics_of(m, "per_layer", cell["name"]):
            v = load_reader(x["name"], m["paths"])(run)
            if v is not None:
                metrics[x["name"]] = {"value": v, "unit": x["unit"]}
        busy_s, window_s = trace_reduce.busy_and_window_s(run["events"])
        device["busy_s"], device["window_s"] = busy_s, window_s
        if not busy_s > 0 and not args.rehearsal:
            run["reasons"].append("the traced window holds no device operation")
    if run["compiles_in_window"]:
        run["reasons"].append(f"{run['compiles_in_window']} programs compiled inside the window")
    for reason in run["reasons"]:
        log(f"NOT CORRECT: {reason}")
    log(f"values {run['values']} setup_s {run['setup_s']:.1f} attempted {run['attempted']} failed {run['failed']}")
    line = {"correct": not run["reasons"], "attempted": run["attempted"], "failed": run["failed"],
            "metrics": metrics, "device": device}
    if args.trace:
        bd = trace_reduce.breakdown(run["events"])
        if bd is not None:
            line["breakdown"] = bd
    if args.rehearsal:
        # no time from a rehearsal may stand under a metric's name
        line = {"rehearsal_host_only": True, "correct": line["correct"], "attempted": line["attempted"],
                "failed": line["failed"], "metric_names": sorted(metrics), "device": system.device_info()}
    # each number that decided ``correct`` beside its limit: last on stderr, and last in the line
    line["compared"] = {k: {"value": v, "limit": lim} for k, (v, lim) in run.get("compared", {}).items()}
    for k, x in line["compared"].items():
        log(f"compared {k}: {x['value']} (limit {x['limit']})")
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)  # daemon stream readers may still sit in a queue get; every child process is already stopped
