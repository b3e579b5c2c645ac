"""Builder-only: are the device's stamps and the host's one clock in a
``jax.profiler`` trace? A controlled trace with nothing else to explain it:
on an idle chip, ``n`` times, one jitted program is called inside a
``TraceAnnotation`` and waited for (``block_until_ready``) inside a second,
then the chip rests. A run cannot begin before the call that launches it
begins, and cannot end after the wait for it returns, so with ``lead`` the
time by which the device's stamps are early against the host's:

    call's start - run's start stamp  <=  lead  <=  wait's end - run's end stamp

Prints one JSON line: both bounds' quartiles over the trials (ms), for the
first and the last quarter of the session too (drift), and how many runs are
stamped before their own call. The session is opened as ``benchmark/run.py``
opens it (``host_tracer_level`` 1, no Python tracer).

    python3 benchmark/tools/clock_probe.py [--trials 600] [--rest-ms 4]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def quartiles_ms(ns):
    return [q / 1e6 for q in statistics.quantiles(ns, n=4)] if len(ns) >= 2 else None


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--trials", type=int, default=600)
    ap.add_argument("--rest-ms", type=float, default=4.0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    from benchmark import host_spans, trace_reduce

    @jax.jit
    def _clock_probe(x):
        for _ in range(8):  # about a millisecond on a v5e: long enough to tell start from end
            x = jnp.tanh(x @ x) * 0.5
        return x

    x = jnp.full((2048, 2048), 0.01, jnp.bfloat16)
    _clock_probe(x).block_until_ready()
    trace_dir = tempfile.mkdtemp(prefix="clock-probe-")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        for _ in range(args.trials):
            with TraceAnnotation("llm::call"):
                y = _clock_probe(x)
            with TraceAnnotation("llm::wait"):
                y.block_until_ready()
            time.sleep(args.rest_ms / 1e3)
    finally:
        jax.profiler.stop_trace()
    try:
        events, host = trace_reduce.read_xplane(trace_dir), host_spans.loop_thread_events(trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    calls = [e for e in host if e[1] == "llm::call"]
    waits = [e for e in host if e[1] == "llm::wait"]
    jit_calls = [e for e in host if e[1].startswith("PjitFunction(")]
    planes = trace_reduce.device_planes(events)
    runs = [m for m in trace_reduce.module_events(events, planes[0])
            if trace_reduce.program_name(m[2]) == "jit__clock_probe"] if planes else []
    line = {"device": jax.devices()[0].device_kind, "trials": args.trials, "calls": len(calls), "waits": len(waits),
            "runs": len(runs), "run_ms": quartiles_ms([m[4] for m in runs])}
    if len(calls) == len(waits) == len(runs) == args.trials:
        runs.sort(key=lambda m: m[3])
        lower = [c[2] - m[3] for c, m in zip(calls, runs)]
        upper = [w[2] + w[3] - (m[3] + m[4]) for w, m in zip(waits, runs)]
        q = max(1, args.trials // 4)
        line.update(
            lead_at_least_ms=quartiles_ms(lower), lead_at_most_ms=quartiles_ms(upper),
            lead_at_least_ms_max=max(lower) / 1e6, lead_at_most_ms_min=min(upper) / 1e6,
            first_quarter={"at_least": quartiles_ms(lower[:q]), "at_most": quartiles_ms(upper[:q])},
            last_quarter={"at_least": quartiles_ms(lower[-q:]), "at_most": quartiles_ms(upper[-q:])},
            runs_stamped_before_their_call=sum(v > 0 for v in lower),
            call_ms=quartiles_ms([c[3] for c in calls]), wait_ms=quartiles_ms([w[3] for w in waits]),
        )
        if len(jit_calls) == args.trials:  # the runtime's own event of the call, as in an engine's trace
            line["runs_stamped_before_their_PjitFunction"] = sum(j[2] > m[3] for j, m in zip(jit_calls, runs))
    print(json.dumps(line), flush=True)
    return 0 if planes else 1


if __name__ == "__main__":
    sys.exit(main())
