"""Builder-only: find a serving cell's knee once, on the chip. After one
set-up it runs the cell's traffic at several rates in one process (a ramp
and a window each, the engine drained in between) and prints one JSON line a
rate: the tails, the TTFT by thirds of the window (a growing backlog shows as
a rising third), the requests without a first token at the window's close,
the output tokens/s, the residence time. The cell's rate is then written
into its traffic file as a number; the benchmark's command never searches.
It is for the serve cells: ``smollm2-1.7b-serve.chat-steady`` and
``.agent-prefix`` (0.8 and 0.35: shares of their knees), and
``.chat-saturated`` (1.3 x the chat knee; its file's ``"backlog":
"expected"`` is honoured, so a request left waiting at the close is not a
failure there).

    python3 benchmark/tools/sweep.py --workload <cell> --rates 2.5,3,3.5 --seconds 40
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()

    from benchmark import manifest, run as runner, serving, system, yardstick

    m = manifest.load()
    cell, config, traffic = runner.load_cell(m, args.workload, args.rehearsal)
    runner.configure_jax()
    print(json.dumps(system.device_info()), flush=True)
    if not args.rehearsal:
        yardstick.peaks(system.device_info()["kind"])
    kind = importlib.import_module(f"benchmark.kinds.{traffic['kind']}")
    ctx = runner.Ctx(manifest=m, cell=cell, config=config, traffic=traffic, seed=args.seed,
                     seconds=args.seconds, trace=False, rehearsal=args.rehearsal)
    served = serving.Served(config, args.seed, runner.log)
    q = yardstick.quantile
    try:
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            p = {**traffic, "rate": rate}
            out = kind.drive(ctx, served, p, args.seconds, seed=args.seed + i)
            end_stats = served.stats()
            w0, w1 = out["window"]
            scored = [t for t in out["turns"] if t.scored and t.token_times]
            thirds = []
            for k in range(3):
                a, b = w0 + k * (w1 - w0) / 3, w0 + (k + 1) * (w1 - w0) / 3
                xs = [1e3 * (t.token_times[0] - t.due) for t in scored if a <= t.due < b]
                thirds.append(round(q(xs, 0.5), 1) if xs else None)
            done = [t for t in out["turns"] if len(t.tokens) == t.max_tokens and t.token_times]
            residence = [t.token_times[-1] - t.due for t in done]
            waiting = sum(1 for t in out["turns"] if t.sent is not None and t.sent <= w1 and not t.error
                          and not (t.token_times and t.token_times[0] <= w1))
            print(json.dumps({
                "rate": rate, "attempted": out["attempted"], "failed": out["failed"],
                "values": {k: (round(v, 2) if v is not None else None) for k, v in out["values"].items()},
                "ttft_p50_by_third_ms": thirds,
                "without_first_token_at_close": waiting, "not_judged": out["waiting"],
                "overtaken": out.get("overtaken"), "window_sample": served.correctness.get("window sample"),
                "after_cancel": {k: end_stats[k] for k in ("queued", "active_slots", "prefilling", "kv_blocks_in_use")},
                "residence_s_mean": round(sum(residence) / len(residence), 2) if residence else None,
                "reasons": out["reasons"],
            }), flush=True)
            deadline = time.time() + 120
            while time.time() < deadline:
                s = served.stats()
                if not (s["queued"] or s["active_slots"] or s["prefilling"]):
                    break
                time.sleep(0.5)
    finally:
        served.close()
    sys.stdout.flush()
    os._exit(0)


if __name__ == "__main__":
    main()
