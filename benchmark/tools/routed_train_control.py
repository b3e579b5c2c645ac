"""Builder-only: the readings the limits of ``routed_train_steps``' comparison
are set from, each through the kind's own ``held_before_the_window`` (its
``compared`` and ``reasons``): on the chip, at the run sizes, for each seed

* ``stated``: the program as the configuration states it. The limits lie
  ABOVE the largest of these over the seeds, and it comes out correct;
* a control in the program's place, which has to come out NOT correct by one
  of the cell's limits: ``reference:<control>`` (the reference made another
  function, one thing at a time, ``benchmark/models/<model>.py`` ``CONTROLS``
  and ``CHIP_CONTROLS``, standing where the float32 program's gradients do:
  ``bf16_products`` and ``bf16_params`` are the precision below the stated
  one), ``bf16_state`` (the program's parameters kept in bfloat16: the first
  update vanishes in their rounding), ``unchanged_state`` (the update thrown
  away: reads exactly 1).

    python3 benchmark/tools/routed_train_control.py --config moonlight-16b-a3b-train-ep8 \\
        --seeds 1,2 --controls stated,reference:bf16_products,bf16_state [--rehearsal]

One JSON line a (seed, control): ``compared`` (value and limit), ``reasons``,
``correct``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", default="routed-train-steps")
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--controls", default="stated")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    if args.rehearsal:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from benchmark import system
    from benchmark.kinds import routed_train_steps as kind
    from benchmark.run import configure_jax

    configure_jax()
    config = system.load_json(f"benchmark/configs/{args.config}.json")
    traffic = system.load_json(f"benchmark/traffic/{args.traffic}.json")
    if args.rehearsal:
        config = system.shrink_for_rehearsal(config)
    for seed in (int(s) for s in args.seeds.split(",")):
        for control in args.controls.split(","):
            held = kind.held_before_the_window(config, traffic, seed, lambda m: print(m, file=sys.stderr, flush=True),
                                               None if control == "stated" else control)
            print(json.dumps({"seed": seed, "control": control, "correct": not held["reasons"], "reasons": held["reasons"],
                              "compared": {k: {"value": v, "limit": lim} for k, (v, lim) in held["compared"].items()}}),
                  flush=True)
            del held   # the state leaves the chip before the next one is made
    return 0


if __name__ == "__main__":
    sys.exit(main())
