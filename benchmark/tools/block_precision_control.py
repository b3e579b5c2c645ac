"""Builder-only, on the chip: readings on both sides of the limits of a
configuration that generates by diffusion over blocks (traffic kind
``block_requests``), through that kind's own comparison (1). For each seed:

* the stated program: ``rel_err``, ``rel_err_same_routing`` (the reference
  following the program's expert selections), ``swap_margin_max_sd`` and
  ``swapped_share``;
* the controls, each the same comparison on a program lowered in one
  respect (``benchmark/tools/precision_control.py`` has the lowerings): the
  router's scores in bfloat16, and every matrix stored through int8 with one
  scale a row (the reference keeps the stated weights);
* ``foreign_prefix`` (``precision_control.foreign_prefix``, through the
  reference alone): what ``near_tie_sd`` is held against.

    python3 benchmark/tools/block_precision_control.py --config sdar-30b-a3b-serve-l6 --seeds 1,2
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

KEYS = ("rel_err", "rel_err_same_routing", "worst_vector_rel_err_same_routing", "swap_margin_max_sd", "swapped_share", "ok")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()

    import jax

    from benchmark import run as runner, system
    from benchmark.kinds.block_requests import check_blocks_against_reference as check
    from benchmark.tools import precision_control as pc

    config = system.load_json(f"benchmark/configs/{args.config}.json")
    if args.rehearsal:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        config = system.shrink_for_rehearsal(config)
    runner.configure_jax()
    print(json.dumps(system.device_info()), flush=True)
    run = config["run"]
    scale = float(run["weights"]["embed_table_scale"])
    cfg = system.model_module(config).program_config(
        config, max_seq_len=run["max_seq_len"], dtype=run["dtype"], param_dtype=run["param_dtype"])

    def stated(seed):
        return jax.block_until_ready(system.make_params(cfg, seed, scale))

    for seed in (int(s) for s in args.seeds.split(",")):
        params = stated(seed)
        line = {"seed": seed, "stated": {k: v for k, v in check(cfg, params, config, seed).items() if k in KEYS}}
        control = pc.with_router_in_bf16(lambda: check(cfg, params, config, seed))
        line["router_bf16"] = {k: control[k] for k in KEYS}
        line["foreign_prefix"] = pc.foreign_prefix(config, params, seed)
        print(json.dumps(line), flush=True)
        jax.tree.map(lambda a: a.delete(), params)
        lowered = pc.weights_through_int8(system.make_params(cfg, seed, scale))

        def restated():
            jax.tree.map(lambda a: a.delete(), lowered)
            return stated(seed)

        control = check(cfg, lowered, config, seed, reference_params=restated)
        print(json.dumps({"seed": seed, "weights_int8": {k: control[k] for k in KEYS}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
