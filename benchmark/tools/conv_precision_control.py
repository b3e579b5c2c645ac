"""Builder-only: the readings the limits of a ``conv_requests`` cell's runner
check are set from (``benchmark/kinds/conv_requests.py``,
``check_conv_against_reference``). On the chip, once a PR that touches the
conv layers, their tails or the expert layer; ``--rehearsal`` walks it here at
toy sizes. For each seed one JSON line a control, every number of the check
beside ``correct`` (whether the kind's own limits pass it):

* ``stated``: the program as the configuration states it, with
  ``copied_share`` (of the tokens the check decoded greedily, the share equal
  to the token fed before them: a tied table's copy logit would read near 1),
  ``near_tie_share`` (of the (token, expert layer) pairs, those the reference
  would have routed otherwise on the input it had) and ``foreign_prefix``
  (``precision_control.foreign_prefix``: what ``near_tie_sd`` is held against);
* ``stated_unmatched``: the same with the reference routing for itself: what
  those near-ties alone cost the logits (why the check hands the reference the
  program's selections);
* ``weights_int8``: every matrix stored through int8, one scale a row (the
  reference keeps the stated weights): the nearest precision below the stated
  one; it has to come out not correct;
* ``experts_int8``: the experts' three projections alone through int8;
* ``router_bf16``: the router's scores in bfloat16 (``router_swap_share``);
* ``zeroed_tail``: a slot's tails zeroed at the chunk cut inside a page
  (``boundary_rel_err``);
* ``wrong_slot``: every follow-up's tails restored from another sequence's
  snapshot (``boundary_rel_err``);
* ``idle_moves``: the decode step's mask of live rows lost while the
  follow-ups decode, so the idle slots' tails move (``tails_rel_err``).

    python3 benchmark/tools/conv_precision_control.py --config lfm2-8b-a1b-serve-l14 --seeds 1,2
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

CONTROLS = ("stated", "stated_unmatched", "router_bf16", "zeroed_tail", "wrong_slot", "idle_moves", "weights_int8",
            "experts_int8")
SKIP = ("prompt_lengths", "cuts", "snapshots_at", "vectors", "slots", "snapshot_entries")
EXPERT_WEIGHTS = ("we1", "we2", "we3")


def experts_through_int8(params):
    """``precision_control.weights_through_int8`` for the experts' three projections alone."""
    import jax

    from benchmark.tools import precision_control as pc

    lowered = pc.weights_through_int8({name: params["expert_ffn"][name] for name in EXPERT_WEIGHTS})
    return {**params, "expert_ffn": {**params["expert_ffn"], **jax.block_until_ready(lowered)}}


def route_in_bf16(cfg, layer, x2):
    """``ray_tpu.models.transformer.route`` with the scores in bfloat16."""
    import jax
    import jax.numpy as jnp

    logits = x2.astype(jnp.bfloat16) @ layer["router"].astype(jnp.bfloat16)
    scores = jax.nn.sigmoid(logits) if cfg.router_score == "sigmoid" else jax.nn.softmax(logits, axis=-1)
    choose = scores + layer["router_bias"].astype(jnp.bfloat16) if cfg.router_bias else scores
    _, experts = jax.lax.top_k(choose, cfg.expert_top_k)
    weights = jnp.take_along_axis(scores, experts, axis=-1).astype(jnp.float32)
    if cfg.route_norm:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + cfg.route_norm_eps)
    return experts.astype(jnp.int32), weights * cfg.route_scale


def foreign_prefix(config, params, seed: int, tokens: int = 1536):
    """``precision_control.foreign_prefix``'s comparison and statistics
    (through the reference alone: sequence A, and A with its first two thirds
    replaced; at each later position the token the wrong context would serve
    and how far under the top it lies given the right one, in logit spreads),
    and beside its statistics of ``served_tokens`` consecutive positions the
    two a window sample is held to, which checks hundreds of a request's
    tokens at once: the ``worst`` of all the positions and the share of them
    over the limit."""
    import numpy as np

    import jax.numpy as jnp

    from benchmark import system

    cc = config["run"]["correctness"]
    rng = np.random.default_rng([seed, 17])
    a, b = rng.integers(1, config["vocab_size"], size=(2, tokens))
    cut = tokens * 2 // 3
    positions = jnp.arange(cut + 64, tokens)
    ref_logits, _ = system.model_module(config).make_reference(config)
    served = np.asarray(jnp.argmax(ref_logits(params, jnp.asarray(np.concatenate([b[:cut], a[cut:]])), positions), -1))
    right = np.asarray(ref_logits(params, jnp.asarray(a), positions))
    deficit = (right.max(-1) - right[np.arange(len(served)), served]) / right.std(-1)
    n, limit = int(cc["served_tokens"]), cc["near_tie_sd"]
    blocks = deficit[: len(deficit) // n * n].reshape(-1, n).max(-1)
    return {"positions": len(deficit), "median": float(np.median(deficit)), "worst": float(deficit.max()),
            "share_over_limit": float(np.mean(deficit > limit)), "block_max_min": float(blocks.min()),
            "block_max_median": float(np.median(blocks)), "blocks_within_limit": int(np.sum(blocks <= limit)),
            "blocks": len(blocks)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--controls", default=",".join(CONTROLS))
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--qk-norm-gain", type=float, default=None, help="instead of the file's weights.qk_norm_gain")
    args = ap.parse_args()
    controls = args.controls.split(",")

    import jax

    from benchmark import run as runner, system
    from benchmark.kinds.conv_requests import check_conv_against_reference as check, make_params
    from benchmark.tools import precision_control as pc
    from ray_tpu.models import transformer

    config = system.load_json(f"benchmark/configs/{args.config}.json")
    if args.rehearsal:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        config = system.shrink_for_rehearsal(config)
    runner.configure_jax()
    print(json.dumps(system.device_info()), flush=True)
    run = config["run"]
    if args.qk_norm_gain is not None:
        run["weights"] = {**run["weights"], "qk_norm_gain": args.qk_norm_gain}
    cfg = system.model_module(config).program_config(
        config, max_seq_len=run["max_seq_len"], dtype=run["dtype"], param_dtype=run["param_dtype"])

    def stated(seed):
        return jax.block_until_ready(make_params(cfg, config, seed))

    def line(seed, control, out, **more):
        print(json.dumps({"seed": seed, "control": control, "correct": out["ok"],
                          **{k: v for k, v in out.items() if k not in SKIP and k != "ok"}, **more}), flush=True)

    for seed in (int(s) for s in args.seeds.split(",")):
        params = stated(seed)
        if "stated" in controls:
            more = {} if args.rehearsal else {"foreign_prefix": foreign_prefix(config, params, seed)}
            line(seed, "stated", check(cfg, params, config, seed), **more)
        if "stated_unmatched" in controls:
            line(seed, "stated_unmatched", check(cfg, params, config, seed, matched=False))
        if "router_bf16" in controls:
            with pc.swapped_in(transformer, "route", route_in_bf16):
                line(seed, "router_bf16", check(cfg, params, config, seed))
        for fault in ("zeroed_tail", "wrong_slot", "idle_moves"):
            if fault in controls:
                line(seed, fault, check(cfg, params, config, seed, fault=fault))
        jax.tree.map(lambda a: a.delete(), params)
        for control, lower in (("weights_int8", pc.weights_through_int8), ("experts_int8", experts_through_int8)):
            if control not in controls:
                continue
            lowered = lower(make_params(cfg, config, seed))

            def restated(lowered=lowered):
                jax.tree.map(lambda a: a.delete(), lowered)
                return stated(seed)

            line(seed, control, check(cfg, lowered, config, seed, reference_params=restated))
    return 0


if __name__ == "__main__":
    sys.exit(main())
