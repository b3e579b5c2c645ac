"""Builder-only: what the logits check of an expert-layer configuration is
made of, and its lower-precision controls. On the chip, once a PR that
touches the expert layer; the tier-1 tests run the same functions at toy size
(``tests/perfbench/test_trinity_cell.py``).

Top-k routing is not continuous: the bf16 program and the float32 reference
agree on a token's experts except where the k-th and (k+1)-th score lie
within the rounding of the router's input, and there one swapped expert
moves the token's logits by far more than the arithmetic does. The benchmark's
own comparison (``serving.check_paged_against_reference``) cannot hold the
two apart: it hands the reference the tokens, not the program's selections.
This tool can. Every reading below comes out of that same function, with
something swapped in around it. For each seed one JSON line:

* ``rel_err``: the check as the configuration states it;
* ``rel_err_same_routing``: the same check with the reference following the
  program's selections (recorded from the program's own router, fed through
  the reference's ``on_router``): the arithmetic alone;
* ``swapped_share``: the share of (token, expert layer) pairs of the checked
  sequences whose selected set differs from what the reference, on the same
  routing upstream, would have selected itself; ``swapped_margin_max_sd`` /
  ``_p50_sd``: over those pairs, how far under the reference's k-th score the
  program's lowest choice lies, in spreads of that token's scores: every swap
  is a near-tie;
* the controls, each the stated check on a program lowered in one respect:
  ``rel_err_router_bf16`` (the router's scores in bfloat16) and
  ``rel_err_weights_int8`` (every matrix stored through int8, one scale a
  row; the reference keeps the stated weights), with ``_same_routing``
  beside each where the reference follows the lowered program's selections;
* ``foreign_prefix``: what ``near_tie_sd`` is held against. Through the
  reference alone: the first two thirds of a sequence replaced, the token a
  wrong context would serve at each later position, and how far under the top
  it lies given the right one; ``block_max_min`` is the smallest worst token
  of any ``served_tokens`` consecutive positions, the statistic the served
  checks take.

    python3 benchmark/tools/precision_control.py --config trinity-mini-serve-l5 --seeds 1,2
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


@contextlib.contextmanager
def swapped_in(owner, name, value):
    real = getattr(owner, name)
    setattr(owner, name, value)
    try:
        yield real
    finally:
        setattr(owner, name, real)


# --- the program, lowered in one respect ---------------------------------------------------
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
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return experts.astype(jnp.int32), weights * cfg.route_scale


def with_router_in_bf16(fn):
    """Run ``fn()`` with the program's router replaced by :func:`route_in_bf16`."""
    from ray_tpu.models import transformer

    with swapped_in(transformer, "route", route_in_bf16):
        return fn()


def weights_through_int8(params):
    """The parameter tree with every matrix stored through int8 (one absmax
    scale a row of the last axis) and read back in its own type, in place
    (the argument's buffers are donated). Gains of one come back exact; the
    router's selection bias, a float32 buffer, is left as it is."""
    import jax
    import jax.numpy as jnp

    def lower(path, w):
        if path[-1].key == "router_bias" or w.ndim < 2:
            return w
        x = w.astype(jnp.float32)
        scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0 + 1e-30
        return (jnp.round(x / scale) * scale).astype(w.dtype)

    return jax.jit(lambda p: jax.tree_util.tree_map_with_path(lower, p), donate_argnums=0)(params)


# --- the reference, following the program's routing --------------------------------------
class Routing:
    """The program's selections, recorded in program order from its own
    router, and the reference made to follow them."""

    def __init__(self, config):
        self.config = config
        self.calls = []      # experts [rows, k] of each router call, in program order
        self.pairs = 0
        self.swapped = 0
        self.margins = []

    def route(self, real):
        import jax

        def recording(cfg, layer, x2):
            experts, weights = real(cfg, layer, x2)
            jax.debug.callback(lambda e: self.calls.append(e), experts, ordered=True)
            return experts, weights

        return recording

    def _selections(self, upto: int, length: int, T: int, row: int):
        """[expert layers, T, k] for the sequence whose prefill begins at
        router call ``upto``: its chunks' selections, then row ``row`` of each
        decode step's; -1 where the program computed nothing."""
        import numpy as np

        c, run = self.config, self.config["run"]
        layers = c["num_hidden_layers"] - c["num_dense_layers"]
        C, steps = run["prefill_chunk_tokens"], run["correctness"]["decode_steps"]
        out = np.full((layers, T, c["num_experts_per_tok"]), -1, np.int32)
        for chunk in range(-(-length // C)):
            n = min(C, length - chunk * C)
            for j in range(layers):
                out[j, chunk * C : chunk * C + n] = self.calls[upto + chunk * layers + j][:n]
        decode = len(self.calls) - steps * layers
        for t in range(steps):
            for j in range(layers):
                out[j, length + t] = self.calls[decode + t * layers + j][row]
        return out, upto + -(-length // C) * layers

    def make_reference(self, real):
        """``make_reference`` whose logits follow the recorded selections,
        sequence by sequence in the order the check asks for them."""
        import jax
        import jax.numpy as jnp

        c = self.config
        k, nd = c["num_experts_per_tok"], c["num_dense_layers"]

        def make(config):
            ref_logits, ref_loss = real(config)
            state = {"upto": 0, "row": 0}

            def following(params, seq, positions):
                jax.effects_barrier()
                chosen, state["upto"] = self._selections(state["upto"], int(positions[0]) + 1, seq.shape[0], state["row"])
                state["row"] += 1
                chosen = jnp.asarray(chosen)

                def on_router(i, m, w):
                    with jax.default_matmul_precision("highest"):
                        logits = m @ w["router"]
                    s = jax.nn.sigmoid(logits) if c["score_func"] == "sigmoid" else jax.nn.softmax(logits, axis=-1)
                    sb = s + w["expert_bias"]
                    kth, own = jax.lax.top_k(sb, k)
                    prog = chosen[i - nd]
                    have = prog[:, 0] >= 0
                    differs = have & jnp.any(jnp.sort(own, -1) != jnp.sort(prog, -1), axis=-1)
                    lowest = jnp.take_along_axis(sb, jnp.maximum(prog, 0), axis=-1).min(-1)
                    self.pairs += int(have.sum())
                    self.swapped += int(differs.sum())
                    self.margins.extend(((kth[:, -1] - lowest) / sb.std(-1))[differs].tolist())
                    return jnp.where(have[:, None], prog, own)

                return ref_logits(params, seq, positions, on_router=on_router)

            return following, ref_loss

        return make


def check_with_same_routing(cfg, params, config, seed, route=None):
    """The benchmark's logits check with the reference following the
    program's selections, and what the two routers disagreed on. ``route``:
    another router for the program (a control's)."""
    from benchmark import serving, system
    from ray_tpu.models import transformer

    routing, module = Routing(config), system.model_module(config)
    with swapped_in(transformer, "route", routing.route(route or transformer.route)), \
            swapped_in(module, "make_reference", routing.make_reference(module.make_reference)):
        out = serving.check_paged_against_reference(cfg, params, config, seed)
    margins = sorted(routing.margins)
    return dict(out, swapped_share=routing.swapped / max(routing.pairs, 1), pairs=routing.pairs,
                swapped_margin_max_sd=margins[-1] if margins else 0.0,
                swapped_margin_p50_sd=margins[len(margins) // 2] if margins else 0.0)


def check_with_weights_through_int8(cfg, config, seed, same_routing: bool = False):
    """The stated check (or :func:`check_with_same_routing`) on a program
    whose matrices went through int8, against the reference on the stated
    weights. One copy of the weights is alive at a time: the program's are
    dropped and the stated ones made again from the seed when the reference
    is first asked."""
    import jax

    from benchmark import serving, system

    scale, module = float(config["run"]["weights"]["embed_table_scale"]), system.model_module(config)
    lowered = weights_through_int8(system.make_params(cfg, seed, scale))
    real = module.make_reference

    def make(c):
        ref_logits, ref_loss = real(c)
        stated = []

        def on_stated(params, *args, **kw):
            if not stated:
                jax.tree.map(lambda a: a.delete(), params)
                stated.append(jax.block_until_ready(system.make_params(cfg, seed, scale)))
            return ref_logits(stated[0], *args, **kw)

        return on_stated, ref_loss

    with swapped_in(module, "make_reference", make):
        if same_routing:
            return check_with_same_routing(cfg, lowered, config, seed)
        return serving.check_paged_against_reference(cfg, lowered, config, seed)


# --- what near_tie_sd is held against -------------------------------------------------------
def foreign_prefix(config, params, seed: int, tokens: int = 1536):
    """Through the reference alone: sequence A, and A with its first two
    thirds replaced. At each position of the last third but its first 64, the
    token the wrong context would serve (its top choice) and how far under
    the top that token lies given the right context, in logit spreads."""
    import numpy as np

    import jax.numpy as jnp

    from benchmark import system

    cc = config["run"]["correctness"]
    rng = np.random.default_rng([seed, 17])
    a, b = rng.integers(1, config["vocab_size"], size=(2, tokens))
    cut = tokens * 2 // 3
    wrong = np.concatenate([b[:cut], a[cut:]])
    positions = jnp.arange(cut + 64, tokens)
    ref_logits, _ = system.model_module(config).make_reference(config)
    served = np.asarray(jnp.argmax(ref_logits(params, jnp.asarray(wrong), positions), -1))
    right = np.asarray(ref_logits(params, jnp.asarray(a), positions))
    deficit = (right.max(-1) - right[np.arange(len(served)), served]) / right.std(-1)
    n = int(cc["served_tokens"])
    blocks = deficit[: len(deficit) // n * n].reshape(-1, n).max(-1)
    return {"positions": len(deficit), "median": float(np.median(deficit)),
            "share_within_limit": float(np.mean(deficit <= cc["near_tie_sd"])),
            "block_max_min": float(blocks.min()), "block_max_median": float(np.median(blocks)),
            "blocks_within_limit": int(np.sum(blocks <= cc["near_tie_sd"])), "blocks": len(blocks)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()

    import jax

    from benchmark import run as runner, serving, system

    config = system.load_json(f"benchmark/configs/{args.config}.json")
    if args.rehearsal:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        config = system.shrink_for_rehearsal(config)
    runner.configure_jax()
    print(json.dumps(system.device_info()), flush=True)
    run, cc = config["run"], config["run"]["correctness"]
    cfg = system.model_module(config).program_config(
        config, max_seq_len=run["max_seq_len"], dtype=run["dtype"], param_dtype=run["param_dtype"])
    for seed in (int(s) for s in args.seeds.split(",")):
        params = jax.block_until_ready(system.make_params(cfg, seed, float(run["weights"]["embed_table_scale"])))
        stated = serving.check_paged_against_reference(cfg, params, config, seed)
        same = check_with_same_routing(cfg, params, config, seed)
        line = {"seed": seed, "rel_tol": cc["rel_tol"], "near_tie_sd": cc["near_tie_sd"],
                "rel_err": stated["rel_err"], "worst_vector_rel_err": stated["worst_vector_rel_err"],
                "rel_err_same_routing": same["rel_err"], "worst_vector_rel_err_same_routing": same["worst_vector_rel_err"],
                **{key: same[key] for key in ("swapped_share", "pairs", "swapped_margin_max_sd", "swapped_margin_p50_sd")}}
        print(json.dumps(line), flush=True)
        control = with_router_in_bf16(lambda: serving.check_paged_against_reference(cfg, params, config, seed))
        same = check_with_same_routing(cfg, params, config, seed, route=route_in_bf16)
        line = {"seed": seed, "rel_err_router_bf16": control["rel_err"],
                "rel_err_router_bf16_same_routing": same["rel_err"], "swapped_share_router_bf16": same["swapped_share"],
                "swapped_margin_max_sd_router_bf16": same["swapped_margin_max_sd"],
                "foreign_prefix": foreign_prefix(config, params, seed)}
        print(json.dumps(line), flush=True)
        jax.tree.map(lambda a: a.delete(), params)
        control = check_with_weights_through_int8(cfg, config, seed)
        same = check_with_weights_through_int8(cfg, config, seed, same_routing=True)
        print(json.dumps({"seed": seed, "rel_err_weights_int8": control["rel_err"],
                          "rel_err_weights_int8_same_routing": same["rel_err"],
                          "swapped_share_weights_int8": same["swapped_share"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
