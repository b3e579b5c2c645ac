"""Builder-only, on the chip: readings on both sides of the limits of a
configuration with linear layers (traffic kind ``state_sessions``), through
that kind's own comparisons (1) and (2). For each seed:

* the stated program: ``rel_err`` and ``restored_rel_err``;
* ``state_bf16``: the same comparisons on a program whose recurrent state is
  kept in bfloat16: rounded after every token, in prefill (the recurrence run
  token by token in place of the chunked form, which holds no per-token state
  to round) and in decode, and so in every snapshot;
* ``weights_int8``: on a program whose matrices went through int8 with one
  scale a row (``precision_control.weights_through_int8``; the float32 gate
  buffers ``A_log`` and ``dt_bias`` are left as they are), the reference on
  the stated weights;
* ``no_restore``: the stated program with the follow-up's slot left zero
  instead of restored (an engine that lost a snapshot and skipped prefill
  all the same): what ``restored_rel_err`` and ``restored_state_rel_err``
  are held against;
* ``foreign_prefix`` (``precision_control.foreign_prefix``, through the
  reference alone): what ``near_tie_sd`` is held against;
* ``alpha``: quantiles of the first linear layer's decay over 4096 random
  tokens (the configuration file's ``assumed`` quotes them);
* ``act_f32`` / ``act_f32_state_bf16``: comparisons (1) and (2) with the
  activations in float32 (the stated weights), the state in float32 and in
  bfloat16: what a bfloat16 state costs by itself, once the bfloat16
  activations' error no longer lies over it;
* ``engine_state_bf16`` / ``engine_no_restore``: comparison (4), through the
  served engine itself (``StateServed`` without the runner's check), with the
  state in bfloat16 and with an admission that zeroes the slot where it
  should restore it: both have to come out not ``ok``.

    python3 benchmark/tools/state_precision_control.py --config olmo-hybrid-7b-serve-l16 --seeds 1,2
    ... --variants runner,int8             (the default)
    ... --variants act_f32
    ... --variants engine_state_bf16       (one seed, a process of its own: a served engine's
    ... --variants engine_no_restore        memory outlives its ``close()``, and the chip holds one)
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

KEYS = ("rel_err", "restored_rel_err", "worst_restored_vector_rel_err", "restored_state_rel_err",
        "state_worst_head_rel_err", "state_median_head_rel_err", "ok")


def state_in_bf16(fn):
    """Run ``fn()`` with the program's recurrence keeping its state in bfloat16."""
    import jax
    import jax.numpy as jnp

    from benchmark.tools.precision_control import swapped_in
    from ray_tpu.models import generation
    from ray_tpu.ops import gated_delta as gd

    def rounded(S):
        return S.astype(jnp.bfloat16).astype(jnp.float32)

    def chunked(S0, q, k, v, g, beta, valid=None, chunk=None):
        ok = jnp.ones(q.shape[:2], bool) if valid is None else valid

        def token(S, xs):
            q_t, k_t, v_t, g_t, b_t, ok_t = xs
            o, Sn = gd.gated_delta_step(S, q_t, k_t, v_t, jnp.exp(g_t), b_t)
            return jnp.where(ok_t[:, None, None, None], rounded(Sn), S), o

        xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta, ok))
        S, o = jax.lax.scan(token, rounded(S0.astype(jnp.float32)), xs)
        return jnp.moveaxis(o, 0, 1), S

    real = generation.gated_delta_decode

    def decode(state, layer, slots, live, *args, **kw):
        o, state = real(state, layer, slots, live, *args, **kw)
        return o, state.at[layer, slots].set(rounded(state[layer, slots]))

    with swapped_in(generation, "gated_delta_chunked", chunked), swapped_in(generation, "gated_delta_decode", decode):
        return fn()


def engine_never_restores(fn):
    """Run ``fn()`` with an engine whose admission counts a restore and leaves the slot zero."""
    from benchmark.tools.precision_control import swapped_in
    from ray_tpu.serve import llm

    real = llm.LLMEngine._place_state

    def place(self, req, snapshot):
        keep, self._restore_state = self._restore_state, lambda cache, snaps, slot, entry: self._zero_state(cache, slot)
        try:
            return real(self, req, snapshot)
        finally:
            self._restore_state = keep

    with swapped_in(llm.LLMEngine, "_place_state", place):
        return fn()


def engine_state(config, seed: int):
    """Comparison (4) of a served engine built now (under whatever is swapped in)."""
    from benchmark.kinds.state_sessions import StateServed

    served = StateServed(config, seed, lambda msg: print(msg, file=sys.stderr, flush=True), runner_check=False)
    try:
        return served.correctness["engine state"]
    finally:
        served.close()


def matrices_through_int8(params):
    """``precision_control.weights_through_int8`` that leaves the float32 gate buffers alone."""
    import jax
    import jax.numpy as jnp

    def lower(path, w):
        if path[-1].key in ("A_log", "dt_bias") or w.ndim < 2:
            return w
        x = w.astype(jnp.float32)
        scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0 + 1e-30
        return (jnp.round(x / scale) * scale).astype(w.dtype)

    return jax.jit(lambda p: jax.tree_util.tree_map_with_path(lower, p), donate_argnums=0)(params)


def alpha_quantiles(config, params, seed: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    layer = {k: v[0].astype(jnp.float32) for k, v in params["linear_layers"][0].items() if k in ("lin_wa", "A_log", "dt_bias")}
    toks = np.random.default_rng([seed, 23]).integers(1, config["vocab_size"], size=4096)
    x = params["embed"][jnp.asarray(toks)].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        alpha = jnp.exp(-jnp.exp(layer["A_log"]) * jax.nn.softplus(x @ layer["lin_wa"] + layer["dt_bias"]))
    qs = np.quantile(np.asarray(alpha), [0.01, 0.1, 0.5, 0.9, 0.99])
    return {f"q{int(100 * q):02d}": float(v) for q, v in zip((0.01, 0.1, 0.5, 0.9, 0.99), qs)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--variants", default="runner,int8")
    args = ap.parse_args()
    variants = set(args.variants.split(","))

    import dataclasses

    import jax
    import jax.numpy as jnp

    from benchmark import run as runner, system
    from benchmark.kinds.state_sessions import check_state_against_reference as check
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
        for name, under in (("engine_state_bf16", state_in_bf16), ("engine_no_restore", engine_never_restores)):
            if name in variants:
                print(json.dumps({"seed": seed, name: under(lambda: engine_state(config, seed))}), flush=True)
        params = stated(seed)
        if "runner" in variants:
            line = {"seed": seed, "stated": {k: v for k, v in check(cfg, params, config, seed).items() if k in KEYS},
                    "alpha": alpha_quantiles(config, params, seed)}
            print(json.dumps(line), flush=True)
            control = state_in_bf16(lambda: check(cfg, params, config, seed))
            fault = check(cfg, params, config, seed, restore=False)
            line = {"seed": seed, "state_bf16": {k: control[k] for k in KEYS}, "no_restore": {k: fault[k] for k in KEYS}}
            if not args.rehearsal:
                line["foreign_prefix"] = pc.foreign_prefix(config, params, seed)
            print(json.dumps(line), flush=True)
        if "act_f32" in variants:
            wide = dataclasses.replace(cfg, dtype=jnp.float32)
            with jax.default_matmul_precision("highest"):  # (the chip's products round float32 operands to bfloat16 otherwise)
                sound = check(wide, params, config, seed)
                control = state_in_bf16(lambda: check(wide, params, config, seed))
            print(json.dumps({"seed": seed, "act_f32": {k: sound[k] for k in KEYS},
                              "act_f32_state_bf16": {k: control[k] for k in KEYS}}), flush=True)
        jax.tree.map(lambda a: a.delete(), params)
        if "int8" in variants:
            lowered = matrices_through_int8(system.make_params(cfg, seed, scale))

            def restated():
                jax.tree.map(lambda a: a.delete(), lowered)
                return stated(seed)

            control = check(cfg, lowered, config, seed, reference_params=restated)
            print(json.dumps({"seed": seed, "weights_int8": {k: control[k] for k in KEYS}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
