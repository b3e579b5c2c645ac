"""Builder-only, on the chip: ``state_precision_control.py`` for a
configuration that also has latent attention layers, a decay a key channel and
a share of its experts (``kimi_linear``): readings on both sides of the limits
of ``state_sessions``' comparisons. Its controls, as that tool runs them (the
stated program, ``state_bf16``, ``no_restore``, ``foreign_prefix``,
``engine_state_bf16``, ``engine_no_restore``), and what this family adds:

* ``latent_8bit``: the stated program with every row rounded to 8 bits
  (``float8_e4m3fn``) on its way into the latent pool, in prefill and in
  decode: what a quantised latent cache would read. It has to fail at least
  one limit;
* ``pool`` (``--variants pool``): comparison (5) of ``benchmark/kinds/latent_sessions.py``, the latent
  pool's rows read back and held to the reference's, for the stated program
  and under ``latent_8bit``: the limit the 8-bit pool fails (with ``int8`` beside it: the
  same comparison of a program on weights through int8, instead of that control's logits);
* ``weights_int8``: every matrix through int8 with one scale a row; the
  float32 buffers (``A_log``, ``dt_bias``, the router's selection bias) are
  left as they are; the reference on the stated weights;
* ``alpha``: quantiles of the first KDA layer's decay, a key channel, over
  4096 random tokens (the configuration file's ``assumed`` quotes them).

    python3 benchmark/tools/latent_precision_control.py --config kimi-linear-48b-a3b-serve-l8 --seeds 1,2
    ... --variants runner,int8,latent8     (the default)
    ... --variants stated                  (the stated program alone: more seeds of the sound readings)
    ... --variants pool                    (the latent pool's rows, stated and rounded to 8 bits)
    ... --variants engine_state_bf16       (one seed, a process of its own: a served engine's
    ... --variants engine_no_restore        memory outlives its ``close()``, and the chip holds one)
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

BUFFERS = ("A_log", "dt_bias", "router_bias")


def latent_rows_in_8_bits(fn):
    """Run ``fn()`` with the program's latent rows rounded to float8_e4m3fn before they are cached."""
    import jax.numpy as jnp

    from benchmark.tools.precision_control import swapped_in
    from ray_tpu.models import generation

    real = generation.latent_qkv

    def rounded(cfg, layer, h):
        q, row = real(cfg, layer, h)
        return q, row.astype(jnp.float8_e4m3fn).astype(row.dtype)

    with swapped_in(generation, "latent_qkv", rounded):
        return fn()


def matrices_through_int8(params):
    import jax
    import jax.numpy as jnp

    def lower(path, w):
        if path[-1].key in BUFFERS or w.ndim < 2:
            return w
        x = w.astype(jnp.float32)
        scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0 + 1e-30
        return (jnp.round(x / scale) * scale).astype(w.dtype)

    return jax.jit(lambda p: jax.tree_util.tree_map_with_path(lower, p), donate_argnums=0)(params)


def alpha_quantiles(config, params, seed: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    L = {k: v[0].astype(jnp.float32) for k, v in params["linear_layers"][0].items()
         if k in ("lin_wfa", "lin_wfb", "A_log", "dt_bias", "attn_norm")}
    toks = np.random.default_rng([seed, 23]).integers(1, config["vocab_size"], size=4096)
    x = params["embed"][jnp.asarray(toks)].astype(jnp.float32)
    h = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + config["rms_norm_eps"]) * L["attn_norm"]
    with jax.default_matmul_precision("highest"):
        gate = jnp.einsum("tr,rhk->thk", h @ L["lin_wfa"], L["lin_wfb"]) + L["dt_bias"]
        alpha = jnp.exp(-jnp.exp(L["A_log"])[None, :, None] * jax.nn.softplus(gate))
    qs = np.quantile(np.asarray(alpha), [0.01, 0.1, 0.5, 0.9, 0.99])
    return {f"q{int(100 * q):02d}": float(v) for q, v in zip((0.01, 0.1, 0.5, 0.9, 0.99), qs)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--variants", default="runner,int8,latent8")
    args = ap.parse_args()
    variants = set(args.variants.split(","))

    import jax

    from benchmark import run as runner, system
    from benchmark.kinds.latent_sessions import check_latent_pool
    from benchmark.kinds.state_sessions import check_state_against_reference as check
    from benchmark.tools import precision_control as pc
    from benchmark.tools.state_precision_control import KEYS, engine_never_restores, engine_state, state_in_bf16

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

    def keys(result):
        return {k: result[k] for k in KEYS}

    for seed in (int(s) for s in args.seeds.split(",")):
        for name, under in (("engine_state_bf16", state_in_bf16), ("engine_no_restore", engine_never_restores)):
            if name in variants:
                print(json.dumps({"seed": seed, name: under(lambda: engine_state(config, seed))}), flush=True)
        if not variants - {"engine_state_bf16", "engine_no_restore"}:
            continue  # (the engine's memory outlives its close(): nothing more fits in this process)
        params = stated(seed)
        if variants & {"runner", "stated"}:
            print(json.dumps({"seed": seed, "stated": keys(check(cfg, params, config, seed)),
                              "alpha": alpha_quantiles(config, params, seed)}), flush=True)
        if "runner" in variants:
            line = {"seed": seed, "state_bf16": keys(state_in_bf16(lambda: check(cfg, params, config, seed))),
                    "no_restore": keys(check(cfg, params, config, seed, restore=False))}
            if not args.rehearsal:
                line["foreign_prefix"] = pc.foreign_prefix(config, params, seed)
            print(json.dumps(line), flush=True)
        if "pool" in variants:
            print(json.dumps({"seed": seed, "pool_stated": check_latent_pool(cfg, params, config, seed),
                              "pool_latent_8bit": latent_rows_in_8_bits(
                                  lambda: check_latent_pool(cfg, params, config, seed))}), flush=True)
        if "latent8" in variants:
            print(json.dumps({"seed": seed, "latent_8bit": keys(latent_rows_in_8_bits(
                lambda: check(cfg, params, config, seed)))}), flush=True)
        jax.tree.map(lambda a: a.delete(), params)
        if "int8" in variants:
            lowered = matrices_through_int8(system.make_params(cfg, seed, scale))

            def restated():
                jax.tree.map(lambda a: a.delete(), lowered)
                return stated(seed)

            if "pool" in variants:
                print(json.dumps({"seed": seed, "pool_weights_int8": check_latent_pool(
                    cfg, lowered, config, seed, reference_params=restated)}), flush=True)
            else:
                print(json.dumps({"seed": seed, "weights_int8": keys(
                    check(cfg, lowered, config, seed, reference_params=restated))}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
