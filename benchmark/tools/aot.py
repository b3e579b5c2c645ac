"""Builder-only: compile each cell's programs at their real sizes for a
described (not attached) ``v5e:2x2`` and print the compiler's memory
analysis. Costs no chip time; says nothing about results or speed. It sizes
the KV pool, the train batch and the depth that the configuration files
state.

    JAX_PLATFORMS=cpu python3 benchmark/tools/aot.py smollm2-1.7b-train-ring4 \
        [--set num_hidden_layers=12 --run batch=2]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def _gib(compiled):
    m = compiled.memory_analysis()
    need = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)
    return {
        "arguments": round(m.argument_size_in_bytes / 2**30, 2),
        "temporaries": round(m.temp_size_in_bytes / 2**30, 2),
        "outputs": round(m.output_size_in_bytes / 2**30, 2),
        "aliased": round(m.alias_size_in_bytes / 2**30, 2),
        "needs_gib": round(need / 2**30, 2),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("--set", action="append", default=[], help="key=value over the file")
    ap.add_argument("--run", action="append", default=[], help="key=value over its run group")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

    from benchmark import system
    from ray_tpu.ops import backend

    backend.on_tpu = lambda: True  # answer the one platform predicate as the chip will
    jax.config.update("jax_enable_compilation_cache", False)
    devices = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices

    c = system.load_json(f"benchmark/configs/{args.config}.json")
    for kv in args.set:
        k, v = kv.split("=")
        c[k] = json.loads(v)
    for kv in args.run:
        k, v = kv.split("=")
        c["run"][k] = json.loads(v)
    run = c["run"]
    model = system.model_module(c)
    one = SingleDeviceSharding(devices[0])
    t0 = time.time()

    def abstract(tree, sharding):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree)

    out = {"config": args.config, "layers": c["num_hidden_layers"], "run": run}
    if run["role"] == "serve":
        from ray_tpu.models import init_params
        from ray_tpu.models.generation import (init_paged_cache, paged_decode_step,
                                               paged_forward_with_cache)

        cfg = model.program_config(c, max_seq_len=run["max_seq_len"], dtype=run["dtype"],
                                   param_dtype=run["param_dtype"])
        bs, B = run["kv_block_size"], run["max_batch_size"]
        M = -(-run["max_seq_len"] // bs)
        params = abstract(jax.eval_shape(lambda: init_params(cfg, jax.random.key(0))), one)
        cache = abstract(jax.eval_shape(lambda: init_paged_cache(cfg, run["kv_num_blocks"], bs)), one)
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)  # noqa: E731

        def decode(params, cache, toks, pos, bt):
            logits, cache = paged_decode_step(cfg, params, cache, toks, pos, bt)
            return jnp.argmax(logits, -1), cache

        lowered = jax.jit(decode, donate_argnums=(1,)).trace(
            params, cache, i32(B), i32(B), i32(B, M)).lower(lowering_platforms=("tpu",))
        assert "tpu_custom_call" in lowered.as_text()
        out["decode"] = _gib(lowered.compile())
        C = run["prefill_chunk_tokens"]

        def prefill(params, cache, toks, bt, start, length):
            positions = start + jnp.arange(C)[None, :]
            valid = (jnp.arange(C) < length)[None, :]
            logits, cache = paged_forward_with_cache(cfg, params, cache, bt, toks, positions,
                                                     valid=valid, use_decode_kernel=False)
            return logits[0, length - 1], cache

        lowered = jax.jit(prefill, donate_argnums=(1,)).trace(
            params, cache, i32(1, C), i32(1, M), i32(), i32()).lower(lowering_platforms=("tpu",))
        out["prefill_chunk"] = _gib(lowered.compile())
    else:
        from ray_tpu.models.transformer import make_train_step

        cfg = model.program_config(
            c, max_seq_len=run["seq_len"], dtype=run["dtype"], param_dtype=run["param_dtype"],
            attention=run["attention"], remat=run["remat"], scan_layers=run["scan_layers"])
        mesh = None
        if run.get("mesh"):
            shape = [run["mesh"][a] for a in run["mesh_axes"]]
            mesh = Mesh(np.array(devices).reshape(shape), tuple(run["mesh_axes"]))
        init_state, step = make_train_step(cfg, mesh=mesh)
        plain_init, _ = make_train_step(cfg)
        state = jax.eval_shape(plain_init, jax.random.key(0))
        if mesh is None:
            state = abstract(state, one)
            tokens = jax.ShapeDtypeStruct((run["batch"], run["seq_len"]), jnp.int32, sharding=one)
            lowered = step.trace(state, tokens).lower(lowering_platforms=("tpu",))
        else:
            shardings = system.train_state_shardings(cfg, mesh, state)
            state = jax.tree.map(
                lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh), state, shardings)
            tokens = jax.ShapeDtypeStruct((run["batch"], run["seq_len"]), jnp.int32,
                                          sharding=NamedSharding(mesh, P("dp", None)))
            lowered = step.lower(state, tokens)
        text = lowered.as_text()
        assert "tpu_custom_call" in text
        compiled = lowered.compile()
        out["train_step"] = _gib(compiled)
        hlo = compiled.as_text()
        out["collectives"] = {k: hlo.count(f" {k}(") + hlo.count(f" {k}-start(") for k in
                              ("all-reduce", "all-gather", "collective-permute", "reduce-scatter", "all-to-all")}
        out["tokens_per_step"] = run["batch"] * run["seq_len"]
        out["params_m"] = round(model.n_params(c) / 1e6, 1)
    out["compile_s"] = round(time.time() - t0, 1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
