"""Builder-only: is a tree's program the parent's? Lowers, chipless, for a
described v5e, the decode (or block) step and the prefill chunk of the serve
configurations and the train step of the train configurations of a TREE (this
checkout, or a copy of the parent commit made with ``git archive``), and
prints one digest of each program's text and one of its Mosaic kernels:
locations stripped, and every ``tpu_custom_call``'s payload (base64 MLIR
bytecode that carries file paths and line numbers) decoded, parsed and printed
without debug information. Equal lines from two trees are equal programs.

    JAX_PLATFORMS=cpu python3 benchmark/tools/lowered_digests.py /root/scratch/parent > parent.json
    JAX_PLATFORMS=cpu python3 benchmark/tools/lowered_digests.py . > mine.json && diff parent.json mine.json

A payload that does not parse is printed as ``UNPARSED`` and counted: a
comparison that holds any is void (PR 51's first round compared five such).
"""

from __future__ import annotations

import base64
import hashlib
import json
import os
import re
import sys

SERVE = ("trinity-mini-serve-l5", "sdar-30b-a3b-serve-l6", "kimi-linear-48b-a3b-serve-l8", "olmo-hybrid-7b-serve-l16",
         "smollm2-1.7b-serve")
TRAIN = ("smollm2-1.7b-train-l8", "smollm2-1.7b-train-ring4")


def kernel_text(raw: bytes) -> str:
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu

    ctx = mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True   # the payload's ops are "stable_mosaic.*"
    with ctx:
        from jaxlib.mlir import ir

        return ir.Module.parse(raw).operation.get_asm(enable_debug_info=False)


def normal(text: str):
    """(the module's text without locations and payloads, the payloads' texts)."""
    config = r'backend_config = "((?:[^"\\]|\\.)*)"'
    kernels = []
    for m in re.finditer(config, text):
        try:
            cfg = json.loads(re.sub(r"\\([0-9A-Fa-f]{2})", lambda h: chr(int(h.group(1), 16)), m.group(1)))
            kernels.append(kernel_text(base64.b64decode(cfg["custom_call_config"].pop("body"))))
            kernels.append(json.dumps(cfg, sort_keys=True))
        except Exception as e:  # noqa: BLE001 - whatever fails, the comparison has to say so
            kernels.append(f"UNPARSED {type(e).__name__} {m.group(1)[:60]}")
    return re.sub(config, 'backend_config = "..."', re.sub(r"loc\([^)]*\)", "", text)), kernels


def digest(lowered_text: str):
    text, kernels = normal(lowered_text)
    sha = lambda s: hashlib.sha256(s.encode()).hexdigest()[:16]  # noqa: E731
    return {"text": sha(text), "kernels": sha("\n".join(kernels)), "payloads": len(kernels) // 2,
            "unparsed": sum(k.startswith("UNPARSED") for k in kernels)}


def slot_step(T, cell: str):
    """The decode step of a configuration whose sequences hold a slot of
    recurrent state (``tests/test_tpu_lowering.py``'s helper builds no such
    pool): (function, abstract arguments)."""
    import jax
    import jax.numpy as jnp

    from benchmark import system
    from ray_tpu.models import init_params
    from ray_tpu.models.generation import init_paged_cache, paged_forward_counted

    config = system.load_json(f"benchmark/configs/{cell}.json")
    run = config["run"]
    cfg = system.model_module(config).program_config(config, max_seq_len=run["max_seq_len"], dtype=run["dtype"],
                                                     param_dtype=run["param_dtype"])
    B, bs = run["max_batch_size"], run["kv_block_size"]
    params = T._abstract_tree(lambda: init_params(cfg, jax.random.key(0)))
    cache = T._abstract_tree(lambda: init_paged_cache(cfg, run["kv_num_blocks"], bs, slots=B))
    toks, pos, bt = T._abstract([((B,), jnp.int32), ((B,), jnp.int32), ((B, run["max_seq_len"] // bs), jnp.int32)])

    def step(params, cache, toks, pos, bt):
        return paged_forward_counted(cfg, params, cache, bt, toks[:, None], pos[:, None], valid=(bt[:, 0] > 0)[:, None],
                                     slots=jnp.arange(B, dtype=jnp.int32))

    return step, (params, cache, toks, pos, bt)


def main() -> int:
    root = os.path.abspath(sys.argv[1] if len(sys.argv) > 1 else ".")
    sys.path[:0] = [root, os.path.join(root, "tests")]
    os.chdir(root)
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

    import test_tpu_lowering as T   # the tree's own abstract programs
    from benchmark import system
    from ray_tpu.models.transformer import make_train_step
    from ray_tpu.ops import backend

    backend.on_tpu = lambda: True
    out = {}
    for cell in SERVE:
        try:
            programs = T._cell_programs(cell)
        except ValueError:   # a recurrent state a sequence: the pool takes slots, and the step says which
            programs = {"step": slot_step(T, cell)}
        for which, (fn, args) in programs.items():
            out[f"{cell}.{which}"] = digest(jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).as_text())
    devices = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices
    for name in TRAIN:
        c = system.load_json(f"benchmark/configs/{name}.json")
        run = c["run"]
        cfg = system.model_module(c).program_config(
            c, max_seq_len=run["seq_len"], dtype=run["dtype"], param_dtype=run["param_dtype"],
            attention=run["attention"], remat=run["remat"], scan_layers=run["scan_layers"])
        mesh = None
        if run.get("mesh"):
            mesh = Mesh(np.array(devices).reshape([run["mesh"][a] for a in run["mesh_axes"]]), tuple(run["mesh_axes"]))
        _, step = make_train_step(cfg, mesh=mesh, learning_rate=run["learning_rate"])
        state = jax.eval_shape(make_train_step(cfg)[0], jax.random.key(0))
        if mesh is None:
            one = SingleDeviceSharding(devices[0])
            state = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), state)
            tokens = jax.ShapeDtypeStruct((run["batch"], run["seq_len"]), jnp.int32, sharding=one)
            lowered = step.trace(state, tokens).lower(lowering_platforms=("tpu",))
        else:
            shardings = system.train_state_shardings(cfg, mesh, state)
            state = jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), state, shardings)
            tokens = jax.ShapeDtypeStruct((run["batch"], run["seq_len"]), jnp.int32, sharding=NamedSharding(mesh, P("dp", None)))
            lowered = step.lower(state, tokens)
        out[name] = digest(lowered.as_text())
    print(json.dumps(out, indent=1, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
