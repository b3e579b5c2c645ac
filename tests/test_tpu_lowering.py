"""What the chip will be asked to compile, checked without a chip.

The installed jax/libtpu can cross-lower for TPU from a CPU host
(``lower(lowering_platforms=("tpu",))``) and AOT-compile against a chipless
``v5e:2x2`` topology. With ``ops.backend.on_tpu`` answering as the chip
will, every Pallas kernel and the engine's paged decode program are lowered
at ``chip_smoke.py``'s shapes — a BlockSpec Mosaic cannot tile is refused
right here — and, where the topology can be built, compiled for real (an
oversize-VMEM kernel or a Mosaic call GSPMD cannot partition is refused at
that step). An AOT compile is a lead, not a pass: only ``chip_smoke.py`` on
the chip shows the program runs and computes the right numbers.

Also pins the two rules the lowering relies on: one comparison against the
jax default backend in the package, and a compile cache placed from outside.
"""

import functools
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

import chip_smoke
from bench import TRAIN_BATCH, train_config
from ray_tpu.ops import backend
from ray_tpu.ops.attention import flash_attention
from ray_tpu.ops.decode_attention import (decode_attention, paged_decode_attention, paged_prefill_attention,
                                          paged_write_rows, paged_write_segments)
from ray_tpu.ops.quantization import int8_matmul
from ray_tpu.scripts.llm_bench import serving_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FULL = chip_smoke.FULL
BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture
def as_chip(monkeypatch):
    """Answer the one platform predicate as the chip will."""
    monkeypatch.setattr(backend, "on_tpu", lambda: True)


@functools.cache
def _v5e_topology():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices
    except Exception as exc:  # noqa: BLE001 — no libtpu / no chipless topology here
        return exc


@pytest.fixture
def v5e():
    """Four chipless ``TPU v5 lite`` devices to AOT-compile against. The
    persistent cache is off meanwhile: an AOT executable can be written to
    it but never read back, so it would only grow the directory."""
    devices = _v5e_topology()
    if isinstance(devices, Exception):
        pytest.skip(f"cannot build a chipless v5e:2x2 topology: {devices!r}")
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield devices
    finally:
        jax.config.update("jax_enable_compilation_cache", True)


def _flash_grad(q, k, v):
    return jax.grad(lambda q, k, v: flash_attention(q, k, v).astype(F32).sum(), argnums=(0, 1, 2))(q, k, v)


def _kernel_cases():
    f, p, d, g = FULL["flash"], FULL["prefill"], FULL["decode"], FULL["paged"]
    qkv = [((f["B"], f["H"], f["T"], f["D"]), BF16)] * 3
    yield "flash_fwd", flash_attention, qkv
    yield "flash_bwd", _flash_grad, qkv
    # the training cells' calls, forward and both backward kernels: bf16 operands
    # as they arrive, P and dS in bf16 terms, transposed where the keys' and
    # values' gradient contracts over the queries (Moonlight: keys of 192, values of 128)
    for cell, (b, h, t, dk, dv) in {"moonlight": (1, 16, 8192, 192, 128), "train_l8": (4, 32, 2048, 64, 64)}.items():
        shapes = [((b, h, t, dk), BF16)] * 2 + [((b, h, t, dv), BF16)]
        yield f"flash_fwd_{cell}", flash_attention, shapes
        yield f"flash_bwd_{cell}", _flash_grad, shapes
    for T in p["widths"]:
        yield f"prefill_T{T}", flash_attention, [((1, p["H"], T, p["D"]), BF16)] * 3
    for T in (33, 100, 2049):  # ragged: padded to the block, tail masked in-kernel
        yield f"flash_ragged_T{T}", flash_attention, [((1, 2, T, 64), BF16)] * 3
    B, H, Hkv, D = d["B"], d["H"], d["Hkv"], d["D"]
    # S=1000 has no 128-multiple divisor: the block search must pad, not pick 500
    for S, dt in ((d["S"], BF16), (1000, BF16), (1000, F32), (4096, F32)):
        yield f"decode_dense_S{S}_{dt.__name__}", decode_attention, [
            ((B, H, D), dt), ((B, Hkv, S, D), dt), ((B, Hkv, S, D), dt), ((B,), I32)]
    B, H, Hkv, D = g["B"], g["H"], g["Hkv"], g["D"]

    def paged(H, Hkv, D, bs, dt):
        M = g["M"] * g["bs"] // bs
        pool = ((2, B * M + 1, bs, Hkv * D), dt)  # two layers: the index map picks one
        return [((B, H, D), dt), pool, pool, ((B, M), I32), ((B,), I32), ((), I32)]

    for bs in (g["bs"], 128):
        for dt in (BF16, F32):
            yield f"decode_paged_bs{bs}_{dt.__name__}", paged_decode_attention, paged(H, Hkv, D, bs, dt)
    # the units are whole lane tiles of the page row: MHA at the cells' 64-wide
    # head (eight heads a unit, their queries the rows of one block), one
    # 128-wide head (its own tile) under GQA, and thirty of them under MHA (six a unit)
    yield "decode_paged_mha32_d64", paged_decode_attention, paged(32, 32, 64, g["bs"], BF16)
    yield "decode_paged_gqa4_1_d128", paged_decode_attention, paged(4, 1, 128, g["bs"], BF16)
    yield "decode_paged_mha30_d128", paged_decode_attention, paged(30, 30, 128, g["bs"], BF16)
    # the paged prefill kernel at the two served families' chunk, capacity and
    # head shapes (32 x 64 wide heads, two to a lane tile; 32 over 4 of 128 with
    # the window as a traced scalar), and at widths below and across its query tile
    def chunk(T, H, Hkv, D, M, dt=BF16, window=False):
        pool = ((2, M + 1, g["bs"], Hkv * D), dt)
        return [((1, T, H, D), dt), pool, pool, ((1, M), I32), ((1,), I32), ((1,), I32), ((), I32)] + [((), I32)] * window

    def windowed(q, kp, vp, bt, start, length, layer, window):
        return paged_prefill_attention(q, kp, vp, bt, start, length, layer, window=window)

    yield "prefill_paged_smollm2", paged_prefill_attention, chunk(512, 32, 32, 64, 256)
    yield "prefill_paged_trinity", windowed, chunk(512, 32, 4, 128, 512, window=True)
    for T in (16, 200, 1024):
        yield f"prefill_paged_T{T}", paged_prefill_attention, chunk(T, H, Hkv, D, g["M"])
    yield "prefill_paged_float32", paged_prefill_attention, chunk(64, H, Hkv, D, g["M"], F32)
    # generation by diffusion over blocks at the SDAR cell's shapes (32 query
    # heads over 4 KV heads of 128, a table of 256 pages): the prefill chunk
    # under the block-causal mask, and the block step, 48 rows x 4 positions
    def block_causal(q, kp, vp, bt, start, length, layer):
        return paged_prefill_attention(q, kp, vp, bt, start, length, layer, block=4)

    yield "prefill_paged_sdar_chunk", block_causal, chunk(512, 32, 4, 128, 256)
    pool = ((2, 48 * 64 + 1, g["bs"], 4 * 128), BF16)
    yield "block_step_sdar", block_causal, [((48, 4, 32, 128), BF16), pool, pool, ((48, 256), I32), ((48,), I32),
                                            ((48,), I32), ((), I32)]
    yield "int8_matmul", int8_matmul, [((512, 1024), BF16), ((1024, 1024), jnp.int8), ((1024,), F32)]
    # the pools' write at the five served families' rows: a decode call (a row a
    # slot: pages read, a row laid over, written back), a block step's 48 x 4
    # rows, and a 512-row chunk (whole pages copied from where XLA laid them
    # out); two pools, and the latent layers' one
    for name, n, lanes, B, T in (
            ("smollm2_decode", 2, 2048, 40, 1), ("smollm2_chunk", 2, 2048, 1, 512), ("trinity_decode", 2, 512, 48, 1),
            ("sdar_block_step", 2, 512, 48, 4), ("olmo_hybrid_decode", 2, 3840, 32, 1), ("olmo_hybrid_chunk", 2, 3840, 1, 512),
            ("kimi_latent_decode", 1, 640, 64, 1), ("kimi_latent_chunk", 1, 640, 1, 512)):
        pool, rows, at = ((2, 65, 16, lanes), BF16), ((B * T, lanes), BF16), ((B * T,), I32)
        yield f"paged_write_{name}", functools.partial(_paged_write, n, B), [pool] * n + [rows] * n + [((), I32), at, at]


def _paged_write(n, B, *args):
    pools, rows, (layer, phys, off) = args[:n], args[n:2 * n], args[2 * n:]
    return paged_write_rows(pools, rows, layer, paged_write_segments(phys, off, sequences=B, block_size=pools[0].shape[2]))


KERNEL_CASES = list(_kernel_cases())


def _abstract(specs, sharding=None):
    return [jax.ShapeDtypeStruct(shape, dt, sharding=sharding) for shape, dt in specs]


def _abstract_tree(make, sharding=None):
    """The shapes of what ``make()`` would build, placed by ``sharding``."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), jax.eval_shape(make)
    )


def _lower_for_tpu(fn, args):
    lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    assert "tpu_custom_call" in lowered.as_text(), "lowered without a Mosaic kernel"
    return lowered


@pytest.mark.parametrize("name,fn,specs", KERNEL_CASES, ids=[c[0] for c in KERNEL_CASES])
def test_kernel_lowers_for_tpu(as_chip, name, fn, specs):
    _lower_for_tpu(fn, _abstract(specs))


@pytest.mark.parametrize("name,fn,specs", KERNEL_CASES, ids=[c[0] for c in KERNEL_CASES])
def test_kernel_compiles_for_v5e(as_chip, v5e, name, fn, specs):
    _lower_for_tpu(fn, _abstract(specs, SingleDeviceSharding(v5e[0]))).compile()


def _engine_decode_args(sharding=None):
    """The default engine's decode step at ``chip_smoke``'s serving shapes:
    ``paged_decode_step`` with the kernel auto-selected, as ``LLMEngine``
    builds it (``serve/llm.py:_decode_k_paged``)."""
    from ray_tpu.models import init_params
    from ray_tpu.models.generation import init_paged_cache

    cfg = serving_config()
    s = FULL["serve"]
    bs = 16  # Config.kv_block_size default
    M = s["seq"] // bs

    params = _abstract_tree(lambda: init_params(cfg, jax.random.key(0)), sharding)
    cache = _abstract_tree(lambda: init_paged_cache(cfg, s["slots"] * M + 1, bs), sharding)
    toks, bt = _abstract([((s["slots"],), I32), ((s["slots"], M), I32)], sharding)
    return cfg, (params, cache, toks, toks, bt)


def _engine_decode_step(cfg):
    from ray_tpu.models.generation import paged_decode_step

    return lambda params, cache, toks, pos, bt: paged_decode_step(cfg, params, cache, toks, pos, bt)


def test_engine_paged_decode_program_lowers_for_tpu(as_chip):
    cfg, args = _engine_decode_args()
    _lower_for_tpu(_engine_decode_step(cfg), args)


def test_engine_paged_decode_program_compiles_for_v5e(as_chip, v5e):
    cfg, args = _engine_decode_args(SingleDeviceSharding(v5e[0]))
    _lower_for_tpu(_engine_decode_step(cfg), args).compile()


def _engine_program_args(eng, sharding=None):
    """Abstract arguments of ``ModelRunner._decode_k_paged`` as the loop passes
    them: params, the pool, the rows' last tokens as the program's previous
    run left them on the device, the tokens the host sampled since (-1: none),
    positions, temperatures, the key and the masked block tables."""
    def abstract(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    params, cache, key = jax.tree.map(abstract, (eng.runner.params, eng.runner.cache, eng.runner.key))
    toks, temps, bt = _abstract([((eng.B,), I32), ((eng.B,), F32), (eng.store.block_tables.shape, I32)], sharding)
    return params, cache, toks, toks, toks, temps, key, bt


@pytest.fixture
def small_engine(as_chip):
    import dataclasses

    from ray_tpu.models import init_params
    from ray_tpu.serve.llm import LLMEngine

    cfg = dataclasses.replace(serving_config(), n_layers=2, vocab_size=512)
    eng = LLMEngine(cfg, init_params(cfg, jax.random.key(0)), max_batch_size=8, max_seq_len=256, decode_chunk=2)
    yield eng
    eng.shutdown()


def test_the_engines_own_decode_program_keeps_its_tokens_on_the_device(small_engine):
    """The program the loop dispatches, not a stand-in: it takes its own
    previous tokens and the host's joins, and hands back ``[B, K]`` tokens
    for the host beside the pool, the key and the ``[B]`` last tokens that
    the next run takes unread. Lowered for the chip, the kernel is in it."""
    eng = small_engine
    traced = eng.runner._decode_k_paged.trace(*_engine_program_args(eng))
    out = traced.out_info
    assert [(o.shape, o.dtype) for o in (out[0], out[2], out[3])] == [
        ((8, 2), I32), ((), eng.runner.key.dtype), ((8,), I32)]
    assert jax.tree.structure(out[1]) == jax.tree.structure(eng.runner.cache) and len(out) == 4
    assert "tpu_custom_call" in traced.lower(lowering_platforms=("tpu",)).as_text()


def test_the_engines_own_decode_program_compiles_for_v5e(small_engine, v5e):
    eng = small_engine
    args = _engine_program_args(eng, SingleDeviceSharding(v5e[0]))
    compiled = eng.runner._decode_k_paged.trace(*args).lower(lowering_platforms=("tpu",)).compile()
    pool_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(eng.runner.cache))
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes  # donated through, as before


@pytest.mark.parametrize("on_chip", [True, False], ids=["on_the_chip", "off_it"])
def test_the_engines_programs_hold_the_write_they_say(monkeypatch, on_chip):
    """``stats()["kv_write"]`` is a fact of how the runner's two programs were
    built, and the programs bear it out: on the chip the decode program and
    the prefill chunk each hold one ``paged_write`` custom-call a layer stack
    (beside the attention kernel's) and no scatter whose result is a pool;
    off it (the plain reference) a scatter a pool and no such call. Through
    the lowering only: nothing is compiled or run."""
    import dataclasses

    from ray_tpu.models import init_params
    from ray_tpu.serve.llm import LLMEngine

    monkeypatch.setattr(backend, "on_tpu", lambda: on_chip)
    cfg = dataclasses.replace(serving_config(), n_layers=2, vocab_size=512)
    eng = LLMEngine(cfg, init_params(cfg, jax.random.key(0)), max_batch_size=8, max_seq_len=256, prefill_chunk_tokens=64)
    try:
        assert eng.stats()["kv_write"] == eng.runner.kv_write == ("kernel" if on_chip else "scatter")
        pool = "x".join(str(d) for d in eng.runner.cache["k"].shape)
        for traced, attention in ((eng.runner.traced_decode(), "paged_decode"), (eng.runner.traced_prefill_chunk(64), "paged_prefill")):
            text = traced.lower(lowering_platforms=("tpu",)).as_text()
            results = re.findall(r'"stablehlo\.scatter".*?\}\) : \(.*?\) -> tensor<([\dx]+)x\w+>', text, re.S)
            pool_scatters = [shape for shape in results if shape == pool]
            names = re.findall(r'kernel_name = "(\w+)"', text)
            if on_chip:
                assert names == ["paged_write", attention] and not pool_scatters
            else:
                assert not names and len(pool_scatters) == 2
    finally:
        eng.shutdown()


def _pallas_grids(jaxpr):
    """The grid of every ``pallas_call`` in ``jaxpr``, scans and calls walked."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield tuple(eqn.params["grid_mapping"].grid)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_grids(sub)


def test_the_decode_programs_paged_kernel_has_one_grid_step_a_slot(as_chip):
    """The pages axis stays out of the grid: a slot that holds nothing costs
    one grid step a layer, and the kernel's work follows the live pages
    (with ``(slots, max_blocks)`` 90-98% of the benchmark's grid steps held
    nothing and were most of a decode step)."""
    cfg, args = _engine_decode_args()
    grids = list(_pallas_grids(jax.make_jaxpr(_engine_decode_step(cfg))(*args).jaxpr))
    # a layer: the new rows' write (one grid step a call), then the attention
    assert grids == [(1,), (FULL["serve"]["slots"],)]


def _pool_sized_ops(hlo, sizes):
    """The optimized HLO's ``copy`` / ``dynamic-slice`` / ``dynamic-update-slice``
    instructions (fused or not) whose result has one of ``sizes`` elements."""
    found = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?\S+ = \w+\[([\d,]*)\]\S* (copy|dynamic-slice|dynamic-update-slice)\(", line)
        if m and int(np.prod([int(d) for d in m.group(1).split(",") if d])) in sizes:
            found.append(line.strip()[:160])
    return found


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("program", ["decode", "prefill_chunk"])
def test_engine_paged_programs_update_the_pool_in_place(as_chip, v5e, program, chips):
    """The serve programs with the cache donated, at a pool whose one layer
    is 64 MiB: the compiler needs less scratch than one layer's slice of one
    pool, and no instruction copies, slices out or writes back a layer's or
    a whole pool's worth of elements. (The pool used to be re-laid-out per
    layer and stacked into a second pool: 5.4 GiB of temporaries at the
    benchmark's size, and more device time than the attention kernel.)

    On four chips, as ``LLMEngine(mesh=...)`` builds the programs: params per
    ``shard_params``, the pool split over its KV heads and pinned so on the
    way out, no Mosaic kernel. Each device then holds a quarter of every
    layer, and the same holds of its quarter."""
    import dataclasses

    from ray_tpu.models import init_params
    from ray_tpu.models.generation import (
        init_paged_cache, paged_cache_spec, paged_decode_step, paged_forward_with_cache)
    from ray_tpu.models.transformer import fit_spec, param_specs

    # a small vocabulary: what is left among the temporaries is the weights the
    # compiler prefetches into on-chip memory (``S(1)``), ~20 MiB here
    cfg = dataclasses.replace(serving_config(), n_layers=4, vocab_size=4096)
    B, bs, M, N, C = 8, 16, 64, 4096, 256
    if chips == 1:
        rest = pool = SingleDeviceSharding(v5e[0])
        params = _abstract_tree(lambda: init_params(cfg, jax.random.key(0)), rest)
        pinned, kernel = None, None
    else:
        mesh = Mesh(np.array(v5e), ("tp",))
        rest, pool = NamedSharding(mesh, P()), NamedSharding(mesh, paged_cache_spec("tp"))
        params = jax.tree.map(
            lambda x, spec: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=NamedSharding(mesh, fit_spec(x.shape, spec, mesh))),
            jax.eval_shape(lambda: init_params(cfg, jax.random.key(0))), param_specs(cfg, ep="tp"),
            is_leaf=lambda x: isinstance(x, P))
        pinned, kernel = (None, pool), False
    cache = _abstract_tree(lambda: init_paged_cache(cfg, N, bs), pool)
    layer_elems = int(np.prod(cache["k"].shape[1:])) // chips
    layer_bytes = layer_elems * cache["k"].dtype.itemsize
    assert layer_bytes == 64 * 2**20 // chips

    if program == "decode":
        def fn(params, cache, toks, pos, bt):
            return paged_decode_step(cfg, params, cache, toks, pos, bt, use_decode_kernel=kernel)

        args = _abstract([((B,), I32), ((B,), I32), ((B, M), I32)], rest)
    else:
        def fn(params, cache, toks, bt, start, length):
            positions = start + jnp.arange(C)[None, :]
            valid = (jnp.arange(C) < length)[None, :]
            return paged_forward_with_cache(
                cfg, params, cache, bt, toks, positions, valid=valid, use_decode_kernel=kernel)

        args = _abstract([((1, C), I32), ((1, M), I32), ((), I32), ((), I32)], rest)
    lowered = jax.jit(fn, donate_argnums=(1,), out_shardings=pinned).trace(params, cache, *args).lower(
        lowering_platforms=("tpu",))
    # on one chip both programs attend through a Mosaic kernel; under a mesh neither does
    assert ("tpu_custom_call" in lowered.as_text()) == (chips == 1)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()  # of one device
    assert mem.alias_size_in_bytes >= 2 * cfg.n_layers * layer_bytes  # both pools donated through
    assert mem.temp_size_in_bytes < layer_bytes, f"{mem.temp_size_in_bytes / 2**20:.1f} MiB of temporaries"
    assert _pool_sized_ops(compiled.as_text(), {layer_elems, cfg.n_layers * layer_elems}) == []
    if program == "prefill_chunk":
        # the float32 scores of a chunk over the table's whole capacity: gone with the kernel
        assert (re.search(rf"f32\[(?:\d+,){{2,}}{C},{M * bs}\]", compiled.as_text()) is None) == (chips == 1)


def test_a_windowed_expert_config_compiles_one_in_place_decode_program(as_chip, v5e):
    """Layer kinds riding the scan, the paged kernel with its window scalar,
    two layer stacks and the dropless expert layer's grouped products, at the
    served widths of the second family (GQA 32/4, head 128, experts 2048 x
    1024) and a small vocabulary: the chip's compiler takes the decode
    program, both pools are still donated through, and nothing pool-sized is
    copied."""
    import dataclasses

    from ray_tpu.models import TransformerConfig, init_params
    from ray_tpu.models.generation import init_paged_cache, paged_forward_counted

    cfg = TransformerConfig(
        vocab_size=4096, d_model=2048, n_layers=5, n_heads=32, n_kv_heads=4, head_dim=128, d_ff=6144,
        max_seq_len=8192, dtype=BF16, param_dtype=BF16, norm_eps=1e-5, tie_embeddings=False, qk_norm=True,
        attn_gate=True, post_norms=True, layer_types=("sliding",) * 3 + ("full", "sliding"), sliding_window=2048,
        rope_full_layers=False, num_experts=16, expert_top_k=8, num_dense_layers=1, expert_d_ff=1024,
        num_shared_experts=1, router_score="sigmoid", route_scale=2.826, router_bias=True)
    one = SingleDeviceSharding(v5e[0])
    B, bs, M, N = 48, 16, 512, 8192
    params = _abstract_tree(lambda: init_params(cfg, jax.random.key(0)), one)
    cache = _abstract_tree(lambda: init_paged_cache(cfg, N, bs), one)
    layer_elems = int(np.prod(cache["k"].shape[1:]))

    def fn(params, cache, toks, pos, bt):
        return paged_forward_counted(cfg, params, cache, bt, toks[:, None], pos[:, None], valid=(bt[:, 0] > 0)[:, None])

    args = _abstract([((B,), I32), ((B,), I32), ((B, M), I32)], one)
    lowered = jax.jit(fn, donate_argnums=(1,)).trace(params, cache, *args).lower(lowering_platforms=("tpu",))
    assert "tpu_custom_call" in lowered.as_text()
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * cfg.n_layers * layer_elems * 2
    assert _pool_sized_ops(compiled.as_text(), {layer_elems, cfg.n_layers * layer_elems}) == []
    assert dataclasses.replace(cfg, scan_layers=False).layer_windows == (2048, 2048, 2048, 0, 2048)


def test_the_sdar_cells_block_step_and_prefill_chunk_compile_for_v5e_and_fit(as_chip, v5e):
    """The benchmark's diffusion configuration as its file states it (6 layers
    of 128 experts at published widths, 48 slots, 12 288 pages): the engine's
    own decode program, a block step of 48 rows x 4 positions with its state
    on the device, and its prefill chunk, which runs no head, compile for a
    v5e with the Mosaic kernel in them, update the donated pool in place and
    fit the chip's 16 GB."""
    from benchmark import system
    from ray_tpu.models import init_params
    from ray_tpu.models.generation import init_paged_cache, open_blocks, paged_block_step, paged_forward_counted

    config = system.load_json("benchmark/configs/sdar-30b-a3b-serve-l6.json")
    run = config["run"]
    cfg = system.model_module(config).program_config(
        config, max_seq_len=run["max_seq_len"], dtype=run["dtype"], param_dtype=run["param_dtype"])
    one = SingleDeviceSharding(v5e[0])
    B, bs, C = run["max_batch_size"], run["kv_block_size"], run["prefill_chunk_tokens"]
    M = run["max_seq_len"] // bs
    params = _abstract_tree(lambda: init_params(cfg, jax.random.key(0)), one)
    cache = _abstract_tree(lambda: init_paged_cache(cfg, run["kv_num_blocks"], bs), one)
    state = _abstract_tree(lambda: open_blocks(cfg, jnp.ones(B, I32)), one)
    pos, bt, toks, row, scalar = _abstract([((B,), I32), ((B, M), I32), ((1, C), I32), ((1, M), I32), ((), I32)], one)
    pool_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(cache))

    def step(params, cache, state, pos, bt):
        _, cache, state, done, moe = paged_block_step(cfg, params, cache, bt, state, pos, live=bt[:, 0] > 0)
        return done, cache, state, moe

    def chunk(params, cache, toks, bt, start, length):
        valid = (jnp.arange(C) < length)[None, :]
        _, cache, moe = paged_forward_counted(cfg, params, cache, bt, toks, start + jnp.arange(C)[None, :],
                                              valid=valid, with_logits=False)
        return cache, moe

    for fn, args in ((step, (params, cache, state, pos, bt)), (chunk, (params, cache, toks, row, scalar, scalar))):
        lowered = jax.jit(fn, donate_argnums=(1,)).trace(*args).lower(lowering_platforms=("tpu",))
        assert "tpu_custom_call" in lowered.as_text()
        m = lowered.compile().memory_analysis()
        assert m.alias_size_in_bytes >= pool_bytes
        assert m.argument_size_in_bytes + m.temp_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes < 15.5e9


def test_the_olmo_hybrid_cells_decode_step_and_prefill_chunk_compile_for_v5e_and_fit(as_chip, v5e):
    """The benchmark's hybrid configuration as its file states it (16 layers at
    published widths: 12 Gated DeltaNet, 4 full attention; 32 slots, 3072
    pages for the full layers only, 64 state snapshots): a decode step of 32
    rows and a prefill chunk of 512 tokens compile for a v5e with both Mosaic
    kernels in the decode step (the paged attention and the state update),
    update the donated pages, states and convolution tails in place, and fit
    the chip's 16 GB beside the snapshot pool. The memory analysis is what the
    configuration file's ``sizing`` quotes."""
    from benchmark import system
    from ray_tpu.models import init_params
    from ray_tpu.models.generation import (copy_sequence_state, init_paged_cache, init_sequence_state,
                                           paged_forward_counted)

    config = system.load_json("benchmark/configs/olmo-hybrid-7b-serve-l16.json")
    run = config["run"]
    cfg = system.model_module(config).program_config(
        config, max_seq_len=run["max_seq_len"], dtype=run["dtype"], param_dtype=run["param_dtype"])
    one = SingleDeviceSharding(v5e[0])
    B, bs, C = run["max_batch_size"], run["kv_block_size"], run["prefill_chunk_tokens"]
    M = run["max_seq_len"] // bs
    params = _abstract_tree(lambda: init_params(cfg, jax.random.key(0)), one)
    cache = _abstract_tree(lambda: init_paged_cache(cfg, run["kv_num_blocks"], bs, slots=B), one)
    snaps = _abstract_tree(lambda: init_sequence_state(cfg, run["state_snapshots"]), one)
    assert cache["k"].shape == (4, 3072, 16, 3840) and cache["state"].shape == (12, 32, 15, 96, 384)
    assert cache["conv"].shape == (12, 32, 3 * 11520) and snaps["state"].shape == (12, 64, 15, 96, 384)
    toks, pos, bt, chunk, row, slot, scalar = _abstract(
        [((B,), I32), ((B,), I32), ((B, M), I32), ((1, C), I32), ((1, M), I32), ((1,), I32), ((), I32)], one)
    nbytes = lambda tree: sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(tree))  # noqa: E731
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params)) == system.model_module(config).n_params(config)

    def step(params, cache, toks, pos, bt):
        live = (bt[:, 0] > 0)[:, None]
        logits, cache, _ = paged_forward_counted(cfg, params, cache, bt, toks[:, None], pos[:, None], valid=live,
                                                 slots=jnp.arange(B, dtype=I32))
        return jnp.argmax(logits[:, 0], -1), cache

    def prefill(params, cache, toks, bt, start, length, slot):
        valid = (jnp.arange(C) < length)[None, :]
        logits, cache, _ = paged_forward_counted(cfg, params, cache, bt, toks, start + jnp.arange(C)[None, :],
                                                 valid=valid, slots=slot)
        return jax.lax.dynamic_index_in_dim(logits[0], length - 1, 0, keepdims=False), cache

    report = {}
    for name, fn, args, kernels in (("decode", step, (params, cache, toks, pos, bt), 2),
                                    ("prefill_chunk", prefill, (params, cache, chunk, row, scalar, scalar, slot), 1)):
        lowered = jax.jit(fn, donate_argnums=(1,)).trace(*args).lower(lowering_platforms=("tpu",))
        text = lowered.as_text()
        assert "tpu_custom_call" in text
        if kernels == 2:
            assert "gated_delta_decode" in text
        m = lowered.compile().memory_analysis()
        assert m.alias_size_in_bytes >= nbytes(cache)  # pages, states and tails are updated where they lie
        need = m.argument_size_in_bytes + m.temp_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes
        report[name] = {"arguments_gb": round(m.argument_size_in_bytes / 1e9, 2), "temporaries_gb": round(m.temp_size_in_bytes / 1e9, 2)}
        assert need + nbytes(snaps) < 15.5e9, (name, report)
    # a snapshot's copy moves one sequence's state and nothing else
    lowered = jax.jit(copy_sequence_state, donate_argnums=(0,)).trace(snaps, cache, scalar, scalar).lower(
        lowering_platforms=("tpu",))
    m = lowered.compile().memory_analysis()
    one_slot = nbytes({k: cache[k] for k in ("state", "conv")}) // B
    assert m.temp_size_in_bytes < 2 * one_slot and one_slot == system.model_module(config).state_bytes_per_sequence(config)
    print("olmo hybrid sizing:", report, "snapshots_gb", round(nbytes(snaps) / 1e9, 2), "cache_gb", round(nbytes(cache) / 1e9, 2))


def test_the_kimi_linear_cells_decode_step_and_prefill_chunk_compile_for_v5e_and_fit(as_chip, v5e):
    """The benchmark's Kimi Linear configuration as its file states it (8
    layers at published widths: 6 KDA, 2 latent attention; layer 1's FFN
    dense, seven expert layers holding 64 of 256 experts; 64 slots, 32 768
    pages of ONE latent pool, 192 state snapshots): a decode step of 64 rows
    and a prefill chunk of 512 tokens compile for a v5e with the three Mosaic
    kernels by name (the latent page walk, the state update, the grouped
    products), update the donated pool, states and tails in place, build no K
    and no V pool, and fit the chip's 16 GB beside the snapshot pool. The
    memory analysis is what the configuration file's ``sizing`` quotes."""
    from benchmark import system
    from ray_tpu.models import init_params
    from ray_tpu.models.generation import init_paged_cache, init_sequence_state, paged_forward_counted

    config = system.load_json("benchmark/configs/kimi-linear-48b-a3b-serve-l8.json")
    run = config["run"]
    cfg = system.model_module(config).program_config(
        config, max_seq_len=run["max_seq_len"], dtype=run["dtype"], param_dtype=run["param_dtype"])
    one = SingleDeviceSharding(v5e[0])
    B, bs, C = run["max_batch_size"], run["kv_block_size"], run["prefill_chunk_tokens"]
    M = run["max_seq_len"] // bs
    params = _abstract_tree(lambda: init_params(cfg, jax.random.key(0)), one)
    cache = _abstract_tree(lambda: init_paged_cache(cfg, run["kv_num_blocks"], bs, slots=B), one)
    snaps = _abstract_tree(lambda: init_sequence_state(cfg, run["state_snapshots"]), one)
    assert set(cache) == {"latent", "state", "conv"} and cache["latent"].shape == (2, 32768, 16, 640)
    assert cache["state"].shape == (6, 64, 32, 128, 128) and cache["conv"].shape == (6, 64, 3 * 12288)
    assert params["expert_ffn"]["we1"].shape == (7, 64, 2304, 1024) and params["expert_ffn"]["router"].shape == (7, 2304, 256)
    toks, pos, bt, chunk, row, slot, scalar = _abstract(
        [((B,), I32), ((B,), I32), ((B, M), I32), ((1, C), I32), ((1, M), I32), ((1,), I32), ((), I32)], one)
    nbytes = lambda tree: sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(tree))  # noqa: E731
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params)) == system.model_module(config).n_params(config)

    def step(params, cache, toks, pos, bt):
        live = (bt[:, 0] > 0)[:, None]
        logits, cache, moe = paged_forward_counted(cfg, params, cache, bt, toks[:, None], pos[:, None], valid=live,
                                                   slots=jnp.arange(B, dtype=I32))
        return jnp.argmax(logits[:, 0], -1), cache, moe

    def prefill(params, cache, toks, bt, start, length, slot):
        valid = (jnp.arange(C) < length)[None, :]
        logits, cache, moe = paged_forward_counted(cfg, params, cache, bt, toks, start + jnp.arange(C)[None, :],
                                                   valid=valid, slots=slot)
        return jax.lax.dynamic_index_in_dim(logits[0], length - 1, 0, keepdims=False), cache, moe

    report = {}
    for name, fn, args, kernels in (
            ("decode", step, (params, cache, toks, pos, bt), ("latent_paged_decode", "gated_delta_decode")),
            ("prefill_chunk", prefill, (params, cache, chunk, row, scalar, scalar, slot), ("latent_paged_prefill",))):
        lowered = jax.jit(fn, donate_argnums=(1,)).trace(*args).lower(lowering_platforms=("tpu",))
        text = lowered.as_text()
        for kernel in kernels:
            assert kernel in text, (name, kernel)
        assert "paged_decode\"" not in text.replace("latent_paged_decode", "")  # no K/V walk: there is no such pool
        m = lowered.compile().memory_analysis()
        assert m.alias_size_in_bytes >= nbytes(cache)  # the pool, the states and the tails are updated where they lie
        need = m.argument_size_in_bytes + m.temp_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes
        report[name] = {"arguments_gb": round(m.argument_size_in_bytes / 1e9, 2), "temporaries_gb": round(m.temp_size_in_bytes / 1e9, 2)}
        assert need + nbytes(snaps) < 15.5e9, (name, report)
    print("kimi linear sizing:", report, "params_gb", round(nbytes(params) / 1e9, 2), "snapshots_gb", round(nbytes(snaps) / 1e9, 2),
          "cache_gb", round(nbytes(cache) / 1e9, 2), "latent_pool_gb", round(nbytes(cache["latent"]) / 1e9, 2))


def test_the_lfm2_cells_decode_step_and_prefill_chunk_compile_for_v5e_and_fit(as_chip, v5e):
    """The benchmark's LFM2 configuration as its file states it (14 layers at
    published widths: 11 gated short convolutions and 3 GQA layers by the plan
    (2, 4, 3); 2 dense FFNs and 12 expert layers of all 32 experts; 128 slots,
    16 384 pages of 3 KV layers, 512 tail snapshots): a decode step of 128
    rows and a prefill chunk of 512 tokens compile for a v5e with the paged
    kernels and the grouped products by name, update the donated pools and
    tails in place (no tail pool is copied: the temporaries stay under a chunk's
    float32 logits and activations), keep no recurrent matrix, and fit the
    chip's 16 GB beside the snapshot pool. The memory analysis is what the
    configuration file's ``sizing`` quotes."""
    from benchmark import system
    from ray_tpu.models import init_params
    from ray_tpu.models.generation import init_paged_cache, init_sequence_state, paged_forward_counted

    config = system.load_json("benchmark/configs/lfm2-8b-a1b-serve-l14.json")
    run = config["run"]
    cfg = system.model_module(config).program_config(
        config, max_seq_len=run["max_seq_len"], dtype=run["dtype"], param_dtype=run["param_dtype"])
    assert cfg.plan == (2, 4, 3) and (cfg.conv_layers, cfg.kv_layers) == (11, 3)
    one = SingleDeviceSharding(v5e[0])
    B, bs, C = run["max_batch_size"], run["kv_block_size"], run["prefill_chunk_tokens"]
    M = run["max_seq_len"] // bs
    params = _abstract_tree(lambda: init_params(cfg, jax.random.key(0)), one)
    cache = _abstract_tree(lambda: init_paged_cache(cfg, run["kv_num_blocks"], bs, slots=B), one)
    snaps = _abstract_tree(lambda: init_sequence_state(cfg, run["state_snapshots"]), one)
    assert set(cache) == {"k", "v", "conv"} and cache["k"].shape == (3, 16384, 16, 512)
    assert cache["conv"].shape == (11, 128, 2 * 2048) and set(snaps) == {"conv"}
    assert params["expert_ffn"]["we1"].shape == (12, 32, 2048, 1792) and params["dense_ffn"]["w1"].shape == (2, 2048, 7168)
    assert [len(params[k]) for k in ("lead_layers", "period_layers", "tail_layers")] == [2, 4, 0]
    toks, pos, bt, chunk, row, slot, scalar = _abstract(
        [((B,), I32), ((B,), I32), ((B, M), I32), ((1, C), I32), ((1, M), I32), ((1,), I32), ((), I32)], one)
    nbytes = lambda tree: sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(tree))  # noqa: E731
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params)) == system.model_module(config).n_params(config)

    def step(params, cache, toks, pos, bt):
        live = (bt[:, 0] > 0)[:, None]
        logits, cache, moe = paged_forward_counted(cfg, params, cache, bt, toks[:, None], pos[:, None], valid=live,
                                                   slots=jnp.arange(B, dtype=I32))
        return jnp.argmax(logits[:, 0], -1), cache, moe

    def prefill(params, cache, toks, bt, start, length, slot):
        valid = (jnp.arange(C) < length)[None, :]
        logits, cache, moe = paged_forward_counted(cfg, params, cache, bt, toks, start + jnp.arange(C)[None, :],
                                                   valid=valid, slots=slot)
        return jax.lax.dynamic_index_in_dim(logits[0], length - 1, 0, keepdims=False), cache, moe

    report = {}
    for name, fn, args, kernels in (
            ("decode", step, (params, cache, toks, pos, bt), ("paged_decode", "grouped_matmul", "paged_write")),
            ("prefill_chunk", prefill, (params, cache, chunk, row, scalar, scalar, slot), ("paged_prefill", "grouped_matmul"))):
        lowered = jax.jit(fn, donate_argnums=(1,)).trace(*args).lower(lowering_platforms=("tpu",))
        text = lowered.as_text()
        for kernel in kernels:
            assert kernel in text, (name, kernel)
        assert "gated_delta" not in text  # a tail a slot and no recurrent matrix
        m = lowered.compile().memory_analysis()
        assert m.alias_size_in_bytes >= nbytes(cache)  # the pools and the tails are updated where they lie
        need = m.argument_size_in_bytes + m.temp_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes
        report[name] = {"arguments_gb": round(m.argument_size_in_bytes / 1e9, 2), "temporaries_gb": round(m.temp_size_in_bytes / 1e9, 3)}
        assert m.temp_size_in_bytes < 0.6e9, (name, report)  # no expert stack, mixer stack or pool is copied out
        assert need + nbytes(snaps) < 15.5e9, (name, report)
    print("lfm2 sizing:", report, "params_gb", round(nbytes(params) / 1e9, 2), "snapshots_gb", round(nbytes(snaps) / 1e9, 3),
          "cache_gb", round(nbytes(cache) / 1e9, 2), "tails_gb", round(nbytes(cache["conv"]) / 1e9, 4))


def _cell_programs(name):
    """A serve cell's decode (or block) step and prefill chunk at the
    benchmark's own configuration, abstract arguments and all, not placed."""
    from benchmark import system
    from ray_tpu.models import init_params
    from ray_tpu.models.generation import init_paged_cache, open_blocks, paged_block_step, paged_forward_counted

    config = system.load_json(f"benchmark/configs/{name}.json")
    run = config["run"]
    cfg = system.model_module(config).program_config(
        config, max_seq_len=run["max_seq_len"], dtype=run["dtype"], param_dtype=run["param_dtype"])
    B, bs, C = run["max_batch_size"], run["kv_block_size"], run["prefill_chunk_tokens"]
    M = run["max_seq_len"] // bs
    params = _abstract_tree(lambda: init_params(cfg, jax.random.key(0)))
    cache = _abstract_tree(lambda: init_paged_cache(cfg, run["kv_num_blocks"], bs))
    pos, bt, toks, row, scalar = _abstract([((B,), I32), ((B, M), I32), ((1, C), I32), ((1, M), I32), ((), I32)])

    def chunk(params, cache, toks, bt, start, length):
        valid = (jnp.arange(C) < length)[None, :]
        return paged_forward_counted(cfg, params, cache, bt, toks, start + jnp.arange(C)[None, :], valid=valid,
                                     with_logits=not cfg.block)

    if cfg.block:
        state = _abstract_tree(lambda: open_blocks(cfg, jnp.ones(B, I32)))
        step = lambda params, cache, state, pos, bt: paged_block_step(cfg, params, cache, bt, state, pos, live=bt[:, 0] > 0)
        step_args = (params, cache, state, pos, bt)
    else:
        step = lambda params, cache, toks, pos, bt: paged_forward_counted(
            cfg, params, cache, bt, toks[:, None], pos[:, None], valid=(bt[:, 0] > 0)[:, None])
        step_args = (params, cache, pos, pos, bt)
    return {"step": (step, step_args), "chunk": (chunk, (params, cache, toks, row, scalar, scalar))}


@pytest.mark.parametrize("cell,program,products", [
    ("trinity-mini-serve-l5", "step", 3), ("trinity-mini-serve-l5", "chunk", 3),
    ("sdar-30b-a3b-serve-l6", "step", 3), ("sdar-30b-a3b-serve-l6", "chunk", 3),
    ("smollm2-1.7b-serve", "step", 0), ("smollm2-1.7b-serve", "chunk", 0)])
def test_the_expert_layers_grouped_products_are_the_named_kernel(as_chip, cell, program, products):
    """Lowered for the chip, an expert layer's three projections are three
    Mosaic calls named ``grouped_matmul`` (the layer scan's body holds them
    once) and no ``ragged_dot`` is left; a dense model's programs hold none."""
    fn, args = _cell_programs(cell)[program]
    text = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count('kernel_name = "grouped_matmul"') == products
    assert "ragged_dot" not in text
    assert "tpu_custom_call" in text  # the paged attention kernels, in every program


# --------------------------------------------------------------------------
# make_train_step, AOT
# --------------------------------------------------------------------------
def _abstract_train_state(cfg, mesh=None, device=None):
    """The train state's shapes with the shardings ``sharded_init`` gives
    them: params per ``param_specs``, adam moments like their params, step
    counts replicated."""
    from ray_tpu.models.transformer import _kv_tp_ok, make_train_step, param_specs

    init_state, _ = make_train_step(cfg)
    state = jax.eval_shape(init_state, jax.random.key(0))

    def struct(x, sharding):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    if mesh is None:
        return jax.tree.map(lambda x: struct(x, SingleDeviceSharding(device)), state)
    specs = param_specs(cfg, kv_tp=_kv_tp_ok(cfg, mesh, "tp"))
    params_def = jax.tree.structure(state["params"])

    def params_like(node):
        return jax.tree.structure(node) == params_def

    def place(node):
        if params_like(node):  # the params, adam's mu and nu
            return jax.tree.map(lambda x, s: struct(x, NamedSharding(mesh, s)), node, specs)
        return struct(node, NamedSharding(mesh, P()))

    return jax.tree.map(place, state, is_leaf=params_like)


def test_train_step_602m_compiles_for_one_v5e_and_fits(as_chip, v5e):
    """The job ``bench.py`` and ``chip_smoke.py`` name, at the batch they
    name: compiles with its Mosaic kernels and fits one chip's 16 GiB."""
    from ray_tpu.models.transformer import make_train_step

    cfg = train_config()
    _, train_step = make_train_step(cfg)
    state = _abstract_train_state(cfg, device=v5e[0])
    tokens = jax.ShapeDtypeStruct((TRAIN_BATCH, cfg.max_seq_len), I32, sharding=SingleDeviceSharding(v5e[0]))
    lowered = train_step.trace(state, tokens).lower(lowering_platforms=("tpu",))
    assert "tpu_custom_call" in lowered.as_text()
    mem = lowered.compile().memory_analysis()
    need = mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes - mem.alias_size_in_bytes
    assert need < 16 * 2**30, f"train step needs {need / 2**30:.2f} GiB"


def test_the_moonlight_cells_train_step_compiles_for_one_v5e_and_fits(as_chip, v5e):
    """The benchmark's Moonlight configuration as its file states it (layer 0
    dense + 5 expert layers at published widths, 8 of 64 experts and 20 480
    vocabulary rows held, 8192 tokens a step): ``make_train_step``'s one
    program compiles for a v5e with the flash kernels at keys of 192 and
    values of 128 and the grouped kernel by name, carries the load counter,
    updates the donated state where it lies, and fits the chip. The memory
    analysis is what the configuration file's ``sizing`` quotes."""
    from benchmark import system
    from ray_tpu.models.transformer import make_train_step

    config = system.load_json("benchmark/configs/moonlight-16b-a3b-train-ep8.json")
    run = config["run"]
    cfg = system.model_module(config).program_config(
        config, max_seq_len=run["seq_len"], dtype=run["dtype"], param_dtype=run["param_dtype"],
        attention=run["attention"], remat=run["remat"], scan_layers=run["scan_layers"])
    from benchmark.kinds.routed_train_steps import learning_rate

    init_state, train_step = make_train_step(cfg, learning_rate=learning_rate(run))    # the cell's warm-up
    state = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=SingleDeviceSharding(v5e[0])),
                         jax.eval_shape(init_state, jax.random.key(0)))
    assert state["expert_load"].shape == (5, 64) and state["params"]["layers"]["we1"].shape == (5, 8, 2048, 1408)
    assert state["params"]["head"].shape == (20480, 2048) and "router_bias" not in state["opt"][0].mu["layers"]
    tokens = jax.ShapeDtypeStruct((run["batch"], run["seq_len"]), I32, sharding=SingleDeviceSharding(v5e[0]))
    lowered = train_step.trace(state, tokens).lower(lowering_platforms=("tpu",))
    text = lowered.as_text()
    assert "grouped_matmul" in text and text.count("tpu_custom_call") >= 6 * 3 + 5 * 3
    assert "bf16[16,8192,192]" in text.replace("tensor<16x8192x192xbf16>", "bf16[16,8192,192]")  # keys of 192 ...
    assert "16x8192x128xbf16" in text                                                              # ... values of 128
    m = lowered.compile().memory_analysis()
    state_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(state))
    assert m.alias_size_in_bytes >= state_bytes - 64  # parameters and moments are updated where they lie
    need = m.argument_size_in_bytes + m.temp_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes
    print("moonlight sizing:", {"state_gib": round(state_bytes / 2**30, 2), "temporaries_gib": round(m.temp_size_in_bytes / 2**30, 2),
                                "needs_gib": round(need / 2**30, 2)})
    assert 0.6 * 15.75 * 2**30 < need < 15.0 * 2**30, f"train step needs {need / 2**30:.2f} GiB"


def test_the_moonlight_cells_float32_gradient_program_compiles_for_one_v5e_and_fits(as_chip, v5e):
    """What the cell's comparison runs before its window beside the step: the
    same ``loss_and_load`` at float32 activations and "highest" products. The
    flash backward's tiles at keys of 192 and values of 128 fit the kernel's
    VMEM only at the float32 blocks (``default_blocks``: (512, 1024) was
    refused by 1.86 MB), and the program fits beside the step's gradients."""
    from benchmark import system
    from benchmark.kinds import routed_train_steps as kind
    from ray_tpu.models.transformer import init_params, loss_and_load
    from ray_tpu.ops.attention import default_blocks

    assert default_blocks(192) == default_blocks(192, 2) == (512, 1024) and default_blocks(192, 4) == (512, 512)
    config = system.load_json("benchmark/configs/moonlight-16b-a3b-train-ep8.json")
    run = config["run"]
    cfg, cfg32 = kind.program_configs(config, system.model_module(config))
    assert cfg32.dtype == jnp.float32 and cfg32.attention == "flash" and cfg.dtype == jnp.bfloat16
    one = SingleDeviceSharding(v5e[0])
    params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
                          jax.eval_shape(lambda: init_params(cfg32, jax.random.key(0))))
    tokens = jax.ShapeDtypeStruct((run["batch"], run["seq_len"]), I32, sharding=one)
    fn = jax.jit(jax.value_and_grad(lambda p, t: loss_and_load(cfg32, p, t), has_aux=True))
    with jax.default_matmul_precision("highest"):
        lowered = fn.trace(params, tokens).lower(lowering_platforms=("tpu",))
    assert "16x8192x192xf32" in lowered.as_text()
    m = lowered.compile().memory_analysis()
    need = m.argument_size_in_bytes + m.temp_size_in_bytes + m.output_size_in_bytes
    # beside it: the step's own gradients (2.49 GiB) wait for the reference
    assert need + 2.5 * 2**30 < 15.0 * 2**30, f"the float32 gradient program needs {need / 2**30:.2f} GiB"


@pytest.mark.parametrize("attention", ["auto", "dense", "ring"])
def test_train_step_compiles_under_a_four_chip_mesh(as_chip, v5e, attention):
    """Every attention mode ``make_train_step`` accepts with a mesh compiles
    for four real chips (602M widths, depth cut to 2: the refusals this
    guards — an unpartitionable Mosaic call, a bad block — are per layer)."""
    import dataclasses

    from ray_tpu.models.transformer import make_train_step

    cfg = dataclasses.replace(train_config(), attention=attention, n_layers=2)
    mesh = Mesh(np.array(v5e).reshape(1, 2, 2), ("dp", "sp", "tp"))
    _, train_step = make_train_step(cfg, mesh=mesh)
    state = _abstract_train_state(cfg, mesh=mesh)
    tokens = jax.ShapeDtypeStruct((2, cfg.max_seq_len), I32, sharding=NamedSharding(mesh, P("dp", None)))
    lowered = train_step.lower(state, tokens)
    assert ("tpu_custom_call" in lowered.as_text()) == (attention == "ring")
    assert train_step.ring_layout == ("zigzag" if attention == "ring" else None)  # 2048 tokens over sp2 cut in four
    lowered.compile()


def test_an_expert_layer_under_a_mesh_keeps_xlas_grouped_product(as_chip, v5e):
    """GSPMD partitions ``ragged_dot`` over the sharded experts and refuses a
    Mosaic call ("cannot be automatically partitioned"): under a mesh the
    train step's expert layers hold no ``grouped_matmul`` kernel, and the
    step compiles for four chips as it did before the kernel came."""
    from ray_tpu.models import TransformerConfig
    from ray_tpu.models.transformer import make_train_step

    cfg = TransformerConfig(
        vocab_size=4096, d_model=1024, n_layers=2, n_heads=8, n_kv_heads=4, head_dim=128, d_ff=2048, max_seq_len=1024,
        dtype=BF16, param_dtype=F32, num_experts=8, expert_top_k=2, expert_d_ff=512, attention="dense")
    mesh = Mesh(np.array(v5e).reshape(2, 1, 2), ("dp", "sp", "tp"))
    _, train_step = make_train_step(cfg, mesh=mesh)
    tokens = jax.ShapeDtypeStruct((4, cfg.max_seq_len), I32, sharding=NamedSharding(mesh, P("dp", None)))
    lowered = train_step.lower(_abstract_train_state(cfg, mesh=mesh), tokens)
    assert "ragged_dot" in lowered.as_text() and "grouped_matmul" not in lowered.as_text()
    lowered.compile()
    _, alone = make_train_step(cfg)  # one chip: the kernel, and ragged_dot only in its gradient
    state = _abstract_train_state(cfg, device=v5e[0])
    lowered = alone.trace(state, jax.ShapeDtypeStruct((4, cfg.max_seq_len), I32, sharding=SingleDeviceSharding(v5e[0]))).lower(
        lowering_platforms=("tpu",))
    assert 'kernel_name = "grouped_matmul"' in lowered.as_text() and "ragged_dot" in lowered.as_text()
    lowered.compile()


def test_flash_under_a_mesh_is_refused_at_build_time():
    """CPU meshes used to accept this (interpret mode lowers to ordinary
    ops GSPMD can partition) and chips refuse it; now both refuse, early."""
    import dataclasses

    from ray_tpu.models.transformer import make_train_step

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    cfg = dataclasses.replace(train_config(), attention="flash")
    with pytest.raises(ValueError, match='"ring"'):
        make_train_step(cfg, mesh=mesh)


# --------------------------------------------------------------------------
# the rules
# --------------------------------------------------------------------------
def test_default_backend_is_compared_in_one_place():
    hits = []
    for root, _, files in os.walk(os.path.join(REPO, "ray_tpu")):
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(root, name)
                with open(path) as f:
                    for n, line in enumerate(f, 1):
                        if re.search(r"default_backend\(\)", line):
                            hits.append(f"{os.path.relpath(path, REPO)}:{n}")
    assert len(hits) == 1 and hits[0].startswith("ray_tpu/ops/backend.py:"), hits


_CACHE_PROBE = """
import jax
set_in_code = []
update = jax.config.update
jax.config.update = lambda key, value: (set_in_code.append(key), update(key, value))
from ray_tpu.ops.backend import use_compile_cache
print(use_compile_cache())
print(jax.config.jax_compilation_cache_dir)
print("jax_compilation_cache_dir" in set_in_code)
"""


def _cache_probe(env_dir):
    """(what the helper returned, what jax holds, whether code set it) in a
    fresh process with ``JAX_COMPILATION_CACHE_DIR`` set to ``env_dir``."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=120, check=True,
    ).stdout.split()
    return out[0], out[1], out[2] == "True"


def test_compile_cache_unset_is_one_fixed_path_in_the_checkout():
    fixed = os.path.join(REPO, ".jax_cache")
    assert _cache_probe(None) == _cache_probe(None) == (fixed, fixed, True)


def test_compile_cache_env_is_left_to_jax(tmp_path):
    """Placed from outside: jax reads the variable, the code sets nothing."""
    placed = str(tmp_path / "placed")
    assert _cache_probe(placed) == (placed, placed, False)


def test_chip_smoke_fails_without_a_chip():
    """No flag, no accelerator: non-zero exit and no result on stdout."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "platform 'cpu'" in proc.stderr


def test_chip_smoke_last_line_is_the_verdict_and_nothing_else():
    """The driver parses the last stdout line: exactly ``ok`` and ``device``
    (``platform``, ``kind``, ``count``). No leg ran, so ``ok`` is false."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), "--rehearsal", "--legs", ""],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 1
    report, last = (json.loads(line) for line in proc.stdout.strip().splitlines())
    assert set(last) == {"ok", "device"} and last["ok"] is False
    assert last["device"] == {"platform": "cpu", "kind": "cpu", "count": jax.device_count()}
    assert report["rehearsal"] is True and report["claim"] is None and report["legs"] == {}
    assert chip_smoke.verdict(report) == last


def test_the_digest_tool_reads_a_kernels_payload_and_not_where_its_caller_stands(as_chip):
    """``benchmark/tools/lowered_digests.py``: a Mosaic payload is decoded
    (the text escapes a quote as ``\\22``), parsed and printed without debug
    information, so the same kernel called from two places in a file digests
    alike, another kernel does not, and a payload that does not parse is
    counted, not passed over (a comparison of unparsed payloads compares
    nothing: PR 51's first round)."""
    from benchmark.tools import lowered_digests as tool
    from ray_tpu.ops.grouped_matmul import grouped_matmul

    rows, w, sizes = _abstract([((64, 128), jnp.bfloat16), ((4, 128, 256), jnp.bfloat16), ((4,), I32)])

    def here():
        def product(rows, w, sizes):
            return grouped_matmul(rows, w, sizes)
        return product

    def there():
        def product(rows, w, sizes):

            return grouped_matmul(rows, w, sizes)
        return product

    here, there = here(), there()

    def lowered(fn, *args):
        return jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).as_text()

    a, b = tool.digest(lowered(here, rows, w, sizes)), tool.digest(lowered(there, rows, w, sizes))
    assert a == b and a["payloads"] == 1 and a["unparsed"] == 0
    wide = _abstract([((4, 128, 512), jnp.bfloat16)])[0]
    assert tool.digest(lowered(here, rows, wide, sizes))["kernels"] != a["kernels"]
    broken = lowered(here, rows, w, sizes).replace('\\22body\\22: \\22', '\\22body\\22: \\22AAAA')
    assert tool.digest(broken)["unparsed"] == 1
