"""``paged_kernel_us_per_live_page`` (PR 30): its manifest entry, its reader
on recorded data, and the engine counter it divides by, ``kv_live_pages``,
against sequences counted by hand."""

from __future__ import annotations

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import manifest, run as runner, trace_reduce  # noqa: E402

METRIC = "paged_kernel_us_per_live_page"
SERVE_CELLS = ["smollm2-1.7b-serve.chat-steady", "smollm2-1.7b-serve.agent-prefix",
               "smollm2-1.7b-serve.chat-saturated", "trinity-mini-serve-l5.mixed-lengths"]


def test_the_metric_is_the_serving_kernels_and_lists_the_four_serve_cells():
    m = manifest.load()
    assert manifest.problems(m, ROOT) == []
    entry = [x for x in m["per_layer"] if x["name"] == METRIC]
    assert entry == [{"name": METRIC, "unit": "us", "better": "lower", "source": "device_trace",
                      "layer": "kernels, serving", "moves": "itl_mean_ms", "workloads": SERVE_CELLS}]
    assert m["per_layer"][-1] == entry[0]  # appended, nothing before it moved
    for cell in SERVE_CELLS:
        assert "itl_mean_ms" in {x["name"] for x in manifest.metrics_of(m, "end_to_end", cell)}


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(ROOT, "benchmark", "testdata", "recorded_trace.json")) as f:
        return json.load(f)["events"]


def _run(events, samples, slots=64, kv_heads=32, layers=24):
    config = {"num_key_value_heads": kv_heads, "num_hidden_layers": layers, "run": {"max_batch_size": slots}}
    sampler = types.SimpleNamespace(samples=[(0.5, s) for s in samples])
    return {"ctx": types.SimpleNamespace(config=config), "events": events, "window": (0.0, 1.0),
            "probe": types.SimpleNamespace(sampler=sampler)}


def test_the_reader_divides_the_kernels_time_a_step_by_the_live_pages(recorded):
    read = runner.load_reader(METRIC, manifest.load()["paths"])
    plane = trace_reduce.device_planes(recorded)[0]
    steps = len(trace_reduce.program_runs(recorded, plane)["jit__decode_k_paged"])
    # the recorded decode steps' kernel: ``closed_call.14 custom-call bf16[64,32,8,64]``, once a layer
    kernel = [e for e in trace_reduce.ops_inside(recorded, plane, "jit__decode_k_paged")
              if e[2].endswith("custom-call bf16[64,32,8,64]")]
    assert steps >= 1 and len(kernel) == 24 * steps
    live = [{"kv_live_pages": 300.0}, {"kv_live_pages": 500.0}, {"active_slots": 3}]
    want = sum(e[4] for e in kernel) / 1e3 / steps / (400.0 * 24)
    assert read(_run(recorded, live)) == pytest.approx(want) and want > 0
    assert read(_run(recorded, live, layers=12)) == pytest.approx(2 * want)
    # a program without the counter (the parent's), nothing live, no trace, another engine's shapes
    assert read(_run(recorded, [{"active_slots": 3}])) is None
    assert read(_run(recorded, [{"kv_live_pages": 0.0}])) is None
    assert read(_run([], live)) is None
    assert read(_run(recorded, live, slots=40)) is None
    assert read({**_run(recorded, live), "ctx": None}) is None


def _engine(cfg, **kw):
    import jax

    from ray_tpu.models.transformer import init_params
    from ray_tpu.serve.llm import LLMEngine

    eng = LLMEngine(cfg, init_params(cfg, jax.random.key(0)), max_seq_len=256, kv_block_size=16, kv_num_blocks=64, **kw)
    eng.shutdown()  # the counter reads the slot arrays: no program has to run
    return eng


def test_kv_live_pages_counts_the_pages_a_decode_step_visits():
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64, dtype=jnp.float32, max_seq_len=256)
    eng = _engine(cfg, max_batch_size=4)
    assert eng.stats()["kv_live_pages"] == 0.0  # nothing live
    # cached tokens 0, 15, 16, 200: the step writes one more and attends over 1, 16, 17, 201
    eng._active[:], eng._pos[:] = True, [0, 15, 16, 200]
    assert eng.kv_live_pages() == 1 + 1 + 2 + 13
    eng._active[3] = False  # an idle slot's stale position counts nothing
    assert eng.stats()["kv_live_pages"] == 4.0


def test_kv_live_pages_starts_a_sliding_layer_at_its_windows_first_page():
    from benchmark import system

    config = system.shrink_for_rehearsal(system.load_json("benchmark/configs/trinity-mini-serve-l5.json"))
    cfg = system.model_module(config).program_config(config, max_seq_len=256, dtype="float32", param_dtype="float32")
    assert cfg.layer_windows == (16, 16, 16, 0, 16)
    eng = _engine(cfg, max_batch_size=3, prefill_chunk_tokens=32)
    # lengths 6 and 41 at the step: the full layer walks 1 + 3 pages; a sliding one sees
    # positions 0-5 (page 0) and 25-40 (pages 1-2): 1 + 2
    eng._active[:2], eng._pos[:2] = True, [5, 40]
    assert eng.kv_live_pages() == pytest.approx((4 * 3 + 4) / 5)
    # length 48: the window's first position, 32, opens page 2: one page where a full layer walks 3
    eng._active[:2], eng._pos[:2] = [True, False], [47, 40]
    assert eng.kv_live_pages() == pytest.approx((4 * 1 + 3) / 5)
