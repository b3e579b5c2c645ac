"""``prefill_kv_read_share`` (PR 39): its manifest entry, its reader on
counters written by hand (the parent's ``stats()`` has none: the reader must
leave the metric out, not raise), and the engine counters it divides."""

from __future__ import annotations

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import manifest, run as runner  # noqa: E402

METRIC = "prefill_kv_read_share"
VISITED, CAPACITY = "prefill_kv_tokens_visited", "prefill_kv_tokens_capacity"
CELLS = ["smollm2-1.7b-serve.chat-steady", "smollm2-1.7b-serve.agent-prefix", "trinity-mini-serve-l5.mixed-lengths"]


def test_the_metric_is_the_serving_kernels_and_lists_the_cells_below_the_knee():
    m = manifest.load()
    assert manifest.problems(m, ROOT) == []
    entry = [x for x in m["per_layer"] if x["name"] == METRIC]
    assert entry == [{"name": METRIC, "unit": "share", "better": "lower", "source": "program_counter",
                      "layer": "kernels, serving", "moves": "first16_mean_ms", "workloads": CELLS}]
    # the layer is one the manifest already names, letter for letter
    assert sum(x["layer"] == "kernels, serving" for x in m["per_layer"]) >= 3
    for cell in CELLS:
        assert "first16_mean_ms" in {x["name"] for x in manifest.metrics_of(m, "end_to_end", cell)}
        assert METRIC in {x["name"] for x in manifest.metrics_of(m, "per_layer", cell)}
    # above the knee the first line of a reply is not reported, so nothing is there for it to move
    for cell in ("smollm2-1.7b-serve.chat-saturated", "smollm2-1.7b-train-l8.steps", "smollm2-1.7b-train-ring4.steps"):
        assert METRIC not in {x["name"] for x in manifest.metrics_of(m, "per_layer", cell)}


def _run(opened, closed):
    probe = types.SimpleNamespace(stats_open=opened and (0.0, opened), stats_close=closed and (1.0, closed))
    return {"probe": probe}


@pytest.mark.parametrize("opened,closed,want", [
    ({VISITED: 1000.0, CAPACITY: 8192}, {VISITED: 1590.0, CAPACITY: 12288}, 590 / 4096),
    ({VISITED: 0.0, CAPACITY: 0}, {VISITED: 20.5, CAPACITY: 64}, 20.5 / 64),
    # the parent: chunks are counted, nothing says what their attention read
    ({"prefill_chunks": 3}, {"prefill_chunks": 9}, None),
    # no chunk inside the window; no counters read at its edges
    ({VISITED: 44.0, CAPACITY: 192}, {VISITED: 44.0, CAPACITY: 192}, None),
    (None, {VISITED: 1590.0, CAPACITY: 12288}, None),
    ({VISITED: 1000.0, CAPACITY: 8192}, None, None),
], ids=["ratio", "from_zero", "parent", "no_chunks", "no_open", "no_close"])
def test_the_reader_divides_the_windows_visited_tokens_by_its_capacity(opened, closed, want):
    read = runner.load_reader(METRIC, manifest.load()["paths"])
    got = read(_run(opened, closed))
    assert got is None if want is None else got == pytest.approx(want)


def test_the_engine_counts_what_the_reader_divides():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig, init_params
    from ray_tpu.serve.llm import LLMEngine

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64, dtype=jnp.float32, max_seq_len=64)
    eng = LLMEngine(cfg, init_params(cfg, jax.random.key(0)), max_batch_size=2, max_seq_len=64, kv_block_size=16,
                    prefill_chunk_tokens=16)
    try:
        before = eng.stats()
        assert (before[VISITED], before[CAPACITY]) == (0, 0)
        assert len(eng.generate(list(range(1, 41)), max_tokens=2)) == 2
        after = eng.stats()
        # 40 tokens in chunks of 16, 16 and 8 over a table of 4 pages of 16
        assert (after["prefill_chunks"], after[VISITED], after[CAPACITY]) == (3, 16 + 32 + 40, 3 * 64)
        read = runner.load_reader(METRIC, manifest.load()["paths"])
        assert read(_run(before, after)) == pytest.approx(88 / 192)
    finally:
        eng.shutdown()
