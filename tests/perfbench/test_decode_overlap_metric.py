"""``decode_overlap_share`` (PR 32): its manifest entry, its reader on
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

METRIC = "decode_overlap_share"
SERVE_CELLS = ["smollm2-1.7b-serve.chat-steady", "smollm2-1.7b-serve.agent-prefix",
               "smollm2-1.7b-serve.chat-saturated", "trinity-mini-serve-l5.mixed-lengths"]


def test_the_metric_is_the_schedulers_and_lists_the_four_serve_cells():
    m = manifest.load()
    assert manifest.problems(m, ROOT) == []
    entry = [x for x in m["per_layer"] if x["name"] == METRIC]
    assert entry == [{"name": METRIC, "unit": "share", "better": "higher", "source": "program_counter",
                      "layer": "admission and scheduler", "moves": "itl_mean_ms", "workloads": SERVE_CELLS}]
    # the layer is one the manifest already names, letter for letter
    assert sum(x["layer"] == "admission and scheduler" for x in m["per_layer"]) >= 2
    for cell in SERVE_CELLS:
        assert "itl_mean_ms" in {x["name"] for x in manifest.metrics_of(m, "end_to_end", cell)}
        assert METRIC in {x["name"] for x in manifest.metrics_of(m, "per_layer", cell)}
    for cell in ("smollm2-1.7b-train-l8.steps", "smollm2-1.7b-train-ring4.steps"):
        assert METRIC not in {x["name"] for x in manifest.metrics_of(m, "per_layer", cell)}


def _run(opened, closed):
    probe = types.SimpleNamespace(stats_open=opened and (0.0, opened), stats_close=closed and (1.0, closed))
    return {"probe": probe}


@pytest.mark.parametrize("opened,closed,want", [
    ({"decode_steps": 100, "decode_steps_overlapped": 90}, {"decode_steps": 300, "decode_steps_overlapped": 280}, 0.95),
    ({"decode_steps": 0, "decode_steps_overlapped": 0}, {"decode_steps": 8, "decode_steps_overlapped": 0}, 0.0),
    # the parent: steps are counted, nothing says how they were dispatched
    ({"decode_steps": 100}, {"decode_steps": 300}, None),
    # nothing decoded inside the window; no counters read at its edges
    ({"decode_steps": 7, "decode_steps_overlapped": 5}, {"decode_steps": 7, "decode_steps_overlapped": 5}, None),
    (None, {"decode_steps": 300, "decode_steps_overlapped": 280}, None),
    ({"decode_steps": 100, "decode_steps_overlapped": 90}, None, None),
], ids=["ratio", "none_overlapped", "parent", "no_steps", "no_open", "no_close"])
def test_the_reader_divides_the_windows_overlapped_steps_by_its_steps(opened, closed, want):
    read = runner.load_reader(METRIC, manifest.load()["paths"])
    got = read(_run(opened, closed))
    assert got is None if want is None else got == pytest.approx(want)


def test_the_engine_counts_what_the_reader_divides():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig, init_params
    from ray_tpu.serve.llm import LLMEngine

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64, dtype=jnp.float32, max_seq_len=64)
    eng = LLMEngine(cfg, init_params(cfg, jax.random.key(0)), max_batch_size=2, max_seq_len=64, kv_block_size=16)
    try:
        before = eng.stats()
        assert (before["decode_steps"], before["decode_steps_overlapped"], before["decode_row_steps_discarded"]) == (0, 0, 0)
        assert len(eng.generate([3, 1, 4], max_tokens=30)) == 30
        after = eng.stats()
        # 29 decode steps for one row: all but the first dispatched with the step before unread
        assert after["decode_steps"] == 29 and after["decode_steps_overlapped"] == 28
        read = runner.load_reader(METRIC, manifest.load()["paths"])
        assert read(_run(before, after)) == pytest.approx(28 / 29)
    finally:
        eng.shutdown()
