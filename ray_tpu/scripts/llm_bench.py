"""Continuous-batching LLM decode throughput on the current device.

Measures the serving engine's aggregate generated-tokens/s with a full
slot pool of concurrent requests — the serving-side counterpart of
``bench.py``'s ``model_train_step`` row.  The reference delegates LLM
serving to vLLM (``python/ray/llm/``); this engine is in-tree
(``ray_tpu/serve/llm.py``), so its number documents the beyond-parity
surface rather than competing with a reference baseline.

Usage: python -m ray_tpu.scripts.llm_bench [out.json]
Prints one JSON line; optionally writes it to the given path.
"""

from __future__ import annotations

import json
import sys
import threading
import time


def serving_config():
    """The serving-class decoder this script (and ``chip_smoke.py``) runs:
    ~284M params (GPT-2-medium scale, tied embeddings), bf16, GQA 16q/8kv —
    shapes that tile the MXU."""
    import jax.numpy as jnp

    from ray_tpu.models import TransformerConfig

    return TransformerConfig(
        vocab_size=32000, d_model=1024, n_layers=16, n_heads=16, n_kv_heads=8,
        d_ff=4096, max_seq_len=1024, attention="dense", dtype=jnp.bfloat16,
    )


def main(out_path: str | None = None) -> dict:
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import TransformerConfig, init_params
    from ray_tpu.ops import backend
    from ray_tpu.serve.llm import LLMEngine

    import os

    backend.use_compile_cache()
    dev = jax.devices()[0]
    tiny = bool(os.environ.get("RAY_TPU_LLM_BENCH_TINY"))
    if not tiny and not backend.on_tpu():
        # a tokens/s taken on the host CPU is not this row's metric
        raise SystemExit(
            f"llm_bench measures the engine on a TPU; jax reports {dev.platform!r}"
        )
    if tiny:
        # in-suite smoke: exercises the same waves/warmup/accounting paths
        cfg = TransformerConfig(
            vocab_size=97, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
            d_ff=64, max_seq_len=128, attention="dense", dtype=jnp.float32,
        )
        B, new_tokens, prompt_len, seq_cap = 2, 4, 3, 128
    else:
        cfg = serving_config()
        B, new_tokens, prompt_len, seq_cap = 8, 128, 64, 1024
    params = init_params(cfg, jax.random.key(0))
    n_params = sum(x.size for x in jax.tree.leaves(params))

    chunk = int(os.environ.get("RAY_TPU_LLM_BENCH_CHUNK", "1"))
    engine = LLMEngine(
        cfg, params, max_batch_size=B, max_seq_len=seq_cap, decode_chunk=chunk
    )
    try:
        vocab_span = cfg.vocab_size - 2
        prompts = [
            [(7 * i + j) % vocab_span + 1 for j in range(prompt_len)] for i in range(B)
        ]

        def run_wave() -> int:
            done = []
            errors = []
            lock = threading.Lock()

            def one(p):
                try:
                    out = engine.generate(p, max_tokens=new_tokens, temperature=0)
                    with lock:
                        done.append(len(out))
                except BaseException as exc:  # noqa: BLE001 — re-raised below
                    with lock:
                        errors.append(exc)

            ts = [threading.Thread(target=one, args=(p,)) for p in prompts]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            if errors:
                # a partial wave would print a silently-wrong throughput
                raise errors[0]
            return sum(done)

        run_wave()  # warmup: traces prefill buckets + decode step
        t0 = time.perf_counter()
        waves = 3
        total = sum(run_wave() for _ in range(waves))
        dt = time.perf_counter() - t0
    finally:
        engine.shutdown()

    result = {
        "metric": "llm_decode_throughput",
        "value": round(total / dt, 1),
        "unit": "tokens/s",
        "extra": {
            "params_millions": round(n_params / 1e6, 1),
            "decode_chunk": chunk,
            "batch_slots": B,
            "new_tokens_per_request": new_tokens,
            "prompt_len": prompt_len,
            "waves": waves,
            "total_tokens": total,
            "wall_s": round(dt, 2),
            "platform": dev.platform,
            "device": dev.device_kind,
        },
    }
    print(json.dumps(result))
    if out_path:
        with open(out_path, "w") as f:
            json.dump(result, f)
    return result


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else None)
