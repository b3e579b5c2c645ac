"""The plain reference of the LLM engine's identity tests: the one-shot
``models.generation.generate()`` (dense rows, no engine, no pages)."""

import jax.numpy as jnp
import numpy as np

from ray_tpu.models import generate


def greedy_reference(cfg, params, prompt, n):
    """Greedy continuation of ``prompt`` by ``n`` tokens, as a token list."""
    p = jnp.asarray([prompt], jnp.int32)
    out, lens = generate(cfg, params, p, max_new_tokens=n, temperature=0)
    return np.asarray(out[0, len(prompt): int(lens[0])]).tolist()


def late_eos_case(cfg, params, lo=2, hi=10):
    """``(prompt, continuation, j)``: a prompt whose greedy continuation
    first shows some token at index ``lo <= j < hi``. As ``eos_id`` that
    token fires inside decode (index 0 is the prefill's own sample), so the
    engine reads it with the next step already dispatched."""
    for seed in range(1, 80):
        prompt = [seed, (seed * 7) % 88 + 1, (seed * 3) % 88 + 1]
        out = greedy_reference(cfg, params, prompt, hi + 2)
        for j in range(lo, hi):
            if out[j] not in out[:j]:
                return prompt, out, j
    raise AssertionError("no prompt whose greedy continuation changes token late")
