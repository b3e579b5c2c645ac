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
