"""Shared model-layer helpers."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def dense_init(key, shape, fan_in, dtype=jnp.float32):
    """1/sqrt(fan_in) normal init — the shared recipe of every model here."""
    return (jax.random.normal(key, shape, dtype) / math.sqrt(fan_in)).astype(dtype)


def patchify(images: jax.Array, patch_size: int) -> jax.Array:
    """[B, H, W, C] -> [B, (H/p)*(W/p), p*p*C] (ViT/DiT patch embedding)."""
    B, H, W, C = images.shape
    p = patch_size
    x = images.reshape(B, H // p, p, W // p, p, C)
    return jnp.transpose(x, (0, 1, 3, 2, 4, 5)).reshape(B, (H // p) * (W // p), p * p * C)


def unpatchify(patches: jax.Array, image_size: int, patch_size: int, channels: int) -> jax.Array:
    """[B, N, p*p*C] -> [B, H, W, C] — inverse of :func:`patchify`."""
    B = patches.shape[0]
    p = patch_size
    g = image_size // p
    x = patches.reshape(B, g, g, p, p, channels)
    return jnp.transpose(x, (0, 1, 3, 2, 4, 5)).reshape(B, image_size, image_size, channels)


class JittedStep:
    """Callable train step carrying its batch-placement helper (jit wrappers
    don't accept attribute assignment). Shared by the decoder and ViT train
    steps so sharding/donation fixes land in one place.

    ``ring_layout``: how the step's newest trace placed the sequence for
    ``attention="ring"`` (``"zigzag"`` or ``"contiguous"``,
    ``parallel/ring.py``); None before a trace and without a ring."""

    def __init__(self, fn, shard_batch):
        self._fn = fn
        self.shard_batch = shard_batch
        self.ring_layout = None

    def __call__(self, *args):
        return self._fn(*args)

    def lower(self, *args):
        """The wrapped jit's ``lower`` — so a sharded step can be inspected
        and AOT-compiled the same way as the mesh-less ``jax.jit`` one."""
        return self._fn.lower(*args)
