"""Weight quantization ops: int8 storage, on-the-fly dequant matmul.

No reference counterpart (the reference delegates quantization to user
frameworks); on TPU this is a first-class serving op. Decode-time matmuls
are HBM-bandwidth-bound on the WEIGHTS (batch is small, weights are not),
so storing them int8 halves the bytes per token versus bf16 — the dequant
multiply is free next to the DMA.

- :func:`quantize_int8` — symmetric per-channel absmax quantization.
- :func:`int8_matmul` — Pallas kernel streaming int8 weight tiles through
  VMEM, dequantizing in-register against the f32 accumulator (W8A16:
  activations stay wide; int8 activations would need per-row dynamic
  scales, a later optimization).
- :func:`quantize_tree` / :func:`dequantize_tree` — pytree helpers for
  whole-model weight sets.

Non-TPU backends run the kernel in interpret mode.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import _use_interpret


def quantize_int8(w: jax.Array, axis: int = 0) -> Tuple[jax.Array, jax.Array]:
    """Symmetric absmax int8 quantization, per channel along every axis
    EXCEPT ``axis`` (the contraction axis that gets summed in a matmul).

    Returns (w_q int8 same shape, scales f32 with ``axis`` reduced to 1);
    ``w ~= w_q * scales``."""
    absmax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=axis, keepdims=True)
    scales = jnp.where(absmax == 0, 1.0, absmax / 127.0)
    w_q = jnp.clip(jnp.round(w.astype(jnp.float32) / scales), -127, 127).astype(jnp.int8)
    return w_q, scales


def dequantize_int8(w_q: jax.Array, scales: jax.Array, dtype=jnp.float32) -> jax.Array:
    return (w_q.astype(jnp.float32) * scales).astype(dtype)


def _int8_matmul_kernel(x_ref, w_ref, s_ref, o_ref, acc_scr, *, n_k: int):
    """Grid (M_blocks, N_blocks, K_blocks), K innermost.

    x_ref: [bm, bk] (f32/bf16); w_ref: [bk, bn] int8; s_ref: [1, bn] f32;
    o_ref: [bm, bn]; acc [bm, bn] f32 scratch."""
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)  # int8 -> f32 in-register
    acc_scr[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(ki == n_k - 1)
    def _final():
        o_ref[...] = (acc_scr[...] * s_ref[0, :][None, :]).astype(o_ref.dtype)


def _pad_dim(a, axis, mult):
    pad = (-a.shape[axis]) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


def int8_matmul(
    x: jax.Array,        # [M, K] f32/bf16 activations
    w_q: jax.Array,      # [K, N] int8 weights
    scales: jax.Array,   # [1, N] or [N] f32 per-output-channel scales
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    out_dtype=None,
) -> jax.Array:
    """x @ (w_q * scales) with the weights kept int8 in HBM."""
    M, K = x.shape
    K2, N = w_q.shape
    assert K == K2, (x.shape, w_q.shape)
    scales = scales.reshape(1, N).astype(jnp.float32)
    out_dtype = out_dtype or x.dtype

    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    xp = _pad_dim(_pad_dim(x, 0, bm), 1, bk)
    wp = _pad_dim(_pad_dim(w_q, 0, bk), 1, bn)
    sp = _pad_dim(scales, 1, bn)
    Mp, Kp = xp.shape
    Np = wp.shape[1]
    n_k = Kp // bk

    out = pl.pallas_call(
        functools.partial(_int8_matmul_kernel, n_k=n_k),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        grid=(Mp // bm, Np // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=_use_interpret(),
    )(xp, wp, sp)
    return out[:M, :N]


# ---------------------------------------------------------------------------
# pytree helpers
# ---------------------------------------------------------------------------
class _NoScale:
    """Sentinel leaf marking an unquantized entry in the scales tree (None
    would be pruned as an empty subtree by jax.tree)."""

    _instance = None

    def __new__(cls):
        if cls._instance is None:
            cls._instance = super().__new__(cls)
        return cls._instance

    def __repr__(self):
        return "NO_SCALE"


NO_SCALE = _NoScale()


def quantize_tree(
    params: Any,
    *,
    min_size: int = 4096,
    contract_axis: int = 0,
) -> Tuple[Any, Any]:
    """Quantize every float leaf with >= min_size elements and ndim >= 2.

    Returns (tree with int8 leaves where quantized, scales tree with f32
    scale leaves there and NO_SCALE sentinels elsewhere)."""

    class _QP:
        """Opaque (weight, scale) pair — deliberately NOT a tuple, so a
        structural 2-tuple inside the user's pytree can never be mistaken
        for a quantization pair."""

        __slots__ = ("w", "s")

        def __init__(self, w, s):
            self.w, self.s = w, s

    def q(leaf):
        if (
            hasattr(leaf, "ndim")
            and leaf.ndim >= 2
            and leaf.size >= min_size
            and jnp.issubdtype(leaf.dtype, jnp.floating)
        ):
            axis = contract_axis if contract_axis < leaf.ndim else 0
            return _QP(*quantize_int8(leaf, axis=axis))
        return _QP(leaf, NO_SCALE)

    pairs = jax.tree.map(q, params)
    is_pair = lambda p: isinstance(p, _QP)  # noqa: E731
    wq = jax.tree.map(lambda p: p.w, pairs, is_leaf=is_pair)
    sc = jax.tree.map(lambda p: p.s, pairs, is_leaf=is_pair)
    return wq, sc


def dequantize_tree(wq: Any, scales: Any, dtype=jnp.float32) -> Any:
    def dq(w, s):
        if s is NO_SCALE:
            return w
        return dequantize_int8(w, s, dtype)

    return jax.tree.map(dq, wq, scales)


# keys of the transformer's stacked-layer LINEAR weights (ray_tpu.models.
# transformer.init_params layout) — the bandwidth bulk worth quantizing;
# norm gains stay exact and the embedding keeps output quality
TRANSFORMER_LINEAR_KEYS = frozenset(
    {"wq", "wk", "wv", "wo", "w1", "w2", "w3", "we1", "we2", "we3", "router"}
)


def quantize_layers(
    layers: Dict[str, jax.Array],
    *,
    keys=TRANSFORMER_LINEAR_KEYS,
    min_size: int = 4096,
) -> Tuple[Dict[str, jax.Array], Dict[str, jax.Array]]:
    """Quantize a stacked-layer dict ([L, ...] leaves) for in-scan dequant.

    Returns (layers with int8 leaves where quantized, DENSE scales dict —
    broadcast-ones where unquantized — shaped to ride a lax.scan as xs:
    every scale has leading dim L). Quantization axis is 1 (the first
    per-layer axis); scales varying along a contraction axis are fine
    because the consumer dequantizes elementwise before its matmul."""
    q, sc = {}, {}
    for k, w in layers.items():
        if k in keys and w.size >= min_size and jnp.issubdtype(w.dtype, jnp.floating):
            q[k], sc[k] = quantize_int8(w, axis=1)
        else:
            q[k] = w
            sc[k] = jnp.ones((w.shape[0],) + (1,) * (w.ndim - 1), jnp.float32)
    return q, sc
