"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

New engineering for the TPU rebuild (SURVEY §5.7: the reference has no
sequence-parallel support — ``ray.util.collective`` stops at tensor
collectives).  Two strategies over a mesh axis holding sequence shards:

* **Ring attention** (Liu et al.): K/V blocks rotate around the ICI ring via
  ``ppermute`` while each device accumulates blockwise attention with the
  online-softmax (log-sum-exp) recurrence, so peak memory stays
  O(T_local^2-free) and the sequence scales with the ring size.
* **Ulysses**: ``all_to_all`` swaps the sharding between sequence and heads,
  runs dense per-head attention locally, and swaps back — cheaper when
  head_count >= ring size and sequence blocks are small.

Both are pure SPMD functions for use inside ``shard_map``; the ``*_sharded``
wrappers bind them to a mesh.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _to_varying(x, axis_name: str):
    """Mark an array as device-varying over the axis (shard_map vma typing)."""
    return lax.pcast(x, (axis_name,), to="varying")


def ring_attention(
    q, k, v, axis_name: str, *, causal: bool = True, sm_scale: Optional[float] = None,
    block_q: Optional[int] = None, block_k: Optional[int] = None,
):
    """Blockwise ring attention over sequence shards (call inside shard_map).

    q, k, v: [B, H, T_local, D] — the local sequence shard.
    Returns [B, H, T_local, D] in q.dtype.

    Each ring step runs the Pallas flash kernel on the local Q against the
    currently-held K/V shard (``flash_attention_with_lse``) and merges the
    normalized partial outputs with lse-softmax weights — so per-step
    compute rides the MXU kernel and per-device memory stays linear in the
    shard length. For a causal mask the shard either attends fully
    (earlier shard), causally (the diagonal shard), or not at all (later
    shard) — picked per step with ``lax.switch``.
    """
    n = lax.axis_size(axis_name)
    my_block = lax.axis_index(axis_name)
    B, H, Tq, D = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)

    from ray_tpu.ops.attention import flash_attention_with_lse

    o0 = jnp.zeros((B, H, Tq, D), jnp.float32)   # unnormalized accumulator
    m0 = jnp.full((B, H, Tq), NEG_INF, jnp.float32)  # running max of lse_i
    w0 = jnp.zeros((B, H, Tq), jnp.float32)      # sum of exp(lse_i - m)
    o0, m0, w0 = (_to_varying(x, axis_name) for x in (o0, m0, w0))

    def local_full(k_cur, v_cur):
        out, lse = flash_attention_with_lse(q, k_cur, v_cur, scale, False, block_q, block_k)
        return out.astype(jnp.float32), lse

    def local_diag(k_cur, v_cur):
        out, lse = flash_attention_with_lse(q, k_cur, v_cur, scale, True, block_q, block_k)
        return out.astype(jnp.float32), lse

    def local_empty(k_cur, v_cur):
        return jnp.zeros((B, H, Tq, D), jnp.float32), jnp.full((B, H, Tq), NEG_INF, jnp.float32)

    def body(step, carry):
        k_cur, v_cur, o_acc, m_run, w_sum = carry
        src_block = (my_block - step) % n  # sequence block k_cur holds now
        if causal:
            # 0: src < my (full), 1: src == my (diagonal), 2: src > my (skip)
            idx = jnp.where(src_block == my_block, 1, jnp.where(src_block < my_block, 0, 2))
            o_i, lse_i = lax.switch(idx, (local_full, local_diag, local_empty), k_cur, v_cur)
        else:
            o_i, lse_i = local_full(k_cur, v_cur)
        # accumulate UNNORMALIZED against the running max: one divide after
        # the loop replaces a full-tensor renormalize per step
        m_new = jnp.maximum(m_run, lse_i)
        alpha = jnp.exp(m_run - m_new)
        beta = jnp.exp(lse_i - m_new)
        o_acc = o_acc * alpha[..., None] + o_i * beta[..., None]
        w_sum = w_sum * alpha + beta
        # rotate K/V to the next rank on the ICI ring
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return k_nxt, v_nxt, o_acc, m_new, w_sum

    _, _, o, _m, w = lax.fori_loop(0, n, body, (k, v, o0, m0, w0))
    w_safe = jnp.where(w == 0, 1.0, w)
    return (o / w_safe[..., None]).astype(q.dtype)


def ring_attention_sharded(
    q, k, v, mesh: Mesh, axis_name: str = "sp", *, causal: bool = True,
    sm_scale: Optional[float] = None, block_q: Optional[int] = None, block_k: Optional[int] = None,
    batch_axis: Optional[str] = None, head_axis: Optional[str] = None,
):
    """Bind ring attention onto a mesh: [B, H, T, D] arrays sharded on T.

    ``batch_axis``/``head_axis`` shard B and H through the shard_map too —
    without them a dp/tp-sharded caller pays an all-gather into the
    shard_map and redundant per-replica attention compute."""
    spec = P(batch_axis, head_axis, axis_name, None)
    fn = functools.partial(
        ring_attention, axis_name=axis_name, causal=causal, sm_scale=sm_scale,
        block_q=block_q, block_k=block_k,
    )
    # check_vma=False: pallas_call out_shapes carry no vma annotation, and
    # the kernel outputs are trivially device-varying over the shard axis
    return shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False
    )(q, k, v)


# --------------------------------------------------------------------------
# Ulysses-style all-to-all sequence parallelism
# --------------------------------------------------------------------------
def ulysses_attention(q, k, v, axis_name: str, *, causal: bool = True, sm_scale: Optional[float] = None):
    """Head/sequence all-to-all attention (call inside shard_map).

    q, k, v: [B, H, T_local, D] with H divisible by the axis size.  Swaps to
    [B, H_local, T_full, D], runs dense attention, swaps back.
    """
    def swap_to_heads(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    def swap_to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    from ray_tpu.ops.attention import flash_attention

    qh, kh, vh = swap_to_heads(q), swap_to_heads(k), swap_to_heads(v)
    out = flash_attention(qh, kh, vh, sm_scale, causal)
    return swap_to_seq(out)


def ulysses_attention_sharded(q, k, v, mesh: Mesh, axis_name: str = "sp", *, causal: bool = True, sm_scale=None):
    spec = P(None, None, axis_name, None)
    fn = functools.partial(ulysses_attention, axis_name=axis_name, causal=causal, sm_scale=sm_scale)
    return shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False
    )(q, k, v)
