"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

New engineering for the TPU rebuild (SURVEY §5.7: the reference has no
sequence-parallel support — ``ray.util.collective`` stops at tensor
collectives).  Two strategies over a mesh axis holding sequence shards:

* **Ring attention** (Liu et al.): K/V blocks rotate around the ICI ring via
  ``ppermute`` while each device accumulates blockwise attention with the
  online-softmax (log-sum-exp) recurrence, so peak memory stays
  O(T_local^2-free) and the sequence scales with the ring size.  Under the
  causal mask the sequence is placed **zigzag** (:func:`ring_order`): cut into
  ``2n`` pieces, rank ``i`` holds the early piece ``c_i`` and the late piece
  ``c_{2n-1-i}``.  On contiguous shards the last rank attends to ``n`` blocks
  and the first to one, and every ring step ends with the early ranks
  waiting in a ``collective-permute`` for the late ones; zigzag gives every
  rank the same tiles at every step.  The caller places the sequence (once,
  on token ids: ``models/transformer.py``); the ring's block masks follow.
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
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _to_varying(x, axis_name: str):
    """Mark an array as device-varying over the axis (shard_map vma typing)."""
    return lax.pcast(x, (axis_name,), to="varying")


def ring_layout(T: int, n: int, causal: bool) -> str:
    """The placement the ring expects of a ``T``-long sequence over ``n``
    ranks: ``"zigzag"`` (:func:`ring_order`) when the mask is causal and the
    sequence cuts into ``2n`` equal pieces, else ``"contiguous"`` (rank ``i``
    holds block ``i``; a ring without a mask is balanced as it lies).
    :func:`ring_attention` reads the same rule off its shard: a causal ring
    over shards of even length is zigzag."""
    return "zigzag" if causal and T % (2 * n) == 0 else "contiguous"


def ring_order(T: int, n: int) -> np.ndarray:
    """The zigzag placement as a permutation: ``ring_order(T, n)[p]`` is the
    sequence position held at index ``p`` of the placed sequence, whose
    contiguous ``n``-way split gives rank ``i`` the pieces ``(c_i, c_{2n-1-i})``
    of ``2n``. Place with ``x[order]``, restore with ``y[np.argsort(order)]``."""
    pieces = np.arange(T).reshape(2 * n, T // (2 * n))
    return np.concatenate([pieces[[i, 2 * n - 1 - i]].reshape(-1) for i in range(n)])


def ring_attention(
    q, k, v, axis_name: str, *, causal: bool = True, sm_scale: Optional[float] = None,
    block_q: Optional[int] = None, block_k: Optional[int] = None,
):
    """Blockwise ring attention over sequence shards (call inside shard_map).

    q, k, v: [B, H, T_local, D] — the local shard of a sequence placed as
    :func:`ring_layout` says (causal and ``T_local`` even: zigzag).
    Returns [B, H, T_local, D] in q.dtype, placed like ``q``.

    Each ring step runs the Pallas flash kernel on the local Q against the
    currently-held K/V shard (``flash_attention_with_lse``) and merges the
    normalized partial outputs with lse-softmax weights — so per-step
    compute rides the MXU kernel and per-device memory stays linear in the
    shard length. K/V make ``n - 1`` hops, each issued before the products
    of the step that does not need it. The first step is the rank's own
    shard under the causal mask. After it the K/V held come from an earlier
    or a later rank, picked per step with ``lax.switch``: zigzag, all of Q
    against their early half or the late half of Q against all of them (the
    same tiles either way); contiguous, all of them or nothing.
    """
    n = lax.axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    B, H, Tq, D = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    zigzag = ring_layout(n * Tq, n, causal) == "zigzag"
    half = Tq // 2

    from ray_tpu.ops.attention import flash_attention_with_lse

    def flash(q, k, v, masked=False):
        out, lse = flash_attention_with_lse(q, k, v, scale, masked, block_q, block_k)
        return out.astype(jnp.float32), lse

    def unseen(rows):  # what queries that see none of these keys add to the merge
        return jnp.zeros((B, H, rows, D), jnp.float32), jnp.full((B, H, rows), NEG_INF, jnp.float32)

    def from_earlier(k_cur, v_cur):
        if zigzag:  # their early piece lies before both of ours, their late piece after both
            return flash(q, k_cur[:, :, :half], v_cur[:, :, :half])
        return flash(q, k_cur, v_cur)

    def from_later(k_cur, v_cur):
        if zigzag:  # both their pieces lie between ours: the late piece of q sees them whole
            seen = flash(q[:, :, half:], k_cur, v_cur)
            return tuple(jnp.concatenate(pair, axis=2) for pair in zip(unseen(half), seen))
        return unseen(Tq)

    perm = [(i, (i + 1) % n) for i in range(n)]
    held = (k, v)
    for step in range(n):
        # the hop goes out before the products that do not need it; none after the last
        arriving = tuple(lax.ppermute(x, axis_name, perm) for x in held) if step < n - 1 else None
        if step == 0:
            # unnormalized accumulator, running max of the lse, sum of exp(lse - max):
            # one divide after the loop replaces a full-tensor renormalize per step
            o_acc, m_run = flash(q, *held, masked=causal)
            w_sum = jnp.ones_like(m_run)
        else:
            if causal:  # K/V of rank (rank - step) % n: an earlier rank iff rank >= step
                o_i, lse_i = lax.switch((rank < step).astype(jnp.int32), (from_earlier, from_later), *held)
            else:
                o_i, lse_i = flash(q, *held)
            m_new = jnp.maximum(m_run, lse_i)
            alpha = jnp.exp(m_run - m_new)
            beta = jnp.exp(lse_i - m_new)
            o_acc = o_acc * alpha[..., None] + o_i * beta[..., None]
            w_sum = w_sum * alpha + beta
            m_run = m_new
        held = arriving
    return (o_acc / w_sum[..., None]).astype(q.dtype)


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
