"""The gated delta rule (Gated DeltaNet's recurrence), in three forms.

Per head, with a state ``S`` in ``R^{dk x dv}`` kept in float32, a key ``k``
and query ``q`` of unit norm, a value ``v``, a decay ``alpha`` in (0, 1] and a
writing strength ``beta`` (in (0, 2) when negative eigenvalues are allowed):

    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
    o_t = S_t^T q_t

* :func:`gated_delta_step` — one token, plain ``jax.numpy``: the definition.
* :func:`gated_delta_chunked` — ``T`` tokens from any start state, the WY form
  over chunks of ``chunk`` positions: inside a chunk the ``T`` rank-one writes
  are one triangular system (``(I + tril(diag(beta) K K^T . Gamma, -1))^-1``,
  inverted exactly by halving: it is unit lower triangular), between chunks
  the state is carried by a ``lax.scan``. A ``valid`` mask turns padded
  positions into no-ops (``beta`` 0, ``alpha`` 1), so any length runs.
  With a decay **a key channel** (``g`` ``[.., H, dk]``: Kimi Delta Attention,
  ``S' = Diag(alpha_t) S_{t-1}``) no scalar ``Gamma[i, j]`` comes out of
  ``K K^T``: the products are ``(k_i e^{gc_i}) . (k_j e^{-gc_j})`` a channel,
  and ``e^{-gc_j}`` overflows inside a chunk once a channel forgets fast. The
  chunk is therefore cut into sub-chunks of ``SUB`` positions
  (:func:`_decayed_gram`): a block of rows against the columns before its
  sub-chunk takes both decays relative to the sub-chunk's start, where both
  exponents are <= 0 (two bounded factors, one product); the block on the
  diagonal takes ``e^{gc_i - gc_j}`` a pair and channel, masked before the
  ``exp``. No exponent is ever positive, so the form holds for any ``g <= 0``.
* :func:`gated_delta_decode` — one token a row for a batch of rows whose
  states live in a stacked per-slot array: on the chip a Pallas kernel that,
  a row a grid step, reads the row's state of layer ``l``, applies the update
  and writes it back in place (the array is aliased, the layer and the rows'
  slots ride scalar prefetch) and emits ``o``; it moves the state's bytes
  twice and nothing else of weight. Elsewhere the same through
  :func:`gated_delta_step`.

**Layout of the stacked state.** ``[layers, slots, H / g, dk, g * dv]``:
``g`` heads lie side by side on the minor axis (:func:`lane_group`), head
``h`` at group ``h // g``, lanes ``[(h % g) dv, (h % g + 1) dv)``. A TPU
array's minor axis is tiled by 128 lanes; with one head a row a ``dv`` of 192
pads to 256 and the state to 4/3 of its bytes (chipless compile: 1.13 GB
against 0.85 for 12 layers x 32 slots of 30 x 96 x 192), with two heads a row
384 = 3 x 128 pads nothing. :func:`pack_state` / :func:`unpack_state` convert.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import _use_interpret

CHUNK = 64
SUB = 16  # positions a sub-chunk of the channel-decay form (the published kernels' too)
_HI = jax.lax.Precision.HIGHEST


def lane_group(heads: int, dv: int) -> int:
    """Heads that share a row of the stacked state: the fewest (a divisor of
    ``heads``) whose values fill whole 128-lane tiles; 1 where none does."""
    for g in range(1, heads + 1):
        if heads % g == 0 and (g * dv) % 128 == 0:
            return g
    return 1


def pack_state(S: jax.Array, g: int) -> jax.Array:
    """``[..., H, dk, dv]`` -> ``[..., H / g, dk, g * dv]``."""
    *lead, H, dk, dv = S.shape
    S = S.reshape(*lead, H // g, g, dk, dv)
    return jnp.swapaxes(S, -3, -2).reshape(*lead, H // g, dk, g * dv)


def unpack_state(Sp: jax.Array, g: int) -> jax.Array:
    """``[..., H / g, dk, g * dv]`` -> ``[..., H, dk, dv]``."""
    *lead, Hg, dk, gdv = Sp.shape
    S = Sp.reshape(*lead, Hg, dk, g, gdv // g)
    return jnp.swapaxes(S, -3, -2).reshape(*lead, Hg * g, dk, gdv // g)


def gated_delta_step(S, q, k, v, alpha, beta):
    """One token. S ``[..., H, dk, dv]`` f32; q, k ``[..., H, dk]``; v
    ``[..., H, dv]``; alpha ``[..., H]`` (a decay a head) or ``[..., H, dk]``
    (a decay a key channel: a row of the state is scaled by its own
    channel's); beta ``[..., H]``. Returns (o ``[..., H, dv]``, the state
    after). Products and sums on the vector unit, in float32: a matrix
    product would round its operands on the chip."""
    S, q, k, v = (x.astype(jnp.float32) for x in (S, q, k, v))
    alpha = alpha.astype(jnp.float32)
    Sd = S * (alpha[..., None] if alpha.ndim == k.ndim else alpha[..., None, None])
    u = beta.astype(jnp.float32)[..., None] * (v - (Sd * k[..., None]).sum(-2))
    Sn = Sd + k[..., None] * u[..., None, :]
    return (Sn * q[..., None]).sum(-2), Sn


def unit_lower_inverse(M: jax.Array) -> jax.Array:
    """Inverse of a unit lower triangular ``[..., n, n]`` (``n`` a power of
    two), by halving: ``[[A, 0], [C, D]]^-1 = [[A^-1, 0], [-D^-1 C A^-1,
    D^-1]]``; the two halves of a level are inverted as one batch. Exact
    arithmetic apart from rounding; ``log2 n`` levels of two products."""
    n = M.shape[-1]
    if n == 1:
        return jnp.ones_like(M)
    h = n // 2
    both = unit_lower_inverse(jnp.stack([M[..., :h, :h], M[..., h:, h:]]))
    Ai, Di = both[0], both[1]
    C = -jnp.einsum("...ij,...jk,...kl->...il", Di, M[..., h:, :h], Ai, precision=_HI)
    top = jnp.concatenate([Ai, jnp.zeros_like(Ai)], axis=-1)
    return jnp.concatenate([top, jnp.concatenate([C, Di], axis=-1)], axis=-2)


def gated_delta_chunked(S0, q, k, v, g, beta, valid=None, chunk: int = CHUNK):
    """``T`` tokens. S0 ``[B, H, dk, dv]``; q, k ``[B, T, H, dk]``; v
    ``[B, T, H, dv]``; g (``log alpha``, <= 0) ``[B, T, H]`` or, a decay a key
    channel, ``[B, T, H, dk]``; beta ``[B, T, H]``; valid ``[B, T]`` bool or
    None. Returns (o ``[B, T, H, dv]`` f32, the state after
    the last valid token). Float32 at the highest matmul precision; named
    ``gated_delta_chunked`` in a profile (``jax.named_scope``)."""
    with jax.named_scope("gated_delta_chunked"):
        if g.ndim == q.ndim:
            return _chunked_channel(S0, q, k, v, g, beta, valid, chunk)
        return _chunked(S0, q, k, v, g, beta, valid, chunk)


def _chunked(S0, q, k, v, g, beta, valid, C):
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    if valid is not None:
        g = jnp.where(valid[..., None], g, 0.0)
        beta = jnp.where(valid[..., None], beta, 0.0)
    pad = (-T) % C
    if pad:
        q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)) for x in (q, k, v, g, beta))
    N = (T + pad) // C

    def chunks(x):  # [B, N*C, H, ...] -> [N, B, H, C, ...]
        x = x.reshape(B, N, C, H, *x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
    gc = jnp.cumsum(g, axis=-1)                                   # [N,B,H,C] log decay from the chunk's start
    decay = jnp.exp(gc)
    lower = jnp.tril(jnp.ones((C, C), bool))
    # Gamma[i, j] = prod_{j < m <= i} alpha_m for i >= j; masked before the exp (above the diagonal it overflows)
    gamma = jnp.exp(jnp.where(lower, gc[..., :, None] - gc[..., None, :], -jnp.inf))
    kb, vb = k * beta[..., None], v * beta[..., None]
    kk = jnp.einsum("...ik,...jk->...ij", kb, k, precision=_HI) * gamma
    A = jnp.where(jnp.tril(jnp.ones((C, C), bool), -1), kk, 0.0)
    Tm = unit_lower_inverse(A + jnp.eye(C, dtype=f32))
    u = jnp.einsum("...ij,...jv->...iv", Tm, vb, precision=_HI)                       # [N,B,H,C,dv]
    w = jnp.einsum("...ij,...jk->...ik", Tm, kb * decay[..., None], precision=_HI)   # [N,B,H,C,dk]
    attn = jnp.einsum("...ik,...jk->...ij", q, k, precision=_HI) * gamma          # lower, diagonal included
    qd = q * decay[..., None]
    k_out = k * jnp.exp(gc[..., -1:] - gc)[..., None]             # each key decayed to the chunk's end
    last = decay[..., -1]

    def body(S, xs):
        u_n, w_n, attn_n, qd_n, k_n, last_n = xs
        v_new = u_n - jnp.einsum("...ck,...kv->...cv", w_n, S, precision=_HI)
        o = jnp.einsum("...ck,...kv->...cv", qd_n, S, precision=_HI) + \
            jnp.einsum("...ij,...jv->...iv", attn_n, v_new, precision=_HI)
        S = S * last_n[..., None, None] + jnp.einsum("...ck,...cv->...kv", k_n, v_new, precision=_HI)
        return S, o

    S, o = jax.lax.scan(body, S0.astype(f32), (u, w, attn, qd, k_out, last))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(B, N * C, H, dv)  # [N,B,H,C,dv] -> [B,T,H,dv]
    return o[:, :T], S


def _decayed_gram(x, k, g, gc, sub):
    """``G[i, j] = sum_c x_i[c] k_j[c] exp(gc_i[c] - gc_j[c])`` for ``j <= i``
    (0 above the diagonal) of one chunk: x, k, g, gc ``[..., C, dk]``, ``gc``
    the running sum of ``g`` from the chunk's start. Sub-chunks of ``sub``
    positions: rows of sub-chunk ``a`` against the columns before it as one
    product of ``x_i e^{gc_i - r_a}`` and ``k_j e^{r_a - gc_j}`` (``r_a``: the
    sum up to ``a``'s start; both exponents <= 0), against its own columns
    pair by pair. ``x`` may carry a leading axis of its own (several left
    operands share the exponentials)."""
    *lead, C, dk = k.shape
    n = C // sub
    cut = lambda a: a.reshape(*a.shape[:-2], n, sub, dk)  # noqa: E731
    xs, ks, gcs, gs = cut(x), cut(k), cut(gc), cut(g)
    r = gcs[..., :1, :] - gs[..., :1, :]                          # [..., n, 1, dk]: the sum before a's first position
    left = xs * jnp.exp(gcs - r)
    before = (jnp.arange(C)[None, :] < (jnp.arange(n) * sub)[:, None])[..., None]          # [n, C, 1]: j before a's start
    right = k[..., None, :, :] * jnp.exp(jnp.where(before, r - gc[..., None, :, :], -jnp.inf))  # [..., n, C, dk]
    off = jnp.einsum("...atc,...ajc->...atj", left, right, precision=_HI)            # [..., n, sub, C]
    own = jnp.tril(jnp.ones((sub, sub), bool))[..., None]
    pair = ks[..., None, :, :] * jnp.exp(jnp.where(own, gcs[..., :, None, :] - gcs[..., None, :, :], -jnp.inf))
    diag = jnp.einsum("...atc,...atjc->...atj", xs, pair, precision=_HI)             # [..., n, sub, sub]
    diag = diag[..., None, :] * jnp.eye(n, dtype=diag.dtype)[:, None, :, None]          # [..., a, t, b, j]
    return (off + diag.reshape(*diag.shape[:-2], C)).reshape(*off.shape[:-3], C, C)


def _chunked_channel(S0, q, k, v, g, beta, valid, C, sub=SUB):
    """:func:`_chunked` with ``g`` ``[B, T, H, dk]``: the same WY form, the
    scalar ``Gamma`` replaced by :func:`_decayed_gram`."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    if valid is not None:
        g = jnp.where(valid[..., None, None], g, 0.0)
        beta = jnp.where(valid[..., None], beta, 0.0)
    pad = (-T) % C
    if pad:
        q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)) for x in (q, k, v, g, beta))
    N = (T + pad) // C

    def chunks(x):  # [B, N*C, H, ...] -> [N, B, H, C, ...]
        x = x.reshape(B, N, C, H, *x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
    gc = jnp.cumsum(g, axis=-2)                                   # [N,B,H,C,dk] log decay from the chunk's start
    decay = jnp.exp(gc)
    kb, vb = k * beta[..., None], v * beta[..., None]
    gram = _decayed_gram(jnp.stack([kb, q]), k, g, gc, sub)       # [2,N,B,H,C,C], lower, diagonal included
    A = jnp.where(jnp.tril(jnp.ones((C, C), bool), -1), gram[0], 0.0)
    attn = gram[1]
    Tm = unit_lower_inverse(A + jnp.eye(C, dtype=f32))
    u = jnp.einsum("...ij,...jv->...iv", Tm, vb, precision=_HI)             # [N,B,H,C,dv]
    w = jnp.einsum("...ij,...jk->...ik", Tm, kb * decay, precision=_HI)     # [N,B,H,C,dk]
    qd = q * decay
    k_out = k * jnp.exp(gc[..., -1:, :] - gc)                     # each key decayed to the chunk's end, a channel
    last = decay[..., -1, :]                                      # [N,B,H,dk]

    def body(S, xs):
        u_n, w_n, attn_n, qd_n, k_n, last_n = xs
        v_new = u_n - jnp.einsum("...ck,...kv->...cv", w_n, S, precision=_HI)
        o = jnp.einsum("...ck,...kv->...cv", qd_n, S, precision=_HI) + \
            jnp.einsum("...ij,...jv->...iv", attn_n, v_new, precision=_HI)
        S = S * last_n[..., None] + jnp.einsum("...ck,...cv->...kv", k_n, v_new, precision=_HI)
        return S, o

    S, o = jax.lax.scan(body, S0.astype(f32), (u, w, attn, qd, k_out, last))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(B, N * C, H, dv)
    return o[:, :T], S


# ---------------------------------------------------------------------------
# the decode step over a stacked per-slot state
# ---------------------------------------------------------------------------
def _decode_kernel(l_ref, slots_ref, live_ref, kT_ref, qT_ref, v_ref, a_ref, b_ref, s_ref, o_ref, s_out_ref,
                   *, groups, g, dv, channel=False):
    """One row: every lane group of the row's state. ``kT``/``qT`` ``[dk, H]``
    (a head a lane), ``v``/``a``/``b`` ``[groups, g * dv]`` (a head's scalar
    repeated over its ``dv`` lanes). A key's column, broadcast over the lanes
    of its head, stands in for the head axis: no lane is ever sliced off the
    state. ``channel``: ``a`` is ``[dk, H]`` like the key, a decay a key
    channel, and reaches a row of the state as the key's column does."""
    del l_ref, slots_ref
    live = live_ref[pl.program_id(0)] > 0
    dk = s_ref.shape[-2]
    lane = jax.lax.broadcasted_iota(jnp.int32, (dk, g * dv), 1)
    kT, qT = kT_ref[0], qT_ref[0]

    def over_lanes(xT, p):
        out = xT[:, p * g : p * g + 1]
        for r in range(1, g):
            out = jnp.where(lane < r * dv, out, xT[:, p * g + r : p * g + r + 1])
        return out

    for p in range(groups):
        S = s_ref[0, 0, p]
        kx, qx = over_lanes(kT, p), over_lanes(qT, p)
        Sd = S * (over_lanes(a_ref[0], p) if channel else a_ref[0, p : p + 1, :])
        u = b_ref[0, p : p + 1, :] * (v_ref[0, p : p + 1, :] - jnp.sum(Sd * kx, axis=0, keepdims=True))
        Sn = Sd + kx * u
        o_ref[0, p : p + 1, :] = jnp.sum(Sn * qx, axis=0, keepdims=True)
        s_out_ref[0, 0, p] = jnp.where(live, Sn, S)


def gated_delta_decode(state, layer, slots, live, q, k, v, alpha, beta, *, kernel: bool):
    """One token a row. state ``[L, slots, H / g, dk, g * dv]`` f32, updated
    at ``[layer, slots[r]]`` for the rows ``live`` marks and left as it is for
    the others; ``layer`` a traced scalar; slots ``[B]`` int32 (distinct);
    live ``[B]`` bool; q, k ``[B, H, dk]``; v ``[B, H, dv]``; alpha ``[B, H]``
    or ``[B, H, dk]`` (a decay a key channel); beta ``[B, H]``. Returns (o ``[B, H, dv]`` f32, state). ``kernel``: the Pallas
    kernel (the caller asks ``ops.backend.on_tpu()`` once, as for the paged
    attention kernels); else :func:`gated_delta_step` on the gathered rows."""
    B, H, dk = q.shape
    dv = v.shape[-1]
    Hg, gdv = state.shape[2], state.shape[4]
    g = H // Hg
    f32 = jnp.float32
    if not kernel:
        S = unpack_state(state[layer, slots], g)
        o, Sn = gated_delta_step(S, q, k, v, alpha, beta)
        Sn = jnp.where(live[:, None, None, None], Sn, S)
        return o, state.at[layer, slots].set(pack_state(Sn, g))

    def lanes(x):  # [B, H] -> [B, Hg, g * dv]: a head's scalar on each of its lanes
        return jnp.repeat(x.astype(f32), dv, axis=-1).reshape(B, Hg, gdv)

    kT, qT = (jnp.swapaxes(x.astype(f32), 1, 2) for x in (k, q))  # [B, dk, H]
    channel = alpha.ndim == 3
    rows = lambda r, *_: (r, 0, 0)  # noqa: E731
    at = lambda r, l, slots, live: (l[0], slots[r], 0, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(B,),
        in_specs=[pl.BlockSpec((1, dk, H), rows), pl.BlockSpec((1, dk, H), rows),
                  pl.BlockSpec((1, Hg, gdv), rows),
                  pl.BlockSpec((1, dk, H) if channel else (1, Hg, gdv), rows), pl.BlockSpec((1, Hg, gdv), rows),
                  pl.BlockSpec((1, 1, Hg, dk, gdv), at)],
        out_specs=[pl.BlockSpec((1, Hg, gdv), rows), pl.BlockSpec((1, 1, Hg, dk, gdv), at)],
    )
    o, state = pl.pallas_call(
        functools.partial(_decode_kernel, groups=Hg, g=g, dv=dv, **({"channel": True} if channel else {})),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, Hg, gdv), f32), jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={8: 1},  # the state, counted with the three prefetched scalars
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=48 * 2**20),
        interpret=_use_interpret(),
        name="gated_delta_decode",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), slots.astype(jnp.int32), live.astype(jnp.int32),
      kT, qT, v.astype(f32).reshape(B, Hg, gdv), jnp.swapaxes(alpha.astype(f32), 1, 2) if channel else lanes(alpha),
      lanes(beta), state)
    return o.reshape(B, H, dv), state
