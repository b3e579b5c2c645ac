"""Decoder-only transformer (GPT/Llama-style), TPU-first.

Design notes (not a port — the reference has no model core; RLlib's torch
nets are the closest analog, ``rllib/core/rl_module/rl_module.py``):

- Pure-pytree params + functional ``forward`` so the whole train step jits
  to ONE XLA program; sharding is declared with ``PartitionSpec`` and GSPMD
  propagates collectives (psum over ``tp``, all-gather over ``sp`` for KV).
- bfloat16 activations, float32 params/optimizer — the MXU-native recipe.
- RMSNorm + RoPE + SwiGLU; optional top-2 MoE FFN whose expert dimension
  shards over the ``ep`` mesh axis (expert parallelism).
- Attention: Pallas flash kernel (``ray_tpu.ops.attention``) on a single
  chip (no mesh); XLA einsum attention under any mesh; or
  ``attention="ring"`` — sequence-parallel ring attention
  (``ray_tpu.parallel.ring``: ppermute K/V rotation + per-step flash
  kernel) sharded over (dp, tp, sp), the long-context mode.

Mesh axes: ``dp`` (batch), ``sp`` (sequence), ``tp`` (hidden/heads),
``ep`` (experts; may be folded into ``dp`` on small meshes).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.ops import backend
from ray_tpu.ops.attention import NEG_INF, flash_attention, mha


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: Optional[int] = None  # None => MHA; < n_heads => GQA (Llama-2/3 style)
    d_ff: int = 2048
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    num_experts: int = 0          # 0 => dense FFN
    expert_top_k: int = 2
    # 0 => dense dispatch (every expert computes every token — exact, the
    # small-scale default); > 0 => GShard/Switch capacity dispatch: expert
    # slots = ceil(top_k * T * factor / E), FLOPs per token drop from E
    # expert-FFNs to top_k, overflow tokens fall through the residual
    moe_capacity_factor: float = 0.0
    dtype: Any = jnp.bfloat16     # activation dtype
    param_dtype: Any = jnp.float32
    attention: str = "auto"       # auto | flash | dense | ring (sp-sharded)
    # Rematerialization per layer: False => save everything; True/"full" =>
    # jax.checkpoint (recompute the whole layer in bwd — ~33% extra fwd
    # FLOPs); "dots" => checkpoint with the dots_saveable policy: matmul
    # outputs are SAVED, only cheap elementwise work recomputes — near-full
    # memory savings at ~zero FLOP overhead (the right default on TPU,
    # where the MXU is the scarce resource).
    remat: Any = False
    # lax.scan over layers (one traced layer, fast compile) vs an unrolled
    # Python loop (bigger HLO, but remat saves stay plain buffers instead
    # of scan-stacked dynamic-update-slices — worth ~25% step time at 602M)
    scan_layers: bool = True

    def __post_init__(self):
        if self.d_model % self.n_heads:
            raise ValueError(f"d_model {self.d_model} not divisible by n_heads {self.n_heads}")
        if self.remat not in (False, True, "full", "dots"):
            # a typo like "Dots" would silently select full-layer recompute
            raise ValueError(f'remat must be False, True, "full", or "dots"; got {self.remat!r}')
        kv = self.n_kv_heads
        if kv is not None and (kv < 1 or kv > self.n_heads or self.n_heads % kv):
            raise ValueError(
                f"n_kv_heads {kv} must be a positive divisor of n_heads {self.n_heads}"
            )

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def init_params(cfg: TransformerConfig, key: jax.Array) -> Dict[str, Any]:
    # third split kept (not dropped) so existing seeds reproduce their init
    k_embed, k_layers, _k_unused = jax.random.split(key, 3)
    pd = cfg.param_dtype
    d, h, hkv, dh, ff = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.d_ff

    from ray_tpu.models.common import dense_init as _dinit

    def dense_init(k, shape, fan_in):
        return _dinit(k, shape, fan_in, pd)

    layer_keys = jax.random.split(k_layers, cfg.n_layers)

    def one_layer(k):
        ks = jax.random.split(k, 8)
        layer = {
            "attn_norm": jnp.ones((d,), pd),
            "wq": dense_init(ks[0], (d, h, dh), d),
            "wk": dense_init(ks[1], (d, hkv, dh), d),
            "wv": dense_init(ks[2], (d, hkv, dh), d),
            "wo": dense_init(ks[3], (h, dh, d), d),
            "ffn_norm": jnp.ones((d,), pd),
        }
        if cfg.num_experts > 0:
            e = cfg.num_experts
            layer["router"] = dense_init(ks[7], (d, e), d)
            layer["we1"] = dense_init(ks[4], (e, d, ff), d)
            layer["we3"] = dense_init(ks[5], (e, d, ff), d)
            layer["we2"] = dense_init(ks[6], (e, ff, d), ff)
        else:
            layer["w1"] = dense_init(ks[4], (d, ff), d)
            layer["w3"] = dense_init(ks[5], (d, ff), d)
            layer["w2"] = dense_init(ks[6], (ff, d), ff)
        return layer

    # stacked layers: leaves get a leading [n_layers] dim, scanned in forward.
    layers = jax.tree.map(lambda *xs: jnp.stack(xs), *[one_layer(k) for k in layer_keys])
    return {
        # tied embedding/unembed: init at 1/sqrt(d) std (unembed wants unit
        # row norms so init logits are O(1) — std-1 rows made the model a
        # confident token-COPIER at init: diag logit ~= |E_t|^2 ~= d); the
        # input path multiplies by sqrt(d) in forward() to keep the residual
        # stream at its usual scale (Gemma-style tied-embedding recipe)
        "embed": dense_init(k_embed, (cfg.vocab_size, d), d),
        "layers": layers,
        "final_norm": jnp.ones((d,), pd),
    }


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------
def param_specs(
    cfg: TransformerConfig,
    *,
    dp: str = "dp",
    tp: str = "tp",
    ep: Optional[str] = None,
    kv_tp: bool = True,
) -> Dict[str, Any]:
    """Megatron-style TP layout as PartitionSpecs (leading axis of stacked
    layer leaves is the layer dim, unsharded).

    ``kv_tp=False`` replicates wk/wv across tp — required under GQA when
    ``kv_heads`` isn't divisible by the tp axis size (callers with a mesh,
    e.g. :func:`make_train_step`, decide automatically)."""
    ep = ep or dp
    kv = tp if kv_tp else None
    layer_specs = {
        "attn_norm": P(None, None),
        "wq": P(None, None, tp, None),
        "wk": P(None, None, kv, None),
        "wv": P(None, None, kv, None),
        "wo": P(None, tp, None, None),
        "ffn_norm": P(None, None),
    }
    if cfg.num_experts > 0:
        layer_specs.update(
            router=P(None, None, None),
            we1=P(None, ep, None, tp),
            we3=P(None, ep, None, tp),
            we2=P(None, ep, tp, None),
        )
    else:
        layer_specs.update(w1=P(None, None, tp), w3=P(None, None, tp), w2=P(None, tp, None))
    return {"embed": P(tp, None), "layers": layer_specs, "final_norm": P(None)}


def _kv_tp_ok(cfg: TransformerConfig, mesh: Mesh, tp: str) -> bool:
    """Whether the kv-head axis can shard over tp (GQA may make it too small)."""
    n = mesh.shape.get(tp, 1)
    return cfg.kv_heads % n == 0


def fit_spec(shape, spec: P, mesh: Mesh) -> P:
    """Make a PartitionSpec legal for this array/mesh: drop mesh axes on
    dimensions they don't divide (e.g. an odd vocab size under tp) and
    repeated axes (a spec may name each mesh axis once — e.g. MoE specs
    with ep folded into tp keep only the first occurrence). A replicated
    dim beats a crash — but an axis the mesh doesn't HAVE is a typo and
    raises, not a silent full replication."""
    parts = []
    used = set()
    for dim, ax in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if ax is None:
            parts.append(None)
            continue
        named = (ax,) if isinstance(ax, str) else tuple(ax)
        unknown = [a for a in named if a not in mesh.shape]
        if unknown:
            raise ValueError(
                f"PartitionSpec axis {unknown[0]!r} is not a mesh axis "
                f"(mesh has {sorted(mesh.shape)}): likely a typo in the "
                f"dp/tp/ep axis names passed to shard_params/param_specs"
            )
        axes = tuple(a for a in named if a not in used)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        if not axes or dim % size != 0:
            parts.append(None)
            continue
        used.update(axes)
        parts.append(axes[0] if len(axes) == 1 else axes)
    return P(*parts)


def shard_params(params, mesh: Mesh, cfg: TransformerConfig, **axes):
    if "kv_tp" not in axes:
        axes["kv_tp"] = _kv_tp_ok(cfg, mesh, axes.get("tp", "tp"))
    if "ep" not in axes and "dp" not in axes and "dp" not in mesh.shape:
        # param_specs defaults ep to dp; on a dp-less mesh (tp-only
        # inference) fold experts into tp instead of raising on the
        # IMPLICIT 'dp' default. An explicitly-passed dp still goes
        # through fit_spec's typo check untouched.
        axes["ep"] = axes.get("tp", "tp")
    specs = param_specs(cfg, **axes)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, fit_spec(x.shape, s, mesh))),
        params,
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _rms_norm(x, scale, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale.astype(x.dtype)


def _rope(x, positions, theta: float):
    # x: [B, T, H, Dh]
    dh = x.shape[-1]
    half = dh // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32) * (math.log(theta) / half))
    angles = positions[:, :, None].astype(jnp.float32) * freqs[None, None, :]  # [B,T,half]
    cos, sin = jnp.cos(angles)[:, :, None, :], jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _repeat_kv(x, n_rep: int):
    """[B, T, Hkv, Dh] -> [B, T, Hkv*n_rep, Dh] (GQA group broadcast)."""
    if n_rep == 1:
        return x
    B, T, Hkv, Dh = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (B, T, Hkv, n_rep, Dh)).reshape(B, T, Hkv * n_rep, Dh)


def _gqa_mha(qt, k, v, *, causal: bool, sm_scale: float):
    """Grouped-query attention, K/V kept at kv-head width (no materialized
    repeat — decode/train HBM traffic stays 1/n_rep of the MHA layout).

    qt: [B, H, T, Dh]; k, v: [B, T, Hkv, Dh]."""
    B, H, T, Dh = qt.shape
    Hkv = k.shape[2]
    n_rep = H // Hkv
    qg = qt.reshape(B, Hkv, n_rep, T, Dh)
    kt = jnp.transpose(k, (0, 2, 1, 3))  # [B, Hkv, S, Dh]
    vt = jnp.transpose(v, (0, 2, 1, 3))
    s = jnp.einsum("bgrtd,bgsd->bgrts", qg, kt, preferred_element_type=jnp.float32) * sm_scale
    if causal:
        S = s.shape[-1]
        mask = jnp.arange(S)[None, :] <= jnp.arange(T)[:, None]
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrts,bgsd->bgrtd", p, vt.astype(jnp.float32))
    return o.reshape(B, H, T, Dh).astype(qt.dtype)


def _attention(cfg: TransformerConfig, q, k, v, use_flash: bool, mesh=None, sp_axis=None):
    # q: [B, T, H, Dh]; k, v: [B, T, Hkv, Dh] (unrepeated under GQA)
    n_rep = cfg.n_heads // cfg.kv_heads
    qt = jnp.transpose(q, (0, 2, 1, 3))
    if not use_flash and cfg.attention != "ring":
        # grouped einsum path: K/V never widen to n_heads
        o = _gqa_mha(qt, k, v, causal=True, sm_scale=1.0 / math.sqrt(cfg.head_dim))
        return jnp.transpose(o, (0, 2, 1, 3))
    # the Pallas flash / ring kernels take [B, H, T, Dh] with full heads
    k, v = _repeat_kv(k, n_rep), _repeat_kv(v, n_rep)
    kt, vt = (jnp.transpose(x, (0, 2, 1, 3)) for x in (k, v))
    if cfg.attention == "ring" and mesh is not None and sp_axis is not None:
        # sequence-parallel ring attention: K/V shards rotate over the sp
        # ICI axis; each step runs the Pallas flash kernel locally
        # (parallel/ring.py). GSPMD would instead all-gather K/V.
        from ray_tpu.parallel.ring import ring_attention_sharded

        T = qt.shape[2]
        n_sp = mesh.shape[sp_axis]
        pad = (-T) % n_sp
        if pad:
            # tail-pad to an even sp split; causal masking keeps padded
            # KEYS invisible to real queries, padded QUERY rows are sliced
            widths = ((0, 0), (0, 0), (0, pad), (0, 0))
            qt, kt, vt = (jnp.pad(x, widths) for x in (qt, kt, vt))
        axes = set(mesh.axis_names)
        o = ring_attention_sharded(
            qt, kt, vt, mesh, sp_axis, causal=True,
            batch_axis="dp" if "dp" in axes else None,
            head_axis="tp" if "tp" in axes else None,
        )
        if pad:
            o = o[:, :, :T]
    elif use_flash:
        o = flash_attention(qt, kt, vt, None, True)
    else:
        o = mha(qt, kt, vt, causal=True)
    return jnp.transpose(o, (0, 2, 1, 3))


def _moe_ffn(cfg: TransformerConfig, layer, x):
    """Top-k MoE dispatcher. ``moe_capacity_factor > 0`` routes through the
    capacity formulation (:func:`_moe_ffn_capacity` — top_k FFNs per
    token); otherwise dense dispatch: every expert computes every token and
    the router mask selects — exact, and fine when E is small."""
    if cfg.moe_capacity_factor > 0:
        return _moe_ffn_capacity(cfg, layer, x)
    e, k = cfg.num_experts, cfg.expert_top_k
    logits = jnp.einsum("btd,de->bte", x.astype(jnp.float32), layer["router"].astype(jnp.float32))
    gates = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(gates, k)
    mask = jnp.sum(jax.nn.one_hot(topi, e, dtype=gates.dtype) * topv[..., None], axis=-2)  # [B,T,E]
    mask = (mask / (jnp.sum(mask, -1, keepdims=True) + 1e-9)).astype(x.dtype)
    h = jnp.einsum("btd,edf->betf", x, layer["we1"].astype(x.dtype))
    g = jnp.einsum("btd,edf->betf", x, layer["we3"].astype(x.dtype))
    h = jax.nn.silu(g) * h
    out = jnp.einsum("betf,efd->betd", h, layer["we2"].astype(x.dtype))
    return jnp.einsum("betd,bte->btd", out, mask)


def _moe_ffn_capacity(cfg: TransformerConfig, layer, x):
    """Capacity-based top-k MoE (GShard/Switch): tokens route to at most
    ``C = ceil(top_k * T * factor / E)`` slots per expert via one-hot
    dispatch/combine einsums — compute per token is top_k expert-FFNs
    instead of all E. Overflow tokens are dropped (standard; they pass
    through the residual). The dispatch einsums partition over ep×tp the
    same way the dense formulation does — [B, E, C, d] expert blocks are
    the all-to-all payload under expert parallelism."""
    B, T, d = x.shape
    E, k = cfg.num_experts, cfg.expert_top_k
    C = max(1, math.ceil(k * T * cfg.moe_capacity_factor / E))
    logits = jnp.einsum("btd,de->bte", x.astype(jnp.float32), layer["router"].astype(jnp.float32))
    gates = jax.nn.softmax(logits, axis=-1)                      # [B,T,E]
    topv, topi = jax.lax.top_k(gates, k)                         # [B,T,k]
    topv = topv / (jnp.sum(topv, -1, keepdims=True) + 1e-9)
    sel = jax.nn.one_hot(topi, E, dtype=jnp.float32)             # [B,T,k,E]
    # slot index per (token, choice): how many earlier assignments this
    # expert already has (cumsum over the flattened (T, k) order)
    flat = sel.reshape(B, T * k, E)
    pos = jnp.cumsum(flat, axis=1) - flat                        # [B,T*k,E]
    slot = jnp.sum(pos.reshape(B, T, k, E) * sel, axis=-1)       # [B,T,k]
    keep = (slot < C).astype(jnp.float32)                       # fits capacity
    slot_oh = jax.nn.one_hot(slot.astype(jnp.int32), C, dtype=jnp.float32) * keep[..., None]
    # dispatch [B,T,E,C]: 1 where token t goes to expert e slot c
    dispatch = jnp.einsum("btke,btkc->btec", sel, slot_oh)
    combine = jnp.einsum("btk,btke,btkc->btec", topv.astype(jnp.float32), sel, slot_oh)
    xin = jnp.einsum("btec,btd->becd", dispatch.astype(x.dtype), x)   # [B,E,C,d]
    h = jnp.einsum("becd,edf->becf", xin, layer["we1"].astype(x.dtype))
    g = jnp.einsum("becd,edf->becf", xin, layer["we3"].astype(x.dtype))
    h = jax.nn.silu(g) * h
    out = jnp.einsum("becf,efd->becd", h, layer["we2"].astype(x.dtype))
    return jnp.einsum("btec,becd->btd", combine.astype(x.dtype), out)


def _dense_ffn(layer, x):
    h = jax.nn.silu(x @ layer["w3"].astype(x.dtype)) * (x @ layer["w1"].astype(x.dtype))
    return h @ layer["w2"].astype(x.dtype)


def forward(
    cfg: TransformerConfig,
    params: Dict[str, Any],
    tokens: jax.Array,  # [B, T] int32
    *,
    act_spec: Optional[P] = None,
    mesh: Optional[Mesh] = None,
    sp_axis: Optional[str] = None,
) -> jax.Array:
    """Returns logits [B, T, V]."""
    use_flash = cfg.attention == "flash" or (
        cfg.attention == "auto" and backend.on_tpu() and act_spec is None
    )
    B, T = tokens.shape
    x = embed_tokens(cfg, params, tokens)
    positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))

    def layer_fn(x, layer):
        h = _rms_norm(x, layer["attn_norm"])
        q = jnp.einsum("btd,dhk->bthk", h, layer["wq"].astype(h.dtype))
        k = jnp.einsum("btd,dhk->bthk", h, layer["wk"].astype(h.dtype))
        v = jnp.einsum("btd,dhk->bthk", h, layer["wv"].astype(h.dtype))
        q, k = _rope(q, positions, cfg.rope_theta), _rope(k, positions, cfg.rope_theta)
        o = _attention(cfg, q, k, v, use_flash, mesh=mesh, sp_axis=sp_axis)
        x = x + jnp.einsum("bthk,hkd->btd", o, layer["wo"].astype(o.dtype))
        h = _rms_norm(x, layer["ffn_norm"])
        ffn = _moe_ffn(cfg, layer, h) if cfg.num_experts > 0 else _dense_ffn(layer, h)
        x = x + ffn
        if act_spec is not None:
            x = jax.lax.with_sharding_constraint(x, act_spec)
        return x, None

    if cfg.remat == "dots":
        step = jax.checkpoint(
            layer_fn, policy=jax.checkpoint_policies.dots_saveable
        )
    elif cfg.remat:
        step = jax.checkpoint(layer_fn)
    else:
        step = layer_fn
    if cfg.scan_layers:
        x, _ = jax.lax.scan(step, x, params["layers"])
    else:
        # Unrolled layer loop: under remat, scan stacks every saved
        # activation through dynamic-update-slice writes (and reads them
        # back by dynamic-slice in bwd) — measured ~25% of a 602M train
        # step on v5e.  Straight-line layers keep saves as plain buffers.
        for i in range(cfg.n_layers):
            layer_i = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
            x, _ = step(x, layer_i)
    x = _rms_norm(x, params["final_norm"])
    logits = jnp.einsum("btd,vd->btv", x, params["embed"].astype(x.dtype))
    return logits.astype(jnp.float32)


def embed_tokens(cfg: TransformerConfig, params, tokens) -> jax.Array:
    """THE tied-embedding input path (training forward AND cached decode
    import this — a drifted copy would make serving logits diverge from
    training by the scale factor): sqrt(d) input scale pairs with the
    1/sqrt(d)-std embedding init so the residual stream keeps its usual
    magnitude while unembed rows stay ~unit-norm (init logits O(1), never
    an input-copier)."""
    return params["embed"].astype(cfg.dtype)[tokens] * math.sqrt(cfg.d_model)


def loss_fn(cfg: TransformerConfig, params, tokens, *, act_spec=None, mesh=None, sp_axis=None) -> jax.Array:
    """Next-token cross entropy: position t predicts tokens[:, t+1].

    The forward runs on the FULL [B, T] batch with the last position masked
    out of the mean, rather than slicing to [B, T-1]: causality makes the
    first T-1 positions' logits identical either way, but odd T-1
    activations force XLA to pad/slice every (8,128)-tiled tensor in the
    step (measured ~2% of a 602M train step), while full-T stays
    tile-aligned."""
    from ray_tpu.parallel._compat import spmd_roll

    B, T = tokens.shape
    logits = forward(cfg, params, tokens, act_spec=act_spec, mesh=mesh, sp_axis=sp_axis)
    targets = spmd_roll(tokens, -1, axis=1)  # [:, T-1] rolls around: masked
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    mask = (jnp.arange(T) < T - 1).astype(nll.dtype)[None, :]
    return jnp.sum(nll * mask) / (B * (T - 1))


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------
def make_train_step(
    cfg: TransformerConfig,
    *,
    mesh: Optional[Mesh] = None,
    learning_rate: float = 3e-4,
    dp: str = "dp",
    sp: Optional[str] = "sp",
    tp: str = "tp",
    ep: Optional[str] = None,
):
    """Build (init_state, train_step). Jitted to one XLA program; with a mesh,
    params/opt shard per ``param_specs`` and batch shards over (dp, sp)."""
    import optax

    opt = optax.adamw(learning_rate)

    def init_state(key):
        params = init_params(cfg, key)
        return {"params": params, "opt": opt.init(params), "step": jnp.zeros((), jnp.int32)}

    act_spec = None
    ring_mesh = None
    sp_ax = None
    if mesh is not None:
        from ray_tpu.parallel._compat import constraint_sharding

        axis_names = set(mesh.axis_names)
        sp_ax = sp if (sp and sp in axis_names) else None
        # bound to a NamedSharding so the jitted step works without an
        # ambient mesh context at the call site (see parallel/_compat.py)
        act_spec = constraint_sharding(mesh, P(dp if dp in axis_names else None, sp_ax, None))
        if cfg.attention == "flash":
            # GSPMD cannot partition a Mosaic kernel; the CPU mesh only
            # accepted this because interpret mode lowers to ordinary ops
            raise ValueError(
                'attention="flash" cannot run under a mesh. Supported with a '
                'mesh: "auto"/"dense" (XLA einsum attention, partitioned by '
                'GSPMD) and "ring" (the flash kernel inside a shard_map over '
                "dp/tp/sp; needs an sp mesh axis)"
            )
        if cfg.attention == "ring":
            if sp_ax is None:
                raise ValueError(
                    'attention="ring" needs a sequence-parallel mesh axis '
                    f"(sp={sp!r} not in mesh axes {sorted(axis_names)}); "
                    "silently falling back to dense would lose the memory "
                    "scaling the mode promises"
                )
            ring_mesh = mesh

    def train_step(state, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, tokens, act_spec=act_spec, mesh=ring_mesh, sp_axis=sp_ax)
        )(state["params"])
        updates, new_opt = opt.update(grads, state["opt"], state["params"])
        new_params = optax.apply_updates(state["params"], updates)
        return {"params": new_params, "opt": new_opt, "step": state["step"] + 1}, loss

    if mesh is None:
        return init_state, jax.jit(train_step, donate_argnums=(0,))

    pspecs = param_specs(cfg, dp=dp, tp=tp, ep=ep, kv_tp=_kv_tp_ok(cfg, mesh, tp))
    param_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs, is_leaf=lambda x: isinstance(x, P))

    def sharded_init(key):
        # params placed per the TP layout; the (eagerly-run) optax init then
        # inherits each leaf's sharding through zeros_like, so opt state is
        # laid out identically with no explicit spec tree.
        params = jax.tree.map(lambda x, s: jax.device_put(x, s), init_params(cfg, key), param_sh)
        return {"params": params, "opt": opt.init(params), "step": jnp.zeros((), jnp.int32)}

    def shard_batch(tokens):
        return jax.device_put(tokens, NamedSharding(mesh, P(dp, None)))

    from ray_tpu.models.common import JittedStep

    return sharded_init, JittedStep(jax.jit(train_step, donate_argnums=(0,)), shard_batch)
