"""SmolLM2 (Llama-architecture decoder; HuggingFaceTB/SmolLM2-1.7B).

Three things, all keyed by the configuration file's own (HuggingFace) names:

* :func:`program_config` — the sizes as ``ray_tpu.models.TransformerConfig``
  takes them (the only place the benchmark names the program's fields);
* the plain reference: the published forward pass in straightforward float32
  ``jax.numpy`` under ``jax.default_matmul_precision("highest")`` — no kernel,
  no cache, no batching, nothing imported from ``ray_tpu``. It holds one
  layer's float32 weights at a time;
* the arithmetic: parameters, FLOPs a token needs, bytes a decode step moves.

Departures of the program from the published model, which the reference is
told about through ``departures`` in the configuration file so that the two
compute the same function: tied embeddings are scaled by sqrt(hidden_size)
on the input side (published: 1), and RMSNorm's epsilon is 1e-6 (published:
1e-5).
"""

from __future__ import annotations

import math
from typing import Any, Dict

# ---------------------------------------------------------------------------
# arithmetic (pure Python: usable without jax)
# ---------------------------------------------------------------------------


def n_params(c: Dict[str, Any]) -> int:
    """Parameters of the decoder at this depth; tied embeddings count once."""
    d, ff = c["hidden_size"], c["intermediate_size"]
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    dh = c.get("head_dim", d // h)
    per_layer = d * h * dh + 2 * d * hkv * dh + h * dh * d + 3 * d * ff + 2 * d
    return c["num_hidden_layers"] * per_layer + c["vocab_size"] * d + d


def train_flops_per_token(c: Dict[str, Any], seq_len: int) -> float:
    """FLOPs the forward and backward passes need per token, recomputation
    not counted: 6 per matmul parameter (the tied embedding once, as the
    output head; norm gains are not matmuls) plus causal attention, which
    needs half of the full T x T score and value products:
    fwd 2 * 2 * T * d / 2 per layer, times 3 for fwd + bwd."""
    d = c["hidden_size"]
    matmul_params = n_params(c) - (2 * c["num_hidden_layers"] + 1) * d
    return 6.0 * matmul_params + 6.0 * c["num_hidden_layers"] * d * seq_len


def decode_step_bytes(c: Dict[str, Any], live_tokens: int, weight_bytes: int = 2,
                      kv_bytes: int = 2) -> float:
    """Bytes one decode step has to read: every weight once and the K and V
    of every live token. (What a roofline share of the decode step will be
    taken against, once the program reports per-step context lengths.)"""
    d, h, hkv = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    dh = c.get("head_dim", d // h)
    kv = 2 * c["num_hidden_layers"] * hkv * dh * live_tokens * kv_bytes
    return n_params(c) * weight_bytes + kv


# ---------------------------------------------------------------------------
# the program's config
# ---------------------------------------------------------------------------


def program_config(c: Dict[str, Any], **overrides):
    """``TransformerConfig`` for this file's sizes. ``overrides`` are the
    run's own choices (attention mode, remat, dtypes, sequence length)."""
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig

    if not c.get("tie_word_embeddings", False):
        raise ValueError("the program only has tied embeddings")
    if c.get("hidden_act", "silu") != "silu":
        raise ValueError("the program's MLP is SiLU-gated")
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    kw = dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        max_seq_len=c["max_position_embeddings"], rope_theta=float(c["rope_theta"]),
    )
    kw.update(overrides)
    for key in ("dtype", "param_dtype"):
        if isinstance(kw.get(key), str):
            kw[key] = dtypes[kw[key]]
    cfg = TransformerConfig(**kw)
    if cfg.head_dim != c.get("head_dim", cfg.head_dim):
        raise ValueError("head_dim of the file disagrees with hidden_size / heads")
    return cfg


def reference_layer(params, i: int):
    """Layer ``i`` of the program's parameter tree in the reference's plain
    layout (2-D float32 matrices, HuggingFace's names)."""
    import jax.numpy as jnp

    L = params["layers"]
    d = L["wq"].shape[1]
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    return {
        "input_layernorm": f32(L["attn_norm"][i]),
        "q_proj": f32(L["wq"][i]).reshape(d, -1),
        "k_proj": f32(L["wk"][i]).reshape(d, -1),
        "v_proj": f32(L["wv"][i]).reshape(d, -1),
        "o_proj": f32(L["wo"][i]).reshape(-1, d),
        "post_attention_layernorm": f32(L["ffn_norm"][i]),
        "gate_proj": f32(L["w3"][i]),
        "up_proj": f32(L["w1"][i]),
        "down_proj": f32(L["w2"][i]),
    }


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
_QUERY_BLOCK = 512  # attention, and the output head, take this many positions at a time


def _rms_norm(x, gain, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _rope(x, theta):
    """Rotary embedding, HuggingFace's rotate-half convention. x: [T, H, dh]."""
    import jax.numpy as jnp

    T, _, dh = x.shape
    half = dh // 2
    inv_freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _ref_layer(x, w, *, n_heads, n_kv_heads, theta, eps):
    """One decoder layer on one sequence. x: [T, d] float32."""
    import jax
    import jax.numpy as jnp

    T, d = x.shape
    dh = w["q_proj"].shape[1] // n_heads
    h = _rms_norm(x, w["input_layernorm"], eps)
    q = _rope((h @ w["q_proj"]).reshape(T, n_heads, dh), theta)
    k = _rope((h @ w["k_proj"]).reshape(T, n_kv_heads, dh), theta)
    v = (h @ w["v_proj"]).reshape(T, n_kv_heads, dh)
    rep = n_heads // n_kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    blocks = []
    for start in range(0, T, _QUERY_BLOCK):
        qb = q[start : start + _QUERY_BLOCK]
        s = jnp.einsum("thd,shd->hts", qb, k) / math.sqrt(dh)
        visible = jnp.arange(T)[None, :] <= (start + jnp.arange(qb.shape[0]))[:, None]
        p = jax.nn.softmax(jnp.where(visible[None], s, -jnp.inf), axis=-1)
        blocks.append(jnp.einsum("hts,shd->thd", p, v))
    o = jnp.concatenate(blocks, axis=0).reshape(T, n_heads * dh)
    x = x + o @ w["o_proj"]
    h = _rms_norm(x, w["post_attention_layernorm"], eps)
    return x + (jax.nn.silu(h @ w["gate_proj"]) * (h @ w["up_proj"])) @ w["down_proj"]


def make_reference(c: Dict[str, Any]):
    """Returns ``logits(params, tokens[T], positions=None) -> [T or P, V]`` and
    ``loss(params, tokens[B, T]) -> scalar`` (next-token cross entropy, mean
    over the B * (T - 1) predicted positions), both float32 at "highest"
    matmul precision, one layer's float32 weights alive at a time."""
    import jax
    import jax.numpy as jnp

    dep = c.get("departures", {})
    eps = float(dep.get("rms_norm_eps", c["rms_norm_eps"]))
    embed_scale = float(dep.get("embed_scale", 1.0))
    kw = dict(
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        theta=float(c["rope_theta"]), eps=eps,
    )

    @jax.jit
    def embed(table, tokens):
        with jax.default_matmul_precision("highest"):
            return table[tokens].astype(jnp.float32) * embed_scale

    @jax.jit
    def layer(x, w):
        with jax.default_matmul_precision("highest"):
            return _ref_layer(x, w, **kw)

    @jax.jit
    def head(x, gain, table):
        with jax.default_matmul_precision("highest"):
            return _rms_norm(x, gain.astype(jnp.float32), eps) @ table.astype(jnp.float32).T

    @jax.jit
    def block_nll(x, gain, table, targets):
        logp = jax.nn.log_softmax(head(x, gain, table), axis=-1)
        return -jnp.take_along_axis(logp, targets[:, None], axis=-1).sum()

    def hidden(params, tokens):
        """Final hidden states [T, d] of one sequence, before the last norm."""
        x = embed(params["embed"], tokens)
        for i in range(c["num_hidden_layers"]):
            x = layer(x, reference_layer(params, i))
        return x

    def logits(params, tokens, positions=None):
        x = hidden(params, tokens)
        if positions is not None:
            x = x[positions]
        return head(x, params["final_norm"], params["embed"])

    def loss(params, tokens):
        B, T = tokens.shape
        total = 0.0
        for b in range(B):
            x = hidden(params, tokens[b])
            for s in range(0, T - 1, _QUERY_BLOCK):
                e = min(s + _QUERY_BLOCK, T - 1)
                total += float(
                    block_nll(x[s:e], params["final_norm"], params["embed"], tokens[b, s + 1 : e + 1])
                )
        return total / (B * (T - 1))

    return logits, loss
