"""AFMoE (Arcee Trinity: ``model_type: afmoe``; arcee-ai/Trinity-Mini).

Three things, all keyed by the configuration file's own (HuggingFace) names:

* :func:`program_config` — the sizes and switches as
  ``ray_tpu.models.TransformerConfig`` takes them (the only place the
  benchmark names the program's fields); it refuses a file whose keys the
  program cannot honour;
* the plain reference: the published forward pass in straightforward float32
  ``jax.numpy`` under ``jax.default_matmul_precision("highest")`` — no kernel,
  no cache, no batching, nothing imported from ``ray_tpu``. Attention runs in
  query blocks, the expert layer as a plain sum over experts (each expert on
  every token, times the token's weight for it, which is zero unless the
  router chose it), and at most one layer's non-expert weights, one block of
  experts' weights and one block of the output head are alive in float32;
* the arithmetic: parameters, FLOPs a token needs, bytes a decode step and
  its expert layers have to read.

The equations of one layer (d hidden, H query heads, Hkv KV heads, Dh head
size, W ``sliding_window``; layer ``l`` is ``layer_types[l]``; layers
``l < num_dense_layers`` have a dense MLP, the rest an expert layer). The
config's keys give the sizes; what they do not give is taken from the
family's published model code (``transformers``, ``models/afmoe``) and listed
under ``assumed`` in the configuration file:

    x_0 = E_in[token] * sqrt(d)                                    (mup_enabled)
    a = RMSNorm_in(x);  q = RMSNorm_q(a Wq), k = RMSNorm_k(a Wk) per head over Dh;  v = a Wv
    sliding layers: q, k = RoPE(q, k) (rotate-half, theta, absolute position);  full layers: none
    attention causal, scale 1/sqrt(Dh), H/Hkv query heads to a KV head;
        sliding layers: key j visible to query i iff i - W < j <= i
    o = attn * sigmoid(a Wg);  x = x + RMSNorm_post_attn(o Wo)
    m = RMSNorm_pre_mlp(x)
    dense layer:  f = W_down(silu(W_gate m) * (W_up m))
    expert layer: s = sigmoid(m W_r) in float32;  S = the k largest of s + b;
        w_e = s_e for e in S;  w = w / (sum(w) + 1e-20) (route_norm);  w = route_scale * w;
        f = shared(m) + sum_{e in S} w_e expert_e(m), every expert the dense layer's MLP at its own width
    x = x + RMSNorm_post_mlp(f)
    logits = RMSNorm_f(x_L) W_head^T, W_head a matrix of its own

Training-only parts (``load_balance_coeff``, the update rule of the bias
``b``) are not part of the function served and are left out.
"""

from __future__ import annotations

import math
from typing import Any, Dict

# ---------------------------------------------------------------------------
# arithmetic (pure Python: usable without jax)
# ---------------------------------------------------------------------------


def _sizes(c: Dict[str, Any]):
    d, h, hkv, dh = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    attn = 2 * d * h * dh + 2 * d * hkv * dh + h * dh * d  # wq, wg, wk, wv, wo
    norms = 4 * d + 2 * dh
    expert = 3 * d * c["moe_intermediate_size"]
    return d, attn, norms, expert


def n_params(c: Dict[str, Any]) -> int:
    """Parameters at this depth: dense layers, expert layers (router, its
    bias, routed and shared experts), both tables, the last norm."""
    d, attn, norms, expert = _sizes(c)
    dense = attn + norms + 3 * d * c["intermediate_size"]
    moe = (attn + norms + d * c["num_experts"] + c["num_experts"]
           + (c["num_experts"] + c["num_shared_experts"]) * expert)
    nd = c["num_dense_layers"]
    tables = (1 if c["tie_word_embeddings"] else 2) * c["vocab_size"] * d
    return nd * dense + (c["num_hidden_layers"] - nd) * moe + tables + d


def active_params_per_token(c: Dict[str, Any]) -> int:
    """Matmul parameters one token passes through (the input table is a
    lookup; k routed experts and the shared ones of each expert layer)."""
    d, attn, _, expert = _sizes(c)
    nd = c["num_dense_layers"]
    moe = attn + d * c["num_experts"] + (c["num_experts_per_tok"] + c["num_shared_experts"]) * expert
    return nd * (attn + 3 * d * c["intermediate_size"]) + (c["num_hidden_layers"] - nd) * moe + c["vocab_size"] * d


def forward_flops_per_token(c: Dict[str, Any], context: int) -> float:
    """FLOPs one token's forward pass needs at ``context`` visible positions
    in a full layer (a sliding layer sees at most ``sliding_window``): 2 per
    active matmul parameter plus the score and value products."""
    h, dh = c["num_attention_heads"], c["head_dim"]
    seen = sum(min(context, c["sliding_window"]) if t == "sliding_attention" else context
               for t in c["layer_types"][: c["num_hidden_layers"]])
    return 2.0 * active_params_per_token(c) + 4.0 * h * dh * seen


def expert_step_bytes(c: Dict[str, Any], experts_hit: float, tokens: int = 0, weight_bytes: int = 2,
                      act_bytes: int = 2) -> float:
    """Bytes the grouped products of one step have to read: the three
    matrices of each (layer, expert) pair that got a token, once each
    (``experts_hit`` pairs over all expert layers), and each assignment's
    activations once (in: d, between: 2 x width, out: d), for ``tokens``
    tokens a step."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    layers = c["num_hidden_layers"] - c["num_dense_layers"]
    acts = layers * tokens * c["num_experts_per_tok"] * (2 * d + 3 * f) * act_bytes
    return experts_hit * 3 * d * f * weight_bytes + acts


def decode_step_bytes(c: Dict[str, Any], live_tokens: int, experts_hit: float, kv_read_share: float = 1.0,
                      weight_bytes: int = 2, kv_bytes: int = 2) -> float:
    """Bytes one decode step has to read: every non-expert weight once
    (the input table is a lookup), the experts that got a token, and the K
    and V a step must see (``kv_read_share`` of the live tokens: a sliding
    layer reads at most its window)."""
    d, attn, norms, expert = _sizes(c)
    nd, L = c["num_dense_layers"], c["num_hidden_layers"]
    fixed = (L * (attn + norms) + nd * 3 * d * c["intermediate_size"]
             + (L - nd) * (d * c["num_experts"] + c["num_shared_experts"] * expert) + c["vocab_size"] * d + d)
    kv = 2 * L * c["num_key_value_heads"] * c["head_dim"] * live_tokens * kv_read_share * kv_bytes
    return fixed * weight_bytes + expert_step_bytes(c, experts_hit, weight_bytes=weight_bytes) + kv


# ---------------------------------------------------------------------------
# the program's config
# ---------------------------------------------------------------------------
_KINDS = {"sliding_attention": "sliding", "full_attention": "full"}


def program_config(c: Dict[str, Any], **overrides):
    """``TransformerConfig`` for this file's sizes. ``overrides`` are the
    run's own choices (dtypes, sequence length, attention mode)."""
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig

    L = c["num_hidden_layers"]
    refused = {
        "hidden_act": c.get("hidden_act", "silu") != "silu",
        "rope_scaling": c.get("rope_scaling") is not None,
        "n_group/topk_group": (c.get("n_group", 1), c.get("topk_group", 1)) != (1, 1),
        "layer_types": len(c["layer_types"]) < L or set(c["layer_types"]) - set(_KINDS),
        "score_func": c["score_func"] not in ("sigmoid", "softmax"),
        "mup_enabled": not c.get("mup_enabled", False),
        "num_dense_layers": not 0 <= c["num_dense_layers"] < L,
    }
    bad = sorted(k for k, v in refused.items() if v)
    if bad:
        raise ValueError(f"the program cannot honour this file's {bad}")
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    kw = dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_layers=L,
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], max_seq_len=c["max_position_embeddings"], rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]), embed_scale=math.sqrt(c["hidden_size"]),
        tie_embeddings=bool(c["tie_word_embeddings"]), qk_norm=True, attn_gate=True, post_norms=True,
        layer_types=tuple(_KINDS[t] for t in c["layer_types"][:L]), sliding_window=c["sliding_window"],
        rope_full_layers=False,
        num_experts=c["num_experts"], expert_top_k=c["num_experts_per_tok"], num_dense_layers=c["num_dense_layers"],
        expert_d_ff=c["moe_intermediate_size"], num_shared_experts=c["num_shared_experts"],
        router_score=c["score_func"], route_norm=bool(c["route_norm"]), route_scale=float(c["route_scale"]),
        router_bias=True,
    )
    kw.update(overrides)
    for key in ("dtype", "param_dtype"):
        if isinstance(kw.get(key), str):
            kw[key] = dtypes[kw[key]]
    return TransformerConfig(**kw)


def reference_layer(params, i: int, nd: int):
    """The non-expert weights of layer ``i`` of the program's parameter tree
    in the reference's plain layout (2-D float32 matrices, HuggingFace's
    names), and the stack and index its experts are read from."""
    import jax.numpy as jnp

    stack, j = (params["dense_layers"], i) if i < nd else (params["layers"], i - nd)
    d = stack["wq"].shape[1]
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    w = {
        "input_layernorm": f32(stack["attn_norm"][j]),
        "q_proj": f32(stack["wq"][j]).reshape(d, -1),
        "k_proj": f32(stack["wk"][j]).reshape(d, -1),
        "v_proj": f32(stack["wv"][j]).reshape(d, -1),
        "gate_proj": f32(stack["wg"][j]).reshape(d, -1),
        "o_proj": f32(stack["wo"][j]).reshape(-1, d),
        "q_norm": f32(stack["q_norm"][j]),
        "k_norm": f32(stack["k_norm"][j]),
        "post_attention_layernorm": f32(stack["post_attn_norm"][j]),
        "pre_mlp_layernorm": f32(stack["ffn_norm"][j]),
        "post_mlp_layernorm": f32(stack["post_ffn_norm"][j]),
    }
    if i < nd:
        w["mlp"] = {"gate": f32(stack["w3"][j]), "up": f32(stack["w1"][j]), "down": f32(stack["w2"][j])}
    else:
        w["router"] = f32(stack["router"][j])
        w["expert_bias"] = f32(stack["router_bias"][j])
        if "ws1" in stack:
            w["shared"] = {"gate": f32(stack["ws3"][j]), "up": f32(stack["ws1"][j]), "down": f32(stack["ws2"][j])}
    return w, stack, j


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
_QUERY_BLOCK = 512   # attention takes this many query positions at a time
_EXPERT_BLOCK = 16   # experts whose weights are alive in float32 at a time
_VOCAB_BLOCKS = 8    # the output head is applied in this many slices of its rows


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


def _mlp(m, w):
    import jax

    return (jax.nn.silu(m @ w["gate"]) * (m @ w["up"])) @ w["down"]


def _attention_branch(x, w, *, n_heads, n_kv_heads, theta, eps, sliding: bool, window: int):
    """``x + RMSNorm_post_attn(o Wo)`` on one sequence. x: [T, d] float32."""
    import jax
    import jax.numpy as jnp

    T, _ = x.shape
    dh = w["q_proj"].shape[1] // n_heads
    a = _rms_norm(x, w["input_layernorm"], eps)
    q = _rms_norm((a @ w["q_proj"]).reshape(T, n_heads, dh), w["q_norm"], eps)
    k = _rms_norm((a @ w["k_proj"]).reshape(T, n_kv_heads, dh), w["k_norm"], eps)
    v = (a @ w["v_proj"]).reshape(T, n_kv_heads, dh)
    if sliding:
        q, k = _rope(q, theta), _rope(k, theta)
    rep = n_heads // n_kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    blocks = []
    for start in range(0, T, _QUERY_BLOCK):
        qb = q[start : start + _QUERY_BLOCK]
        s = jnp.einsum("thd,shd->hts", qb, k) / math.sqrt(dh)
        i = (start + jnp.arange(qb.shape[0]))[:, None]
        j = jnp.arange(T)[None, :]
        visible = (j <= i) & (j > i - window) if sliding else j <= i
        p = jax.nn.softmax(jnp.where(visible[None], s, -jnp.inf), axis=-1)
        blocks.append(jnp.einsum("hts,shd->thd", p, v))
    o = jnp.concatenate(blocks, axis=0).reshape(T, n_heads * dh)
    o = o * jax.nn.sigmoid(a @ w["gate_proj"])
    return x + _rms_norm(o @ w["o_proj"], w["post_attention_layernorm"], eps)


def _route(m, w, chosen=None, *, k, score_func, route_norm, route_scale):
    """[T, E] float32: a token's weight for each expert, zero unless chosen.
    ``chosen`` [T, k]: a selection given from outside instead of the router's
    own (the weights are still this router's scores)."""
    import jax
    import jax.numpy as jnp

    logits = m @ w["router"]
    s = jax.nn.sigmoid(logits) if score_func == "sigmoid" else jax.nn.softmax(logits, axis=-1)
    if chosen is None:
        _, chosen = jax.lax.top_k(s + w["expert_bias"], k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if route_norm:
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    picked = picked * route_scale
    rows = jnp.arange(m.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(picked)


def make_reference(c: Dict[str, Any]):
    """Returns ``logits(params, tokens[T], positions=None) -> [T or P, V]`` and
    ``loss(params, tokens[B, T]) -> scalar`` (next-token cross entropy, mean
    over the B * (T - 1) predicted positions), both float32 at "highest"
    matmul precision."""
    import jax
    import jax.numpy as jnp

    L, nd, eps = c["num_hidden_layers"], c["num_dense_layers"], float(c["rms_norm_eps"])
    kinds = c["layer_types"][:L]
    attn_kw = dict(n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
                   theta=float(c["rope_theta"]), eps=eps, window=int(c["sliding_window"]))
    route_kw = dict(k=c["num_experts_per_tok"], score_func=c["score_func"], route_norm=bool(c["route_norm"]),
                    route_scale=float(c["route_scale"]))
    embed_scale = math.sqrt(c["hidden_size"]) if c.get("mup_enabled") else 1.0

    def highest(fn, **jit_kw):
        def run(*args, **kw):
            with jax.default_matmul_precision("highest"):
                return fn(*args, **kw)

        return jax.jit(run, **jit_kw)

    embed = highest(lambda table, tokens: table[tokens].astype(jnp.float32) * embed_scale)
    attention = highest(lambda x, w, sliding: _attention_branch(x, w, sliding=sliding, **attn_kw),
                        static_argnames=("sliding",))
    pre_mlp = highest(lambda x, w: _rms_norm(x, w["pre_mlp_layernorm"], eps))
    routing = highest(lambda m, w, chosen=None: _route(m, w, chosen, **route_kw))
    mlp = highest(_mlp)
    close = highest(lambda x, f, w: x + _rms_norm(f, w["post_mlp_layernorm"], eps))
    last_norm = highest(lambda x, gain: _rms_norm(x, gain.astype(jnp.float32), eps))
    head_block = highest(lambda xn, rows: xn @ rows.astype(jnp.float32).T)

    @highest
    def expert_block(m, weights, gate, up, down):
        """sum over this block's experts e of weights[:, e] * expert_e(m)."""
        gate, up, down = (a.astype(jnp.float32) for a in (gate, up, down))
        h = jax.nn.silu(jnp.einsum("td,edf->etf", m, gate)) * jnp.einsum("td,edf->etf", m, up)
        return jnp.einsum("etf,efd->td", h * weights.T[:, :, None], down)

    def hidden(params, tokens, on_router=None):
        """Final hidden states [T, d] of one sequence, before the last norm.
        ``on_router(layer, m, w)`` is shown each expert layer's input and
        router weights; what it returns, if anything, is the selection
        ``[T, k]`` that layer uses instead of its own (the builder's
        precision control holds the routing equal to the program's with it)."""
        x = embed(params["embed"], tokens)
        for i in range(L):
            w, stack, j = reference_layer(params, i, nd)
            x = attention(x, w, sliding=kinds[i] == "sliding_attention")
            m = pre_mlp(x, w)
            if i < nd:
                f = mlp(m, w["mlp"])
            else:
                weights = routing(m, w, on_router(i, m, w) if on_router is not None else None)
                f = mlp(m, w["shared"]) if "shared" in w else jnp.zeros_like(m)
                for e in range(0, c["num_experts"], _EXPERT_BLOCK):
                    sl = slice(e, e + _EXPERT_BLOCK)
                    f = f + expert_block(m, weights[:, sl], stack["we3"][j, sl], stack["we1"][j, sl],
                                         stack["we2"][j, sl])
            x = close(x, f, w)
        return x

    def head(params, x):
        table = params["embed"] if c["tie_word_embeddings"] else params["head"]
        xn = last_norm(x, params["final_norm"])
        step = -(-table.shape[0] // _VOCAB_BLOCKS)
        return jnp.concatenate([head_block(xn, table[a : a + step]) for a in range(0, table.shape[0], step)], axis=-1)

    def logits(params, tokens, positions=None, on_router=None):
        x = hidden(params, tokens, on_router)
        if positions is not None:
            x = x[positions]
        return head(params, x)

    def loss(params, tokens):
        B, T = tokens.shape
        total = 0.0
        for b in range(B):
            x = hidden(params, tokens[b])
            for s in range(0, T - 1, _QUERY_BLOCK):
                e = min(s + _QUERY_BLOCK, T - 1)
                logp = jax.nn.log_softmax(head(params, x[s:e]), axis=-1)
                total += float(-jnp.take_along_axis(logp, tokens[b, s + 1 : e + 1, None], axis=-1).sum())
        return total / (B * (T - 1))

    return logits, loss
