"""LFM2-MoE (``model_type: lfm2_moe``; LiquidAI/LFM2-8B-A1B): a decoder whose
token mixers are, three layers of four, a **gated short convolution** (two
products around a depthwise causal convolution of ``conv_L_cache`` = 3 taps:
no keys, no values, no recurrent matrix; a sequence carries its last two
inputs to the convolution a layer) and, every fourth or so, grouped-query
softmax attention; whose first ``num_dense_layers`` FFNs are dense SwiGLUs and
every other a routed expert layer of ``num_experts`` experts without a shared
one. The order of layer kinds is the published ``layer_types`` list and is no
period that ends in attention: two convolution layers lead, then each period
*begins* with its attention layer, and the published 24 end in a shorter one.

Three things, all keyed by the configuration file's own (HuggingFace) names:

* :func:`program_config` — the sizes and switches as
  ``ray_tpu.models.TransformerConfig`` takes them (the only place the
  benchmark names the program's fields); it refuses a file whose keys the
  program cannot honour;
* the plain reference: the forward pass in straightforward float32
  ``jax.numpy`` under ``jax.default_matmul_precision("highest")`` — no kernel,
  no cache, no chunking, no batching, no sort, nothing imported from
  ``ray_tpu``. The convolution is three shifted products over the whole
  sequence; attention runs in query blocks; the expert layer is a plain sum
  over experts (each expert on every token, times the token's weight for it,
  zero unless the router chose it), a block of experts' weights float32 at a
  time; the head runs in vocabulary slices; one layer's weights are float32 at
  a time (:func:`reference_layer` hands them over layer by layer: 14 layers in
  float32 are 18.7 GB and would not fit whole);
* the arithmetic: parameters, the bytes a decode step reads, and the bytes the
  convolution mixers have to move (:func:`conv_mixer_bytes`).

The equations (``d`` hidden, ``L`` = ``conv_L_cache``; ``RMSNorm(x; g) = g * x
/ sqrt(mean(x^2) + norm_eps)``). The catalog's keys give the sizes; what they
do not give is listed under ``assumed`` in the configuration file (the
published ``modeling_lfm2_moe.py`` as recalled: no network here), and the
reference and the program follow the file:

    x_0 = E[token]                                                       (no multiplier)
    every layer l:  x <- x + Mixer_l(RMSNorm(x; operator_norm_l));  x <- x + FFN_l(RMSNorm(x; ffn_norm_l))
    logits = RMSNorm(x; embedding_norm) E^T                              (tied)
    conv mixer:  [B_t | C_t | X_t] = h_t W_in  (d -> 3d);  u_t = B_t * X_t
                 c_t = sum_{j=0..L-1} w_j * u_{t-(L-1)+j}  (depthwise, causal, u_s = 0 for s < 0)
                 y_t = C_t * c_t;  mixer = y_t W_out.  No activation, no bias.
                 What a sequence carries: u_{t-L+1} .. u_{t-1}, (L - 1) x d numbers a layer.
    full_attention mixer:  q = h W_q (H heads of Dh), k = h W_k, v = h W_v (Hkv heads);  q, k each through an
                 RMSNorm over a head's Dh numbers (gains q_layernorm, k_layernorm), then RoPE at rope_theta
                 over the whole head, half-split;  causal softmax at 1 / sqrt(Dh), H / Hkv query heads a KV
                 head;  W_o
    dense FFN (l < num_dense_layers):  W_2(silu(W_1 h) * W_3 h), width intermediate_size
    expert FFN:  s = sigmoid(h W_g) in float32 over all num_experts;  the chosen: the num_experts_per_tok
                 largest of s + b (b = expert_bias, a float32 buffer: it selects and never weighs);
                 w_e = routed_scaling_factor * s_e / (sum_{chosen} s + 1e-6)  (norm_topk_prob);
                 FFN = sum_{chosen} w_e W2_e(silu(W1_e h) * W3_e h), width moe_intermediate_size

Training-only parts (how ``expert_bias`` is moved) are not part of the
function served and are left out.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List

ROUTE_NORM_EPS = 1e-6   # what the published router adds to the chosen scores' sum before it divides
KINDS = {"conv": "conv", "full_attention": "full"}


# ---------------------------------------------------------------------------
# arithmetic (pure Python: usable without jax)
# ---------------------------------------------------------------------------
def layer_kinds(c: Dict[str, Any]) -> List[str]:
    return list(c["layer_types"][: c["num_hidden_layers"]])


def conv_layers(c: Dict[str, Any]) -> int:
    return layer_kinds(c).count("conv")


def head_dim(c: Dict[str, Any]) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def mixer_params(c: Dict[str, Any], kind: str) -> int:
    """Parameters of one mixer of ``kind`` and its norm."""
    d = c["hidden_size"]
    if kind == "conv":
        return d * 3 * d + d * d + c["conv_L_cache"] * d + d
    dh = head_dim(c)
    return d * c["num_attention_heads"] * dh + 2 * d * c["num_key_value_heads"] * dh \
        + c["num_attention_heads"] * dh * d + 2 * dh + d


def expert_params(c: Dict[str, Any]) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def ffn_params(c: Dict[str, Any], layer: int) -> int:
    """Parameters of layer ``layer``'s FFN and its norm (the selection bias counted)."""
    d = c["hidden_size"]
    if layer < c["num_dense_layers"]:
        return 3 * d * c["intermediate_size"] + d
    return c["num_experts"] * expert_params(c) + d * c["num_experts"] + c["num_experts"] + d


def n_params(c: Dict[str, Any]) -> int:
    """Parameters at this depth: every layer by its kinds, the one table, the last norm."""
    d = c["hidden_size"]
    layers = sum(mixer_params(c, kind) + ffn_params(c, i) for i, kind in enumerate(layer_kinds(c)))
    tables = (1 if c["tie_embedding"] else 2) * c["vocab_size"] * d
    return layers + tables + d


def tail_bytes_per_sequence(c: Dict[str, Any], act_bytes: int = 2) -> int:
    """What a sequence carries beside its pages: the last ``L - 1`` inputs of every conv layer's convolution."""
    return conv_layers(c) * (c["conv_L_cache"] - 1) * c["hidden_size"] * act_bytes


def kv_bytes_per_token(c: Dict[str, Any], kv_bytes: int = 2) -> int:
    full = c["num_hidden_layers"] - conv_layers(c)
    return 2 * full * c["num_key_value_heads"] * head_dim(c) * kv_bytes


def conv_mixer_bytes(c: Dict[str, Any], rows: float, out_proj: bool = True, weight_bytes: int = 2,
                     act_bytes: int = 2) -> float:
    """Bytes one decode step's convolution mixers have to move for ``rows``
    live rows, whatever implements them: every conv layer's mixer weights once
    (both projections and the taps), and per live row and layer the input
    ``h`` (d), the 3d-wide projection written and read, the tail read and
    written (2 x (L - 1) x d), ``y`` (d) and the output (d).
    ``out_proj=False`` leaves the output projection out (its weights, ``y``
    and the output): what a reader that cannot tell that product from other
    layers' counts against the time of the rest."""
    d, L = c["hidden_size"], c["conv_L_cache"]
    weights = (d * 3 * d + L * d + out_proj * d * d) * weight_bytes
    per_row = (d + 2 * 3 * d + 2 * (L - 1) * d + out_proj * 2 * d) * act_bytes
    return conv_layers(c) * (weights + float(rows) * per_row)


def decode_step_bytes(c: Dict[str, Any], live_tokens: int, rows: int = 0, experts_hit: float = None,
                      weight_bytes: int = 2, kv_bytes: int = 2) -> float:
    """Bytes one decode step has to read and write: every weight but the input
    table's unused rows once (``experts_hit``: the (layer, expert) pairs that
    got a token; None: all), K and V of every live token in the full layers,
    and the conv layers' tails and vectors for ``rows`` live rows."""
    d = c["hidden_size"]
    moe_layers = c["num_hidden_layers"] - c["num_dense_layers"]
    pairs = moe_layers * c["num_experts"]
    unread = (pairs - (pairs if experts_hit is None else experts_hit)) * expert_params(c)
    weights = (n_params(c) - unread) * weight_bytes if c["tie_embedding"] else \
        (n_params(c) - unread - c["vocab_size"] * d) * weight_bytes
    acts = conv_mixer_bytes(c, rows) - conv_mixer_bytes(c, 0)
    return weights + kv_bytes_per_token(c, kv_bytes) * live_tokens + acts


# ---------------------------------------------------------------------------
# the program's config
# ---------------------------------------------------------------------------
def program_config(c: Dict[str, Any], **overrides):
    """``TransformerConfig`` for this file's sizes. ``overrides`` are the
    run's own choices (dtypes, sequence length)."""
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig

    L = c["num_hidden_layers"]
    if "conv_width" not in TransformerConfig.__dataclass_fields__:
        raise ValueError('this program has no "conv" layers (TransformerConfig has no conv_width): it cannot run '
                         "a configuration whose mixers are gated short convolutions")
    refused = {
        "conv_bias true": c.get("conv_bias", False),
        "use_expert_bias false (the router's selection bias is part of the function)": not c.get("use_expert_bias", True),
        "a layer_types entry other than conv / full_attention, or fewer entries than layers":
            len(c["layer_types"]) < L or any(t not in KINDS for t in c["layer_types"]),
        "num_dense_layers outside [0, num_hidden_layers)": not 0 <= c["num_dense_layers"] < L,
        "rope_scaling": c.get("rope_scaling") is not None,
        "tie_embedding false (the family's head is its input table)": not c.get("tie_embedding", True),
    }
    bad = [k for k, v in refused.items() if v]
    if bad:
        raise ValueError("the program cannot honour: " + "; ".join(bad))
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    kw = dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_layers=L, n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=head_dim(c), d_ff=c["intermediate_size"],
        max_seq_len=c["max_position_embeddings"], rope_theta=float(c["rope_theta"]), norm_eps=float(c["norm_eps"]),
        embed_scale=1.0, tie_embeddings=True, qk_norm=True,
        layer_types=tuple(KINDS[t] for t in c["layer_types"][:L]), conv_width=c["conv_L_cache"],
        num_experts=c["num_experts"], expert_top_k=c["num_experts_per_tok"], num_dense_layers=c["num_dense_layers"],
        expert_d_ff=c["moe_intermediate_size"], router_score="sigmoid", route_norm=bool(c["norm_topk_prob"]),
        route_scale=float(c["routed_scaling_factor"]), router_bias=True, route_norm_eps=ROUTE_NORM_EPS,
    )
    kw.update(overrides)
    for key in ("dtype", "param_dtype"):
        if isinstance(kw.get(key), str):
            kw[key] = dtypes[kw[key]]
    return TransformerConfig(**kw)


def reference_layer(params, i: int, c: Dict[str, Any]):
    """Layer ``i`` of the program's parameter tree (its own types: the
    reference casts inside its layer functions, one layer at a time) under the
    reference's names: ``(mixer, ffn)``; an expert layer's ``ffn`` holds the
    whole stacks ``we1, we2, we3`` and the index ``at`` they are read at.

    The program keeps its mixers by the plan of its layer loop: the leading
    layers each a tree (``lead_layers``), the period's places each a stack
    ``[repeats, ...]`` (``period_layers``), the trailing layers each a tree
    (``tail_layers``); its FFNs in two stacks in layer order (``dense_ffn``,
    ``expert_ffn``). The plan is read from the tree itself."""
    import jax

    lead, period = len(params["lead_layers"]), len(params["period_layers"])
    repeats = jax.tree.leaves(params["period_layers"][0])[0].shape[0] if period else 0
    if i < lead:
        m = params["lead_layers"][i]
    elif i < lead + period * repeats:
        r, j = divmod(i - lead, period)
        m = {name: leaf[r] for name, leaf in params["period_layers"][j].items()}
    else:
        m = params["tail_layers"][i - lead - period * repeats]
    d = c["hidden_size"]
    if c["layer_types"][i] == "conv":
        mixer = {"operator_norm": m["attn_norm"], "in_proj": m["conv_in"], "conv": m["conv_w"], "out_proj": m["conv_out"]}
    else:
        mixer = {"operator_norm": m["attn_norm"], "q_proj": m["wq"].reshape(d, -1), "k_proj": m["wk"].reshape(d, -1),
                 "v_proj": m["wv"].reshape(d, -1), "out_proj": m["wo"].reshape(-1, d),
                 "q_layernorm": m["q_norm"], "k_layernorm": m["k_norm"]}
    nd = c["num_dense_layers"]
    if i < nd:
        f = {name: leaf[i] for name, leaf in params["dense_ffn"].items()}
        ffn = {"ffn_norm": f["ffn_norm"], "w1": f["w3"], "w3": f["w1"], "w2": f["w2"]}   # the program's w3 is the gate
    else:
        s = params["expert_ffn"]
        ffn = {"ffn_norm": s["ffn_norm"][i - nd], "gate": s["router"][i - nd], "expert_bias": s["router_bias"][i - nd],
               "we1": s["we3"], "we3": s["we1"], "we2": s["we2"], "at": i - nd}               # the program's we3 is the gate
    return mixer, ffn


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
_QUERY_BLOCK = 512    # attention takes this many queries at a time
_EXPERT_BLOCK = 8     # experts whose weights are alive in float32 at a time
_VOCAB_SLICE = 8192   # the head this many rows of its table


def _rms_norm(x, gain, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _rope(x, theta):
    """Rotary embedding over the whole head, half-split. x: [T, H, dh]."""
    import jax.numpy as jnp

    T, _, dh = x.shape
    half = dh // 2
    inv_freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _ref_conv_mixer(x, w, *, L, eps, length):
    """A conv layer's mixer branch on one sequence. x: [T, d] float32.
    Returns (``x + mixer``, ``u`` of the last ``L - 1`` positions before
    ``length``: what a sequence carries after ``length`` tokens; zeros where
    the sequence is shorter)."""
    import jax
    import jax.numpy as jnp

    T, d = x.shape
    h = _rms_norm(x, w["operator_norm"], eps)
    b, cgate, xg = jnp.split(h @ w["in_proj"], 3, axis=-1)
    u = b * xg
    padded = jnp.concatenate([jnp.zeros((L - 1, d), u.dtype), u], axis=0)   # u_s = 0 for s < 0
    conv = sum(w["conv"][j] * padded[j : j + T] for j in range(L))          # c_t = sum_j w_j u_{t-(L-1)+j}
    tail = jax.lax.dynamic_slice_in_dim(padded, length, L - 1, axis=0)      # u_{length-L+1} .. u_{length-1}
    return x + (cgate * conv) @ w["out_proj"], tail


def _ref_attention_mixer(x, w, *, n_heads, n_kv_heads, theta, eps):
    """A full-attention layer's mixer branch on one sequence. x: [T, d] float32."""
    import jax
    import jax.numpy as jnp

    T = x.shape[0]
    dh = w["q_proj"].shape[1] // n_heads
    h = _rms_norm(x, w["operator_norm"], eps)
    q = _rope(_rms_norm((h @ w["q_proj"]).reshape(T, n_heads, dh), w["q_layernorm"], eps), theta)
    k = _rope(_rms_norm((h @ w["k_proj"]).reshape(T, n_kv_heads, dh), w["k_layernorm"], eps), theta)
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
    return x + jnp.concatenate(blocks, axis=0).reshape(T, n_heads * dh) @ w["out_proj"]


def _ref_route(h, gate, bias, chosen=None, *, top_k, scale, renormalize):
    """[T, E] float32: a token's weight for each expert, zero unless chosen;
    and the selection [T, k]. ``chosen``: a selection given from outside
    instead of the router's own (the weights are still this router's scores)."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(h @ gate)
    if chosen is None:
        _, chosen = jax.lax.top_k(s + bias, top_k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if renormalize:
        picked = picked / (picked.sum(-1, keepdims=True) + ROUTE_NORM_EPS)
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(picked * scale), chosen


def make_reference(c: Dict[str, Any]):
    """Returns ``logits(params, tokens[T], positions=None) -> [T or P, V]`` and
    ``loss(params, tokens[B, T]) -> scalar`` (next-token cross entropy, mean
    over the B * (T - 1) predicted positions), both float32 at "highest"
    matmul precision, one layer's weights float32 at a time and of an expert
    layer one block of experts'.

    ``logits(..., tails_after=n)`` also returns every conv layer's tail after
    the first ``n`` tokens, ``[conv layers, L - 1, d]``: what a program's
    per-sequence state is held to. ``logits(..., on_router=f)``: ``f(layer, h,
    gate, bias)`` is shown each expert layer's float32 input and router; what
    it returns, if anything, is the selection ``[T, k]`` that layer uses
    instead of its own."""
    import jax
    import jax.numpy as jnp

    L, eps, nd = int(c["conv_L_cache"]), float(c["norm_eps"]), c["num_dense_layers"]
    kinds = layer_kinds(c)
    attn_kw = dict(n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
                   theta=float(c["rope_theta"]), eps=eps)
    route_kw = dict(top_k=c["num_experts_per_tok"], scale=float(c["routed_scaling_factor"]),
                    renormalize=bool(c["norm_topk_prob"]))
    f32 = lambda w: jax.tree.map(lambda a: a.astype(jnp.float32), w)  # noqa: E731

    def highest(fn, **jit_kw):
        def run(*args, **kw):
            with jax.default_matmul_precision("highest"):
                return fn(*args, **kw)

        return jax.jit(run, **jit_kw)

    embed = highest(lambda table, tokens: table[tokens].astype(jnp.float32))
    conv_mixer = highest(lambda x, w, length: _ref_conv_mixer(x, f32(w), L=L, eps=eps, length=length))
    attention_mixer = highest(lambda x, w: _ref_attention_mixer(x, f32(w), **attn_kw))
    ffn_input = highest(lambda x, gain: _rms_norm(x, gain.astype(jnp.float32), eps))
    dense_ffn = highest(lambda x, h, w: x + (jax.nn.silu(h @ w["w1"].astype(jnp.float32))
                                             * (h @ w["w3"].astype(jnp.float32))) @ w["w2"].astype(jnp.float32))
    routing = highest(lambda h, gate, bias, chosen=None: _ref_route(
        h, gate.astype(jnp.float32), bias.astype(jnp.float32), chosen, **route_kw))
    last_norm = highest(lambda x, gain: _rms_norm(x, gain.astype(jnp.float32), eps))
    head_slice = highest(lambda xn, rows: xn @ rows.astype(jnp.float32).T)

    @highest
    def expert_block(h, weights, w1, w3, w2):
        """sum over this block's experts e of weights[:, e] * W2_e(silu(W1_e h) * W3_e h)."""
        w1, w3, w2 = (a.astype(jnp.float32) for a in (w1, w3, w2))
        a = jax.nn.silu(jnp.einsum("td,edf->etf", h, w1)) * jnp.einsum("td,edf->etf", h, w3)
        return jnp.einsum("etf,efd->td", a * weights.T[:, :, None], w2)

    def expert_ffn(h, weights, ffn):
        """An expert layer's branch on its normed input ``h`` [T, d] at the
        tokens' ``weights`` [T, E] (zero unless chosen), a block of experts at a time."""
        at, out = ffn["at"], 0.0
        for e in range(0, c["num_experts"], _EXPERT_BLOCK):
            sl = slice(e, e + _EXPERT_BLOCK)
            out = out + expert_block(h, weights[:, sl], ffn["we1"][at, sl], ffn["we3"][at, sl], ffn["we2"][at, sl])
        return out

    def hidden(params, tokens, tails_after=None, on_router=None):
        """Final hidden states [T, d] of one sequence, before the last norm, and the conv layers' tails."""
        x = embed(params["embed"], tokens)
        length = jnp.int32(tokens.shape[0] if tails_after is None else tails_after)
        tails = []
        for i, kind in enumerate(kinds):
            mixer, ffn = reference_layer(params, i, c)
            if kind == "conv":
                x, tail = conv_mixer(x, mixer, length)
                tails.append(tail)
            else:
                x = attention_mixer(x, mixer)
            h = ffn_input(x, ffn["ffn_norm"])
            if i < nd:
                x = dense_ffn(x, h, {k: ffn[k] for k in ("w1", "w3", "w2")})
                continue
            chosen = on_router(i, h, ffn["gate"], ffn["expert_bias"]) if on_router is not None else None
            weights, _ = routing(h, ffn["gate"], ffn["expert_bias"], chosen)
            x = x + expert_ffn(h, weights, ffn)
        return x, tails

    def head(params, x):
        xn = last_norm(x, params["final_norm"])
        table = params["embed"]
        return jnp.concatenate([head_slice(xn, table[s : s + _VOCAB_SLICE])
                                for s in range(0, table.shape[0], _VOCAB_SLICE)], axis=-1)

    def logits(params, tokens, positions=None, tails_after=None, on_router=None):
        x, tails = hidden(params, tokens, tails_after, on_router)
        if positions is not None:
            x = x[positions]
        out = head(params, x)
        return out if tails_after is None else (out, jnp.stack(tails))

    def loss(params, tokens):
        B, T = tokens.shape
        total = 0.0
        for b in range(B):
            x, _ = hidden(params, tokens[b])
            for s in range(0, T - 1, _QUERY_BLOCK):
                e = min(s + _QUERY_BLOCK, T - 1)
                logp = jax.nn.log_softmax(head(params, x[s:e]), axis=-1)
                total += float(-jnp.take_along_axis(logp, tokens[b, s + 1 : e + 1, None], axis=-1).sum())
        return total / (B * (T - 1))

    logits.route = routing   # the reference's router alone: what a program's router function is held to
    logits.expert_ffn = expert_ffn   # an expert layer's branch alone: what a program's expert layer is held to
    return logits, loss
