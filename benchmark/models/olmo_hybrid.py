"""Olmo Hybrid (``model_type: olmo_hybrid``; allenai/Olmo-Hybrid-7B): a dense
decoder whose token mixers are, three layers of four, Gated DeltaNet (a
recurrent state a head instead of keys and values) and, every fourth, full
softmax attention.

Three things, all keyed by the configuration file's own (HuggingFace) names:

* :func:`program_config` — the sizes and switches as
  ``ray_tpu.models.TransformerConfig`` takes them (the only place the
  benchmark names the program's fields); it refuses a file whose keys the
  program cannot honour;
* the plain reference: the forward pass in straightforward float32
  ``jax.numpy`` under ``jax.default_matmul_precision("highest")`` — no kernel,
  no cache, no chunking, no batching, nothing imported from ``ray_tpu``. The
  recurrence is a ``lax.scan`` over single tokens; attention runs in query
  blocks and the head in vocabulary slices, and one layer's weights are
  float32 at a time, so it fits beside the engine;
* the arithmetic: parameters, the bytes a decode step reads, and the bytes
  the linear layers' state update has to move (:func:`state_update_bytes`).

The equations (``d`` hidden, ``H`` heads of the linear layers with key size
``dk`` and value size ``dv``; ``Hq`` = ``Hkv`` heads of ``Dh`` in the full
layers). The catalog's keys give the sizes; what they do not give is listed
under ``assumed`` in the configuration file (the published model code as
recalled: no network here), and the reference and the program follow the file:

    x_0 = E_in[token]                                                   (unscaled)
    both kinds:  x <- x + RMSNorm(mixer(x));  x <- x + RMSNorm(MLP(x))   (no norm on a branch's input)
                 MLP(x) = W_down(silu(W_gate x) * (W_up x))
    linear:  [q~; k~; v~] = [W_q; W_k; W_v] x;  each channel c_t = silu(sum_{j=0..3} w_j u_{t-3+j})  (no bias)
             per head  q_t = l2norm(q~_t) / sqrt(dk),  k_t = l2norm(k~_t),  v_t = v~_t
             beta_t = 2 sigmoid(W_b x_t)  (the 2: linear_allow_neg_eigval)
             g_t = -exp(A_log) softplus(W_a x_t + dt_bias),  alpha_t = exp(g_t)
             S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T,  S_{-1} = 0, float32
             o_t = S_t^T q_t;  y_t = RMSNorm_dv(o_t) * silu(W_g x_t);  mixer = W_o y_t
    full:    q = RMSNorm(W_q x), k = RMSNorm(W_k x) with gains over the whole projection, v = W_v x,
             no rotary embedding (rope_theta null), causal softmax at 1 / sqrt(Dh), mixer = W_o a
    logits = RMSNorm_f(x_L) W_head^T, W_head a matrix of its own

``l2norm(x) = x / sqrt(sum x^2 + 1e-6)``. Departures of this file from the
published code are the ``assumed`` entries and nothing else: the program's
parameter tree keeps the ``j``-th linear layers of all periods as one stack, a
projection as ``[d, heads, size]`` and the convolution
as ``[width, channels]`` where the published layer has ``[channels, 1,
width]``; :func:`reference_layer` flattens the former and reads the latter as
it lies (tap ``j`` weighs the input ``3 - j`` steps back, as above).
"""

from __future__ import annotations

import math
from typing import Any, Dict

KINDS = {"linear_attention": "linear", "full_attention": "full"}


# ---------------------------------------------------------------------------
# arithmetic (pure Python: usable without jax)
# ---------------------------------------------------------------------------
def _sizes(c: Dict[str, Any]):
    d, ff = c["hidden_size"], c["intermediate_size"]
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    dh = c.get("head_dim") or d // hq
    H, dk, dv = c["linear_num_value_heads"], c["linear_key_head_dim"], c["linear_value_head_dim"]
    return d, ff, hq, hkv, dh, H, dk, dv


def linear_layers(c: Dict[str, Any]) -> int:
    return sum(1 for t in c["layer_types"][: c["num_hidden_layers"]] if t == "linear_attention")


def conv_channels(c: Dict[str, Any]) -> int:
    _, _, _, _, _, H, dk, dv = _sizes(c)
    return H * (2 * dk + dv)


def layer_params(c: Dict[str, Any], kind: str) -> int:
    """Parameters of one layer of ``kind``: the mixer, the MLP, the two branch norms."""
    d, ff, hq, hkv, dh, H, dk, dv = _sizes(c)
    mlp = 3 * d * ff + 2 * d
    if kind == "linear_attention":
        mixer = 2 * d * H * dk + 2 * d * H * dv + H * dv * d + 2 * d * H + 2 * H \
            + conv_channels(c) * c["linear_conv_kernel_dim"] + dv
    else:
        mixer = d * hq * dh + 2 * d * hkv * dh + hq * dh * d + hq * dh + hkv * dh
    return mixer + mlp


def n_params(c: Dict[str, Any]) -> int:
    """Parameters at this depth: every layer by its kind, both tables, the last norm."""
    d = c["hidden_size"]
    layers = sum(layer_params(c, t) for t in c["layer_types"][: c["num_hidden_layers"]])
    tables = (1 if c["tie_word_embeddings"] else 2) * c["vocab_size"] * d
    return layers + tables + d


def state_bytes_per_sequence(c: Dict[str, Any], act_bytes: int = 2) -> int:
    """What a sequence carries beside its pages: the float32 state of every
    linear layer and the convolution's last ``width - 1`` inputs."""
    _, _, _, _, _, H, dk, dv = _sizes(c)
    tail = (c["linear_conv_kernel_dim"] - 1) * conv_channels(c) * act_bytes
    return linear_layers(c) * (H * dk * dv * 4 + tail)


def state_update_bytes(c: Dict[str, Any], rows: float, act_bytes: int = 2) -> float:
    """Bytes one decode step's linear layers have to move for ``rows`` live
    rows, whatever implements them: per row and layer the float32 state read
    and written, the convolution tail read and written, q, k, v and the
    output in float32, and the two gates."""
    _, _, _, _, _, H, dk, dv = _sizes(c)
    state = 2 * H * dk * dv * 4
    tail = 2 * (c["linear_conv_kernel_dim"] - 1) * conv_channels(c) * act_bytes
    vectors = (2 * H * dk + 2 * H * dv + 2 * H) * 4
    return float(rows) * linear_layers(c) * (state + tail + vectors)


def decode_step_bytes(c: Dict[str, Any], live_tokens: int, rows: int = 0, weight_bytes: int = 2,
                      kv_bytes: int = 2) -> float:
    """Bytes one decode step has to read and write: every weight but the input
    table once, K and V of every live token in the full layers, and the
    linear layers' state update for ``rows`` live rows."""
    d, _, _, hkv, dh, _, _, _ = _sizes(c)
    full = c["num_hidden_layers"] - linear_layers(c)
    kv = 2 * full * hkv * dh * live_tokens * kv_bytes
    return (n_params(c) - c["vocab_size"] * d) * weight_bytes + kv + state_update_bytes(c, rows)


# ---------------------------------------------------------------------------
# the program's config
# ---------------------------------------------------------------------------
def program_config(c: Dict[str, Any], **overrides):
    """``TransformerConfig`` for this file's sizes. ``overrides`` are the
    run's own choices (dtypes, sequence length)."""
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig

    refused = {
        "tie_word_embeddings true (the family's head is a matrix of its own)": c.get("tie_word_embeddings", False),
        "hidden_act other than silu": c.get("hidden_act", "silu") != "silu",
        "attention_bias": c.get("attention_bias", False),
        "linear_num_key_heads != linear_num_value_heads": c["linear_num_key_heads"] != c["linear_num_value_heads"],
        "a rope_theta (the full layers carry no rotary embedding)":
            (c.get("rope_parameters") or {}).get("rope_theta") is not None,
        "a layer_types entry other than linear_attention / full_attention":
            any(t not in KINDS for t in c["layer_types"]),
    }
    bad = [k for k, v in refused.items() if v]
    if bad:
        raise ValueError("the program cannot honour: " + "; ".join(bad))
    d, ff, hq, hkv, dh, H, dk, dv = _sizes(c)
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    kw = dict(
        vocab_size=c["vocab_size"], d_model=d, n_layers=c["num_hidden_layers"], n_heads=hq, n_kv_heads=hkv,
        head_dim=dh, d_ff=ff, max_seq_len=c["max_position_embeddings"], norm_eps=float(c["rms_norm_eps"]),
        embed_scale=1.0, tie_embeddings=False, qk_norm=True, qk_norm_whole=True, pre_norms=False, post_norms=True,
        layer_types=tuple(KINDS[t] for t in c["layer_types"][: c["num_hidden_layers"]]), rope_full_layers=False,
        linear_heads=H, linear_key_dim=dk, linear_value_dim=dv, linear_conv_width=c["linear_conv_kernel_dim"],
        linear_allow_neg_eigval=bool(c["linear_allow_neg_eigval"]),
    )
    kw.update(overrides)
    for key in ("dtype", "param_dtype"):
        if isinstance(kw.get(key), str):
            kw[key] = dtypes[kw[key]]
    return TransformerConfig(**kw)


def reference_layer(params, i: int, kinds):
    """Layer ``i`` of the program's parameter tree (its own types: the
    reference casts inside its layer function, one layer at a time) under
    the reference's names, with ``kinds`` the file's ``layer_types``."""
    k = kinds.index("full_attention")  # linear layers a period
    p, j = divmod(i, k + 1)
    if kinds[i] == "linear_attention":
        L = {name: leaf[p] for name, leaf in params["linear_layers"][j].items()}
        d = L["lin_wq"].shape[0]
        return {
            "q_proj": L["lin_wq"].reshape(d, -1), "k_proj": L["lin_wk"].reshape(d, -1),
            "v_proj": L["lin_wv"].reshape(d, -1), "g_proj": L["lin_wg"].reshape(d, -1),
            "o_proj": L["lin_wo"].reshape(-1, d), "a_proj": L["lin_wa"], "b_proj": L["lin_wb"],
            "A_log": L["A_log"], "dt_bias": L["dt_bias"], "conv": L["conv_w"], "o_norm": L["o_norm"],
            "post_mixer_norm": L["post_attn_norm"], "post_mlp_norm": L["post_ffn_norm"],
            "gate_proj": L["w3"], "up_proj": L["w1"], "down_proj": L["w2"],
        }
    L = {name: leaf[p] for name, leaf in params["layers"].items()}
    d = L["wq"].shape[0]
    return {
        "q_proj": L["wq"].reshape(d, -1), "k_proj": L["wk"].reshape(d, -1), "v_proj": L["wv"].reshape(d, -1),
        "o_proj": L["wo"].reshape(-1, d), "q_norm": L["q_norm"], "k_norm": L["k_norm"],
        "post_mixer_norm": L["post_attn_norm"], "post_mlp_norm": L["post_ffn_norm"],
        "gate_proj": L["w3"], "up_proj": L["w1"], "down_proj": L["w2"],
    }


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
_QUERY_BLOCK = 256     # attention takes this many queries at a time
_VOCAB_SLICE = 8192    # the head this many rows of its table


def _rms_norm(x, gain, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _mlp(x, w):
    import jax

    return (jax.nn.silu(x @ w["gate_proj"]) * (x @ w["up_proj"])) @ w["down_proj"]


def _l2norm(x):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _ref_mlp_branch(x, w, eps):
    """The second branch of either layer kind: ``x + RMSNorm(MLP(x))``."""
    return x + _rms_norm(_mlp(x, w), w["post_mlp_norm"], eps)


def _ref_linear_mixer(x, w, *, H, dk, dv, neg_eigval, eps, length=None):
    """The first branch of a Gated DeltaNet layer on one sequence. x: [T, d]
    float32. Returns (``x + RMSNorm(mixer(x))``, the state [H, dk, dv] after
    the last token, or after ``length`` tokens where the rest of ``x`` is
    padding)."""
    import jax
    import jax.numpy as jnp

    T = x.shape[0]
    K = w["conv"].shape[0]
    u = jnp.concatenate([x @ w["q_proj"], x @ w["k_proj"], x @ w["v_proj"]], axis=-1)     # [T, channels]
    padded = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), u.dtype), u], axis=0)
    c = jax.nn.silu(sum(w["conv"][j] * padded[j : j + T] for j in range(K)))
    q, k, v = c[:, : H * dk], c[:, H * dk : 2 * H * dk], c[:, 2 * H * dk :]
    q = _l2norm(q.reshape(T, H, dk)) / math.sqrt(dk)
    k = _l2norm(k.reshape(T, H, dk))
    v = v.reshape(T, H, dv)
    beta = jax.nn.sigmoid(x @ w["b_proj"]) * (2.0 if neg_eigval else 1.0)                  # [T, H]
    alpha = jnp.exp(-jnp.exp(w["A_log"]) * jax.nn.softplus(x @ w["a_proj"] + w["dt_bias"]))

    def token(S, xs):
        q_t, k_t, v_t, a_t, b_t = xs
        S = S * a_t[:, None, None]
        written = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
        S = S + k_t[:, :, None] * written[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    if length is not None:  # the state after ``length`` tokens: what follows them writes nothing
        beta = jnp.where((jnp.arange(T) < length)[:, None], beta, 0.0)
        alpha = jnp.where((jnp.arange(T) < length)[:, None], alpha, 1.0)
    S, o = jax.lax.scan(token, jnp.zeros((H, dk, dv), jnp.float32), (q, k, v, alpha, beta))  # [T, H, dv]
    y = _rms_norm(o, w["o_norm"], eps) * jax.nn.silu((x @ w["g_proj"]).reshape(T, H, dv))
    return x + _rms_norm(y.reshape(T, H * dv) @ w["o_proj"], w["post_mixer_norm"], eps), S


def _ref_full_mixer(x, w, *, n_heads, n_kv_heads, eps):
    """The first branch of a full-attention layer on one sequence. x: [T, d] float32."""
    import jax
    import jax.numpy as jnp

    T = x.shape[0]
    dh = w["q_proj"].shape[1] // n_heads
    q = _rms_norm(x @ w["q_proj"], w["q_norm"], eps).reshape(T, n_heads, dh)
    k = _rms_norm(x @ w["k_proj"], w["k_norm"], eps).reshape(T, n_kv_heads, dh)
    v = (x @ w["v_proj"]).reshape(T, n_kv_heads, dh)
    rep = n_heads // n_kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    blocks = []
    for start in range(0, T, _QUERY_BLOCK):
        qb = q[start : start + _QUERY_BLOCK]
        s = jnp.einsum("thd,shd->hts", qb, k) / math.sqrt(dh)
        visible = jnp.arange(T)[None, :] <= (start + jnp.arange(qb.shape[0]))[:, None]
        p = jax.nn.softmax(jnp.where(visible[None], s, -jnp.inf), axis=-1)
        blocks.append(jnp.einsum("hts,shd->thd", p, v))
    a = jnp.concatenate(blocks, axis=0).reshape(T, n_heads * dh)
    return x + _rms_norm(a @ w["o_proj"], w["post_mixer_norm"], eps)


def make_reference(c: Dict[str, Any]):
    """Returns ``logits(params, tokens[T], positions=None) -> [T or P, V]`` and
    ``loss(params, tokens[B, T]) -> scalar`` (next-token cross entropy, mean
    over the B * (T - 1) predicted positions), both float32 at "highest"
    matmul precision, one branch's float32 weights alive at a time (a
    layer's mixer and its MLP are programs of their own: the reference runs
    beside an engine that fills the chip).
    ``logits(..., states_after=n)`` also returns every linear layer's state
    after the first ``n`` tokens, ``[linear layers, H, dk, dv]``: what a
    program's per-sequence state is held to."""
    import jax
    import jax.numpy as jnp

    d, ff, hq, hkv, dh, H, dk, dv = _sizes(c)
    eps = float(c["rms_norm_eps"])
    kinds = list(c["layer_types"][: c["num_hidden_layers"]])
    f32 = lambda w: jax.tree.map(lambda a: a.astype(jnp.float32), w)  # noqa: E731

    @jax.jit
    def embed(table, tokens):
        return table[tokens].astype(jnp.float32)

    MLP = ("gate_proj", "up_proj", "down_proj", "post_mlp_norm")

    @jax.jit
    def linear_mixer(x, w, length):
        with jax.default_matmul_precision("highest"):
            return _ref_linear_mixer(x, f32(w), H=H, dk=dk, dv=dv, neg_eigval=bool(c["linear_allow_neg_eigval"]),
                                     eps=eps, length=length)

    @jax.jit
    def full_mixer(x, w):
        with jax.default_matmul_precision("highest"):
            return _ref_full_mixer(x, f32(w), n_heads=hq, n_kv_heads=hkv, eps=eps)

    @jax.jit
    def mlp_branch(x, w):
        with jax.default_matmul_precision("highest"):
            return _ref_mlp_branch(x, f32(w), eps)

    @jax.jit
    def normed(x, gain):
        return _rms_norm(x, gain.astype(jnp.float32), eps)

    @jax.jit
    def head_slice(xn, rows):
        with jax.default_matmul_precision("highest"):
            return xn @ rows.astype(jnp.float32).T

    def head(x, gain, table):
        xn = normed(x, gain)
        V = table.shape[0]
        return jnp.concatenate([head_slice(xn, table[s : s + _VOCAB_SLICE]) for s in range(0, V, _VOCAB_SLICE)], axis=-1)

    def hidden(params, tokens, states_after=None):
        """Final hidden states [T, d] of one sequence, before the last norm, and the linear layers' states."""
        x = embed(params["embed"], tokens)
        states = []
        length = jnp.int32(tokens.shape[0] if states_after is None else states_after)
        for i, kind in enumerate(kinds):
            w = reference_layer(params, i, kinds)
            mixer = {k: v for k, v in w.items() if k not in MLP}
            if kind == "linear_attention":
                x, S = linear_mixer(x, mixer, length)
                states.append(S)
            else:
                x = full_mixer(x, mixer)
            x = mlp_branch(x, {k: w[k] for k in MLP})
        return x, states

    def logits(params, tokens, positions=None, states_after=None):
        x, states = hidden(params, tokens, states_after)
        if positions is not None:
            x = x[positions]
        out = head(x, params["final_norm"], params["head"])
        return out if states_after is None else (out, jnp.stack(states))

    def loss(params, tokens):
        B, T = tokens.shape
        total = 0.0
        for b in range(B):
            x, _ = hidden(params, tokens[b])
            for s in range(0, T - 1, _QUERY_BLOCK):
                e = min(s + _QUERY_BLOCK, T - 1)
                logp = jax.nn.log_softmax(head(x[s:e], params["final_norm"], params["head"]), axis=-1)
                total += float(-jnp.take_along_axis(logp, tokens[b, s + 1 : e + 1, None], axis=-1).sum())
        return total / (B * (T - 1))

    return logits, loss
