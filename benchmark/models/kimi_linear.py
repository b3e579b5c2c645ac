"""Kimi Linear (``model_type: kimi_linear``; moonshotai/Kimi-Linear-48B-A3B-Instruct):
a decoder whose token mixers are, three layers of four, Kimi Delta Attention
(the gated delta rule with a decay **a key channel**: a recurrent state a head
instead of keys and values) and, every fourth, multi-head latent attention
without rotary embedding (one latent row a token is all that is cached); whose
first layer's FFN is a dense SwiGLU and every other a routed expert layer with
one shared expert.

Three things, all keyed by the configuration file's own (HuggingFace) names:

* :func:`program_config` — the sizes and switches as
  ``ray_tpu.models.TransformerConfig`` takes them (the only place the
  benchmark names the program's fields); it refuses a file whose keys the
  program cannot honour;
* the plain reference: the forward pass in straightforward float32
  ``jax.numpy`` under ``jax.default_matmul_precision("highest")`` — no kernel,
  no cache, no chunking, no batching, no absorption, nothing imported from
  ``ray_tpu``. The recurrence is a ``lax.scan`` over single tokens; latent
  attention is the **published, expanded** form (every head's keys and values
  expanded from the latents) in query blocks; the experts are a loop over the
  experts held, one expert's weights float32 at a time; the head runs in
  vocabulary slices: it fits beside an engine that fills the chip;
* the arithmetic: parameters, the bytes a decode step reads, the bytes the KDA
  layers' state update has to move (:func:`state_update_bytes`), and the bytes
  and FLOPs of latent attention over the cached tokens (:func:`latent_attn_work`).

The equations (``d`` hidden; ``h = RMSNorm(x)`` a branch's input, eps
``rms_norm_eps``). The catalog's keys give the sizes; what they do not give is
listed under ``assumed`` in the configuration file (the published model code
as recalled: no network here), and the reference and the program follow the
file:

    x_0 = E_in[token]                                                  (unscaled)
    every layer:  x <- x + mixer(RMSNorm(x));  x <- x + ffn(RMSNorm(x));  logits = RMSNorm_f(x_L) W_head^T
    KDA (H heads, dk = dv = head_dim, r = head_dim):
        [q~; k~; v~] = [W_q; W_k; W_v] h;  each channel c_t = silu(sum_{j=0..3} w_j u_{t-3+j})   (no bias)
        per head  q_t = l2norm(q~_t) / sqrt(dk),  k_t = l2norm(k~_t),  v_t = v~_t
        beta_t = sigmoid(W_b h_t)                                      (a scalar a head)
        g_t = -exp(A_log)[head] softplus(W_fb (W_fa h_t) + dt_bias)    in R^{H x dk};  alpha_t = exp(g_t)
        S' = Diag(alpha_t) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T;  S_{-1} = 0, float32
        o_t = S_t^T q_t;  y_t = RMSNorm_dv(o_t) * sigmoid(W_gb (W_ga h_t));  mixer = W_o y_t
    MLA (H heads, latent r = kv_lora_rank, nope, rope, value sizes; mla_use_nope: nothing is rotated):
        q = W_q h -> [H, nope + rope];  [c~; k_pe] = W_kva h;  c = RMSNorm_r(c~)
        [k_nope; v] = W_kvb c  a head;  k_head = [k_nope_head; k_pe]  (k_pe shared by every head)
        causal softmax at 1 / sqrt(nope + rope);  mixer = W_o concat(o_head)
    expert FFN (layers after the first first_k_dense_replace):
        s = sigmoid(W_r h) in float32 over all the experts;  the chosen: top num_experts_per_token of s + b
        w_e = routed_scaling_factor * s_e / sum_{chosen} s;  E(h) = W_down(silu(W_gate h) * (W_up h))
        y = sum_{chosen, lo <= e < hi} w_e E_e(h) + E_shared(h)
    the first layer's FFN: E(h) of width intermediate_size

**The share.** The file's ``experts_held`` ``[lo, hi)`` of ``experts_routed``
is what this chip of the deployment holds: the router scores, chooses and
normalises over all ``experts_routed``; the terms of experts outside the
range are left out, here and in the program alike, and that partial result
goes on to the next layer (on one chip nothing stands in for the exchange).

``l2norm(x) = x / sqrt(sum x^2 + 1e-6)``. Departures of this file from the
published code are the ``assumed`` entries and nothing else: the program's
parameter tree keeps the ``j``-th KDA layers of all periods as one stack, the
FFNs as stacks of their own, a projection as ``[d, heads, size]`` and the
convolution as ``[width, channels]``; :func:`reference_layer` flattens the
former and reads the latter as it lies (tap ``j`` weighs the input ``3 - j``
steps back, as above).
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple


# ---------------------------------------------------------------------------
# arithmetic (pure Python: usable without jax)
# ---------------------------------------------------------------------------
def layer_kinds(c: Dict[str, Any]) -> List[str]:
    """``"kda"`` or ``"mla"`` for each of the file's layers (its lists count from 1)."""
    lac = c["linear_attn_config"]
    kda, full = set(lac["kda_layers"]), set(lac["full_attn_layers"])
    kinds = []
    for i in range(1, c["num_hidden_layers"] + 1):
        if (i in kda) == (i in full):
            raise ValueError(f"layer {i} must be in exactly one of kda_layers and full_attn_layers")
        kinds.append("kda" if i in kda else "mla")
    return kinds


def held(c: Dict[str, Any]) -> Tuple[int, int]:
    """The experts this chip holds, ``[lo, hi)`` of ``experts_routed`` (all of ``num_experts`` by default)."""
    lo, hi = c.get("experts_held") or (0, c["num_experts"])
    return int(lo), int(hi)


def routed(c: Dict[str, Any]) -> int:
    return int(c.get("experts_routed") or c["num_experts"])


def _kda(c):
    lac = c["linear_attn_config"]
    return lac["num_heads"], lac["head_dim"], lac["head_dim"], lac["short_conv_kernel_size"]


def conv_channels(c: Dict[str, Any]) -> int:
    H, dk, dv, _ = _kda(c)
    return H * (2 * dk + dv)


def linear_layers(c: Dict[str, Any]) -> int:
    return layer_kinds(c).count("kda")


def latent_layers(c: Dict[str, Any]) -> int:
    return layer_kinds(c).count("mla")


def mixer_params(c: Dict[str, Any], kind: str) -> int:
    d = c["hidden_size"]
    if kind == "kda":
        H, dk, dv, K = _kda(c)
        r = dv  # the gates' rank: the head size, as published
        return (2 * d * H * dk + d * H * dv + H * dv * d + conv_channels(c) * K + d * H      # q, k, v, o, conv, beta
                + d * r + r * H * dk + H * dk + H                                          # decay: W_fa, W_fb, dt_bias, A_log
                + d * r + r * H * dv + dv + d)                                             # output gate, o_norm, branch norm
    Hq, r = c["num_attention_heads"], c["kv_lora_rank"]
    nope, rope, v = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    return d * Hq * (nope + rope) + d * (r + rope) + r + r * Hq * (nope + v) + Hq * v * d + d


def expert_params(c: Dict[str, Any]) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def ffn_params(c: Dict[str, Any], layer: int) -> int:
    """Parameters of layer ``layer``'s FFN as this chip holds it (the branch's norm included)."""
    d = c["hidden_size"]
    if layer < c["first_k_dense_replace"]:
        return 3 * d * c["intermediate_size"] + d
    lo, hi = held(c)
    return ((hi - lo) + c["num_shared_experts"]) * expert_params(c) + d * routed(c) + routed(c) + d


def n_params(c: Dict[str, Any], experts: bool = True) -> int:
    """Parameters this chip holds at this depth: every layer's mixer by its
    kind, its FFN (the experts held; ``experts=False``: without the routed
    experts), both tables, the last norm."""
    d = c["hidden_size"]
    total = (1 if c["tie_word_embeddings"] else 2) * c["vocab_size"] * d + d
    lo, hi = held(c)
    for i, kind in enumerate(layer_kinds(c)):
        total += mixer_params(c, kind) + ffn_params(c, i)
        if not experts and i >= c["first_k_dense_replace"]:
            total -= (hi - lo) * expert_params(c)
    return total


def state_bytes_per_sequence(c: Dict[str, Any], act_bytes: int = 2) -> int:
    """What a sequence carries beside its latent pages: the float32 state of
    every KDA layer and the convolution's last ``width - 1`` inputs."""
    H, dk, dv, K = _kda(c)
    return linear_layers(c) * (H * dk * dv * 4 + (K - 1) * conv_channels(c) * act_bytes)


def state_update_bytes(c: Dict[str, Any], rows: float, act_bytes: int = 2) -> float:
    """Bytes one decode step's KDA layers have to move for ``rows`` live rows,
    whatever implements them: per row and layer the float32 state read and
    written, the convolution tail read and written, q, k, v and the output in
    float32, the decay a key channel and the writing strength a head."""
    H, dk, dv, K = _kda(c)
    state = 2 * H * dk * dv * 4
    tail = 2 * (K - 1) * conv_channels(c) * act_bytes
    vectors = (2 * H * dk + 2 * H * dv + H * dk + H) * 4
    return float(rows) * linear_layers(c) * (state + tail + vectors)


def latent_attn_work(c: Dict[str, Any], live_tokens: float, rows: float, act_bytes: int = 2) -> Tuple[float, float]:
    """(bytes, FLOPs) of one decode step's latent attention over
    ``live_tokens`` cached tokens (summed over the ``rows`` live rows),
    whatever implements it, all MLA layers: a cached token is its
    ``kv_lora_rank + qk_rope_head_dim`` numbers read once, and for every head
    one dot product over them (the score) and one weighted sum over the
    latent (the value); each row also reads its heads' absorbed queries and
    writes their latent outputs."""
    r, rope, H = c["kv_lora_rank"], c["qk_rope_head_dim"], c["num_attention_heads"]
    layers = latent_layers(c)
    bytes_ = layers * (live_tokens * (r + rope) + rows * H * (2 * r + rope)) * act_bytes
    flops = layers * live_tokens * 2.0 * H * ((r + rope) + r)
    return float(bytes_), float(flops)


def decode_step_bytes(c: Dict[str, Any], live_tokens: int, rows: int = 0, experts_hit: float = None,
                      weight_bytes: int = 2, kv_bytes: int = 2) -> float:
    """Bytes one decode step has to read and write: every weight but the
    input table and the routed experts once, the routed experts that got a
    row (``experts_hit`` (layer, expert) pairs; all held by default), the
    latent rows of every live token, and the KDA state update."""
    d = c["hidden_size"]
    lo, hi = held(c)
    expert_layers = c["num_hidden_layers"] - c["first_k_dense_replace"]
    hit = (hi - lo) * expert_layers if experts_hit is None else experts_hit
    weights = (n_params(c, experts=False) - c["vocab_size"] * d + hit * expert_params(c)) * weight_bytes
    return weights + latent_attn_work(c, live_tokens, rows, kv_bytes)[0] + state_update_bytes(c, rows)


# ---------------------------------------------------------------------------
# the program's config
# ---------------------------------------------------------------------------
def program_config(c: Dict[str, Any], **overrides):
    """``TransformerConfig`` for this file's sizes. ``overrides`` are the
    run's own choices (dtypes, sequence length)."""
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig

    lo, hi = held(c)
    refused = {
        "tie_word_embeddings true (the family's head is a matrix of its own)": c.get("tie_word_embeddings", False),
        "hidden_act other than silu": c.get("hidden_act", "silu") != "silu",
        "a q_lora_rank (the queries' projection is full rank here)": c.get("q_lora_rank") is not None,
        "mla_use_nope false (the latent layers rotate nothing)": not c.get("mla_use_nope", False),
        "expert groups (num_expert_group, topk_group other than 1)":
            c.get("num_expert_group", 1) != 1 or c.get("topk_group", 1) != 1,
        "moe_layer_freq other than 1": c.get("moe_layer_freq", 1) != 1,
        "num_nextn_predict_layers": c.get("num_nextn_predict_layers", 0) != 0,
        "a router activation other than sigmoid or softmax":
            c["moe_router_activation_func"] not in ("sigmoid", "softmax"),
        "experts_held that is not num_experts experts of experts_routed":
            hi - lo != c["num_experts"] or not 0 <= lo < hi <= routed(c),
    }
    bad = [k for k, v in refused.items() if v]
    if bad:
        raise ValueError("the program cannot honour: " + "; ".join(bad))
    H, dk, dv, K = _kda(c)
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    kw = dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_layers=c["num_hidden_layers"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        max_seq_len=c["model_max_length"], norm_eps=float(c["rms_norm_eps"]), embed_scale=1.0, tie_embeddings=False,
        layer_types=tuple({"kda": "linear", "mla": "latent"}[k] for k in layer_kinds(c)), rope_full_layers=False,
        linear_heads=H, linear_key_dim=dk, linear_value_dim=dv, linear_conv_width=K,
        linear_gate="channel", linear_gate_rank=dv, linear_out_gate="sigmoid",
        latent_rank=c["kv_lora_rank"], latent_nope_dim=c["qk_nope_head_dim"], latent_rope_dim=c["qk_rope_head_dim"],
        latent_value_dim=c["v_head_dim"],
        num_experts=routed(c), expert_top_k=c["num_experts_per_token"], num_dense_layers=c["first_k_dense_replace"],
        expert_d_ff=c["moe_intermediate_size"], num_shared_experts=c["num_shared_experts"],
        router_score=c["moe_router_activation_func"], route_norm=bool(c["moe_renormalize"]),
        route_scale=float(c["routed_scaling_factor"]), router_bias=True,
        experts_held=None if (lo, hi) == (0, routed(c)) else (lo, hi),
    )
    kw.update(overrides)
    for key in ("dtype", "param_dtype"):
        if isinstance(kw.get(key), str):
            kw[key] = dtypes[kw[key]]
    return TransformerConfig(**kw)


def reference_layer(params, i: int, c: Dict[str, Any]):
    """Layer ``i`` of the program's parameter tree under the reference's
    names: ``(kind, mixer, ffn)``. The routed experts' weights are handed over
    as the whole stacks with the layer's index in them (``ffn["experts"]``):
    the reference reads one expert at a time where they lie."""
    kinds = layer_kinds(c)
    k = kinds.index("mla")  # KDA layers a period
    p, j = divmod(i, k + 1)
    if kinds[i] == "kda":
        L = {name: leaf[p] for name, leaf in params["linear_layers"][j].items()}
        d = L["lin_wq"].shape[0]
        flat = lambda a: a.reshape(a.shape[0], -1)  # noqa: E731
        mixer = {"q_proj": flat(L["lin_wq"]), "k_proj": flat(L["lin_wk"]), "v_proj": flat(L["lin_wv"]),
                 "o_proj": L["lin_wo"].reshape(-1, d), "b_proj": L["lin_wb"],
                 "f_a_proj": L["lin_wfa"], "f_b_proj": flat(L["lin_wfb"]), "dt_bias": L["dt_bias"].reshape(-1),
                 "A_log": L["A_log"], "g_a_proj": L["lin_wga"], "g_b_proj": flat(L["lin_wgb"]),
                 "conv": L["conv_w"], "o_norm": L["o_norm"], "input_norm": L["attn_norm"]}
    else:
        L = {name: leaf[p] for name, leaf in params["layers"].items()}
        d = L["lat_wq"].shape[0]
        mixer = {"q_proj": L["lat_wq"].reshape(d, -1), "kv_a_proj": L["lat_wkva"], "kv_a_norm": L["lat_norm"],
                 "kv_b_proj": L["lat_wkvb"].reshape(L["lat_wkvb"].shape[0], -1), "o_proj": L["lat_wo"].reshape(-1, d),
                 "input_norm": L["attn_norm"]}
    nd = c["first_k_dense_replace"]
    if i < nd:
        F = {name: leaf[i] for name, leaf in params["dense_ffn"].items()}
        ffn = {"gate_proj": F["w3"], "up_proj": F["w1"], "down_proj": F["w2"], "post_norm": F["ffn_norm"]}
    else:
        S = params["expert_ffn"]
        F = {name: leaf[i - nd] for name, leaf in S.items() if name not in ("we1", "we3", "we2")}
        ffn = {"router": F["router"], "bias": F["router_bias"], "post_norm": F["ffn_norm"],
               "shared_gate": F["ws3"], "shared_up": F["ws1"], "shared_down": F["ws2"],
               "experts": {"gate": S["we3"], "up": S["we1"], "down": S["we2"], "layer": i - nd}}
    return kinds[i], mixer, ffn


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
_QUERY_BLOCK = 128     # attention takes this many queries at a time
_VOCAB_SLICE = 8192    # the head this many rows of its table


def _rms_norm(x, gain, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _mlp(x, gate, up, down):
    import jax

    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _l2norm(x):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _ref_kda_mixer(x, w, *, H, dk, dv, eps, length=None):
    """The first branch of a KDA layer on one sequence. x: [T, d] float32.
    Returns (``x + mixer(RMSNorm(x))``, the state [H, dk, dv] after the last
    token, or after ``length`` tokens where the rest of ``x`` is padding)."""
    import jax
    import jax.numpy as jnp

    T = x.shape[0]
    K = w["conv"].shape[0]
    h = _rms_norm(x, w["input_norm"], eps)
    u = jnp.concatenate([h @ w["q_proj"], h @ w["k_proj"], h @ w["v_proj"]], axis=-1)     # [T, channels]
    padded = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), u.dtype), u], axis=0)
    c = jax.nn.silu(sum(w["conv"][j] * padded[j : j + T] for j in range(K)))
    q, k, v = c[:, : H * dk], c[:, H * dk : 2 * H * dk], c[:, 2 * H * dk :]
    q = _l2norm(q.reshape(T, H, dk)) / math.sqrt(dk)
    k = _l2norm(k.reshape(T, H, dk))
    v = v.reshape(T, H, dv)
    beta = jax.nn.sigmoid(h @ w["b_proj"])                                                 # [T, H]
    gate = ((h @ w["f_a_proj"]) @ w["f_b_proj"] + w["dt_bias"]).reshape(T, H, dk)
    alpha = jnp.exp(-jnp.exp(w["A_log"])[None, :, None] * jax.nn.softplus(gate))           # [T, H, dk]

    def token(S, xs):
        q_t, k_t, v_t, a_t, b_t = xs
        S = S * a_t[:, :, None]                                       # Diag(alpha_t) S: a row by its own channel's decay
        written = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
        S = S + k_t[:, :, None] * written[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    if length is not None:  # the state after ``length`` tokens: what follows them writes nothing
        real = jnp.arange(T) < length
        beta = jnp.where(real[:, None], beta, 0.0)
        alpha = jnp.where(real[:, None, None], alpha, 1.0)
    S, o = jax.lax.scan(token, jnp.zeros((H, dk, dv), jnp.float32), (q, k, v, alpha, beta))  # [T, H, dv]
    y = _rms_norm(o, w["o_norm"], eps) * jax.nn.sigmoid(((h @ w["g_a_proj"]) @ w["g_b_proj"]).reshape(T, H, dv))
    return x + y.reshape(T, H * dv) @ w["o_proj"], S


def _ref_mla_mixer(x, w, *, H, r, nope, rope, dv, eps):
    """The first branch of a latent attention layer on one sequence, in the
    expanded form: every head's keys and values from the latents. x: [T, d].
    Returns (``x + mixer(RMSNorm(x))``, the rows a cache of this layer would
    hold: ``[T, r + rope]`` = ``(c, k_pe)``)."""
    import jax
    import jax.numpy as jnp

    T = x.shape[0]
    h = _rms_norm(x, w["input_norm"], eps)
    ckv = h @ w["kv_a_proj"]
    c, k_pe = _rms_norm(ckv[:, :r], w["kv_a_norm"], eps), ckv[:, r:]
    kv = (c @ w["kv_b_proj"]).reshape(T, H, nope + dv)                 # every head's k_nope and v, from the latents
    k_nope, v = kv[..., :nope], kv[..., nope:]
    blocks = -(-T // _QUERY_BLOCK)
    hq = jnp.pad(h, ((0, blocks * _QUERY_BLOCK - T), (0, 0))).reshape(blocks, _QUERY_BLOCK, -1)

    def block(args):  # one block of queries against every key (a loop, not unrolled: one block is compiled)
        i, hb = args
        qb = (hb @ w["q_proj"]).reshape(_QUERY_BLOCK, H, nope + rope)
        # k_head = [k_nope_head; k_pe]: the score is the two parts' sum (k_pe is every head's, so it is not copied out)
        s = (jnp.einsum("thd,shd->hts", qb[..., :nope], k_nope) + jnp.einsum("thd,sd->hts", qb[..., nope:], k_pe))
        s = s / math.sqrt(nope + rope)
        visible = jnp.arange(T)[None, :] <= (i * _QUERY_BLOCK + jnp.arange(_QUERY_BLOCK))[:, None]
        p = jax.nn.softmax(jnp.where(visible[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", p, v)

    a = jax.lax.map(block, (jnp.arange(blocks), hq)).reshape(blocks * _QUERY_BLOCK, H * dv)[:T]
    return x + a @ w["o_proj"], jnp.concatenate([c, k_pe], axis=-1)


def _ref_route(h, router, bias, *, top_k, scale, renormalize, score):
    """(experts int32[T, k], weights f32[T, k]): the top ``k`` of score + bias, weighed by their scores."""
    import jax
    import jax.numpy as jnp

    logits = h @ router
    s = jax.nn.sigmoid(logits) if score == "sigmoid" else jax.nn.softmax(logits, axis=-1)
    _, chosen = jax.lax.top_k(s + bias, top_k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if renormalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * scale


def make_reference(c: Dict[str, Any]):
    """Returns ``logits(params, tokens[T], positions=None) -> [T or P, V]`` and
    ``loss(params, tokens[B, T]) -> scalar`` (next-token cross entropy, mean
    over the B * (T - 1) predicted positions), both float32 at "highest"
    matmul precision, one branch's float32 weights alive at a time (of the
    routed experts, one expert's).
    ``logits(..., states_after=n)`` also returns every KDA layer's state
    after the first ``n`` tokens, ``[KDA layers, H, dk, dv]``: what a
    program's per-sequence state is held to; ``logits(..., latent_rows=True)``
    the rows a latent cache would hold, ``[MLA layers, T, r + rope]``: what a
    program's latent pool is held to."""
    import jax
    import jax.numpy as jnp

    H, dk, dv, _ = _kda(c)
    eps = float(c["rms_norm_eps"])
    lo, hi = held(c)
    f32 = lambda w: jax.tree.map(lambda a: a.astype(jnp.float32), w)  # noqa: E731

    @jax.jit
    def embed(table, tokens):
        return table[tokens].astype(jnp.float32)

    @jax.jit
    def kda_mixer(x, w, length):
        with jax.default_matmul_precision("highest"):
            return _ref_kda_mixer(x, f32(w), H=H, dk=dk, dv=dv, eps=eps, length=length)

    @jax.jit
    def mla_mixer(x, w):
        with jax.default_matmul_precision("highest"):
            return _ref_mla_mixer(x, f32(w), H=c["num_attention_heads"], r=c["kv_lora_rank"],
                                  nope=c["qk_nope_head_dim"], rope=c["qk_rope_head_dim"], dv=c["v_head_dim"], eps=eps)

    @jax.jit
    def dense_branch(x, w):
        with jax.default_matmul_precision("highest"):
            w = f32(w)
            return x + _mlp(_rms_norm(x, w["post_norm"], eps), w["gate_proj"], w["up_proj"], w["down_proj"])

    @jax.jit
    def expert_branch(x, w, gate, up, down, layer):
        """``x + sum_{chosen, held} w_e E_e(h) + E_shared(h)``; gate, up, down: the whole stacks ``[L, held, ..]``."""
        with jax.default_matmul_precision("highest"):
            w = f32(w)
            h = _rms_norm(x, w["post_norm"], eps)
            chosen, weights = _ref_route(h, w["router"], w["bias"], top_k=c["num_experts_per_token"],
                                         scale=float(c["routed_scaling_factor"]), renormalize=bool(c["moe_renormalize"]),
                                         score=c["moe_router_activation_func"])

            def one(e, y):  # expert lo + e, for the tokens that chose it
                mine = jnp.sum(jnp.where(chosen == lo + e, weights, 0.0), axis=-1, keepdims=True)   # [T, 1]
                return y + mine * _mlp(h, gate[layer, e].astype(jnp.float32), up[layer, e].astype(jnp.float32),
                                       down[layer, e].astype(jnp.float32))

            y = jax.lax.fori_loop(0, hi - lo, one, jnp.zeros_like(x))
            return x + y + _mlp(h, w["shared_gate"], w["shared_up"], w["shared_down"])

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
        """Final hidden states [T, d] of one sequence, before the last norm, the KDA layers' states
        and the MLA layers' cached rows."""
        x = embed(params["embed"], tokens)
        states, rows = [], []
        length = jnp.int32(tokens.shape[0] if states_after is None else states_after)
        for i in range(c["num_hidden_layers"]):
            kind, mixer, ffn = reference_layer(params, i, c)
            if kind == "kda":
                x, S = kda_mixer(x, mixer, length)
                states.append(S)
            else:
                x, row = mla_mixer(x, mixer)
                rows.append(row)
            if "experts" in ffn:
                e = ffn.pop("experts")
                x = expert_branch(x, ffn, e["gate"], e["up"], e["down"], jnp.int32(e["layer"]))
            else:
                x = dense_branch(x, ffn)
        return x, states, rows

    def logits(params, tokens, positions=None, states_after=None, latent_rows=False):
        x, states, rows = hidden(params, tokens, states_after)
        if positions is not None:
            x = x[positions]
        out = head(x, params["final_norm"], params["head"])
        if latent_rows:
            return out, jnp.stack(rows)
        return out if states_after is None else (out, jnp.stack(states))

    def loss(params, tokens):
        B, T = tokens.shape
        total = 0.0
        for b in range(B):
            x = hidden(params, tokens[b])[0]
            for s in range(0, T - 1, _QUERY_BLOCK):
                e = min(s + _QUERY_BLOCK, T - 1)
                logp = jax.nn.log_softmax(head(x[s:e], params["final_norm"], params["head"]), axis=-1)
                total += float(-jnp.take_along_axis(logp, tokens[b, s + 1 : e + 1, None], axis=-1).sum())
        return total / (B * (T - 1))

    return logits, loss
