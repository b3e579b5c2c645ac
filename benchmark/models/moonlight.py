"""Moonlight (``model_type: deepseek_v3``; moonshotai/Moonlight-16B-A3B): a
decoder of multi-head latent attention in every layer, the shared key part
and each query's last ``qk_rope_head_dim`` numbers rotated; the first layer's
FFN a dense SwiGLU, every other a routed expert layer (sigmoid scores, a
per-expert bias that selects and never weighs, ``topk_method: noaux_tc``) with
shared experts. Here it is **trained**.

Three things, all keyed by the configuration file's own (HuggingFace) names:

* :func:`program_config` — the sizes and switches as
  ``ray_tpu.models.TransformerConfig`` takes them (the only place the
  benchmark names the program's fields); it refuses a file whose keys the
  program cannot honour;
* the plain reference (:func:`make_reference`): forward, loss **and
  gradients** in straightforward float32 ``jax.numpy`` under
  ``jax.default_matmul_precision("highest")`` — no kernel, no sort, no
  grouped product, nothing imported from ``ray_tpu``. Attention is the
  published, expanded form in query blocks; the experts held run one at a
  time over every token, weighed by a mask; the gradient is taken a layer at
  a time (``jax.vjp`` of one layer, its attention blocks and experts under
  ``jax.checkpoint``) and the head in slices of positions, so that it fits
  beside a train state that fills the chip; and the load rule
  (:func:`bias_step`);
* the arithmetic: parameters, the FLOPs a token needs
  (:func:`train_flops_per_token`), the grouped products' and the flash
  kernels' (:func:`routed_ffn_flops`, :func:`latent_flash_flops`).

The equations (``d`` hidden, ``h = RMSNorm(x)`` a branch's input, eps
``rms_norm_eps``):

    x_0 = E_in[token]                                                  (unscaled)
    every layer:  x <- x + Latent(RMSNorm(x));  x <- x + FFN(RMSNorm(x));  logits = RMSNorm_f(x_L) W_head^T
    Latent, H heads:  q = W_q h -> [H, nope + rope] = (q_n, q_r);  [c~; k~_r] = W_kva h;  l = RMSNorm_r(c~)
        k_r = rot(k~_r, t), one for all heads;  q_r <- rot(q_r, t);  (k_n, v) = W_kvb l  a head
        scores ([q_n; q_r] . [k_n; k_r]) / sqrt(nope + rope), causal softmax;  o = P v;  out = W_o concat(o_head)
    rot(u, t): pairs (u[2i], u[2i+1]) turned by t * rope_theta^(-2i / rope)  (the published code's
        de-interleave-then-rotate-halves; the result's order is irrelevant to q . k)
    layer < first_k_dense_replace:  FFN(h) = W_down(silu(W_gate h) * (W_up h)), width intermediate_size
    else:  s = sigmoid(W_r h) in float32 over all n_routed experts;  chosen: the num_experts_per_tok largest of s + b
        w_e = routed_scaling_factor * s_e / sum_{chosen} s   (norm_topk_prob);  E(h) of width moe_intermediate_size
        FFN(h) = sum_{chosen, lo <= e < hi} w_e E_e(h) + E_shared(h),  the shared one of n_shared x that width
    loss: mean next-token cross entropy over the vocabulary rows held
    after the optimizer's update, each expert layer:  b_i <- b_i + gamma * sign(mean(n) - n_i),
        n the step's assignments a routed expert over all of them; b is no leaf of the optimizer

**The share.** The file's ``experts_held`` ``[lo, hi)`` of ``experts_routed``
is what this chip of the deployment holds: the router scores, chooses and
normalises over all ``experts_routed``; the terms of experts outside the range
are left out, here and in the program alike, and that partial result goes on
to the next layer. ``vocab_size`` is the rows of both tables held: ids, logits
and loss are over them.

Departures and assumptions are the configuration file's ``departures`` and
``assumed`` and nothing else.
"""

from __future__ import annotations

import math
import types
from typing import Any, Callable, Dict, Optional, Tuple

# ---------------------------------------------------------------------------
# arithmetic (pure Python: usable without jax)
# ---------------------------------------------------------------------------


def held(c: Dict[str, Any]) -> Tuple[int, int]:
    lo, hi = c.get("experts_held") or (0, c["n_routed_experts"])
    return int(lo), int(hi)


def routed(c: Dict[str, Any]) -> int:
    """Experts the routers score: the published count, whatever share is held."""
    return int(c.get("experts_routed", c["n_routed_experts"]))


def expert_layers(c: Dict[str, Any]) -> int:
    return c["num_hidden_layers"] - c["first_k_dense_replace"]


def mixer_params(c: Dict[str, Any]) -> int:
    d, H, r = c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"]
    nope, rope, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    return d * H * (nope + rope) + d * (r + rope) + r * H * (nope + dv) + H * dv * d


def expert_params(c: Dict[str, Any]) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def n_params(c: Dict[str, Any]) -> int:
    """Matrix parameters of the tree held (norm gains and the bias left out)."""
    d = c["hidden_size"]
    lo, hi = held(c)
    dense = c["first_k_dense_replace"] * (mixer_params(c) + 3 * d * c["intermediate_size"])
    expert = expert_layers(c) * (mixer_params(c) + (hi - lo + c["n_shared_experts"]) * expert_params(c) + d * routed(c))
    return dense + expert + 2 * c["vocab_size"] * d


def latent_flash_flops(c: Dict[str, Any], seq_len: int, sequences: int = 1) -> float:
    """FLOPs causal attention needs forward and backward in ONE layer: of
    each (query, visible key) pair a product over ``nope + rope`` and one
    over ``v_head_dim`` forward, twice that backward; half of ``T x T``
    pairs are visible. (The flash backward recomputes the scores on top:
    not counted, so the share stays under what the MXU did.)"""
    per_pair = 2.0 * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"])
    return 3.0 * per_pair * c["num_attention_heads"] * sequences * seq_len * seq_len / 2.0


def routed_ffn_flops(c: Dict[str, Any], rows: float) -> float:
    """FLOPs of the grouped products over ``rows`` assignments to experts
    held: three projections, forward and the two backward products each."""
    return 3.0 * 3.0 * 2.0 * c["hidden_size"] * c["moe_intermediate_size"] * rows


def train_flops_per_token(c: Dict[str, Any], seq_len: int) -> float:
    """FLOPs the forward and backward passes need per token, recomputation
    not counted: 6 a matmul parameter a token sees (the mixers, the dense
    FFN, the routers, the shared experts, the output head; the input table is
    a lookup; of the routed experts the rows the held ones are EXPECTED to
    get under even routing, ``num_experts_per_tok x held / routed`` experts a
    token) plus the causal half of attention (:func:`latent_flash_flops`)."""
    d, L = c["hidden_size"], c["num_hidden_layers"]
    lo, hi = held(c)
    per_token_experts = c["num_experts_per_tok"] * (hi - lo) / routed(c)
    dense = c["first_k_dense_replace"] * (mixer_params(c) + 3 * d * c["intermediate_size"])
    expert = expert_layers(c) * (mixer_params(c) + d * routed(c)
                                 + (c["n_shared_experts"] + per_token_experts) * expert_params(c))
    return 6.0 * (dense + expert + c["vocab_size"] * d) + L * latent_flash_flops(c, seq_len) / seq_len


def bias_step(bias, counts, gamma: float):
    """The load rule on numpy arrays ``[layers, experts]``: the bias after a
    step whose assignments were ``counts``."""
    import numpy as np

    n = np.asarray(counts, np.float64)
    return np.asarray(bias, np.float32) + np.float32(gamma) * np.sign(n.mean(axis=-1, keepdims=True) - n).astype(np.float32)


def warmup_rate(run: Dict[str, Any], step: int) -> float:
    """The learning rate of 1-based ``step`` under the run's linear warm-up:
    ``learning_rate x min(1, step / warmup_steps)`` (no warm-up: the rate)."""
    warmup = int(run.get("warmup_steps", 0))
    return float(run["learning_rate"]) * (min(1.0, step / warmup) if warmup else 1.0)


ADAMW = {"b1": 0.9, "b2": 0.999, "eps": 1e-8, "weight_decay": 1e-4}   # the program's (optax.adamw's defaults)


def adamw_first_step(p, g, rate: float):
    """One leaf after AdamW's FIRST step from zero moments, float32, written
    out: ``m = (1 - b1) g``, ``v = (1 - b2) g^2``, both divided by ``1 - b^1``,
    ``p - rate x (m^ / (sqrt(v^) + eps) + weight_decay x p)``."""
    import jax.numpy as jnp

    p, g = p.astype(jnp.float32), g.astype(jnp.float32)
    b1, b2 = ADAMW["b1"], ADAMW["b2"]
    m_hat = ((1.0 - b1) * g) / (1.0 - b1)
    v_hat = ((1.0 - b2) * g * g) / (1.0 - b2)
    return p - rate * (m_hat / (jnp.sqrt(v_hat) + ADAMW["eps"]) + ADAMW["weight_decay"] * p)


# ---------------------------------------------------------------------------
# the program's config
# ---------------------------------------------------------------------------


def program_config(c: Dict[str, Any], **overrides):
    """``TransformerConfig`` for this file's sizes. ``overrides`` are the
    run's own choices (dtypes, sequence length, attention, remat)."""
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig

    lo, hi = held(c)
    refused = {
        "tie_word_embeddings true (the family's head is a matrix of its own)": c.get("tie_word_embeddings", False),
        "hidden_act other than silu": c.get("hidden_act", "silu") != "silu",
        "a q_lora_rank (the queries' projection is full rank here)": c.get("q_lora_rank") is not None,
        "rope_scaling (no mscale, no frequency change is built)": c.get("rope_scaling") is not None,
        "expert groups (n_group, topk_group other than 1)": c.get("n_group", 1) != 1 or c.get("topk_group", 1) != 1,
        "moe_layer_freq other than 1": c.get("moe_layer_freq", 1) != 1,
        "num_nextn_predict_layers": c.get("num_nextn_predict_layers", 0) != 0,
        "attention_bias": c.get("attention_bias", False),
        "a scoring_func other than sigmoid or softmax": c["scoring_func"] not in ("sigmoid", "softmax"),
        "a topk_method other than noaux_tc (the bias that selects)": c.get("topk_method") != "noaux_tc",
        "experts_held that is not n_routed_experts experts of experts_routed":
            hi - lo != c["n_routed_experts"] or not 0 <= lo < hi <= routed(c),
    }
    bad = [k for k, v in refused.items() if v]
    if bad:
        raise ValueError("the program cannot honour: " + "; ".join(bad))
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    kw = dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_layers=c["num_hidden_layers"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        max_seq_len=c["max_position_embeddings"], rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]), embed_scale=1.0, tie_embeddings=False,
        layer_types=("latent",) * c["num_hidden_layers"], rope_full_layers=True,
        latent_rank=c["kv_lora_rank"], latent_nope_dim=c["qk_nope_head_dim"], latent_rope_dim=c["qk_rope_head_dim"],
        latent_value_dim=c["v_head_dim"],
        num_experts=routed(c), expert_top_k=c["num_experts_per_tok"], num_dense_layers=c["first_k_dense_replace"],
        expert_d_ff=c["moe_intermediate_size"], num_shared_experts=c["n_shared_experts"],
        router_score=c["scoring_func"], route_norm=bool(c["norm_topk_prob"]),
        route_scale=float(c["routed_scaling_factor"]), router_bias=True,
        experts_held=None if (lo, hi) == (0, routed(c)) else (lo, hi),
    )
    kw.update(overrides)
    for key in ("dtype", "param_dtype"):
        if isinstance(kw.get(key), str):
            kw[key] = dtypes[kw[key]]
    return TransformerConfig(**kw)


# the program's leaf of each of the reference's matrices, a layer
LEAVES = {
    "input_layernorm": "attn_norm", "q_proj": "lat_wq", "kv_a_proj_with_mqa": "lat_wkva", "kv_a_layernorm": "lat_norm",
    "kv_b_proj": "lat_wkvb", "o_proj": "lat_wo", "post_attention_layernorm": "ffn_norm",
    "gate_proj": "w3", "up_proj": "w1", "down_proj": "w2",
    "router": "router", "experts_gate": "we3", "experts_up": "we1", "experts_down": "we2",
    "shared_gate": "ws3", "shared_up": "ws1", "shared_down": "ws2",
}


def layer_place(c: Dict[str, Any], i: int) -> Tuple[str, int]:
    """Where layer ``i`` lies in the program's tree: (stack, index)."""
    nd = c["first_k_dense_replace"]
    return ("dense_layers", i) if i < nd else ("layers", i - nd)


def reference_layer(params, i: int, c: Dict[str, Any]):
    """Layer ``i`` of the program's parameter tree in the reference's plain
    layout (float32, 2-D matrices; the experts held ``[held, ., .]``), and the
    selection bias beside it (a buffer, no parameter)."""
    import jax.numpy as jnp

    stack, j = layer_place(c, i)
    L = params[stack]
    d, r = c["hidden_size"], c["kv_lora_rank"]
    shapes = {"q_proj": (d, -1), "kv_b_proj": (r, -1), "o_proj": (-1, d)}
    w = {}
    for name, leaf in LEAVES.items():
        if leaf in L:
            a = L[leaf][j].astype(jnp.float32)
            w[name] = a.reshape(shapes[name]) if name in shapes else a
    bias = L["router_bias"][j].astype(jnp.float32) if "router_bias" in L else None
    return w, bias


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
_QUERY_BLOCK = 512  # attention, and the output head, take this many positions at a time

# what a control changes, each one thing (benchmark/tools/routed_train_control.py): the reference
# then computes ANOTHER function, and the comparison has to say so
CONTROLS = ("bf16_params", "bf16_router", "no_rotation", "values_scaled", "rotate_halves")
# ... and one that only the chip honours: every product's operands rounded to bfloat16 (a CPU multiplies in float32
# whatever precision is asked for): the reference in the precision below the one it states
CHIP_CONTROLS = ("bf16_products",)


def _rms_norm(x, gain, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _mlp(x, gate, up, down):
    import jax

    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _rot(u, theta, halves: bool = False):
    """u: [T, ..., n]. The published pairing: ``view(n/2, 2).transpose`` (the
    even numbers, then the odd), then ``u cos + rotate_half(u) sin``.
    ``halves`` (a control): the pairing without the de-interleave."""
    import jax.numpy as jnp

    T, n = u.shape[0], u.shape[-1]
    if not halves:
        u = jnp.concatenate([u[..., 0::2], u[..., 1::2]], axis=-1)
    inv_freq = 1.0 / (theta ** (jnp.arange(0, n, 2, dtype=jnp.float32) / n))
    ang = jnp.arange(T, dtype=jnp.float32).reshape(T, *(1,) * (u.ndim - 1)) * inv_freq
    cos, sin = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1), jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)
    turned = jnp.concatenate([-u[..., n // 2:], u[..., : n // 2]], axis=-1)
    return u * cos + turned * sin


def _latent(h, w, *, H, r, nope, rope, dv, theta, eps, control=None):
    """The latent mixer on one sequence. h: [T, d] float32 -> [T, d]."""
    import jax
    import jax.numpy as jnp

    T = h.shape[0]
    q = (h @ w["q_proj"]).reshape(T, H, nope + rope)
    c = h @ w["kv_a_proj_with_mqa"]
    lat = _rms_norm(c[:, :r], w["kv_a_layernorm"], eps)
    k_r, q_r = c[:, r:], q[..., nope:]
    if control != "no_rotation":
        k_r, q_r = (_rot(u, theta, halves=control == "rotate_halves") for u in (k_r, q_r))
    kv = (lat @ w["kv_b_proj"]).reshape(T, H, nope + dv)
    qf = jnp.concatenate([q[..., :nope], q_r], axis=-1)
    kf = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_r[:, None, :], (T, H, rope))], axis=-1)
    v = kv[..., nope:] * (1.25 if control == "values_scaled" else 1.0)
    scale = 1.0 / math.sqrt(nope + rope)

    @jax.checkpoint
    def block(qb, start):
        s = jnp.einsum("thd,shd->hts", qb, kf) * scale
        visible = jnp.arange(T)[None, :] <= (start + jnp.arange(qb.shape[0]))[:, None]
        return jnp.einsum("hts,shv->thv", jax.nn.softmax(jnp.where(visible[None], s, -jnp.inf), axis=-1), v)

    if T % _QUERY_BLOCK == 0 and T > _QUERY_BLOCK:   # whole blocks: a loop that is compiled once
        starts = jnp.arange(0, T, _QUERY_BLOCK)
        o = jax.lax.map(lambda a: block(*a), (qf.reshape(-1, _QUERY_BLOCK, H, nope + rope), starts)).reshape(T, H, dv)
    else:
        o = jnp.concatenate([block(qf[s: s + _QUERY_BLOCK], s) for s in range(0, T, _QUERY_BLOCK)], axis=0)
    return o.reshape(T, H * dv) @ w["o_proj"]


def _route(h, router, bias, *, top_k, scale, renormalize, score, control=None):
    """(chosen int[T, k], weights f32[T, k]) over all the experts the router scores."""
    import jax
    import jax.numpy as jnp

    if control == "bf16_router":
        logits = (h.astype(jnp.bfloat16) @ router.astype(jnp.bfloat16)).astype(jnp.float32)
    else:
        logits = h @ router
    s = jax.nn.sigmoid(logits) if score == "sigmoid" else jax.nn.softmax(logits, axis=-1)
    _, chosen = jax.lax.top_k(s + bias[None, :], top_k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if renormalize:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return chosen, w * scale


def _experts(h, w, bias, *, lo, hi, route_kw):
    """The routed + shared FFN on [T, d]: each expert held over every token,
    weighed by the mask of who chose it. Returns (out, counts int32[routed])."""
    import jax
    import jax.numpy as jnp

    chosen, weights = _route(h, w["router"], bias, **route_kw)

    @jax.checkpoint
    def one(y, expert):
        e, gate, up, down = expert
        weight = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)   # 0 for a token that did not choose e
        return y + weight[:, None] * _mlp(h, gate, up, down), None

    y = _mlp(h, w["shared_gate"], w["shared_up"], w["shared_down"])
    y, _ = jax.lax.scan(one, y, (jnp.arange(lo, hi), w["experts_gate"], w["experts_up"], w["experts_down"]))
    counts = jnp.zeros((w["router"].shape[1],), jnp.int32).at[chosen.reshape(-1)].add(1)
    return y, counts


def make_reference(c: Dict[str, Any], control: Optional[str] = None):
    """Returns an object with ``logits(params, tokens[T])``, ``loss(params,
    tokens[B, T])``, ``counts(params, tokens[T])`` (int32[expert layers,
    routed]) and ``loss_and_grads(params, tokens[B, T], sink)``: the loss, and
    every leaf's gradient handed to ``sink(stack, index or None, leaf,
    gradient)`` in the program's own shape as soon as its block is
    differentiated (a layer at a time, last to first; then the tables), each
    sequence's counts to ``counts_sink``.
    ``control`` (:data:`CONTROLS`) makes it another function, on purpose."""
    import jax
    import jax.numpy as jnp

    if control is not None and control not in CONTROLS + CHIP_CONTROLS:
        raise ValueError(f"control {control!r} is none of {CONTROLS + CHIP_CONTROLS}")
    eps = float(c["rms_norm_eps"])
    lo, hi = held(c)
    nd, n_layers = c["first_k_dense_replace"], c["num_hidden_layers"]
    mixer_kw = dict(H=c["num_attention_heads"], r=c["kv_lora_rank"], nope=c["qk_nope_head_dim"],
                    rope=c["qk_rope_head_dim"], dv=c["v_head_dim"], theta=float(c["rope_theta"]), eps=eps,
                    control=control)
    route_kw = dict(top_k=c["num_experts_per_tok"], scale=float(c["routed_scaling_factor"]),
                    renormalize=bool(c["norm_topk_prob"]), score=c["scoring_func"], control=control)

    precision = "bfloat16" if control == "bf16_products" else "highest"

    def rounded(w):
        if control != "bf16_params":
            return w
        return jax.tree.map(lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), w)

    def layer_fn(x, w, bias):
        """One decoder layer on one sequence [T, d]; (x, counts or None)."""
        with jax.default_matmul_precision(precision):
            w = rounded(w)
            x = x + _latent(_rms_norm(x, w["input_layernorm"], eps), w, **mixer_kw)
            h = _rms_norm(x, w["post_attention_layernorm"], eps)
            if bias is None:
                return x + _mlp(h, w["gate_proj"], w["up_proj"], w["down_proj"]), None
            y, counts = _experts(h, w, bias, lo=lo, hi=hi, route_kw=route_kw)
            return x + y, counts

    layer = jax.jit(layer_fn)

    @jax.jit
    def layer_vjp(x, w, bias, g):
        _, vjp = jax.vjp(lambda x, w: layer_fn(x, w, bias)[0], x, w)
        return vjp(g)

    def head_nll(x, gain, table, targets):
        """Summed cross entropy of one block of positions."""
        with jax.default_matmul_precision(precision):
            logits = _rms_norm(x, rounded(gain), eps) @ rounded(table).T
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, targets[:, None], axis=-1).sum()

    head_block = jax.jit(jax.value_and_grad(head_nll, argnums=(0, 1, 2)))

    @jax.jit
    def head_logits(x, gain, table):
        with jax.default_matmul_precision(precision):
            return _rms_norm(x, gain, eps) @ table.T

    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731

    def states(params, tokens):
        """The hidden states entering each layer and leaving the last, and the
        expert layers' counts, of one sequence."""
        xs, counts = [rounded(f32(params["embed"]))[tokens]], []
        for i in range(n_layers):
            x, n = layer(xs[-1], *reference_layer(params, i, c))
            xs.append(x)
            if n is not None:
                counts.append(n)
        return xs, counts

    def logits(params, tokens):
        return head_logits(states(params, tokens)[0][-1], f32(params["final_norm"]), f32(params["head"]))

    def counts(params, tokens):
        return jnp.stack(states(params, tokens)[1])

    def loss_and_grads(params, tokens, sink: Optional[Callable] = None, counts_sink: Optional[Callable] = None):
        B, T = tokens.shape
        scale = 1.0 / (B * (T - 1))
        gain, table = f32(params["final_norm"]), f32(params["head"])
        total = 0.0
        acc: Dict[Any, Any] = {}

        def add(key, g):
            acc[key] = g if key not in acc else acc[key] + g

        for b in range(B):
            xs, n = states(params, tokens[b])
            if counts_sink is not None:
                counts_sink(jnp.stack(n))
            dx = jnp.zeros_like(xs[-1])
            for s in range(0, T - 1, _QUERY_BLOCK):
                e = min(s + _QUERY_BLOCK, T - 1)
                nll, (gx, gg, gt) = head_block(xs[-1][s:e], gain, table, tokens[b, s + 1: e + 1])
                total += float(nll)
                if sink is not None:
                    dx = dx.at[s:e].set(gx * scale)
                    add(("final_norm", None), gg * scale)
                    add(("head", None), gt * scale)
            if sink is None:
                continue
            for i in reversed(range(n_layers)):
                w, bias = reference_layer(params, i, c)
                dx, dw = layer_vjp(xs[i], w, bias, dx)
                for name, g in dw.items():
                    if B == 1:   # one sequence: hand a layer's gradients on at once, so that only one layer's are alive
                        sink(*layer_place(c, i), LEAVES[name], g)
                    else:
                        add((*layer_place(c, i), LEAVES[name]), g)
                del dw
            add(("embed", None), jnp.zeros(params["embed"].shape, jnp.float32).at[tokens[b]].add(dx))
        if sink is not None:
            for key, g in acc.items():
                if len(key) == 2:
                    sink(key[0], None, None, g)
                else:
                    sink(*key, g)
        return total * scale

    def loss(params, tokens):
        return loss_and_grads(params, tokens, None)

    return types.SimpleNamespace(logits=logits, loss=loss, counts=counts, loss_and_grads=loss_and_grads)
