"""SDAR-MoE (JetLM SDAR: ``model_type: sdar_moe``; JetLM/SDAR-30B-A3B-Chat):
a Qwen3-MoE decoder that generates by diffusion over blocks.

Three things, all keyed by the configuration file's own (HuggingFace) names:

* :func:`program_config` — the sizes and switches as
  ``ray_tpu.models.TransformerConfig`` takes them (the only place the
  benchmark names the program's fields); it refuses a file whose keys the
  program cannot honour;
* the plain reference: the forward pass and the generation loop in
  straightforward float32 ``jax.numpy`` under
  ``jax.default_matmul_precision("highest")`` — no kernel, no cache, no
  batching, nothing imported from ``ray_tpu``. Attention runs in query
  blocks, the expert layer as a plain sum over experts in blocks of 16, the
  head in row slices, so it fits beside the engine;
* the arithmetic: parameters, and the bytes a block step's attention has to
  read (:func:`block_attention_bytes`).

The equations of one layer (d hidden, H query heads, Hkv KV heads, Dh head
size, Bk ``block_length``). The config's keys give the sizes; what they do
not give is the family's convention (the published model and generation
code as recalled: no network here) and is listed under ``assumed`` in the
configuration file:

    x_0 = E_in[token]                                              (unscaled)
    a = RMSNorm_in(x);  q = RMSNorm_q(a Wq), k = RMSNorm_k(a Wk) per head over Dh;  v = a Wv  (no biases)
    q, k = RoPE(q, k) (rotate-half, theta, absolute position) on every layer
    scores q k^T / sqrt(Dh); key j visible to query i iff floor(j / Bk) <= floor(i / Bk); softmax in float32
    x = x + (P v) Wo
    m = RMSNorm_post_attn(x);  s = softmax(m W_r) over all experts in float32;  S = the k largest;
        w_e = s_e / sum_{S} s  (norm_topk_prob);  x = x + sum_{e in S} w_e (silu(m W1_e) * (m W3_e)) W2_e
    logits = RMSNorm_f(x_L) W_head^T, W_head a matrix of its own; a masked
    position's own logits score that position's token (no shift)

Generation (``low_confidence_static``): the prompt's first floor(n / Bk) Bk
tokens are context; the n mod Bk left over open the first block as known
positions. A block starts as known positions and ``mask_token_id`` elsewhere.
Denoising step s of S: one forward of everything before the block plus the
block as it stands; at each masked position the candidate is the arg-max
over the vocabulary without the mask id, its confidence the softmax
probability of the candidate; the ceil(masked left / steps left) masked
positions of highest confidence (ties: lowest position) take their
candidates. When no mask is left the block's tokens are final and the next
block begins; generation stops after ``max_tokens`` new tokens and what the
last block holds beyond them is dropped.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional

# ---------------------------------------------------------------------------
# arithmetic (pure Python: usable without jax)
# ---------------------------------------------------------------------------


def n_params(c: Dict[str, Any]) -> int:
    """Parameters at this depth: attention, norms, router and experts of
    every layer, both tables, the last norm."""
    d, h, hkv, dh = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    attn = 2 * d * h * dh + 2 * d * hkv * dh
    norms = 2 * d + 2 * dh
    experts = c["num_experts"] * 3 * d * c["moe_intermediate_size"]
    layer = attn + norms + d * c["num_experts"] + experts
    tables = (1 if c["tie_word_embeddings"] else 2) * c["vocab_size"] * d
    return c["num_hidden_layers"] * layer + tables + d


def block_attention_bytes(c: Dict[str, Any], live_pages: float, rows: int, page_tokens: int = 16,
                          kv_bytes: int = 2, act_bytes: int = 2) -> float:
    """Bytes the attention of one block step has to read and write over all
    layers: K and V of every page some query of a live row sees (``live_pages``
    a layer, summed over the rows: the pages that hold a row's committed
    tokens and its block), once each, and the queries and outputs of ``rows``
    rows x ``block_length`` positions. It counts what is visible, not what an
    implementation chooses to read."""
    L, h, hkv, dh = c["num_hidden_layers"], c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    kv = live_pages * page_tokens * 2 * hkv * dh * kv_bytes
    qo = rows * int(c["block_length"]) * 2 * h * dh * act_bytes
    return L * (kv + qo)


# ---------------------------------------------------------------------------
# the program's config
# ---------------------------------------------------------------------------


def program_config(c: Dict[str, Any], **overrides):
    """``TransformerConfig`` for this file's sizes. ``overrides`` are the
    run's own choices (dtypes, sequence length, attention mode)."""
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig

    refused = {
        "hidden_act": c.get("hidden_act", "silu") != "silu",
        "rope_scaling": c.get("rope_scaling") is not None,
        "attention_bias": bool(c.get("attention_bias", False)),
        "use_sliding_window": bool(c.get("use_sliding_window", False)),
        "mlp_only_layers": bool(c.get("mlp_only_layers")),
        "decoder_sparse_step": c.get("decoder_sparse_step", 1) != 1,
        "norm_topk_prob": not c.get("norm_topk_prob", False),
        "block_length": int(c.get("block_length", 0)) < 2,
    }
    bad = sorted(k for k, v in refused.items() if v)
    if bad:
        raise ValueError(f"the program cannot honour this file's {bad}")
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    kw = dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_layers=c["num_hidden_layers"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], max_seq_len=c["max_position_embeddings"], rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]), embed_scale=1.0, tie_embeddings=bool(c["tie_word_embeddings"]),
        qk_norm=True, num_experts=c["num_experts"], expert_top_k=c["num_experts_per_tok"], num_dense_layers=0,
        expert_d_ff=c["moe_intermediate_size"], num_shared_experts=0, router_score="softmax", route_norm=True,
        block_length=int(c["block_length"]), mask_token_id=int(c["mask_token_id"]),
    )
    kw.update(overrides)
    for key in ("dtype", "param_dtype"):
        if isinstance(kw.get(key), str):
            kw[key] = dtypes[kw[key]]
    return TransformerConfig(**kw)


def reference_layer(params, i: int):
    """The non-expert weights of layer ``i`` of the program's parameter tree
    in the reference's plain layout (2-D float32 matrices, HuggingFace's
    names); the experts are read from ``params["layers"]`` at ``i``."""
    import jax.numpy as jnp

    stack = params["layers"]
    d = stack["wq"].shape[1]
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    return {
        "input_layernorm": f32(stack["attn_norm"][i]),
        "q_proj": f32(stack["wq"][i]).reshape(d, -1),
        "k_proj": f32(stack["wk"][i]).reshape(d, -1),
        "v_proj": f32(stack["wv"][i]).reshape(d, -1),
        "o_proj": f32(stack["wo"][i]).reshape(-1, d),
        "q_norm": f32(stack["q_norm"][i]),
        "k_norm": f32(stack["k_norm"][i]),
        "post_attention_layernorm": f32(stack["ffn_norm"][i]),
        "router": f32(stack["router"][i]),
    }


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


def _attention_branch(x, w, *, n_heads, n_kv_heads, theta, eps, block):
    """``x + (P v) Wo`` on one sequence under the block-causal mask. x: [T, d] float32."""
    import jax
    import jax.numpy as jnp

    T, _ = x.shape
    dh = w["q_proj"].shape[1] // n_heads
    a = _rms_norm(x, w["input_layernorm"], eps)
    q = _rms_norm((a @ w["q_proj"]).reshape(T, n_heads, dh), w["q_norm"], eps)
    k = _rms_norm((a @ w["k_proj"]).reshape(T, n_kv_heads, dh), w["k_norm"], eps)
    v = (a @ w["v_proj"]).reshape(T, n_kv_heads, dh)
    q, k = _rope(q, theta), _rope(k, theta)
    rep = n_heads // n_kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    blocks = []
    for start in range(0, T, _QUERY_BLOCK):
        qb = q[start : start + _QUERY_BLOCK]
        s = jnp.einsum("thd,shd->hts", qb, k) / math.sqrt(dh)
        i = (start + jnp.arange(qb.shape[0]))[:, None]
        j = jnp.arange(T)[None, :]
        visible = j // block <= i // block
        p = jax.nn.softmax(jnp.where(visible[None], s, -jnp.inf), axis=-1)
        blocks.append(jnp.einsum("hts,shd->thd", p, v))
    o = jnp.concatenate(blocks, axis=0).reshape(T, n_heads * dh)
    return x + o @ w["o_proj"]


def _route(m, w, chosen=None, *, k):
    """[T, E] float32: a token's weight for each expert, zero unless chosen:
    softmax scores over all experts, the k largest, re-normed over the
    chosen. ``chosen`` [T, k]: a selection given from outside instead of the
    router's own (the weights are still this router's scores)."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.softmax(m @ w["router"], axis=-1)
    if chosen is None:
        _, chosen = jax.lax.top_k(s, k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    picked = picked / picked.sum(-1, keepdims=True)
    rows = jnp.arange(m.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(picked)


def make_reference(c: Dict[str, Any]):
    """Returns ``logits(params, tokens[T], positions=None, on_router=None) ->
    [T or P, V]`` and ``generate(params, prompt, max_tokens, steps=None,
    on_step=None) -> tokens``, both float32 at "highest" matmul precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    L, eps, Bk = c["num_hidden_layers"], float(c["rms_norm_eps"]), int(c["block_length"])
    mask_id = int(c["mask_token_id"])
    attn_kw = dict(n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
                   theta=float(c["rope_theta"]), eps=eps, block=Bk)

    def highest(fn, **jit_kw):
        def run(*args, **kw):
            with jax.default_matmul_precision("highest"):
                return fn(*args, **kw)

        return jax.jit(run, **jit_kw)

    embed = highest(lambda table, tokens: table[tokens].astype(jnp.float32))
    attention = highest(lambda x, w: _attention_branch(x, w, **attn_kw))
    pre_mlp = highest(lambda x, w: _rms_norm(x, w["post_attention_layernorm"], eps))
    routing = highest(lambda m, w, chosen=None: _route(m, w, chosen, k=c["num_experts_per_tok"]))
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
        ``on_router(layer, m, w)`` is shown each layer's expert input and
        router weights; what it returns, if anything, is the selection
        ``[T, k]`` that layer uses instead of its own."""
        x = embed(params["embed"], tokens)
        stack = params["layers"]
        for i in range(L):
            w = reference_layer(params, i)
            x = attention(x, w)
            m = pre_mlp(x, w)
            weights = routing(m, w, on_router(i, m, w) if on_router is not None else None)
            f = jnp.zeros_like(m)
            for e in range(0, c["num_experts"], _EXPERT_BLOCK):
                sl = slice(e, e + _EXPERT_BLOCK)
                f = f + expert_block(m, weights[:, sl], stack["we3"][i, sl], stack["we1"][i, sl], stack["we2"][i, sl])
            x = x + f
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

    def denoise(lg, block: List[int], masked: List[bool], steps_left: int):
        """One step's unmasking on the block's logits ``lg [Bk, V]``: the
        block and its mask after it, and the positions that took a token."""
        lg = np.array(lg, np.float32)
        lg[:, mask_id] = -np.inf
        cand = lg.argmax(-1)
        top = lg.max(-1)
        conf = 1.0 / np.exp(lg - top[:, None]).sum(-1)  # softmax probability of the arg-max
        left = [i for i in range(Bk) if masked[i]]
        n = -(-len(left) // steps_left)
        chosen = sorted(left, key=lambda i: (-conf[i], i))[:n]
        block, masked = list(block), list(masked)
        for i in chosen:
            block[i], masked[i] = int(cand[i]), False
        return block, masked, chosen

    def generate(params, prompt: List[int], max_tokens: int, steps: Optional[int] = None, on_step=None):
        """The generation loop on one sequence, greedy. ``on_step(context,
        block, masked, lg)`` sees every denoising forward: the committed
        tokens, the block as it went in, its mask and its logits [Bk, V]."""
        steps = int(steps or c["denoising_steps"])
        n = len(prompt)
        committed, block = list(prompt[: n - n % Bk]), list(prompt[n - n % Bk :])
        out: List[int] = []
        while len(out) < max_tokens:
            known = len(block)
            block = block + [mask_id] * (Bk - known)
            masked = [i >= known for i in range(Bk)]
            steps_left = steps
            while any(masked):
                seq = jnp.asarray(committed + block, jnp.int32)
                lg = logits(params, seq, jnp.arange(len(committed), len(committed) + Bk))
                if on_step is not None:
                    on_step(list(committed), list(block), list(masked), lg)
                block, masked, _ = denoise(lg, block, masked, steps_left)
                steps_left -= 1
            out.extend(block[known:])
            committed, block = committed + block, []
        return out[:max_tokens]

    generate.denoise = denoise
    return logits, generate
