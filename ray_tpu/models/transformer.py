"""Decoder-only transformer (GPT/Llama-style), TPU-first.

Design notes (not a port — the reference has no model core; RLlib's torch
nets are the closest analog, ``rllib/core/rl_module/rl_module.py``):

- Pure-pytree params + functional ``forward`` so the whole train step jits
  to ONE XLA program; sharding is declared with ``PartitionSpec`` and GSPMD
  propagates collectives (psum over ``tp``, all-gather over ``sp`` for KV).
- bfloat16 activations, float32 params/optimizer — the MXU-native recipe.
- RMSNorm + RoPE + SwiGLU. The FFN is dense or a top-k expert layer whose
  expert dimension shards over the ``ep`` mesh axis: dropless (assignments
  sorted by expert, one grouped matrix product a projection, T x k rows
  whatever the imbalance) with softmax or sigmoid scores, a selection bias,
  and shared experts beside the routed; or capacity dispatch
  (``moe_capacity_factor > 0``: softmax top-k, overflow dropped).
- One block, many families: ``head_dim``, norm eps, embedding scale and tying
  are fields; query/key norms, an output gate and sandwich norms are
  switches; ``layer_types`` gives each layer its attention kind (sliding
  window or full, RoPE or none), and the kinds ride the layer scan as
  per-layer values, so one traced body serves all of them. Leading dense
  layers and expert layers are two stacks, scanned one after the other.
  ``docs/models.md`` shows how a published config maps onto the fields.
- A third layer kind, ``"linear"`` (Gated DeltaNet: a recurrent state a head
  in place of keys and values, ``ops/gated_delta.py``), has a parameter tree
  of its own, so it cannot ride the scan as a per-layer value: a config that
  names it repeats a period of ``k`` linear layers and one full layer, the
  linear layers are stacks of their own (``params["linear_layers"]``: a list
  of ``k`` trees, the ``j``-th the stack ``[periods, ...]`` of every period's
  ``j``-th linear layer) beside the full layers' ``[periods, ...]``, and the
  scan's body is a period (:func:`hybrid_scan`).
- A fourth kind, ``"latent"`` (multi-head latent attention), may stand in a
  period's last place instead of the full layer: its cache is one row a token
  (the normalised latent and the key part every head shares), and a config
  that has expert layers beside linear ones keeps **mixers and FFNs in
  stacks of their own** (``params["dense_ffn"]``, ``params["expert_ffn"]``),
  each layer naming its index in both (:func:`split_ffn`): the first
  period's leading layers take the dense FFN and every other an expert FFN
  while the scan's body stays one period. An expert layer may hold a
  contiguous share of its experts (``experts_held``): it routes over all of
  them and computes its own part.
- A fifth kind, ``"conv"`` (a gated short convolution as a layer's whole
  mixer: two products around a depthwise causal convolution of ``conv_width``
  taps, :func:`conv_mixer`), keeps per sequence its last ``conv_width - 1``
  inputs and nothing else. Its configs need not be periods that end in
  attention: the layer loop's plan is read from ``layer_types``
  (:func:`plan_layers`: leading layers traced one by one, the repeating
  period one scanned body, a tail traced one by one), and their mixers lie
  by that plan (``params["lead_layers"]``, ``["period_layers"]``: a stack
  ``[repeats, ...]`` a place, ``["tail_layers"]``).
- Attention: Pallas flash kernel (``ray_tpu.ops.attention``) on a single
  chip (no mesh); XLA einsum attention under any mesh; or
  ``attention="ring"`` — sequence-parallel ring attention
  (``ray_tpu.parallel.ring``: ppermute K/V rotation + per-step flash
  kernel) sharded over (dp, tp, sp), the long-context mode. The ring's
  causal work is balanced by placing the sequence zigzag over ``sp``
  (:func:`ring_placement`: ids, targets and mask gathered once a step,
  RoPE given the permutation as positions).

Mesh axes: ``dp`` (batch), ``sp`` (sequence), ``tp`` (hidden/heads),
``ep`` (experts; may be folded into ``dp`` on small meshes).
"""

from __future__ import annotations

import dataclasses
import math
from functools import lru_cache, partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.ops import backend
from ray_tpu.ops.attention import NEG_INF, flash_attention_with_lse, mha
from ray_tpu.ops.decode_attention import block_last
from ray_tpu.ops.gated_delta import gated_delta_chunked
from ray_tpu.ops.grouped_matmul import grouped_matmul as grouped_matmul_kernel


@lru_cache(maxsize=None)
def plan_layers(kinds: Tuple[str, ...], dense: int = 0) -> Tuple[int, int, int]:
    """The layer loop of a list of layer kinds: ``(lead, period, repeats)``.
    Layers ``[0, lead)`` are traced one by one, then ``kinds[lead : lead +
    period]`` is one scanned body that runs ``repeats`` times, and what is
    left behind it, a tail that is no whole period, is traced one by one.

    The rule, read from the list alone: a period holds every kind the list
    has (so a run of one kind is never a period of its own), and of all
    ``(lead, period)`` the plan that traces the fewest layer bodies
    (``len(kinds) - (repeats - 1) * period``: compile time follows what is
    traced, not the depth) wins; then the one with the fewest places whose
    layers are dense FFNs in some periods and expert FFNs in others (``dense``:
    how many leading layers have a dense FFN beside expert layers; such a
    place chooses by ``cond``, :func:`split_ffn`); then the shortest lead,
    then the shortest period. ``(k x "linear", "full") x m`` gives ``(0, k + 1,
    m)``; two leading layers and then periods that begin with their attention
    layer give ``(2, period, m)``; a list that repeats nothing is one period,
    scanned once."""
    N, every = len(kinds), set(kinds)
    best = None
    for lead in range(N):
        for period in range(1, N - lead + 1):
            body = kinds[lead : lead + period]
            if set(body) != every:
                continue
            repeats = 1
            while kinds[lead + repeats * period : lead + (repeats + 1) * period] == body:
                repeats += 1
            last = lead + (repeats - 1) * period
            mixed = sum(1 for j in range(period) if lead + j < dense <= last + j)
            key = (N - (repeats - 1) * period, mixed, lead, period)
            if best is None or key < best[0]:
                best = (key, (lead, period, repeats))
    return best[1]


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
    # 0 => dropless dispatch (the T x k assignments sorted by expert, one
    # grouped product a projection: exact, FLOPs follow top_k); > 0 =>
    # GShard/Switch capacity dispatch: expert slots = ceil(top_k * T * factor
    # / E), overflow tokens fall through the residual
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
    # ---- beyond the Llama block. Every default keeps the function and the
    # parameter tree of a config that does not name the field.
    head_dim: Optional[int] = None      # None => d_model // n_heads
    norm_eps: float = 1e-6
    embed_scale: Optional[float] = None  # input embedding multiplier; None => sqrt(d_model)
    tie_embeddings: bool = True         # False => params["head"], a [V, d] leaf of its own
    qk_norm: bool = False               # RMSNorm over head_dim on q and k, before RoPE
    attn_gate: bool = False             # o * sigmoid(h @ wg) before wo
    post_norms: bool = False            # sandwich: RMSNorm on each branch's output before the residual add
    # per-layer mixer kinds. "sliding" (key j visible to query i iff
    # i - sliding_window < j <= i; RoPE) and "full" (causal; RoPE unless
    # rope_full_layers is False) keep keys and values; "linear" (a recurrent
    # state), "latent" (one latent row a token) and "conv" (a gated short
    # convolution: the last conv_width - 1 inputs) are described with their
    # fields below. None => every layer full with RoPE
    layer_types: Optional[Tuple[str, ...]] = None
    sliding_window: int = 0
    rope_full_layers: bool = True
    # expert layers: the first num_dense_layers are dense at d_ff (a stack of
    # their own, params["dense_layers"]), the rest route over num_experts of
    # width expert_d_ff (None => d_ff) plus num_shared_experts always-on ones
    num_dense_layers: int = 0
    expert_d_ff: Optional[int] = None
    num_shared_experts: int = 0
    router_score: str = "softmax"       # softmax | sigmoid, in float32
    route_norm: bool = True             # selected weights / their sum
    route_scale: float = 1.0
    router_bias: bool = False           # per-expert bias added for SELECTION only (params: "router_bias")
    # generation by diffusion over blocks (SDAR): key j is visible to query i
    # iff j // block_length <= i // block_length (causal across blocks, full
    # inside one); 0 or 1 => causal. The serving engine then decodes a block
    # of block_length positions a step, masked positions holding
    # mask_token_id until they are unmasked (serve/llm.py)
    block_length: int = 0
    mask_token_id: int = 0
    # "linear" layers (Gated DeltaNet, ops/gated_delta.py): linear_heads key
    # heads of linear_key_dim and as many value heads of linear_value_dim, a
    # causal depthwise convolution of linear_conv_width over time on q, k, v,
    # beta = sigmoid (x 2 with linear_allow_neg_eigval). layer_types must then
    # repeat (k x "linear", "full"): the period is the layer scan's body
    linear_heads: int = 0
    linear_key_dim: int = 0
    linear_value_dim: int = 0
    linear_conv_width: int = 4
    linear_allow_neg_eigval: bool = False
    # OLMo 2/3's norm placement is post_norms without pre_norms: x + norm(branch(x))
    pre_norms: bool = True              # False => no RMSNorm on a branch's input (needs post_norms)
    qk_norm_whole: bool = False         # qk_norm's gains span the whole projection (H*Dh, Hkv*Dh), not head_dim
    # "latent" layers (multi-head latent attention): n_heads queries of
    # latent_nope_dim + latent_rope_dim, keys and values expanded from one
    # latent of latent_rank a token, values of latent_value_dim; the
    # latent_rope_dim key numbers are shared by every head. Two layouts: in a
    # period's last place behind "linear" layers they carry no rotation
    # (rope_full_layers must be False) and the cache holds latent_rank +
    # latent_rope_dim numbers a token a layer, read as keys and as values;
    # as EVERY layer of the plain stack (dense layers, then expert layers)
    # they rotate the shared key part and each query's last latent_rope_dim
    # numbers at rope_theta, adjacent pairs (rope_full_layers must be True):
    # that stack is trained (forward, make_train_step), not yet served
    latent_rank: int = 0
    latent_nope_dim: int = 0
    latent_rope_dim: int = 0
    latent_value_dim: int = 0
    # the linear layer's decay: "head" (one a head: Gated DeltaNet) or "channel"
    # (one a key channel: Kimi Delta Attention); linear_gate_rank > 0 makes the
    # output gate's projection low-rank, d -> rank -> heads x size, and is what
    # a channel decay's projection always is (rank 0 is refused with it);
    # linear_out_gate is the output gate's activation
    linear_gate: str = "head"
    linear_gate_rank: int = 0
    linear_out_gate: str = "silu"       # silu | sigmoid
    # the share of the experts this parameter tree holds, [lo, hi) of
    # num_experts; None: all. The router scores and normalises over all
    # num_experts; the layer adds the terms of the experts held (and the
    # shared experts) and leaves the absent ones' out
    experts_held: Optional[Tuple[int, int]] = None
    # what the dropless router adds to the chosen scores' sum before it divides by it (route_norm)
    route_norm_eps: float = 1e-20
    # "conv" layers (a gated short convolution as the whole mixer, LFM2):
    # [B | C | X] = h W_in (d -> 3d), u = B * X, a causal depthwise convolution
    # of conv_width taps over u, times C, W_out; no activation, no bias. Beside
    # "full" layers only, in any order (:func:`plan_layers`); a sequence keeps
    # its last conv_width - 1 rows of u a conv layer
    conv_width: int = 3

    def __post_init__(self):
        if self.block_length > 1:
            if self.layer_types is not None and "sliding" in self.layer_types:
                raise ValueError("block_length > 1 has no sliding layers: a window would cut a block")
            if self.attention == "ring":
                raise ValueError('block_length > 1 has no attention="ring": the ring kernel is causal')
            if not 0 <= self.mask_token_id < self.vocab_size:
                raise ValueError(f"mask_token_id {self.mask_token_id} is not a row of the {self.vocab_size}-row tables")
        if self.head_dim is None:
            if self.d_model % self.n_heads:
                raise ValueError(f"d_model {self.d_model} not divisible by n_heads {self.n_heads}")
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        if self.layer_types is not None:
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
            bad = set(self.layer_types) - {"sliding", "full", "linear", "latent", "conv"}
            if bad or len(self.layer_types) != self.n_layers:
                raise ValueError(
                    f'layer_types must name "sliding", "full", "linear", "latent" or "conv" for each of the '
                    f"{self.n_layers} layers; got {self.layer_types!r}"
                )
            if "sliding" in self.layer_types and self.sliding_window < 1:
                raise ValueError("a sliding layer needs sliding_window >= 1")
            if "conv" in self.layer_types:
                self._check_conv()  # it stands beside "full" layers only
            else:
                if "latent" in self.layer_types:
                    self._check_latent()
                if "linear" in self.layer_types:
                    self._check_hybrid()
        if self.experts_held is not None:
            object.__setattr__(self, "experts_held", tuple(int(e) for e in self.experts_held))
            lo, hi = self.experts_held
            if not (self.dropless and 0 <= lo < hi <= self.num_experts):
                raise ValueError(f"experts_held {self.experts_held!r} must be a range [lo, hi) of the "
                                 f"{self.num_experts} experts of a dropless expert layer")
        if self.linear_gate not in ("head", "channel") or self.linear_out_gate not in ("silu", "sigmoid"):
            raise ValueError(f'linear_gate must be "head" or "channel" and linear_out_gate "silu" or "sigmoid"; '
                             f"got {self.linear_gate!r}, {self.linear_out_gate!r}")
        if self.linear_gate == "channel" and self.linear_gate_rank < 1:
            raise ValueError('linear_gate="channel" projects its decay d -> linear_gate_rank -> heads x key channels: '
                             "linear_gate_rank must be > 0 (no served configuration has a full-rank channel decay)")
        if not self.pre_norms and not self.post_norms:
            raise ValueError("pre_norms=False leaves a branch without any norm: it goes with post_norms=True")
        if self.qk_norm_whole and not self.qk_norm:
            raise ValueError("qk_norm_whole places qk_norm's gains: set qk_norm=True")
        if self.router_score not in ("softmax", "sigmoid"):
            raise ValueError(f'router_score must be "softmax" or "sigmoid"; got {self.router_score!r}')
        if self.num_experts > 0 and not 0 <= self.num_dense_layers < self.n_layers:
            raise ValueError(f"num_dense_layers {self.num_dense_layers} must leave an expert layer of {self.n_layers}")
        if self.moe_capacity_factor > 0 and (self.router_score != "softmax" or not self.route_norm
                                             or self.route_scale != 1.0 or self.router_bias
                                             or self.num_shared_experts > 0):
            # the capacity dispatch keeps its softmax top-k; nothing is silently ignored
            raise ValueError("router_score, route_norm, route_scale, router_bias and num_shared_experts "
                             "belong to the dropless expert layer: leave moe_capacity_factor at 0")
        if self.remat not in (False, True, "full", "dots"):
            # a typo like "Dots" would silently select full-layer recompute
            raise ValueError(f'remat must be False, True, "full", or "dots"; got {self.remat!r}')
        kv = self.n_kv_heads
        if kv is not None and (kv < 1 or kv > self.n_heads or self.n_heads % kv):
            raise ValueError(
                f"n_kv_heads {kv} must be a positive divisor of n_heads {self.n_heads}"
            )

    def _check_latent(self) -> None:
        """A config with "latent" layers: sizes given, and either each the
        last layer of a period of linear layers, without rotation, or every
        layer of the plain stack, with it."""
        if min(self.latent_rank, self.latent_nope_dim, self.latent_value_dim) < 1 or self.latent_rope_dim < 0:
            raise ValueError('a "latent" layer needs latent_rank, latent_nope_dim and latent_value_dim >= 1')
        period = "linear" in self.layer_types
        refused = {"rope_full_layers=True (behind linear layers a latent layer rotates nothing: its shared key part "
                   "is cached as it is projected)": self.rope_full_layers and period,
                   'rope_full_layers=False in layer_types without "linear" layers (an all-latent stack rotates its '
                   "shared key part; a latent layer without rotation is the last of a period of linear layers)":
                       not self.rope_full_layers and not period,
                   "an odd or missing latent_rope_dim (the rotation takes pairs)":
                       not period and (self.latent_rope_dim < 2 or self.latent_rope_dim % 2),
                   '"full" or "sliding" layers beside it': bool({"full", "sliding"} & set(self.layer_types)),
                   'attention="ring" (the ring kernel takes one head size)': self.attention == "ring" and not period,
                   "block_length > 1": self.block_length > 1 and not period,
                   "qk_norm": self.qk_norm, "attn_gate": self.attn_gate}
        bad = [name for name, hit in refused.items() if hit]
        if bad:
            raise ValueError('"latent" layers do not go with ' + "; ".join(bad))

    def _check_hybrid(self) -> None:
        """A config with "linear" layers: sizes given, and a whole number of
        periods of ``k`` linear layers and one full (or one latent) layer."""
        if min(self.linear_heads, self.linear_key_dim, self.linear_value_dim) < 1 or self.linear_conv_width < 2:
            raise ValueError('a "linear" layer needs linear_heads, linear_key_dim and linear_value_dim >= 1 '
                             "and linear_conv_width >= 2")
        last = self.attn_kind
        k = self.layer_types.index(last) if last in self.layer_types else 0
        period = ("linear",) * k + (last,)
        if k < 1 or self.n_layers % len(period) or self.layer_types != period * (self.n_layers // len(period)):
            raise ValueError('layer_types with "linear" layers must repeat one period of k >= 1 "linear" layers '
                             f'followed by one "full" (or one "latent") layer; got {self.layer_types!r}')
        refused = {"moe_capacity_factor > 0 (its expert layers are dropless)": self.moe_capacity_factor > 0,
                   'num_experts > 0 beside "full" layers (the FFN stacks of their own go with a "latent" period)':
                       self.num_experts > 0 and last == "full",
                   "block_length > 1": self.block_length > 1, 'attention="ring"': self.attention == "ring"}
        bad = [name for name, hit in refused.items() if hit]
        if bad:
            raise ValueError('"linear" layers do not go with ' + ", ".join(bad))

    def _check_conv(self) -> None:
        """A config with "conv" layers: beside "full" layers only, on one
        device, autoregressive, its expert layers dropless."""
        kinds = set(self.layer_types)
        refused = {'"linear" layers in one config': "linear" in kinds, '"latent" layers in one config': "latent" in kinds,
                   '"sliding" layers in one config': "sliding" in kinds,
                   'no "full" layer at all (the paged pool would hold no layer)': "full" not in kinds,
                   "conv_width < 2": self.conv_width < 2,
                   'attention="ring"': self.attention == "ring", "block_length > 1": self.block_length > 1,
                   "moe_capacity_factor > 0 (its expert layers are dropless)": self.moe_capacity_factor > 0,
                   "experts_held (a share of the experts)": self.experts_held is not None}
        bad = [name for name, hit in refused.items() if hit]
        if bad:
            raise ValueError('"conv" layers do not go with ' + "; ".join(bad))

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def hybrid(self) -> bool:
        """Whether some layers keep a state a sequence and no K/V: "linear" (a
        recurrent state and a convolution tail) or "conv" (a tail alone)."""
        return self.layer_types is not None and bool({"linear", "conv"} & set(self.layer_types))

    @property
    def plan(self) -> Tuple[int, int, int]:
        """The layer loop of a :attr:`hybrid` config, read from
        ``layer_types`` (:func:`plan_layers`): ``(lead, period, repeats)``."""
        return plan_layers(self.layer_types, self.num_dense_layers if self.num_experts > 0 else 0)

    @property
    def conv_layers(self) -> int:
        """Layers whose whole mixer is a gated short convolution: the tails' layer axis."""
        return self.layer_types.count("conv") if self.layer_types is not None else 0

    @property
    def attn_kind(self) -> str:
        """The kind of a hybrid config's period's last layer: "full" or "latent"."""
        return "latent" if self.layer_types is not None and "latent" in self.layer_types else "full"

    @property
    def linear_per_period(self) -> int:
        return self.layer_types.index(self.attn_kind) if self.linear_layers else 0

    @property
    def periods(self) -> int:
        """How often a hybrid config's period repeats: the layer scan's length."""
        return self.plan[2] if self.hybrid else 0

    @property
    def linear_layers(self) -> int:
        return self.layer_types.count("linear") if self.layer_types is not None else 0

    @property
    def latent_layers(self) -> int:
        """Layers that keep one latent row a token: the latent pool's layer axis."""
        return self.layer_types.count("latent") if self.layer_types is not None else 0

    @property
    def kv_layers(self) -> int:
        """Layers that keep keys and values: the paged pool's layer axis."""
        return self.n_layers - self.linear_layers - self.latent_layers - self.conv_layers

    @property
    def latent_row(self) -> int:
        """Numbers a token a latent layer caches: the latent and the shared key part."""
        return self.latent_rank + self.latent_rope_dim

    @property
    def latent_row_lanes(self) -> int:
        """``latent_row`` rounded up to whole 128-lane tiles: the latent pool's
        minor axis. The chip tiles a minor axis of 576 to 640 lanes whatever is
        asked for, so the pad costs no byte, and with it written down the
        kernels copy, slice and contract whole tiles."""
        return -(-self.latent_row // 128) * 128

    @property
    def split_ffn(self) -> bool:
        """Whether mixers and FFNs are stacks of their own (:func:`split_ffn`):
        a config with linear or conv layers and expert layers."""
        return self.hybrid and self.num_experts > 0

    @property
    def experts_here(self) -> int:
        """Experts whose weights this tree holds."""
        return self.experts_held[1] - self.experts_held[0] if self.experts_held else self.num_experts

    @property
    def linear_channels(self) -> int:
        """Channels of a linear layer's convolution: q, k and v side by side."""
        return self.linear_heads * (2 * self.linear_key_dim + self.linear_value_dim)

    @property
    def block(self) -> int:
        """Positions a block of the attention mask (and of a decode step) holds; 1: causal."""
        return max(1, self.block_length)

    @property
    def dense_stack(self) -> int:
        """Layers of ``params["dense_layers"]`` (0: one stack holds every layer)."""
        return self.num_dense_layers if self.num_experts > 0 else 0

    @property
    def expert_layers(self) -> int:
        return self.n_layers - self.num_dense_layers if self.num_experts > 0 else 0

    @property
    def dropless(self) -> bool:
        """Expert layers on the dropless dispatch (their weights are read
        where they lie, not as slices riding the layer scan)."""
        return self.num_experts > 0 and self.moe_capacity_factor == 0

    @property
    def expert_width(self) -> int:
        return self.expert_d_ff or self.d_ff

    @property
    def layer_windows(self) -> Optional[Tuple[int, ...]]:
        """Per layer, the window in tokens (0: full attention); None where no layer has a kind."""
        if self.layer_types is None:
            return None
        return tuple(self.sliding_window if t == "sliding" else 0 for t in self.layer_types)

    @property
    def layer_rope(self) -> Optional[Tuple[bool, ...]]:
        if self.layer_types is None:
            return None
        return tuple(t == "sliding" or self.rope_full_layers for t in self.layer_types)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def init_params(cfg: TransformerConfig, key: jax.Array) -> Dict[str, Any]:
    # third split: the untied output head's key (existing seeds reproduce their init)
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    pd = cfg.param_dtype
    d, h, hkv, dh, ff = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.d_ff

    from ray_tpu.models.common import dense_init as _dinit

    def dense_init(k, shape, fan_in):
        return _dinit(k, shape, fan_in, pd)

    layer_keys = jax.random.split(k_layers, cfg.n_layers)

    def attention_leaves(k):
        """A "full" or "sliding" layer's mixer without its branch norms."""
        ks = jax.random.split(k, 8)
        layer = {
            "wq": dense_init(ks[0], (d, h, dh), d),
            "wk": dense_init(ks[1], (d, hkv, dh), d),
            "wv": dense_init(ks[2], (d, hkv, dh), d),
            "wo": dense_init(ks[3], (h, dh, d), h * dh),
        }
        # leaves of the newer switches draw from keys folded off the layer's
        # own, so the eight splits above stay what they were
        if cfg.qk_norm:
            layer["q_norm"] = jnp.ones((h * dh if cfg.qk_norm_whole else dh,), pd)
            layer["k_norm"] = jnp.ones((hkv * dh if cfg.qk_norm_whole else dh,), pd)
        if cfg.attn_gate:
            layer["wg"] = dense_init(jax.random.fold_in(k, 8), (d, h, dh), d)
        return layer

    def one_layer(k, experts: bool):
        layer = attention_leaves(k)
        if cfg.pre_norms:
            layer.update(attn_norm=jnp.ones((d,), pd), ffn_norm=jnp.ones((d,), pd))
        if cfg.post_norms:
            layer["post_attn_norm"] = jnp.ones((d,), pd)
            layer["post_ffn_norm"] = jnp.ones((d,), pd)
        layer.update(ffn_leaves(k, experts))
        return layer

    def ffn_leaves(k, experts: bool):
        """A layer's FFN weights (no norm): the dense SwiGLU's, or the router,
        the experts held and the shared experts. Drawn from the layer's own
        key as :func:`one_layer` always drew them."""
        ks = jax.random.split(k, 8)
        if not experts:
            return {"w1": dense_init(ks[4], (d, ff), d), "w3": dense_init(ks[5], (d, ff), d),
                    "w2": dense_init(ks[6], (ff, d), ff)}
        e, held, fe = cfg.num_experts, cfg.experts_here, cfg.expert_width
        layer = {
            "router": dense_init(ks[7], (d, e), d),
            "we1": dense_init(ks[4], (held, d, fe), d),
            "we3": dense_init(ks[5], (held, d, fe), d),
            "we2": dense_init(ks[6], (held, fe, d), fe),
        }
        if cfg.router_bias:
            # a buffer the router's balance rule moves, not a gradient; zero
            # when training starts. Drawn small here so that serving code
            # which dropped it (or put it into the weights) computes another function
            layer["router_bias"] = 0.01 * jax.random.normal(jax.random.fold_in(k, 9), (e,), jnp.float32)
        if cfg.num_shared_experts:
            fs = cfg.num_shared_experts * fe
            layer["ws1"] = dense_init(jax.random.fold_in(k, 10), (d, fs), d)
            layer["ws3"] = dense_init(jax.random.fold_in(k, 11), (d, fs), d)
            layer["ws2"] = dense_init(jax.random.fold_in(k, 12), (fs, d), fs)
        return layer

    def ffn_layer(k, experts: bool):
        """One entry of a split config's FFN stacks: the weights and the branch's norms."""
        layer = ffn_leaves(k, experts)
        if cfg.pre_norms:
            layer["ffn_norm"] = jnp.ones((d,), pd)
        if cfg.post_norms:
            layer["post_ffn_norm"] = jnp.ones((d,), pd)
        return layer

    def mixer_norms(with_ffn: bool):
        names = ["attn_norm"] * cfg.pre_norms + ["post_attn_norm"] * cfg.post_norms
        if with_ffn:
            names += ["ffn_norm"] * cfg.pre_norms + ["post_ffn_norm"] * cfg.post_norms
        return {name: jnp.ones((d,), pd) for name in names}

    def latent_layer(k, experts: bool = False):
        """A latent attention layer's mixer (``lat_*``) and, unless the FFNs
        are stacks of their own, its FFN (dense, or the expert layer's)."""
        r, nope, rope, dv_ = cfg.latent_rank, cfg.latent_nope_dim, cfg.latent_rope_dim, cfg.latent_value_dim
        ks = jax.random.split(jax.random.fold_in(k, 30), 4)
        layer = {
            "lat_wq": dense_init(ks[0], (d, h, nope + rope), d),
            "lat_wkva": dense_init(ks[1], (d, r + rope), d),
            "lat_norm": jnp.ones((r,), pd),
            "lat_wkvb": dense_init(ks[2], (r, h, nope + dv_), r),
            "lat_wo": dense_init(ks[3], (h, dv_, d), h * dv_),
            **mixer_norms(not cfg.split_ffn),
        }
        if not cfg.split_ffn:
            layer.update(ffn_leaves(k, experts))
        return layer

    def linear_layer(k):
        """A Gated DeltaNet layer: its mixer's leaves (``lin_*``, the gates'
        ``A_log`` and ``dt_bias``, the convolution, the output norm) and the
        dense FFN's. ``A_log`` and ``dt_bias`` are float32 buffers drawn as the
        published layer draws them (A uniform in (0, 16); dt log-uniform in
        [0.001, 0.1] through the inverse softplus), so that with seeded
        weights the decay ``alpha = exp(-A softplus(.))`` spreads over (0, 1)."""
        ks = jax.random.split(k, 12)
        H, dk, dv, K = cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim, cfg.linear_conv_width
        channel, rank = cfg.linear_gate == "channel", cfg.linear_gate_rank
        gates = (H, dk) if channel else (H,)  # a decay a key channel (its projection low-rank), or a head
        dt = jnp.exp(jax.random.uniform(ks[8], gates) * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
        layer = {
            "lin_wq": dense_init(ks[0], (d, H, dk), d),
            "lin_wk": dense_init(ks[1], (d, H, dk), d),
            "lin_wv": dense_init(ks[2], (d, H, dv), d),
            "lin_wo": dense_init(ks[4], (H, dv, d), H * dv),
            "lin_wb": dense_init(ks[6], (d, H), d),
            # the channel-decay layer draws A in (1, 16), as published: one a head in either kind
            "A_log": jnp.log(jax.random.uniform(ks[7], (H,), minval=1.0 if channel else 1e-3, maxval=16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            # [width, channels], channels = q, k, v side by side; tap j weighs the input 3 - j steps back
            "conv_w": jax.random.uniform(ks[9], (K, cfg.linear_channels), minval=-1.0, maxval=1.0).astype(pd) / math.sqrt(K),
            "o_norm": jnp.ones((dv,), pd),
        }
        if rank:  # low-rank gates: d -> rank -> heads x size
            layer["lin_wga"] = dense_init(ks[3], (d, rank), d)
            layer["lin_wgb"] = dense_init(jax.random.fold_in(k, 20), (rank, H, dv), rank)
        else:
            layer["lin_wg"] = dense_init(ks[3], (d, H, dv), d)
        if channel:
            layer["lin_wfa"] = dense_init(ks[5], (d, rank), d)
            layer["lin_wfb"] = dense_init(jax.random.fold_in(k, 21), (rank, H, dk), rank)
        else:
            layer["lin_wa"] = dense_init(ks[5], (d, H), d)
        if not cfg.split_ffn:
            layer.update(w1=dense_init(jax.random.fold_in(k, 4), (d, ff), d),
                         w3=dense_init(jax.random.fold_in(k, 5), (d, ff), d),
                         w2=dense_init(jax.random.fold_in(k, 6), (ff, d), ff))
        layer.update(mixer_norms(not cfg.split_ffn))
        return layer

    def planned_mixer(kind: str):
        """How a layer of ``kind`` is made in a config whose mixers lie by its
        plan: the mixer's leaves, its branch norms and, unless the FFNs are
        stacks of their own, the dense FFN's."""
        def make(k):
            if kind == "conv":
                # in: d -> [B | C | X]; the taps [width, d], tap j weighs the input width - 1 - j steps back
                ks = jax.random.split(jax.random.fold_in(k, 40), 3)
                K = cfg.conv_width
                layer = {"conv_in": dense_init(ks[0], (d, 3 * d), d), "conv_out": dense_init(ks[1], (d, d), d),
                         "conv_w": jax.random.uniform(ks[2], (K, d), minval=-1.0, maxval=1.0).astype(pd) / math.sqrt(K)}
            else:
                layer = attention_leaves(k)
            layer.update(mixer_norms(not cfg.split_ffn))
            if not cfg.split_ffn:
                layer.update(ffn_leaves(k, False))
            return layer

        return make

    def stack(keys, experts: bool, make=None):
        # stacked layers: leaves get a leading [layers] dim, scanned in forward.
        make = make or (lambda k: one_layer(k, experts))
        return jax.tree.map(lambda *xs: jnp.stack(xs), *[make(k) for k in keys])

    def split_ffn_stacks():
        """FFNs in stacks of their own, each kind in layer order: layer i's FFN
        is dense_ffn[i] for i < num_dense_layers, else
        expert_ffn[i - num_dense_layers] (:func:`split_ffn`)."""
        nd_ = cfg.num_dense_layers
        stacks = {"expert_ffn": stack(layer_keys[nd_:], True, lambda k: ffn_layer(k, True))}
        if nd_:
            stacks["dense_ffn"] = stack(layer_keys[:nd_], False, lambda k: ffn_layer(k, False))
        return stacks

    if cfg.conv_layers:
        # mixers by the plan (:func:`plan_layers`): the leading and the
        # trailing layers each a tree of its own, the period's places each a
        # stack [repeats, ...], so that the scan's slice of it is one layer's
        # weights, read where they lie
        lead, period, repeats = cfg.plan
        behind = lead + period * repeats
        kinds = cfg.layer_types
        params = {
            "embed": dense_init(k_embed, (cfg.vocab_size, d), d),
            "lead_layers": [planned_mixer(kinds[i])(layer_keys[i]) for i in range(lead)],
            "period_layers": [stack(layer_keys[lead + j : behind : period], False, planned_mixer(kinds[lead + j]))
                              for j in range(period)],
            "tail_layers": [planned_mixer(kinds[i])(layer_keys[i]) for i in range(behind, cfg.n_layers)],
            "final_norm": jnp.ones((d,), pd),
        }
        if cfg.split_ffn:
            params.update(split_ffn_stacks())
        if not cfg.tie_embeddings:
            params["head"] = dense_init(k_head, (cfg.vocab_size, d), d)
        return params

    if cfg.hybrid:
        # a period of k linear layers and one full layer is the scan's body: the
        # j-th linear layers of all periods are one stack [periods, ...] (so that
        # the scan's slice of it is one layer's weights, read where they lie),
        # the full layers another
        k_lin, per = cfg.linear_per_period, cfg.linear_per_period + 1
        params = {
            "embed": dense_init(k_embed, (cfg.vocab_size, d), d),
            "linear_layers": [stack(layer_keys[j::per], False, linear_layer) for j in range(k_lin)],
            "layers": stack(layer_keys[k_lin::per], False, latent_layer if cfg.attn_kind == "latent" else None),
            "final_norm": jnp.ones((d,), pd),
        }
        if cfg.split_ffn:
            params.update(split_ffn_stacks())  # mixers above, FFNs here
        if not cfg.tie_embeddings:
            params["head"] = dense_init(k_head, (cfg.vocab_size, d), d)
        return params

    nd = cfg.dense_stack
    def make(experts: bool):
        """An all-latent stack: the same two stacks, each layer's mixer the latent one (None: :func:`one_layer`)."""
        return partial(latent_layer, experts=experts) if cfg.latent_layers else None

    params = {
        # embedding at 1/sqrt(d) std (a tied unembed wants unit row norms so
        # init logits are O(1) — std-1 rows made the model a confident
        # token-COPIER at init: diag logit ~= |E_t|^2 ~= d); the input path
        # multiplies by cfg.embed_scale (default sqrt(d)) in embed_tokens() to
        # keep the residual stream at its usual scale
        "embed": dense_init(k_embed, (cfg.vocab_size, d), d),
        "layers": stack(layer_keys[nd:], cfg.num_experts > 0, make(cfg.num_experts > 0)),
        "final_norm": jnp.ones((d,), pd),
    }
    if nd:
        params["dense_layers"] = stack(layer_keys[:nd], False, make(False))
    if not cfg.tie_embeddings:
        params["head"] = dense_init(k_head, (cfg.vocab_size, d), d)
    return params


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
    refused = {'"latent" layers': cfg.latent_layers > 0, '"conv" layers': cfg.conv_layers > 0, "experts_held (a share of the experts)": cfg.experts_held is not None,
               "expert layers beside linear layers": cfg.split_ffn,
               'linear_gate="channel" or linear_gate_rank > 0': cfg.linear_gate != "head" or cfg.linear_gate_rank > 0}
    bad = [name for name, hit in refused.items() if hit]
    if bad:
        raise ValueError("param_specs (a mesh) has no layout for a config with " + "; ".join(bad)
                         + ": it runs on one device")
    ep = ep or dp
    kv = tp if kv_tp else None

    def layer_specs(experts: bool):
        specs = {
            "wq": P(None, None, tp, None),
            "wk": P(None, None, kv, None),
            "wv": P(None, None, kv, None),
            "wo": P(None, tp, None, None),
        }
        if cfg.pre_norms:
            specs.update(attn_norm=P(None, None), ffn_norm=P(None, None))
        if cfg.qk_norm:
            specs.update(q_norm=P(None, None), k_norm=P(None, None))
        if cfg.attn_gate:
            specs["wg"] = P(None, None, tp, None)
        if cfg.post_norms:
            specs.update(post_attn_norm=P(None, None), post_ffn_norm=P(None, None))
        if experts:
            specs.update(
                router=P(None, None, None),
                we1=P(None, ep, None, tp),
                we3=P(None, ep, None, tp),
                we2=P(None, ep, tp, None),
            )
            if cfg.router_bias:
                specs["router_bias"] = P(None, None)
            if cfg.num_shared_experts:
                specs.update(ws1=P(None, None, tp), ws3=P(None, None, tp), ws2=P(None, tp, None))
        else:
            specs.update(w1=P(None, None, tp), w3=P(None, None, tp), w2=P(None, tp, None))
        return specs

    specs = {"embed": P(tp, None), "layers": layer_specs(cfg.num_experts > 0), "final_norm": P(None)}
    if cfg.hybrid:
        # replicated: the serving engine refuses a mesh for a config with linear layers (ROADMAP R6)
        names = ["lin_wq", "lin_wk", "lin_wv", "lin_wg", "lin_wo", "lin_wa", "lin_wb", "A_log", "dt_bias",
                 "conv_w", "o_norm", "w1", "w3", "w2"]
        names += ["attn_norm", "ffn_norm"] * cfg.pre_norms + ["post_attn_norm", "post_ffn_norm"] * cfg.post_norms
        specs["linear_layers"] = [{name: P() for name in names} for _ in range(cfg.linear_per_period)]
    if cfg.dense_stack:
        specs["dense_layers"] = layer_specs(False)
    if not cfg.tie_embeddings:
        specs["head"] = P(tp, None)
    return specs


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


def _rope_pairs(x, positions, theta: float):
    """Rotary embedding over ADJACENT pairs ``(x[2i], x[2i + 1])``, pair ``i``
    at ``theta ** (-2i / dh)``: the pairing of the published ``deepseek_v3``
    code, which de-interleaves a projection's numbers before it rotates
    halves. The result lies de-interleaved as there (first components, then
    second): queries and keys alike, so their products are the pairing's.
    x: [B, T, ..., dh]; positions: [B, T]."""
    half = x.shape[-1] // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32) * (math.log(theta) / half))
    angles = positions.astype(jnp.float32).reshape(*positions.shape, *(1,) * (x.ndim - 2)) * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], half, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


def _repeat_kv(x, n_rep: int):
    """[B, T, Hkv, Dh] -> [B, T, Hkv*n_rep, Dh] (GQA group broadcast)."""
    if n_rep == 1:
        return x
    B, T, Hkv, Dh = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (B, T, Hkv, n_rep, Dh)).reshape(B, T, Hkv * n_rep, Dh)


def _gqa_mha(qt, k, v, *, causal: bool, sm_scale: float, window=None, block: int = 1):
    """Grouped-query attention, K/V kept at kv-head width (no materialized
    repeat — decode/train HBM traffic stays 1/n_rep of the MHA layout).

    qt: [B, H, T, Dh]; k, v: [B, T, Hkv, Dh]. ``window`` (an int or a traced
    scalar; 0 or None: none) hides keys at or before ``i - window``.
    ``block`` > 1: a query also sees the keys after it in its own block of
    ``block`` positions (:func:`block_last`)."""
    B, H, T, Dh = qt.shape
    Hkv = k.shape[2]
    n_rep = H // Hkv
    qg = qt.reshape(B, Hkv, n_rep, T, Dh)
    kt = jnp.transpose(k, (0, 2, 1, 3))  # [B, Hkv, S, Dh]
    vt = jnp.transpose(v, (0, 2, 1, 3))
    s = jnp.einsum("bgrtd,bgsd->bgrts", qg, kt, preferred_element_type=jnp.float32) * sm_scale
    if causal:
        S = s.shape[-1]
        mask = jnp.arange(S)[None, :] <= block_last(jnp.arange(T)[:, None], block)
        if window is not None:
            mask = mask & in_window(jnp.arange(S)[None, :], jnp.arange(T)[:, None], window)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrts,bgsd->bgrtd", p, vt.astype(jnp.float32))
    return o.reshape(B, H, T, Dh).astype(qt.dtype)


def in_window(kv_pos, q_pos, window):
    """Key ``j`` is inside query ``i``'s window iff ``j > i - window``; a
    window of 0 is none. ``window`` may be traced (it rides the layer scan)."""
    return (kv_pos > q_pos - window) | (window <= 0)


def _attention(cfg: TransformerConfig, q, k, v, use_flash: bool, mesh=None, sp_axis=None, window=None):
    # q: [B, T, H, Dh]; k, v: [B, T, Hkv, Dh] (unrepeated under GQA).
    # window: this layer's (an int where the layers are unrolled, a traced
    # scalar where they are scanned; None where the config names no kinds)
    n_rep = cfg.n_heads // cfg.kv_heads
    qt = jnp.transpose(q, (0, 2, 1, 3))
    if not use_flash and cfg.attention != "ring":
        # grouped einsum path: K/V never widen to n_heads
        o = _gqa_mha(qt, k, v, causal=True, sm_scale=1.0 / math.sqrt(cfg.head_dim), window=window, block=cfg.block)
        return jnp.transpose(o, (0, 2, 1, 3))
    if window is not None and cfg.attention == "ring":
        raise ValueError('attention="ring" has no sliding window: use "auto", "flash" or "dense" with layer_types')
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
            # KEYS invisible to real queries, padded QUERY rows are sliced.
            # The tokens lie contiguous (ring_placement), which the ring
            # reads off a shard of odd length: pad to one
            pad += n_sp * ((T + pad) % (2 * n_sp) == 0)
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
        o = flash_by_kind(cfg, qt, kt, vt, None, window)
    else:
        o = mha(qt, kt, vt, causal=True)
    return jnp.transpose(o, (0, 2, 1, 3))


def flash_by_kind(cfg: TransformerConfig, qt, kt, vt, sm_scale, window):
    """Causal flash attention ([B, H, T, Dh] operands) of a layer whose
    ``window`` is None (the config names no kinds), an int (unrolled layers)
    or a traced scalar riding the layer scan: the kernel's window is static,
    so a scanned layer picks its kind by ``cond``."""
    def flash(w):
        return lambda: flash_attention_with_lse(qt, kt, vt, sm_scale, True, window=w)[0]

    if window is None or isinstance(window, int):
        return flash(window or None)()
    return jax.lax.cond(window > 0, flash(cfg.sliding_window or None), flash(None))


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


def route(cfg: TransformerConfig, layer, x2):
    """The dropless layer's router on tokens ``x2`` [N, d]: scores in float32
    (softmax or sigmoid over all E), the k experts with the largest
    ``score + bias`` (the bias selects, it never weighs), their scores as
    weights, normalised and scaled. Returns (experts int32[N, k], weights f32[N, k])."""
    # float32 in fact, not in name: the TPU's default matmul precision would
    # round both operands to bf16, and the 8th and 9th expert lie close
    logits = jnp.dot(x2.astype(jnp.float32), layer["router"].astype(jnp.float32), precision="highest")
    scores = jax.nn.sigmoid(logits) if cfg.router_score == "sigmoid" else jax.nn.softmax(logits, axis=-1)
    choose = scores + layer["router_bias"].astype(jnp.float32) if cfg.router_bias else scores
    _, experts = jax.lax.top_k(choose, cfg.expert_top_k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if cfg.route_norm:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + cfg.route_norm_eps)
    return experts.astype(jnp.int32), weights * cfg.route_scale


def grouped_matmul(rows, weights, group_sizes):
    """``rows[start_e : start_e + group_sizes[e]] @ weights[e]`` for every
    group ``e``: rows [R, a] sorted by group, weights [E, a, b] -> [R, b].
    On the chip the Pallas kernel of ``ops/grouped_matmul.py``: each group
    that holds rows has its weights copied out of HBM once, at a product as
    tall as its rows, and a group without rows costs nothing. Elsewhere
    ``jax.lax.ragged_dot``."""
    if backend.on_tpu():
        return grouped_matmul_kernel(rows, weights, group_sizes)
    return _ragged_dot(rows, weights, group_sizes)


def _ragged_dot(rows, weights, group_sizes):
    return jax.lax.ragged_dot(rows, weights.astype(rows.dtype), group_sizes)


EXPERT_WEIGHTS = ("we1", "we3", "we2")


def scanned_leaves(cfg: TransformerConfig, stack):
    """What of a layer stack rides the layer scan as xs: every leaf but a
    dropless expert layer's three weight stacks. A scan hands its body a
    slice of each xs leaf, and a slice that feeds a kernel is a copy: 0.5 GB
    a projection a layer a step at 128 experts of 2048 x 1024. The grouped
    products take the whole stack where it lies instead, as ``L x E`` groups
    (``moe_ffn_dropless``): on the chip ``ops/grouped_matmul.py`` copies out
    of it the weights of the groups that hold rows and of no other."""
    if cfg.dropless and "we1" in stack:
        return {k: v for k, v in stack.items() if k not in EXPERT_WEIGHTS}
    return stack


def moe_ffn_dropless(cfg: TransformerConfig, layer, x, valid=None, *, stack=None, index=0, kernel=True,
                     count_routed: bool = False, routes: bool = False):
    """The dropless routed + shared expert layer: route, sort the N x k
    assignments by expert, one grouped product a projection over exactly
    those N x k rows, unsort, weigh and add; the shared experts see every
    token. Nothing is dropped at any imbalance and every shape is static.

    The experts' weights come from ``layer`` (``[E, ..]`` leaves) or, inside a
    layer loop, from ``stack`` (the whole ``[L, E, ..]`` leaves) at layer
    ``index``, which may be traced: the stack is read as ``L x E`` groups of
    which only this layer's hold rows, so nothing is sliced out of it.
    ``kernel=False`` keeps the products XLA's own on the chip too: under a
    mesh, where GSPMD partitions ``ragged_dot`` and refuses a Mosaic call.
    Read from ``layer`` the weights are cast to the activations' type first,
    as a dense layer's are: a train step's unrolled loop hands each layer its
    own slice, so the products' cotangent is the layer's, not a stack ``L``
    times its size.

    A config that holds a share of the experts (``cfg.experts_held``: ``E``
    below is then the experts held, the router's width stays
    ``cfg.num_experts``) routes and normalises over all of them and adds the
    terms of its own. (Of its ``N x k`` sorted assignments only the first
    ``sum(group_sizes)`` are rows of an expert held, 1 in 8 at a share of an
    eighth, yet every product, gather and activation here is ``N x k`` rows
    tall: PERF.md has what that costs a train step.)

    Returns (out [B, T, d], assignments int32[E]): how many (token, choice)
    pairs each expert got, counting only tokens ``valid`` [B, T] marks. Bucket
    padding and idle decode rows still compute (their rows are there), but
    they follow the first valid token's experts, so they make the products
    read no expert that no real token asked for. ``count_routed``: the counts
    are over all ``cfg.num_experts`` routed experts instead, int32[num_experts],
    whatever share is held: what the router's load rule moves its bias by.
    ``routes``: instead of the counts, the selection itself, int32[N, k] (the
    experts the products ran each token through, a pad's being the first valid
    token's): what a comparison hands a reference whose own router would
    break a near-tie the other way."""
    B, T, d = x.shape
    N, E, k = B * T, cfg.experts_here, cfg.expert_top_k
    x2 = x.reshape(N, d)
    experts, weights = route(cfg, layer, x2)
    if valid is not None:
        real = valid.reshape(N)
        experts = jnp.where(real[:, None], experts, experts[jnp.argmax(real)][None, :])
    if cfg.experts_held is None:
        flat = experts.reshape(N * k)
        order = jnp.argsort(flat)                       # assignments grouped by expert (stable)
        group_sizes = jnp.bincount(flat, length=E).astype(jnp.int32)
    else:
        # a share: the assignments to experts held elsewhere are dropped before
        # the sort (they sort last, as group E that no size counts), so the
        # grouped products see rows for the experts held only and leave the
        # rest zeros, which the weighted sum below then adds as nothing
        lo, hi = cfg.experts_held
        flat = jnp.where((experts >= lo) & (experts < hi), experts - lo, E).reshape(N * k)
        order = jnp.argsort(flat)
        group_sizes = jnp.zeros((E,), jnp.int32).at[flat].add(1, mode="drop")
    rows = x2[order // k]                           # [N*k, d]: each assignment's token
    if stack is None:   # the layer's own weights, a stack of one
        w, index = {name: layer[name][None].astype(x.dtype) for name in EXPERT_WEIGHTS}, 0
    else:
        w = stack
    L = w["we1"].shape[0]
    in_stack = jnp.zeros((L, E), jnp.int32).at[index].set(group_sizes).reshape(L * E)

    multiply = grouped_matmul if kernel else _ragged_dot

    def product(a, name):
        return multiply(a, w[name].reshape(L * E, *w[name].shape[2:]), in_stack)

    out = product(jax.nn.silu(product(rows, "we3")) * product(rows, "we1"), "we2")  # [N*k, d]
    back = jnp.argsort(order)                       # where each (token, choice) landed
    out = out[back].reshape(N, k, d)
    y = jnp.einsum("nkd,nk->nd", out, weights.astype(out.dtype))
    if cfg.num_shared_experts:
        shared = jax.nn.silu(x2 @ layer["ws3"].astype(x.dtype)) * (x2 @ layer["ws1"].astype(x.dtype))
        y = y + shared @ layer["ws2"].astype(x.dtype)
    if routes:
        counted = experts
    elif count_routed:
        every = jnp.ones((N,), jnp.int32) if valid is None else real.astype(jnp.int32)
        counted = jnp.zeros((cfg.num_experts,), jnp.int32).at[experts.reshape(N * k)].add(jnp.repeat(every, k))
    elif valid is None:
        counted = group_sizes
    else:
        # (index E, a share's dropped assignment, is out of range: not counted)
        counted = jnp.zeros((E,), jnp.int32).at[flat].add(jnp.repeat(real, k).astype(jnp.int32))
    return y.reshape(B, T, d).astype(x.dtype), counted


# ---------------------------------------------------------------------------
# the block, written once: forward(), forward_with_cache() and
# paged_forward_with_cache() differ only in where K and V live
# ---------------------------------------------------------------------------
def layer_stacks(cfg: TransformerConfig, params):
    """The stacked layer trees in order, each with its layer range:
    ``[(tree, first, last + 1)]``. Leading dense layers are a stack of their
    own (their leaves differ from an expert layer's), scanned first."""
    nd = cfg.dense_stack
    stacks = [(params["dense_layers"], 0, nd)] if nd else []
    return stacks + [(params["layers"], nd, cfg.n_layers)]


def layer_kinds(cfg: TransformerConfig, first: int, last: int):
    """What rides the layer scan beside layers ``[first, last)``' weights:
    each layer's window (0: full attention) and whether it applies RoPE, as
    arrays; None where the config names no kinds (every layer full, RoPE)."""
    if cfg.layer_types is None:
        return None
    return {"window": jnp.asarray(cfg.layer_windows[first:last], jnp.int32),
            "rope": jnp.asarray(cfg.layer_rope[first:last], bool)}


def block_qkv(cfg: TransformerConfig, layer, h, positions, kind=None):
    """Projections, query/key norms and RoPE of one layer: q [B,T,H,Dh], k, v
    [B,T,Hkv,Dh]. ``kind``: this layer's entry of :func:`layer_kinds`."""
    q = jnp.einsum("btd,dhk->bthk", h, layer["wq"].astype(h.dtype))
    k = jnp.einsum("btd,dhk->bthk", h, layer["wk"].astype(h.dtype))
    v = jnp.einsum("btd,dhk->bthk", h, layer["wv"].astype(h.dtype))
    if cfg.qk_norm_whole:
        # one gain vector over all heads' outputs, the mean square over the whole projection
        q = _rms_norm(q.reshape(*q.shape[:2], -1), layer["q_norm"], cfg.norm_eps).reshape(q.shape)
        k = _rms_norm(k.reshape(*k.shape[:2], -1), layer["k_norm"], cfg.norm_eps).reshape(k.shape)
    elif cfg.qk_norm:
        q, k = _rms_norm(q, layer["q_norm"], cfg.norm_eps), _rms_norm(k, layer["k_norm"], cfg.norm_eps)
    if kind is not None and kind["rope"] is False:  # known while tracing: a period's full layer
        return q, k, v
    rq, rk = _rope(q, positions, cfg.rope_theta), _rope(k, positions, cfg.rope_theta)
    if kind is None:
        return rq, rk, v
    return jnp.where(kind["rope"], rq, q), jnp.where(kind["rope"], rk, k), v


def block_attn_out(cfg: TransformerConfig, layer, x, h, o):
    """The attention branch's tail: output gate, ``wo``, post-norm, residual. o: [B,T,H,Dh]."""
    if cfg.attn_gate:
        o = o * jax.nn.sigmoid(jnp.einsum("btd,dhk->bthk", h, layer["wg"].astype(h.dtype)))
    a = jnp.einsum("bthk,hkd->btd", o, layer["wo"].astype(o.dtype))
    if cfg.post_norms:
        a = _rms_norm(a, layer["post_attn_norm"], cfg.norm_eps)
    return x + a


def block_ffn(cfg: TransformerConfig, layer, x, valid=None, *, stack=None, index=0, kernel=True,
              count_routed: bool = False, routes: bool = False):
    """The feed-forward branch: dense or expert layer by the layer's own
    leaves (``stack``, ``index``: where a layer loop keeps the dropless
    experts' weights, see :func:`scanned_leaves`; ``kernel``: whether its
    grouped products may be a Mosaic call, ``count_routed``: which experts
    the counts are of, ``routes``: the selection in the counts' place, see
    :func:`moe_ffn_dropless`).
    Returns (x, the dropless layer's assignment counts or None)."""
    h = pre_norm(cfg, layer, "ffn_norm", x)
    counts = None
    if "router" not in layer:
        ffn = _dense_ffn(layer, h)
    elif cfg.moe_capacity_factor > 0:
        ffn = _moe_ffn_capacity(cfg, layer, h)
    else:
        ffn, counts = moe_ffn_dropless(cfg, layer, h, valid, stack=stack, index=index, kernel=kernel,
                                       count_routed=count_routed, routes=routes)
    if cfg.post_norms:
        ffn = _rms_norm(ffn, layer["post_ffn_norm"], cfg.norm_eps)
    return x + ffn, counts


def pre_norm(cfg: TransformerConfig, layer, name: str, x):
    """A branch's input: ``RMSNorm(x)`` by the gain ``name``, or ``x`` itself without pre_norms."""
    return _rms_norm(x, layer[name], cfg.norm_eps) if cfg.pre_norms else x


# ---------------------------------------------------------------------------
# the "linear" layer (Gated DeltaNet) and the period scan of a hybrid config
# ---------------------------------------------------------------------------
def linear_inputs(cfg: TransformerConfig, layer, h, tail=None, lengths=None):
    """What the recurrence of a linear layer takes, from the layer's input
    ``h`` [B, T, d]: q, k [B, T, H, dk] (unit norm, q over sqrt(dk)), v
    [B, T, H, dv], ``g = log alpha`` and ``beta`` [B, T, H] (``g`` [B, T, H, dk]
    with ``linear_gate="channel"``), all float32, and the convolution's tail
    after the call.

    q, k and v pass a causal depthwise convolution of ``linear_conv_width``
    over time and SiLU. ``tail`` [B, width - 1, channels] holds the inputs
    before this call (None: zeros, the sequence starts here); the tail
    returned holds the last ``width - 1`` inputs up to ``lengths`` [B] real
    tokens of this call (None: all ``T``)."""
    B, T, _ = h.shape
    H, dk, dv, K = cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim, cfg.linear_conv_width
    f32 = jnp.float32
    u = jnp.concatenate([jnp.einsum("btd,dhk->bthk", h, layer[name].astype(h.dtype)).reshape(B, T, -1)
                         for name in ("lin_wq", "lin_wk", "lin_wv")], axis=-1)          # [B, T, channels]
    if tail is None:
        tail = jnp.zeros((B, K - 1, u.shape[-1]), u.dtype)
    seq = jnp.concatenate([tail.astype(u.dtype), u], axis=1)                             # [B, K - 1 + T, channels]
    w = layer["conv_w"].astype(f32)
    c = jax.nn.silu(sum(seq[:, j : j + T].astype(f32) * w[j] for j in range(K)))
    if lengths is None:
        new_tail = seq[:, T:]
    else:
        new_tail = jax.vmap(lambda s, n: jax.lax.dynamic_slice_in_dim(s, n, K - 1, axis=0))(seq, lengths)
    q, k, v = jnp.split(c, [H * dk, 2 * H * dk], axis=-1)
    q, k, v = q.reshape(B, T, H, dk), k.reshape(B, T, H, dk), v.reshape(B, T, H, dv)

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

    # the gates in float32 in fact: the chip's default precision would round h and the weights' product
    channel = cfg.linear_gate == "channel"
    if channel:
        # a decay a key channel [B, T, H, dk], its projection low-rank
        a = jnp.einsum("btd,dr->btr", h.astype(f32), layer["lin_wfa"].astype(f32), precision="highest")
        a = jnp.einsum("btr,rhk->bthk", a, layer["lin_wfb"].astype(f32), precision="highest")
    else:
        a = jnp.einsum("btd,dh->bth", h.astype(f32), layer["lin_wa"].astype(f32), precision="highest")
    b = jnp.einsum("btd,dh->bth", h.astype(f32), layer["lin_wb"].astype(f32), precision="highest")
    A = jnp.exp(layer["A_log"].astype(f32))
    g = -(A[:, None] if channel else A) * jax.nn.softplus(a + layer["dt_bias"].astype(f32))
    beta = jax.nn.sigmoid(b) * (2.0 if cfg.linear_allow_neg_eigval else 1.0)
    return unit(q) / math.sqrt(dk), unit(k), v, g, beta, new_tail


def linear_out(cfg: TransformerConfig, layer, x, h, o):
    """The linear branch's tail: ``RMSNorm_dv(o) * act(h Wg)`` (``act``:
    ``linear_out_gate``; ``Wg`` low-rank with ``linear_gate_rank``), ``Wo``,
    post-norm, residual. o: [B,T,H,dv] f32."""
    if "lin_wga" in layer:
        gate = jnp.einsum("btr,rhv->bthv", h @ layer["lin_wga"].astype(h.dtype), layer["lin_wgb"].astype(h.dtype))
    else:
        gate = jnp.einsum("btd,dhv->bthv", h, layer["lin_wg"].astype(h.dtype))
    act = jax.nn.sigmoid if cfg.linear_out_gate == "sigmoid" else jax.nn.silu
    y = (_rms_norm(o, layer["o_norm"].astype(jnp.float32), cfg.norm_eps) * act(gate.astype(jnp.float32))).astype(h.dtype)
    a = jnp.einsum("bthv,hvd->btd", y, layer["lin_wo"].astype(y.dtype))
    if cfg.post_norms:
        a = _rms_norm(a, layer["post_attn_norm"], cfg.norm_eps)
    return x + a


def conv_mixer(cfg: TransformerConfig, layer, x, h, tail=None, lengths=None):
    """A "conv" layer's whole mixer branch on its input ``h`` [B, T, d]: ``[B |
    C | X] = h W_in``, ``u = B * X``, a causal depthwise convolution of
    ``conv_width`` taps over ``u`` in float32, times ``C``, ``W_out``,
    post-norm, residual. No activation and no bias. Returns (x, the tail
    after the call).

    ``tail`` [B, width - 1, d] holds ``u`` of the positions before this call
    (None: zeros, the sequence starts here); the tail returned holds the last
    ``width - 1`` rows of ``u`` up to ``lengths`` [B] real tokens of this call
    (None: all ``T``), so a row without a real token gets its own tail back."""
    B, T, d = h.shape
    K = cfg.conv_width
    with jax.named_scope("conv_mixer"):
        b, c, xg = jnp.split(h @ layer["conv_in"].astype(h.dtype), 3, axis=-1)
        u = b * xg
        if tail is None:
            tail = jnp.zeros((B, K - 1, d), u.dtype)
        seq = jnp.concatenate([tail.astype(u.dtype), u], axis=1)                         # [B, K - 1 + T, d]
        w = layer["conv_w"].astype(jnp.float32)
        conv = sum(seq[:, j : j + T].astype(jnp.float32) * w[j] for j in range(K))
        a = (c.astype(jnp.float32) * conv).astype(h.dtype) @ layer["conv_out"].astype(h.dtype)
        if lengths is None:
            new_tail = seq[:, T:]
        else:
            new_tail = jax.vmap(lambda s, n: jax.lax.dynamic_slice_in_dim(s, n, K - 1, axis=0))(seq, lengths)
    if cfg.post_norms:
        a = _rms_norm(a, layer["post_attn_norm"], cfg.norm_eps)
    return x + a, new_tail


def latent_qkv(cfg: TransformerConfig, layer, h):
    """A latent layer's projections of its input ``h`` [B, T, d]: the queries
    ``[B, T, H, nope + rope]`` and the row the cache keeps a token,
    ``[B, T, latent_row]`` = ``(RMSNorm(c~), k_pe)``: the normalised latent
    every head's keys and values are expanded from, and the key part every
    head shares, as projected (no rotation)."""
    r = cfg.latent_rank
    q = jnp.einsum("btd,dhk->bthk", h, layer["lat_wq"].astype(h.dtype))
    ckv = h @ layer["lat_wkva"].astype(h.dtype)
    c = _rms_norm(ckv[..., :r], layer["lat_norm"], cfg.norm_eps)
    return q, jnp.concatenate([c, ckv[..., r:]], axis=-1)


def latent_absorb(cfg: TransformerConfig, layer, q, lanes: Optional[int] = None):
    """The queries in the absorbed form, ``[B, T, H, latent_row]`` (zeros up
    to ``lanes`` where given): ``q' = W_kvb[K, head]^T q_nope`` beside
    ``q_pe``, so that a head's score against a cached row is one dot product,
    ``q' . c + q_pe . k_pe``, and no key is ever expanded."""
    nope = cfg.latent_nope_dim
    wk = layer["lat_wkvb"][..., :nope].astype(q.dtype)                       # [r, H, nope]
    parts = [jnp.einsum("bthn,rhn->bthr", q[..., :nope], wk), q[..., nope:]]
    if lanes is not None and lanes > cfg.latent_row:
        parts.append(jnp.zeros((*q.shape[:-1], lanes - cfg.latent_row), q.dtype))
    return jnp.concatenate(parts, axis=-1)


def latent_out(cfg: TransformerConfig, layer, x, o_lat):
    """The latent branch's tail from the attention-weighted latents ``o_lat``
    [B, T, H, r] (``sum_j a_j c_j`` a head): the head's values ``W_kvb[V,
    head] o_lat``, ``W_o``, post-norm, residual."""
    wv = layer["lat_wkvb"][..., cfg.latent_nope_dim:].astype(o_lat.dtype)   # [r, H, v]
    return _latent_wo(cfg, layer, x, jnp.einsum("bthr,rhv->bthv", o_lat, wv))


def _latent_wo(cfg: TransformerConfig, layer, x, o):
    """``W_o`` over the heads' values ``o`` [B, T, H, v], post-norm, residual."""
    a = jnp.einsum("bthv,hvd->btd", o, layer["lat_wo"].astype(o.dtype))
    if cfg.post_norms:
        a = _rms_norm(a, layer["post_attn_norm"], cfg.norm_eps)
    return x + a


def latent_scale(cfg: TransformerConfig) -> float:
    return 1.0 / math.sqrt(cfg.latent_nope_dim + cfg.latent_rope_dim)


def latent_attention_expanded(cfg: TransformerConfig, layer, x, h, positions=None, use_flash: bool = False):
    """A latent layer's whole attention branch in the published, expanded
    form, causal over the call's own ``T`` tokens: every head's keys
    ``[k_nope; k_pe]`` and values expanded from the latents, softmax at
    ``1 / sqrt(nope + rope)``, ``W_o``, residual. What :func:`forward` runs;
    the cached paths run the absorbed form (:func:`latent_absorb`,
    :func:`latent_out`), the same function.

    An all-latent stack (``rope_full_layers``) rotates ``k_pe`` and each
    query's last ``rope`` numbers at ``positions`` [B, T] first
    (:func:`_rope_pairs`). ``use_flash``: the flash kernel, keys of ``nope +
    rope`` numbers a head against values of ``latent_value_dim``, instead of
    the ``T x T`` scores."""
    B, T, _ = h.shape
    r, nope = cfg.latent_rank, cfg.latent_nope_dim
    q, row = latent_qkv(cfg, layer, h)
    kv = jnp.einsum("btr,rhk->bthk", row[..., :r], layer["lat_wkvb"].astype(h.dtype))   # [B, T, H, nope + v]
    k_pe = row[..., r:]
    if cfg.rope_full_layers:
        q = jnp.concatenate([q[..., :nope], _rope_pairs(q[..., nope:], positions, cfg.rope_theta)], axis=-1)
        k_pe = _rope_pairs(k_pe, positions, cfg.rope_theta)
    k_pe = jnp.broadcast_to(k_pe[:, :, None, :], (B, T, cfg.n_heads, cfg.latent_rope_dim))
    k, v = jnp.concatenate([kv[..., :nope], k_pe], axis=-1), kv[..., nope:]
    if use_flash:
        with jax.named_scope("latent_flash_attention"):
            qt, kt, vt = (jnp.transpose(a, (0, 2, 1, 3)) for a in (q, k, v))
            o = jnp.transpose(flash_attention_with_lse(qt, kt, vt, latent_scale(cfg), True)[0], (0, 2, 1, 3))
        return _latent_wo(cfg, layer, x, o)
    s = jnp.einsum("bthk,bshk->bhts", q.astype(jnp.float32), k.astype(jnp.float32)) * latent_scale(cfg)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, NEG_INF)
    o = jnp.einsum("bhts,bshv->bthv", jax.nn.softmax(s, axis=-1), v.astype(jnp.float32)).astype(h.dtype)
    return _latent_wo(cfg, layer, x, o)


def split_ffn(cfg: TransformerConfig, params, x, i, first: int, valid=None, kernel: bool = True,
              routes: bool = False):
    """The FFN branch of layer ``i`` of a config whose FFNs are stacks of
    their own (``cfg.split_ffn``). ``i`` is traced in the scanned period,
    where ``first`` is the first layer that takes this place of the period
    (known while tracing), and a Python int for a layer traced on its own.
    Layer ``i`` takes ``params["dense_ffn"][i]`` while ``i < num_dense_layers``
    and ``params["expert_ffn"][i - num_dense_layers]`` after: a place that is
    dense in its first period and routed in the others chooses by ``cond``,
    every other place is routed and traces no branch. The small leaves are
    read at a traced index, as a scan reads its xs; the experts' weights stay
    where they lie (:func:`scanned_leaves`). Returns (x, the expert layer's
    assignment counts int32[experts held]; zeros from a dense layer), or with
    ``routes`` its selection int32[tokens, k] (-1 from a dense layer)."""
    nd = cfg.num_dense_layers

    def at(stack, index):
        return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, index, 0, keepdims=False), stack)

    def dense(x):
        layer = at(params["dense_ffn"], 0 if nd == 1 else jnp.clip(i, 0, nd - 1))
        if routes:
            return block_ffn(cfg, layer, x)[0], jnp.full((x.shape[0] * x.shape[1], cfg.expert_top_k), -1, jnp.int32)
        return block_ffn(cfg, layer, x)[0], jnp.zeros((cfg.experts_here,), jnp.int32)

    def routed(x):
        stack = params["expert_ffn"]
        index = jnp.maximum(i - nd, 0)
        return block_ffn(cfg, at(scanned_leaves(cfg, stack), index), x, valid, stack=stack, index=index, kernel=kernel,
                         routes=routes)

    if isinstance(i, int):
        return dense(x) if i < nd else routed(x)
    if first >= nd:
        return routed(x)
    return jax.lax.cond(i < nd, dense, routed, x)


def planned_stacks(params):
    """A hybrid config's mixers as its plan walks them: (the leading layers'
    trees, the period's stacks a place, the trailing layers' trees). A config
    with "linear" layers is whole periods, its linear places'
    ``params["linear_layers"]`` and its last place ``params["layers"]``."""
    if "period_layers" in params:
        return params["lead_layers"], tuple(params["period_layers"]), params["tail_layers"]
    return (), (*params["linear_layers"], params["layers"]), ()


def hybrid_scan(cfg: TransformerConfig, params, carry, x, mixers, valid=None, kernel: bool = True,
                routes: bool = False):
    """The layer loop of a config whose layers are not one stack (linear or
    conv layers beside attention), by its plan (``cfg.plan``,
    :func:`plan_layers`): the leading layers one by one, then a ``lax.scan``
    over the period's repeats whose body runs the period's layers in their
    order, then the trailing layers one by one; so what is traced and compiled
    is the lead, one period and the tail, whatever the depth.
    ``mixers[kind](carry, x, layer, i)`` runs the mixer branch of a layer of
    ``kind`` and returns ``(carry, x)``; ``i`` counts the layers of that kind
    from 0 (traced inside the period); ``carry`` is whatever the caller keeps
    beside ``x`` (the caches, updated in place). The FFN branch follows each
    here: the layer's own leaves, or for a config whose FFNs are stacks of
    their own :func:`split_ffn` (``valid``, ``kernel``: as :func:`block_ffn`
    takes them). Returns ``(carry, x, counts, extra)``: the expert layers'
    assignment counts ``int32[repeats, period, experts held]`` of the scanned
    layers and ``int32[layers, experts held]`` of the expert layers outside
    it, each None where there are none; with ``routes`` (a config whose FFNs
    are stacks of their own) the selections ``int32[.., tokens, k]`` in the
    counts' places.

    The period's layers are the body's own lines, each with its own stack
    among the scan's xs: the scan's slice of a stack is then one layer's
    weights, which the products read where they lie. (An inner scan over a
    ``[periods, k, ...]`` stack takes the period's slice as a loop invariant,
    and a static index into such a slice fares no better: XLA copies the slice
    out of the stack every period. The chipless compile at the benchmark's
    sizes: 1.3 GB of temporaries, which a decode step would write and read
    besides the weights themselves.)"""
    lead, P, R = cfg.plan
    kinds = cfg.layer_types
    body = kinds[lead : lead + P]
    leads, places, tails = planned_stacks(params)

    def before(i):
        """Layers of layer ``i``'s kind that lie before it."""
        return kinds[:i].count(kinds[i])

    def ffn(x, layer, i, first):
        if cfg.split_ffn:
            return split_ffn(cfg, params, x, i, first, valid, kernel, routes)
        return block_ffn(cfg, layer, x)

    def alone(state, layers, start):
        """Layers ``start ...`` traced one by one, each from a tree of its own."""
        counts = []
        for i, layer in enumerate(layers, start):
            carry, x = mixers[kinds[i]](*state, layer, before(i))
            x, c = ffn(x, layer, i, i)
            state = (carry, x)
            if c is not None and i >= cfg.num_dense_layers:
                counts.append(c)
        return state, counts

    def period(state, xs):
        *layers, p = xs
        counts = []
        for j in range(P):
            per, off = body.count(body[j]), before(lead + j)
            i = p if (per, off) == (1, 0) else p * per + off  # this layer's count among its kind
            carry, x = mixers[body[j]](*state, layers[j], i)
            x, c = ffn(x, layers[j], p * P + (lead + j), lead + j)
            state = (carry, x)
            counts.append(c)
        return state, jnp.stack(counts) if cfg.split_ffn else None

    if cfg.remat:
        period = jax.checkpoint(period, policy=jax.checkpoint_policies.dots_saveable if cfg.remat == "dots" else None)
    state, extra = alone((carry, x), leads, 0)
    state, counts = jax.lax.scan(period, state, (*places, jnp.arange(R, dtype=jnp.int32)))
    (carry, x), more = alone(state, tails, lead + P * R)
    extra = jnp.stack(extra + more) if extra + more else None
    return carry, x, counts, extra


def full_kind(cfg: TransformerConfig):
    """The ``kind`` of a hybrid config's full layers: no window; RoPE or none is known while tracing."""
    return {"window": None, "rope": bool(cfg.rope_full_layers)}


def _hybrid_forward(cfg: TransformerConfig, params, x, positions, use_flash: bool):
    B = x.shape[0]
    S0 = jnp.zeros((B, cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim), jnp.float32)

    def linear_fn(carry, x, layer, _):
        h = pre_norm(cfg, layer, "attn_norm", x)
        q, k, v, g, beta, _ = linear_inputs(cfg, layer, h)
        o, _ = gated_delta_chunked(S0, q, k, v, g, beta)
        return carry, linear_out(cfg, layer, x, h, o)

    def conv_fn(carry, x, layer, _):
        return carry, conv_mixer(cfg, layer, x, pre_norm(cfg, layer, "attn_norm", x))[0]

    def full_fn(carry, x, layer, _):
        h = pre_norm(cfg, layer, "attn_norm", x)
        q, k, v = block_qkv(cfg, layer, h, positions, full_kind(cfg))
        return carry, block_attn_out(cfg, layer, x, h, _attention(cfg, q, k, v, use_flash))

    def latent_fn(carry, x, layer, _):
        return carry, latent_attention_expanded(cfg, layer, x, pre_norm(cfg, layer, "attn_norm", x))

    mixers = {"linear": linear_fn, "conv": conv_fn, "full": full_fn, "latent": latent_fn}
    return hybrid_scan(cfg, params, (), x, mixers)[1]


def unembed(cfg: TransformerConfig, params, x):
    """Final norm and output head (the embedding table when tied): [B,T,V] float32."""
    x = _rms_norm(x, params["final_norm"], cfg.norm_eps)
    table = params["embed"] if cfg.tie_embeddings else params["head"]
    return jnp.einsum("btd,vd->btv", x, table.astype(x.dtype)).astype(jnp.float32)


def ring_placement(cfg: TransformerConfig, mesh, sp_axis, T: int):
    """Where ``attention="ring"`` runs over ``sp_axis`` and a ``T``-long
    sequence cuts into two pieces a rank, the ring's zigzag placement
    (``parallel/ring.py::ring_order``, a permutation of ``arange(T)``); else
    None: the tokens stay as they lie."""
    if cfg.attention != "ring" or mesh is None or sp_axis is None:
        return None
    from ray_tpu.parallel.ring import ring_layout, ring_order

    n = mesh.shape[sp_axis]
    return ring_order(T, n) if ring_layout(T, n, causal=True) == "zigzag" else None


def load_ruled(cfg: TransformerConfig) -> bool:
    """Whether a train step moves this config's router bias by load: it has
    one, and its dropless expert layers are the plain layer stack's (a config
    with "linear" layers keeps its counts in ``hybrid_scan``: served only)."""
    return cfg.router_bias and cfg.dropless and not cfg.hybrid


def forward(
    cfg: TransformerConfig,
    params: Dict[str, Any],
    tokens: jax.Array,  # [B, T] int32
    *,
    act_spec: Optional[P] = None,
    mesh: Optional[Mesh] = None,
    sp_axis: Optional[str] = None,
    positions=None,
) -> jax.Array:
    """Returns logits [B, T, V].

    ``positions`` ([T] ints; default ``arange(T)``) are the sequence
    positions of ``tokens`` as they lie; the logits lie the same way. The
    ring wants its own placement (:func:`ring_placement`): ``loss_fn`` places
    the ids, targets and mask once and passes the placement here. Called
    without ``positions``, ``forward`` takes tokens and returns logits in
    natural order whatever the attention: under a zigzag ring it places the
    ids itself and restores the order on the final hidden states (one
    gather a call, before the head)."""
    return forward_and_load(cfg, params, tokens, act_spec=act_spec, mesh=mesh, sp_axis=sp_axis, positions=positions)[0]


def forward_and_load(cfg: TransformerConfig, params, tokens, *, act_spec=None, mesh=None, sp_axis=None,
                     positions=None):
    """:func:`forward`, and what a train step's load rule reads beside the
    logits: (logits, each expert layer's assignment counts over all routed
    experts, int32[expert layers, num_experts]) for a config whose bias the
    rule moves (:func:`load_ruled`), (logits, None) for any other, whose
    program gains nothing."""
    use_flash = cfg.attention == "flash" or (
        cfg.attention == "auto" and backend.on_tpu() and act_spec is None
    )
    if cfg.block > 1:
        if cfg.attention == "flash":
            raise ValueError('block_length > 1 has no attention="flash": the flash kernel is causal')
        use_flash = False  # the grouped einsum carries the block-causal mask
    B, T = tokens.shape
    restore = None
    if positions is None:
        positions = ring_placement(cfg, mesh, sp_axis, T)
        if positions is not None:
            tokens, restore = tokens[:, positions], np.argsort(positions)
    x = embed_tokens(cfg, params, tokens)
    positions = jnp.broadcast_to((jnp.arange(T) if positions is None else jnp.asarray(positions))[None, :], (B, T))

    if cfg.hybrid:
        if act_spec is not None:
            raise ValueError('a config with "linear" or "conv" layers runs on one device: its forward takes no mesh')
        return unembed(cfg, params, _hybrid_forward(cfg, params, x, positions, use_flash)), None
    counted = load_ruled(cfg)

    def layer_fn(stack, x, layer_xs):
        layer, kind, index = layer_xs
        h = pre_norm(cfg, layer, "attn_norm", x)
        if cfg.latent_layers:
            x = latent_attention_expanded(cfg, layer, x, h, positions, use_flash)
        else:
            q, k, v = block_qkv(cfg, layer, h, positions, kind)
            o = _attention(cfg, q, k, v, use_flash, mesh=mesh, sp_axis=sp_axis,
                           window=None if kind is None else kind["window"])
            x = block_attn_out(cfg, layer, x, h, o)
        x, counts = block_ffn(cfg, layer, x, stack=stack, index=index, kernel=act_spec is None, count_routed=counted)
        if act_spec is not None:
            x = jax.lax.with_sharding_constraint(x, act_spec)
        return x, counts if counted else None

    load = []
    for stack, first, last in layer_stacks(cfg, params):
        step = partial(layer_fn, stack)
        leaves = scanned_leaves(cfg, stack)
        if not cfg.scan_layers and leaves is not stack:
            # unrolled, a dropless layer takes its own slice of the experts'
            # weights (moe_ffn_dropless): nothing rides a scan here
            step, leaves = partial(layer_fn, None), stack
        if cfg.remat == "dots":
            step = jax.checkpoint(step, policy=jax.checkpoint_policies.dots_saveable)
        elif cfg.remat:
            step = jax.checkpoint(step)
        if cfg.scan_layers:
            x, counts = jax.lax.scan(step, x, (leaves, layer_kinds(cfg, first, last), jnp.arange(last - first)))
            load.append(counts)
            continue
        # Unrolled layer loop: under remat, scan stacks every saved
        # activation through dynamic-update-slice writes (and reads them
        # back by dynamic-slice in bwd) — measured ~25% of a 602M train
        # step on v5e.  Straight-line layers keep saves as plain buffers.
        for i in range(first, last):
            layer_i = jax.tree_util.tree_map(lambda a: a[i - first], leaves)
            kind = None if cfg.layer_types is None else {
                "window": cfg.layer_windows[i], "rope": cfg.layer_rope[i]}
            x, counts = step(x, (layer_i, kind, i - first))
            load.append(None if counts is None else counts[None])
    if restore is not None:
        x = x[:, restore]
    return unembed(cfg, params, x), jnp.concatenate([c for c in load if c is not None]) if counted else None


def embed_tokens(cfg: TransformerConfig, params, tokens) -> jax.Array:
    """THE embedding input path (training forward AND cached decode import
    this — a drifted copy would make serving logits diverge from training
    by the scale factor): the input scale (``cfg.embed_scale``, default
    sqrt(d)) pairs with the 1/sqrt(d)-std embedding init so the residual
    stream keeps its usual magnitude while tied unembed rows stay
    ~unit-norm (init logits O(1), never an input-copier)."""
    scale = math.sqrt(cfg.d_model) if cfg.embed_scale is None else cfg.embed_scale
    return params["embed"].astype(cfg.dtype)[tokens] * scale


def loss_fn(cfg: TransformerConfig, params, tokens, *, act_spec=None, mesh=None, sp_axis=None) -> jax.Array:
    """Next-token cross entropy: position t predicts tokens[:, t+1].

    The forward runs on the FULL [B, T] batch with the last position masked
    out of the mean, rather than slicing to [B, T-1]: causality makes the
    first T-1 positions' logits identical either way, but odd T-1
    activations force XLA to pad/slice every (8,128)-tiled tensor in the
    step (measured ~2% of a 602M train step), while full-T stays
    tile-aligned."""
    return loss_and_load(cfg, params, tokens, act_spec=act_spec, mesh=mesh, sp_axis=sp_axis)[0]


def loss_and_load(cfg: TransformerConfig, params, tokens, *, act_spec=None, mesh=None, sp_axis=None):
    """(:func:`loss_fn`'s loss, :func:`forward_and_load`'s counts or None):
    what the train step differentiates, the counts its aux."""
    from ray_tpu.parallel._compat import spmd_roll

    B, T = tokens.shape
    # the ring's placement, once a step and on integers: ids, targets and
    # mask are gathered; every layer but attention is position-wise and the
    # loss is a mean, so nothing wide is ever re-laid
    order = ring_placement(cfg, mesh, sp_axis, T)
    placed = tokens if order is None else tokens[:, order]
    logits, load = forward_and_load(cfg, params, placed, act_spec=act_spec, mesh=mesh, sp_axis=sp_axis, positions=order)
    targets = spmd_roll(tokens, -1, axis=1)  # [:, T-1] rolls around: masked
    if order is not None:
        targets = targets[:, order]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    mask = (jnp.arange(T) < T - 1).astype(nll.dtype)[None, :]
    if order is not None:
        mask = mask[:, order]
    return jnp.sum(nll * mask) / (B * (T - 1)), load


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------
ROUTER_BIAS_RATE = 1e-3   # what the load rule moves a router's selection bias by, an expert a step (DeepSeek-V3's gamma)


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
    params/opt shard per ``param_specs`` and batch shards over (dp, sp).
    ``learning_rate``: AdamW's, a number or an optax schedule (step -> rate).

    A config with ``router_bias`` trains its routers' selection bias by load,
    not by gradient (the auxiliary-loss-free balance of DeepSeek-V3,
    ``topk_method: noaux_tc``): after the optimizer's update each expert
    layer's ``b_i += ROUTER_BIAS_RATE * sign(mean(n) - n_i)``, ``n`` the
    step's assignments a routed expert over all ``num_experts`` (this
    program's tokens; a share held masks no selection). The bias is no leaf
    of the optimizer: no moments, no weight decay, no gradient taken. The
    state then carries ``expert_load``, uint32[expert layers, num_experts]:
    the assignments counted since the state was made (the rule uses a step's
    own increment; a host reads the array when it likes). A config without
    the bias has neither and compiles to the step it always did."""
    import optax

    opt = optax.adamw(learning_rate)
    balanced = load_ruled(cfg)
    if cfg.conv_layers:
        raise ValueError('a config with "conv" layers is served, not trained: make_train_step has no gradient test '
                         "over the convolution mixer and its plan's lead, period and tail (forward and loss_fn run it)")
    if cfg.router_bias and cfg.dropless and not balanced:
        raise ValueError('router_bias beside "linear" layers is served, not trained: the load rule reads the '
                         "plain layer stack's assignment counts (forward_and_load)")

    def split_bias(params):
        """(the leaves the optimizer trains, the routers' bias [expert layers, num_experts])."""
        layers = dict(params["layers"])
        bias = layers.pop("router_bias")
        return {**params, "layers": layers}, bias

    def join_bias(trained, bias):
        return {**trained, "layers": {**trained["layers"], "router_bias": bias}}

    def new_state(params):
        state = {"params": params, "opt": opt.init(split_bias(params)[0] if balanced else params),
                 "step": jnp.zeros((), jnp.int32)}
        if balanced:
            state["expert_load"] = jnp.zeros((cfg.expert_layers, cfg.num_experts), jnp.uint32)
        return state

    def init_state(key):
        return new_state(init_params(cfg, key))

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
        if ring_mesh is not None:  # while tracing: the placement is a fact of the compiled step
            from ray_tpu.parallel.ring import ring_layout

            step.ring_layout = ring_layout(tokens.shape[1], mesh.shape[sp_ax], causal=True)
        if balanced:
            return balanced_step(state, tokens)
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, tokens, act_spec=act_spec, mesh=ring_mesh, sp_axis=sp_ax)
        )(state["params"])
        updates, new_opt = opt.update(grads, state["opt"], state["params"])
        new_params = optax.apply_updates(state["params"], updates)
        return {"params": new_params, "opt": new_opt, "step": state["step"] + 1}, loss

    def balanced_step(state, tokens):
        trained, bias = split_bias(state["params"])
        (loss, load), grads = jax.value_and_grad(
            lambda p: loss_and_load(cfg, join_bias(p, bias), tokens, act_spec=act_spec, mesh=ring_mesh, sp_axis=sp_ax),
            has_aux=True)(trained)
        updates, new_opt = opt.update(grads, state["opt"], trained)
        n = load.astype(jnp.float32)
        bias = bias + ROUTER_BIAS_RATE * jnp.sign(jnp.mean(n, axis=-1, keepdims=True) - n).astype(bias.dtype)
        return {"params": join_bias(optax.apply_updates(trained, updates), bias), "opt": new_opt,
                "step": state["step"] + 1, "expert_load": state["expert_load"] + load.astype(jnp.uint32)}, loss

    if mesh is None:
        return init_state, jax.jit(train_step, donate_argnums=(0,))

    pspecs = param_specs(cfg, dp=dp, tp=tp, ep=ep, kv_tp=_kv_tp_ok(cfg, mesh, tp))
    param_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs, is_leaf=lambda x: isinstance(x, P))

    def sharded_init(key):
        # params placed per the TP layout; the (eagerly-run) optax init then
        # inherits each leaf's sharding through zeros_like, so opt state is
        # laid out identically with no explicit spec tree.
        return new_state(jax.tree.map(lambda x, s: jax.device_put(x, s), init_params(cfg, key), param_sh))

    def shard_batch(tokens):
        return jax.device_put(tokens, NamedSharding(mesh, P(dp, None)))

    from ray_tpu.models.common import JittedStep

    step = JittedStep(jax.jit(train_step, donate_argnums=(0,)), shard_batch)
    return sharded_init, step
