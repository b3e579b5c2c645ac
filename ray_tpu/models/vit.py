"""Vision Transformer: the image model family, TPU-first.

Design notes (no reference counterpart — Ray ships no vision models; this
rounds out the model stack next to the decoder transformer):

- Patch embedding as a single einsum over unfolded patches (a strided
  reshape + matmul — the MXU path; no conv primitive needed).
- Encoder blocks reuse the decoder's RMSNorm/SwiGLU recipe with
  BIDIRECTIONAL flash attention (``causal=False``).
- Learned position embeddings + a CLS token; classification head over the
  CLS representation.
- Same sharding story as the decoder: ``param_specs`` gives the
  Megatron-style TP layout; the train step jits to one XLA program with
  batch sharded over dp.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.models.common import JittedStep, dense_init
from ray_tpu.models.common import patchify as _patchify
from ray_tpu.models.transformer import _dense_ffn, _rms_norm
from ray_tpu.ops import backend
from ray_tpu.ops.attention import flash_attention, mha


@dataclasses.dataclass(frozen=True)
class ViTConfig:
    image_size: int = 224
    patch_size: int = 16
    channels: int = 3
    num_classes: int = 1000
    d_model: int = 384
    n_layers: int = 6
    n_heads: int = 6
    d_ff: int = 1536
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attention: str = "auto"       # auto | flash | dense
    remat: bool = False

    def __post_init__(self):
        if self.image_size % self.patch_size:
            raise ValueError(
                f"image_size {self.image_size} not divisible by patch_size {self.patch_size}"
            )
        if self.d_model % self.n_heads:
            raise ValueError(f"d_model {self.d_model} not divisible by n_heads {self.n_heads}")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def num_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    @property
    def patch_dim(self) -> int:
        return self.patch_size * self.patch_size * self.channels


def init_vit_params(cfg: ViTConfig, key: jax.Array) -> Dict[str, Any]:
    pd = cfg.param_dtype
    d, h, dh, ff = cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff
    ks = jax.random.split(key, 4)

    def dense(k, shape, fan_in):
        return dense_init(k, shape, fan_in, pd)

    def one_layer(k):
        lk = jax.random.split(k, 7)
        return {
            "attn_norm": jnp.ones((d,), pd),
            "wq": dense(lk[0], (d, h, dh), d),
            "wk": dense(lk[1], (d, h, dh), d),
            "wv": dense(lk[2], (d, h, dh), d),
            "wo": dense(lk[3], (h, dh, d), d),
            "ffn_norm": jnp.ones((d,), pd),
            "w1": dense(lk[4], (d, ff), d),
            "w3": dense(lk[5], (d, ff), d),
            "w2": dense(lk[6], (ff, d), ff),
        }

    layer_keys = jax.random.split(ks[1], cfg.n_layers)
    layers = jax.tree.map(lambda *xs: jnp.stack(xs), *[one_layer(k) for k in layer_keys])
    return {
        "patch_embed": dense(ks[0], (cfg.patch_dim, d), cfg.patch_dim),
        "cls_token": jnp.zeros((1, 1, d), pd),
        "pos_embed": (jax.random.normal(ks[2], (1, cfg.num_patches + 1, d), pd) * 0.02).astype(pd),
        "layers": layers,
        "final_norm": jnp.ones((d,), pd),
        "head": dense(ks[3], (d, cfg.num_classes), d),
    }


def vit_param_specs(cfg: ViTConfig, *, tp: str = "tp") -> Dict[str, Any]:
    """Megatron-style TP layout (decoder parity: transformer.param_specs)."""
    return {
        "patch_embed": P(None, tp),
        "cls_token": P(None, None, None),
        "pos_embed": P(None, None, None),
        "layers": {
            "attn_norm": P(None, None),
            "wq": P(None, None, tp, None),
            "wk": P(None, None, tp, None),
            "wv": P(None, None, tp, None),
            "wo": P(None, tp, None, None),
            "ffn_norm": P(None, None),
            "w1": P(None, None, tp),
            "w3": P(None, None, tp),
            "w2": P(None, tp, None),
        },
        "final_norm": P(None),
        "head": P(tp, None),
    }


def patchify(cfg: ViTConfig, images: jax.Array) -> jax.Array:
    """[B, H, W, C] -> [B, num_patches, patch_dim] via strided reshape."""
    return _patchify(images, cfg.patch_size)


def vit_forward(
    cfg: ViTConfig, params: Dict[str, Any], images: jax.Array, *, act_spec: Optional[P] = None
) -> jax.Array:
    """images [B, H, W, C] float -> logits [B, num_classes] f32.

    ``act_spec``: activation sharding under a mesh. Like the decoder, the
    Pallas flash kernel only runs unsharded (GSPMD cannot partition a
    custom call) — sharded runs take the einsum attention path.
    """
    use_flash = cfg.attention == "flash" or (
        cfg.attention == "auto" and backend.on_tpu() and act_spec is None
    )
    x = patchify(cfg, images.astype(cfg.dtype)) @ params["patch_embed"].astype(cfg.dtype)
    B = x.shape[0]
    cls = jnp.broadcast_to(params["cls_token"].astype(cfg.dtype), (B, 1, cfg.d_model))
    x = jnp.concatenate([cls, x], axis=1) + params["pos_embed"].astype(cfg.dtype)

    def layer_fn(x, layer):
        h = _rms_norm(x, layer["attn_norm"])
        q = jnp.einsum("btd,dhk->bthk", h, layer["wq"].astype(h.dtype))
        k = jnp.einsum("btd,dhk->bthk", h, layer["wk"].astype(h.dtype))
        v = jnp.einsum("btd,dhk->bthk", h, layer["wv"].astype(h.dtype))
        qt, kt, vt = (jnp.transpose(t, (0, 2, 1, 3)) for t in (q, k, v))
        if use_flash:
            o = flash_attention(qt, kt, vt, None, False)   # bidirectional
        else:
            o = mha(qt, kt, vt, causal=False)
        o = jnp.transpose(o, (0, 2, 1, 3))
        x = x + jnp.einsum("bthk,hkd->btd", o, layer["wo"].astype(o.dtype))
        h = _rms_norm(x, layer["ffn_norm"])
        x = x + _dense_ffn(layer, h)
        if act_spec is not None:
            x = jax.lax.with_sharding_constraint(x, act_spec)
        return x, None

    step = jax.checkpoint(layer_fn) if cfg.remat else layer_fn
    x, _ = jax.lax.scan(step, x, params["layers"])
    cls_repr = _rms_norm(x[:, 0], params["final_norm"])
    return (cls_repr @ params["head"].astype(cls_repr.dtype)).astype(jnp.float32)


def vit_loss_fn(cfg: ViTConfig, params, images, labels, *, act_spec=None) -> jax.Array:
    logits = vit_forward(cfg, params, images, act_spec=act_spec)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def make_vit_train_step(
    cfg: ViTConfig,
    *,
    mesh: Optional[Mesh] = None,
    learning_rate: float = 1e-3,
    dp: str = "dp",
    tp: str = "tp",
):
    """(init_state, train_step(state, images, labels)) — one XLA program;
    with a mesh, params shard per vit_param_specs and the batch over dp."""
    import optax

    opt = optax.adamw(learning_rate)

    act_spec = None
    dp_ax = None
    if mesh is not None:
        if cfg.attention == "flash":
            raise ValueError(
                'attention="flash" cannot run under a mesh (GSPMD cannot '
                'partition a Mosaic kernel); use "auto" or "dense"'
            )
        dp_ax = dp if dp in mesh.axis_names else None
        act_spec = P(dp_ax, None, None)

    def train_step(state, images, labels):
        loss, grads = jax.value_and_grad(
            lambda p: vit_loss_fn(cfg, p, images, labels, act_spec=act_spec)
        )(state["params"])
        updates, new_opt = opt.update(grads, state["opt"], state["params"])
        new_params = optax.apply_updates(state["params"], updates)
        return {"params": new_params, "opt": new_opt, "step": state["step"] + 1}, loss

    if mesh is None:
        def init_state(key):
            params = init_vit_params(cfg, key)
            return {"params": params, "opt": opt.init(params), "step": jnp.zeros((), jnp.int32)}

        return init_state, jax.jit(train_step, donate_argnums=(0,))

    specs = vit_param_specs(cfg, tp=tp)
    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs, is_leaf=lambda x: isinstance(x, P)
    )

    def sharded_init(key):
        params = jax.tree.map(
            lambda x, s: jax.device_put(x, s), init_vit_params(cfg, key), shardings
        )
        return {"params": params, "opt": opt.init(params), "step": jnp.zeros((), jnp.int32)}

    batch_sharding = NamedSharding(mesh, P(dp_ax, None, None, None))
    label_sharding = NamedSharding(mesh, P(dp_ax))

    def shard_batch(images, labels):
        return (
            jax.device_put(images, batch_sharding),
            jax.device_put(labels, label_sharding),
        )

    return sharded_init, JittedStep(jax.jit(train_step, donate_argnums=(0,)), shard_batch)
