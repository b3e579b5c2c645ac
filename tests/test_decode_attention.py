"""Decode-attention kernel tests (interpret mode on CPU): vs reference
einsum over ragged lengths, GQA groups, multi-block streaming, and the
forward_with_cache integration."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.decode_attention import decode_attention


def _reference(q, kc, vc, lengths):
    B, H, D = q.shape
    Hkv, S = kc.shape[1], kc.shape[2]
    n_rep = H // Hkv
    keys = jnp.repeat(kc, n_rep, axis=1).astype(jnp.float32)   # [B, H, S, D]
    vals = jnp.repeat(vc, n_rep, axis=1).astype(jnp.float32)
    s = jnp.einsum("bhd,bhsd->bhs", q.astype(jnp.float32), keys) / math.sqrt(D)
    mask = jnp.arange(S)[None, None, :] < lengths[:, None, None]
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhs,bhsd->bhd", p, vals)


@pytest.mark.parametrize("n_rep", [1, 4])
@pytest.mark.parametrize("block_s", [64, 128])
def test_matches_reference(n_rep, block_s):
    rng = np.random.default_rng(0)
    B, Hkv, S, D = 3, 2, 200, 32  # S not a block multiple: exercises padding
    H = Hkv * n_rep
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
    lengths = jnp.asarray([1, 77, 200], jnp.int32)
    out = decode_attention(q, kc, vc, lengths, block_s=block_s)
    ref = _reference(q, kc, vc, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("n_rep", [1, 4])
@pytest.mark.parametrize("block_size", [16, 128])
def test_paged_kernel_matches_reference_on_the_same_cache(n_rep, block_size):
    """The dense cache cut into shuffled pages (groups of 8 pages and of
    one): the paged kernel gives this file's reference and the dense
    kernel's answer."""
    from ray_tpu.ops.decode_attention import paged_decode_attention

    rng = np.random.default_rng(2)
    B, Hkv, S, D = 3, 2, 256, 32
    M = S // block_size
    q = jnp.asarray(rng.standard_normal((B, Hkv * n_rep, D)), jnp.float32)
    kc, vc = (jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32) for _ in range(2))
    lengths = jnp.asarray([1, 129, 256], jnp.int32)
    bt = rng.permutation(np.arange(1, B * M + 1)).reshape(B, M).astype(np.int32)

    def pool(cache):  # [1, pages, block_size, Hkv*D], page 0 the garbage page
        pages = jnp.transpose(cache, (0, 2, 1, 3)).reshape(B * M, block_size, Hkv * D)
        return jnp.zeros((1, B * M + 1, block_size, Hkv * D), jnp.float32).at[0, bt.reshape(-1)].set(pages)

    out = paged_decode_attention(q, pool(kc), pool(vc), jnp.asarray(bt), lengths, jnp.int32(0))
    np.testing.assert_allclose(np.asarray(out), np.asarray(_reference(q, kc, vc, lengths)), rtol=2e-5, atol=2e-5)
    dense = decode_attention(q, kc, vc, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), rtol=2e-5, atol=2e-5)


def test_bf16_cache():
    rng = np.random.default_rng(1)
    B, Hkv, S, D = 2, 2, 64, 16
    q = jnp.asarray(rng.standard_normal((B, 4, D)), jnp.bfloat16)
    kc = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.bfloat16)
    vc = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.bfloat16)
    lengths = jnp.asarray([5, 64], jnp.int32)
    out = decode_attention(q, kc, vc, lengths)
    ref = _reference(q, kc, vc, lengths)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=3e-2, atol=3e-2
    )


def test_forward_with_cache_kernel_path_matches_einsum():
    from ray_tpu.models import TransformerConfig, init_params
    from ray_tpu.models.generation import forward_with_cache, init_cache

    cfg = TransformerConfig(
        vocab_size=53, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=64,
        attention="dense", dtype=jnp.float32,
    )
    params = init_params(cfg, jax.random.key(2))
    cache = init_cache(cfg, 2, 24)
    # prefill via the einsum path
    toks = jnp.asarray(np.random.default_rng(3).integers(0, 53, (2, 6)), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(6)[None, :], (2, 6))
    _, cache = forward_with_cache(cfg, params, cache, toks, pos)
    # one decode step, both paths, same cache
    tok = jnp.asarray([[7], [9]], jnp.int32)
    dpos = jnp.asarray([[6], [6]], jnp.int32)
    l_kernel, _ = forward_with_cache(cfg, params, cache, tok, dpos, use_decode_kernel=True)
    l_einsum, _ = forward_with_cache(cfg, params, cache, tok, dpos, use_decode_kernel=False)
    np.testing.assert_allclose(
        np.asarray(l_kernel), np.asarray(l_einsum), rtol=2e-4, atol=2e-4
    )


def test_generate_with_kernel_matches():
    """Full generate loop with the kernel forced on equals the einsum loop."""
    import functools

    from ray_tpu.models import TransformerConfig, init_params
    from ray_tpu.models.generation import decode_step, init_cache, prefill

    cfg = TransformerConfig(
        vocab_size=41, d_model=16, n_layers=1, n_heads=2, d_ff=32,
        attention="dense", dtype=jnp.float32,
    )
    params = init_params(cfg, jax.random.key(4))
    prompt = jnp.asarray([[3, 5, 8]], jnp.int32)
    outs = {}
    for use in (True, False):
        cache = init_cache(cfg, 1, 8)
        logits, cache = prefill(cfg, params, cache, prompt, jnp.asarray([3], jnp.int32))
        toks = []
        pos = jnp.asarray([3], jnp.int32)
        for _ in range(4):
            t = jnp.argmax(logits, -1).astype(jnp.int32)
            toks.append(int(t[0]))
            logits, cache = __import__("ray_tpu.models.generation", fromlist=["forward_with_cache"]).forward_with_cache(
                cfg, params, cache, t[:, None], pos[:, None], use_decode_kernel=use
            )
            logits = logits[:, 0]
            pos = pos + 1
        outs[use] = toks
    assert outs[True] == outs[False]


def test_large_n_rep_sublane_rounding():
    """n_rep > 8 and not a multiple of 8 (rounds up to 16 sublanes)."""
    rng = np.random.default_rng(5)
    B, Hkv, n_rep, S, D = 2, 2, 12, 64, 16
    q = jnp.asarray(rng.standard_normal((B, Hkv * n_rep, D)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
    lengths = jnp.asarray([10, 64], jnp.int32)
    out = decode_attention(q, kc, vc, lengths)
    ref = _reference(q, kc, vc, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_block_shrinks_to_divisor_instead_of_padding():
    """S=600 with block_s=512 -> block shrinks to 300 (divisor), no pad."""
    rng = np.random.default_rng(6)
    B, Hkv, S, D = 2, 1, 600, 32
    q = jnp.asarray(rng.standard_normal((B, 2, D)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
    lengths = jnp.asarray([600, 123], jnp.int32)
    out = decode_attention(q, kc, vc, lengths, block_s=512)
    ref = _reference(q, kc, vc, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_zero_length_rows_yield_zeros():
    """An empty slot (lengths == 0) must emit zeros, not garbage-V means."""
    rng = np.random.default_rng(7)
    B, Hkv, S, D = 3, 2, 64, 16
    q = jnp.asarray(rng.standard_normal((B, 4, D)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
    lengths = jnp.asarray([0, 5, 0], jnp.int32)
    out = np.asarray(decode_attention(q, kc, vc, lengths))
    assert (out[0] == 0).all() and (out[2] == 0).all()
    ref = _reference(q, kc, vc, lengths)
    np.testing.assert_allclose(out[1], np.asarray(ref[1]), rtol=2e-5, atol=2e-5)
