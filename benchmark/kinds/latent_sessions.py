"""Traffic kind ``latent_sessions``: ``state_sessions`` for a configuration
whose attention layers are latent (``layer_types`` naming latent attention:
Kimi Linear), so that what is cached a token is one row, the normalised
latent and the key part every head shares, in ONE pool. The schedule, the
driving, the runner's and the engine's comparisons (1) to (4) and ``Served``
are ``state_sessions``'; one comparison more, because none of those four can
see the pool's precision: with seeded weights a latent layer attends almost
evenly over thousands of rows, what it adds to the residual stream is small
beside what the routed experts add, and the logits of a program whose pool
is rounded to 8 bits read like the stated program's
(``benchmark/tools/latent_precision_control.py``, the ``latent_8bit``
control; the configuration file has the readings).

5. *the latent pool itself* (``check_latent_pool``): a seeded prompt of
   ``latent_prompt`` tokens (more than one chunk and a tail after its whole
   pages) is prefilled chunk by chunk through the engine's own programs'
   function into pages scattered over a pool, ``latent_decode_rows`` seeded
   tokens more are fed one a call as a decode step feeds them (so that rows
   are written by the single-token program too, which also reads every row
   before them through the decode kernel), and the rows its block table
   names are read back, layer by layer, and held to the rows the reference's
   latent layers would cache (``make_reference``'s ``latent_rows``).
   ``latent_rows_rel_err``: a row's relative error, the MEDIAN over the
   prompt's tokens, the worst layer's. The median because top-k routing is
   not continuous: a near-tie swaps an expert for a few tokens in every
   expert layer before a latent layer, those tokens' rows then differ by
   tenths, and a norm over all rows reads the swaps (as (1) and (2) do: the
   configuration file has the readings); the median token carries no swap
   and reads the arithmetic: bfloat16 against float32, or what lower
   precision adds. Rows written to other pages or offsets, a latent left
   unnormalised or a key part rotated read of order one.
   ``latent_decode_rows_rel_err``: the same median over the rows the
   single-token calls wrote alone, under a limit of its own (few rows, late
   in the sequence: the configuration file has the readings and what the
   limit can see).
   ``latent_page_rel_err``: the median within each page of 16 rows, the
   WORST page's, the worst layer's: what the median over all tokens cannot
   see, one page among many written or read at the wrong place (a page of
   another's rows reads ~1.4; ``wrong_page_control`` is the same number with
   two of the read-back pages exchanged, computed every time beside it). A
   page's median still carries no swap unless half its tokens swapped.
   ``latent_8bit_exact_share``: of the rows' non-zero values the share an
   8-bit float (``float8_e4m3fn``) holds exactly, a sixteenth for a bfloat16
   pool (four more mantissa bits happen to be zero) and 1 for a pool rounded
   to 8 bits: the stated precision of the cache held as a property, as
   ``state_bf16_exact_share`` holds the state's. The pad lanes past the row
   must read zero (the kernels contract over them).

Parameters (the traffic file): as ``sessions``.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from benchmark import system
from benchmark.kinds import state_sessions

now = state_sessions.now


def check_latent_pool(cfg, params, config: Dict[str, Any], seed: int, reference_params=None) -> Dict[str, Any]:
    """Comparison (5). ``reference_params``: as ``check_state_against_reference`` takes it (the
    builder's control of a program on lowered weights)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.generation import init_paged_cache, paged_forward_counted

    run, cc = config["run"], config["run"]["correctness"]
    C, bs, n0 = run["prefill_chunk_tokens"], run["kv_block_size"], int(cc["latent_prompt"])
    n = n0 + int(cc["latent_decode_rows"])
    rng = np.random.default_rng([seed, 19])
    prompt = rng.integers(1, cfg.vocab_size, size=n)  # the last ``latent_decode_rows`` of it go in one a call
    M = -(-(n + C) // bs)  # the last chunk padded
    cache = init_paged_cache(cfg, 2 * M + 1, bs, slots=1)
    table = rng.permutation(np.arange(1, 2 * M + 1))[:M].astype(np.int32)  # pages out of order, others between them

    @jax.jit
    def chunk(params, cache, toks, bt, start, length):  # as ``state_sessions``' own: the engine's ``_prefill_chunk``
        valid = (jnp.arange(C) < length)[None, :]
        _, cache, _ = paged_forward_counted(cfg, params, cache, bt, toks, start + jnp.arange(C)[None, :],
                                            valid=valid, slots=jnp.zeros((1,), jnp.int32), with_logits=False)
        return cache

    @jax.jit
    def step(params, cache, tok, bt, pos):  # a decode step of one row, as ``state_sessions``' own
        _, cache, _ = paged_forward_counted(cfg, params, cache, bt, tok[:, None], pos[:, None],
                                            slots=jnp.zeros((1,), jnp.int32))
        return cache

    bt = jnp.asarray(table[None, :])
    for pos in range(0, n0, C):
        m = min(C, n0 - pos)
        toks = np.zeros((1, C), np.int32)
        toks[0, :m] = prompt[pos : pos + m]
        cache = chunk(params, cache, jnp.asarray(toks), bt, jnp.int32(pos), jnp.int32(m))
    for pos in range(n0, n):
        cache = step(params, cache, jnp.asarray(prompt[pos : pos + 1], jnp.int32), bt, jnp.asarray([pos], jnp.int32))
    pool = cache["latent"]
    row = cfg.latent_row
    held = pool[:, jnp.asarray(table[: -(-n // bs)])].reshape(pool.shape[0], -1, pool.shape[-1])[:, :n].astype(jnp.float32)
    pad_clean = bool(jnp.all(held[..., row:] == 0))
    held = held[..., :row]
    del cache, pool
    if reference_params is not None:
        params = reference_params()
    ref_logits, _ = system.model_module(config).make_reference(config)
    padded = np.zeros(-(-n // 512) * 512, np.int32)  # causal: what follows changes nothing before it
    padded[:n] = prompt
    _, want = ref_logits(params, jnp.asarray(padded), jnp.asarray([0]), latent_rows=True)
    want = want[:, :n]
    def rows_err(held):
        return jnp.linalg.norm(held - want, axis=-1) / jnp.linalg.norm(want, axis=-1)          # [layers, n]

    def worst_page(by_row):  # the median within a page of whole rows, the worst page's, the worst layer's
        whole_pages = by_row[:, : n // bs * bs].reshape(by_row.shape[0], -1, bs)
        return float(jnp.median(whole_pages, axis=-1).max())

    by_row = rows_err(held)
    by_layer = [float(x) for x in jnp.median(by_row, axis=-1)]
    decode_err = float(jnp.median(by_row[:, n0:], axis=-1).max()) if n > n0 else 0.0
    page_err = worst_page(by_row)
    a, b = (n // bs) // 3 * bs, 2 * ((n // bs) // 3) * bs  # two whole pages of the read-back rows exchanged
    swapped = held.at[:, a : a + bs].set(held[:, b : b + bs]).at[:, b : b + bs].set(held[:, a : a + bs])
    control = worst_page(rows_err(swapped))
    whole = [float(jnp.linalg.norm(held[l] - want[l]) / jnp.linalg.norm(want[l])) for l in range(held.shape[0])]
    bits = np.asarray(held).view(np.uint32)
    exact = float(((bits & 0xFFFFF) == 0)[bits << 1 != 0].mean())  # 3 mantissa bits of float32's 23 (zeros left out)
    err = max(by_layer)
    return {"latent_rows_rel_err": err, "latent_rows_rel_tol": cc["latent_rows_rel_tol"], "by_layer": by_layer,
            "latent_decode_rows_rel_err": decode_err, "latent_decode_rows_rel_tol": cc["latent_decode_rows_rel_tol"],
            "decode_rows": n - n0,
            "latent_page_rel_err": page_err, "latent_page_rel_tol": cc["latent_page_rel_tol"], "wrong_page_control": control,
            "all_rows_rel_err_by_layer": whole, "worst_row_rel_err": float(by_row.max()),
            "latent_8bit_exact_share": exact, "latent_8bit_exact_max": cc["latent_8bit_exact_max"],
            "pad_lanes_zero": pad_clean, "tokens": n, "pages": int(-(-n // bs)),
            "ok": bool(np.isfinite(err) and err < cc["latent_rows_rel_tol"] and decode_err < cc["latent_decode_rows_rel_tol"]
                       and page_err < cc["latent_page_rel_tol"] and exact < cc["latent_8bit_exact_max"] and pad_clean)}


class LatentServed(state_sessions.StateServed):
    """``StateServed``, then comparison (5) beside the engine (it needs a
    few pages and one slot)."""

    def __init__(self, config: Dict[str, Any], seed: int, log, runner_check: bool = True):
        super().__init__(config, seed, log, runner_check)
        if runner_check:
            t = now()
            self.correctness["latent pool"] = check_latent_pool(self.cfg, self.params, config, seed)
            log(f"the latent pool against the reference in {now() - t:.1f} s: {self.correctness['latent pool']}")


def run(ctx) -> Dict[str, Any]:
    served = LatentServed(ctx.config, ctx.seed, ctx.log)
    try:
        return drive(ctx, served, ctx.traffic, ctx.seconds)
    finally:
        served.close()


def drive(ctx, served, p: Dict[str, Any], seconds: float, seed=None) -> Dict[str, Any]:
    """``state_sessions.drive``, and the pool's numbers beside their limits
    (``served``: a ``LatentServed``, or the builder's sweep's ``StateServed``)."""
    out = state_sessions.drive(ctx, served, p, seconds, seed)
    pool = served.correctness.get("latent pool", {})
    for name, limit in (("latent_rows_rel_err", "latent_rows_rel_tol"), ("latent_decode_rows_rel_err", "latent_decode_rows_rel_tol"),
                        ("latent_page_rel_err", "latent_page_rel_tol"), ("latent_8bit_exact_share", "latent_8bit_exact_max")):
        if name in pool:
            out["compared"][name] = [pool[name], pool[limit]]
    return out
