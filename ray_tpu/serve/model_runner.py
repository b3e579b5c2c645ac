"""The model runner: a serving engine's device state and every program over it.

One :class:`ModelRunner` holds the parameters (sharded under a mesh, int8 with
``quantize``), the paged pool, a config with linear or conv layers' slot states and
snapshot pool, the sampling key and the rows' last tokens, and builds every
jitted callable once, so the engine's loop never traces. Each method enqueues
its program and returns without waiting: the caller decides when to read.
Nothing here knows a request queue, a page's owner or a lock.

Beside the decode program lives the description of what one step of it is
(:class:`TokenSteps`, :class:`BlockSteps`; ``runner.steps``, chosen once from
the config): how far a step carries a row, which rows still owe one, what the
host uploads to join a row, and what a read step hands each row.

- **Token steps.** ``decode_chunk`` tokens a row a dispatch; the host knows a
  ``max_tokens`` finish by count, ahead of the device.
- **Generation by diffusion over blocks.** A config with ``block_length`` > 1
  (SDAR) switches the decode program to block steps: every row carries its
  block of ``block_length`` positions (mask ids among them), which positions
  are masked and its step counters as device-resident state, a step is one
  forward of the block over everything committed plus the block itself and
  unmasks the most confident positions inside the program, and a row whose
  block holds no mask runs the commit forward, whose K/V are final, and
  yields the block's tokens: 0 tokens a row on a denoise step, up to
  ``block_length`` on a commit, rows of one batch in different phases. The
  host knows each row's schedule by count, so the one step in flight stays.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.generation import (
    copy_paged_page,
    copy_sequence_state,
    export_paged_page,
    filter_top_k_top_p,
    init_paged_cache,
    init_sequence_state,
    open_blocks,
    page_pools,
    paged_block_step,
    paged_cache_spec,
    paged_forward_counted,
    select_rows,
    write_paged_pages,
    zero_sequence_state,
)
from ray_tpu.models.transformer import TransformerConfig
from ray_tpu.ops import backend
from ray_tpu.ops.gated_delta import lane_group, unpack_state

def _abstract(x):
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)


class TokenSteps:
    """Decode steps of an autoregressive config: ``chunk`` tokens a row a
    dispatch. A row's last sampled token lives on the device (what the last
    dispatched step returned); ``join`` carries the tokens the HOST sampled
    since the last dispatch (a sequence fresh from prefill or migration), -1
    elsewhere: the decode program takes a row's token from here where it is
    >= 0."""

    block = 1  # prefill caches every prompt token ...
    first_from_prefill = True  # ... and the prompt's last logits give the first token
    # sampled tokens a request never writes back: its last. It owes dispatches
    # for ``max_tokens - 1`` (the finish is known by count, ahead of the
    # device), and the pool holds the K/V of all it generated but that one
    unwritten = 1

    def __init__(self, B: int, chunk: int):
        # positions a step writes a row (the copy-on-write span), and the decode steps a dispatch counts for
        self.span = self.count = chunk
        self.join = np.full(B, -1, np.int32)

    def fresh_rows(self, B: int):
        return jnp.zeros(B, jnp.int32)  # no row reads its last token before joining from the host

    def abstract_rows(self, dev_toks):
        toks = jax.ShapeDtypeStruct(dev_toks.shape, jnp.int32)
        return toks, toks

    def join_row(self, req, slot: int, tok0: int) -> int:
        """A prefilled (or migrated) request joins the batch with its first
        token; returns the position its next step writes."""
        self.join[slot] = tok0
        return len(req.prompt)

    def uploads(self):
        """A copy of the join mirror for the device (a transfer may read its
        host buffer after the call returns, and the mirror changes at once)."""
        return jnp.asarray(self.join.copy())

    def clear(self) -> None:
        self.join[:] = -1

    def advance(self, rows, pos) -> None:
        for i, req in rows:
            req.dispatched += self.span
            pos[i] += self.span

    def read(self, out):
        return np.asarray(out)  # [B, K]

    def handed(self, sampled, rows, commits):
        """(slot, request, its new tokens, None) a row a token, in the order sampled."""
        for k in range(sampled.shape[1]):
            for i, req in rows:
                yield i, req, [int(sampled[i, k])], None

    def dropped(self, n: int) -> None:  # a cancelled row leaves nothing under way
        pass

    def stats(self, decode_steps: int) -> Dict[str, Any]:
        return {}


class BlockSteps:
    """Decode steps of a diffusion config: one block step a dispatch. A row's
    schedule is known by count (a block of ``m`` masked positions takes
    ``min(m, steps)`` denoise forwards, then the commit), so positions and
    the ``max_tokens`` count advance at the commit's dispatch, ahead of the
    device; which rows committed is read back with the tokens. Rows join with
    their first block: the prompt's tail as known positions, and the
    request's steps a block."""

    first_from_prefill = False  # no token comes from prefill; the first come with the block's commit
    # every emitted token is committed, and the last block is written whole
    # (a page holds whole blocks): a row owes dispatches for all ``max_tokens``
    unwritten = 0
    count = 1

    def __init__(self, cfg: TransformerConfig, B: int):
        self.cfg = cfg
        # prefill caches the prompt's whole blocks (the rest opens the first
        # block as known positions), and a step writes a block a row
        self.block = self.span = cfg.block
        self.join = {
            "row": np.zeros(B, bool),
            "known": np.zeros(B, np.int32),
            "toks": np.zeros((B, self.span), np.int32),
            "steps": np.ones(B, np.int32),
        }
        # forwards of live rows (denoise and commit), blocks committed, tokens
        # they emitted and positions they unmasked, and blocks a cancelled row
        # left uncommitted
        self.row_forwards = self.commits = self.tokens_emitted = self.tokens_unmasked = self.blocks_dropped = 0

    def fresh_rows(self, B: int):
        return open_blocks(self.cfg, jnp.ones(B, jnp.int32))  # the rows' blocks

    def abstract_rows(self, dev_toks):
        return jax.tree.map(_abstract, (dev_toks, self.uploads()))

    def join_row(self, req, slot: int, tok0) -> int:
        """The prompt is in the paged cache as far as its whole blocks go:
        the row joins with its first block opened, the prompt's tail as its
        known positions."""
        fill = len(req.prompt) - len(req.prompt) % self.span
        known = len(req.prompt) - fill
        req.block_known = known
        req.forwards_left = min(self.span - known, req.denoising_steps) + 1
        self.join["row"][slot] = True
        self.join["known"][slot] = known
        self.join["toks"][slot, :known] = req.prompt[fill:]
        self.join["steps"][slot] = req.denoising_steps
        return fill

    def uploads(self):
        """Copies of the join mirrors for the device (a transfer may read its
        host buffer after the call returns, and the mirrors change at once)."""
        return {k: jnp.asarray(v.copy()) for k, v in self.join.items()}

    def clear(self) -> None:
        self.join["row"][:] = False

    def advance(self, rows, pos) -> Dict[int, int]:
        """slot -> known positions of the block this step commits."""
        Bk = self.span
        commits: Dict[int, int] = {}
        for i, req in rows:
            req.forwards_left -= 1
            if req.forwards_left == 0:  # this step commits the row's block; the next opens all masked
                commits[i] = req.block_known
                req.dispatched += Bk - req.block_known
                pos[i] += Bk
                req.block_known = 0
                req.forwards_left = min(Bk, req.denoising_steps) + 1
        return commits

    def read(self, out):
        return jax.device_get(out)

    def handed(self, done, rows, commits):
        """Nothing on a denoise step; on a commit (slot, request, the block's
        new tokens, the denoising step at which each took its value), cut at
        ``max_tokens`` or after an EOS: what the block holds beyond is dropped."""
        self.row_forwards += len(rows)
        for i, req in rows:
            known = commits.get(i)
            if bool(done["committed"][i]) != (known is not None):
                raise RuntimeError(f"block step out of step with its schedule in slot {i}: the device "
                                   f"{'committed' if known is None else 'did not commit'} a block")
            if known is None:
                continue
            toks = done["toks"][i, known:].tolist()[: req.max_tokens - len(req.generated)]
            if req.eos_id is not None and req.eos_id in toks:
                toks = toks[: toks.index(req.eos_id) + 1]
            self.commits += 1
            self.tokens_emitted += len(toks)
            self.tokens_unmasked += self.span - known
            yield i, req, toks, done["unmasked_at"][i, known : known + len(toks)].tolist()

    def dropped(self, n: int) -> None:
        """A row of a diffusion config always has a block under way: its
        tentative K/V go with its pages, which nothing shared."""
        self.blocks_dropped += n

    def stats(self, decode_steps: int) -> Dict[str, Any]:
        return {
            "block_length": self.span,
            "block_steps": decode_steps,
            "block_row_forwards": self.row_forwards,
            "block_commits": self.commits,
            "tokens_emitted": self.tokens_emitted,
            "tokens_unmasked": self.tokens_unmasked,
            "blocks_dropped": self.blocks_dropped,
        }


class ModelRunner:
    """Parameters, pools and programs of one model on one device or mesh.
    The pool is sized by the caller (``kv_num_blocks`` pages of
    ``kv_block_size`` tokens, ``B`` slots, ``n_snapshots`` snapshot entries)."""

    def __init__(self, cfg: TransformerConfig, params: Any, *, B: int, S: int, kv_block_size: int, kv_num_blocks: int,
                 n_snapshots: int = 0, top_k: Optional[int] = None, top_p: Optional[float] = None,
                 quantize: bool = False, quantize_min_size: int = 4096, mesh: Optional[Any] = None, tp: str = "tp",
                 decode_chunk: int = 1):
        self.cfg = cfg
        self.B = B
        self.kv_block_size = kv_block_size
        self.kv_num_blocks = kv_num_blocks
        self.n_snapshots = n_snapshots
        self.table_shape = (B, -(-S // kv_block_size))
        self.snaps = None  # the device arrays of the snapshot pool (``reset``)
        self.state_bytes_per_slot = 0
        self.steps = TokenSteps(B, decode_chunk) if cfg.block == 1 else BlockSteps(cfg, B)
        self.kv_sharding = None
        if mesh is not None:
            # tensor-parallel serving: params shard per the Megatron layout
            # (ray_tpu.models.transformer.param_specs), the KV pool over its
            # heads when tp divides them (each device then holds whole pages
            # of its own heads); GSPMD partitions the einsum attention, so
            # decode collectives ride ICI. The Pallas decode kernel is
            # bypassed (GSPMD cannot partition a Mosaic kernel).
            from jax.sharding import NamedSharding

            from ray_tpu.models.transformer import _kv_tp_ok, shard_params

            params = shard_params(params, mesh, cfg, tp=tp, ep=tp)
            self.kv_sharding = NamedSharding(
                mesh, paged_cache_spec(tp if _kv_tp_ok(cfg, mesh, tp) else None)
            )
        if quantize:
            # weight-only int8 on the stacked layer LINEAR weights (norm
            # gains and the embedding stay full precision). Scales ride the
            # layer scan as xs, so dequant happens per layer IN the scan
            # body — only one layer is ever wide, never a whole-tree copy.
            from ray_tpu.ops.quantization import quantize_layers

            q_layers, self._layer_scales = quantize_layers(
                params["layers"], min_size=quantize_min_size
            )
            self.params = {**params, "layers": q_layers}
        else:
            self._layer_scales = None
            self.params = params

        self.reset()
        self.key = jax.random.key(np.random.randint(0, 2**31 - 1))

        cfg_ = cfg
        # the dropless expert layers' own counters (models/generation.py,
        # ``paged_forward_counted``): the prefill and decode programs return
        # them beside the tokens. For any other config the programs drop them
        moe_counted = cfg.dropless
        layer_scales = self._layer_scales
        # under a mesh the einsum path partitions via GSPMD; the Pallas
        # paged kernels (decode and prefill) stay for the single-device engine
        use_kernel = None if mesh is None else False
        # which write of a call's K and V rows the programs below hold: a fact of
        # how they are built, recorded once (``stats()["kv_write"]``). "kernel":
        # whole pages by ``ops.decode_attention.paged_write_rows``; "scatter": XLA's, a row an update
        self.kv_write = "kernel" if (backend.on_tpu() if use_kernel is None else use_kernel) else "scatter"
        # under a mesh a program that returns the pool returns it as it was
        # placed: the donated buffers are updated where they lie and the next
        # call finds the sharding it was compiled for (left to itself GSPMD
        # re-shards a replicated pool). None, jit's default, on one device
        kv_sharding = self.kv_sharding

        def pool_among(n_outputs: int):  # the expert counts, where returned, come last
            rest = (None,) * (n_outputs - 2 + moe_counted)
            return None if kv_sharding is None else (None, kv_sharding) + rest

        top_k_, top_p_ = top_k, top_p

        def _sample_impl(key, logits, temps):
            """Per-slot temperature; temp <= 0 means greedy."""
            greedy = temps <= 0.0
            t = jnp.where(greedy, 1.0, temps)
            scaled = filter_top_k_top_p(logits / t[:, None], top_k_, top_p_)
            keys = jax.random.split(key, logits.shape[0])
            sampled = jax.vmap(jax.random.categorical)(keys, scaled)
            return jnp.where(greedy, jnp.argmax(logits, -1), sampled).astype(jnp.int32)

        self._sample = jax.jit(_sample_impl)

        # the decode program: K sequential decode+sample steps inside ONE
        # jitted lax.scan (K = decode_chunk; 1 = classic per-token
        # stepping), so the host pays one dispatch/readback round trip per
        # K tokens. One key split per generated token.  The cache is
        # donated: the runner holds the only reference and reassigns, so
        # XLA updates the pool's buffers in place.  It also hands back every
        # row's last token as a device array, which the next run takes as
        # it is: the loop dispatches that run before it reads this one's.
        K_chunk = decode_chunk
        block = cfg.block
        hybrid = cfg.hybrid

        @functools.partial(jax.jit, donate_argnums=(1,), out_shardings=pool_among(2))
        def _prefill_chunk(params, cache, toks, bt, start, length, slot=None):
            """toks [1, C] chunk-padded; bt [1, M]; start/length traced,
            so every chunk of every prompt at width C shares ONE
            compile. Writes K/V for the chunk's ``length`` real tokens
            through the block table and returns the last real token's
            logits [V] (only the final chunk's are consumed). ``slot`` [1]
            (a config with linear or conv layers): where the sequence's state lives."""
            C = toks.shape[1]
            positions = start + jnp.arange(C)[None, :]
            valid = (jnp.arange(C) < length)[None, :]
            logits, cache, moe = paged_forward_counted(
                cfg_, params, cache, bt, toks, positions,
                valid=valid, layer_scales=layer_scales, use_decode_kernel=use_kernel,
                with_logits=block == 1, slots=slot,
            )
            if logits is None:
                # no token comes from a diffusion config's prefill: the head is
                # not run, and what is waited for is a word of the written pool
                last = cache["k"][0, 0, 0, :1].astype(jnp.float32)
            else:
                last = jax.lax.dynamic_index_in_dim(logits[0], length - 1, 0, keepdims=False)
            return (last, cache, moe) if moe_counted else (last, cache)

        def _block_step(params, cache, state, join, pos, temps, key, bt):
            """The decode program of a diffusion config: one block step
            (``models/generation.paged_block_step``). ``state``: the rows'
            blocks as the last run left them, never read by the host in
            between; ``join["row"]`` where the host opened a row's first
            block since then. Hands back what the step finished (which rows
            committed, and their tokens), the pool, the key and the state."""
            state = select_rows(join["row"], open_blocks(cfg_, join["steps"], join["known"], join["toks"]), state)
            key, sub = jax.random.split(key)
            _, cache, state, done, moe = paged_block_step(
                cfg_, params, cache, bt, state, pos, live=bt[:, 0] > 0,
                sample=lambda flat: _sample_impl(sub, flat, jnp.repeat(temps, block)),
                use_decode_kernel=use_kernel,
            )
            out = (done, cache, key, state)
            return out + (moe,) if moe_counted else out

        @functools.partial(jax.jit, donate_argnums=(1,), out_shardings=pool_among(4))
        def _decode_k_paged(params, cache, toks, join, pos, temps, key, bt):
            if block > 1:
                return _block_step(params, cache, toks, join, pos, temps, key, bt)
            # ``toks``: what the last run of this program returned, never
            # read by the host in between; ``join`` >= 0 where the host
            # sampled a row's token itself since then (its first)
            toks = jnp.where(join >= 0, join, toks)
            # a live row's first page is never the garbage page 0 (idle
            # rows decode through all-zero tables): the expert layers
            # count the live rows' assignments only
            # (and an idle row's recurrent state or convolution tail stays as it is)
            live = (bt[:, 0] > 0)[:, None] if moe_counted or hybrid else None
            slots = jnp.arange(bt.shape[0], dtype=jnp.int32) if hybrid else None

            def body(carry, _):
                cache, toks, pos, key = carry
                logits, cache, moe = paged_forward_counted(
                    cfg_, params, cache, bt, toks[:, None], pos[:, None],
                    layer_scales=layer_scales, use_decode_kernel=use_kernel, valid=live, slots=slots,
                )
                key, sub = jax.random.split(key)
                nxt = _sample_impl(sub, logits[:, 0], temps)
                return (cache, nxt, pos + 1, key), (nxt, moe)

            (cache, last, _, key), (toks_k, moe) = jax.lax.scan(
                body, (cache, toks, pos, key), None, length=K_chunk
            )
            out = (jnp.swapaxes(toks_k, 0, 1), cache, key, last)  # [B, K] ... [B]
            if moe_counted:
                out += (jax.tree.map(lambda a: a.sum(0), moe),)  # over the K steps
            return out

        # copy-on-write primitive (models/generation.copy_paged_page):
        # donated so XLA copies the page in place in the pool buffers
        self._copy_page = jax.jit(copy_paged_page, donate_argnums=(0,), out_shardings=kv_sharding)

        # Land a migrated block set ``[N, 2, L, block_size, Hkv, Dh]`` into
        # the pool in ONE donated scatter: per-block writes cost a
        # dispatch each — 24 blocks of a long prompt stall the engine
        # loop ~10ms on the bench box. Callers bucket-pad N by repeating
        # the last (block, page) pair (the duplicate scatter indices stay
        # idempotent), keeping the compile count at O(log blocks), not
        # one per block count.
        self._write_blocks = jax.jit(write_paged_pages, donate_argnums=(0,), out_shardings=kv_sharding)
        # the page index is traced: every exported block shares one compile
        self._export_page = jax.jit(functools.partial(export_paged_page, cfg_))
        self._prefill_chunk = _prefill_chunk
        self._decode_k_paged = _decode_k_paged
        if hybrid:
            # a slot's state at admission: zero, or a snapshot's copy; and the
            # snapshots of one dispatch, one program (``n`` of the ``B`` pairs
            # are real). Slots and entries are traced: one compile each
            self._zero_state = jax.jit(zero_sequence_state, donate_argnums=(0,))
            self._restore_state = jax.jit(copy_sequence_state, donate_argnums=(0,))

            def _snapshot_rows(snaps, cache, slots, entries, n):
                return jax.lax.fori_loop(
                    0, n, lambda i, snaps: copy_sequence_state(snaps, cache, entries[i], slots[i]), snaps)

            self._snapshot_state = jax.jit(_snapshot_rows, donate_argnums=(0,))

    def reset(self) -> None:
        """(Re)allocate the device state — also the recovery path after a
        failed donated step leaves the old buffers deleted."""
        cfg = self.cfg
        init = functools.partial(init_paged_cache, cfg, self.kv_num_blocks, self.kv_block_size,
                                 **({"slots": self.B} if cfg.hybrid else {}))
        if cfg.hybrid:
            # the slots' states went with the cache: so do the snapshots of them
            self.snaps = init_sequence_state(cfg, self.n_snapshots) if self.n_snapshots else None
            one = init_sequence_state(cfg, 1)
            self.state_bytes_per_slot = int(sum(a.size * a.dtype.itemsize for a in one.values()))
        if self.kv_sharding is not None:
            # each device zeroes its own shard: the whole pool never lies on one
            init = jax.jit(init, out_shardings=self.kv_sharding)
        self.cache = init()
        # bytes a cached token takes in the pools as built, all attention layers
        pools = [self.cache[name] for name in page_pools(self.cache)]
        self.kv_bytes_per_token = int(sum(a.shape[0] * a.shape[-1] * a.dtype.itemsize for a in pools))
        # the rows' last tokens (a diffusion config: their blocks) as the
        # decode program last returned them: part of the same device state (a
        # step that failed in flight leaves its outputs poisoned)
        self.dev_toks = self.steps.fresh_rows(self.B)

    def cache_lost(self) -> bool:
        """A donated program consumed the cache, then failed."""
        return next(iter(self.cache.values())).is_deleted()

    def traced_decode(self):
        """The decode program as the loop runs it (same params, cache and
        slot-array shapes), traced: ``.lower()`` it for a platform."""
        params, cache = jax.tree.map(_abstract, (self.params, self.cache))
        state, join = self.steps.abstract_rows(self.dev_toks)
        toks = jax.ShapeDtypeStruct((self.B,), jnp.int32)
        temps = jax.ShapeDtypeStruct((self.B,), jnp.float32)
        bt = jax.ShapeDtypeStruct(self.table_shape, jnp.int32)
        return self._decode_k_paged.trace(params, cache, state, join, toks, temps, self.key, bt)

    def lowered_decode_text(self) -> str:
        """StableHLO text of the decode program. ``chip_smoke.py`` looks for
        ``tpu_custom_call`` in it: which attention path the engine compiled
        is read from the program, not assumed from a flag."""
        return self.traced_decode().lower().as_text()

    def traced_prefill_chunk(self, chunk: int):
        """The prefill program at a chunk of ``chunk`` tokens, traced."""
        params, cache = jax.tree.map(_abstract, (self.params, self.cache))
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
        at = (i32(1),) if self.cfg.hybrid else ()
        return self._prefill_chunk.trace(params, cache, i32(1, chunk), i32(1, self.table_shape[1]), i32(), i32(), *at)

    # -- programs: each enqueues and returns without waiting ----------------
    def prefill_chunk(self, toks, bt, start: int, n: int, slot: int):
        """One chunk of a prompt (``toks`` [1, C] padded, ``n`` real, at
        position ``start``) through the table row ``bt`` [1, M] of ``slot``.
        Returns the last real token's logits and the expert counts."""
        bt = jnp.asarray(bt)
        at = (jnp.asarray([slot], jnp.int32),) if self.cfg.hybrid else ()
        logits, self.cache, *moe = self._prefill_chunk(
            self.params, self.cache, jnp.asarray(toks), bt, jnp.int32(start), jnp.int32(n), *at,
        )
        return logits, moe

    def step(self, join, pos, temps, bt):
        """One decode step over all rows; returns what it yields (tokens
        ``[B, K]``, or what a block step finished) and the expert counts."""
        out, self.cache, self.key, self.dev_toks, *moe = self._decode_k_paged(
            self.params, self.cache, self.dev_toks, join, pos, temps, self.key, bt,
        )
        return out, moe

    def sample_first(self, logits, temperature: float) -> int:
        """The first token of a sequence from its prompt's last logits (read
        at once: the host emits it)."""
        self.key, sub = jax.random.split(self.key)
        return int(self._sample(sub, logits[None, :], jnp.asarray([temperature], jnp.float32))[0])

    def copy_page(self, src: int, dst: int) -> None:
        self.cache = self._copy_page(self.cache, jnp.int32(src), jnp.int32(dst))

    def write_blocks(self, blocks, pages) -> None:
        self.cache = self._write_blocks(self.cache, blocks, pages)

    def export_pages(self, pages: List[int]) -> list:
        """Copies of ``pages`` as NEW buffers (they survive later donated
        steps), waited for."""
        arrays = [self._export_page(self.cache, page) for page in pages]
        if arrays:
            jax.block_until_ready(arrays[-1])
        return arrays

    # ``program``: the engine's own handle on the two programs of a slot's
    # state, which ``benchmark/tools/state_precision_control.py`` swaps there
    def zero_state(self, slot: int, program=None) -> None:
        with jax.profiler.TraceAnnotation("llm::state_restore"):
            self.cache = (program or self._zero_state)(self.cache, jnp.int32(slot))

    def restore_state(self, slot: int, entry: int, program=None) -> None:
        with jax.profiler.TraceAnnotation("llm::state_restore"):
            self.cache = (program or self._restore_state)(self.cache, self.snaps, jnp.int32(slot), jnp.int32(entry))

    def snapshot_rows(self, slots, entries, n: int) -> None:
        """Copy the states of ``slots[:n]`` into the snapshot pool's
        ``entries[:n]``, behind the program that produced them."""
        with jax.profiler.TraceAnnotation("llm::state_snapshot"):
            self.snaps = self._snapshot_state(self.snaps, self.cache, jnp.asarray(slots), jnp.asarray(entries),
                                              jnp.int32(n))

    def read_snapshot(self, snaps, entry: int):
        """Entry ``entry`` of the snapshot arrays ``snaps`` as float32: the
        recurrent state [linear layers, heads, key dim, value dim], or, of a
        config whose state is its conv layers' tails alone, those: [conv
        layers, width - 1, d], oldest input first."""
        if "state" not in snaps:
            tails = np.asarray(snaps["conv"][:, entry].astype(jnp.float32))
            return tails.reshape(tails.shape[0], self.cfg.conv_width - 1, -1)
        group = lane_group(self.cfg.linear_heads, self.cfg.linear_value_dim)
        return np.asarray(unpack_state(snaps["state"][:, entry], group))
