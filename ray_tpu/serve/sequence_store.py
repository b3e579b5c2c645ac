"""The sequence store: what state a served sequence keeps, and who owns it.

One :class:`SequenceStore` owns the host's books of the device pools the
model runner (``serve/model_runner.py``) holds: which pages a slot names,
which a radix node caches, which snapshot entry belongs to whom, and the
block sets staged for migration. It reaches the device only through the
runner's methods, and shares the engine's one lock: a ``*_locked`` method is
called with it held, every other takes it itself.

- **Paged KV cache.** K/V live in a shared HBM pool of fixed-size pages
  ``[L, num_blocks, block_size, Hkv*Dh]``; each slot names its pages in a
  static-shape ``int32[B, max_blocks_per_slot]`` block table
  (PagedAttention, Kwon et al. 2023). Admission is block-aware — a request
  is admitted when enough PAGES are free, so HBM capacity is proportional
  to tokens actually reserved, not ``B * max_len``. Under a mesh the pool
  shards over its KV heads (``models/generation.paged_cache_spec``) and the
  same admission, prefill and decode programs run, partitioned by GSPMD.
- **Prefix-aware KV reuse (on by default).** Finished
  requests publish the full blocks of prompt+completion into a radix
  prefix cache (``serve/prefix_cache.py``); admission matches the longest
  cached prefix and ``share()``s those pages straight into the new block
  table, so prefill starts at the first UNCACHED token and reserves pool
  budget only for the suffix. Pages are refcounted; a write that would
  land in a shared page goes through copy-on-write; when the pool runs
  short, unreferenced cached leaves are LRU-evicted before admission holds
  or sheds (vLLM PagedAttention / SGLang RadixAttention idiom).
- **A sequence's state beside the pages.** A config with "linear" layers
  (Gated DeltaNet) or "conv" layers (a gated short convolution as the whole
  mixer), ``cfg.hybrid``, keeps keys and values in its full layers only; a
  linear layer carries a recurrent state and a convolution tail a sequence, a
  conv layer a tail and nothing else, and they live in the cache at the
  sequence's decode slot (whatever keys ``init_sequence_state`` gives). A slot's
  state is zeroed or restored from a snapshot on the device at admission, in
  order with the step in flight. A page match alone is no prefix hit there:
  a request skips prefill only as far as the deepest matched radix node that
  carries a *state snapshot* (``serve/prefix_cache.py``), an entry of a
  second device pool (``state_snapshots`` entries, ``serve/kv_blocks.py``
  ``SnapshotPool``) holding the state after exactly that node's tokens.
  Snapshots are taken on the device right behind the program that produced
  the state: after the chunk that ends a prompt's last whole page (when no
  later one is certain to come) and after a decode step that ends a page
  (every such step of a row with an EOS to wait for, else the last one of
  the reply, known by count). One taken with a decode step is tentative
  until that step's tokens are read and kept: a row-step discarded because
  an EOS or a cancel was seen a step late has advanced the slot's state,
  and its snapshot is dropped with it. A finished request's snapshot goes
  to the radix node of its depth when its pages are published. A request
  that has to prefill two chunks or more over pages the cache holds (no
  snapshot was ever taken at the end of what it shares, or that one aged
  out) cuts a chunk where its tokens part from another request's and
  leaves a snapshot on that node at once, for the requests after it
  (``branch_snapshot_at``). Both pools
  evict the least recently used, and requests are admitted in order of
  arrival: a waiting session keeps its pages and its snapshot only while
  the pools' turnover (the unreferenced pages over the rate new ones are
  asked for) outlasts its wait; past that every returning turn prefills its
  history again (``docs/tpu_design.md``, "State snapshots").
"""

from __future__ import annotations

from collections import Counter
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from ray_tpu.models.transformer import TransformerConfig
from ray_tpu.observability import metric_defs
from ray_tpu.serve.kv_blocks import BlockAllocator, SnapshotPool
from ray_tpu.serve.model_runner import ModelRunner
from ray_tpu.serve.prefix_cache import PrefixCache, chain_keys

_PREFIX_RESULT_TAGS = {
    "hit": {"result": "hit"},
    "partial": {"result": "partial"},
    "miss": {"result": "miss"},
}


class Reservation(NamedTuple):
    """What admission got a request: its slot's table names its whole budget."""

    matched: int  # prompt tokens the cache supplied (pages, and the state after them): prefill resumes here
    snapshot: int  # the snapshot entry the slot's state is to be restored from; -1: zeroed
    cow_src: int  # a full-prompt hit: the shared tail page (still pinned) to copy into ``cow_dst``; else -1
    cow_dst: int
    result: Optional[str]  # "hit" / "partial" / "miss"; None without a prefix cache
    evicted: int
    gauges: Tuple[int, int, int]


class SequenceStore:
    """Pages, cached prefixes, state snapshots and staged migrations of the
    ``B`` slots of one engine. The pool is sized by the caller:
    ``kv_num_blocks`` pages (one the garbage page) of ``kv_block_size``
    tokens; ``max_blocks`` bounds the prefix cache (0 = what the pool can
    spare); ``n_snapshots`` entries of a config with linear layers' snapshot
    pool; ``gap``: the tokens of shared prefill that earn a branch snapshot."""

    def __init__(self, cfg: TransformerConfig, runner: ModelRunner, lock, *, B: int, S: int, kv_block_size: int,
                 kv_num_blocks: int, prefix_cache: bool = True, max_blocks: int = 0, n_snapshots: int = 0,
                 gap: int = 0, tags: Optional[Dict[str, str]] = None):
        self.cfg = cfg
        self._runner = runner
        self._steps = runner.steps
        self._lock = lock
        self._tags = tags
        self.B = B
        self.kv_block_size = kv_block_size
        # static block-table width: enough logical blocks for a max-length
        # sequence — the table shape never depends on the allocation pattern
        self.max_blocks_per_slot = -(-S // kv_block_size)
        self.allocator = BlockAllocator(kv_num_blocks)
        self.prefix = PrefixCache(kv_block_size, max_blocks) if prefix_cache else None
        # a config with linear or conv layers keeps a state a sequence at its
        # slot (a recurrent state and tails, or tails alone), and a pool of
        # snapshots of it beside the page pool
        self.keeps_state = cfg.hybrid
        self.n_snapshots = n_snapshots  # the pool's size: fixed, read without the lock
        self.snap_pool = SnapshotPool(n_snapshots)
        self._gap = gap
        # per-slot block tables (host mirror of the device int32[B, M] array)
        # and pages held per slot
        self.block_tables = np.zeros((B, self.max_blocks_per_slot), np.int32)
        self.slot_blocks: List[List[int]] = [[] for _ in range(B)]
        # prefix-cache outcome counts per admitted request, tokens whose
        # prefill compute was skipped, and copy-on-write page copies
        self.prefix_results = {"hit": 0, "partial": 0, "miss": 0}
        self.prefix_tokens_reused = 0
        self.prefix_tokens_matched = 0
        self.cow_count = 0
        self.state_snapshots_taken = 0
        self.state_restores = 0
        self.state_zeroed = 0
        self.state_reset_s = 0.0  # host seconds the scheduler spent placing admitted slots' states
        # disaggregated serving: staged exports parked by migration id
        # (the extracted block arrays outlive the prefill request's pool
        # pages — those retire into the prefix cache at export)
        self.staged: Dict[str, dict] = {}
        # layers that walk pages: K and V, or a latent layer's one row a token
        attn_layers = cfg.kv_layers + cfg.latent_layers
        # (window, layers that have it); 0: a full layer
        # (a linear or conv layer has no K/V: ``chunk_kv_visited`` still averages over every layer)
        self._layers_by_window = sorted(Counter(
            (0,) * attn_layers if cfg.hybrid else cfg.layer_windows or (0,) * cfg.n_layers).items())
        self.gauges(1)

    def gauges(self, on: int) -> None:
        """This engine's series of the pools' sizes, nothing in use (0 at
        shutdown: the series label is reused by the next engine)."""
        metric_defs.LLM_KV_BLOCK_POOL_SIZE.set(on * self.allocator.capacity, self._tags)
        self.publish_pool_gauges(0, 0, 0)
        if self.keeps_state:
            metric_defs.LLM_STATE_SNAPSHOT_POOL_SIZE.set(on * self.n_snapshots, self._tags)
            metric_defs.LLM_STATE_SNAPSHOTS_IN_USE.set(0, self._tags)

    # -- admission ----------------------------------------------------------
    def pages_needed(self, prompt_len: int, max_tokens: int) -> int:
        """Pages a request's whole budget takes: to the last position it
        writes (the last sampled token never is; a diffusion config writes
        its last block whole, to the end of the block that holds it)."""
        return (prompt_len + max_tokens - 1 - self._steps.unwritten) // self.kv_block_size + 1

    def reserve_locked(self, req, slot: int) -> Optional[Reservation]:
        """Block-aware admission: reserve the request's whole page budget up
        front (``ceil((prompt + max_tokens - 1) / block_size)`` — the last
        written position is ``prompt + max_tokens - 2``), so an admitted
        request can never hit a mid-decode pool OOM and nothing is ever
        preempted. None: not yet — the pool is short even after eviction.

        With the prefix cache, the longest cached prefix of the prompt is
        ``share()``d straight into the block table (zero prefill compute for
        the hit region — chunked prefill starts at the first uncached token)
        and only the uncached suffix reserves fresh pages. A full-prompt hit
        still recomputes the LAST prompt token (its logits seed sampling),
        and that write would land in the final matched block — a shared
        page — so that block is copy-on-write: the request gets a fresh
        page populated by a device page copy (``copy_tail``) instead of a share."""
        bs = self.kv_block_size
        tp = len(req.prompt)
        total = self.pages_needed(tp, req.max_tokens)
        pages: List[int] = []
        matched = 0
        snapshot = -1
        if self.prefix is not None and not self.keeps_state:
            pages, matched = self.prefix.match(req.prompt, req.block_keys)
        elif self.prefix is not None:
            # the state after the matched pages has to exist too: skip
            # as far as the deepest matched node with a snapshot, short
            # of the last token (its logits seed sampling, and a state
            # cannot be stepped back), and share no page beyond it
            pages, offered, snapshot, matched = self.prefix.match_snapshot(req.prompt, tp - 1, req.block_keys)
            self.prefix_tokens_matched += min(offered, (tp - 1) // bs * bs)
            pages = pages[: matched // bs]
            req.branch_at = self.branch_snapshot_at(req, offered, matched)
        cow_src = -1
        if matched == tp and self._steps.first_from_prefill:
            # full-prompt hit: the tail block must be writable (a
            # diffusion config recomputes nothing: no token comes
            # from its prefill, and its first block opens a page)
            cow_src = pages.pop()
            matched -= bs
        # pin the hit region (and the COW source) FIRST: the
        # eviction sweep below must never free a page we matched
        pins = pages + ([cow_src] if cow_src >= 0 else [])
        if pins:
            self.allocator.share(pins)
        needed = total - len(pages)
        short = needed - self.allocator.free_blocks
        evicted_n = 0
        if short > 0 and self.prefix is not None:
            # pool short: LRU-sweep unreferenced cached leaves
            # before holding (and long before admission sheds)
            evicted = self.evict_pages_locked(short)
            if evicted:
                self.allocator.free(evicted)
                evicted_n = len(evicted)
        if needed > self.allocator.free_blocks:
            # head-of-line waits for release paths to return pages;
            # skipping it would starve big requests behind small
            # ones. Drop the pins — it re-probes the cache on wake.
            if pins:
                self.allocator.free(pins)
            if evicted_n:
                metric_defs.LLM_PREFIX_EVICTIONS.inc(evicted_n)
            return None
        blocks = pages + self.allocator.alloc(needed)
        if snapshot >= 0:
            self.prefix.restored(snapshot)
        self.slot_blocks[slot] = blocks
        self.block_tables[slot, :] = 0
        self.block_tables[slot, : len(blocks)] = blocks
        hit_tokens = matched + (bs if cow_src >= 0 else 0)
        result = None
        if self.prefix is not None:
            fb = (tp // bs) * bs  # the matchable (full-block) region
            result = (
                ("hit" if hit_tokens == fb else "partial")
                if hit_tokens > 0
                else "miss"
            )
            self.prefix_results[result] += 1
            self.prefix_tokens_reused += (
                tp - 1 if cow_src >= 0 else matched
            )
        # (the fresh page for the tail block of a full hit: the first allocated)
        return Reservation(matched, snapshot, cow_src, blocks[len(pages)] if cow_src >= 0 else -1, result,
                           evicted_n, self.pool_gauges_locked())

    def publish_reserved(self, got: Reservation) -> None:
        if got.evicted:
            metric_defs.LLM_PREFIX_EVICTIONS.inc(got.evicted)
        self.publish_pool_gauges(*got.gauges)
        if got.result is not None:
            metric_defs.LLM_PREFIX_CACHE_HITS.inc(tags=_PREFIX_RESULT_TAGS[got.result])

    def copy_tail(self, got: Reservation) -> None:
        """A full-prompt hit: enqueue the copy of the shared tail page into
        the request's own, and drop the pin that kept the source alive."""
        try:
            self._runner.copy_page(got.cow_src, got.cow_dst)
            with self._lock:
                self.allocator.free([got.cow_src])  # drop the copy pin
                self.cow_count += 1
        except BaseException:
            with self._lock:
                self.allocator.free([got.cow_src])
            raise

    def peek_prefix_match(self, prompt: List[int]) -> int:
        """Longest cached prefix (tokens) of ``prompt`` in THIS replica's
        prefix cache — the decode side probes before pulling so a warm
        prefix short-circuits re-migration of shared-prefix blocks.
        Advisory: admission re-matches, and a shrink in between surfaces
        as a typed migration error (the ladder re-prefills)."""
        if self.prefix is None:
            return 0
        with self._lock:
            _, matched = self.prefix.match(prompt)
        return matched

    def kv_free_blocks(self) -> int:
        """Free pages right now — the decode-pool routing signal."""
        with self._lock:
            return self.allocator.free_blocks

    # -- eviction and the pools' gauges --------------------------------------
    def flush_prefix_cache(self) -> int:
        """Evict every prefix-cache entry not currently shared into a live
        request and return the number of pages freed.  Ops hook — also the
        leak-check primitive: on a quiesced engine, ``kv_blocks_in_use``
        equals ``prefix_cache_blocks`` and a flush takes both to zero."""
        if self.prefix is None:
            return 0
        with self._lock:
            pages = self.evict_pages_locked(len(self.prefix))
            if pages:
                self.allocator.free(pages)
            gauges = self.pool_gauges_locked()
        if pages:
            metric_defs.LLM_PREFIX_EVICTIONS.inc(len(pages))
        self.publish_pool_gauges(*gauges)
        return len(pages)

    def evictable(self, page: int) -> bool:
        """An eviction may only take pages whose sole reference is the
        cache's own — refcount 1 means no live block table names the page.
        Caller holds the lock."""
        return self.allocator.refcount(page) == 1

    def evict_pages_locked(self, want: int) -> List[int]:
        """LRU-evict up to ``want`` unreferenced cached leaves and return
        their pages for the caller to free; the state snapshots of the nodes
        that went return to their pool here."""
        pages = self.prefix.evict(want, self.evictable)
        self.reclaim_snapshots_locked()
        return pages

    def reclaim_snapshots_locked(self) -> None:
        """Return to the snapshot pool the entries of radix nodes that went."""
        for entry in self.prefix.take_freed_snapshots():
            self.snap_pool.free(entry)

    def drop_snapshot_locked(self, req) -> None:
        """A request leaves without publishing its pages: its snapshot goes too."""
        if req.snap is not None:
            self.snap_pool.free(req.snap[0])
            req.snap = None

    def alloc_snapshot_locked(self) -> int:
        """An entry of the snapshot pool: a free one, else the least recently
        used one a radix node carries (its pages stay), else -1: the caller
        goes without. Snapshot exhaustion fails no request."""
        entry = self.snap_pool.alloc()
        if entry < 0 and self.prefix is not None:
            freed = self.prefix.evict_snapshot()
            if freed >= 0:
                self.snap_pool.free(freed)
                entry = self.snap_pool.alloc()
        return entry

    def pool_gauges_locked(self) -> Tuple[int, int, int]:
        """(in_use, shared, cache_blocks) snapshot."""
        return (
            self.allocator.used_blocks,
            self.allocator.shared_blocks,
            len(self.prefix) if self.prefix is not None else 0,
        )

    def publish_pool_gauges(self, in_use: int, shared: int, cache_blocks: int) -> None:
        metric_defs.LLM_KV_BLOCKS_IN_USE.set(in_use, self._tags)
        metric_defs.LLM_KV_BLOCKS_SHARED.set(shared, self._tags)
        metric_defs.LLM_PREFIX_CACHE_BLOCKS.set(cache_blocks, self._tags)

    # -- state snapshots ------------------------------------------------------
    def reset_snapshots(self) -> None:
        """The slots' states went with the cache: so do the snapshots of them."""
        if self.keeps_state:
            with self._lock:
                self.snap_pool = SnapshotPool(self.n_snapshots)

    def state_snapshot(self, tokens: List[int]) -> Optional[Dict[str, Any]]:
        """Read-out for a check (a config with linear or conv layers, an
        engine at rest): the deepest state snapshot the prefix cache holds on
        the path of ``tokens``, as ``{"tokens": how many of them it covers,
        "state": what a sequence carries after exactly those}`` (float32: the
        recurrent state [linear layers, heads, key dim, value dim], or a conv
        config's tails [conv layers, width - 1, d]), or None if no node on
        the path carries one. No clock of either pool moves. The engine thread replaces the
        pool's arrays whenever it takes a snapshot, so call this while
        nothing decodes."""
        if self.prefix is None or self._runner.snaps is None:
            return None
        with self._lock:
            entry, covered = self.prefix.snapshot_at(tokens)
            snaps = self._runner.snaps
        if entry < 0:
            return None
        return {"tokens": covered, "state": self._runner.read_snapshot(snaps, entry)}

    def snapshot_after_prompt(self, req) -> int:
        """Tokens of ``req``'s prompt a snapshot is to be taken after during
        prefill: its whole pages, or 0 for none: no snapshot pool or prefix
        cache, a prompt shorter than a page, or a reply that is certain (no
        EOS to end it early) to reach a later page boundary while decoding,
        whose snapshot would replace this one."""
        bs = self.kv_block_size
        tp = len(req.prompt)
        whole = tp // bs * bs
        if not self.n_snapshots or self.prefix is None or not whole:
            return 0
        later = req.eos_id is None and tp + req.max_tokens - 2 >= whole + bs - 1
        return 0 if later else whole

    def branch_snapshot_at(self, req, offered: int, matched: int) -> int:
        """Tokens of ``req``'s prompt after which its prefill leaves a
        snapshot on the cached node that ends them, 0 for none. Pages are
        cached ``offered`` tokens deep and the state only ``matched``: the
        request prefills what lies between again, over tokens other requests
        share, and so will every request after it (a document whose first
        reader's prompt ran on past it never had a snapshot at its end; one
        whose snapshot aged out of a full pool never gets another from a
        prompt's end). Where those tokens are two chunks or more and part
        from another request's at a node (``PrefixCache.branch_point``), the
        chunk is cut there and the state kept. Caller holds the lock."""
        if not self.n_snapshots or offered - matched < self._gap:
            return 0
        at = self.prefix.branch_point(req.prompt, len(req.prompt) - 1, req.block_keys)
        return at if at - matched >= self._gap else 0

    def snapshot_branch(self, req) -> None:
        """The chunk just enqueued ends at ``req.branch_at``: the state behind
        it goes to the cached node there, unless another request got there
        first (the entry is free again)."""
        for _, entry, tokens in self.take_snapshots([(req, req.branch_at)]):
            with self._lock:
                if not self.prefix.attach_snapshot(req.prompt, tokens, entry, req.block_keys):
                    self.snap_pool.free(entry)

    def take_snapshots(self, rows: List[Tuple[Any, int]]) -> List[Tuple[Any, int, int]]:
        """Enqueue, behind the program that produced them, the copies of the
        states of ``rows`` ((request, tokens its state then covers)) into
        entries of the snapshot pool: one program for all of them. Returns
        (request, entry, tokens) of those that got an entry."""
        taken: List[Tuple[Any, int, int]] = []
        if not rows:  # (always, without a prefix cache to publish them to)
            return taken
        with self._lock:
            detached = self.prefix.snapshot_evictions
            for req, tokens in rows:
                entry = self.alloc_snapshot_locked()
                if entry >= 0:
                    taken.append((req, entry, tokens))
            in_use = self.snap_pool.in_use
            self.state_snapshots_taken += len(taken)
            detached = self.prefix.snapshot_evictions - detached
        if detached:
            metric_defs.LLM_STATE_SNAPSHOTS_EVICTED.inc(detached)
        if not taken:
            return taken
        slots, entries = np.zeros(self.B, np.int32), np.zeros(self.B, np.int32)
        for j, (req, entry, _) in enumerate(taken):
            slots[j], entries[j] = req.slot, entry
        self._runner.snapshot_rows(slots, entries, len(taken))
        metric_defs.LLM_STATE_SNAPSHOTS_TAKEN.inc(len(taken))
        metric_defs.LLM_STATE_SNAPSHOTS_IN_USE.set(in_use, self._tags)
        return taken

    def keep_snapshot(self, req, entry: int, tokens: int) -> None:
        """``req``'s newest snapshot replaces the one it held."""
        with self._lock:
            self.drop_snapshot_locked(req)
            req.snap = (entry, tokens)

    def free_snapshot(self, entry: int) -> None:
        """A tentative snapshot whose row-step was discarded: its token is in the state."""
        with self._lock:
            self.snap_pool.free(entry)

    def rows_ending_a_page(self, rows: List[Tuple[int, Any]], pos) -> List[Tuple[Any, int]]:
        """Of the rows of the decode step just enqueued (``pos`` not yet
        advanced), those whose state after it is to be snapshotted, each
        with the tokens that state covers: the step writes position ``pos``
        and that ends a page; a row with an EOS to wait for at every such
        step, any other at the last one of its reply (the last position it
        writes is known by count)."""
        bs = self.kv_block_size
        if not self.n_snapshots or self.prefix is None:
            return []
        out = []
        for i, req in rows:
            covered = int(pos[i]) + 1
            if covered % bs:
                continue
            last_written = len(req.prompt) + req.max_tokens - 2
            if req.eos_id is not None or covered + bs > last_written + 1:
                out.append((req, covered))
        return out

    # -- release ----------------------------------------------------------------
    def release_locked(self, slot: int, req=None) -> None:
        """Drop a slot's page references (a request holds exactly ONE per
        block-table entry, shared or not, so every release path — finish,
        shed, evict, crash — is this same free). ``req`` leaves without
        publishing its pages: its snapshot goes too.

        A decode step may still be in flight for this slot (an EOS is read
        one step late, a cancel whenever it comes). Freeing under it is
        sound: that step writes the row's K/V at positions >= ``pos``, in
        pages only this request could write (``cow_shared_writes``) and
        that ``retire_locked`` never publishes; whoever is given the
        pages next enqueues its writes later, the device runs programs in
        the order they were enqueued, and no one reads a position of its
        page before writing it. The row's tokens are dropped at the engine's
        ``_collect`` by the request's identity, never through the slot."""
        blocks = self.slot_blocks[slot]
        self.slot_blocks[slot] = []
        self.block_tables[slot, :] = 0
        if blocks:
            self.allocator.free(blocks)
        if req is not None:
            self.drop_snapshot_locked(req)

    def retire_locked(self, req) -> int:
        """Finish path: publish the request's full KV blocks into the prefix
        cache (the request's reference TRANSFERS to the cache for newly
        adopted nodes) and free everything else. Returns the number of
        pages LRU-evicted to respect the prefix cache's bound."""
        slot = req.slot
        blocks = self.slot_blocks[slot]
        self.slot_blocks[slot] = []
        self.block_tables[slot, :] = 0
        if not blocks or self.prefix is None:
            self.drop_snapshot_locked(req)
            if blocks:
                self.allocator.free(blocks)
            return 0
        # cache exactly the full blocks of what was written: every token but
        # the sampled ones never written back. (A step in flight past an EOS
        # writes position len(cached) and up: in no full block of ``cached``,
        # so never in a published page. What a diffusion config's last block
        # holds past the emitted tokens was dropped: its page is not full)
        cached = req.prompt + req.generated[: len(req.generated) - self._steps.unwritten]
        bs = self.kv_block_size
        keys = tuple(chain_keys(cached, len(cached) // bs, bs, req.block_keys))  # the reply's blocks behind the prompt's
        adopted, evicted = self.prefix.insert(cached, blocks, self.evictable, keys)
        if req.snap is not None:
            # the state after exactly ``tokens`` of ``cached`` goes to the node that ends them;
            # where that node is not cached, or has one already, the entry is free again
            entry, tokens = req.snap
            if tokens <= len(cached) and self.prefix.attach_snapshot(cached, tokens, entry, keys):
                req.snap = None
            self.drop_snapshot_locked(req)
        self.reclaim_snapshots_locked()
        if evicted:
            self.allocator.free(evicted)
        rest = [b for b in blocks if b not in adopted]
        if rest:
            self.allocator.free(rest)
        return len(evicted)

    def drop_all_locked(self, victims) -> None:
        """Loop-crash recovery: every slot's pages return to the pool, and the
        device pool is about to be re-initialized; cached page CONTENTS die
        with it, so the index must too — drop every node and its reference
        unconditionally."""
        for i in range(self.B):
            self.release_locked(i)
        if self.prefix is not None:
            stale = self.prefix.drain()
            if stale:
                self.allocator.free(stale)
            self.prefix.take_freed_snapshots()  # ``reset_snapshots`` makes the snapshot pool anew
        for r in victims:
            r.snap = None

    def cow_shared_writes(self, slot: int, start: int, n: int) -> None:
        """Copy-on-write guard for the position range ``[start, start+n)``
        of ``slot``: any page the write would touch that is still shared
        (refcount > 1) is replaced by a freshly allocated copy and the
        block-table entry swapped, so shared pages are only ever READ.
        By construction the admission path never maps a to-be-written block
        to a shared page, so this is an invariant net, not a hot path."""
        if n < 1:
            return
        bs = self.kv_block_size
        lo = max(0, start // bs)
        # decode overshoot past the table scatters into page 0 — no COW
        hi = min((start + n - 1) // bs, self.max_blocks_per_slot - 1)
        for bidx in range(lo, hi + 1):
            with self._lock:
                old = int(self.block_tables[slot, bidx])
                if old == 0 or self.allocator.refcount(old) <= 1:
                    continue
                if self.allocator.free_blocks < 1 and self.prefix is not None:
                    evicted = self.evict_pages_locked(1)
                    if evicted:
                        self.allocator.free(evicted)
                new = self.allocator.alloc(1)[0]  # typed shed if truly none
            # the old page holds >= 2 refs (ours included) so it cannot be
            # reallocated while the device copy reads it
            self._runner.copy_page(old, new)
            with self._lock:
                bl = self.slot_blocks[slot]
                bl[bl.index(old)] = new
                self.block_tables[slot, bidx] = new
                self.allocator.free([old])
                self.cow_count += 1

    # -- migration (serve/disagg.py) -----------------------------------------------
    def land_migrated(self, req) -> int:
        """Write an admitted IMPORT request's pulled block arrays into its
        freshly allocated pages (on the engine loop — the only thread allowed
        to touch the donated cache). A warm local prefix covers its blocks
        without any write (the re-migration short-circuit). Returns -1, or
        the index of a block neither cached nor pulled — the prefix shrank
        between the caller's probe and now."""
        bs = self.kv_block_size
        # prefill_pos = matched tokens (a multiple of block_size)
        writes = []
        for bidx in range(req.prefill_pos // bs, -(-len(req.prompt) // bs)):
            arr = (req.import_arrays or {}).get(bidx)
            if arr is None:
                return bidx
            writes.append((arr, int(self.block_tables[req.slot, bidx])))
        if writes:
            bucket = 1
            while bucket < len(writes):
                bucket *= 2
            while len(writes) < bucket:  # idempotent scatter pad
                writes.append(writes[-1])
            # host-side stack: jnp.stack dispatches an expand_dims
            # per block (~1.5ms for a long prompt's 32); np views
            # of CPU-backend arrays memcpy in ~80µs, and the jit
            # boundary ships one contiguous buffer
            self._runner.write_blocks(
                np.stack([np.asarray(a) for a, _ in writes]),
                np.asarray([p for _, p in writes], np.int32),
            )
        return -1

    def export_pages(self, req) -> list:
        """The prompt blocks of an EXPORT request as device-array copies."""
        n_blocks = -(-len(req.prompt) // self.kv_block_size)
        return self._runner.export_pages([int(self.block_tables[req.slot, bidx]) for bidx in range(n_blocks)])

    def release_migration(self, mig_id: str) -> bool:
        """Drop a staged export: forget the arrays and unregister the
        host-fallback source.  Device-plane offers have no cancel API —
        unpulled ones expire via the transfer server's staging TTL (a
        documented device_plane caveat).  Idempotent; True if the staging
        existed.  The prefill-side POOL pages were already retired into
        the prefix cache at export, so this never touches the pool —
        exactly-once freeing is the export path's invariant."""
        with self._lock:
            entry = self.staged.pop(mig_id, None)
        if entry is None:
            return False
        self.unregister([mig_id])
        return True

    def unregister(self, mig_ids: List[str]) -> None:
        from ray_tpu.runtime import data_plane

        for mig_id in mig_ids:
            data_plane.unregister_kv_block_source(mig_id)

    # -- what stats() reports of the pools ---------------------------------------------
    def chunk_kv_visited(self, start: int, n: int) -> float:
        """Cached tokens the attention of a chunk of ``n`` tokens at
        ``start`` has to visit in a layer, averaged over the layers: all
        ``start + n`` in a full layer, less those below its first query's
        window in a sliding one."""
        seen = sum(count * (start + n - (max(0, start - w + 1) if w else 0)) for w, count in self._layers_by_window)
        return seen / self.cfg.n_layers

    def stats_locked(self) -> Dict[str, Any]:
        alloc, prefix = self.allocator, self.prefix
        return {
            "staged_migrations": len(self.staged),
            "kv_block_size": self.kv_block_size,
            "kv_block_pool_size": alloc.capacity,
            "kv_blocks_in_use": alloc.used_blocks,
            "kv_blocks_shared": alloc.shared_blocks,
            "prefix_cache_enabled": prefix is not None,
            "prefix_cache_blocks": len(prefix) if prefix is not None else 0,
            "prefix_cache_hits": self.prefix_results["hit"],
            "prefix_cache_partial": self.prefix_results["partial"],
            "prefix_cache_misses": self.prefix_results["miss"],
            "prefix_tokens_reused": self.prefix_tokens_reused,
            "prefix_evictions": prefix.evictions if prefix is not None else 0,
            "prefix_evict_scanned": prefix.scanned if prefix is not None else 0,
            "cow_copies": self.cow_count,
        }

    def state_stats_locked(self) -> Dict[str, Any]:
        """The per-slot state's own counters (absent for a config without
        linear or conv layers): the snapshot pool's size and entries held (by live
        requests and by radix nodes), snapshots taken, snapshots detached
        because the pool was full, slots restored from a snapshot and slots
        zeroed at admission, the prompt tokens a page match offered
        (``prefix_tokens_reused`` beside it: those a snapshot let the engine
        skip), and the bytes a slot's state and convolution tail take."""
        if not self.keeps_state:
            return {}
        return {
            "state_snapshot_pool_size": self.snap_pool.size,
            "state_snapshots_in_use": self.snap_pool.in_use,
            "state_snapshots_taken": self.state_snapshots_taken,
            "state_snapshots_evicted": self.prefix.snapshot_evictions if self.prefix is not None else 0,
            "state_restores": self.state_restores,
            "state_zeroed": self.state_zeroed,
            "prefix_tokens_matched": self.prefix_tokens_matched,
            "state_bytes_per_slot": self._runner.state_bytes_per_slot,
            # host seconds spent giving admitted slots their state (zeroed or restored), enqueue to return
            "state_reset_s": self.state_reset_s,
            # a config with latent layers: how many, and the bytes a cached token takes in all the
            # pools as they were built (every attention layer, the pad lanes included)
            **({"latent_layers": self.cfg.latent_layers, "kv_bytes_per_token": self._runner.kv_bytes_per_token}
               if self.cfg.latent_layers else {}),
            # a config with conv layers: how many, and the bytes of one slot's tails (all of its state)
            **({"conv_layers": self.cfg.conv_layers, "conv_tail_bytes_per_slot": self._runner.state_bytes_per_slot}
               if self.cfg.conv_layers else {}),
        }
