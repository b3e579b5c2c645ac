"""Radix prefix cache over the paged KV block pool.

Production LLM traffic is prefix-heavy — shared system prompts, few-shot
templates, multi-turn chats — and the engine's block-table indirection
(`serve/kv_blocks.py`) is exactly the mechanism vLLM's PagedAttention and
SGLang's RadixAttention use to make shared prefixes free: if a FULL block
of tokens was already prefetched into some page, a new request can name
that same physical page in its own block table and skip the prefill
compute for it entirely.

This module is the host-side index mapping token prefixes to pages. It is
a hash chain (a radix tree whose edges are whole blocks): node ``i`` of a
chain is keyed by the running blake2b digest

    key_i = blake2b(key_{i-1} || tokens[i*bs : (i+1)*bs])

so lookup never compares token lists, only digests, and two prompts share
chain nodes exactly as far as they share block-aligned token prefixes.
Python's ``hash()`` is per-process salted and never used here — keys (and
therefore eviction order) are deterministic across processes and runs.

Ownership: the cache holds ONE allocator reference per node (taken over
from the finishing request at ``insert``). A cache hit ``share()``s the
matched pages into the requesting block table, so a page's refcount is
``1 (cache) + number of live requests naming it``. Eviction is LRU over
**unreferenced leaves only** — a leaf whose page has refcount 1 — with a
deterministic ``(last_used, seq)`` tie-break (``seq`` is insertion order),
so the same workload always evicts the same pages.

One ``evict`` call is one pass over the nodes plus a heap: the pass gathers
the leaves that may go, the heap orders them by ``(last_used, seq)``, and a
parent whose last child was just taken joins the heap. That is
``O(nodes + want log leaves)`` a call, whatever ``want`` is: an admission
that needs 110 pages from a pool of 6 000 cached ones examines ~6 100
nodes, not 110 x 6 000. ``scanned`` counts the nodes examined.

**State snapshots** (a configuration whose layers keep a state a sequence: a
recurrent state, or a convolution tail alone). There a page
match alone is no hit: the state after the matched tokens has to
exist too. A node may carry a *snapshot*: the index of an entry of the
engine's snapshot pool that holds the state after exactly the tokens of the
chain down to that node. ``match_snapshot`` returns, with the pages, the
deepest node on the path that carries one; the engine shares pages and skips
prefill up to there and no further. A node's snapshot goes with the node
(``evict``, ``drain``: the index lands in ``freed_snapshots`` for the engine
to return to its pool); when the snapshot pool itself is full,
``evict_snapshot`` detaches the least recently used one (used: attached, or
restored from) and the node's page stays. A snapshot on a node with one
child lies on the only path to the deeper snapshot just attached below it,
so attaching ages it at once: a turn of one conversation, which no request
reaches without passing a better one. A node that more than one request
restored from is a shared prefix (a document, a system prompt) and is not
aged, however few children it has at the moment. The rule still guesses
where a shared prefix has had ONE user so far, and at a full pool that
snapshot goes; ``branch_point`` is how it comes back: the deepest node on a
prompt's cached path where another request's tokens part from it, which is
where the engine takes a snapshot when it has to prefill again over pages
the cache already holds. ``snapshot_at`` looks one up without touching any
clock.

Every method that walks a prompt's chain takes ``keys``, the chain's first
digests where the caller has them (``chain_keys``; the engine computes a
prompt's once, in ``submit``, off its loop).

The cache never touches device memory and never calls the allocator: the
engine owns the allocator lock and frees/shares pages around these calls.
Not thread-safe on its own; the engine serializes access under its
admission lock.
"""

from __future__ import annotations

import hashlib
import heapq
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

# digest of the chain root (depth -1); any constant works, but make it
# content-distinct from real node keys
_ROOT = hashlib.blake2b(b"ray_tpu.prefix_cache.root", digest_size=16).digest()


def chain_keys(tokens: Sequence[int], blocks: int, block_size: int,
               known: Sequence[bytes] = (), parent: bytes = _ROOT):
    """The running digests of the first ``blocks`` blocks of ``tokens`` in
    turn, each chained onto the one before (the first onto ``parent``).
    Deterministic across processes (no Python ``hash``); token ids are
    encoded as fixed-width little-endian int64 so there is no ambiguity
    between e.g. [1, 23] and [12, 3].

    ONE conversion of the tokens and one update a block: a 16k-token prompt
    is ~1000 blocks, chained at every match, insert and snapshot, and a call
    a token stood ~15 ms of host time in front of every admission of such a
    prompt. ``known``: the chain's first keys where the caller has them (a
    request carries its prompt's from ``submit`` on): they are yielded as
    they are and only what lies behind them is converted and hashed."""
    known = known[:blocks]
    yield from known
    if known:
        parent = known[-1]
    step = 8 * block_size
    buf = memoryview(np.asarray(tokens[len(known) * block_size : blocks * block_size], dtype="<i8").tobytes())
    for i in range(blocks - len(known)):
        h = hashlib.blake2b(parent, digest_size=16)
        h.update(buf[i * step : (i + 1) * step])
        parent = h.digest()
        yield parent


def chain_key(parent: bytes, tokens: Sequence[int]) -> bytes:
    """One block's key: :func:`chain_keys` of ``tokens`` as a single block on ``parent``."""
    return next(chain_keys(tokens, 1, len(tokens), parent=parent))


@dataclass
class _Node:
    key: bytes
    parent: Optional[bytes]  # None for depth-0 nodes
    page: int
    seq: int  # insertion order — the deterministic LRU tie-break
    last_used: int  # monotonic touch counter (bumped on every match walk)
    children: int = 0  # live child count; leaf iff 0
    snapshot: int = -1  # entry of the engine's state-snapshot pool; -1: none
    snap_used: int = 0  # LRU clock of the snapshot (attached, restored from)
    snap_hits: int = 0  # requests that restored from the snapshot


class PrefixCache:
    """Longest-prefix index of FULL KV blocks: token chunks -> page ids.

    ``max_blocks`` bounds how many pages the cache may pin (0 = bounded
    only by the pool itself); at the bound, ``insert`` evicts LRU leaves to
    make room and stops adopting when nothing is evictable.
    """

    def __init__(self, block_size: int, max_blocks: int = 0):
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.block_size = int(block_size)
        self.max_blocks = max(0, int(max_blocks))
        self._nodes: Dict[bytes, _Node] = {}
        self._tick = 0  # LRU clock: one bump per touch/insert
        self._seq = 0  # insertion counter (never reused)
        self.evictions = 0  # cumulative, for the evictions counter metric
        self.scanned = 0  # cumulative nodes examined by evict(), added once a call
        # state snapshots: the nodes that carry one, by pool entry, and the
        # entries of nodes that went (the engine returns them to its pool)
        self._snap_nodes: Dict[int, _Node] = {}
        self.freed_snapshots: List[int] = []
        self.snapshot_evictions = 0  # detached by evict_snapshot(): the pool was full

    def __len__(self) -> int:
        return len(self._nodes)

    def keys(self) -> Set[bytes]:
        """Snapshot of live node keys (eviction-determinism tests compare
        these across identical workloads)."""
        return set(self._nodes)

    def _touch(self, node: _Node) -> None:
        self._tick += 1
        node.last_used = self._tick

    # -- lookup --------------------------------------------------------------
    def match(self, tokens: Sequence[int], keys: Sequence[bytes] = ()) -> Tuple[List[int], int]:
        """Longest cached prefix of ``tokens`` at full-block granularity.

        Returns ``(pages, matched_token_count)`` — ``pages[i]`` holds the
        KV of tokens ``[i*bs, (i+1)*bs)``. Every node on the path is
        touched (it is the LRU signal), including on walks whose request is
        later held; the caller ``share()``s the pages only when it actually
        admits."""
        bs = self.block_size
        pages: List[int] = []
        for key in chain_keys(tokens, len(tokens) // bs, bs, keys):
            node = self._nodes.get(key)
            if node is None:
                break
            self._touch(node)
            pages.append(node.page)
        return pages, len(pages) * bs

    # -- state snapshots -----------------------------------------------------
    @property
    def snapshots(self) -> int:
        return len(self._snap_nodes)

    def _chain(self, tokens: Sequence[int], blocks: int, keys: Sequence[bytes] = ()) -> List[_Node]:
        """The nodes of the first ``blocks`` blocks of ``tokens``, as far as cached."""
        bs = self.block_size
        out: List[_Node] = []
        for key in chain_keys(tokens, blocks, bs, keys):
            node = self._nodes.get(key)
            if node is None:
                break
            out.append(node)
        return out

    def match_snapshot(self, tokens: Sequence[int], limit: int,
                       keys: Sequence[bytes] = ()) -> Tuple[List[int], int, int, int]:
        """:meth:`match`, and the deepest node on the matched path that
        carries a snapshot of at most ``limit`` tokens: ``(pages, matched
        tokens, snapshot, snapshot tokens)``, the last two ``(-1, 0)``
        where no node on the path has one. The snapshot's own LRU clock is
        bumped: the caller restores from it, and says so with
        :meth:`restored` once the request is admitted (a request held for
        want of pages probes again at every wake)."""
        bs = self.block_size
        path = self._chain(tokens, len(tokens) // bs, keys)
        best = -1
        for i, node in enumerate(path):
            self._touch(node)
            if node.snapshot >= 0 and (i + 1) * bs <= limit:
                best = i
        if best < 0:
            return [nd.page for nd in path], len(path) * bs, -1, 0
        self._tick += 1
        path[best].snap_used = self._tick
        return [nd.page for nd in path], len(path) * bs, path[best].snapshot, (best + 1) * bs

    def restored(self, snapshot: int) -> None:
        """A request was admitted on the copy of ``snapshot``: one more
        request restored from its node (what keeps a shared prefix's
        snapshot from ageing, ``attach_snapshot``)."""
        node = self._snap_nodes.get(snapshot)
        if node is not None:
            node.snap_hits += 1

    def branch_point(self, tokens: Sequence[int], limit: int, keys: Sequence[bytes] = ()) -> int:
        """Tokens down to the deepest node on the cached path of ``tokens``
        (at most ``limit``) where another request's tokens part from these:
        a node with a child off this path, which for the last cached node
        is any child. 0 if there is none. A request that prefills past such
        a node again (its snapshot went, or none was ever taken there: the
        first request's prompt ran on beyond it) leaves a snapshot there for
        the next. No clock moves."""
        bs = self.block_size
        path = self._chain(tokens, len(tokens) // bs, keys)
        for i in range(min(len(path), limit // bs) - 1, -1, -1):
            on_path = 1 if i + 1 < len(path) else 0  # the child that continues these tokens
            if path[i].children > on_path:
                return (i + 1) * bs
        return 0

    def snapshot_at(self, tokens: Sequence[int]) -> Tuple[int, int]:
        """The deepest snapshot on the cached path of ``tokens`` and the
        tokens it covers, ``(-1, 0)`` if there is none; no clock moves (a
        read-out, not a use)."""
        bs = self.block_size
        best = (-1, 0)
        for i, node in enumerate(self._chain(tokens, len(tokens) // bs)):
            if node.snapshot >= 0:
                best = (node.snapshot, (i + 1) * bs)
        return best

    def attach_snapshot(self, tokens: Sequence[int], n_tokens: int, snapshot: int,
                        keys: Sequence[bytes] = ()) -> bool:
        """Give the node that ends the first ``n_tokens`` (whole blocks) of
        ``tokens`` the snapshot ``snapshot``. False (the caller keeps the
        entry, and frees it) if that node is not cached or carries one
        already. Snapshots above it on single-child nodes that at most one
        request restored from age at once."""
        bs = self.block_size
        blocks = n_tokens // bs
        path = self._chain(tokens, blocks, keys) if blocks and n_tokens % bs == 0 else []
        if len(path) != blocks or not path or path[-1].snapshot >= 0:
            return False
        self._tick += 1
        path[-1].snapshot, path[-1].snap_used, path[-1].snap_hits = int(snapshot), self._tick, 0
        self._snap_nodes[int(snapshot)] = path[-1]
        for node in path[:-1]:
            if node.snapshot >= 0 and node.children == 1 and node.snap_hits <= 1:
                node.snap_used = 0
        return True

    def evict_snapshot(self) -> int:
        """Detach the least recently used snapshot (ties: the older node)
        and return its pool entry, -1 if no node carries one. The node and
        its page stay: a later request re-prefills from the deepest snapshot
        left."""
        if not self._snap_nodes:
            return -1
        node = min(self._snap_nodes.values(), key=lambda nd: (nd.snap_used, nd.seq))
        self.snapshot_evictions += 1
        return self._detach(node)

    def _detach(self, node: _Node) -> int:
        idx, node.snapshot = node.snapshot, -1
        del self._snap_nodes[idx]
        return idx

    def take_freed_snapshots(self) -> List[int]:
        """The pool entries of nodes that went since the last call."""
        out, self.freed_snapshots = self.freed_snapshots, []
        return out

    # -- insertion -----------------------------------------------------------
    def insert(
        self,
        tokens: Sequence[int],
        pages: Sequence[int],
        evictable: Callable[[int], bool],
        keys: Sequence[bytes] = (),
    ) -> Tuple[Set[int], List[int]]:
        """Adopt the full blocks of ``tokens`` (``pages[i]`` is the caller's
        page for block ``i``) into the cache.

        Returns ``(adopted, evicted)``: ``adopted`` pages had their caller
        reference TRANSFERRED to the cache (the caller must not free them);
        ``evicted`` pages were dropped to stay under ``max_blocks`` and the
        caller must free the cache's reference on each. Blocks already
        cached adopt nothing — the caller keeps (and frees) its own copy.
        ``evictable(page)`` says whether only the cache still references a
        page (allocator refcount 1)."""
        bs = self.block_size
        adopted: Set[int] = set()
        evicted: List[int] = []
        parent = _ROOT
        parent_node: Optional[_Node] = None
        protect: Set[bytes] = set()  # the chain being built: never evict it
        for i, key in enumerate(chain_keys(tokens, min(len(tokens) // bs, len(pages)), bs, keys)):
            node = self._nodes.get(key)
            if node is None:
                if self.max_blocks and len(self._nodes) >= self.max_blocks:
                    evicted += self.evict(
                        len(self._nodes) - self.max_blocks + 1,
                        evictable,
                        protect=protect,
                    )
                    if len(self._nodes) >= self.max_blocks:
                        break  # nothing evictable: stop adopting, keep what we have
                self._seq += 1
                self._tick += 1
                node = _Node(
                    key=key,
                    parent=None if parent is _ROOT else parent,
                    page=int(pages[i]),
                    seq=self._seq,
                    last_used=self._tick,
                )
                self._nodes[key] = node
                if parent_node is not None:
                    parent_node.children += 1
                adopted.add(int(pages[i]))
            else:
                self._touch(node)
            protect.add(key)
            parent = key
            parent_node = node
        return adopted, evicted

    # -- eviction ------------------------------------------------------------
    def evict(
        self,
        want: int,
        evictable: Callable[[int], bool],
        protect: Optional[Set[bytes]] = None,
    ) -> List[int]:
        """LRU sweep: drop up to ``want`` unreferenced leaves and return
        their pages (the caller frees the cache's reference on each).

        Deterministic: victims are chosen by ascending ``(last_used, seq)``
        — same workload, same eviction order. Evicting a leaf can expose
        its parent as the next leaf, so the sweep cascades up cold chains.
        Interior nodes and pages still shared into live requests are never
        taken.

        One pass over the nodes gathers this call's candidates into a heap;
        after that only a victim's parent is examined, when its last child
        goes. ``evictable`` is asked once per candidate: the caller holds
        the allocator's lock and frees the returned pages after the call, so
        no answer changes during it. Cost ``O(nodes + want log leaves)``."""
        if want <= 0:
            return []
        nodes = self._nodes
        protect = protect or ()
        heap = [
            (nd.last_used, nd.seq, nd)
            for nd in nodes.values()
            if not nd.children and nd.key not in protect and evictable(nd.page)
        ]
        heapq.heapify(heap)  # seq is unique: a comparison never reaches the node
        scanned = len(nodes)
        freed: List[int] = []
        while heap and len(freed) < want:
            victim = heapq.heappop(heap)[2]
            del nodes[victim.key]
            freed.append(victim.page)
            if victim.snapshot >= 0:
                self.freed_snapshots.append(self._detach(victim))
            parent = nodes.get(victim.parent)  # None for a depth-0 victim
            if parent is None:
                continue
            parent.children -= 1
            scanned += 1
            if not parent.children and parent.key not in protect and evictable(parent.page):
                heapq.heappush(heap, (parent.last_used, parent.seq, parent))
        self.evictions += len(freed)
        self.scanned += scanned
        return freed

    def drain(self) -> List[int]:
        """Drop EVERY node regardless of sharing and return all pages the
        cache held a reference on. Used when the device-side pool is gone
        (loop-crash cache reset): the page contents no longer exist, so the
        index must not survive them."""
        pages = [nd.page for nd in self._nodes.values()]
        self._nodes.clear()
        self.freed_snapshots.extend(self._snap_nodes)
        self._snap_nodes.clear()
        return pages
