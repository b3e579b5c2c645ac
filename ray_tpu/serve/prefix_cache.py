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

# digest of the chain root (depth -1); any constant works, but make it
# content-distinct from real node keys
_ROOT = hashlib.blake2b(b"ray_tpu.prefix_cache.root", digest_size=16).digest()


def chain_key(parent: bytes, tokens: Sequence[int]) -> bytes:
    """Running digest of one block's tokens chained onto ``parent``.
    Deterministic across processes (no Python ``hash``); token ids are
    encoded as fixed-width little-endian int64 so there is no ambiguity
    between e.g. [1, 23] and [12, 3]."""
    h = hashlib.blake2b(parent, digest_size=16)
    for t in tokens:
        h.update(int(t).to_bytes(8, "little", signed=True))
    return h.digest()


@dataclass
class _Node:
    key: bytes
    parent: Optional[bytes]  # None for depth-0 nodes
    page: int
    seq: int  # insertion order — the deterministic LRU tie-break
    last_used: int  # monotonic touch counter (bumped on every match walk)
    children: int = 0  # live child count; leaf iff 0


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
    def match(self, tokens: Sequence[int]) -> Tuple[List[int], int]:
        """Longest cached prefix of ``tokens`` at full-block granularity.

        Returns ``(pages, matched_token_count)`` — ``pages[i]`` holds the
        KV of tokens ``[i*bs, (i+1)*bs)``. Every node on the path is
        touched (it is the LRU signal), including on walks whose request is
        later held; the caller ``share()``s the pages only when it actually
        admits."""
        bs = self.block_size
        pages: List[int] = []
        parent = _ROOT
        for i in range(len(tokens) // bs):
            key = chain_key(parent, tokens[i * bs : (i + 1) * bs])
            node = self._nodes.get(key)
            if node is None:
                break
            self._touch(node)
            pages.append(node.page)
            parent = key
        return pages, len(pages) * bs

    # -- insertion -----------------------------------------------------------
    def insert(
        self,
        tokens: Sequence[int],
        pages: Sequence[int],
        evictable: Callable[[int], bool],
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
        for i in range(min(len(tokens) // bs, len(pages))):
            key = chain_key(parent, tokens[i * bs : (i + 1) * bs])
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
        return pages
