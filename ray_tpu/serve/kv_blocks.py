"""Free-list allocator for the paged KV cache's HBM block pool.

The serving engine's paged cache (``models/generation.init_paged_cache``)
is one shared pool of fixed-size pages per layer; sequences own disjoint
sets of pages named by their block tables. This module is the host-side
bookkeeping: which pages are free, which are held, and a typed
``OverloadedError`` (the PR-9 admission contract, with ``retry_after_s``)
when a request asks for more pages than are currently free.

Page 0 is never handed out: it is the **garbage page**. Inactive decode
rows and bucket-padded prefill tails scatter their K/V through all-zero
block-table entries, and pointing those at a sacrificial page is what lets
one static-shape decode program serve every allocation pattern without
masking writes per row. Attention masks page 0 out by length, so its
contents are never read. It is also never SHARED: sharing it would give it
a refcount, and a refcount on the sentinel would let a release path return
it to the free list.

Pages are **reference counted** so the prefix cache can share one physical
page into many block tables (vLLM-style): ``alloc`` hands pages out at
refcount 1, ``share`` takes another reference on already-held pages, and
``free`` drops one reference — the page re-enters the free list only when
the last holder lets go. A holder is either a live request (one reference
per block-table entry) or the prefix cache (one reference per cached
node), so every existing release path stays a plain ``free`` of the slot's
pages.

A configuration whose layers keep a state a sequence keeps a second pool beside the pages:
:class:`SnapshotPool`, the free list over the entries of a device array of
state snapshots (one entry: every such layer's state after some prefix: a
recurrent state and a convolution tail, or where the mixer is a short
convolution the tail alone, a few KB a layer). An entry has one owner at a time: a live request
(a snapshot taken for it and not yet published) or a node of the prefix
cache; there is nothing to share, so there are no reference counts.

Not thread-safe on its own: the engine serializes every alloc/share/free
under its admission lock, same as the WeightedFairQueue.
"""

from __future__ import annotations

from typing import Dict, List

from ray_tpu.runtime import admission


class BlockAllocator:
    """LIFO free list over pages ``1..num_blocks-1`` (page 0 reserved).

    Alloc/free are O(n) in the request's own block count and allocation
    order cannot fragment: pages are interchangeable (the block table
    provides the indirection), so ANY ``n <= free_blocks`` pages satisfy a
    request — there is no adjacency requirement to fragment against.
    """

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError(
                f"pool needs >= 2 blocks (1 usable + the garbage page), got {num_blocks}"
            )
        self.num_blocks = int(num_blocks)
        #: pages a single request may ever hold (pool minus the garbage page)
        self.capacity = self.num_blocks - 1
        self._free: List[int] = list(range(self.num_blocks - 1, 0, -1))
        # page -> reference count; a page is either on the free list or in
        # here with count >= 1, never both
        self._refs: Dict[int, int] = {}
        self._shared = 0  # pages at count >= 2, kept as they cross it

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        return self.capacity - len(self._free)

    @property
    def shared_blocks(self) -> int:
        """Pages currently held by more than one reference (the
        ``llm_kv_blocks_shared`` gauge). A count kept by ``share`` and
        ``free``: the engine reads it at every admission, chunk and finish,
        and a walk over a pool of 30 000 held pages each time stood in
        front of the next decode step."""
        return self._shared

    def refcount(self, block: int) -> int:
        """References on ``block`` (0 = free or the garbage page). The
        copy-on-write rule reads this: a write may only land on a page with
        refcount 1."""
        return self._refs.get(block, 0)

    def alloc(self, n: int) -> List[int]:
        """Take ``n`` pages off the free list at refcount 1; raises the
        typed admission shed (``OverloadedError`` with ``retry_after_s``)
        when fewer than ``n`` are free — the caller leaves the request
        queued and retries as release paths return pages."""
        if n < 1:
            raise ValueError(f"alloc wants >= 1 block, got {n}")
        if n > len(self._free):
            raise admission.shed(
                "engine", "kv_blocks",
                message=(
                    f"KV block pool exhausted: {n} blocks wanted, "
                    f"{len(self._free)} of {self.capacity} free"
                ),
            )
        blocks = [self._free.pop() for _ in range(n)]
        for b in blocks:
            self._refs[b] = 1
        return blocks

    def share(self, blocks: List[int]) -> None:
        """Take one more reference on each page (prefix-cache hit: the same
        physical page enters another block table). Only held pages can be
        shared — sharing a free page or the garbage page 0 is corruption
        and raises, same contract as double-free."""
        for b in blocks:
            if b not in self._refs:
                raise ValueError(f"sharing block {b} that is not held")
        for b in blocks:
            self._refs[b] += 1
            if self._refs[b] == 2:
                self._shared += 1

    def free(self, blocks: List[int]) -> None:
        """Drop one reference per page; a page returns to the pool only at
        refcount 0. Double-frees and foreign pages raise — a leak check
        must see corruption, not absorb it."""
        for b in blocks:
            if b not in self._refs:
                raise ValueError(f"freeing block {b} that is not held")
            self._refs[b] -= 1
            if self._refs[b] == 1:
                self._shared -= 1
            elif self._refs[b] == 0:
                del self._refs[b]
                self._free.append(b)


class SnapshotPool:
    """Free list over ``size`` state-snapshot entries. ``alloc`` gives -1
    when none is free (the caller evicts one from the prefix cache or goes
    without: a snapshot is an optimisation, never a request's need); a
    double free or a foreign entry raises, as for pages."""

    def __init__(self, size: int):
        self.size = max(0, int(size))
        self._free: List[int] = list(range(self.size - 1, -1, -1))
        self._held = set()

    @property
    def in_use(self) -> int:
        return len(self._held)

    def alloc(self) -> int:
        if not self._free:
            return -1
        idx = self._free.pop()
        self._held.add(idx)
        return idx

    def free(self, idx: int) -> None:
        if idx not in self._held:
            raise ValueError(f"freeing state snapshot {idx} that is not held")
        self._held.remove(idx)
        self._free.append(idx)
