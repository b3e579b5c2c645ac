"""Prefix-aware KV reuse tests.

Four contracts:
- PrefixCache unit: hash-chain keys are process-stable and unambiguous,
  match/insert/evict round-trip pages, eviction is LRU over unreferenced
  leaves with a deterministic (last_used, seq) order, max_blocks is honored
  without ever evicting the chain being inserted; the heap sweep takes the
  victims of the plain sweep it replaced, in its order, and examines each
  node once a call (``scanned``)
- allocator refcounts: share() pins pages, free() releases one reference,
  pages return to the pool only at zero, and misuse (double free, sharing a
  free page or the garbage page) raises instead of corrupting the pool
- engine identity: warm runs (full hit + COW, partial hit, chunked prefill
  resuming mid-prompt, divergent suffixes off a shared prefix) are
  token-identical to one-shot ``generate()`` under greedy decoding
- leak + determinism: every release path under ACTIVE sharing returns the
  request's references (pool == cache after quiesce, flush drains both),
  loop crash invalidates the whole cache, and the same workload on a
  bounded cache evicts the same pages in the same order
"""

import copy
import functools
import hashlib
import random
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_reference import greedy_reference
from ray_tpu.models import TransformerConfig, init_params
from ray_tpu.serve.kv_blocks import BlockAllocator
from ray_tpu.serve.llm import LLMEngine
from ray_tpu.serve.prefix_cache import PrefixCache, chain_key, _ROOT

CFG = TransformerConfig(
    vocab_size=89, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=64,
    attention="dense", dtype=jnp.float32,
)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.key(11))


_reference = functools.partial(greedy_reference, CFG)


def _paged(params, **kw):
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("max_seq_len", 64)
    return LLMEngine(CFG, params, **kw)


def _wait(pred, timeout=60):
    deadline = time.time() + timeout
    while not pred() and time.time() < deadline:
        time.sleep(0.005)
    assert pred()


def _engine_sources():
    from ray_tpu.runtime import admission

    return [s for s in admission.sources_snapshot() if s.get("layer") == "engine"]


def _assert_no_leak(eng):
    st = eng.stats()
    assert st["kv_blocks_in_use"] == st["prefix_cache_blocks"]
    eng.store.flush_prefix_cache()
    st = eng.stats()
    assert st["kv_blocks_in_use"] == 0 and st["prefix_cache_blocks"] == 0


# --------------------------------------------------------------------------
# chain keys
# --------------------------------------------------------------------------
def test_chain_key_stable_and_unambiguous():
    # fixed-width encoding: [1, 23] and [12, 3] must not collide
    assert chain_key(_ROOT, [1, 23]) != chain_key(_ROOT, [12, 3])
    # same inputs, same digest (no per-process salt)
    assert chain_key(_ROOT, [7, 8, 9]) == chain_key(_ROOT, [7, 8, 9])
    # chained: depends on the parent
    k1 = chain_key(_ROOT, [1, 2])
    assert chain_key(k1, [3, 4]) != chain_key(_ROOT, [3, 4])
    # negative token ids encode without error
    assert chain_key(_ROOT, [-1]) != chain_key(_ROOT, [1])
    # the digest is the one of a token's eight little-endian bytes after another (what other processes computed)
    h = hashlib.blake2b(_ROOT, digest_size=16)
    for t in (7, -8, 2**40):
        h.update(t.to_bytes(8, "little", signed=True))
    assert chain_key(_ROOT, [7, -8, 2**40]) == h.digest()


@pytest.mark.parametrize("as_array", [False, True])
@pytest.mark.parametrize("blocks", [0, 1, 5, 64])
def test_chain_keys_of_a_whole_prompt_are_the_keys_block_by_block(blocks, as_array):
    from ray_tpu.serve.prefix_cache import chain_keys

    tokens = np.random.default_rng(blocks).integers(-5, 200_000, size=64 * 16 + 7)
    want, parent = [], _ROOT
    for i in range(blocks):
        parent = chain_key(parent, tokens[i * 16 : (i + 1) * 16].tolist())
        want.append(parent)
    assert list(chain_keys(tokens if as_array else tokens.tolist(), blocks, 16)) == want


@pytest.mark.parametrize("known", [0, 1, 5, 9, 12])
def test_chain_keys_resume_behind_the_keys_the_caller_has(known):
    from ray_tpu.serve.prefix_cache import chain_keys

    tokens = np.random.default_rng(3).integers(0, 200_000, size=9 * 16 + 5).tolist()
    want = list(chain_keys(tokens, 9, 16))
    have = tuple(want) + (b"x" * 16,) * 3  # keys past ``blocks`` are not the caller's to give: never yielded
    assert list(chain_keys(tokens, 9, 16, have[:known])) == want
    assert list(chain_keys(tokens + [7] * 40, 11, 16, want[:known]))[:9] == want  # a reply's blocks behind the prompt's


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_allocators_shared_count_is_the_recount_after_any_shares_and_frees(seed):
    rng = random.Random(seed)
    a = BlockAllocator(40)
    held = []  # one entry a reference
    for _ in range(400):
        roll = rng.random()
        if roll < 0.3 and a.free_blocks >= 3:
            held += a.alloc(3)
        elif roll < 0.6 and held:
            pick = rng.sample(held, min(len(held), 4))
            a.share(list(dict.fromkeys(pick)))
            held += list(dict.fromkeys(pick))
        elif held:
            for b in rng.sample(held, min(len(held), 5)):
                held.remove(b)
                a.free([b])
        assert a.shared_blocks == sum(1 for b in set(held) if held.count(b) > 1)
    a.free(held)
    assert a.shared_blocks == 0 and a.free_blocks == a.capacity


# --------------------------------------------------------------------------
# PrefixCache unit
# --------------------------------------------------------------------------
def test_match_insert_roundtrip():
    pc = PrefixCache(block_size=4)
    toks = [1, 2, 3, 4, 5, 6, 7, 8, 9]  # 2 full blocks + 1 partial token
    adopted, evicted = pc.insert(toks, [10, 11], lambda p: True)
    assert adopted == {10, 11} and evicted == []
    assert len(pc) == 2
    pages, n = pc.match(toks)
    assert pages == [10, 11] and n == 8
    # longer prompt with the same prefix matches the shared chain
    pages, n = pc.match(toks[:8] + [40, 41, 42, 43])
    assert pages == [10, 11] and n == 8
    # diverging second block matches only the first
    pages, n = pc.match([1, 2, 3, 4, 9, 9, 9, 9])
    assert pages == [10] and n == 4
    # no full block -> no match
    assert pc.match([1, 2, 3]) == ([], 0)


def test_insert_adopts_only_new_blocks():
    pc = PrefixCache(block_size=2)
    a1, _ = pc.insert([1, 2, 3, 4], [5, 6], lambda p: True)
    assert a1 == {5, 6}
    # re-inserting the same chain with different pages adopts nothing:
    # the caller keeps (and frees) its duplicates
    a2, _ = pc.insert([1, 2, 3, 4, 9, 9], [7, 8, 9], lambda p: True)
    assert a2 == {9}
    pages, n = pc.match([1, 2, 3, 4])
    assert pages == [5, 6] and n == 4


def test_evict_is_lru_over_unreferenced_leaves():
    pc = PrefixCache(block_size=1)
    pc.insert([1], [11], lambda p: True)
    pc.insert([2], [12], lambda p: True)
    pc.insert([3], [13], lambda p: True)
    pc.match([1])  # chain [1] is now the most recently used
    # LRU order: [2] then [3] then [1]
    assert pc.evict(2, lambda p: True) == [12, 13]
    assert pc.evict(5, lambda p: True) == [11]
    assert len(pc) == 0 and pc.evictions == 3


def test_evict_skips_shared_pages_and_interior_nodes():
    pc = PrefixCache(block_size=1)
    pc.insert([1, 2], [11, 12], lambda p: True)  # chain: 11 -> 12
    # interior node 11 is not a leaf; leaf 12 is "shared" (not evictable)
    assert pc.evict(2, lambda p: p != 12) == []
    assert len(pc) == 2
    # once the leaf is droppable, the sweep cascades up the cold chain
    assert pc.evict(2, lambda p: True) == [12, 11]


def test_insert_at_bound_never_evicts_own_chain():
    pc = PrefixCache(block_size=1, max_blocks=2)
    pc.insert([1], [11], lambda p: True)
    # a 3-deep chain at bound 2: the chain being built is protected, so the
    # sweep takes the cold [1] entry, then stops adopting when nothing else
    # is evictable — never stranding a mid-chain node
    adopted, evicted = pc.insert([5, 6, 7], [21, 22, 23], lambda p: True)
    assert evicted == [11]
    assert adopted == {21, 22}  # third block did not fit; chain intact
    pages, n = pc.match([5, 6, 7])
    assert pages == [21, 22] and n == 2


def test_drain_returns_every_page_regardless_of_sharing():
    pc = PrefixCache(block_size=1)
    pc.insert([1, 2, 3], [11, 12, 13], lambda p: True)
    assert sorted(pc.drain()) == [11, 12, 13]
    assert len(pc) == 0
    assert pc.match([1]) == ([], 0)


def test_cache_eviction_deterministic_across_instances():
    """Same workload, two fresh caches: identical surviving keys and
    identical eviction order (acceptance: same workload -> same evicted
    pages)."""
    def run():
        pc = PrefixCache(block_size=2, max_blocks=3)
        order = []
        for toks in ([1, 2, 3, 4], [5, 6], [7, 8, 9, 10], [1, 2, 11, 12]):
            _, ev = pc.insert(toks, list(range(20, 20 + len(toks) // 2)),
                              lambda p: True)
            order += ev
        pc.match([5, 6])
        order += pc.evict(2, lambda p: True)
        return order, sorted(pc.keys())

    assert run() == run()


# --------------------------------------------------------------------------
# the heap sweep against the plain sweep it replaced
# --------------------------------------------------------------------------
def _evict_reference(nodes, want, evictable, protect=None):
    """The sweep as it stood before the heap, kept as the independent
    reference: for each page it frees it walks every node for the least
    recently used droppable leaf. Mutates ``nodes``; returns the pages."""
    freed = []
    while len(freed) < want:
        victim = None
        for nd in nodes.values():
            if nd.children:
                continue
            if protect is not None and nd.key in protect:
                continue
            if not evictable(nd.page):
                continue
            if victim is None or (nd.last_used, nd.seq) < (victim.last_used, victim.seq):
                victim = nd
        if victim is None:
            break
        del nodes[victim.key]
        if victim.parent is not None:
            parent = nodes.get(victim.parent)
            if parent is not None:
                parent.children -= 1
        freed.append(victim.page)
    return freed


def _chain_keys(tokens):
    """The node keys of a prompt's chain at block size 1."""
    keys, parent = [], _ROOT
    for t in tokens:
        parent = chain_key(parent, [t])
        keys.append(parent)
    return keys


def _sweep_case(shape, seed):
    """A cache of a given shape with its LRU order shuffled by match walks,
    and the arguments of one ``evict`` call: (cache, want, pinned pages,
    protected keys)."""
    rng = random.Random(seed)
    pc = PrefixCache(block_size=1)
    page = iter(range(1, 1 << 20))
    prompts = []

    def grow(stem, n):
        toks = stem + [rng.randrange(1 << 30) for _ in range(n)]
        pc.insert(toks, [next(page) for _ in toks], lambda p: True)
        prompts.append(toks)
        return toks

    if shape == "forks":
        stem = grow([], 12)
        for _ in range(5):  # branches off the stem, and twigs off each branch
            branch = grow(stem[: rng.randrange(4, 13)], rng.randrange(5, 16))
            for _ in range(rng.randrange(0, 3)):
                grow(branch[: rng.randrange(len(stem) // 2, len(branch))], rng.randrange(1, 8))
    else:
        for _ in range(8):
            grow([], rng.randrange(20, 41))
        for _ in range(4):  # a few forks among the chains too
            src = rng.choice(prompts)
            grow(src[: rng.randrange(1, len(src))], rng.randrange(1, 10))
    for _ in range(12):  # walks of whole prompts and of stems alone: a parent newer than its leaf
        toks = rng.choice(prompts)
        pc.match(toks[: rng.randrange(1, len(toks) + 1)])
    pinned, protect = set(), None
    n = len(pc)
    want = n // 3
    if shape in ("pinned", "want_exceeds"):
        # live requests name a prompt's pages from its root down; a stray page besides
        for toks in rng.sample(prompts, 3):
            pinned.update(pc.match(toks[: rng.randrange(1, len(toks) + 1)])[0])
        pinned.update(rng.sample(range(1, n + 1), n // 10))
    if shape == "want_exceeds":
        want = 2 * n
    if shape == "want_len":
        want = n
    if shape == "protect":
        protect = set(_chain_keys(rng.choice(prompts)))
        want = n  # everything but the protected chain goes
    return pc, want, pinned, protect


@pytest.mark.parametrize("seed", [3, 17, 2147483659])
@pytest.mark.parametrize("shape", ["chains", "forks", "pinned", "protect", "want_exceeds", "want_len"])
def test_evict_takes_the_plain_sweeps_victims_in_its_order(shape, seed):
    pc, want, pinned, protect = _sweep_case(shape, seed)
    evictable = lambda p: p not in pinned  # noqa: E731
    ref_nodes = copy.deepcopy(pc._nodes)
    n = len(pc)
    want_pages = _evict_reference(ref_nodes, want, evictable, protect)
    got = pc.evict(want, evictable, protect=protect)
    assert got == want_pages  # the same pages in the same order
    assert pc.keys() == set(ref_nodes)
    assert {k: nd.children for k, nd in pc._nodes.items()} == {k: nd.children for k, nd in ref_nodes.items()}
    assert pc.evictions == len(want_pages)
    assert n <= pc.scanned <= n + len(got)  # one pass, then a parent for each victim at most
    if shape in ("chains", "forks"):
        assert len(got) == want
    elif shape == "want_len":
        assert len(got) == n and len(pc) == 0
    elif shape == "protect":
        assert pc.keys() == protect
    else:
        assert not pinned & set(got)
        if shape == "want_exceeds":
            assert 0 < len(got) < n
    # a second call goes on where the first stopped, as the plain sweep does
    assert pc.evict(5, evictable, protect=protect) == _evict_reference(ref_nodes, 5, evictable, protect)
    assert pc.keys() == set(ref_nodes)


def test_evict_examines_each_node_once_a_call():
    """The work bound, with no clock in it: freeing 110 pages from 4 000
    cached ones examines the nodes once and then one parent a victim, where
    the plain sweep walked all of them for every page."""
    pc = PrefixCache(block_size=1)
    for c in range(40):
        pc.insert([c * 1000 + i for i in range(100)], [c * 100 + i + 1 for i in range(100)], lambda p: True)
    n = len(pc)
    assert n == 4000
    ref_nodes = copy.deepcopy(pc._nodes)
    assert pc.evict(0, lambda p: True) == [] and pc.scanned == 0  # nothing asked, nothing examined
    got = pc.evict(110, lambda p: True)
    assert got == _evict_reference(ref_nodes, 110, lambda p: True)
    assert got[:100] == list(range(100, 0, -1))  # the coldest chain from its tail, then the next
    assert n <= pc.scanned <= n + 2 * 110
    assert pc.evictions == 110 and len(pc) == n - 110


# --------------------------------------------------------------------------
# allocator refcounts
# --------------------------------------------------------------------------
def test_allocator_share_and_refcounts():
    a = BlockAllocator(6)
    got = a.alloc(2)
    assert all(a.refcount(b) == 1 for b in got) and a.shared_blocks == 0
    a.share(got)
    assert all(a.refcount(b) == 2 for b in got) and a.shared_blocks == 2
    a.free(got)  # one reference down: pages still held
    assert a.used_blocks == 2 and all(a.refcount(b) == 1 for b in got)
    assert a.shared_blocks == 0
    a.free(got)  # last reference: pages return to the pool
    assert a.used_blocks == 0 and a.free_blocks == 5
    assert a.refcount(got[0]) == 0


def test_allocator_share_misuse_raises_and_is_atomic():
    a = BlockAllocator(6)
    got = a.alloc(2)
    with pytest.raises(ValueError):
        a.share([0])  # the garbage page is never shared
    with pytest.raises(ValueError):
        a.share([got[0], 99])  # 99 is not held
    # atomic: the failed share must not have bumped got[0]
    assert a.refcount(got[0]) == 1
    a.free(got)
    with pytest.raises(ValueError):
        a.share(got)  # sharing a freed page
    with pytest.raises(ValueError):
        a.free(got)  # double free
    assert a.free_blocks == 5


# --------------------------------------------------------------------------
# engine: warm-path token identity
# --------------------------------------------------------------------------
def test_full_hit_cow_token_identical_to_generate(params):
    eng = _paged(params, kv_block_size=8)
    try:
        p = list(range(1, 25))  # 24 tokens = 3 full blocks
        want6 = _reference(params, p, 6)
        want10 = _reference(params, p, 10)
        assert eng.generate(p, max_tokens=6) == want6  # cold
        # warm, different generation length: full hit + COW on the tail block
        assert eng.generate(p, max_tokens=10) == want10
        st = eng.stats()
        assert st["prefix_cache_hits"] >= 1 and st["cow_copies"] >= 1
        assert st["prefix_tokens_reused"] >= 23
        _assert_no_leak(eng)
    finally:
        eng.shutdown()


def test_divergent_suffixes_share_prefix_blocks_to_generate(params):
    eng = _paged(params, kv_block_size=8)
    try:
        base = list(range(30, 46))  # 16 tokens = 2 full blocks
        p1, p2 = base + [5, 6, 7], base + [8, 9]
        assert eng.generate(p1, max_tokens=5) == _reference(params, p1, 5)
        assert eng.generate(p2, max_tokens=5) == _reference(params, p2, 5)
        st = eng.stats()
        # p2 reused base's two blocks without COW (its suffix diverges)
        assert st["prefix_cache_hits"] + st["prefix_cache_partial"] >= 1
        assert st["prefix_tokens_reused"] >= 16
        _assert_no_leak(eng)
    finally:
        eng.shutdown()


@pytest.mark.parametrize("chunk", [7, 8, 16])
def test_chunked_prefill_resumes_at_first_uncached_token_to_generate(params, chunk):
    """Chunked prefill x cache hit: the warm run starts prefill mid-prompt
    (at the first uncached token) and still produces generate()'s tokens."""
    eng = _paged(params, kv_block_size=8, prefill_chunk_tokens=chunk)
    try:
        p = list(range(1, 31))  # 30 tokens
        want = _reference(params, p, 5)
        assert eng.generate(p, max_tokens=5) == want
        chunks_cold = eng.stats()["prefill_chunks"]
        assert eng.generate(p, max_tokens=5) == want
        st = eng.stats()
        # warm prefill only covered the uncached tail: fewer chunks than cold
        assert st["prefill_chunks"] - chunks_cold < chunks_cold
        assert st["prefix_cache_hits"] >= 1
        # an EXTENDED prompt diverges inside the cached completion's block:
        # a PARTIAL hit that resumes after the shared full blocks
        p2 = p + [60, 61, 62]
        assert eng.generate(p2, max_tokens=5) == _reference(params, p2, 5)
        assert eng.stats()["prefix_cache_partial"] >= 1
        _assert_no_leak(eng)
    finally:
        eng.shutdown()


def test_shared_pages_visible_while_request_live(params):
    """While a warm request decodes, the matched pages carry two references
    (cache + block table) and show up in kv_blocks_shared; disconnect-evict
    mid-decode drops only the request's reference."""
    eng = _paged(params, kv_block_size=8)
    try:
        p = list(range(1, 18))  # 2 full blocks
        eng.generate(p, max_tokens=3)  # populate the cache
        cached = eng.stats()["prefix_cache_blocks"]
        assert cached >= 2
        stream = eng.submit_stream(p, max_tokens=40)
        next(stream)
        assert eng.stats()["kv_blocks_shared"] >= 2
        stream.close()  # evict mid-decode while sharing is active
        _wait(lambda: eng.stats()["active_slots"] == 0)
        _wait(lambda: eng.stats()["kv_blocks_in_use"]
              == eng.stats()["prefix_cache_blocks"])
        assert eng.stats()["kv_blocks_shared"] == 0
        _assert_no_leak(eng)
    finally:
        eng.shutdown()


@pytest.mark.parametrize("K", [1, 4])
def test_full_hit_tail_page_copied_while_a_step_is_in_flight(params, K):
    """A full-prompt hit copies its tail page at admission. With another
    request decoding, that copy is enqueued behind a step that has not been
    read yet: the device runs it in order, and both requests get the
    reference's tokens."""
    eng = _paged(params, kv_block_size=8, max_seq_len=512, decode_chunk=K)
    try:
        p, long = list(range(1, 25)), [70, 71, 72]  # p: 3 full blocks
        want4, want10, want_long = _reference(params, p, 4), _reference(params, p, 10), _reference(params, long, 400)
        assert eng.generate(p, max_tokens=4) == want4  # cold: p's blocks are cached
        fut = eng.submit(long, max_tokens=400)
        _wait(lambda: eng.stats()["decode_steps_overlapped"] > 0)
        cow0 = eng.stats()["cow_copies"]
        assert eng.generate(p, max_tokens=10) == want10  # full hit, tail block copied on write
        assert not fut.done(), "the long request was to be decoding meanwhile"
        assert eng.stats()["cow_copies"] == cow0 + 1
        assert fut.result(timeout=120) == want_long
        assert eng.stats()["decode_row_steps_discarded"] == 0
        _assert_no_leak(eng)
    finally:
        eng.shutdown()


def test_decode_write_to_a_page_shared_mid_flight_is_copied_first(params):
    """The copy-on-write net under the loop's new order: pages a live row
    has yet to write become shared while its step is in flight. That step
    (dispatched when the page was the row's own) writes the old page; the
    next dispatch copies it, the write included, before it writes on: the
    tokens are the reference's and the shared pages are never written."""
    eng = _paged(params, kv_block_size=8, max_seq_len=512)
    try:
        prompt = [70, 71, 72]
        want = _reference(params, prompt, 300)
        fut = eng.submit(prompt, max_tokens=300)
        _wait(lambda: eng.stats()["decode_steps_overlapped"] > 2)
        with eng._lock:  # another reader appears for every page the row holds
            slot = next(i for i, r in enumerate(eng._slots) if r is not None)
            pages = [int(b) for b in eng.store.block_tables[slot] if b > 0]
            eng.store.allocator.share(pages)
        assert fut.result(timeout=120) == want
        st = eng.stats()
        # the tail page of that moment and every page after it were copied; full ones are only read
        assert 1 <= st["cow_copies"] <= len(pages)
        with eng._lock:
            eng.store.allocator.free(pages)  # the other reader goes
        _assert_no_leak(eng)
    finally:
        eng.shutdown()


# --------------------------------------------------------------------------
# engine: release paths under active sharing
# --------------------------------------------------------------------------
def test_blocks_released_on_deadline_shed_with_warm_cache(params):
    eng = _paged(params, max_batch_size=1, kv_block_size=8)
    try:
        p = list(range(1, 18))
        eng.generate(p, max_tokens=3)  # warm
        blocker = eng.submit(p, max_tokens=40)  # warm admit, shares pages
        doomed = eng.submit(p, max_tokens=2, deadline_ts=time.time() + 0.05)
        from ray_tpu.exceptions import DeadlineExceededError

        with pytest.raises(DeadlineExceededError):
            doomed.result(timeout=120)
        blocker.result(timeout=120)
        _assert_no_leak(eng)
    finally:
        eng.shutdown()


def test_blocks_released_on_disconnect_mid_prefill_under_sharing(params):
    eng = _paged(params, kv_block_size=8, prefill_chunk_tokens=8)
    try:
        p = list(range(1, 25))
        eng.generate(p, max_tokens=3)  # warm: 3+ blocks cached
        entered = threading.Event()
        real = eng.runner._prefill_chunk

        def slow(*a, **k):
            entered.set()
            time.sleep(0.1)
            return real(*a, **k)

        eng.runner._prefill_chunk = slow
        # partial hit + a 12-token uncached suffix -> at least 2 chunks
        stream = eng.submit_stream(p + list(range(50, 62)), max_tokens=20)
        assert entered.wait(timeout=60)
        stream.close()  # abandon while its prefill is still running
        _wait(lambda: eng.stats()["active_slots"] == 0
              and eng.stats()["prefilling"] == 0
              and eng.stats()["queued"] == 0)
        _wait(lambda: eng.stats()["kv_blocks_in_use"]
              == eng.stats()["prefix_cache_blocks"])
        eng.runner._prefill_chunk = real
        # the pool still serves warm traffic afterwards
        assert len(eng.generate(p, max_tokens=3)) == 3
        _assert_no_leak(eng)
    finally:
        eng.shutdown()


def test_loop_crash_invalidates_whole_cache(params):
    """After _fail_inflight resets the device pool, every cached page's
    contents are gone — the index must drain with them, and the next warm
    prompt is a MISS that still decodes correctly."""
    eng = _paged(params, kv_block_size=8)
    try:
        p = list(range(1, 18))
        want = eng.generate(p, max_tokens=4)
        assert eng.stats()["prefix_cache_blocks"] > 0
        real = eng.runner._decode_k_paged
        eng.runner._decode_k_paged = lambda *a, **k: (_ for _ in ()).throw(
            RuntimeError("injected decode fault")
        )
        with pytest.raises(RuntimeError):
            eng.submit(p, max_tokens=8).result(timeout=120)
        _wait(lambda: eng.stats()["kv_blocks_in_use"] == 0)
        assert eng.stats()["prefix_cache_blocks"] == 0  # drained, not leaked
        eng.runner._decode_k_paged = real
        misses = eng.stats()["prefix_cache_misses"]
        assert eng.generate(p, max_tokens=4) == want  # recomputed, identical
        assert eng.stats()["prefix_cache_misses"] == misses + 1
        _assert_no_leak(eng)
    finally:
        eng.shutdown()


def test_shutdown_with_populated_cache(params):
    eng = _paged(params, kv_block_size=8)
    eng.generate(list(range(1, 18)), max_tokens=3)
    assert eng.stats()["prefix_cache_blocks"] > 0
    eng.shutdown()  # must not raise; gauges zeroed with pages still cached


# --------------------------------------------------------------------------
# engine: pool pressure + determinism
# --------------------------------------------------------------------------
def test_pool_short_admission_evicts_cache_before_holding(params):
    eng = _paged(params, max_batch_size=1, kv_num_blocks=5)  # 4 usable
    try:
        assert len(eng.generate([1] * 40, max_tokens=20)) == 20
        assert eng.stats()["prefix_cache_blocks"] == 3  # 59 tokens, bs=16
        # a different prompt needs all 4 pages: admission LRU-sweeps the
        # cache instead of holding (no other request will ever free pages)
        assert len(eng.generate([2] * 40, max_tokens=20)) == 20
        assert eng.stats()["prefix_evictions"] >= 3
        _assert_no_leak(eng)
    finally:
        eng.shutdown()


def test_evict_scanned_moves_with_an_evicting_admission_and_not_with_decode_steps(params):
    eng = _paged(params, max_batch_size=1, kv_num_blocks=5)  # 4 usable
    try:
        assert len(eng.generate([1] * 40, max_tokens=20)) == 20
        st0 = eng.stats()
        assert st0["prefix_cache_blocks"] == 3
        assert st0["prefix_evict_scanned"] == 0 and st0["prefix_evictions"] == 0  # a roomy pool never sweeps
        stream = eng.submit_stream([2] * 40, max_tokens=20)
        next(stream)  # admitted: 3 pages short, so the chain of 3 went, tail first
        st1 = eng.stats()
        assert st1["prefix_evictions"] == 3
        assert st1["prefix_evict_scanned"] == 3 + 2  # one pass over 3 nodes, then the 2 parents
        assert len(list(stream)) == 19
        _wait(lambda: eng.stats()["active_slots"] == 0)
        st2 = eng.stats()
        assert st2["decode_steps"] - st0["decode_steps"] >= 19
        assert st2["prefix_evict_scanned"] == st1["prefix_evict_scanned"]  # no step, emit or retire moved it
        assert _engine_sources()[-1]["prefix_evict_scanned"] == 5
        _assert_no_leak(eng)
    finally:
        eng.shutdown()


def test_never_fitting_prompt_rejected_with_cache_populated(params):
    eng = _paged(params, kv_num_blocks=4)  # 3 usable blocks
    try:
        eng.generate(list(range(1, 18)), max_tokens=2)  # caches 1 block
        with pytest.raises(ValueError, match="never be admitted"):
            eng.submit([1] * 40, max_tokens=20)  # needs 4 > 3 total
        _assert_no_leak(eng)
    finally:
        eng.shutdown()


def test_pool_exhaustion_shed_is_typed_with_retry_hint():
    from ray_tpu.exceptions import OverloadedError

    a = BlockAllocator(4)
    held = a.alloc(2)
    a.share(held)  # sharing must not change the exhaustion contract
    with pytest.raises(OverloadedError) as exc:
        a.alloc(2)
    assert exc.value.layer == "engine" and exc.value.reason == "kv_blocks"
    assert exc.value.retry_after_s > 0
    a.free(held)
    a.free(held)
    assert a.free_blocks == 3


def test_engine_eviction_deterministic_across_runs(params):
    """Same workload on a bounded cache, twice: identical surviving chain
    keys, identical eviction and hit counters."""
    prompts = [list(range(1, 18)), list(range(40, 57)),
               list(range(1, 22)), list(range(60, 77))]

    def run():
        eng = _paged(params, kv_block_size=8, prefix_cache_max_blocks=4)
        try:
            for p in prompts:
                eng.generate(p, max_tokens=3)
            st = eng.stats()
            return (sorted(eng.store.prefix.keys()), st["prefix_evictions"],
                    st["prefix_cache_hits"], st["prefix_cache_partial"],
                    st["prefix_cache_misses"])
        finally:
            eng.shutdown()

    assert run() == run()


def test_prefix_metric_families_registered(params):
    from ray_tpu.observability import metric_defs

    names = {m.name for m in metric_defs.ALL_METRICS}
    for family in (
        "llm_prefix_cache_hits_total",
        "llm_prefix_cache_blocks",
        "llm_kv_blocks_shared",
        "llm_prefix_evictions_total",
    ):
        assert family in names
    eng = _paged(params, kv_block_size=8)
    try:
        p = list(range(1, 18))
        eng.generate(p, max_tokens=3)
        eng.generate(p, max_tokens=3)
        snap = _engine_sources()[-1]
        assert snap["prefix_cache_enabled"] is True
        assert snap["prefix_cache_blocks"] >= 2
        assert 0.0 < snap["prefix_hit_rate"] <= 1.0
        assert snap["prefix_tokens_reused"] >= 16
    finally:
        eng.shutdown()
