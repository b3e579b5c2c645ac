"""The serving engine is three boxes and the arrows point one way.

- by ``ast``: ``serve/model_runner.py`` imports nothing from ``ray_tpu.serve``,
  ``serve/sequence_store.py`` does not import ``serve/llm.py``, and the
  scheduler's six loop methods test neither of the two decisions that live
  behind those modules (``_bk``: what a decode step yields; ``_hybrid``: what
  state a sequence keeps)
- a ``SequenceStore`` driven alone against a runner that only records calls:
  the layer is testable without a device program, which is the point of the seam
"""

import ast
import copy
import inspect
import textwrap
import threading
from pathlib import Path
from types import SimpleNamespace

import pytest

from ray_tpu.models.transformer import TransformerConfig
from ray_tpu.serve import llm
from ray_tpu.serve.model_runner import TokenSteps
from ray_tpu.serve.prefix_cache import chain_keys
from ray_tpu.serve.sequence_store import SequenceStore

SERVE = Path(llm.__file__).parent
BS = 4


def _imports(path):
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            yield (node.module or "") if not node.level else "ray_tpu.serve." + (node.module or "")


def test_the_imports_run_one_way():
    runner = set(_imports(SERVE / "model_runner.py"))
    assert not [m for m in runner if m.startswith("ray_tpu.serve")], runner
    assert all(m.split(".")[0] in ("jax", "numpy", "functools", "typing", "__future__") or
               m.startswith(("ray_tpu.models", "ray_tpu.ops")) for m in runner), runner
    store = set(_imports(SERVE / "sequence_store.py"))
    assert "ray_tpu.serve.llm" not in store and "ray_tpu.serve" not in store, store
    assert {"ray_tpu.serve.kv_blocks", "ray_tpu.serve.prefix_cache", "ray_tpu.serve.model_runner"} <= store
    scheduler = set(_imports(SERVE / "llm.py"))
    assert {"ray_tpu.serve.sequence_store", "ray_tpu.serve.model_runner"} <= scheduler


@pytest.mark.parametrize("method", ["_dispatch", "_collect", "_loop", "_admit", "_prefill_enqueue", "_prefill_finish"])
def test_the_schedulers_loop_tests_neither_decision(method):
    tree = ast.parse(textwrap.dedent(inspect.getsource(getattr(llm.LLMEngine, method))))
    named = {n.attr for n in ast.walk(tree) if isinstance(n, ast.Attribute)} | \
            {n.id for n in ast.walk(tree) if isinstance(n, ast.Name)}
    assert not named & {"_bk", "_hybrid", "block", "hybrid"}, named
    assert not hasattr(llm.LLMEngine, "_dispatch_blocks") and not hasattr(llm.LLMEngine, "_collect_blocks")


# ---------------------------------------------------------------------------
# the store alone
# ---------------------------------------------------------------------------
class RecordingRunner:
    """What the store asks of the device, written down and not done."""

    def __init__(self, B):
        self.steps = TokenSteps(B, 1)
        self.snaps = {"state": None}
        self.state_bytes_per_slot = self.kv_bytes_per_token = 0
        self.calls = []

    def copy_page(self, src, dst):
        self.calls.append(("copy_page", src, dst))

    def snapshot_rows(self, slots, entries, n):
        self.calls.append(("snapshot_rows", slots[:n].tolist(), entries[:n].tolist()))


def _request(prompt, max_tokens=4, eos_id=None, slot=-1):
    keys = tuple(chain_keys(prompt, len(prompt) // BS, BS))
    return SimpleNamespace(prompt=list(prompt), max_tokens=max_tokens, eos_id=eos_id, block_keys=keys, branch_at=0,
                           snap=None, slot=slot, generated=[], prefill_pos=0)


def _store(cfg, *, pages=9, B=2, n_snapshots=0):
    runner = RecordingRunner(B)
    lock = threading.Lock()
    return SequenceStore(cfg, runner, lock, B=B, S=32, kv_block_size=BS, kv_num_blocks=pages, n_snapshots=n_snapshots,
                         gap=64, tags={"layer": "engine", "engine": "test"}), runner, lock


def _serve(store, lock, req, slot, generated):
    """Admit ``req`` into ``slot``, let it generate, and retire it."""
    with lock:
        got = store.reserve_locked(req, slot)
    assert got is not None
    req.slot, req.generated = slot, list(generated)
    with lock:
        store.retire_locked(req)
    return got


PLAIN = TransformerConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=2, d_ff=64, max_seq_len=64)


def test_a_full_prompt_hit_pins_the_tail_page_and_copies_it():
    store, runner, lock = _store(PLAIN)
    prompt = list(range(1, 9))  # two whole pages
    miss = _serve(store, lock, _request(prompt), 0, [50, 51, 52])
    assert (miss.matched, miss.cow_src, miss.result) == (0, -1, "miss")
    assert len(store.prefix) == 2 and store.allocator.used_blocks == 2  # retired: both prompt pages published
    cached = store.prefix.match(prompt)[0]

    req = _request(prompt)
    with lock:
        got = store.reserve_locked(req, 1)
    # the first page is shared into the table, the tail page is the copy's source and stays pinned meanwhile
    assert (got.matched, got.cow_src, got.result) == (BS, cached[1], "hit")
    assert store.slot_blocks[1][0] == cached[0] and got.cow_dst == store.slot_blocks[1][1] != cached[1]
    assert store.allocator.refcount(cached[0]) == 2 and store.allocator.refcount(cached[1]) == 2
    store.copy_tail(got)
    assert runner.calls == [("copy_page", cached[1], got.cow_dst)]
    assert store.allocator.refcount(cached[1]) == 1 and store.cow_count == 1  # the copy's pin is gone
    s = store.stats_locked()
    assert (s["prefix_cache_hits"], s["prefix_cache_misses"], s["prefix_tokens_reused"]) == (1, 1, len(prompt) - 1)


def test_a_full_pool_evicts_the_victims_prefix_cache_evict_gives_in_its_order():
    store, _, lock = _store(PLAIN, pages=7)  # six usable pages
    for i, first in enumerate((1, 21, 41)):  # three cold chains of two pages each fill the pool
        _serve(store, lock, _request(range(first, first + 8), max_tokens=1), i % 2, [60])
    assert store.allocator.free_blocks == 0 and len(store.prefix) == 6

    # the same books, asked directly: what the sweep would take, in order
    want = copy.deepcopy(store.prefix).evict(3, store.evictable)

    freed = []
    real_free = store.allocator.free
    store.allocator.free = lambda pages: (freed.append(list(pages)), real_free(pages))[1]
    req = _request(range(90, 99), max_tokens=3)  # 9 + 3 - 1 positions: three pages
    with lock:
        got = store.reserve_locked(req, 0)
    assert got is not None and got.evicted == 3 and freed[0] == want
    assert store.prefix.evictions == 3 and len(store.prefix) == 3
    # a pool that cannot be made to hold the request says "not yet" and leaves nothing pinned
    with lock:
        assert store.reserve_locked(_request(range(1, 9), max_tokens=25), 1) is None
    assert store.allocator.shared_blocks == 0


HYBRID = TransformerConfig(
    vocab_size=64, d_model=32, n_layers=4, n_heads=2, d_ff=64, max_seq_len=64, qk_norm=True, qk_norm_whole=True,
    pre_norms=False, post_norms=True, rope_full_layers=False, tie_embeddings=False,
    layer_types=("linear", "linear", "linear", "full"), linear_heads=2, linear_key_dim=8, linear_value_dim=16,
    linear_conv_width=4)


def test_a_snapshot_is_taken_kept_replaced_published_and_reclaimed():
    store, runner, lock = _store(HYBRID, n_snapshots=2)
    assert store.keeps_state and store.state_stats_locked()["state_snapshot_pool_size"] == 2
    prompt = list(range(1, 9))
    req = _request(prompt, max_tokens=6, eos_id=63)
    with lock:
        got = store.reserve_locked(req, 0)
    req.slot = 0
    assert (got.matched, got.snapshot) == (0, -1)  # nothing cached: the slot starts from zero
    assert store.snapshot_after_prompt(req) == 8  # an EOS may end the reply before the next page ends

    (taken,) = store.take_snapshots([(req, 8)])  # behind the chunk that ends the prompt's pages
    assert runner.calls == [("snapshot_rows", [0], [taken[1]])]
    store.keep_snapshot(*taken)
    assert req.snap == (taken[1], 8) and store.snap_pool.in_use == 1
    # the decode step that ends the next page replaces it
    req.generated = [40, 41, 42, 43]
    assert store.rows_ending_a_page([(0, req)], [11]) == [(req, 12)]
    (newer,) = store.take_snapshots([(req, 12)])
    store.keep_snapshot(*newer)
    assert req.snap == (newer[1], 12) and store.snap_pool.in_use == 1 and store.state_snapshots_taken == 2
    # retired: three pages and the snapshot go to the radix node 12 tokens deep
    req.generated.append(44)
    with lock:
        store.retire_locked(req)
    assert req.snap is None and store.prefix.snapshots == 1 and store.snap_pool.in_use == 1
    assert store.prefix.snapshot_at(prompt + req.generated) == (newer[1], 12)
    # the next request with these tokens restores from it, short of its last token
    again = _request(prompt + [40, 41, 42, 43, 44], max_tokens=2)
    with lock:
        got = store.reserve_locked(again, 1)
    assert (got.matched, got.snapshot) == (12, newer[1]) and len(store.slot_blocks[1]) == 4
    with lock:
        store.release_locked(1, again)
    # evicting the pages reclaims the entry
    assert store.flush_prefix_cache() == 3
    assert store.snap_pool.in_use == 0 and store.prefix.snapshots == 0 and store.allocator.used_blocks == 0


# ---------------------------------------------------------------------------
# what combines with what: one table
# ---------------------------------------------------------------------------
BLOCK = TransformerConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=2, d_ff=64, max_seq_len=64, block_length=4,
                          mask_token_id=63)
EXPERTS = TransformerConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=2, d_ff=64, max_seq_len=64, num_experts=4,
                            expert_top_k=2)
MESH = object()  # the table asks only whether there is one
# (a configuration with the row's property, one without it)
ROWS = {"block": (BLOCK, PLAIN), "autoregressive": (PLAIN, BLOCK), "slot state": (HYBRID, PLAIN), "no slot state": (PLAIN, HYBRID),
        "mesh": (PLAIN, PLAIN), "dense_stack or dropless": (EXPERTS, PLAIN)}
NAMED = dict(kv_block_size=6, max_seq_len=62, state_snapshots=4, role="decode", tp="tp", axes=("x",), denoising_steps=9)


@pytest.mark.parametrize("row,head,what,why", [(row, head, what, why) for row, head, cells in llm._REFUSED
                                               for what, why in cells.items()],
                         ids=lambda v: v if isinstance(v, str) and len(v) < 24 else "")
def test_each_entry_of_the_table_refuses_its_pair_by_name_and_serves_the_config_without_the_property(row, head, what, why):
    held, free = ROWS[row]
    with pytest.raises(ValueError) as err:
        llm._check_combination(held, MESH if row == "mesh" else None, {what: True}, **NAMED)
    said = str(err.value)
    assert why.format(block=held.block, **NAMED) in said and said.startswith(head.format(block=held.block))
    llm._check_combination(free, None, {what: True}, **NAMED)
    llm._check_combination(held, MESH if row == "mesh" else None, {what: False}, **NAMED)
