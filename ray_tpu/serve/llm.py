"""LLM serving: continuous batching over a shared KV cache.

The reference's Serve ships no inference engine (its LLM guides delegate to
vLLM on GPU). On TPU the engine IS the framework's job, and the design is
dictated by XLA's static-shape compilation model:

- **Fixed decode slots.** B = ``max_batch_size`` decode slots; a request
  occupies a slot from admission to completion and every decode step is ONE
  jitted program over all B slots (inactive slots compute masked garbage —
  the static-shape price, paid in exchange for zero recompiles at any
  admission pattern).
- **Paged KV cache.** K/V live in a shared HBM pool of fixed-size pages
  ``[L, num_blocks, block_size, Hkv*Dh]``; each slot names its pages in a
  static-shape ``int32[B, max_blocks_per_slot]`` block table
  (PagedAttention, Kwon et al. 2023). Admission is block-aware — a request
  is admitted when enough PAGES are free, so HBM capacity is proportional
  to tokens actually reserved, not ``B * max_len``. Under a mesh the pool
  shards over its KV heads (``models/generation.paged_cache_spec``) and the
  same admission, prefill and decode programs run, partitioned by GSPMD.
- **Chunked prefill.** Prompts prefill in fixed-size chunks interleaved
  between decode steps (Sarathi-style bounded per-iteration budget,
  ``prefill_chunk_tokens``; 0 = one-shot with power-of-2 bucketing), so a
  long prompt stalls running decodes by at most one chunk's forward.
- **Prefix-aware KV reuse (on by default).** Finished
  requests publish the full blocks of prompt+completion into a radix
  prefix cache (``serve/prefix_cache.py``); admission matches the longest
  cached prefix and ``share()``s those pages straight into the new block
  table, so prefill starts at the first UNCACHED token and reserves pool
  budget only for the suffix. Pages are refcounted; a write that would
  land in a shared page goes through copy-on-write; when the pool runs
  short, unreferenced cached leaves are LRU-evicted before admission holds
  or sheds (vLLM PagedAttention / SGLang RadixAttention idiom).
- **Continuous batching.** New requests join between decode steps
  (vLLM-style iteration-level scheduling); finished ones free their slot
  and pages immediately. Per-request ``max_tokens`` and ``temperature``
  ride as device arrays, so mixed sampling configs share one compiled step.
- **One decode step in flight.** The loop dispatches step N+1 before it
  reads step N: the rows' last tokens stay on the device (the program hands
  them to its own next run), positions, tables and ``max_tokens`` counts the
  host knows ahead, so everything the host does in an iteration runs beside
  the device. An EOS or a cancel is seen one step late and costs one
  dropped row-step (``docs/tpu_design.md``, "Paged KV + chunked prefill").

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

- **Recurrent state beside the pages.** A config with "linear" layers
  (Gated DeltaNet: ``cfg.hybrid``) keeps keys and values in its full layers
  only; its linear layers carry a recurrent state and a convolution tail a
  sequence, which live in the cache at the sequence's decode slot. A slot's
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
  (``_branch_snapshot_at``). Both pools
  evict the least recently used, and requests are admitted in order of
  arrival: a waiting session keeps its pages and its snapshot only while
  the pools' turnover (the unreferenced pages over the rate new ones are
  asked for) outlasts its wait; past that every returning turn prefills its
  history again (``docs/tpu_design.md``, "State snapshots").

``LLMServer`` is the Serve-facing wrapper: a deployment class whose
replicas each own an engine; requests arrive via handle/HTTP and block on a
per-request Future.
"""

from __future__ import annotations

import functools
import threading
import time
from collections import Counter, deque
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.exceptions import DeadlineExceededError
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
from ray_tpu.observability import metric_defs
from ray_tpu.observability.sketch import LatencySketch
from ray_tpu.observability.tracing import LoopClock
from ray_tpu.ops.gated_delta import lane_group, unpack_state
from ray_tpu.runtime import admission
from ray_tpu.runtime.context import (
    current_deadline_ts,
    current_request_trace,
    current_tenant,
)
from ray_tpu.serve.kv_blocks import BlockAllocator, SnapshotPool
from ray_tpu.serve.prefix_cache import PrefixCache, chain_keys

_STREAM_END = object()


class TokenBlock(NamedTuple):
    """What a diffusion config's stream delivers: one committed block's new
    tokens as one event; ``unmasked_at[i]`` is the denoising step (from 1) at
    which ``tokens[i]`` took its value."""

    tokens: List[int]
    unmasked_at: List[int]


#: The engine loop's phases (``llm::<phase>`` in a profiler trace, the keys
#: of ``stats()["loop_phase_s"]``), in the order an iteration meets them.
#: ``collect_wait``, ``prefill_wait`` and ``idle`` wait (for the device, for
#: a request); the other nine are the host path. The loop rests in the last.
LOOP_PHASES = (
    "evict", "admit", "prefill_enqueue", "dispatch_rows", "dispatch_enqueue", "collect_wait",
    "collect_counts", "emit", "prefill_wait", "prefill_counts", "first_token", "idle",
)
#: What a decode step found when it was enqueued: ``queued`` behind work
#: the device still had, ``dry`` (the step in flight had finished and no
#: chunk went ahead: the device idled until this call) or ``cold`` (no
#: step in flight: the batch had emptied or is starting).
_DISPATCH_KINDS = ("queued", "dry", "cold")

# prebuilt tag dicts for the per-request admission hot path
_EVICT_DISCONNECT_TAGS = {"reason": "disconnect"}
_LOOP_PHASE_TAGS = {p: {"phase": p} for p in LOOP_PHASES}
_DISPATCH_TAGS = {k: {"device": k} for k in _DISPATCH_KINDS}
_PREFIX_RESULT_TAGS = {
    "hit": {"result": "hit"},
    "partial": {"result": "partial"},
    "miss": {"result": "miss"},
}


@dataclass
class GenRequest:
    prompt: List[int]
    max_tokens: int = 32
    temperature: float = 0.0
    eos_id: Optional[int] = None
    future: Future = field(default_factory=Future)
    stream_queue: Optional[Any] = None  # queue.Queue when streaming
    # admission metadata: the requesting tenant (weighted fairness key) and
    # the PR-8 deadline riding the request context — an expired deadline
    # sheds on arrival so doomed work never occupies a decode slot
    tenant: Optional[str] = None
    deadline_ts: Optional[float] = None
    # consumer-gone flag (streaming): the stream pump marks an abandoned
    # iterator and the engine evicts the decode slot instead of generating
    # for nobody
    cancelled: bool = False
    # filled by the engine
    slot: int = -1
    generated: List[int] = field(default_factory=list)
    # decode tokens dispatched for this request, read back or not: a
    # ``max_tokens`` finish is known by this count, ahead of the device
    dispatched: int = 0
    # chunked prefill progress: prompt tokens already cached (paged engine)
    prefill_pos: int = 0
    # request-scope observability: the lifecycle trace born at the proxy
    # (None when tracing is off, the request skipped sampling, or the
    # engine is driven directly without a serve ingress) plus engine-side
    # perf_counter stamps that feed the per-engine latency sketches
    # whether or not a trace is riding along
    trace: Optional[Any] = None
    t_submit: float = 0.0
    t_first: float = 0.0
    t_last_tok: float = 0.0
    # queue-wait observed exactly once (a held head-of-line request is
    # resumed through _pop_admissible again and must not double-count)
    wfq_popped: bool = False
    # disaggregated serving (serve/disagg.py): an EXPORT request runs
    # chunked prefill, then stages its block set under this migration id
    # and resolves its future with a ticket instead of decoding; an IMPORT
    # request carries the producer's ticket + pulled block arrays and
    # joins the decode batch without prefilling
    export_mig_id: Optional[str] = None
    import_ticket: Optional[dict] = None
    import_arrays: Optional[Dict[int, Any]] = None
    # generation by diffusion over blocks: the steps a block is denoised in,
    # and the host's count of the block under way, kept ahead of the device:
    # positions of it the prompt's tail made known, and forwards it still
    # needs (its denoise steps, then the commit)
    denoising_steps: int = 0
    block_known: int = 0
    forwards_left: int = 0
    # a config with linear layers: the state snapshot taken for this request
    # and not yet published, (snapshot pool entry, tokens it covers), or None
    snap: Optional[Tuple[int, int]] = None
    # with a prefix cache: the digests of the prompt's whole blocks
    # (``prefix_cache.chain_keys``), computed once by ``submit`` on its
    # caller's thread; and, for a config with linear layers, the tokens of
    # the prompt after which prefill leaves a snapshot for the requests that
    # share them (``PrefixCache.branch_point``), 0 for none
    block_keys: Sequence[bytes] = ()
    branch_at: int = 0

    def emit(self, tok: int) -> None:
        if self.stream_queue is not None:
            self.stream_queue.put(tok)

    def emit_block(self, toks: List[int], unmasked_at: List[int]) -> None:
        if self.stream_queue is not None:
            self.stream_queue.put(TokenBlock(toks, unmasked_at))


class _TokenStream:
    """Iterator over a streaming request's tokens whose ``close()`` (called
    explicitly, via GC of an abandoned iterator, or by GeneratorExit
    propagation from a disconnected SSE client) marks the request
    ABANDONED — the engine frees its decode slot (or its waiting-queue
    budget, if never admitted) instead of generating for nobody.  A plain
    generator's finally-block cannot do this: closing a generator that
    never started skips its body entirely."""

    __slots__ = ("_gen", "_req", "_engine")

    def __init__(self, gen, req: GenRequest, engine: "LLMEngine"):
        self._gen = gen
        self._req = req
        self._engine = engine

    def __iter__(self):
        return self

    def __next__(self):
        return next(self._gen)

    def close(self) -> None:
        self._gen.close()
        if not self._req.future.done():
            self._engine._abandon_stream(self._req)

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: BLE001 — GC teardown must never raise
            pass


@dataclass
class _Flight:
    """A decode step dispatched and not yet read. ``rows`` are the (slot,
    request) pairs it decodes for, as they stood at dispatch: by the time
    the tokens are read a slot may be another request's."""

    out: Any  # device int32[B, K]; a block step: what it finished (``paged_block_step``)
    moe: list  # the expert layers' counts, where the program returns them
    rows: List[Tuple[int, GenRequest]]
    # a block step: slot -> known positions of the block this step commits
    commits: Dict[int, int] = field(default_factory=dict)
    # state snapshots taken right behind this step, tentative until its tokens
    # are read and kept: (request, snapshot pool entry, tokens covered)
    snaps: List[Tuple[GenRequest, int, int]] = field(default_factory=list)


def _bucket(n: int, lo: int = 16, cap: Optional[int] = None) -> int:
    """Smallest power-of-2 bucket >= n (floored at ``lo``), clamped to
    ``cap``. A length past the cap raises — the caller surfaces it as the
    typed never-fits ``ValueError`` at submit instead of letting the bucket
    grow past the cache and failing deep inside prefill."""
    if cap is not None and n > cap:
        raise ValueError(f"length {n} exceeds the cache capacity {cap}")
    b = lo
    while b < n:
        b *= 2
    return b if cap is None else min(b, cap)


class LLMEngine:
    """Continuous-batching decode engine for one model on one device/mesh.

    Thread model: callers enqueue via :meth:`submit` (thread-safe); one
    background loop admits requests and steps the batch. All jitted callables
    are built once in __init__ so the loop never traces.

    The KV pool is sized here and nowhere else. ``kv_block_size``: tokens a
    page (a multiple of the sublane tile, 8 for f32 and 16 for bf16, keeps
    the decode kernel's page reads aligned). ``kv_num_blocks``: pages in the
    pool, one the garbage page; 0 = every slot can hold ``max_seq_len``.
    ``prefill_chunk_tokens``: prompts enter the cache this many tokens at a
    time, a decode step between chunks; 0 = the uncached suffix in one
    power-of-2 bucketed call. ``prefix_cache``: finished requests' full
    blocks stay cached and are shared into later requests, at most
    ``prefix_cache_max_blocks`` of them (0 = what the pool can spare).
    ``state_snapshots``: entries of the state-snapshot pool of a config with
    linear layers (None = twice ``max_batch_size``; 0 = none: every request
    prefills its whole prompt); any other config has no such pool and takes
    only None or 0.
    """

    def __init__(
        self,
        cfg: TransformerConfig,
        params: Any,
        *,
        max_batch_size: int = 8,
        max_seq_len: int = 512,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        quantize: bool = False,
        quantize_min_size: int = 4096,
        mesh: Optional[Any] = None,
        tp: str = "tp",
        decode_chunk: int = 1,
        max_queued_requests: int = 256,
        max_queued_prefill_tokens: int = 0,
        tenant_weights: Optional[Dict[str, float]] = None,
        kv_block_size: int = 16,
        kv_num_blocks: int = 0,
        prefill_chunk_tokens: int = 0,
        prefix_cache: bool = True,
        prefix_cache_max_blocks: int = 0,
        role: Optional[str] = None,
        state_snapshots: Optional[int] = None,
    ):
        self.cfg = cfg
        self.B = max_batch_size
        self.S = max_seq_len
        # disaggregated pool role ("prefill"/"decode", "" = co-located).
        # Informational except for validation: either role can run either
        # path, the router just never sends a prefill replica decodes.
        if role not in (None, "", "prefill", "decode"):
            raise ValueError(f"role must be 'prefill' or 'decode', got {role!r}")
        self.role = role or ""
        self.kv_block_size = int(kv_block_size)
        if self.kv_block_size < 1:
            raise ValueError(f"kv_block_size must be >= 1, got {self.kv_block_size}")
        # static block-table width: enough logical blocks for a max-length
        # sequence — the table shape never depends on the allocation pattern
        self.max_blocks_per_slot = -(-self.S // self.kv_block_size)
        nb = int(kv_num_blocks)
        if nb <= 0:
            # auto: every slot can hold a max-length sequence (+1 for the
            # garbage page)
            nb = self.B * self.max_blocks_per_slot + 1
        self.kv_num_blocks = nb
        self.prefill_chunk_tokens = int(prefill_chunk_tokens)
        self._allocator = BlockAllocator(nb)
        self._prefix = (
            PrefixCache(self.kv_block_size, int(prefix_cache_max_blocks))
            if prefix_cache
            else None
        )
        # prefix-cache outcome counts per admitted request, tokens whose
        # prefill compute was skipped, and copy-on-write page copies
        self._prefix_results = {"hit": 0, "partial": 0, "miss": 0}
        self._prefix_tokens_reused = 0
        self._cow_count = 0
        # bounded waiting queue (overload survival, ISSUE 9): past the
        # request-count bound, or the prefill-token budget (0 = unbounded),
        # submit() sheds with a typed OverloadedError instead of growing
        # the waiting list while decode falls behind
        self._max_queued = max(0, int(max_queued_requests))
        self._max_queued_tokens = max(0, int(max_queued_prefill_tokens))
        self._queued_tokens = 0
        self.num_slots_evicted = 0
        self.num_shed = 0
        self._prefill_count = 0  # prompts fully prefilled
        # tokens generated per host round trip (1 = per-token stepping).
        # >1 amortizes dispatch/readback latency; admission and stream
        # emission happen at chunk granularity, and a request finishing
        # mid-chunk discards the tail tokens (identical outputs either way)
        self.decode_chunk = max(1, int(decode_chunk))
        # positions a decode step carries a row: 1, or a diffusion config's block
        self._bk = cfg.block
        if self._bk > 1:
            # no silent path: what block steps cannot honour yet is refused by name
            refused = {
                "decode_chunk > 1 (one block step a program)": self.decode_chunk > 1,
                "quantize=True": bool(quantize),
                "mesh (the block step runs the single-device paged kernels)": mesh is not None,
                f"kv_block_size {self.kv_block_size} (a page holds whole blocks of {self._bk})":
                    self.kv_block_size % self._bk != 0,
                f"max_seq_len {self.S} (whole blocks of {self._bk})": self.S % self._bk != 0,
            }
            bad = [k for k, v in refused.items() if v]
            if bad:
                raise ValueError(f"a config with block_length {self._bk} (generation by diffusion over blocks) "
                                 f"cannot be served with " + "; ".join(bad))
        # a config with linear layers keeps a recurrent state a sequence at
        # its slot, and a pool of snapshots of it beside the page pool
        self._hybrid = cfg.hybrid
        # layers that walk pages: K and V, or a latent layer's one row a token
        self._attn_layers = cfg.kv_layers + cfg.latent_layers
        if self._hybrid:
            # no silent path: what the slots' state cannot follow yet is refused by name
            refused = {
                "decode_chunk > 1 (a snapshot is taken behind one step's state)": self.decode_chunk > 1,
                "quantize=True (the int8 scales ride one stack of layers)": bool(quantize),
                "mesh (the state and the snapshot pool are not sharded)": mesh is not None,
            }
            bad = [k for k, v in refused.items() if v]
            if bad:
                raise ValueError('a config with "linear" layers (recurrent state a sequence) cannot be served '
                                 "with " + "; ".join(bad))
        elif state_snapshots:
            raise ValueError(f"state_snapshots={state_snapshots} belongs to a config with \"linear\" layers; "
                             "this one keeps no recurrent state")
        n_snapshots = (2 * self.B if state_snapshots is None else max(0, int(state_snapshots))) if self._hybrid else 0
        self._n_snapshots = n_snapshots  # the pool's size: fixed, read without the lock
        self._snap_pool = SnapshotPool(n_snapshots)
        self._snaps = None  # the device arrays of the pool (``_reset_cache``)
        self._state_snapshots_taken = 0
        self._state_restores = 0
        self._state_zeroed = 0
        self._prefix_tokens_matched = 0
        self.top_k = top_k
        self.top_p = top_p
        self.quantized = quantize
        self._kv_sharding = None
        if mesh is not None:
            # tensor-parallel serving: params shard per the Megatron layout
            # (ray_tpu.models.transformer.param_specs), the KV pool over its
            # heads when tp divides them (each device then holds whole pages
            # of its own heads); GSPMD partitions the einsum attention, so
            # decode collectives ride ICI. The Pallas decode kernel is
            # bypassed (GSPMD cannot partition a Mosaic kernel).
            from jax.sharding import NamedSharding

            from ray_tpu.models.transformer import _kv_tp_ok, shard_params

            if quantize:
                raise ValueError("quantize=True with mesh is not supported yet")
            if self.role:
                raise ValueError(
                    f"role={self.role!r} with mesh is not supported yet: migrated "
                    "blocks are exported from and landed in an unsharded pool"
                )
            if tp not in mesh.axis_names:
                raise ValueError(f"mesh has no {tp!r} axis: {mesh.axis_names}")
            params = shard_params(params, mesh, cfg, tp=tp, ep=tp)
            self._kv_sharding = NamedSharding(
                mesh, paged_cache_spec(tp if _kv_tp_ok(cfg, mesh, tp) else None)
            )
        if quantize and (cfg.dense_stack or cfg.dropless):
            # no silent path: the int8 scales ride ONE stack of layers whose
            # every weight is an xs leaf of the layer scan
            raise ValueError(
                "quantize=True does not cover a config with num_dense_layers > 0 or dropless expert layers "
                "(two layer stacks; expert weights read where they lie): serve it unquantized"
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

        # tenant-keyed weighted fair queue: pops interleave proportionally
        # to tenant_weights (default weight 1), so one hot tenant saturating
        # the queue cannot starve the others' admissions
        self._queue = admission.WeightedFairQueue(tenant_weights)
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._stop = False
        self._admission_token = admission.register_admission_source(
            "llm_engine", self.admission_snapshot
        )
        # per-engine series (keyed by the registry token): two engines
        # must not clobber each other's admission-depth gauge
        self._depth_tags = {"layer": "engine", "engine": str(self._admission_token)}
        # per-engine SLO latency sketches (deterministic fixed-boundary
        # quantiles, observability/sketch.py): fed from the engine's OWN
        # request timestamps, so TTFT/inter-token/queue-wait/e2e
        # percentiles exist even when the engine is driven directly
        # without a serve ingress (no trace riding the request). Written
        # only by the engine/request threads; snapshot readers tolerate a
        # torn single-counter read.
        self._sketches = {
            "ttft": LatencySketch(),
            "inter_token": LatencySketch(),
            "queue_wait": LatencySketch(),
            "e2e": LatencySketch(),
        }
        # bounded ring of recently terminated request summaries — the
        # flight recorder's raw material when the loop crashes
        self._finished_ring: deque = deque(maxlen=64)

        # slot state (host-side mirrors of the device arrays)
        self._slots: List[Optional[GenRequest]] = [None] * self.B
        # a row's last sampled token lives on the device (``_dev_toks``, what
        # the last dispatched step returned). ``_join_tok`` carries the tokens
        # the HOST sampled since the last dispatch (a sequence fresh from
        # prefill or migration), -1 elsewhere: the decode program takes a
        # row's token from here where it is >= 0. ``_pos`` is the position
        # the NEXT dispatch writes: it advances at dispatch, not at readback
        self._join_tok = np.full(self.B, -1, np.int32)
        # a diffusion config's rows join with their first block instead: the
        # prompt's tail as known positions, and the request's steps a block
        self._join = {
            "row": np.zeros(self.B, bool),
            "known": np.zeros(self.B, np.int32),
            "toks": np.zeros((self.B, self._bk), np.int32),
            "steps": np.ones(self.B, np.int32),
        }
        self._pos = np.zeros(self.B, np.int32)
        self._temps = np.zeros(self.B, np.float32)
        self._active = np.zeros(self.B, bool)
        # paged state: per-slot block tables (host mirror of the device
        # int32[B, M] array), pages held per slot, and slots reserved by a
        # request whose chunked prefill is still in flight (the slot is
        # taken but must not receive decode tokens yet)
        self._block_tables = np.zeros((self.B, self.max_blocks_per_slot), np.int32)
        self._slot_blocks: List[List[int]] = [[] for _ in range(self.B)]
        self._reserved = np.zeros(self.B, bool)
        self._prefilling: List[GenRequest] = []
        # head-of-line request popped from the fair queue but waiting for
        # pages: held (not re-pushed — that would break fair ordering)
        # until release paths free enough blocks
        self._held_req: Optional[GenRequest] = None
        self._prefill_chunk_count = 0
        # tokens of K/V the chunks' attention had to visit, averaged over the
        # layers (``_chunk_kv_visited``), and the capacity a chunk's table
        # spans: what the prefill kernel reads of what the dense lines read
        self._prefill_kv_visited = 0.0
        self._prefill_kv_capacity = 0
        # (window, layers that have it); 0: a full layer
        # (a linear layer has no K/V: ``_chunk_kv_visited`` still averages over every layer)
        self._layers_by_window = sorted(Counter(
            (0,) * self._attn_layers if self._hybrid else cfg.layer_windows or (0,) * cfg.n_layers).items())
        self._decode_step_count = 0
        # the decode step dispatched and not yet read (``_dispatch`` /
        # ``_collect``), steps dispatched while the one before was unread,
        # and rows computed past an EOS or a cancel and dropped unread
        self._flight: Optional[_Flight] = None
        self._decode_row_steps_discarded = 0
        # where the loop's time goes and what each step found on the device:
        # engine-thread-owned like ``_slots``, copied by ``stats()``
        self._clock = LoopClock(LOOP_PHASES)
        self._dispatches = dict.fromkeys(_DISPATCH_KINDS, 0)
        # block steps (a diffusion config): forwards of live rows (denoise and
        # commit), blocks committed, tokens they emitted and positions they
        # unmasked, and blocks a cancelled row left uncommitted
        self._block_row_forwards = 0
        self._block_commits = 0
        self._tokens_emitted = 0
        self._tokens_unmasked = 0
        self._blocks_dropped = 0
        # the dropless expert layers' own counters (models/generation.py,
        # ``paged_forward_counted``): the prefill and decode programs return
        # them beside the tokens and the loop adds them up when it reads the
        # step's tokens. For any other config the programs drop them
        self._moe_counted = cfg.dropless
        # (a config that holds a share of its experts counts those it holds,
        # and beside them the pairs its routers chose over all the experts)
        self._moe_expert_assignments = np.zeros(max(cfg.experts_here, 1), np.int64)
        self._moe_routed = 0
        self._moe_experts_hit = 0
        self._moe_experts_hit_decode = 0
        # disaggregated serving: staged exports parked by migration id
        # (the extracted block arrays outlive the prefill request's pool
        # pages — those retire into the prefix cache at export) and the
        # in/out migration counters surfaced by stats()/rt llm
        self._staged: Dict[str, dict] = {}
        self.num_migrations_out = 0
        self.num_migrations_in = 0
        metric_defs.LLM_KV_BLOCK_POOL_SIZE.set(self._allocator.capacity, self._depth_tags)
        metric_defs.LLM_KV_BLOCKS_IN_USE.set(0, self._depth_tags)
        metric_defs.LLM_KV_BLOCKS_SHARED.set(0, self._depth_tags)
        metric_defs.LLM_PREFIX_CACHE_BLOCKS.set(0, self._depth_tags)
        if self._hybrid:
            metric_defs.LLM_STATE_SNAPSHOT_POOL_SIZE.set(self._n_snapshots, self._depth_tags)
            metric_defs.LLM_STATE_SNAPSHOTS_IN_USE.set(0, self._depth_tags)
        if cfg.experts_held is not None:
            metric_defs.LLM_MOE_EXPERTS_HELD.set(cfg.experts_here, self._depth_tags)

        self._reset_cache()
        if cfg.latent_layers:
            metric_defs.LLM_LATENT_LAYERS.set(cfg.latent_layers, self._depth_tags)
            metric_defs.LLM_KV_BYTES_PER_TOKEN.set(self._kv_bytes_per_token, self._depth_tags)
        self._key = jax.random.key(np.random.randint(0, 2**31 - 1))

        cfg_ = cfg
        moe_counted = self._moe_counted
        layer_scales = self._layer_scales
        # under a mesh the einsum path partitions via GSPMD; the Pallas
        # paged kernels (decode and prefill) stay for the single-device engine
        use_kernel = None if mesh is None else False
        # under a mesh a program that returns the pool returns it as it was
        # placed: the donated buffers are updated where they lie and the next
        # call finds the sharding it was compiled for (left to itself GSPMD
        # re-shards a replicated pool). None, jit's default, on one device
        kv_sharding = self._kv_sharding

        def pool_among(n_outputs: int):  # the expert counts, where returned, come last
            rest = (None,) * (n_outputs - 2 + moe_counted)
            return None if kv_sharding is None else (None, kv_sharding) + rest

        top_k_, top_p_ = self.top_k, self.top_p

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
        # donated: the engine holds the only reference and reassigns, so
        # XLA updates the pool's buffers in place.  It also hands back every
        # row's last token as a device array, which the next run takes as
        # it is: the loop dispatches that run before it reads this one's.
        K_chunk = self.decode_chunk
        block = self._bk
        hybrid = self._hybrid

        @functools.partial(jax.jit, donate_argnums=(1,), out_shardings=pool_among(2))
        def _prefill_chunk(params, cache, toks, bt, start, length, slot=None):
            """toks [1, C] chunk-padded; bt [1, M]; start/length traced,
            so every chunk of every prompt at width C shares ONE
            compile. Writes K/V for the chunk's ``length`` real tokens
            through the block table and returns the last real token's
            logits [V] (only the final chunk's are consumed). ``slot`` [1]
            (a config with linear layers): where the sequence's state lives."""
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
            # (and an idle row's recurrent state stays as it is)
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

        self._thread = threading.Thread(target=self._loop, daemon=True, name="llm-engine")
        self._thread.start()

    # -- public API ---------------------------------------------------------
    def submit(
        self,
        prompt: List[int],
        *,
        max_tokens: int = 32,
        temperature: float = 0.0,
        eos_id: Optional[int] = None,
        tenant: Optional[str] = None,
        deadline_ts: Optional[float] = None,
        denoising_steps: Optional[int] = None,
        _stream_queue=None,
    ) -> Future:
        """Enqueue one request; resolves to the generated token-id list.

        ``denoising_steps`` (a diffusion config only; default: its
        ``block_length``): the steps a block is denoised in, 1 to
        ``block_length``; fewer steps, fewer forwards a token.

        ``tenant`` (default: the request-context tenant id set by the
        ingress) keys weighted fair queuing; ``deadline_ts`` (default: the
        PR-8 deadline riding the request context) sheds on arrival when
        already expired.  Raises OverloadedError when the bounded waiting
        queue (count or prefill-token budget) is full."""
        return self._submit_req(
            prompt,
            max_tokens=max_tokens,
            temperature=temperature,
            eos_id=eos_id,
            tenant=tenant,
            deadline_ts=deadline_ts,
            denoising_steps=denoising_steps,
            _stream_queue=_stream_queue,
        ).future

    def _submit_req(
        self,
        prompt: List[int],
        *,
        max_tokens: int = 32,
        temperature: float = 0.0,
        eos_id: Optional[int] = None,
        tenant: Optional[str] = None,
        deadline_ts: Optional[float] = None,
        denoising_steps: Optional[int] = None,
        _stream_queue=None,
        _export_mig_id: Optional[str] = None,
        _import_ticket: Optional[dict] = None,
        _import_arrays: Optional[Dict[int, Any]] = None,
    ) -> GenRequest:
        if self._stop:
            raise RuntimeError("LLMEngine is shut down")
        if not prompt:
            raise ValueError("empty prompt")
        if max_tokens < 1:
            raise ValueError(f"max_tokens must be >= 1, got {max_tokens}")
        if len(prompt) + max_tokens > self.S:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_tokens ({max_tokens}) exceeds "
                f"engine max_seq_len {self.S}"
            )
        if self._hybrid and (_export_mig_id is not None or _import_ticket is not None):
            raise ValueError('prefill_export / adopt_migration are not supported for a config with "linear" layers: '
                             "a migrated block set carries pages, not the sequence's recurrent state")
        if self._bk == 1:
            if denoising_steps is not None:
                raise ValueError("denoising_steps belongs to a config that generates by diffusion over blocks "
                                 "(block_length > 1); this engine's config is autoregressive")
        else:
            if _export_mig_id is not None or _import_ticket is not None:
                raise ValueError("prefill_export / adopt_migration are not supported for a config that generates "
                                 "by diffusion over blocks: an exported prefill carries no first token")
            if denoising_steps is None:
                denoising_steps = self._bk
            if not 1 <= int(denoising_steps) <= self._bk:
                raise ValueError(f"denoising_steps must be 1 to the config's block_length {self._bk}, "
                                 f"got {denoising_steps}")
        # never-fits contract (same as max_queued_prefill_tokens below):
        # a request needing more pages than the POOL holds can never be
        # admitted — that is a config/input error at submit, not a
        # retry-after-able overload and not a failure deep in prefill
        needed = self._pages_needed(len(prompt), max_tokens)
        if needed > self._allocator.capacity:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_tokens ({max_tokens}) needs "
                f"{needed} KV blocks but the pool only holds "
                f"{self._allocator.capacity} and would never be admitted"
            )
        if self._max_queued_tokens and len(prompt) > self._max_queued_tokens:
            # a prompt that ALONE exceeds the budget can never be admitted:
            # that is a config/input error, not a retry-after-able overload
            raise ValueError(
                f"prompt ({len(prompt)} tokens) exceeds the engine's "
                f"max_queued_prefill_tokens budget ({self._max_queued_tokens}) "
                "and would never be admitted"
            )
        if tenant is None:
            tenant = current_tenant()
        if deadline_ts is None:
            deadline_ts = current_deadline_ts()
        # the lifecycle trace rode proxy -> router -> replica context to
        # get here; stamp the engine-submit boundary before any shed so a
        # shed request still shows where it died
        trace = current_request_trace()
        if trace is not None:
            trace.mark("engine_submit")
        if deadline_ts is not None and time.time() >= deadline_ts:
            # shed-on-arrival: the deadline already expired — admitting
            # would burn prefill + a decode slot on an answer nobody can
            # use.  The typed signal is the deadline error, not 429.
            with self._lock:  # += races the other shed paths' increments
                self.num_shed += 1
            admission.record_shed("engine", "deadline_expired")
            raise DeadlineExceededError("llm_request", "engine_admission", 0.0)
        # a long prompt's chain is a millisecond or two of hashing: here, on
        # the caller's thread, not at admission between two decode steps
        bs = self.kv_block_size
        block_keys = tuple(chain_keys(prompt, len(prompt) // bs, bs)) if self._prefix is not None else ()
        with self._lock:
            depth = len(self._queue)
            if self._max_queued and depth >= self._max_queued:
                self.num_shed += 1
                raise admission.shed(
                    "engine", "queue_full",
                    message=(
                        f"engine waiting queue at its {self._max_queued}-"
                        f"request bound"
                    ),
                )
            if (
                self._max_queued_tokens
                and self._queued_tokens + len(prompt) > self._max_queued_tokens
            ):
                self.num_shed += 1
                raise admission.shed(
                    "engine", "token_budget",
                    message=(
                        f"queued prefill tokens {self._queued_tokens} + "
                        f"{len(prompt)} exceed the "
                        f"{self._max_queued_tokens}-token budget"
                    ),
                )
            req = GenRequest(
                list(prompt), max_tokens, temperature, eos_id,
                stream_queue=_stream_queue, tenant=tenant,
                deadline_ts=deadline_ts, trace=trace,
            )
            req.denoising_steps = int(denoising_steps or 0)
            req.block_keys = block_keys
            req.export_mig_id = _export_mig_id
            req.import_ticket = _import_ticket
            req.import_arrays = _import_arrays
            req.t_submit = time.perf_counter()
            self._queue.push(req, tenant)
            self._queued_tokens += len(prompt)
            depth += 1
        metric_defs.ADMISSION_QUEUE_DEPTH.set(depth, self._depth_tags)
        metric_defs.TENANT_ADMISSIONS.inc(tags=admission.tenant_tags(tenant))
        self._wake.set()
        return req

    def _pages_needed(self, prompt_len: int, max_tokens: int) -> int:
        """Pages a request's whole budget takes. The last written position
        is ``prompt + max_tokens - 2`` (the last sampled token is never
        written); a diffusion config commits every emitted token and writes
        its last block whole, to the end of the block that holds position
        ``prompt + max_tokens - 1`` (a page holds whole blocks)."""
        last = prompt_len + max_tokens - (2 if self._bk == 1 else 1)
        return last // self.kv_block_size + 1

    def _fill_len(self, req: GenRequest) -> int:
        """Prompt tokens prefill has to cache: all of them, or for a
        diffusion config the prompt's whole blocks (the rest opens the
        first block as known positions)."""
        return len(req.prompt) - len(req.prompt) % self._bk

    def generate(self, prompt: List[int], **kw) -> List[int]:
        return self.submit(prompt, **kw).result()

    def submit_stream(self, prompt: List[int], *, token_timeout_s: float = 120.0, blocks: bool = False, **kw):
        """Per-token streaming: returns an iterator yielding token ids as
        they are sampled (the continuous-batching analog of the runtime's
        ObjectRefGenerator). Validation errors raise HERE, not mid-stream.
        The iterator ends at eos/max_tokens; engine errors re-raise at the
        end of iteration; a stalled engine raises after ``token_timeout_s``
        without a token (so consumers never block forever). A diffusion
        config delivers a committed block a time: its tokens one after the
        other, or with ``blocks`` each block as one :class:`TokenBlock`."""
        import queue as _queue

        q: "_queue.Queue" = _queue.Queue()
        req = self._submit_req(prompt, _stream_queue=q, **kw)
        return _TokenStream(self._stream_iter(req, q, token_timeout_s, blocks), req, self)

    def _stream_iter(self, req: GenRequest, q, token_timeout_s: float = 120.0, blocks: bool = False):
        """Generator draining ``req``'s stream queue until ``_STREAM_END``
        (shared by submit_stream and the disagg adopt-stream path)."""
        import queue as _queue

        fut = req.future
        while True:
            try:
                tok = q.get(timeout=token_timeout_s)
            except _queue.Empty:
                raise RuntimeError(
                    f"no token for {token_timeout_s}s — engine stalled or overloaded"
                ) from None
            if tok is _STREAM_END:
                exc = fut.exception() if fut.done() else None
                if exc is not None:
                    raise exc
                return
            if isinstance(tok, TokenBlock) and not blocks:
                yield from tok.tokens
            else:
                yield tok

    def _abandon_stream(self, req: GenRequest) -> None:
        """Consumer gone: if the request is still WAITING, drop it from the
        queue NOW (its count + prefill tokens stop holding the bounded
        budget against live traffic); if it holds a decode slot, flag it
        for eviction at the next engine-loop tick."""
        req.cancelled = True
        with self._lock:
            removed = self._queue.remove(req)
            if removed:
                self._queued_tokens -= len(req.prompt)
                self.num_shed += 1  # under the lock: += races other shed paths
            depth = len(self._queue)
        if removed:
            metric_defs.ADMISSION_QUEUE_DEPTH.set(depth, self._depth_tags)
            admission.record_shed("engine", "disconnect")
            self._record_done(req, "disconnect", "stream abandoned while queued")
            if not req.future.done():
                req.future.set_exception(
                    RuntimeError("stream consumer disconnected before admission")
                )
        else:
            self._wake.set()

    # -- disaggregated prefill/decode (serve/disagg.py) ---------------------
    def prefill_export(
        self,
        prompt: List[int],
        *,
        mig_id: str,
        temperature: float = 0.0,
        eos_id: Optional[int] = None,
        tenant: Optional[str] = None,
        deadline_ts: Optional[float] = None,
    ) -> Future:
        """Prefill-pool entry point: chunked-prefill ``prompt`` into local
        paged KV, sample the first token, stage the block set under
        ``mig_id`` and resolve the future with the migration ticket
        (header-only — zero KV payload bytes).  The request reserves only
        the prompt's pages (``max_tokens=1``): decode never runs here."""
        return self._submit_req(
            prompt, max_tokens=1, temperature=temperature, eos_id=eos_id,
            tenant=tenant, deadline_ts=deadline_ts, _export_mig_id=mig_id,
        ).future

    def adopt_migration(
        self,
        ticket: dict,
        arrays: Dict[int, Any],
        *,
        max_tokens: int = 32,
        temperature: float = 0.0,
        eos_id: Optional[int] = None,
        tenant: Optional[str] = None,
        deadline_ts: Optional[float] = None,
        _stream_queue=None,
    ) -> GenRequest:
        """Decode-pool entry point: join the continuous batch from a
        migrated block set.  ``arrays`` maps prompt block index -> the
        pulled ``[2, L, block_size, Hkv, Dh]`` stack (the caller pulls on
        its own thread — only the engine loop may touch the cache); block
        indices already covered by this replica's prefix cache may be
        omitted.  Admission, block budget, COW and prefix-cache semantics
        are the normal paged path; only prefill compute is skipped."""
        return self._submit_req(
            list(ticket["prompt"]), max_tokens=max_tokens,
            temperature=temperature, eos_id=eos_id, tenant=tenant,
            deadline_ts=deadline_ts, _stream_queue=_stream_queue,
            _import_ticket=dict(ticket),
            _import_arrays=dict(arrays),
        )

    def peek_prefix_match(self, prompt: List[int]) -> int:
        """Longest cached prefix (tokens) of ``prompt`` in THIS replica's
        prefix cache — the decode side probes before pulling so a warm
        prefix short-circuits re-migration of shared-prefix blocks.
        Advisory: admission re-matches, and a shrink in between surfaces
        as a typed migration error (the ladder re-prefills)."""
        if self._prefix is None:
            return 0
        with self._lock:
            _, matched = self._prefix.match(prompt)
        return matched

    def kv_free_blocks(self) -> int:
        """Free pages right now — the decode-pool routing signal."""
        with self._lock:
            return self._allocator.free_blocks

    def release_migration(self, mig_id: str) -> bool:
        """Drop a staged export: forget the arrays and unregister the
        host-fallback source.  Device-plane offers have no cancel API —
        unpulled ones expire via the transfer server's staging TTL (a
        documented device_plane caveat).  Idempotent; True if the staging
        existed.  The prefill-side POOL pages were already retired into
        the prefix cache at export, so this never touches the pool —
        exactly-once freeing is the export path's invariant."""
        with self._lock:
            entry = self._staged.pop(mig_id, None)
        if entry is None:
            return False
        from ray_tpu.runtime import data_plane

        data_plane.unregister_kv_block_source(mig_id)
        return True

    def fetch_staged_block(self, mig_id: str, block_idx: int):
        """One staged block.  Returns the staged device array as-is: the
        in-process rung adopts it without a host round-trip, and the
        data-plane ``kv_pull`` op host-converts it only when actually
        serving a remote pull."""
        with self._lock:
            entry = self._staged.get(mig_id)
        if entry is None:
            raise KeyError(f"no staged migration {mig_id!r}")
        return entry["arrays"][block_idx]

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            alloc = self._allocator
            return {
                "role": self.role,
                "migrations_out": self.num_migrations_out,
                "migrations_in": self.num_migrations_in,
                "staged_migrations": len(self._staged),
                "active_slots": int(self._active.sum()),
                "max_batch_size": self.B,
                "queued": len(self._queue),
                "queued_prefill_tokens": self._queued_tokens,
                "prefill_forwards": self._prefill_count,
                "slots_evicted": self.num_slots_evicted,
                "shed": self.num_shed,
                "kv_block_size": self.kv_block_size,
                "kv_block_pool_size": alloc.capacity,
                "kv_blocks_in_use": alloc.used_blocks,
                "kv_blocks_shared": alloc.shared_blocks,
                "prefilling": len(self._prefilling),
                "prefill_chunks": self._prefill_chunk_count,
                "prefill_kv_tokens_visited": self._prefill_kv_visited,
                "prefill_kv_tokens_capacity": self._prefill_kv_capacity,
                "prefix_cache_enabled": self._prefix is not None,
                "prefix_cache_blocks": len(self._prefix) if self._prefix is not None else 0,
                "prefix_cache_hits": self._prefix_results["hit"],
                "prefix_cache_partial": self._prefix_results["partial"],
                "prefix_cache_misses": self._prefix_results["miss"],
                "prefix_tokens_reused": self._prefix_tokens_reused,
                "prefix_evictions": self._prefix.evictions if self._prefix is not None else 0,
                "prefix_evict_scanned": self._prefix.scanned if self._prefix is not None else 0,
                "cow_copies": self._cow_count,
                "decode_steps": self._decode_step_count,
                # dispatched while the step before was still unread: all but the cold ones
                "decode_steps_overlapped": self.decode_chunk * (self._dispatches["queued"] + self._dispatches["dry"]),
                "decode_row_steps_discarded": self._decode_row_steps_discarded,
                "decode_dispatches": dict(self._dispatches),
                "loop_phase_s": dict(self._clock.seconds),
                "kv_read_share": self.kv_read_share(),
                "kv_live_pages": self.kv_live_pages(),
                **self._moe_stats_locked(),
                **self._block_stats_locked(),
                **self._state_stats_locked(),
            }

    def _state_stats_locked(self) -> Dict[str, Any]:
        """The recurrent state's own counters (absent for a config without
        linear layers): the snapshot pool's size and entries held (by live
        requests and by radix nodes), snapshots taken, snapshots detached
        because the pool was full, slots restored from a snapshot and slots
        zeroed at admission, the prompt tokens a page match offered
        (``prefix_tokens_reused`` beside it: those a snapshot let the engine
        skip), and the bytes a slot's state and convolution tail take."""
        if not self._hybrid:
            return {}
        return {
            "state_snapshot_pool_size": self._snap_pool.size,
            "state_snapshots_in_use": self._snap_pool.in_use,
            "state_snapshots_taken": self._state_snapshots_taken,
            "state_snapshots_evicted": self._prefix.snapshot_evictions if self._prefix is not None else 0,
            "state_restores": self._state_restores,
            "state_zeroed": self._state_zeroed,
            "prefix_tokens_matched": self._prefix_tokens_matched,
            "state_bytes_per_slot": self._state_bytes_per_slot,
            **self._latent_stats(),
        }

    def _latent_stats(self) -> Dict[str, Any]:
        """A config with latent layers: how many, and the bytes a cached token
        takes in all the pools as they were built (every attention layer, the
        pad lanes included)."""
        if not self.cfg.latent_layers:
            return {}
        return {"latent_layers": self.cfg.latent_layers, "kv_bytes_per_token": self._kv_bytes_per_token}

    def _block_stats_locked(self) -> Dict[str, Any]:
        """A diffusion config's own counters (absent otherwise): block steps
        (its decode steps), forwards of live rows in them (denoise and
        commit), blocks committed, the tokens they emitted and the positions
        they unmasked, and blocks a cancelled row left uncommitted."""
        if self._bk == 1:
            return {}
        return {
            "block_length": self._bk,
            "block_steps": self._decode_step_count,
            "block_row_forwards": self._block_row_forwards,
            "block_commits": self._block_commits,
            "tokens_emitted": self._tokens_emitted,
            "tokens_unmasked": self._tokens_unmasked,
            "blocks_dropped": self._blocks_dropped,
        }

    def _moe_stats_locked(self) -> Dict[str, Any]:
        """The expert layers' running totals (absent for a config without
        dropless expert layers): (token, choice) pairs routed, per expert and
        in all; (layer, expert) pairs that got at least one token, over all
        program runs and over the decode steps alone. A config that holds a
        share of its experts: ``moe_experts_held``, ``moe_assignments`` the
        pairs routed over all the experts and ``moe_assignments_local`` those
        that landed on the experts held."""
        if not self._moe_counted:
            return {}
        local = int(self._moe_expert_assignments.sum())
        out = {
            "moe_assignments": local,
            "moe_expert_assignments": self._moe_expert_assignments.tolist(),
            "moe_experts_hit": self._moe_experts_hit,
            "moe_experts_hit_decode": self._moe_experts_hit_decode,
            "moe_expert_layers": self.cfg.expert_layers,
        }
        if self.cfg.experts_held is not None:
            # a share: the per-expert and hit counters are over the experts
            # held; routed counts every choice, those that land elsewhere too
            out.update(moe_assignments=self._moe_routed, moe_assignments_local=local,
                       moe_experts_held=self.cfg.experts_here)
        return out

    def lowered_decode_text(self) -> str:
        """StableHLO text of the decode program as the loop runs it (same
        params, cache and slot-array shapes). ``chip_smoke.py`` looks for
        ``tpu_custom_call`` in it: which attention path the engine compiled
        is read from the program, not assumed from a flag."""

        def abstract(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)

        params, cache = jax.tree.map(abstract, (self.params, self._cache))
        toks = jax.ShapeDtypeStruct((self.B,), jnp.int32)
        temps = jax.ShapeDtypeStruct((self.B,), jnp.float32)
        bt = jax.ShapeDtypeStruct(self._block_tables.shape, jnp.int32)
        state, join = (toks, toks) if self._bk == 1 else jax.tree.map(abstract, (self._dev_toks, self._join_arrays()))
        return self._decode_k_paged.lower(params, cache, state, join, toks, temps, self._key, bt).as_text()

    def admission_snapshot(self) -> Dict[str, Any]:
        """Bounds + depths for GET /api/overload (admission source)."""
        with self._lock:
            alloc = self._allocator
            pool = alloc.capacity
            in_use = alloc.used_blocks
            probes = sum(self._prefix_results.values())
            useful = self._prefix_results["hit"] + self._prefix_results["partial"]
            return {
                "layer": "engine",
                "role": self.role,
                "migrations_out": self.num_migrations_out,
                "migrations_in": self.num_migrations_in,
                "staged_migrations": len(self._staged),
                "queued": len(self._queue),
                "queue_bound": self._max_queued,
                "queued_prefill_tokens": self._queued_tokens,
                "token_budget": self._max_queued_tokens,
                "active_slots": int(self._active.sum()),
                "slots": self.B,
                "by_tenant": self._queue.depth_by_tenant(),
                "slots_evicted": self.num_slots_evicted,
                "shed": self.num_shed,
                "kv_block_size": self.kv_block_size,
                "kv_block_pool_size": pool,
                "kv_blocks_in_use": in_use,
                "kv_blocks_shared": alloc.shared_blocks,
                "kv_block_occupancy": in_use / pool,
                "prefilling": len(self._prefilling),
                "prefill_chunks": self._prefill_chunk_count,
                "waiting_for_blocks": 1 if self._held_req is not None else 0,
                "prefix_cache_enabled": self._prefix is not None,
                "prefix_cache_blocks": len(self._prefix) if self._prefix is not None else 0,
                "prefix_hit_rate": (useful / probes) if probes else 0.0,
                "prefix_tokens_reused": self._prefix_tokens_reused,
                "prefix_evictions": self._prefix.evictions if self._prefix is not None else 0,
                "prefix_evict_scanned": self._prefix.scanned if self._prefix is not None else 0,
                "decode_steps": self._decode_step_count,
                "kv_read_share": self.kv_read_share(),
                **self._moe_stats_locked(),
                # SLO percentiles from the engine-side latency sketches
                # (ttft / inter_token / queue_wait / e2e, seconds)
                "latency": {
                    name: sk.percentiles() for name, sk in self._sketches.items()
                },
            }

    def shutdown(self) -> None:
        self._stop = True
        self._wake.set()
        self._thread.join(timeout=5)
        admission.unregister_admission_source(self._admission_token)
        # zero this engine's gauge series; the freed token (and thus the
        # series label) is reused by the next engine
        metric_defs.ADMISSION_QUEUE_DEPTH.set(0, self._depth_tags)
        metric_defs.LLM_KV_BLOCKS_IN_USE.set(0, self._depth_tags)
        metric_defs.LLM_KV_BLOCK_POOL_SIZE.set(0, self._depth_tags)
        metric_defs.LLM_KV_BLOCKS_SHARED.set(0, self._depth_tags)
        metric_defs.LLM_PREFIX_CACHE_BLOCKS.set(0, self._depth_tags)
        if self._hybrid:
            metric_defs.LLM_STATE_SNAPSHOT_POOL_SIZE.set(0, self._depth_tags)
            metric_defs.LLM_STATE_SNAPSHOTS_IN_USE.set(0, self._depth_tags)
        if self.cfg.latent_layers:
            metric_defs.LLM_LATENT_LAYERS.set(0, self._depth_tags)
            metric_defs.LLM_KV_BYTES_PER_TOKEN.set(0, self._depth_tags)
        if self.cfg.experts_held is not None:
            metric_defs.LLM_MOE_EXPERTS_HELD.set(0, self._depth_tags)
        with self._lock:
            pending = [r for r in self._queue.items() if not r.future.done()]
            pending += [r for r in self._slots if r is not None and not r.future.done()]
            pending += [r for r in self._prefilling if not r.future.done()]
            self._prefilling.clear()
            if self._held_req is not None:
                if not self._held_req.future.done():
                    pending.append(self._held_req)
                self._held_req = None
            self._queue.drain()
            self._queued_tokens = 0
            staged = list(self._staged)
            self._staged.clear()
        if staged:
            from ray_tpu.runtime import data_plane

            for mig_id in staged:
                data_plane.unregister_kv_block_source(mig_id)
        for r in pending:
            r.future.set_exception(RuntimeError("LLMEngine shut down"))
            if r.stream_queue is not None:
                r.stream_queue.put(_STREAM_END)

    def flush_prefix_cache(self) -> int:
        """Evict every prefix-cache entry not currently shared into a live
        request and return the number of pages freed.  Ops hook — also the
        leak-check primitive: on a quiesced engine, ``kv_blocks_in_use``
        equals ``prefix_cache_blocks`` and a flush takes both to zero."""
        if self._prefix is None:
            return 0
        with self._lock:
            pages = self._evict_pages_locked(len(self._prefix))
            if pages:
                self._allocator.free(pages)
            gauges = self._pool_gauges_locked()
        if pages:
            metric_defs.LLM_PREFIX_EVICTIONS.inc(len(pages))
        self._publish_pool_gauges(*gauges)
        return len(pages)

    def state_snapshot(self, tokens: List[int]) -> Optional[Dict[str, Any]]:
        """Read-out for a check (a config with linear layers, an engine at
        rest): the deepest state snapshot the prefix cache holds on the path
        of ``tokens``, as ``{"tokens": how many of them it covers, "state":
        the recurrent state after exactly those, float32 [linear layers,
        heads, key dim, value dim]}``, or None if no node on the path carries
        one. No clock of either pool moves. The engine thread replaces the
        pool's arrays whenever it takes a snapshot, so call this while
        nothing decodes."""
        if self._prefix is None or self._snaps is None:
            return None
        with self._lock:
            entry, covered = self._prefix.snapshot_at(tokens)
            snaps = self._snaps
        if entry < 0:
            return None
        group = lane_group(self.cfg.linear_heads, self.cfg.linear_value_dim)
        return {"tokens": covered, "state": np.asarray(unpack_state(snaps["state"][:, entry], group))}

    def _evictable(self, page: int) -> bool:
        """An eviction may only take pages whose sole reference is the
        cache's own — refcount 1 means no live block table names the page.
        Caller holds ``self._lock``."""
        return self._allocator.refcount(page) == 1

    def _evict_pages_locked(self, want: int) -> List[int]:
        """LRU-evict up to ``want`` unreferenced cached leaves and return
        their pages for the caller to free; the state snapshots of the nodes
        that went return to their pool here. Caller holds ``self._lock``."""
        pages = self._prefix.evict(want, self._evictable)
        self._reclaim_snapshots_locked()
        return pages

    def _reclaim_snapshots_locked(self) -> None:
        """Return to the snapshot pool the entries of radix nodes that went."""
        for entry in self._prefix.take_freed_snapshots():
            self._snap_pool.free(entry)

    def _drop_snapshot_locked(self, req: GenRequest) -> None:
        """A request leaves without publishing its pages: its snapshot goes too."""
        if req.snap is not None:
            self._snap_pool.free(req.snap[0])
            req.snap = None

    def _alloc_snapshot_locked(self) -> int:
        """An entry of the snapshot pool: a free one, else the least recently
        used one a radix node carries (its pages stay), else -1: the caller
        goes without. Snapshot exhaustion fails no request."""
        entry = self._snap_pool.alloc()
        if entry < 0 and self._prefix is not None:
            freed = self._prefix.evict_snapshot()
            if freed >= 0:
                self._snap_pool.free(freed)
                entry = self._snap_pool.alloc()
        return entry

    def _pool_gauges_locked(self):
        """(in_use, shared, cache_blocks) snapshot; caller holds the lock."""
        return (
            self._allocator.used_blocks,
            self._allocator.shared_blocks,
            len(self._prefix) if self._prefix is not None else 0,
        )

    def _publish_pool_gauges(self, in_use: int, shared: int, cache_blocks: int) -> None:
        metric_defs.LLM_KV_BLOCKS_IN_USE.set(in_use, self._depth_tags)
        metric_defs.LLM_KV_BLOCKS_SHARED.set(shared, self._depth_tags)
        metric_defs.LLM_PREFIX_CACHE_BLOCKS.set(cache_blocks, self._depth_tags)

    # -- request-scope latency bookkeeping ----------------------------------
    def _note_first_token(self, req: GenRequest) -> None:
        """TTFT boundary: the first sampled token leaves the engine."""
        now = time.perf_counter()
        req.t_first = req.t_last_tok = now
        if req.t_submit:
            ttft = now - req.t_submit
            self._sketches["ttft"].observe(ttft)
            metric_defs.LLM_TTFT.observe(ttft, self._depth_tags)
        if req.trace is not None:
            req.trace.note_token(0.0)  # marks first_token on the trace

    def _note_next_token(self, req: GenRequest) -> None:
        """Inter-token gap: one decode token after the first."""
        now = time.perf_counter()
        gap = now - req.t_last_tok
        req.t_last_tok = now
        self._sketches["inter_token"].observe(gap)
        metric_defs.LLM_INTER_TOKEN.observe(gap, self._depth_tags)
        if req.trace is not None:
            req.trace.note_token(gap)

    def _note_block(self, req: GenRequest, n: int) -> None:
        """A committed block's ``n`` tokens leave the engine at one instant:
        the sketches take them with one stamp (the first carries the gap
        since the block before, or the first-token time; the rest gaps of 0)."""
        first = not req.t_first
        if req.trace is not None:
            req.trace.note_block()  # marks first_block_committed, ahead of first_token
        if first:
            self._note_first_token(req)
        now = time.perf_counter()
        gap = now - req.t_last_tok
        req.t_last_tok = now
        inter = self._sketches["inter_token"]
        for k in range(1 if first else 0, n):
            g = gap if k == 0 else 0.0
            inter.observe(g)
            metric_defs.LLM_INTER_TOKEN.observe(g, self._depth_tags)
            if req.trace is not None:
                req.trace.note_token(g)

    def _note_stall(self) -> None:
        """A prefill forward just stalled every running decode slot: count
        the stall on each stalled request's trace (the decoding requests
        experience the bubble, not the prefilling one)."""
        # rt-lint: disable=lock-discipline -- engine-thread-owned: _slots
        # mutations all run on this same engine loop thread (see _dispatch)
        for r in self._slots:
            if r is not None and r.trace is not None:
                r.trace.note_stall()

    def _record_done(self, req: GenRequest, outcome: str, detail: str = "") -> None:
        """Terminal bookkeeping shared by every exit path: feed the e2e
        sketch (engine-side view: submit -> terminal, successful finishes
        only) and push a summary onto the bounded ring the flight recorder
        snapshots. Abnormal terminals claim the trace outcome HERE so the
        proxy's generic mapping (first-wins) cannot mislabel them."""
        now = time.perf_counter()
        e2e = (now - req.t_submit) if req.t_submit else 0.0
        if outcome == "finish":
            self._sketches["e2e"].observe(e2e)
        elif req.trace is not None:
            req.trace.set_outcome(outcome, detail or f"engine:{outcome}")
        self._finished_ring.append({
            "outcome": outcome,
            "detail": detail,
            "tenant": req.tenant or "",
            "prompt_tokens": len(req.prompt),
            "generated": len(req.generated),
            "e2e_ms": round(e2e * 1000.0, 3),
            "ttft_ms": (
                round((req.t_first - req.t_submit) * 1000.0, 3)
                if req.t_first and req.t_submit else None
            ),
        })

    # -- engine loop --------------------------------------------------------
    def _pop_admissible(self, *, need_free_slot: bool = True):
        """Shared admit-loop head: pop (or resume) the next runnable request.

        Returns ``(req, free_slots)`` with shed-on-pop filtering applied, or
        ``None`` when there is nothing admissible right now. The
        head-of-line request waiting for blocks lives in ``self._held_req``
        and is resumed here (never re-pushed: re-pushing would re-bill its
        stride and let later arrivals overtake the weighted-fair order).
        """
        while True:
            with self._lock:
                free = [
                    i for i in range(self.B)
                    if not self._active[i] and not self._reserved[i]
                ]
                if need_free_slot and not free:
                    return None
                if self._held_req is not None:
                    req = self._held_req
                    self._held_req = None
                elif len(self._queue):
                    req = self._queue.pop()  # weighted fair order across tenants
                    self._queued_tokens -= len(req.prompt)
                else:
                    return None
                depth = len(self._queue)
            metric_defs.ADMISSION_QUEUE_DEPTH.set(depth, self._depth_tags)
            if req.cancelled:
                # abandoned while waiting: never prefill it
                with self._lock:  # += races the request-thread shed paths
                    self.num_shed += 1
                admission.record_shed("engine", "disconnect")
                self._record_done(
                    req, "disconnect", "stream consumer gone before admission"
                )
                if not req.future.done():
                    req.future.set_exception(
                        RuntimeError("stream consumer disconnected before admission")
                    )
                continue
            if req.deadline_ts is not None and time.time() >= req.deadline_ts:
                # expired while queued: shed instead of occupying a slot
                with self._lock:  # += races the request-thread shed paths
                    self.num_shed += 1
                admission.record_shed("engine", "deadline_expired")
                self._record_done(req, "deadline", "deadline expired while queued")
                if not req.future.done():
                    req.future.set_exception(
                        DeadlineExceededError("llm_request", "engine_queue", 0.0)
                    )
                if req.stream_queue is not None:
                    req.stream_queue.put(_STREAM_END)
                continue
            if not req.wfq_popped:
                # queue-wait ends at the FIRST pop; a held head-of-line
                # request resumed from _held_req is in kv_block_wait, not
                # queue time, and must not re-observe
                req.wfq_popped = True
                if req.t_submit:
                    self._sketches["queue_wait"].observe(
                        time.perf_counter() - req.t_submit
                    )
                if req.trace is not None:
                    req.trace.mark("wfq_pop")
            return req, free

    def _admit(self) -> None:
        """Block-aware admission: reserve the request's whole page budget up
        front (``ceil((prompt + max_tokens - 1) / block_size)`` — the last
        written position is ``prompt + max_tokens - 2``), so an admitted
        request can never hit a mid-decode pool OOM and nothing is ever
        preempted. Prefill itself runs later, chunk by chunk, from
        ``_prefill_enqueue`` so decode steps interleave with long prompts.

        With the prefix cache, the longest cached prefix of the prompt is
        ``share()``d straight into the block table (zero prefill compute for
        the hit region — chunked prefill starts at the first uncached token)
        and only the uncached suffix reserves fresh pages. A full-prompt hit
        still recomputes the LAST prompt token (its logits seed sampling),
        and that write would land in the final matched block — a shared
        page — so that block is copy-on-write: the request gets a fresh
        page populated by a device page copy instead of a share."""
        bs = self.kv_block_size
        while True:
            popped = self._pop_admissible()
            if popped is None:
                return
            req, free = popped
            tp = len(req.prompt)
            total = self._pages_needed(tp, req.max_tokens)
            with self._lock:
                pages: List[int] = []
                matched = 0
                snapshot = -1
                if self._prefix is not None and not self._hybrid:
                    pages, matched = self._prefix.match(req.prompt, req.block_keys)
                elif self._prefix is not None:
                    # the state after the matched pages has to exist too: skip
                    # as far as the deepest matched node with a snapshot, short
                    # of the last token (its logits seed sampling, and a state
                    # cannot be stepped back), and share no page beyond it
                    pages, offered, snapshot, matched = self._prefix.match_snapshot(req.prompt, tp - 1, req.block_keys)
                    self._prefix_tokens_matched += min(offered, (tp - 1) // bs * bs)
                    pages = pages[: matched // bs]
                    req.branch_at = self._branch_snapshot_at(req, offered, matched)
                cow_src = -1
                if matched == tp and self._bk == 1:
                    # full-prompt hit: the tail block must be writable (a
                    # diffusion config recomputes nothing: no token comes
                    # from its prefill, and its first block opens a page)
                    cow_src = pages.pop()
                    matched -= bs
                # pin the hit region (and the COW source) FIRST: the
                # eviction sweep below must never free a page we matched
                pins = pages + ([cow_src] if cow_src >= 0 else [])
                if pins:
                    self._allocator.share(pins)
                needed = total - len(pages)
                short = needed - self._allocator.free_blocks
                evicted_n = 0
                if short > 0 and self._prefix is not None:
                    # pool short: LRU-sweep unreferenced cached leaves
                    # before holding (and long before admission sheds)
                    evicted = self._evict_pages_locked(short)
                    if evicted:
                        self._allocator.free(evicted)
                        evicted_n = len(evicted)
                if needed > self._allocator.free_blocks:
                    # head-of-line waits for release paths to return pages;
                    # skipping it would starve big requests behind small
                    # ones. Drop the pins — it re-probes the cache on wake.
                    if pins:
                        self._allocator.free(pins)
                    self._held_req = req
                    if evicted_n:
                        metric_defs.LLM_PREFIX_EVICTIONS.inc(evicted_n)
                    return
                blocks = pages + self._allocator.alloc(needed)
                if snapshot >= 0:
                    self._prefix.restored(snapshot)
                slot = free[0]
                self._reserved[slot] = True
                self._slot_blocks[slot] = blocks
                self._block_tables[slot, :] = 0
                self._block_tables[slot, : len(blocks)] = blocks
                hit_tokens = matched + (bs if cow_src >= 0 else 0)
                if self._prefix is not None:
                    fb = (tp // bs) * bs  # the matchable (full-block) region
                    result = (
                        ("hit" if hit_tokens == fb else "partial")
                        if hit_tokens > 0
                        else "miss"
                    )
                    self._prefix_results[result] += 1
                    self._prefix_tokens_reused += (
                        tp - 1 if cow_src >= 0 else matched
                    )
                gauges = self._pool_gauges_locked()
            if evicted_n:
                metric_defs.LLM_PREFIX_EVICTIONS.inc(evicted_n)
            self._publish_pool_gauges(*gauges)
            if self._prefix is not None:
                metric_defs.LLM_PREFIX_CACHE_HITS.inc(tags=_PREFIX_RESULT_TAGS[result])
            req.slot = slot
            if req.trace is not None:
                # pages reserved: kv_block_wait (wfq_pop -> here) is over
                req.trace.mark("admitted")
            # chunked prefill resumes at the first token whose KV is not
            # already in the table (tp - 1 for a full hit: one recompute)
            req.prefill_pos = matched
            if self._hybrid and not self._place_state(req, snapshot):
                continue
            if cow_src >= 0:
                try:
                    dst = blocks[len(pages)]  # the fresh page for the tail block
                    self._cache = self._copy_page(
                        self._cache, jnp.int32(cow_src), jnp.int32(dst)
                    )
                    with self._lock:
                        self._allocator.free([cow_src])  # drop the copy pin
                        self._cow_count += 1
                except BaseException as exc:  # noqa: BLE001
                    with self._lock:
                        self._allocator.free([cow_src])
                    self._fail_admit(req, exc)
                    continue
                req.prefill_pos = tp - 1
            if req.import_arrays is not None:
                # migrated request: blocks land from the producer's staged
                # arrays (or this replica's own prefix cache) — no prefill.
                # One adoption per admission pass: landing a block set is
                # the heaviest admission step, and a migration burst
                # draining in a single pass would stall the decode cadence
                # for every running stream (the loop re-admits next tick)
                self._adopt_admitted(req, had_cow=cow_src >= 0)
                return
            if req.prefill_pos >= self._fill_len(req):
                # a diffusion config's prompt of whole cached pages, or one
                # shorter than a block: nothing to prefill
                with self._lock:
                    self._prefill_count += 1
                self._finish_prefill(req, None)
                continue
            with self._lock:
                self._prefilling.append(req)

    def _place_state(self, req: GenRequest, snapshot: int) -> bool:
        """The admitted request's slot starts from the state its prefill
        resumes from: the snapshot's copy (the entry may go to another owner
        right after: the device runs the copy first) or zero. Enqueued behind
        whatever the slot's last occupant still had in flight, so nothing of
        it survives. False: the copy failed and the request with it."""
        try:
            with jax.profiler.TraceAnnotation("llm::state_restore"):
                if snapshot >= 0:
                    self._cache = self._restore_state(self._cache, self._snaps, jnp.int32(req.slot), jnp.int32(snapshot))
                else:
                    self._cache = self._zero_state(self._cache, jnp.int32(req.slot))
        except BaseException as exc:  # noqa: BLE001
            self._fail_admit(req, exc)
            return False
        with self._lock:
            if snapshot >= 0:
                self._state_restores += 1
            else:
                self._state_zeroed += 1
        (metric_defs.LLM_STATE_RESTORES if snapshot >= 0 else metric_defs.LLM_STATE_ZEROED).inc()
        return True

    def _snapshot_after_prompt(self, req: GenRequest) -> int:
        """Tokens of ``req``'s prompt a snapshot is to be taken after during
        prefill: its whole pages, or 0 for none: no snapshot pool or prefix
        cache, a prompt shorter than a page, or a reply that is certain (no
        EOS to end it early) to reach a later page boundary while decoding,
        whose snapshot would replace this one."""
        bs = self.kv_block_size
        tp = len(req.prompt)
        whole = tp // bs * bs
        if not self._n_snapshots or self._prefix is None or not whole:
            return 0
        later = req.eos_id is None and tp + req.max_tokens - 2 >= whole + bs - 1
        return 0 if later else whole

    def _branch_snapshot_at(self, req: GenRequest, offered: int, matched: int) -> int:
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
        gap = 2 * (self.prefill_chunk_tokens or self.S)
        if not self._n_snapshots or offered - matched < gap:
            return 0
        at = self._prefix.branch_point(req.prompt, len(req.prompt) - 1, req.block_keys)
        return at if at - matched >= gap else 0

    def _snapshot_branch(self, req: GenRequest) -> None:
        """The chunk just enqueued ends at ``req.branch_at``: the state behind
        it goes to the cached node there, unless another request got there
        first (the entry is free again)."""
        for _, entry, tokens in self._take_snapshots([(req, req.branch_at)]):
            with self._lock:
                if not self._prefix.attach_snapshot(req.prompt, tokens, entry, req.block_keys):
                    self._snap_pool.free(entry)

    def _take_snapshots(self, rows: List[Tuple[GenRequest, int]]) -> List[Tuple[GenRequest, int, int]]:
        """Enqueue, behind the program that produced them, the copies of the
        states of ``rows`` ((request, tokens its state then covers)) into
        entries of the snapshot pool: one program for all of them. Returns
        (request, entry, tokens) of those that got an entry."""
        taken: List[Tuple[GenRequest, int, int]] = []
        if not rows:  # (always, without a prefix cache to publish them to)
            return taken
        with self._lock:
            detached = self._prefix.snapshot_evictions
            for req, tokens in rows:
                entry = self._alloc_snapshot_locked()
                if entry >= 0:
                    taken.append((req, entry, tokens))
            in_use = self._snap_pool.in_use
            self._state_snapshots_taken += len(taken)
            detached = self._prefix.snapshot_evictions - detached
        if detached:
            metric_defs.LLM_STATE_SNAPSHOTS_EVICTED.inc(detached)
        if not taken:
            return taken
        slots, entries = np.zeros(self.B, np.int32), np.zeros(self.B, np.int32)
        for j, (req, entry, _) in enumerate(taken):
            slots[j], entries[j] = req.slot, entry
        with jax.profiler.TraceAnnotation("llm::state_snapshot"):
            self._snaps = self._snapshot_state(self._snaps, self._cache, jnp.asarray(slots), jnp.asarray(entries),
                                               jnp.int32(len(taken)))
        metric_defs.LLM_STATE_SNAPSHOTS_TAKEN.inc(len(taken))
        metric_defs.LLM_STATE_SNAPSHOTS_IN_USE.set(in_use, self._depth_tags)
        return taken

    def _keep_snapshot(self, req: GenRequest, entry: int, tokens: int) -> None:
        """``req``'s newest snapshot replaces the one it held."""
        with self._lock:
            self._drop_snapshot_locked(req)
            req.snap = (entry, tokens)

    def _finish_prefill(self, req: GenRequest, logits) -> None:
        """Prompt is fully in the paged cache: sample the first token and
        hand the slot to the decode batch (or, for an export request,
        stage the block set for migration instead)."""
        if self._bk > 1:
            self._open_first_block(req)
            return
        tp = len(req.prompt)
        self._key, sub = jax.random.split(self._key)
        tok0 = int(
            self._sample(
                sub, logits[None, :], jnp.asarray([req.temperature], jnp.float32)
            )[0]
        )
        if req.export_mig_id is not None:
            self._export_staged(req, tok0)
            return
        req.generated = [tok0]
        self._note_first_token(req)
        req.emit(tok0)
        with self._lock:
            slot = req.slot
            self._slots[slot] = req
            self._active[slot] = True
            self._reserved[slot] = False
            self._join_tok[slot] = tok0
            self._pos[slot] = tp
            self._temps[slot] = req.temperature
        self._maybe_finish(req, tok0)

    def _open_first_block(self, req: GenRequest) -> None:
        """A diffusion config's prompt is in the paged cache as far as its
        whole blocks go: hand the slot to the decode batch with the first
        block opened, the prompt's tail as its known positions. No token
        comes from prefill; the first ones come with the block's commit."""
        fill = self._fill_len(req)
        known = len(req.prompt) - fill
        req.block_known = known
        req.forwards_left = min(self._bk - known, req.denoising_steps) + 1
        with self._lock:
            slot = req.slot
            self._slots[slot] = req
            self._active[slot] = True
            self._reserved[slot] = False
            self._join["row"][slot] = True
            self._join["known"][slot] = known
            self._join["toks"][slot, :known] = req.prompt[fill:]
            self._join["steps"][slot] = req.denoising_steps
            self._pos[slot] = fill
            self._temps[slot] = req.temperature

    def _join_arrays(self):
        """Copies of the join mirrors for the device (a transfer may read its
        host buffer after the call returns, and the mirrors change at once)."""
        return {k: jnp.asarray(v.copy()) for k, v in self._join.items()}

    def _adopt_admitted(self, req: GenRequest, *, had_cow: bool) -> None:
        """Activate an admitted IMPORT request: write the pulled block
        arrays into its freshly allocated pages (runs on the engine loop —
        the only thread allowed to touch the donated cache), then join the
        decode batch at position ``len(prompt)`` with the producer's first
        token.  A warm local prefix covers its blocks without any write
        (the re-migration short-circuit); a block neither cached nor
        pulled — the prefix shrank between the caller's probe and now —
        is the typed migration error, and the ladder re-prefills."""
        from ray_tpu.serve.disagg import KVMigrationError

        ticket = req.import_ticket or {}
        mig_id = ticket.get("mig_id", "?")
        tp = len(req.prompt)
        bs = self.kv_block_size
        n_blocks = -(-tp // bs)
        if not had_cow:
            # prefill_pos = matched tokens (a multiple of block_size);
            # with a full-hit COW every prompt position is already paged
            # in, so there is nothing to write at all
            writes = []
            for bidx in range(req.prefill_pos // bs, n_blocks):
                arr = (req.import_arrays or {}).get(bidx)
                if arr is None:
                    self._fail_admit(req, KVMigrationError(
                        mig_id, "pulled",
                        f"block {bidx} neither locally cached nor pulled "
                        f"(local prefix match shrank to {req.prefill_pos} "
                        "tokens after the probe)",
                    ))
                    return
                writes.append(
                    (arr, int(self._block_tables[req.slot, bidx]))
                )
            if writes:
                bucket = 1
                while bucket < len(writes):
                    bucket *= 2
                while len(writes) < bucket:  # idempotent scatter pad
                    writes.append(writes[-1])
                try:
                    # host-side stack: jnp.stack dispatches an expand_dims
                    # per block (~1.5ms for a long prompt's 32); np views
                    # of CPU-backend arrays memcpy in ~80µs, and the jit
                    # boundary ships one contiguous buffer
                    self._cache = self._write_blocks(
                        self._cache,
                        np.stack([np.asarray(a) for a, _ in writes]),
                        np.asarray([p for _, p in writes], np.int32),
                    )
                except BaseException as exc:  # noqa: BLE001
                    self._fail_admit(req, exc)
                    return
        tok0 = int(ticket.get("tok0", 0))
        req.generated = [tok0]
        now = time.perf_counter()
        req.t_first = req.t_last_tok = now
        if req.trace is not None:
            # the migration phase ends here: first_token was marked on the
            # prefill replica, decode gaps accrue on THIS one
            req.trace.mark("kv_migrate")
        req.emit(tok0)
        with self._lock:
            slot = req.slot
            self._slots[slot] = req
            self._active[slot] = True
            self._reserved[slot] = False
            self._join_tok[slot] = tok0
            self._pos[slot] = tp
            self._temps[slot] = req.temperature
            self.num_migrations_in += 1
        self._maybe_finish(req, tok0)

    def _export_staged(self, req: GenRequest, tok0: int) -> None:
        """Export terminal of a prefill-pool request: extract the prompt
        blocks as device-array copies, stage them for device-to-device
        pull under deterministic ``(request, block)`` uuids, register the
        host fallback source, retire the POOL pages into this replica's
        prefix cache (exactly-once: the staged copies, not the pages,
        migrate), and resolve the future with the header-only ticket."""
        from ray_tpu.runtime import data_plane, device_plane
        from ray_tpu.serve import disagg

        mig_id = req.export_mig_id
        tp = len(req.prompt)
        bs = self.kv_block_size
        n_blocks = -(-tp // bs)
        req.generated = [tok0]
        self._note_first_token(req)
        # engine-thread-only cache reads: the exported blocks are NEW
        # buffers, so the copies survive later donated steps
        arrays = []
        for bidx in range(n_blocks):
            page = int(self._block_tables[req.slot, bidx])
            arrays.append(self._export_page(self._cache, page))
        if arrays:
            jax.block_until_ready(arrays[-1])
        transfer_addr = device_plane.transfer_address()
        if transfer_addr is not None:
            for bidx, arr in enumerate(arrays):
                if not device_plane.offer_device_pull(
                    disagg.migration_uuid(mig_id, bidx), arr
                ):
                    # staging cap hit: advertise no device rung — offers
                    # already made are consumed or TTL-reaped
                    transfer_addr = None
                    break

        def _fetch(idx: int, _arrays=arrays):
            # device array as-is: the in-process rung adopts it zero-copy;
            # the data-plane kv_pull op host-converts only for remote pulls
            return _arrays[idx]

        data_plane.register_kv_block_source(mig_id, _fetch)
        evicted_n = 0
        with self._lock:
            # pool pages retire into the prefix cache NOW (cached tokens =
            # the prompt: tok0 was sampled, never written back) — the one
            # free of the migrated block set on this replica
            evicted_n = self._retire_blocks_locked(req)
            self._staged[mig_id] = {
                "arrays": arrays,
                "prompt": list(req.prompt),
                "n_blocks": n_blocks,
            }
            self.num_migrations_out += 1
            gauges = self._pool_gauges_locked()
        if evicted_n:
            metric_defs.LLM_PREFIX_EVICTIONS.inc(evicted_n)
        self._publish_pool_gauges(*gauges)
        ticket = disagg.make_ticket(
            mig_id,
            prompt=req.prompt,
            tok0=tok0,
            n_blocks=n_blocks,
            block_size=bs,
            block_shape=tuple(arrays[0].shape) if arrays else (0,),
            block_dtype=str(arrays[0].dtype) if arrays else "float32",
            transfer_addr=transfer_addr,
            data_addr=disagg.local_data_addr(),
            source=str(self._admission_token),
        )
        self._record_done(req, "finish", f"export {mig_id}")
        req.future.set_result(ticket)

    def _fail_admit(self, req: GenRequest, exc: BaseException) -> None:
        """A popped request is in neither queue nor slots — fail it HERE or
        its caller hangs forever; return any reserved pages to the pool."""
        self._record_done(req, "crash", f"prefill failed: {exc!r}")
        if not req.future.done():
            req.future.set_exception(RuntimeError(f"prefill failed: {exc!r}"))
        if req.stream_queue is not None:
            req.stream_queue.put(_STREAM_END)
        if req.slot >= 0:
            with self._lock:
                self._release_blocks_locked(req.slot)
                self._drop_snapshot_locked(req)
                gauges = self._pool_gauges_locked()
            self._publish_pool_gauges(*gauges)
        if next(iter(self._cache.values())).is_deleted():
            # a donated chunk or page write consumed the cache then failed: the
            # shared cache is gone, taking every in-flight slot with it
            self._fail_inflight(RuntimeError(f"cache lost in failed prefill: {exc!r}"))
            self._reset_cache()

    def _release_blocks_locked(self, slot: int) -> None:
        """Drop a slot's page references (a request holds exactly ONE per
        block-table entry, shared or not, so every release path — finish,
        shed, evict, crash — is this same free). Caller holds ``self._lock``.

        A decode step may still be in flight for this slot (an EOS is read
        one step late, a cancel whenever it comes). Freeing under it is
        sound: that step writes the row's K/V at positions >= ``pos``, in
        pages only this request could write (``_cow_shared_writes``) and
        that ``_retire_blocks_locked`` never publishes; whoever is given the
        pages next enqueues its writes later, the device runs programs in
        the order they were enqueued, and no one reads a position of its
        page before writing it. The row's tokens are dropped at
        ``_collect`` by the request's identity, never through the slot."""
        blocks = self._slot_blocks[slot]
        self._slot_blocks[slot] = []
        self._block_tables[slot, :] = 0
        self._reserved[slot] = False
        if blocks:
            self._allocator.free(blocks)

    def _retire_blocks_locked(self, req: GenRequest) -> int:
        """Finish path: publish the request's full KV blocks into the prefix
        cache (the request's reference TRANSFERS to the cache for newly
        adopted nodes) and free everything else. Returns the number of
        pages LRU-evicted to respect ``prefix_cache_max_blocks``. Caller
        holds ``self._lock``."""
        slot = req.slot
        blocks = self._slot_blocks[slot]
        self._slot_blocks[slot] = []
        self._block_tables[slot, :] = 0
        self._reserved[slot] = False
        if not blocks or self._prefix is None:
            self._drop_snapshot_locked(req)
            if blocks:
                self._allocator.free(blocks)
            return 0
        # the last sampled token was never written back to the KV cache;
        # every token before it was — cache exactly those full blocks. (A
        # step in flight past an EOS writes position len(cached) and up:
        # in no full block of ``cached``, so never in a published page)
        # A diffusion config committed every token it emitted; what its last
        # block holds past them was dropped, so that block's page is not full
        cached = req.prompt + (req.generated if self._bk > 1 else req.generated[:-1])
        bs = self.kv_block_size
        keys = tuple(chain_keys(cached, len(cached) // bs, bs, req.block_keys))  # the reply's blocks behind the prompt's
        adopted, evicted = self._prefix.insert(cached, blocks, self._evictable, keys)
        if req.snap is not None:
            # the state after exactly ``tokens`` of ``cached`` goes to the node that ends them;
            # where that node is not cached, or has one already, the entry is free again
            entry, tokens = req.snap
            if tokens <= len(cached) and self._prefix.attach_snapshot(cached, tokens, entry, keys):
                req.snap = None
            self._drop_snapshot_locked(req)
        self._reclaim_snapshots_locked()
        if evicted:
            self._allocator.free(evicted)
        rest = [b for b in blocks if b not in adopted]
        if rest:
            self._allocator.free(rest)
        return len(evicted)

    def _cow_shared_writes(self, slot: int, start: int, n: int) -> None:
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
                old = int(self._block_tables[slot, bidx])
                if old == 0 or self._allocator.refcount(old) <= 1:
                    continue
                if self._allocator.free_blocks < 1 and self._prefix is not None:
                    evicted = self._evict_pages_locked(1)
                    if evicted:
                        self._allocator.free(evicted)
                new = self._allocator.alloc(1)[0]  # typed shed if truly none
            # the old page holds >= 2 refs (ours included) so it cannot be
            # reallocated while the device copy reads it
            self._cache = self._copy_page(self._cache, jnp.int32(old), jnp.int32(new))
            with self._lock:
                bl = self._slot_blocks[slot]
                bl[bl.index(old)] = new
                self._block_tables[slot, bidx] = new
                self._allocator.free([old])
                self._cow_count += 1

    def _prefill_enqueue(self):
        """Enqueue one chunk of the head prefilling request and return what
        ``_prefill_finish`` needs, without waiting for it; None if nothing
        is prefilling (or the chunk failed: the request is failed here).

        With ``prefill_chunk_tokens > 0`` every chunk is the same fixed
        width, so a single compiled program serves all prompts and a decode
        step runs between chunks (Sarathi-style stall bounding). With 0 the
        whole prompt goes in one bucketed call."""
        with self._lock:
            while self._prefilling and self._prefilling[0].cancelled:
                req = self._prefilling.pop(0)
                self._release_blocks_locked(req.slot)
                self._drop_snapshot_locked(req)
                self.num_shed += 1
                admission.record_shed("engine", "disconnect")
                self._record_done(
                    req, "disconnect", "stream consumer gone during prefill"
                )
                if not req.future.done():
                    req.future.set_exception(
                        RuntimeError("stream consumer disconnected during prefill")
                    )
                if req.stream_queue is not None:
                    req.stream_queue.put(_STREAM_END)
            if not self._prefilling:
                return None
            req = self._prefilling[0]
            gauges = self._pool_gauges_locked()
        self._publish_pool_gauges(*gauges)
        tp = self._fill_len(req)
        start = req.prefill_pos
        chunk = self.prefill_chunk_tokens
        # one-shot width buckets the UNCACHED suffix, not the whole prompt:
        # a warm request's TTFT is proportional to what it actually computes
        width = min(chunk, self.S) if chunk > 0 else _bucket(tp - start, cap=self.S)
        n = min(width, tp - start)
        # a config with linear layers: a snapshot after the prompt's whole pages
        # needs a chunk that ends there (what is left of the prompt, under a
        # page, is then a chunk of its own)
        snap_at = self._snapshot_after_prompt(req) if self._hybrid else 0
        if start < snap_at < start + n:
            n = snap_at - start
        if start < req.branch_at < start + n:
            n = req.branch_at - start
        toks = np.zeros((1, width), np.int32)
        toks[0, :n] = req.prompt[start : start + n]
        stalled = bool(self._active.any())
        try:
            # invariant net: admission never maps a to-be-written block to a
            # shared page (the full-hit tail is COW'd eagerly), but writes
            # must still never land on refcount > 1 pages
            self._cow_shared_writes(req.slot, start, n)
            # a copy of the row: the transfer may read (or alias) the host
            # buffer after the call returns, and the mirror is rewritten at
            # will before the chunk has been waited for
            bt = jnp.asarray(self._block_tables[req.slot : req.slot + 1].copy())
            slot = (jnp.asarray([req.slot], jnp.int32),) if self._hybrid else ()
            logits, self._cache, *moe = self._prefill_chunk(
                self.params, self._cache, jnp.asarray(toks), bt,
                jnp.int32(start), jnp.int32(n), *slot,
            )
            if snap_at and start + n == snap_at:
                for taken in self._take_snapshots([(req, snap_at)]):
                    self._keep_snapshot(*taken)
            elif req.branch_at and start + n == req.branch_at:
                self._snapshot_branch(req)
        except BaseException as exc:  # noqa: BLE001
            with self._lock:
                self._prefilling.pop(0)
            self._fail_admit(req, exc)
            return None
        return req, n, logits, moe, stalled

    def _prefill_finish(self, req: GenRequest, n: int, logits, moe, stalled: bool) -> None:
        """Wait for the chunk ``_prefill_enqueue`` started and, if it was the
        prompt's last, sample the first token and join the decode batch."""
        lap = self._clock.lap
        t_wait = lap("prefill_wait")
        try:
            jax.block_until_ready(logits)
            t_done = lap("prefill_counts")
            self._note_moe(moe, decode=False)
        except BaseException as exc:  # noqa: BLE001
            with self._lock:
                self._prefilling.pop(0)
            self._fail_admit(req, exc)
            return
        if stalled:
            # rows were live while this chunk ran: the loop has read their
            # step already, so what it waited here is what the chunk added
            # to their next token; chunking bounds it
            metric_defs.LLM_DECODE_STALL.observe(t_done - t_wait)
            self._note_stall()
        metric_defs.LLM_PREFILL_CHUNKS.inc()
        if req.trace is not None:
            req.trace.note_prefill_chunk()
        visited = self._chunk_kv_visited(req.prefill_pos, n)
        capacity = self.max_blocks_per_slot * self.kv_block_size
        metric_defs.LLM_PREFILL_KV_VISITED.inc(visited)
        metric_defs.LLM_PREFILL_KV_CAPACITY.inc(capacity)
        with self._lock:
            self._prefill_chunk_count += 1
            self._prefill_kv_visited += visited
            self._prefill_kv_capacity += capacity
        req.prefill_pos += n
        if req.prefill_pos < self._fill_len(req):
            return
        with self._lock:
            self._prefilling.pop(0)
            self._prefill_count += 1
        lap("first_token")
        try:
            self._finish_prefill(req, logits)
        except BaseException as exc:  # noqa: BLE001
            self._fail_admit(req, exc)

    def _note_moe(self, moe, *, decode: bool) -> None:
        """Add one program run's expert-layer counts (``moe``: empty unless
        the config has dropless expert layers) to the running totals. Called
        right after the run's tokens or logits were read, so the small
        arrays are on the host already: no further sync."""
        if not moe:
            return
        counts = np.asarray(moe[0]["assignments"])
        hit = int(moe[0]["pairs_hit"])
        self._moe_expert_assignments += counts
        self._moe_experts_hit += hit
        if "routed" in moe[0]:
            routed = int(moe[0]["routed"])
            self._moe_routed += routed
            metric_defs.LLM_MOE_ASSIGNMENTS_ROUTED.inc(routed)
            metric_defs.LLM_MOE_ASSIGNMENTS_LOCAL.inc(int(counts.sum()))
        if decode:
            self._moe_experts_hit_decode += hit

    def _chunk_kv_visited(self, start: int, n: int) -> float:
        """Cached tokens the attention of a chunk of ``n`` tokens at
        ``start`` has to visit in a layer, averaged over the layers: all
        ``start + n`` in a full layer, less those below its first query's
        window in a sliding one."""
        seen = sum(count * (start + n - (max(0, start - w + 1) if w else 0)) for w, count in self._layers_by_window)
        return seen / self.cfg.n_layers

    def kv_read_share(self) -> float:
        """Of the cached tokens of the live sequences, the share a decode
        step must read: a sliding layer sees the last ``min(len, window)`` of
        a sequence, a full layer all of it; summed over layers and live
        sequences over layers x the sum of lengths. 1.0 without windows (and
        with nothing live). The pool still holds every page: pages behind a
        window are not freed (one block table serves all layers)."""
        windows = self.cfg.layer_windows
        lens = self._pos[self._active].astype(np.int64)
        if windows is None or not lens.sum():
            return 1.0
        must = sum(int(np.minimum(lens, w).sum()) if w else int(lens.sum()) for w in windows)
        return must / (len(windows) * int(lens.sum()))

    def kv_live_pages(self) -> float:
        """Summed over the live sequences, the pages the next decode step's
        attention visits in a layer, averaged over the layers: a full layer
        walks all ``cdiv(len, block)`` pages of a sequence (``len``: its
        cached tokens and the one the step writes), a sliding layer those
        from the page of its window's first position on. What the paged
        decode kernel's work follows."""
        bs = self.kv_block_size
        lens = self._pos[self._active].astype(np.int64) + self._bk  # to the end of the step's writes
        last = -(-lens // bs)
        if self._hybrid:  # the full (or latent) layers walk every page, the linear layers none
            return self._attn_layers * int(last.sum()) / self.cfg.n_layers
        windows = self.cfg.layer_windows or (0,)
        visited = sum(int((last - np.maximum(lens - w, 0) // bs).sum()) if w else int(last.sum()) for w in windows)
        return visited / len(windows)

    def _maybe_finish(self, req: GenRequest, tok: int) -> bool:
        done = len(req.generated) >= req.max_tokens or (
            req.eos_id is not None and tok == req.eos_id
        )
        if done:
            with self._lock:
                self._active[req.slot] = False
                self._slots[req.slot] = None
                evicted_n = self._retire_blocks_locked(req)
                gauges = self._pool_gauges_locked()
            if evicted_n:
                metric_defs.LLM_PREFIX_EVICTIONS.inc(evicted_n)
            self._publish_pool_gauges(*gauges)
            self._record_done(req, "finish")
            req.future.set_result(req.generated)
            if req.stream_queue is not None:
                req.stream_queue.put(_STREAM_END)
        return done

    def _note_dispatch(self, behind_chunk: bool) -> None:
        """Count what the step about to be enqueued finds on the device
        (``_DISPATCH_KINDS``), its uploads made and the jit call next: one
        ``is_ready()``, no wait."""
        prev = self._flight
        if prev is None:
            kind = "cold"
        elif not behind_chunk and jax.tree.leaves(prev.out)[0].is_ready():
            kind = "dry"
        else:
            kind = "queued"
        self._dispatches[kind] += 1

    def _dispatch(self, behind_chunk: bool) -> Optional[_Flight]:
        """Enqueue one decode step (``decode_chunk`` tokens a row) for every
        row still owed a token and return its handle unread; None if there is
        no such row. The host knows everything the step needs ahead of the
        device but the rows' last tokens, and those the program takes from
        its own previous run (``_dev_toks``) or, for a row that joined since,
        from ``_join_tok``. Positions and the ``max_tokens`` count advance
        here, so the next step can be dispatched before this one is read.
        ``behind_chunk``: this iteration enqueued a prefill chunk ahead."""
        self._clock.lap("dispatch_rows")
        if self._bk > 1:
            return self._dispatch_blocks(behind_chunk)
        K = self.decode_chunk
        rows: List[Tuple[int, GenRequest]] = []
        live = np.zeros(self.B, bool)
        # rt-lint: disable=lock-discipline -- engine-thread-owned: every
        # _slots mutation (admit/finish/evict/fail_inflight) runs on this
        # same engine loop thread; _lock exists for cross-thread READERS
        # (stats, abandon flags), not for us
        for i, req in enumerate(self._slots):
            if req is None or req.dispatched >= req.max_tokens - 1:
                continue  # free, or its last tokens are in flight: known by count
            # copy-on-write net: the step writes positions [pos, pos + K) —
            # if any of those blocks still maps to a shared page, give the
            # slot its own copy before stepping
            self._cow_shared_writes(i, int(self._pos[i]), K)
            rows.append((i, req))
            live[i] = True
        if not rows:
            return None
        self._clock.lap("dispatch_enqueue")
        # rows not in this step decode through all-zero tables -> garbage
        # page 0, so freed pages are never written after release. The device
        # gets copies of the mirrors: a transfer may read (or alias) its host
        # buffer after the call returns, and the mirrors change right below
        bt = jnp.asarray(self._block_tables * live[:, None].astype(np.int32))
        join, pos, temps = jnp.asarray(self._join_tok.copy()), jnp.asarray(self._pos.copy()), jnp.asarray(self._temps.copy())
        self._note_dispatch(behind_chunk)
        out, self._cache, self._key, self._dev_toks, *moe = self._decode_k_paged(
            self.params, self._cache, self._dev_toks, join, pos, temps, self._key, bt,
        )
        self._join_tok[:] = -1
        snaps = self._take_snapshots(self._rows_ending_a_page(rows)) if self._hybrid else []
        for i, req in rows:
            req.dispatched += K
            self._pos[i] += K
        return _Flight(out, moe, rows, snaps=snaps)

    def _rows_ending_a_page(self, rows: List[Tuple[int, GenRequest]]) -> List[Tuple[GenRequest, int]]:
        """Of the rows of the decode step just enqueued (``_pos`` not yet
        advanced), those whose state after it is to be snapshotted, each
        with the tokens that state covers: the step writes position ``pos``
        and that ends a page; a row with an EOS to wait for at every such
        step, any other at the last one of its reply (the last position it
        writes is known by count)."""
        bs = self.kv_block_size
        if not self._n_snapshots or self._prefix is None:
            return []
        out = []
        for i, req in rows:
            covered = int(self._pos[i]) + 1
            if covered % bs:
                continue
            last_written = len(req.prompt) + req.max_tokens - 2
            if req.eos_id is not None or covered + bs > last_written + 1:
                out.append((req, covered))
        return out

    def _dispatch_blocks(self, behind_chunk: bool) -> Optional[_Flight]:
        """:meth:`_dispatch` for a diffusion config: enqueue one block step
        for every row that still owes a token. A row's schedule is known by
        count (a block of ``m`` masked positions takes ``min(m, steps)``
        denoise forwards, then the commit), so positions and the
        ``max_tokens`` count advance here, at the commit's dispatch, ahead of
        the device; which rows committed is read back with the tokens."""
        Bk = self._bk
        rows: List[Tuple[int, GenRequest]] = []
        live = np.zeros(self.B, bool)
        # rt-lint: disable=lock-discipline -- engine-thread-owned (see _dispatch)
        for i, req in enumerate(self._slots):
            if req is None or req.dispatched >= req.max_tokens:
                continue  # free, or its last commit is in flight: known by count
            # the step writes the block's positions, tentative or final, into
            # pages only this row holds: copy-on-write before the first write
            self._cow_shared_writes(i, int(self._pos[i]), Bk)
            rows.append((i, req))
            live[i] = True
        if not rows:
            return None
        self._clock.lap("dispatch_enqueue")
        bt = jnp.asarray(self._block_tables * live[:, None].astype(np.int32))
        join, pos, temps = self._join_arrays(), jnp.asarray(self._pos.copy()), jnp.asarray(self._temps.copy())
        self._note_dispatch(behind_chunk)
        done, self._cache, self._key, self._dev_toks, *moe = self._decode_k_paged(
            self.params, self._cache, self._dev_toks, join, pos, temps, self._key, bt,
        )
        self._join["row"][:] = False
        commits: Dict[int, int] = {}
        for i, req in rows:
            req.forwards_left -= 1
            if req.forwards_left == 0:  # this step commits the row's block; the next opens all masked
                commits[i] = req.block_known
                req.dispatched += Bk - req.block_known
                self._pos[i] += Bk
                req.block_known = 0
                req.forwards_left = min(Bk, req.denoising_steps) + 1
        return _Flight(done, moe, rows, commits=commits)

    def _collect_blocks(self, flight: _Flight) -> None:
        """:meth:`_collect` for a diffusion config: a row yields nothing on a
        denoise step and its block's new tokens, as one stream event, on a
        commit (cut at ``max_tokens`` or after an EOS: what the block holds
        beyond is dropped)."""
        done = jax.device_get(flight.out)
        self._clock.lap("collect_counts")
        self._decode_step_count += 1
        self._note_moe(flight.moe, decode=True)
        # rt-lint: disable=lock-discipline -- engine-thread-owned (see _dispatch)
        rows = [(i, req) for i, req in flight.rows if self._slots[i] is req and not req.cancelled]
        self._decode_row_steps_discarded += len(flight.rows) - len(rows)
        self._block_row_forwards += len(rows)
        self._clock.lap("emit")
        for i, req in rows:
            known = flight.commits.get(i)
            if bool(done["committed"][i]) != (known is not None):
                raise RuntimeError(f"block step out of step with its schedule in slot {i}: the device "
                                   f"{'committed' if known is None else 'did not commit'} a block")
            if known is None:
                continue
            toks = done["toks"][i, known:].tolist()[: req.max_tokens - len(req.generated)]
            if req.eos_id is not None and req.eos_id in toks:
                toks = toks[: toks.index(req.eos_id) + 1]
            req.generated.extend(toks)
            self._block_commits += 1
            self._tokens_emitted += len(toks)
            self._tokens_unmasked += self._bk - known
            self._note_block(req, len(toks))
            req.emit_block(toks, done["unmasked_at"][i, known : known + len(toks)].tolist())
            self._maybe_finish(req, toks[-1])

    def _collect(self, flight: _Flight) -> None:
        """Read a dispatched step's tokens (this waits for that step only,
        not for one dispatched after it) and emit them. A row whose request
        left its slot since the dispatch (an EOS read one step late, a
        cancelled stream evicted) is dropped whole, by identity: the slot
        may be another request's by now."""
        self._clock.lap("collect_wait")
        if self._bk > 1:
            return self._collect_blocks(flight)
        sampled = np.asarray(flight.out)  # [B, K]
        self._clock.lap("collect_counts")
        K = sampled.shape[1]
        self._decode_step_count += K
        self._note_moe(flight.moe, decode=True)
        # rt-lint: disable=lock-discipline -- engine-thread-owned (see _dispatch)
        rows = [(i, req) for i, req in flight.rows if self._slots[i] is req and not req.cancelled]
        self._decode_row_steps_discarded += (len(flight.rows) - len(rows)) * K
        for req, entry, tokens in flight.snaps:
            # taken behind this step: kept if the row's step is (before its request may finish
            # below and publish it); a discarded row-step's token is in its state, so it goes
            # rt-lint: disable=lock-discipline -- engine-thread-owned (see _dispatch)
            if self._slots[req.slot] is req and not req.cancelled:
                self._keep_snapshot(req, entry, tokens)
            else:
                with self._lock:
                    self._snap_pool.free(entry)
        self._clock.lap("emit")
        for k in range(K):
            for i, req in rows:
                # rt-lint: disable=lock-discipline -- engine-thread-owned (see _dispatch)
                if self._slots[i] is not req:
                    continue  # finished earlier in this chunk
                tok = int(sampled[i, k])
                req.generated.append(tok)
                self._note_next_token(req)
                req.emit(tok)
                self._maybe_finish(req, tok)

    def _reset_cache(self) -> None:
        """(Re)allocate the decode cache — also the recovery path after a
        failed donated step leaves the old buffers deleted."""
        init = functools.partial(init_paged_cache, self.cfg, self.kv_num_blocks, self.kv_block_size,
                                 **({"slots": self.B} if self._hybrid else {}))
        if self._hybrid:
            # the slots' states went with the cache: so do the snapshots of them
            size = self._n_snapshots
            with self._lock:
                self._snap_pool = SnapshotPool(size)
            self._snaps = init_sequence_state(self.cfg, size) if size else None
            one = init_sequence_state(self.cfg, 1)
            self._state_bytes_per_slot = int(sum(a.size * a.dtype.itemsize for a in one.values()))
        if self._kv_sharding is not None:
            # each device zeroes its own shard: the whole pool never lies on one
            init = jax.jit(init, out_shardings=self._kv_sharding)
        self._cache = init()
        # bytes a cached token takes in the pools as built, all attention layers
        pools = [self._cache[name] for name in page_pools(self._cache)]
        self._kv_bytes_per_token = int(sum(a.shape[0] * a.shape[-1] * a.dtype.itemsize for a in pools))
        # the rows' last tokens as the decode program last returned them:
        # part of the same device state (a step that failed in flight leaves
        # its outputs poisoned). No row reads it before joining from the host
        self._dev_toks = jnp.zeros(self.B, jnp.int32)
        if self._bk > 1:  # a diffusion config: the rows' blocks (models/generation.open_blocks)
            self._dev_toks = open_blocks(self.cfg, jnp.ones(self.B, jnp.int32))

    def _fail_inflight(self, error: BaseException) -> None:
        """Fail every queued, prefilling, and in-slot request (loop-crash
        recovery): futures resolve with the error, stream iterators
        terminate, and every reserved KV page returns to the pool."""
        with self._lock:
            victims = self._queue.drain() + [r for r in self._slots if r is not None]
            victims += self._prefilling
            self._prefilling.clear()
            if self._held_req is not None:
                victims.append(self._held_req)
                self._held_req = None
            self._queued_tokens = 0
            self._slots = [None] * self.B
            self._active[:] = False
            for i in range(self.B):
                self._release_blocks_locked(i)
            if self._prefix is not None:
                # the device pool is about to be re-initialized; cached
                # page CONTENTS die with it, so the index must too —
                # drop every node and its reference unconditionally
                stale = self._prefix.drain()
                if stale:
                    self._allocator.free(stale)
                self._prefix.take_freed_snapshots()  # ``_reset_cache`` makes the snapshot pool anew
            for r in victims:
                r.snap = None
        # the step in flight goes with them: its rows' requests are victims
        # (engine-thread state, like the loop that dispatched it)
        self._flight = None
        self._join_tok[:] = -1
        self._join["row"][:] = False
        metric_defs.ADMISSION_QUEUE_DEPTH.set(0, self._depth_tags)
        self._publish_pool_gauges(0, 0, 0)
        for r in victims:
            self._record_done(r, "crash", str(error))
            if not r.future.done():
                r.future.set_exception(error)
            if r.stream_queue is not None:
                r.stream_queue.put(_STREAM_END)

    def _evict_cancelled(self) -> None:
        """Free decode slots whose streaming consumer went away: the slot
        (and its KV pages) returns to the batch NOW instead of decoding to an
        abandoned queue until stop/length
        (llm_slots_evicted_total{reason=disconnect})."""
        with self._lock:
            victims = [
                (i, r) for i, r in enumerate(self._slots)
                if r is not None and r.cancelled
            ]
            for i, r in victims:
                self._slots[i] = None
                self._active[i] = False
                self._release_blocks_locked(i)
                self._drop_snapshot_locked(r)
            if self._bk > 1:
                # a row of a diffusion config always has a block under way:
                # its tentative K/V go with its pages, which nothing shared
                self._blocks_dropped += len(victims)
            gauges = self._pool_gauges_locked()
        if victims:
            self._publish_pool_gauges(*gauges)
        for _, r in victims:
            self.num_slots_evicted += 1
            metric_defs.LLM_SLOTS_EVICTED.inc(tags=_EVICT_DISCONNECT_TAGS)
            self._record_done(r, "disconnect", "decode slot evicted mid-stream")
            if not r.future.done():
                r.future.set_exception(
                    RuntimeError("stream consumer disconnected; decode slot evicted")
                )

    def _publish_loop_totals(self, published: Dict[str, float]) -> None:
        """Bring the two loop families up to the engine-owned totals
        (``published``: what they hold already). From the loop, at most once
        a second: never a locked increment a phase."""
        for family, totals, tags in (
            (metric_defs.LLM_LOOP_PHASE_SECONDS, self._clock.seconds, _LOOP_PHASE_TAGS),
            (metric_defs.LLM_DECODE_DISPATCHES, self._dispatches, _DISPATCH_TAGS),
        ):
            for name, total in totals.items():
                delta = total - published.get(name, 0)
                if delta > 0:
                    family.inc(delta, tags[name])
                    published[name] = total

    def _loop(self) -> None:
        clock = self._clock
        published: Dict[str, float] = {}
        publish_at = 0.0
        while not self._stop:
            try:
                # one decode step stays in flight: the next is dispatched
                # before the last one's tokens are read, so everything the
                # host does in an iteration runs beside the device. A chunk
                # goes into the device's queue ahead of that next step, and
                # is waited for only after the step in flight was read.
                # Every instant belongs to one of LOOP_PHASES: a call below
                # opens its own phases where it holds more than one
                now = clock.iteration("evict")
                if now >= publish_at:
                    self._publish_loop_totals(published)
                    publish_at = now + 1.0
                self._evict_cancelled()
                clock.lap("admit")
                self._admit()
                clock.lap("prefill_enqueue")
                chunk = self._prefill_enqueue()
                prev, self._flight = self._flight, self._dispatch(behind_chunk=chunk is not None)
                if prev is not None:
                    self._collect(prev)
                if chunk is not None:
                    self._prefill_finish(*chunk)
                elif prev is None and self._flight is None:
                    clock.lap("idle")
                    self._wake.wait(timeout=0.05)
                    self._wake.clear()
            except BaseException as exc:  # noqa: BLE001 — a dead loop hangs every caller
                # flight-record the crash BEFORE recovery clears the
                # evidence: admission state + the last finished requests
                from ray_tpu.observability import reqtrace

                reqtrace.flight_record(
                    "engine_crash",
                    f"LLMEngine loop crashed: {exc!r}",
                    severity="ERROR",
                    # ... and where the iteration that crashed had spent its time
                    state={**self.admission_snapshot(), "loop_phase_ms": clock.iteration_ms()},
                    requests=list(self._finished_ring)[-8:],
                    engine=str(self._admission_token),
                )
                self._fail_inflight(RuntimeError(f"LLMEngine step failed: {exc!r}"))
                # a failed donated step leaves self._cache pointing at
                # deleted buffers; reallocate so the engine keeps serving
                self._reset_cache()
        clock.close()
        self._publish_loop_totals(published)


class LLMServer:
    """Serve deployment wrapper: each replica owns an engine.

    ``model_factory`` -> (cfg, params) or (cfg, params, tokenizer); called
    once per replica so weights live replica-local (HBM). With a tokenizer
    (anything exposing ``encode(str) -> ids`` / ``decode(ids) -> str``, e.g.
    a HuggingFace tokenizer), requests may pass ``text`` instead of
    ``prompt`` and responses carry decoded ``text``. Deploy with::

        app = serve.deployment(LLMServer).bind(model_factory, max_batch_size=8)
        handle = serve.run(app)
        handle.remote({"prompt": [1,2,3], "max_tokens": 16}).result()
        handle.remote({"text": "once upon", "max_tokens": 16}).result()

    A config with ``block_length`` > 1 generates by diffusion over blocks: a
    request may pass ``denoising_steps`` (1 to ``block_length``, default
    ``block_length``), ``max_tokens`` need be no multiple of the block (what
    the last block holds beyond it is dropped), and a stream delivers a
    committed block a time, as one event ``{"tokens": [...], "unmasked_at":
    [...]}`` (the denoising step at which each token took its value).
    """

    def __init__(
        self,
        model_factory: Callable[[], Any],
        *,
        max_batch_size: int = 8,
        max_seq_len: int = 512,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        quantize: bool = False,
        mesh: Optional[Any] = None,
        tp: str = "tp",
        decode_chunk: int = 1,
        max_queued_requests: int = 256,
        max_queued_prefill_tokens: int = 0,
        tenant_weights: Optional[Dict[str, float]] = None,
        kv_block_size: int = 16,
        kv_num_blocks: int = 0,
        prefill_chunk_tokens: int = 0,
        prefix_cache: bool = True,
        prefix_cache_max_blocks: int = 0,
        role: Optional[str] = None,
        state_snapshots: Optional[int] = None,
    ):
        made = model_factory()
        cfg, params = made[0], made[1]
        self.tokenizer = made[2] if len(made) > 2 else None
        self.role = role or ""
        self.engine = LLMEngine(
            cfg,
            params,
            max_batch_size=max_batch_size,
            max_seq_len=max_seq_len,
            top_k=top_k,
            top_p=top_p,
            quantize=quantize,
            mesh=mesh,
            tp=tp,
            decode_chunk=decode_chunk,
            max_queued_requests=max_queued_requests,
            max_queued_prefill_tokens=max_queued_prefill_tokens,
            tenant_weights=tenant_weights,
            kv_block_size=kv_block_size,
            kv_num_blocks=kv_num_blocks,
            prefill_chunk_tokens=prefill_chunk_tokens,
            prefix_cache=prefix_cache,
            prefix_cache_max_blocks=prefix_cache_max_blocks,
            role=role,
            state_snapshots=state_snapshots,
        )

    def _encode(self, request: Dict[str, Any]) -> List[int]:
        if "prompt" in request:
            return request["prompt"]
        if "text" in request:
            if self.tokenizer is None:
                raise ValueError("this deployment has no tokenizer; send 'prompt' token ids")
            return list(self.tokenizer.encode(request["text"]))
        raise ValueError("request needs 'prompt' (token ids) or 'text'")

    def __call__(self, request: Dict[str, Any]):
        prompt = self._encode(request)
        kw = dict(
            max_tokens=int(request.get("max_tokens", 32)),
            temperature=float(request.get("temperature", 0.0)),
            eos_id=request.get("eos_id"),
        )
        if request.get("denoising_steps") is not None:
            kw["denoising_steps"] = int(request["denoising_steps"])
        if request.get("stream"):
            # submit EAGERLY so validation errors surface as a normal error
            # response, not mid-stream corruption after a 200 was sent;
            # the returned generator of per-token events reaches the proxy
            # by reference (in-proc replicas) and renders as SSE
            stream = self.engine.submit_stream(prompt, blocks=True, **kw)

            def events():
                n = 0
                for tok in stream:
                    if isinstance(tok, TokenBlock):  # a diffusion config: a committed block a time
                        n += len(tok.tokens)
                        yield {"tokens": tok.tokens, "unmasked_at": tok.unmasked_at}
                        continue
                    n += 1
                    yield {"token": tok}
                yield {"done": True, "num_generated": n}

            return events()
        t0 = time.perf_counter()
        out = self.engine.generate(prompt, **kw)
        resp = {
            "tokens": out,
            "num_generated": len(out),
            "latency_s": round(time.perf_counter() - t0, 4),
        }
        if self.tokenizer is not None:
            resp["text"] = self.tokenizer.decode(out)
        return resp

    def stats(self) -> Dict[str, Any]:
        return self.engine.stats()

    def state_snapshot(self, tokens: List[int]) -> Optional[Dict[str, Any]]:
        return self.engine.state_snapshot(tokens)

    def lowered_decode_text(self) -> str:
        return self.engine.lowered_decode_text()

    # -- disaggregated prefill/decode (called by the router's dispatcher) --
    def disagg_prefill(self, request: Dict[str, Any], mig_id: str) -> dict:
        """Prefill-pool half of a disaggregated request: chunked prefill +
        stage, returning the header-only migration ticket."""
        prompt = self._encode(request)
        return self.engine.prefill_export(
            prompt,
            mig_id=mig_id,
            temperature=float(request.get("temperature", 0.0)),
            eos_id=request.get("eos_id"),
        ).result()

    def disagg_decode(self, request: Dict[str, Any], ticket: dict):
        """Decode-pool half: probe the local prefix cache, pull only the
        uncached-suffix blocks (device rung first, host fallback after),
        adopt into the continuous batch and run decode to completion.
        Migration failures return the typed-error envelope the dispatcher
        converts into KVMigrationError — the re-prefill ladder, not a
        crashed request."""
        from ray_tpu.serve import disagg

        prompt = list(ticket["prompt"])
        bs = self.engine.kv_block_size
        n_blocks = int(ticket["n_blocks"])
        matched = self.engine.peek_prefix_match(prompt)
        arrays: Dict[int, Any] = {}
        rung = "device"
        try:
            for bidx in range(matched // bs, n_blocks):
                arr, r = disagg.pull_block(ticket, bidx)
                if r != "device":
                    rung = r
                arrays[bidx] = arr
        except disagg.KVMigrationError as exc:
            return {"_kv_migration_error": True, "stage": exc.stage,
                    "message": str(exc)}
        kw = dict(
            max_tokens=int(request.get("max_tokens", 32)),
            temperature=float(request.get("temperature", 0.0)),
            eos_id=request.get("eos_id"),
        )
        if request.get("stream"):
            import queue as _queue

            q: "_queue.Queue" = _queue.Queue()
            req = self.engine.adopt_migration(
                ticket, arrays, _stream_queue=q, **kw
            )
            stream = _TokenStream(
                self.engine._stream_iter(req, q), req, self.engine
            )

            def events():
                n = 0
                for tok in stream:
                    n += 1
                    yield {"token": tok}
                yield {"done": True, "num_generated": n}

            return {"_stream": events(), "_migration_rung": rung}
        t0 = time.perf_counter()
        req = self.engine.adopt_migration(ticket, arrays, **kw)
        try:
            out = req.future.result()
        except disagg.KVMigrationError as exc:
            return {"_kv_migration_error": True, "stage": exc.stage,
                    "message": str(exc)}
        except RuntimeError as exc:
            cause = exc.__cause__
            if isinstance(cause, disagg.KVMigrationError):
                return {"_kv_migration_error": True, "stage": cause.stage,
                        "message": str(cause)}
            raise
        resp = {
            "tokens": out,
            "num_generated": len(out),
            "latency_s": round(time.perf_counter() - t0, 4),
            "_migration_rung": rung,
        }
        if self.tokenizer is not None:
            resp["text"] = self.tokenizer.decode(out)
        return resp

    def disagg_release(self, mig_id: str) -> bool:
        """Drop a staged export (dispatcher calls exactly once per
        migration, whatever the outcome)."""
        return self.engine.release_migration(mig_id)

    def kv_free_blocks(self) -> int:
        """Decode-pool routing signal for the role-aware router."""
        return self.engine.kv_free_blocks()

    def __del__(self):
        try:
            self.engine.shutdown()
        except Exception:
            pass


class OpenAICompatLLMServer(LLMServer):
    """OpenAI-compatible request/response adapter over :class:`LLMServer`.

    Accepts the body shapes of ``POST /v1/completions`` (``model`` +
    ``prompt``) and ``POST /v1/chat/completions`` (``model`` +
    ``messages``) and answers in the matching OpenAI response envelopes,
    including streaming chunk events over the proxy's SSE path.  Dispatch
    is by body shape — the HTTP proxy routes whole apps by path prefix, so
    one deployment serves both the native protocol and the OpenAI one.
    (Beyond reference parity: the reference delegates OpenAI-compatible
    LLM serving to vLLM.)

    Text prompts/messages need the model_factory to supply a tokenizer;
    token-id prompts work without one.  ``stop`` supports a single token id
    (honored in-engine as eos) or, with a tokenizer, a string trimmed from
    the non-streaming response.
    """

    def __call__(self, request: Any):
        if isinstance(request, dict) and ("messages" in request or "model" in request):
            return self._openai(request)
        return super().__call__(request)

    # ------------------------------------------------------------- openai
    def _openai(self, body: Dict[str, Any]):
        import uuid

        self._reject_unsupported(body)
        chat = "messages" in body
        prompt_ids = self._openai_prompt(body, chat)
        stop = body.get("stop")
        eos_id = None
        stop_text = None
        if isinstance(stop, int):
            eos_id = stop
        elif isinstance(stop, str):
            if self.tokenizer is not None:
                enc = self.tokenizer.encode(stop)
                if len(enc) == 1:
                    eos_id = enc[0]
                else:
                    stop_text = stop
            else:
                raise ValueError("string stop requires a tokenizer")
        elif isinstance(stop, list) and len(stop) == 1:
            return self._openai({**body, "stop": stop[0]})
        elif stop is not None:
            raise ValueError("stop: a single token id or string is supported")

        kw = dict(
            max_tokens=int(body.get("max_tokens", 16)),
            # OpenAI semantics: absent temperature means 1.0 (sampling) —
            # defaulting to greedy here would silently answer a different
            # distribution than every OpenAI SDK client expects
            temperature=float(body.get("temperature", 1.0)),
            eos_id=eos_id,
        )
        rid = ("chatcmpl-" if chat else "cmpl-") + uuid.uuid4().hex[:24]
        model = body.get("model", "ray_tpu")
        created = int(time.time())
        obj = "chat.completion" if chat else "text_completion"

        if body.get("stream"):
            if stop_text is not None:
                raise ValueError(
                    "streaming with a multi-token stop string is not "
                    "supported — use a stop that encodes to one token"
                )
            stream = self.engine.submit_stream(prompt_ids, **kw)

            def chunks():
                reason = "length"
                for tok in stream:
                    if eos_id is not None and tok == eos_id:
                        # OpenAI semantics: the stop sequence is excluded
                        # from the streamed output
                        reason = "stop"
                        continue  # engine ends the stream after eos
                    piece = (
                        self.tokenizer.decode([tok])
                        if self.tokenizer is not None
                        else None
                    )
                    delta = (
                        {"delta": {"content": piece}, "index": 0, "finish_reason": None}
                        if chat
                        else {"text": piece, "token_ids": [tok], "index": 0,
                              "finish_reason": None}
                    )
                    yield {"id": rid, "object": obj + ".chunk", "created": created,
                           "model": model, "choices": [delta]}
                final = (
                    {"delta": {}, "index": 0, "finish_reason": reason}
                    if chat
                    else {"text": "", "index": 0, "finish_reason": reason}
                )
                yield {"id": rid, "object": obj + ".chunk", "created": created,
                       "model": model, "choices": [final]}

            return chunks()

        out = self.engine.generate(prompt_ids, **kw)
        finish = "stop" if (eos_id is not None and out and out[-1] == eos_id) else "length"
        if finish == "stop":
            out = out[:-1]  # OpenAI semantics: stop sequence excluded
        text = self.tokenizer.decode(out) if self.tokenizer is not None else None
        if text is not None and stop_text and stop_text in text:
            # trim at TOKEN granularity so token_ids stay faithful to what
            # the model generated (re-encoding trimmed text could produce
            # ids the model never emitted): keep the longest generated
            # prefix whose decode does not yet contain the stop text, and
            # derive text from it so decode(token_ids) == text
            # contains-stop is monotone in the prefix length, so binary
            # search the cut (a linear scan would decode O(n) prefixes on
            # the serving hot path when the stop lands early)
            lo, hi = 0, len(out)  # invariant: decode(out[:lo]) lacks stop
            while lo < hi:
                mid = (lo + hi + 1) // 2
                if stop_text in self.tokenizer.decode(out[:mid]):
                    hi = mid - 1
                else:
                    lo = mid
            out = out[:lo]
            text = self.tokenizer.decode(out)
            finish = "stop"
        choice: Dict[str, Any] = {"index": 0, "finish_reason": finish, "token_ids": out}
        if chat:
            choice["message"] = {"role": "assistant", "content": text}
        else:
            choice["text"] = text
        return {
            "id": rid,
            "object": obj,
            "created": created,
            "model": model,
            "choices": [choice],
            "usage": {
                "prompt_tokens": len(prompt_ids),
                "completion_tokens": len(out),
                "total_tokens": len(prompt_ids) + len(out),
            },
        }

    def _reject_unsupported(self, body: Dict[str, Any]) -> None:
        """Unimplemented OpenAI sampling params fail loudly — silently
        ignoring them would return samples the client didn't ask for.
        Values matching OpenAI defaults (top_p=1, n=1, zero penalties)
        pass, since SDKs send those unprompted."""
        bad = []
        top_p = body.get("top_p")
        if top_p is not None and top_p < 1.0:
            # sampling config is per-ENGINE: a request may restate the
            # engine's own top_p, but asking for a different distribution
            # must not be silently overridden.  top_p=1.0 always passes —
            # SDKs send the OpenAI default unprompted.
            eng_p = self.engine.top_p
            if eng_p is None or abs(float(top_p) - float(eng_p)) > 1e-9:
                bad.append(
                    f"top_p={top_p} (engine is configured with "
                    f"top_p={eng_p}; per-request nucleus sampling is not "
                    "supported — configure it on the deployment)"
                )
        if body.get("n", 1) not in (None, 1):
            bad.append("n > 1")
        if body.get("best_of", 1) not in (None, 1):
            bad.append("best_of > 1")
        lp = body.get("logprobs")
        if lp is not None and lp is not False:  # NOT `in (None, False)`: 0 == False
            bad.append("logprobs")
        for k in ("presence_penalty", "frequency_penalty"):
            if body.get(k):
                bad.append(k)
        if body.get("echo"):
            bad.append("echo")
        if self.engine.cfg.block > 1:
            # generation by diffusion over blocks: a token comes from a
            # confidence schedule over several forwards of its block, not from
            # one next-token distribution, so nothing that rests on that
            # distribution can be honoured, now or by a later sampler: say so
            why = (f" (the model generates by diffusion over blocks of {self.engine.cfg.block}: "
                   "no next-token distribution a position)")
            bad = [b + why if b in ("logprobs", "n > 1", "best_of > 1") else b for b in bad]
            bad += [k + why for k in ("top_logprobs", "logit_bias") if body.get(k)]
        if bad:
            raise ValueError(
                "unsupported OpenAI parameter(s): " + ", ".join(bad)
            )

    def _openai_prompt(self, body: Dict[str, Any], chat: bool) -> List[int]:
        if chat:
            messages = body["messages"]
            if self.tokenizer is None:
                raise ValueError("chat completions require a tokenizer")
            template = getattr(self.tokenizer, "apply_chat_template", None)
            if template is not None:
                ids = template(messages, add_generation_prompt=True)
                return list(ids)
            joined = "\n".join(f"{m['role']}: {m['content']}" for m in messages)
            return list(self.tokenizer.encode(joined + "\nassistant:"))
        prompt = body.get("prompt")
        if isinstance(prompt, str):
            if self.tokenizer is None:
                raise ValueError("string prompts require a tokenizer")
            return list(self.tokenizer.encode(prompt))
        if isinstance(prompt, list) and all(isinstance(t, int) for t in prompt):
            return prompt
        raise ValueError("prompt must be a string or a list of token ids")
