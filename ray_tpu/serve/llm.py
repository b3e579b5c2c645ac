"""LLM serving: continuous batching over a shared KV cache.

The reference's Serve ships no inference engine (its LLM guides delegate to
vLLM on GPU). On TPU the engine IS the framework's job, and the design is
dictated by XLA's static-shape compilation model. The engine is three boxes
and the arrows point one way: this file is the scheduler; it asks
``serve/sequence_store.py`` what state a sequence keeps and
``serve/model_runner.py`` (which the store calls too, and which calls neither)
for the device state, every program and what a decode step yields.

- **Fixed decode slots.** B = ``max_batch_size`` decode slots; a request
  occupies a slot from admission to completion and every decode step is ONE
  jitted program over all B slots (inactive slots compute masked garbage —
  the static-shape price, paid in exchange for zero recompiles at any
  admission pattern).
- **Block-aware admission.** A request is admitted when the store can
  reserve its whole page budget (paged KV, prefix reuse, a config with linear
  or conv layers' per-slot state and its snapshots: ``serve/sequence_store.py``).
- **Chunked prefill.** Prompts prefill in fixed-size chunks interleaved
  between decode steps (Sarathi-style bounded per-iteration budget,
  ``prefill_chunk_tokens``; 0 = one-shot with power-of-2 bucketing), so a
  long prompt stalls running decodes by at most one chunk's forward.
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
  What a step is — a token a row, or a block step of a config that generates
  by diffusion over blocks — is the runner's (``runner.steps``).

``LLMServer`` is the Serve-facing wrapper: a deployment class whose
replicas each own an engine; requests arrive via handle/HTTP and block on a
per-request Future.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.exceptions import DeadlineExceededError
from ray_tpu.models.transformer import TransformerConfig
from ray_tpu.observability import metric_defs
from ray_tpu.observability.sketch import LatencySketch
from ray_tpu.observability.tracing import LoopClock
from ray_tpu.runtime import admission
from ray_tpu.runtime.context import (
    current_deadline_ts,
    current_request_trace,
    current_tenant,
)
from ray_tpu.serve.model_runner import ModelRunner
from ray_tpu.serve.prefix_cache import chain_keys
from ray_tpu.serve.sequence_store import SequenceStore

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

# What combines with what. A row: a property of the model's configuration, the
# sentence its refusals open with ("" where each is a sentence of its own: the
# first one met is raised) and, by what a constructor call or a submit asks
# for, why the two do not go together. A pair without an entry is served.
_REFUSED = (
    ("block", "a config with block_length {block} (generation by diffusion over blocks) cannot be served with ", {
        "decode_chunk": "decode_chunk > 1 (one block step a program)",
        "quantize": "quantize=True",
        "mesh": "mesh (the block step runs the single-device paged kernels)",
        "page": "kv_block_size {kv_block_size} (a page holds whole blocks of {block})",
        "length": "max_seq_len {max_seq_len} (whole blocks of {block})",
    }),
    ("slot state", 'a config whose layers keep a state a sequence at its slot ("linear": a recurrent state; "conv": '
                   "a convolution tail) cannot be served with ", {
        "decode_chunk": "decode_chunk > 1 (a snapshot is taken behind one step's state)",
        "quantize": "quantize=True (the int8 scales ride one stack of layers)",
        "mesh": "mesh (the state and the snapshot pool are not sharded)",
    }),
    ("no slot state", "", {
        "state_snapshots": 'state_snapshots={state_snapshots} belongs to a config with "linear" or "conv" layers; '
                           "this one keeps no state a sequence beside its pages",
    }),
    ("mesh", "", {
        "quantize": "quantize=True with mesh is not supported yet",
        "role": "role={role!r} with mesh is not supported yet: migrated "
                "blocks are exported from and landed in an unsharded pool",
        "axis": "mesh has no {tp!r} axis: {axes}",
    }),
    # the int8 scales ride ONE stack of layers whose every weight is an xs leaf of the layer scan
    ("dense_stack or dropless", "", {
        "quantize": "quantize=True does not cover a config with num_dense_layers > 0 or dropless expert layers "
                    "(two layer stacks; expert weights read where they lie): serve it unquantized",
    }),
    # at submit
    ("slot state", "", {
        "migration": 'prefill_export / adopt_migration are not supported for a config with "linear" or "conv" '
                     "layers: a migrated block set carries pages, not the sequence's recurrent state or "
                     "convolution tails",
    }),
    ("autoregressive", "", {
        "denoising_steps": "denoising_steps belongs to a config that generates by diffusion over blocks "
                           "(block_length > 1); this engine's config is autoregressive",
    }),
    ("block", "", {
        "migration": "prefill_export / adopt_migration are not supported for a config that generates "
                     "by diffusion over blocks: an exported prefill carries no first token",
        "steps_range": "denoising_steps must be 1 to the config's block_length {block}, got {denoising_steps}",
    }),
)


def _check_combination(cfg: TransformerConfig, mesh: Any, asked: Dict[str, Any], **named) -> None:
    """No silent path: raise what ``_REFUSED`` holds against a call that
    ``asked`` for these (what is asked for by name -> whether it was)."""
    held = {"block": cfg.block > 1, "autoregressive": cfg.block == 1, "slot state": cfg.hybrid, "no slot state": not cfg.hybrid,
            "mesh": mesh is not None, "dense_stack or dropless": bool(cfg.dense_stack or cfg.dropless)}
    for row, head, cells in _REFUSED:
        bad = [why.format(block=cfg.block, **named) for what, why in cells.items() if held[row] and asked.get(what)]
        if bad:
            raise ValueError(head.format(block=cfg.block) + "; ".join(bad) if head else bad[0])


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
    commits: Optional[Dict[int, int]] = None
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
    are built once (``serve/model_runner.py``) so the loop never traces.

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
    linear or conv layers (None = twice ``max_batch_size``; 0 = none: every request
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
        nb = int(kv_num_blocks)
        if nb <= 0:
            # auto: every slot can hold a max-length sequence (+1 for the
            # garbage page)
            nb = self.B * -(-self.S // self.kv_block_size) + 1
        self.kv_num_blocks = nb
        self.prefill_chunk_tokens = int(prefill_chunk_tokens)
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
        _check_combination(
            cfg, mesh,
            {"decode_chunk": self.decode_chunk > 1, "quantize": quantize, "mesh": mesh is not None,
             "page": self.kv_block_size % cfg.block, "length": self.S % cfg.block, "state_snapshots": state_snapshots,
             "role": self.role, "axis": mesh is not None and tp not in mesh.axis_names},
            kv_block_size=self.kv_block_size, max_seq_len=self.S, state_snapshots=state_snapshots, role=self.role,
            tp=tp, axes=getattr(mesh, "axis_names", None))
        n_snapshots = (2 * self.B if state_snapshots is None else max(0, int(state_snapshots))) if cfg.hybrid else 0
        self.top_k, self.top_p = top_k, top_p

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

        # the device state and every program over it, and what a decode step yields
        sizes = dict(B=self.B, S=self.S, kv_block_size=self.kv_block_size, kv_num_blocks=nb, n_snapshots=n_snapshots)
        self.runner = ModelRunner(cfg, params, top_k=top_k, top_p=top_p, quantize=quantize, mesh=mesh, tp=tp,
                                  quantize_min_size=quantize_min_size, decode_chunk=self.decode_chunk, **sizes)
        self._steps = self.runner.steps
        # ``benchmark/tools/state_precision_control.py`` swaps ``_place_state`` on the engine
        # and, inside it, these two: aliases of the runner's programs (None without linear
        # layers) until that tool swaps at the runner (ROADMAP D12)
        self._zero_state = getattr(self.runner, "_zero_state", None)
        self._restore_state = getattr(self.runner, "_restore_state", None)
        # what state a sequence keeps: pages, cached prefixes, state snapshots, staged migrations
        self.store = SequenceStore(
            cfg, self.runner, self._lock, prefix_cache=prefix_cache, max_blocks=int(prefix_cache_max_blocks),
            gap=2 * (self.prefill_chunk_tokens or self.S), tags=self._depth_tags, **sizes)
        # slot state (host-side mirrors of the device arrays). ``_pos`` is the
        # position the NEXT dispatch writes: it advances at dispatch, not at
        # readback. ``_reserved``: slots of a request whose chunked prefill is
        # still in flight (the slot is taken but must not receive decode tokens yet)
        self._slots: List[Optional[GenRequest]] = [None] * self.B
        self._pos = np.zeros(self.B, np.int32)
        self._temps = np.zeros(self.B, np.float32)
        self._active = np.zeros(self.B, bool)
        self._reserved = np.zeros(self.B, bool)
        self._prefilling: List[GenRequest] = []
        # head-of-line request popped from the fair queue but waiting for
        # pages: held (not re-pushed — that would break fair ordering)
        # until release paths free enough blocks
        self._held_req: Optional[GenRequest] = None
        self._prefill_chunk_count = 0
        # tokens of K/V the chunks' attention had to visit, averaged over the
        # layers (``store.chunk_kv_visited``), and the capacity a chunk's table
        # spans: what the prefill kernel reads of what the dense lines read
        self._prefill_kv_visited = 0.0
        self._prefill_kv_capacity = 0
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
        # disaggregated serving: the in/out migration counters surfaced by stats()/rt llm
        self.num_migrations_out = 0
        self.num_migrations_in = 0
        self._model_gauges(1)
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
        # a diffusion config's default: as many steps as a block has positions
        block = self.cfg.block
        n_steps = block if denoising_steps is None else int(denoising_steps)
        _check_combination(
            self.cfg, None,
            {"migration": _export_mig_id is not None or _import_ticket is not None,
             "denoising_steps": denoising_steps is not None, "steps_range": not 1 <= n_steps <= block},
            denoising_steps=denoising_steps)
        # never-fits contract (same as max_queued_prefill_tokens below):
        # a request needing more pages than the POOL holds can never be
        # admitted — that is a config/input error at submit, not a
        # retry-after-able overload and not a failure deep in prefill
        needed = self.store.pages_needed(len(prompt), max_tokens)
        capacity = self.store.allocator.capacity
        if needed > capacity:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_tokens ({max_tokens}) needs "
                f"{needed} KV blocks but the pool only holds "
                f"{capacity} and would never be admitted"
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
        block_keys = tuple(chain_keys(prompt, len(prompt) // bs, bs)) if self.store.prefix is not None else ()
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
            req.denoising_steps = n_steps if block > 1 else 0
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

    def _fill_len(self, req: GenRequest) -> int:
        """Prompt tokens prefill has to cache: all of them, or for a
        diffusion config the prompt's whole blocks (the rest opens the
        first block as known positions)."""
        return len(req.prompt) - len(req.prompt) % self._steps.block

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

    def stats(self) -> Dict[str, Any]:
        """The scheduler's counters, the store's (pages, prefix cache, state
        snapshots) and what the runner's steps count (a diffusion config)."""
        with self._lock:
            return {
                "role": self.role,
                "migrations_out": self.num_migrations_out,
                "migrations_in": self.num_migrations_in,
                "active_slots": int(self._active.sum()),
                "max_batch_size": self.B,
                "queued": len(self._queue),
                "queued_prefill_tokens": self._queued_tokens,
                "prefill_forwards": self._prefill_count,
                "slots_evicted": self.num_slots_evicted,
                "shed": self.num_shed,
                "prefilling": len(self._prefilling),
                "prefill_chunks": self._prefill_chunk_count,
                "prefill_kv_tokens_visited": self._prefill_kv_visited,
                "prefill_kv_tokens_capacity": self._prefill_kv_capacity,
                "decode_steps": self._decode_step_count,
                # dispatched while the step before was still unread: all but the cold ones
                "decode_steps_overlapped": self.decode_chunk * (self._dispatches["queued"] + self._dispatches["dry"]),
                "decode_row_steps_discarded": self._decode_row_steps_discarded,
                "decode_dispatches": dict(self._dispatches),
                "loop_phase_s": dict(self._clock.seconds),
                "kv_read_share": self.kv_read_share(),
                "kv_live_pages": self.kv_live_pages(),
                "kv_write": self.runner.kv_write,
                **self.store.stats_locked(),
                **self._moe_stats_locked(),
                **self._steps.stats(self._decode_step_count),
                **self.store.state_stats_locked(),
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

    def admission_snapshot(self) -> Dict[str, Any]:
        """Bounds + depths for GET /api/overload (admission source)."""
        with self._lock:
            pool = self.store.stats_locked()
            useful = pool["prefix_cache_hits"] + pool["prefix_cache_partial"]
            probes = useful + pool["prefix_cache_misses"]
            return {
                "layer": "engine",
                "role": self.role,
                "migrations_out": self.num_migrations_out,
                "migrations_in": self.num_migrations_in,
                "queued": len(self._queue),
                "queue_bound": self._max_queued,
                "queued_prefill_tokens": self._queued_tokens,
                "token_budget": self._max_queued_tokens,
                "active_slots": int(self._active.sum()),
                "slots": self.B,
                "by_tenant": self._queue.depth_by_tenant(),
                "slots_evicted": self.num_slots_evicted,
                "shed": self.num_shed,
                **{k: pool[k] for k in (
                    "staged_migrations", "kv_block_size", "kv_block_pool_size", "kv_blocks_in_use", "kv_blocks_shared",
                    "prefix_cache_enabled", "prefix_cache_blocks", "prefix_tokens_reused", "prefix_evictions",
                    "prefix_evict_scanned")},
                "kv_block_occupancy": pool["kv_blocks_in_use"] / pool["kv_block_pool_size"],
                "prefilling": len(self._prefilling),
                "prefill_chunks": self._prefill_chunk_count,
                "waiting_for_blocks": 1 if self._held_req is not None else 0,
                "prefix_hit_rate": (useful / probes) if probes else 0.0,
                "decode_steps": self._decode_step_count,
                "kv_read_share": self.kv_read_share(),
                **self._moe_stats_locked(),
                # SLO percentiles from the engine-side latency sketches
                # (ttft / inter_token / queue_wait / e2e, seconds)
                "latency": {
                    name: sk.percentiles() for name, sk in self._sketches.items()
                },
            }

    def _model_gauges(self, on: int) -> None:
        """This engine's series of what its model holds (zeroed at shutdown:
        the series label is reused by the next engine)."""
        cfg = self.cfg
        if cfg.latent_layers:
            metric_defs.LLM_LATENT_LAYERS.set(on * cfg.latent_layers, self._depth_tags)
            metric_defs.LLM_KV_BYTES_PER_TOKEN.set(on * self.runner.kv_bytes_per_token, self._depth_tags)
        if cfg.experts_held is not None:
            metric_defs.LLM_MOE_EXPERTS_HELD.set(on * cfg.experts_here, self._depth_tags)

    def shutdown(self) -> None:
        self._stop = True
        self._wake.set()
        self._thread.join(timeout=5)
        admission.unregister_admission_source(self._admission_token)
        # zero this engine's gauge series; the freed token (and thus the
        # series label) is reused by the next engine
        metric_defs.ADMISSION_QUEUE_DEPTH.set(0, self._depth_tags)
        self.store.gauges(0)
        self._model_gauges(0)
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
            staged = list(self.store.staged)
            self.store.staged.clear()
        self.store.unregister(staged)
        for r in pending:
            r.future.set_exception(RuntimeError("LLMEngine shut down"))
            if r.stream_queue is not None:
                r.stream_queue.put(_STREAM_END)

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
    def _pop_admissible(self):
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
                if not free:
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
        """Admission: pop the next request in fair order, reserve its whole
        page budget from the store (``SequenceStore.reserve_locked``: the
        prefix match, the pins, the eviction sweep; a request the pool cannot
        hold yet waits at the head of the line), place its slot's state, and
        queue it for prefill. Prefill itself runs later, chunk by chunk, from
        ``_prefill_enqueue`` so decode steps interleave with long prompts."""
        store = self.store
        while True:
            popped = self._pop_admissible()
            if popped is None:
                return
            req, free = popped
            slot = free[0]
            with self._lock:
                got = store.reserve_locked(req, slot)
                if got is None:
                    self._held_req = req
                    return
                self._reserved[slot] = True
            store.publish_reserved(got)
            req.slot = slot
            if req.trace is not None:
                # pages reserved: kv_block_wait (wfq_pop -> here) is over
                req.trace.mark("admitted")
            # chunked prefill resumes at the first token whose KV is not
            # already in the table (tp - 1 for a full hit: one recompute)
            req.prefill_pos = got.matched
            if store.keeps_state and not self._place_state(req, got.snapshot):
                continue
            if got.cow_src >= 0:
                try:
                    store.copy_tail(got)
                except BaseException as exc:  # noqa: BLE001
                    self._fail_admit(req, exc)
                    continue
                req.prefill_pos = len(req.prompt) - 1
            if req.import_arrays is not None:
                # migrated request: blocks land from the producer's staged
                # arrays (or this replica's own prefix cache) — no prefill.
                # One adoption per admission pass: landing a block set is
                # the heaviest admission step, and a migration burst
                # draining in a single pass would stall the decode cadence
                # for every running stream (the loop re-admits next tick)
                self._adopt_admitted(req, had_cow=got.cow_src >= 0)
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
        # (the tool named at ``_restore_state`` swaps this method and the two programs it passes)
        t = time.perf_counter()
        try:
            if snapshot >= 0:
                self.runner.restore_state(req.slot, snapshot, self._restore_state)
            else:
                self.runner.zero_state(req.slot, self._zero_state)
        except BaseException as exc:  # noqa: BLE001
            self._fail_admit(req, exc)
            return False
        spent = time.perf_counter() - t  # enqueue to return: the span ``llm::state_restore``
        with self._lock:
            self.store.state_reset_s += spent
            if snapshot >= 0:
                self.store.state_restores += 1
            else:
                self.store.state_zeroed += 1
        (metric_defs.LLM_STATE_RESTORES if snapshot >= 0 else metric_defs.LLM_STATE_ZEROED).inc()
        return True

    def _finish_prefill(self, req: GenRequest, logits) -> None:
        """Prompt is fully in the paged cache: sample the first token, where
        one comes from prefill, and hand the slot to the decode batch (or,
        for an export request, stage the block set for migration instead)."""
        tok0 = None
        if self._steps.first_from_prefill:
            tok0 = self.runner.sample_first(logits, req.temperature)
            if req.export_mig_id is not None:
                self._export_staged(req, tok0)
                return
            req.generated = [tok0]
            self._note_first_token(req)
            req.emit(tok0)
        with self._lock:
            self._join_locked(req, tok0)
        if tok0 is not None:
            self._maybe_finish(req, tok0)

    def _join_locked(self, req: GenRequest, tok0: Optional[int]) -> None:
        """``req``'s slot joins the decode batch, as the runner's steps have a row join."""
        slot = req.slot
        self._slots[slot] = req
        self._active[slot] = True
        self._reserved[slot] = False
        self._pos[slot] = self._steps.join_row(req, slot, tok0)
        self._temps[slot] = req.temperature

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
        if not had_cow:
            # with a full-hit COW every prompt position is already paged
            # in, so there is nothing to write at all
            try:
                missing = self.store.land_migrated(req)
            except BaseException as exc:  # noqa: BLE001
                self._fail_admit(req, exc)
                return
            if missing >= 0:
                self._fail_admit(req, KVMigrationError(
                    mig_id, "pulled",
                    f"block {missing} neither locally cached nor pulled "
                    f"(local prefix match shrank to {req.prefill_pos} "
                    "tokens after the probe)",
                ))
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
            self._join_locked(req, tok0)
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
        arrays = self.store.export_pages(req)
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
            self._reserved[req.slot] = False
            evicted_n = self.store.retire_locked(req)
            self.store.staged[mig_id] = {
                "arrays": arrays,
                "prompt": list(req.prompt),
                "n_blocks": n_blocks,
            }
            self.num_migrations_out += 1
            gauges = self.store.pool_gauges_locked()
        if evicted_n:
            metric_defs.LLM_PREFIX_EVICTIONS.inc(evicted_n)
        self.store.publish_pool_gauges(*gauges)
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
                self._reserved[req.slot] = False
                self.store.release_locked(req.slot, req)
                gauges = self.store.pool_gauges_locked()
            self.store.publish_pool_gauges(*gauges)
        if self.runner.cache_lost():
            # a donated chunk or page write consumed the cache then failed: the
            # shared cache is gone, taking every in-flight slot with it
            self._fail_inflight(RuntimeError(f"cache lost in failed prefill: {exc!r}"))
            self._reset_cache()

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
                self._reserved[req.slot] = False
                self.store.release_locked(req.slot, req)
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
            gauges = self.store.pool_gauges_locked()
        self.store.publish_pool_gauges(*gauges)
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
        snap_at = self.store.snapshot_after_prompt(req)
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
            self.store.cow_shared_writes(req.slot, start, n)
            # a copy of the row: the transfer may read (or alias) the host
            # buffer after the call returns, and the mirror is rewritten at
            # will before the chunk has been waited for
            logits, moe = self.runner.prefill_chunk(
                toks, self.store.block_tables[req.slot : req.slot + 1].copy(), start, n, req.slot)
            if snap_at and start + n == snap_at:
                for taken in self.store.take_snapshots([(req, snap_at)]):
                    self.store.keep_snapshot(*taken)
            elif req.branch_at and start + n == req.branch_at:
                self.store.snapshot_branch(req)
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
        visited = self.store.chunk_kv_visited(req.prefill_pos, n)
        capacity = self.store.max_blocks_per_slot * self.kv_block_size
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
        cfg = self.cfg
        lens = self._pos[self._active].astype(np.int64) + cfg.block  # to the end of the step's writes
        last = -(-lens // bs)
        if cfg.hybrid:  # the full (or latent) layers walk every page, the linear and conv layers none
            return (cfg.kv_layers + cfg.latent_layers) * int(last.sum()) / cfg.n_layers
        windows = cfg.layer_windows or (0,)
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
                self._reserved[req.slot] = False
                evicted_n = self.store.retire_locked(req)
                gauges = self.store.pool_gauges_locked()
            if evicted_n:
                metric_defs.LLM_PREFIX_EVICTIONS.inc(evicted_n)
            self.store.publish_pool_gauges(*gauges)
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
        """Enqueue one decode step (what one is: ``runner.steps``) for every
        row that still owes one and return its handle unread; None if there is
        no such row. The host knows everything the step needs ahead of the
        device but the rows' last tokens (a diffusion config: their blocks),
        and those the program takes from its own previous run or, for a row
        that joined since, from the steps' join mirrors. Positions and the
        ``max_tokens`` count advance here, so the next step can be dispatched
        before this one is read.
        ``behind_chunk``: this iteration enqueued a prefill chunk ahead."""
        self._clock.lap("dispatch_rows")
        steps, store = self._steps, self.store
        rows: List[Tuple[int, GenRequest]] = []
        live = np.zeros(self.B, bool)
        # rt-lint: disable=lock-discipline -- engine-thread-owned: every
        # _slots mutation (admit/finish/evict/fail_inflight) runs on this
        # same engine loop thread; _lock exists for cross-thread READERS
        # (stats, abandon flags), not for us
        for i, req in enumerate(self._slots):
            if req is None or req.dispatched >= req.max_tokens - steps.unwritten:
                continue  # free, or the last it owes is in flight: known by count
            # copy-on-write net: the step writes ``span`` positions from pos (a
            # block's tentative or final) — if any of those blocks still maps to a
            # shared page, give the slot its own copy before stepping
            store.cow_shared_writes(i, int(self._pos[i]), steps.span)
            rows.append((i, req))
            live[i] = True
        if not rows:
            return None
        self._clock.lap("dispatch_enqueue")
        # rows not in this step decode through all-zero tables -> garbage
        # page 0, so freed pages are never written after release. The device
        # gets copies of the mirrors: a transfer may read (or alias) its host
        # buffer after the call returns, and the mirrors change right below
        bt = jnp.asarray(store.block_tables * live[:, None].astype(np.int32))
        join, pos, temps = steps.uploads(), jnp.asarray(self._pos.copy()), jnp.asarray(self._temps.copy())
        self._note_dispatch(behind_chunk)
        out, moe = self.runner.step(join, pos, temps, bt)
        steps.clear()
        snaps = store.take_snapshots(store.rows_ending_a_page(rows, self._pos))
        return _Flight(out, moe, rows, steps.advance(rows, self._pos), snaps)

    def _collect(self, flight: _Flight) -> None:
        """Read a dispatched step (this waits for that step only, not for one
        dispatched after it) and emit what it hands each row. A row whose
        request left its slot since the dispatch (an EOS read one step late, a
        cancelled stream evicted) is dropped whole, by identity: the slot
        may be another request's by now."""
        self._clock.lap("collect_wait")
        steps = self._steps
        host = steps.read(flight.out)
        self._clock.lap("collect_counts")
        self._decode_step_count += steps.count
        self._note_moe(flight.moe, decode=True)
        # rt-lint: disable=lock-discipline -- engine-thread-owned (see _dispatch)
        rows = [(i, req) for i, req in flight.rows if self._slots[i] is req and not req.cancelled]
        self._decode_row_steps_discarded += (len(flight.rows) - len(rows)) * steps.count
        for req, entry, tokens in flight.snaps:
            # taken behind this step: kept if the row's step is (before its request may finish
            # below and publish it); a discarded row-step's token is in its state, so it goes
            # rt-lint: disable=lock-discipline -- engine-thread-owned (see _dispatch)
            if self._slots[req.slot] is req and not req.cancelled:
                self.store.keep_snapshot(req, entry, tokens)
            else:
                self.store.free_snapshot(entry)
        self._clock.lap("emit")
        for i, req, toks, unmasked_at in steps.handed(host, rows, flight.commits):
            # rt-lint: disable=lock-discipline -- engine-thread-owned (see _dispatch)
            if self._slots[i] is not req:
                continue  # finished earlier in this chunk
            req.generated.extend(toks)
            if unmasked_at is None:  # a token, or a committed block as one stream event
                self._note_next_token(req)
                req.emit(toks[0])
            else:
                self._note_block(req, len(toks))
                req.emit_block(toks, unmasked_at)
            self._maybe_finish(req, toks[-1])

    def _reset_cache(self) -> None:
        """(Re)allocate the device state — also the recovery path after a
        failed donated step leaves the old buffers deleted."""
        self.store.reset_snapshots()
        self.runner.reset()

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
            self._reserved[:] = False
            self.store.drop_all_locked(victims)
        # the step in flight goes with them: its rows' requests are victims
        # (engine-thread state, like the loop that dispatched it)
        self._flight = None
        self._steps.clear()
        metric_defs.ADMISSION_QUEUE_DEPTH.set(0, self._depth_tags)
        self.store.publish_pool_gauges(0, 0, 0)
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
                self._reserved[i] = False
                self.store.release_locked(i, r)
            self._steps.dropped(len(victims))
            gauges = self.store.pool_gauges_locked()
        if victims:
            self.store.publish_pool_gauges(*gauges)
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
        return self.engine.store.state_snapshot(tokens)

    def lowered_decode_text(self) -> str:
        return self.engine.runner.lowered_decode_text()

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
        matched = self.engine.store.peek_prefix_match(prompt)
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
        return self.engine.store.release_migration(mig_id)

    def kv_free_blocks(self) -> int:
        """Decode-pool routing signal for the role-aware router."""
        return self.engine.store.kv_free_blocks()

    def __del__(self):
        try:
            self.engine.shutdown()
        except Exception:
            pass

