"""Disaggregated prefill/decode serving: KV-block migration over the
device plane.

Prefill is compute-bound, decode is HBM-bandwidth-bound; co-locating them
on one replica makes every long-prompt burst steal decode compute —
chunked prefill only caps the stall, it doesn't remove it.  Disaggregation
(DistServe, OSDI '24; Splitwise, ISCA '24) splits a deployment declaring
``roles={"prefill": n, "decode": m}`` into two replica pools:

1. the router admits a request into a **prefill** replica (picked by
   queue depth), which runs chunked prefill into its local paged KV and
   parks the resulting block set as a *staged migration*;
2. the block set migrates replica-to-replica over the **device plane**:
   the producer stages each page under a deterministic ``(request, block)``
   uuid (:func:`migration_uuid`) via the transfer server, and the decode
   replica — picked by free KV pages — pulls device-to-device.  The
   control stream carries only the block-table header (:class:`ticket
   <make_ticket>`), zero KV payload bytes;
3. the decode replica's continuous batcher adopts the blocks into its own
   pool (COW / prefix-cache semantics intact) and resumes decode from the
   migrated block table.

Handoff state machine (one migration)::

    prefill-done ──> staging ──> pulled ──> decoding ──> finished
         │              │           │
         │              └───────────┴──[decode replica died / refused]
         │                          ▼
         └────────────────── re-prefill fallback (fresh attempt id)

Ladder per block: in-process staged copy (same-process replicas
short-circuit — identical bytes, zero copies) → device pull (transfer
server) → host-staged pull (data-plane ``kv_pull`` op).  Fallback ladder
per migration: retry with a re-prefill on a fresh replica pair, at most
``Config.kv_migration_attempts`` attempts, then the typed
:class:`KVMigrationError` surfaces to the caller.

Determinism contract: migration ids derive from a per-dispatcher monotonic
counter + attempt index (never random), and block uuids derive from the
migration id — same-seed chaos runs replay byte-identical fault logs.
"""

from __future__ import annotations

import threading
import time
import zlib
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu.core.config import get_config
from ray_tpu.observability import metric_defs

#: the two pool roles a disaggregated deployment declares
ROLE_PREFILL = "prefill"
ROLE_DECODE = "decode"

_OUTCOME_TAGS = {
    "device": {"outcome": "device"},
    "host": {"outcome": "host"},
    "reprefill": {"outcome": "reprefill"},
    "failed": {"outcome": "failed"},
}


class KVMigrationError(RuntimeError):
    """Typed failure of one KV-block migration attempt (decode replica
    died, refused the pull, or lost the staged blocks).  The dispatcher
    catches it to walk the fallback ladder; callers see it only when the
    ladder is exhausted."""

    def __init__(self, mig_id: str, stage: str, message: str):
        super().__init__(f"kv migration {mig_id!r} failed at {stage}: {message}")
        self.mig_id = mig_id
        self.stage = stage


def migration_uuid(mig_id: str, block_idx: int) -> int:
    """Deterministic transfer-server uuid for one staged block: derived
    from the ``(request, block)`` identity, NEVER random — chaos runs must
    replay identical wire traffic.  Mirrors the compiled-plan channel's
    ``_device_frame_uuid`` derivation (crc32 keyspace partitioned by a
    tagged prefix; low 32 bits carry the block index)."""
    hi = zlib.crc32(f"kvmig:{mig_id}".encode()) & 0x7FFFFFFF
    return ((hi << 32) | (block_idx & 0xFFFFFFFF)) or 1


def validate_roles(roles: Optional[Dict[str, int]]) -> None:
    """Deploy-time validation of a disaggregated deployment (fails fast
    with a typed ValueError instead of wedging at the first migration):

    - only the ``prefill`` / ``decode`` roles exist;
    - both pools need at least one replica (zero decode replicas would
      accept prefills that can never decode).
    """
    if roles is None:
        return
    unknown = sorted(set(roles) - {ROLE_PREFILL, ROLE_DECODE})
    if unknown:
        raise ValueError(
            f"unknown deployment role(s) {unknown}: a disaggregated "
            f"deployment declares only {ROLE_PREFILL!r} and {ROLE_DECODE!r}"
        )
    for role in (ROLE_PREFILL, ROLE_DECODE):
        if int(roles.get(role, 0)) < 1:
            raise ValueError(
                f"roles={roles} needs at least one {role!r} replica: a "
                "disaggregated deployment admits into the prefill pool and "
                "decodes on the decode pool — an empty pool wedges every "
                "request at its first migration"
            )


def make_ticket(
    mig_id: str,
    *,
    prompt: List[int],
    tok0: int,
    n_blocks: int,
    block_size: int,
    block_shape: Tuple[int, ...],
    block_dtype: str,
    transfer_addr: Optional[str],
    data_addr: Optional[str],
    source: str,
) -> dict:
    """The migration's control-stream header: block-table metadata only —
    the KV payload rides the device plane (or the host-staged fallback),
    never this dict.  ``source`` names the prefill replica for in-process
    staged-copy resolution and audit attribution."""
    return {
        "mig_id": mig_id,
        "prompt": list(prompt),
        "tok0": int(tok0),
        "n_blocks": int(n_blocks),
        "block_size": int(block_size),
        "block_shape": tuple(block_shape),
        "block_dtype": str(block_dtype),
        "transfer_addr": transfer_addr,
        "data_addr": data_addr,
        "source": source,
    }


_planes: Optional[Tuple[Any, Any]] = None


def _runtime_planes() -> Tuple[Any, Any]:
    """``(data_plane, device_plane)``, imported once.  pull_block runs per
    staged block; re-resolving a package ``from``-import there costs ~100us
    a call and dominated the whole migration wall."""
    global _planes
    if _planes is None:
        from ray_tpu.runtime import data_plane, device_plane

        _planes = (data_plane, device_plane)
    return _planes


def pull_block(ticket: dict, block_idx: int,
               timeout_s: Optional[float] = None) -> Tuple[Any, str]:
    """Fetch one staged block for ``ticket``, walking the per-block rungs:
    in-process staged copy → device pull → host-staged ``kv_pull``.
    Returns ``(array, rung)``; raises :class:`KVMigrationError` when every
    rung refuses (the per-migration ladder then re-prefills)."""
    import jax.numpy as jnp
    import numpy as np

    data_plane, device_plane = _runtime_planes()

    mig_id = ticket["mig_id"]
    if timeout_s is None:
        timeout_s = get_config().kv_migration_pull_timeout_s
    # same-process replicas (inproc execution) short-circuit FIRST: a
    # registry hit means the prefill replica staged these very arrays in
    # this process — identical bytes with zero copies, so round-tripping
    # them through a socket (or the transfer server) would only add
    # serialization cost.  Staged blocks are already device arrays;
    # re-wrapping through jnp.asarray costs a dispatch per block for
    # nothing, so only host arrays get converted.
    fetch = data_plane.kv_block_source(mig_id)
    if fetch is not None:
        import jax

        try:
            arr = fetch(block_idx)
        except Exception as exc:  # noqa: BLE001 — released mid-pull
            raise KVMigrationError(
                mig_id, "pulled", f"staged block {block_idx} lost: {exc!r}"
            ) from exc
        if not isinstance(arr, jax.Array):
            arr = jnp.asarray(arr)
        return arr, "host"
    addr = ticket.get("transfer_addr")
    if addr:
        template = np.zeros(
            ticket["block_shape"], np.dtype(ticket["block_dtype"])
        )
        arr = device_plane.device_pull(
            addr, migration_uuid(mig_id, block_idx), template
        )
        if arr is not None:
            return arr, "device"
    data_addr = ticket.get("data_addr")
    if data_addr:
        arr = data_plane.pull_kv_block(
            data_addr, mig_id, block_idx, timeout=timeout_s
        )
        if arr is not None:
            return jnp.asarray(arr), "host"
    raise KVMigrationError(
        mig_id, "staging",
        f"block {block_idx}: no rung could reach the staged page "
        f"(transfer_addr={addr!r}, data_addr={data_addr!r})",
    )


def local_data_addr() -> Optional[str]:
    """Address of this node's data server (the host-staged fallback
    endpoint a ticket advertises), or None when the engine runs without a
    runtime — the in-process registry rung still works then."""
    try:
        from ray_tpu.runtime.worker import global_worker

        return global_worker().cluster.head_service.data_server.address
    except Exception:  # noqa: BLE001 — engine driven without rt.init
        return None


def _record_audit(event: dict) -> None:
    """Append one migration-lifecycle audit onto the cluster (the chaos
    invariant sweep asserts every staged block set reaches exactly one
    terminal).  Best-effort: engines driven without a runtime still work."""
    try:
        from ray_tpu.runtime.worker import global_worker

        cluster = global_worker().cluster
        audits = getattr(cluster, "kv_migration_audits", None)
        if audits is not None:
            audits.append(event)
    except Exception:  # noqa: BLE001 — audits must never fail a request
        pass


class DisaggDispatcher:
    """Role-aware request flow for one disaggregated deployment.

    Owned by the router (one per deployment with ``roles``); uses the
    router's replica list + metadata and calls replicas through the same
    ``handle_request`` surface as ordinary dispatch, so admission bounds,
    tenant context, and the request trace all ride along unchanged.
    """

    def __init__(self, router, deployment: str):
        self._router = router
        self._deployment = deployment
        self._lock = threading.Lock()
        self._seq = 0
        # monotonic dispatch counters per role (rt llm / /api/overload)
        self.dispatched = {ROLE_PREFILL: 0, ROLE_DECODE: 0}
        self.migrations = {k: 0 for k in _OUTCOME_TAGS}

    # ------------------------------------------------------------ identity
    def _next_mig_id(self) -> str:
        """Derived, never random: ``<deployment>/m<seq>`` with the attempt
        suffix appended per ladder rung — byte-identical across same-seed
        chaos replays."""
        with self._lock:
            self._seq += 1
            return f"{self._deployment}/m{self._seq}"

    # ------------------------------------------------------------ dispatch
    def route(self, request: dict, tenant=None, trace=None):
        """Full disaggregated flow for one request: prefill → migrate →
        decode, with the re-prefill fallback ladder."""
        from ray_tpu.runtime import failpoints

        attempts = max(1, int(get_config().kv_migration_attempts))
        base_id = self._next_mig_id()
        last_exc: Optional[BaseException] = None
        for attempt in range(attempts):
            mig_id = base_id if attempt == 0 else f"{base_id}#a{attempt}"
            t0 = time.perf_counter()
            # prefill-pool failures raise as ordinary request errors, not
            # migration failures: no staged state exists yet
            p_index, ticket = self._prefill(request, mig_id, tenant, trace)
            _record_audit({
                "mig_id": mig_id,
                "event": "staged",
                "deployment": self._deployment,
                "blocks": ticket["n_blocks"],
                "attempt": attempt,
            })
            try:
                hit = failpoints.fp("disagg.decode_call")
                if hit == "raise":  # pragma: no cover — fp() raises itself
                    raise KVMigrationError(mig_id, "staging", "failpoint")
                result, rung = self._decode(request, ticket, tenant, trace)
            except BaseException as exc:  # noqa: BLE001 — ladder catches all
                self._release(p_index, mig_id,
                              "reprefill" if attempt + 1 < attempts
                              else "failed", tenant)
                last_exc = exc
                if attempt + 1 < attempts:
                    outcome = "reprefill"
                    self.migrations[outcome] += 1
                    metric_defs.LLM_KV_MIGRATIONS.inc(tags=_OUTCOME_TAGS[outcome])
                    continue
                self.migrations["failed"] += 1
                metric_defs.LLM_KV_MIGRATIONS.inc(tags=_OUTCOME_TAGS["failed"])
                raise KVMigrationError(
                    mig_id, "pulled",
                    f"fallback ladder exhausted after {attempts} attempt(s): "
                    f"{exc!r}",
                ) from exc
            # decode replica owns its copies now: drop the staged set on
            # the prefill side (its pages already retired into the prefill
            # replica's prefix cache at export)
            self._release(p_index, mig_id, "adopted", tenant)
            self.migrations[rung] += 1
            metric_defs.LLM_KV_MIGRATIONS.inc(tags=_OUTCOME_TAGS[rung])
            metric_defs.LLM_KV_MIGRATION_SECONDS.observe(time.perf_counter() - t0)
            if isinstance(result, dict) and "_stream" in result:
                # streaming decode: hand the per-token event generator
                # straight to the proxy, like the homogeneous path does
                return result["_stream"]
            return result
        raise last_exc  # pragma: no cover — loop always returns or raises

    # ------------------------------------------------------------ replicas
    def _call(self, index: int, method: str, args: tuple, tenant, trace,
              timeout: Optional[float] = None):
        return self._router.call_replica(
            self._deployment, index, method, args, tenant, trace,
            timeout=timeout,
        )

    def _prefill(self, request: dict, mig_id: str, tenant,
                 trace) -> Tuple[int, dict]:
        index = self._router.pick_role_replica(
            self._deployment, ROLE_PREFILL, signal="queue"
        )
        self.dispatched[ROLE_PREFILL] += 1
        ticket = self._call(
            index, "disagg_prefill", (dict(request), mig_id), tenant, trace
        )
        if not isinstance(ticket, dict) or "mig_id" not in ticket:
            raise KVMigrationError(
                mig_id, "prefill-done",
                f"prefill replica returned no ticket: {type(ticket)}",
            )
        return index, ticket

    def _decode(self, request: dict, ticket: dict, tenant, trace):
        index = self._router.pick_role_replica(
            self._deployment, ROLE_DECODE, signal="kv_free"
        )
        self.dispatched[ROLE_DECODE] += 1
        out = self._call(
            index, "disagg_decode", (dict(request), ticket), tenant, trace
        )
        if isinstance(out, dict) and out.pop("_kv_migration_error", None):
            raise KVMigrationError(
                ticket["mig_id"], out.get("stage", "pulled"),
                out.get("message", "decode replica refused the migration"),
            )
        rung = "device"
        if isinstance(out, dict):
            rung = out.pop("_migration_rung", "device")
        return out, rung

    def _release(self, p_index: int, mig_id: str, outcome: str,
                 tenant) -> None:
        """Drop the staged block set on the prefill side — exactly once
        per migration, whatever the outcome.  Best-effort: if the prefill
        replica itself died, the transfer server's TTL reaps its offers
        and the process-global source registry entry dies with it."""
        try:
            self._call(p_index, "disagg_release", (mig_id,), tenant, None)
        except Exception:  # noqa: BLE001 — TTL reaps stragglers
            pass
        _record_audit({
            "mig_id": mig_id,
            "event": "released",
            "deployment": self._deployment,
            "outcome": outcome,
        })

    # --------------------------------------------------------------- stats
    def snapshot(self) -> dict:
        with self._lock:
            return {
                "dispatched": dict(self.dispatched),
                "migrations": dict(self.migrations),
            }
