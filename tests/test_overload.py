"""Overload survival (ISSUE 9): end-to-end admission control, load
shedding, and per-tenant fairness.

Covers every bounded layer's shed trigger (router queue, replica backstop,
LLM engine count + prefill-token budget, per-caller submission cap, the
scheduler's parked demand queue, the object store's bounded spill tier),
weighted fairness between competing tenants, expired-deadline
shed-on-arrival, proxy error->status mappings (429/503/504 + Retry-After),
the chaos ``overload`` schedule kind with invariant 11 and byte-identical
same-seed fault logs, and the /api/overload + ``rt overload`` surface.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import pytest

import ray_tpu
import ray_tpu as rt
from ray_tpu.exceptions import (
    DeadlineExceededError,
    OverloadedError,
    RayActorError,
    StoreFullError,
)

CFG_KW = dict(
    vocab_size=89, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=64,
    attention="dense", dtype=jnp.float32,
)


def _engine(**kw):
    from ray_tpu.models import TransformerConfig, init_params
    from ray_tpu.serve.llm import LLMEngine

    cfg = TransformerConfig(**CFG_KW)
    params = init_params(cfg, jax.random.key(11))
    return LLMEngine(cfg, params, max_seq_len=64, **kw)


def _wait_active(eng, n, timeout=60):
    deadline = time.time() + timeout
    while eng.stats()["active_slots"] < n and time.time() < deadline:
        time.sleep(0.005)
    assert eng.stats()["active_slots"] >= n, "request never admitted to a slot"


# --------------------------------------------------------------------------
# typed error shape
# --------------------------------------------------------------------------
def test_overloaded_error_is_typed_and_picklable():
    import pickle

    err = OverloadedError("router", "queue_full", 2.5)
    clone = pickle.loads(pickle.dumps(err))
    assert clone.layer == "router" and clone.reason == "queue_full"
    assert clone.retry_after_s == 2.5
    full = pickle.loads(pickle.dumps(StoreFullError(waited_s=1.5, needed=64)))
    assert full.waited_s == 1.5 and full.needed == 64
    # custom diagnostic detail survives the process/actor boundary
    detailed = OverloadedError("replica", "queue_full", 1.0,
                               "replica 'X#3' at its bound (4)")
    assert str(pickle.loads(pickle.dumps(detailed))) == str(detailed)


# --------------------------------------------------------------------------
# weighted fair queuing (the fairness kernel)
# --------------------------------------------------------------------------
def test_weighted_fair_queue_ratio():
    from ray_tpu.runtime.admission import WeightedFairQueue

    q = WeightedFairQueue({"a": 2.0, "b": 1.0})
    for i in range(30):
        q.push(("a", i), "a")
    for i in range(30):
        q.push(("b", i), "b")
    first = [q.pop()[0] for _ in range(15)]
    # stride scheduling: a gets ~2/3 of the pops while both queues are live
    assert first.count("a") == 10 and first.count("b") == 5
    # FIFO within each tenant
    a_items = [item for item in (q.pop() for _ in range(45)) if item[0] == "a"]
    assert [i for _, i in a_items] == sorted(i for _, i in a_items)


def test_weighted_fair_queue_hot_tenant_cannot_starve():
    from ray_tpu.runtime.admission import WeightedFairQueue

    q = WeightedFairQueue()
    for i in range(100):
        q.push(("hog", i), "hog")
    q.push(("quiet", 0), "quiet")  # late joiner starts at the live floor
    first = [q.pop()[0] for _ in range(3)]
    assert "quiet" in first  # admitted within a couple of pops, not after 100


def test_weighted_fair_queue_idle_tenant_not_starved_on_return():
    """A tenant that was busy, drained, and went idle must NOT be starved
    by its old vtime when it returns against a fresh tenant (the global
    virtual clock floors every empty-queue push)."""
    from ray_tpu.runtime.admission import WeightedFairQueue

    q = WeightedFairQueue({"a": 1.0, "b": 1.0})
    for i in range(100):
        q.push(("a", i), "a")
    while q.pop() is not None:  # a drains completely (vtime_a ~ 100)
        pass
    q.push(("b", 0), "b")  # fresh tenant
    for i in range(10):
        q.push(("a", i), "a")
    first4 = [q.pop()[0] for _ in range(4)]
    # equal weights: near-alternation, never 4 consecutive b-pops
    assert first4.count("a") >= 1, first4


def test_tenant_label_cardinality_bounded():
    from ray_tpu.runtime import admission

    labels = {admission.tenant_label(f"spam-{i}") for i in range(500)}
    assert "other" in labels
    assert len(labels) <= admission.MAX_TENANT_LABELS + 1
    # known ids keep their own series; None/"" collapse to default
    known = admission.tenant_label("spam-0")
    assert known in ("spam-0", "other")
    assert admission.tenant_label(None) == "default"


def test_weighted_fair_queue_prunes_adhoc_tenants():
    """Tenant ids are client-supplied: drained ad-hoc tenants must not
    accumulate in the overload-protection layer itself."""
    from ray_tpu.runtime.admission import WeightedFairQueue

    q = WeightedFairQueue({"configured": 3.0})
    for i in range(200):
        q.push(i, f"drive-by-{i}")
        assert q.pop() == i
    q.push(0, "configured")
    assert q.pop() == 0
    assert len(q._queues) <= 1 and len(q._vtime) <= 1  # only the configured one


# --------------------------------------------------------------------------
# LLM engine: count bound, token budget, deadline shed, fairness, disconnect
# --------------------------------------------------------------------------
def test_engine_queue_count_shed():
    eng = _engine(max_batch_size=1, max_queued_requests=2)
    try:
        # occupy the single slot with a long request, then fill the queue
        futs = [eng.submit([3, 1, 4], max_tokens=40)]
        _wait_active(eng, 1)
        futs += [eng.submit([3, 1, 4], max_tokens=2) for _ in range(2)]
        with pytest.raises(OverloadedError) as exc:
            eng.submit([3, 1, 4], max_tokens=2)
        assert exc.value.layer == "engine" and exc.value.reason == "queue_full"
        assert exc.value.retry_after_s > 0
        for f in futs:
            f.result(timeout=120)
        assert eng.stats()["shed"] >= 1
    finally:
        eng.shutdown()


def test_engine_prefill_token_budget_shed():
    eng = _engine(max_batch_size=1, max_queued_prefill_tokens=10)
    try:
        blocker = eng.submit([5] * 4, max_tokens=40)
        _wait_active(eng, 1)
        ok = eng.submit([5] * 8, max_tokens=2)  # 8 <= 10 queued tokens
        with pytest.raises(OverloadedError) as exc:
            eng.submit([5] * 8, max_tokens=2)  # 8 + 8 > 10
        assert exc.value.reason == "token_budget"
        blocker.result(timeout=120)
        ok.result(timeout=120)
    finally:
        eng.shutdown()


def test_engine_never_fitting_prompt_is_value_error_not_429():
    """A prompt that alone exceeds the prefill-token budget can never be
    admitted — retrying after the hint would loop forever, so it must be a
    ValueError (config/input error), not a retryable OverloadedError."""
    eng = _engine(max_batch_size=1, max_queued_prefill_tokens=10)
    try:
        with pytest.raises(ValueError, match="never be admitted"):
            eng.submit([5] * 11, max_tokens=2)
    finally:
        eng.shutdown()


def test_engine_expired_deadline_sheds_on_arrival():
    eng = _engine(max_batch_size=2)
    try:
        with pytest.raises(DeadlineExceededError):
            eng.submit([1, 2, 3], max_tokens=2, deadline_ts=time.time() - 1.0)
        assert eng.stats()["shed"] == 1
        assert eng.stats()["active_slots"] == 0  # never occupied a slot
    finally:
        eng.shutdown()


def test_engine_deadline_expired_while_queued_never_takes_slot():
    eng = _engine(max_batch_size=1)
    try:
        blocker = eng.submit([2, 7, 1], max_tokens=60)  # holds the only slot
        doomed = eng.submit([2, 7, 1], max_tokens=2,
                            deadline_ts=time.time() + 0.05)
        with pytest.raises(DeadlineExceededError):
            doomed.result(timeout=120)
        blocker.result(timeout=120)
        assert eng.stats()["shed"] >= 1
        # the shed request never reserved KV pages; the finished blocker's
        # pages are all accounted for by the prefix cache and a flush
        # drains the pool back to empty
        st = eng.stats()
        assert st["kv_blocks_in_use"] == st["prefix_cache_blocks"]
        eng.store.flush_prefix_cache()
        assert eng.stats()["kv_blocks_in_use"] == 0
    finally:
        eng.shutdown()


def test_engine_tenant_weighted_fairness():
    """Two competing tenants at weights 2:1 admit ~2:1 while both queues
    are backlogged (the admission order IS the completion order with one
    decode slot)."""
    eng = _engine(max_batch_size=1, tenant_weights={"a": 2.0, "b": 1.0})
    try:
        order = []
        blocker = eng.submit([9, 9], max_tokens=30)  # pin the slot first
        futs = []
        for i in range(6):
            fa = eng.submit([3, 1], max_tokens=1, tenant="a")
            fa.add_done_callback(lambda _f: order.append("a"))
            fb = eng.submit([3, 1], max_tokens=1, tenant="b")
            fb.add_done_callback(lambda _f: order.append("b"))
            futs += [fa, fb]
        blocker.result(timeout=120)
        for f in futs:
            f.result(timeout=120)
        first6 = order[:6]
        assert first6.count("a") == 4 and first6.count("b") == 2, order
    finally:
        eng.shutdown()


def test_engine_disconnected_stream_frees_slot():
    from ray_tpu.observability import metric_defs

    eng = _engine(max_batch_size=1)
    try:
        stream = eng.submit_stream([4, 2], max_tokens=50)
        got = [next(stream), next(stream)]
        assert len(got) == 2
        assert eng.stats()["active_slots"] == 1
        stream.close()  # consumer disconnects mid-generation
        deadline = time.time() + 30
        while eng.stats()["active_slots"] and time.time() < deadline:
            time.sleep(0.01)
        assert eng.stats()["active_slots"] == 0, "slot never evicted"
        assert eng.stats()["slots_evicted"] == 1
        assert eng.stats()["kv_blocks_in_use"] == 0  # evict freed its pages
        # the freed slot still serves new work
        assert len(eng.generate([4, 2], max_tokens=3)) == 3
    finally:
        eng.shutdown()


def test_engine_abandoned_queued_stream_never_admits():
    eng = _engine(max_batch_size=1)
    try:
        blocker = eng.submit([8, 8], max_tokens=40)
        _wait_active(eng, 1)
        stream = eng.submit_stream([1, 2], max_tokens=50)
        assert eng.stats()["queued"] == 1
        stream.close()  # gone before a slot ever freed
        # the queued entry's count + prefill tokens release IMMEDIATELY —
        # a burst of connect-then-disconnect clients must not hold the
        # bounded waiting queue against live traffic until slots free
        stats = eng.stats()
        assert stats["queued"] == 0 and stats["queued_prefill_tokens"] == 0
        assert stats["shed"] >= 1
        blocker.result(timeout=120)
        assert eng.stats()["active_slots"] == 0
        st = eng.stats()
        assert st["kv_blocks_in_use"] == st["prefix_cache_blocks"]
        eng.store.flush_prefix_cache()
        assert eng.stats()["kv_blocks_in_use"] == 0
    finally:
        eng.shutdown()


def test_engine_admission_snapshot_registered():
    from ray_tpu.runtime import admission

    eng = _engine(max_batch_size=2, max_queued_requests=7)
    try:
        snaps = [s for s in admission.sources_snapshot() if s.get("layer") == "engine"]
        assert snaps and snaps[-1]["queue_bound"] == 7
    finally:
        eng.shutdown()
    assert not [
        s for s in admission.sources_snapshot()
        if s.get("layer") == "engine" and s.get("queue_bound") == 7
    ]


# --------------------------------------------------------------------------
# core submission: per-caller in-flight cap (block and shed policies)
# --------------------------------------------------------------------------
def test_submission_cap_shed_policy():
    rt.init(num_cpus=2, _system_config={
        "max_inflight_tasks_per_caller": 3,
        "task_submit_overload_policy": "shed",
    })
    try:
        @rt.remote
        def hold():
            time.sleep(0.4)
            return 1

        refs, sheds = [], 0
        for _ in range(8):
            try:
                refs.append(hold.remote())
            except OverloadedError as exc:
                assert exc.layer == "submission" and exc.reason == "inflight_cap"
                sheds += 1
        assert len(refs) == 3 and sheds == 5
        assert rt.get(refs, timeout=60) == [1, 1, 1]
        # slots released on terminal commit: submission works again
        assert rt.get(hold.remote(), timeout=60) == 1
    finally:
        rt.shutdown()


def test_submission_cap_block_policy_waits_then_succeeds():
    rt.init(num_cpus=4, _system_config={
        "max_inflight_tasks_per_caller": 2,
        "task_submit_overload_policy": "block",
        "task_submit_block_timeout_s": 30.0,
    })
    try:
        @rt.remote
        def quick():
            time.sleep(0.1)
            return 1

        t0 = time.monotonic()
        refs = [quick.remote() for _ in range(6)]  # blocks at the cap
        assert time.monotonic() - t0 > 0.15  # at least two waves waited
        assert rt.get(refs, timeout=60) == [1] * 6
        gate = rt.get_cluster().core_worker.admission_gate.snapshot()
        assert gate["blocks"] >= 1 and gate["sheds"] == 0
    finally:
        rt.shutdown()


def test_submission_cap_block_timeout_sheds():
    rt.init(num_cpus=1, _system_config={
        "max_inflight_tasks_per_caller": 1,
        "task_submit_overload_policy": "block",
        "task_submit_block_timeout_s": 0.2,
    })
    try:
        @rt.remote
        def hold():
            time.sleep(2.0)
            return 1

        ref = hold.remote()
        with pytest.raises(OverloadedError) as exc:
            hold.remote()
        assert exc.value.reason == "block_timeout"
        assert rt.get(ref, timeout=60) == 1
    finally:
        rt.shutdown()


# --------------------------------------------------------------------------
# scheduler demand queue: bounded parking
# --------------------------------------------------------------------------
def test_demand_queue_bound_sheds_typed():
    rt.init(num_cpus=1, _system_config={
        "demand_queue_max_entries": 3,
        "infeasible_task_timeout_s": 2.0,
    })
    try:
        @rt.remote(num_cpus=8, max_retries=0)  # infeasible on a 1-CPU node
        def big():
            return 1

        refs = [big.remote() for _ in range(8)]
        outcomes = {"overloaded": 0, "infeasible": 0}
        for ref in refs:
            with pytest.raises(Exception) as exc:
                rt.get(ref, timeout=30)
            if isinstance(exc.value, OverloadedError):
                assert exc.value.layer == "demand_queue"
                assert exc.value.retry_after_s > 0
                outcomes["overloaded"] += 1
            else:
                outcomes["infeasible"] += 1
        # 3 parked (fail infeasible at the 2s deadline), 5 shed typed
        assert outcomes["overloaded"] == 5, outcomes
        assert outcomes["infeasible"] == 3, outcomes
        snap = rt.get_cluster().overload_snapshot()
        assert snap["shed_totals"]["demand_queue"]["queue_full"] >= 5
        assert snap["demand_queue"]["bound"] == 3
    finally:
        rt.shutdown()


def test_demand_queue_actor_creation_shed_is_typed():
    """A shed actor creation surfaces the typed OverloadedError (with its
    retry_after_s) to callers — not a generic ActorDiedError."""
    rt.init(num_cpus=1, _system_config={
        "demand_queue_max_entries": 1,
        "infeasible_task_timeout_s": 2.0,
    })
    try:
        @rt.remote(resources={"NO_SUCH_CHIP": 1})
        class Big:
            def ping(self):
                return "pong"

        actors = [Big.remote() for _ in range(3)]  # 1 parks, 2 shed
        errors = []
        for a in actors:
            with pytest.raises(Exception) as exc:
                rt.get(a.ping.remote(), timeout=30)
            errors.append(exc.value)
        overloaded = [e for e in errors if isinstance(e, OverloadedError)]
        assert len(overloaded) == 2, errors
        assert all(e.retry_after_s > 0 for e in overloaded)
    finally:
        rt.shutdown()


# --------------------------------------------------------------------------
# object store: bounded spill tier backpressure
# --------------------------------------------------------------------------
def test_store_full_backpressure_deadline_and_release():
    import hashlib

    import numpy as np

    from ray_tpu.core.config import Config, reset_config, set_config
    from ray_tpu.core.ids import ObjectID
    from ray_tpu.core.object_store import ObjectStore

    cfg = Config()
    cfg.object_store_max_disk_bytes = 1 << 20
    cfg.store_put_backpressure_timeout_s = 0.4
    set_config(cfg)
    try:
        store = ObjectStore(shm_store=None, hbm_budget=1 << 30, host_budget=1 << 20)

        def oid(i):
            return ObjectID(hashlib.blake2b(str(i).encode(), digest_size=24).digest())

        chunk = np.zeros(512 * 1024, np.uint8)
        for i in range(4):  # host (1M) + disk (1M) exactly full
            store.put(oid(i), chunk.copy())
        stats = store.stats()
        assert stats["disk_used"] == 1 << 20 and stats["spills"] >= 2

        # full store + nothing freed -> typed StoreFullError at the deadline
        t0 = time.monotonic()
        with pytest.raises(StoreFullError) as exc:
            store.put(oid(99), chunk.copy())
        assert time.monotonic() - t0 >= 0.35
        assert exc.value.waited_s > 0.3 and exc.value.needed == chunk.nbytes

        # a deletion mid-wait releases the backpressured put
        def free():
            time.sleep(0.1)
            store.delete(oid(0))
            store.delete(oid(1))

        threading.Thread(target=free, daemon=True).start()
        store.put(oid(100), chunk.copy())
        stats = store.stats()
        assert stats["puts_shed"] == 1 and stats["put_backpressure_waits"] >= 2

        # error tombstones bypass the gate even when full
        store.put_error(oid(101), RuntimeError("must always commit"))
        assert store.contains(oid(101))

        # overwriting a DISK-spilled entry frees its disk accounting and
        # file — a re-put producer must not inflate disk_used forever.
        # (The overwrite may trigger a fresh spill of another entry, so
        # assert the LEDGER matches the actual disk-tier entries, and the
        # old spill file is gone.)
        import os as _os

        spilled = [
            o for o in (oid(2), oid(3), oid(100))
            if (store.entry_info(o) or {}).get("tier") == "disk"
        ]
        assert spilled, "expected at least one disk-tier entry"
        with store._lock:
            old_path = store._entries[spilled[0]].disk_path
        store.put(spilled[0], np.zeros(16, np.uint8))  # tiny overwrite
        assert not _os.path.exists(old_path), "orphaned spill file"
        actual = sum(
            info["size"] for _o, info in store.list_entries()
            if info["tier"] == "disk"
        )
        assert store.stats()["disk_used"] == actual
    finally:
        reset_config()


def test_store_concurrent_admits_cannot_overshoot_budget():
    """The admission gate RESERVES bytes: two concurrent puts must not both
    claim the same last free room (check-then-commit race)."""
    import hashlib

    import numpy as np

    from ray_tpu.core.config import Config, reset_config, set_config
    from ray_tpu.core.ids import ObjectID
    from ray_tpu.core.object_store import ObjectStore

    cfg = Config()
    cfg.object_store_max_disk_bytes = 1 << 19  # host 512K + disk 512K = 1M
    cfg.store_put_backpressure_timeout_s = 0.3
    set_config(cfg)
    try:
        store = ObjectStore(shm_store=None, hbm_budget=1 << 30, host_budget=1 << 19)

        def oid(i):
            return ObjectID(hashlib.blake2b(str(i).encode(), digest_size=24).digest())

        nbytes = 1 << 20  # one admit reserves the WHOLE host+disk budget
        # first admit reserves; a second concurrent admit must
        # backpressure-then-shed even though nothing inserted yet
        assert store._admit_put(oid(0), nbytes) is True
        with pytest.raises(StoreFullError):
            store._admit_put(oid(1), nbytes)
        # releasing the reservation (what put()'s insert does) re-opens the gate
        with store._lock:
            store._pending_put_bytes -= nbytes
            store._space.notify_all()
        assert store._admit_put(oid(1), nbytes) is True
    finally:
        reset_config()


# --------------------------------------------------------------------------
# serve: router queue bound, replica backstop, idempotent replay gate
# --------------------------------------------------------------------------
def _serve_runtime():
    # replicas + the controller each hold a CPU: room for several apps
    rt.init(num_cpus=16)
    from ray_tpu import serve

    serve.start(http_port=0)
    return serve


def test_router_bounded_queue_sheds_and_recovers():
    serve = _serve_runtime()
    try:
        release = threading.Event()

        @serve.deployment(num_replicas=1, max_ongoing_requests=1,
                          max_queued_requests=1)
        class Gate:
            def __call__(self, x):
                release.wait(30)
                return x

        handle = serve.run(Gate.bind(), route_prefix=None)
        results = []
        threads = [
            threading.Thread(
                target=lambda i=i: results.append(handle.remote(i).result(timeout=30)),
                daemon=True,
            )
            for i in range(2)  # 1 ongoing + 1 queued
        ]
        for t in threads:
            t.start()
        deadline = time.time() + 10
        while handle._router._queue_waiters < 1 and time.time() < deadline:
            time.sleep(0.01)
        with pytest.raises(OverloadedError) as exc:  # 3rd: queue full
            handle.remote(99).result(timeout=10)
        assert exc.value.layer == "router" and exc.value.retry_after_s > 0
        release.set()
        for t in threads:
            t.join(timeout=30)
        assert sorted(results) == [0, 1]
        # capacity freed: admission works again
        assert handle.remote(7).result(timeout=30) == 7
    finally:
        serve.shutdown()
        rt.shutdown()


def test_replica_backstop_sheds_typed_through_handle():
    serve = _serve_runtime()
    try:
        from ray_tpu.serve.replica import ReplicaActor

        release = threading.Event()

        def slow(x):
            release.wait(30)
            return x

        replica = ReplicaActor.options(execution="inproc", max_concurrency=4).remote(
            slow, (), {}, None, True, max_ongoing_requests=1,
        )
        first = replica.handle_request.remote("__call__", (1,), {})
        time.sleep(0.2)  # the first call occupies the replica
        with pytest.raises(OverloadedError) as exc:
            # a stale router's direct dispatch past the cap: backstop sheds,
            # and the typed cause surfaces unwrapped at the caller
            try:
                ray_tpu.get(replica.handle_request.remote("__call__", (2,), {}))
            except Exception as raw:
                from ray_tpu.runtime.admission import unwrap

                raise unwrap(raw)
        assert exc.value.layer == "replica"
        release.set()
        assert ray_tpu.get(first, timeout=30) == 1
    finally:
        serve.shutdown()
        rt.shutdown()


def test_non_idempotent_deployment_never_replays():
    """The replica-death replay satellite: without idempotent=True the
    router surfaces the typed actor error instead of re-executing a
    possibly-side-effecting request."""
    serve = _serve_runtime()
    try:
        @serve.deployment(num_replicas=1)
        class Solo:
            def __call__(self, x):
                return x + 100

        handle = serve.run(Solo.bind(), route_prefix=None)
        assert handle.remote(1).result(timeout=30) == 101
        from ray_tpu.serve import api as serve_api

        _v, replicas = ray_tpu.get(serve_api._controller.get_replicas.remote("Solo"))
        ray_tpu.kill(replicas[0])
        with pytest.raises(RayActorError):
            handle.remote(7).result(timeout=30)
    finally:
        serve.shutdown()
        rt.shutdown()


# --------------------------------------------------------------------------
# proxy: error -> HTTP status contract (429 + Retry-After / 503 / 504)
# --------------------------------------------------------------------------
def _http(url, body=None, headers=None):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode() if body is not None else None,
        headers=headers or {},
    )
    try:
        with urllib.request.urlopen(req, timeout=30) as resp:
            return resp.status, dict(resp.headers), resp.read()
    except urllib.error.HTTPError as err:
        return err.code, dict(err.headers), err.read()


def test_proxy_status_mappings():
    serve = _serve_runtime()
    try:
        @serve.deployment
        def overloaded(_x):
            raise OverloadedError("engine", "queue_full", 3.0)

        @serve.deployment
        def too_late(_x):
            raise DeadlineExceededError("req", "executing", 1.0)

        @serve.deployment
        def dead_actor(_x):
            from ray_tpu.exceptions import ActorDiedError

            raise ActorDiedError(None, "replica died after retry budget")

        @serve.deployment
        def boom(_x):
            raise ValueError("application bug")

        serve.run(overloaded.bind(), name="overloaded", route_prefix="/overloaded")
        serve.run(too_late.bind(), name="late", route_prefix="/late")
        serve.run(dead_actor.bind(), name="dead", route_prefix="/dead")
        serve.run(boom.bind(), name="boom", route_prefix="/boom")
        base = serve.proxy_url()

        status, headers, body = _http(base + "/overloaded", {"x": 1})
        payload = json.loads(body)
        assert status == 429
        assert headers.get("Retry-After") == "3"
        assert payload["retry_after_s"] == 3.0
        assert payload["type"] == "OverloadedError"

        status, _h, body = _http(base + "/late", {"x": 1})
        assert status == 504
        assert json.loads(body)["type"] == "DeadlineExceededError"

        status, _h, body = _http(base + "/dead", {"x": 1})
        assert status == 503
        assert json.loads(body)["type"] == "ActorDiedError"

        status, _h, _b = _http(base + "/boom", {"x": 1})
        assert status == 500
    finally:
        serve.shutdown()
        rt.shutdown()


def test_proxy_request_timeout_maps_to_504():
    serve = _serve_runtime()
    try:
        from ray_tpu.serve import api as serve_api

        serve_api._proxy.request_timeout_s = 0.3

        @serve.deployment
        def glacial(_x):
            time.sleep(5)
            return "done"

        serve.run(glacial.bind(), route_prefix="/slow")
        status, _h, _b = _http(serve.proxy_url() + "/slow", {"x": 1})
        assert status == 504
    finally:
        serve.shutdown()
        rt.shutdown()


def test_proxy_tenant_header_rides_to_engine_context():
    serve = _serve_runtime()
    try:
        seen = []

        @serve.deployment
        def who(_x):
            from ray_tpu.runtime.context import current_tenant

            seen.append(current_tenant())
            return {"tenant": current_tenant()}

        serve.run(who.bind(), route_prefix="/who")
        status, _h, body = _http(
            serve.proxy_url() + "/who", {"x": 1},
            headers={"X-Tenant-Id": "team-42", "Content-Type": "application/json"},
        )
        assert status == 200
        assert json.loads(body)["tenant"] == "team-42"
        assert seen == ["team-42"]
    finally:
        serve.shutdown()
        rt.shutdown()


def test_grpc_overload_maps_to_resource_exhausted():
    grpc = pytest.importorskip("grpc")
    serve = _serve_runtime()
    try:
        from ray_tpu.serve import api as serve_api

        # open the gRPC ingress alongside the running controller
        serve_api._grpc_proxy = None
        serve.start(grpc_port=0)

        @serve.deployment
        def overloaded(_x):
            raise OverloadedError("engine", "queue_full", 2.0)

        serve.run(overloaded.bind(), name="default", route_prefix=None)
        channel = grpc.insecure_channel(serve.grpc_address())
        predict = channel.unary_unary("/ray_tpu.serve.Serve/Predict")
        with pytest.raises(grpc.RpcError) as exc:
            predict(json.dumps({"x": 1}).encode(), timeout=30)
        assert exc.value.code() == grpc.StatusCode.RESOURCE_EXHAUSTED
        assert "retry_after_s=2" in exc.value.details()
        channel.close()
    finally:
        serve.shutdown()
        rt.shutdown()


# --------------------------------------------------------------------------
# chaos: the `overload` schedule kind + invariant 11
# --------------------------------------------------------------------------
def _run_overload_schedule():
    from ray_tpu.chaos import ChaosRunner, ChaosSchedule

    rt.init(num_cpus=1, _system_config={
        "demand_queue_max_entries": 8,
        "infeasible_task_timeout_s": 2.0,
    })
    try:
        sched = ChaosSchedule.from_dict({
            "seed": 7,
            "events": [
                {"t": 0.0, "kind": "arm", "spec": "scheduler.dispatch=delay(0.001,0.2)"},
                {"t": 0.05, "kind": "overload", "tasks": 24, "cpus": 4},
                {"t": 0.1, "kind": "overload", "tasks": 16, "cpus": 1, "hold_s": 0.02},
            ],
        })

        def workload():
            @rt.remote(max_retries=2)
            def bump(x):
                return x + 1

            return [bump.remote(i) for i in range(30)]

        result = ChaosRunner(sched, quiesce_timeout=60).run(workload)
        return result
    finally:
        rt.shutdown()


def test_chaos_overload_schedule_invariant_11_and_determinism():
    first = _run_overload_schedule()
    assert first.ok, (first.invariants.violations, first.workload_error)
    # the bounded demand queue shed the infeasible burst's overflow, every
    # shed typed + audited, no shed task executed (invariant 11)
    assert first.invariants.checked["overload_sheds"] >= 8
    injected = [e for e in first.events_applied if e["kind"] == "overload"]
    assert injected and all("submitted" in e for e in injected)

    second = _run_overload_schedule()
    assert second.ok, second.invariants.violations
    assert first.same_faults(second), "same-seed fault logs diverged"
    assert len(first.faults) > 0  # the armed failpoint actually decided


def test_chaos_validate_overload_kind(tmp_path):
    from ray_tpu.chaos.schedule import validate_schedule

    ok = {"seed": 1, "events": [
        {"t": 0.0, "kind": "overload", "tasks": 10, "cpus": 2, "hold_s": 0.1},
    ]}
    assert validate_schedule(ok) == []
    bad = {"seed": 1, "events": [
        {"t": 0.0, "kind": "overload", "tasks": 0, "cpus": -1, "hold_s": -2,
         "bogus": 1},
    ]}
    errors = validate_schedule(bad)
    assert len(errors) == 4, errors

    # CLI round trip
    from ray_tpu.scripts.cli import main

    path = tmp_path / "overload.json"
    path.write_text(json.dumps(ok))
    assert main(["chaos", "validate", str(path)]) == 0


# --------------------------------------------------------------------------
# observability: /api/overload + `rt overload`
# --------------------------------------------------------------------------
def test_api_overload_and_cli_smoke(capsys):
    from ray_tpu.scripts.cli import main

    rt.init(
        num_cpus=1,
        include_dashboard=True,
        _system_config={
            "max_inflight_tasks_per_caller": 2,
            "task_submit_overload_policy": "shed",
        },
    )
    try:
        url = rt.get_cluster().dashboard.url

        @rt.remote
        def hold():
            time.sleep(0.3)
            return 1

        refs, sheds = [], 0
        for _ in range(5):
            try:
                refs.append(hold.remote())
            except OverloadedError:
                sheds += 1
        assert sheds >= 1
        rt.get(refs, timeout=60)

        assert main(["overload", "--address", url, "--format", "json"]) == 0
        data = json.loads(capsys.readouterr().out)
        assert data["shed_totals"]["submission"]["inflight_cap"] >= 1
        assert data["submission"]["cap"] == 2
        assert data["demand_queue"]["bound"] > 0
        assert data["events_total"] >= 1

        assert main(["overload", "--address", url]) == 0
        out = capsys.readouterr().out
        assert "sheds:" in out and "submission gate" in out
    finally:
        rt.shutdown()


def test_new_metric_families_registered():
    from ray_tpu.observability import metric_defs

    names = {m.name for m in metric_defs.ALL_METRICS}
    for family in (
        "requests_shed_total",
        "admission_queue_depth",
        "tenant_admissions_total",
        "store_put_backpressure_seconds",
        "llm_slots_evicted_total",
    ):
        assert family in names
