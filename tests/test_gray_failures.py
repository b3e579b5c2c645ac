"""Gray-failure survival (ISSUE 8): incarnation fencing, end-to-end
deadlines, hedged straggler retries.

Fail-stop faults (PRs 2/6/7) die loudly; gray faults fail SLOW and SPLIT —
a partitioned-but-alive agent outliving its death declaration, a straggler
holding a tail latency hostage, a call with no time bound.  These tests
drive each defense end to end:

* raw-socket stale-incarnation frame injection — location commits, task
  results, heartbeats, push results from a superseded epoch are rejected,
  counted (``fenced_frames_total``), and answered with a typed ``fenced``
  notice,
* a real fenced agent self-fences and rejoins as a FRESH node that serves
  new work,
* ``.options(deadline_s=...)`` fires at all four lifecycle stages (parked /
  queued / pulling / executing) within the grace budget and never retries,
* ``.options(hedge_after_s=...)`` launches the second attempt on a
  different node; first commit wins, the loser is cancelled and its late
  commit discarded by attempt fencing,
* the memory monitor killing a lease-pinned warm worker unpins and
  re-grants (ISSUE 8 satellite),
* ``rpc.request`` timeouts are typed ``ControlPlaneTimeout`` and the shared
  backoff helper retries them deterministically.
"""

import os
import threading
import time

import pytest

import ray_tpu as rt
from ray_tpu import api
from ray_tpu.core.config import get_config
from ray_tpu.core.ids import NodeID, ObjectID, TaskID
from ray_tpu.exceptions import DeadlineExceededError
from ray_tpu.observability import metric_defs
from ray_tpu.runtime import rpc
from ray_tpu.runtime.scheduler import NodeAffinitySchedulingStrategy


# ==========================================================================
# incarnation fencing
# ==========================================================================
def _register_fake_agent(address, node_id_bin, rejoin=False, fenced_box=None):
    """Speak the agent registration protocol over a raw rpc connection."""
    handlers = {}
    if fenced_box is not None:
        handlers["fenced"] = lambda c, p: fenced_box.append(p)
    # unsolicited one-ways the head may send (peer_fenced, shutdown) are
    # dropped by the dispatch loop's no-handler error print; register no-ops
    for msg in ("peer_fenced", "shutdown", "pool_update"):
        handlers.setdefault(msg, lambda c, p, rid=None: None)
    conn = rpc.connect(address, handlers=handlers, name="fake-agent")
    conn.request("register_node_config", {})
    payload = {
        "node_id": node_id_bin,
        "resources": {"CPU": 1},
        "labels": {},
        "address": "fake",
        "data_address": None,
    }
    if rejoin:
        payload["rejoin"] = True
        payload["actors"] = []
    reply = conn.request("register_node", payload)
    return conn, reply


def test_stale_incarnation_frames_fenced(ray_start_regular):
    """Raw-socket frame injection: a superseded incarnation's location
    commits, task results, and heartbeats are all rejected and logged."""
    cluster = api.get_cluster()
    address = cluster.start_head_service()
    node_id = NodeID.from_random()
    fenced_a: list = []

    conn_a, reply_a = _register_fake_agent(
        address, node_id.binary(), fenced_box=fenced_a
    )
    assert reply_a["incarnation"] == 1
    handle_a = cluster.nodes[node_id]

    # the same node id re-registers (partition-heal race: the rejoin beat
    # the death sweep): a NEW incarnation supersedes the old epoch
    conn_b, reply_b = _register_fake_agent(address, node_id.binary(), rejoin=True)
    assert reply_b["incarnation"] == 2
    assert cluster.control.nodes.incarnation_of(node_id) == 2
    assert handle_a.dead, "superseded handle must be fenced"
    assert cluster.nodes[node_id] is not handle_a

    base = {
        kind: metric_defs.FENCED_FRAMES.get(tags={"kind": kind})
        for kind in ("object_location", "task_finished", "resource_report")
    }
    oid = ObjectID.from_random()

    # 1. stale location commit (batched form)
    conn_a.send("object_locations", {"locs": [(oid.binary(), 128, False)], "inc": 1})
    # 2. stale task result
    conn_a.send(
        "task_finished",
        {"task_id": TaskID.from_random().binary(), "value": rpc.encode_value(1),
         "error": None, "inc": 1},
    )
    # 3. stale heartbeat (must not refresh the new epoch's liveness)
    conn_a.send(
        "resource_report",
        {"total": {}, "available": {}, "queue_len": 0, "stats": {}, "inc": 1},
    )

    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        if (
            metric_defs.FENCED_FRAMES.get(tags={"kind": "object_location"}) > base["object_location"]
            and metric_defs.FENCED_FRAMES.get(tags={"kind": "task_finished"}) > base["task_finished"]
            and metric_defs.FENCED_FRAMES.get(tags={"kind": "resource_report"}) > base["resource_report"]
        ):
            break
        time.sleep(0.02)
    for kind in base:
        assert metric_defs.FENCED_FRAMES.get(tags={"kind": kind}) > base[kind], kind
    # the stale commit never touched the directory
    assert not cluster.directory.locations(oid)
    # the sender was told, with the kind that tripped the fence
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline and len(fenced_a) < 3:
        time.sleep(0.02)
    assert len(fenced_a) >= 3
    assert {p["kind"] for p in fenced_a} >= {
        "object_location", "task_finished", "resource_report"
    }
    # audit log captured every rejection
    kinds = [fe["kind"] for fe in cluster.fence_events]
    assert "object_location" in kinds and "task_finished" in kinds

    conn_a.close()
    conn_b.close()


def test_stale_push_result_fenced(ray_start_regular):
    """A data-plane push_task result stamped with a superseded incarnation
    is discarded by the owner (attempt fencing keeps the resubmitted
    attempt's result the only one visible)."""
    from ray_tpu.runtime.scheduler import TaskSpec
    from ray_tpu.core.resources import ResourceSet

    cluster = api.get_cluster()
    address = cluster.start_head_service()
    node_id = NodeID.from_random()
    conn_a, _ = _register_fake_agent(address, node_id.binary())
    handle = cluster.nodes[node_id]

    task_id = TaskID.from_random()
    spec = TaskSpec(
        task_id=task_id, name="t", func=None, args=(), kwargs={},
        dependencies=[], num_returns=1,
        return_ids=[ObjectID.for_task_return(task_id, 1)],
        resources=ResourceSet({"CPU": 1}),
    )
    handle._track(spec)
    base = metric_defs.FENCED_FRAMES.get(tags={"kind": "push_result"})
    # supersede the incarnation, then deliver a push result from epoch 1
    conn_b, reply_b = _register_fake_agent(address, node_id.binary(), rejoin=True)
    assert reply_b["incarnation"] == 2
    handle._on_push_reply(spec, {"ok": True, "src": (node_id.hex(), 1)}, 42)
    assert metric_defs.FENCED_FRAMES.get(tags={"kind": "push_result"}) == base + 1
    # the stale result did NOT commit: no terminal record, value not stored
    assert not cluster.head_node.store.contains(spec.return_ids[0])
    # the in-flight spec was adopted by the superseding incarnation's
    # handle (rejoin migration): it is NOT resolved by the stale reply
    assert cluster.nodes[node_id]._lookup(task_id.binary()) is spec
    conn_a.close()
    conn_b.close()


def test_fenced_rejoin_refused_after_death_declaration(ray_start_regular):
    """A rejoin attempt for a node id the death sweep already processed is
    answered ``fenced`` — the agent must join as a fresh node instead."""
    cluster = api.get_cluster()
    address = cluster.start_head_service()
    node_id = NodeID.from_random()
    conn_a, _ = _register_fake_agent(address, node_id.binary())
    handle = cluster.nodes[node_id]
    # break the notification channel first: a gray partition's victim never
    # hears its own death declaration
    handle.conn.close()
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and not handle.dead:
        time.sleep(0.02)
    assert handle.dead

    conn_b, reply = _register_fake_agent(address, node_id.binary(), rejoin=True)
    assert reply.get("fenced") is True
    # a FRESH node id (the self-fence path) is accepted and counted
    rejoins = metric_defs.NODE_REJOINS.get()
    fresh_id = NodeID.from_random()
    conn_c = rpc.connect(address, handlers={}, name="fake-agent")
    conn_c.request("register_node_config", {})
    reply = conn_c.request(
        "register_node",
        {"node_id": fresh_id.binary(), "resources": {"CPU": 1}, "labels": {},
         "address": "fake", "data_address": None, "refenced": True},
    )
    assert reply["incarnation"] == 1
    assert metric_defs.NODE_REJOINS.get() == rejoins + 1
    conn_b.close()
    conn_c.close()


def _spawn_agent(address):
    import subprocess
    import sys

    env = dict(os.environ)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    log_dir = "/tmp/rt_agent_logs"
    os.makedirs(log_dir, exist_ok=True)
    log = open(os.path.join(log_dir, f"gray_agent_{os.getpid()}_{time.monotonic_ns()}.log"), "w")
    try:
        return subprocess.Popen(
            [sys.executable, "-m", "ray_tpu.runtime.agent", "--address", address,
             "--num-cpus", "2", "--resources", '{"remote": 4}'],
            env=env, stdout=subprocess.DEVNULL, stderr=log,
        )
    finally:
        log.close()


def test_fenced_agent_self_fences_and_serves_new_work():
    """End to end with a REAL agent process: partition it past the death
    declaration (the head kills it without the shutdown notice arriving),
    heal — the agent learns it is fenced, self-fences, rejoins as a fresh
    node, and runs new tasks."""
    rt.init(num_cpus=2)
    proc = None
    try:
        cluster = rt.get_cluster()
        address = cluster.start_head_service()
        proc = _spawn_agent(address)
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline:
            remote = [
                n for n in cluster.nodes.values()
                if not n.dead and hasattr(n, "conn")
            ]
            if remote:
                break
            time.sleep(0.05)
        else:
            raise TimeoutError("agent never joined")
        handle = remote[0]
        old_id = handle.node_id

        # gray partition: the head declares the node dead, but the shutdown
        # notice cannot reach it (we sever the send side first) — the agent
        # runtime stays alive, exactly like a real partition victim
        def broken_send(*a, **k):
            raise rpc.RpcError("partitioned")

        handle.conn.send = broken_send
        cluster.kill_node(old_id, reason="test gray partition")
        assert handle.dead
        handle.conn.close()  # heal trigger: the agent reconnects...

        # ...is told it is fenced, self-fences, and rejoins as a FRESH node
        deadline = time.monotonic() + 90
        fresh = None
        while time.monotonic() < deadline:
            fresh = next(
                (
                    n for n in cluster.nodes.values()
                    if not n.dead and hasattr(n, "conn") and n.node_id != old_id
                ),
                None,
            )
            if fresh is not None:
                break
            time.sleep(0.05)
        assert fresh is not None, "fenced agent never rejoined as a fresh node"
        assert fresh.incarnation == 1  # fresh node id, first incarnation
        assert cluster.control.nodes.get(old_id).state.value == "DEAD"

        # the rejoined node serves new work
        @rt.remote(resources={"remote": 1})
        def on_remote(x):
            return x * 3

        assert rt.get([on_remote.remote(i) for i in range(6)], timeout=60) == [
            i * 3 for i in range(6)
        ]
    finally:
        if proc is not None and proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)
        rt.shutdown()


# ==========================================================================
# end-to-end deadlines
# ==========================================================================
@pytest.fixture
def fast_grace():
    cfg = get_config()
    old = cfg.task_deadline_grace_s
    cfg.task_deadline_grace_s = 0.4
    yield cfg
    cfg.task_deadline_grace_s = old


def test_deadline_parked(ray_start_regular, fast_grace):
    @rt.remote(num_cpus=512)
    def infeasible():
        return 1

    t0 = time.monotonic()
    ref = infeasible.options(deadline_s=0.3).remote()
    with pytest.raises(DeadlineExceededError) as ei:
        rt.get(ref, timeout=10)
    assert ei.value.stage == "parked"
    assert time.monotonic() - t0 < 2.0  # well before infeasible_task_timeout_s
    assert metric_defs.TASK_DEADLINE_EXCEEDED.get(tags={"stage": "parked"}) >= 1


def test_deadline_queued(ray_start_regular, fast_grace):
    sem = threading.Event()

    @rt.remote(num_cpus=4, execution="process")
    def hog():
        time.sleep(3)

    @rt.remote(num_cpus=4)
    def target():
        return 1

    blocker = hog.remote()
    time.sleep(0.15)  # let the hog acquire all CPUs
    t0 = time.monotonic()
    ref = target.options(deadline_s=0.3).remote()
    with pytest.raises(DeadlineExceededError) as ei:
        rt.get(ref, timeout=10)
    elapsed = time.monotonic() - t0
    assert ei.value.stage == "queued"
    # fires at the deadline + at most ~a grace of slack, NOT when the hog
    # finally frees the CPUs at t+3s
    assert elapsed < 0.3 + 2 * get_config().task_deadline_grace_s + 1.0
    assert metric_defs.TASK_DEADLINE_EXCEEDED.get(tags={"stage": "queued"}) >= 1
    sem.set()


def test_deadline_pulling(ray_start_regular, fast_grace):
    @rt.remote(execution="process")
    def producer():
        time.sleep(5)
        return 7

    @rt.remote
    def consumer(x):
        return x

    dep = producer.remote()
    t0 = time.monotonic()
    ref = consumer.options(deadline_s=0.3).remote(dep)
    with pytest.raises(DeadlineExceededError) as ei:
        rt.get(ref, timeout=10)
    elapsed = time.monotonic() - t0
    assert ei.value.stage == "pulling"
    assert elapsed < 2.0  # fired at the deadline, not when the dep landed
    assert metric_defs.TASK_DEADLINE_EXCEEDED.get(tags={"stage": "pulling"}) >= 1


def test_deadline_executing_force_kills_within_grace(ray_start_regular, fast_grace):
    @rt.remote(execution="process", max_retries=5)
    def stuck():
        time.sleep(60)

    t0 = time.monotonic()
    ref = stuck.options(deadline_s=0.3).remote()
    with pytest.raises(DeadlineExceededError) as ei:
        rt.get(ref, timeout=20)
    elapsed = time.monotonic() - t0
    assert ei.value.stage == "executing"
    grace = get_config().task_deadline_grace_s
    # cooperative window + force-kill + commit, with CI slack
    assert elapsed < 0.3 + 2 * grace + 3.0, elapsed
    assert metric_defs.TASK_DEADLINE_EXCEEDED.get(tags={"stage": "executing"}) >= 1


def test_deadline_never_retries(ray_start_regular, fast_grace):
    """max_retries is irrelevant to a deadline failure: one attempt, one
    terminal record, no retry spans burned."""
    cluster = api.get_cluster()
    before = cluster.task_manager.num_retries

    @rt.remote(execution="process", max_retries=5, retry_exceptions=True)
    def stuck():
        time.sleep(60)

    with pytest.raises(DeadlineExceededError):
        rt.get(stuck.options(deadline_s=0.2).remote(), timeout=20)
    time.sleep(0.3)
    assert cluster.task_manager.num_retries == before


def test_deadline_nested_budget_propagates(ray_start_regular, fast_grace):
    """A nested call inherits the parent's REMAINING budget: the deadline
    installed around the parent's execution rides into the child's spec."""

    @rt.remote(execution="process")
    def child():
        from ray_tpu.runtime.context import current_deadline_ts

        # the deadline context worker_main installed for THIS (child) task
        # is the budget inherited from the parent
        return current_deadline_ts()

    @rt.remote(execution="process")
    def parent():
        # no explicit child deadline: inheritance must supply one
        return rt.get(child.remote(), timeout=25)

    t0 = time.time()
    child_deadline = rt.get(parent.options(deadline_s=30.0).remote(), timeout=30)
    assert child_deadline is not None, "child inherited no deadline"
    # the child's installed deadline IS (parent submit + 30s), within slack
    assert abs(child_deadline - (t0 + 30.0)) < 5.0

    # and a short parent budget genuinely bounds a stuck child: the child's
    # inherited deadline fires owner-side even though the child set none
    @rt.remote(execution="process")
    def stuck_child():
        time.sleep(60)

    @rt.remote(execution="process")
    def impatient_parent():
        try:
            rt.get(stuck_child.remote(), timeout=50)
            return "no-deadline"
        except DeadlineExceededError as exc:
            return f"child-deadline:{exc.stage}"

    t0 = time.monotonic()
    try:
        result = rt.get(impatient_parent.options(deadline_s=1.0).remote(), timeout=30)
        assert result.startswith("child-deadline:"), result
    except DeadlineExceededError:
        pass  # the parent's own reap won the race — equally bounded
    assert time.monotonic() - t0 < 10.0


# ==========================================================================
# hedged straggler retries
# ==========================================================================
def _two_node_cluster(cluster):
    node_b = cluster.add_node({"CPU": 1})
    return cluster.head_node, node_b


def test_hedge_beats_slow_node(ray_start_cluster):
    """Primary lands on a delay-armed slow node; the hedge launches on the
    other node, wins, and the loser's late commit is discarded — exactly
    one terminal record per (task_id, attempt)."""
    _rt, _cluster = ray_start_cluster
    cluster = api.get_cluster()
    node_a, node_b = cluster.head_node, cluster.add_node({"CPU": 1})
    node_a._chaos_delay_s = 2.5  # deterministic straggler

    @rt.remote(max_retries=3)
    def quick():
        return 11

    # occupy B so the primary deterministically lands on slow A
    @rt.remote(max_retries=0, scheduling_strategy=NodeAffinitySchedulingStrategy(node_b.node_id))
    def blocker():
        time.sleep(0.4)

    b_ref = blocker.remote()
    time.sleep(0.1)
    wd = cluster.watchdog
    won0, events0 = wd.hedges_won, len(cluster.control.task_events)
    t0 = time.monotonic()
    ref = quick.options(hedge_after_s=0.25).remote()
    assert rt.get(ref, timeout=15) == 11
    elapsed = time.monotonic() - t0
    assert elapsed < 2.0, f"hedge never won ({elapsed:.2f}s)"
    assert wd.hedges_won == won0 + 1
    assert metric_defs.TASK_HEDGES.get(tags={"outcome": "won"}) >= 1
    rt.get(b_ref, timeout=10)

    # the loser (still sleeping through the chaos delay) gets cancelled and
    # its commit discarded — zero duplicate terminal records
    deadline = time.monotonic() + 6
    while time.monotonic() < deadline and wd.hedge_discards == 0:
        time.sleep(0.05)
    assert wd.hedge_discards >= 1
    terminal = {}
    for ev in cluster.control.task_events.list_events():
        if ev.get("state") in ("FINISHED", "FAILED"):
            key = (ev["task_id"], ev.get("attempt"))
            terminal[key] = terminal.get(key, 0) + 1
    assert all(n == 1 for n in terminal.values()), terminal
    node_a._chaos_delay_s = 0.0


def test_hedge_lost_when_primary_wins(ray_start_cluster):
    """The hedge lands on a node slower than the primary: the primary
    commits first and the hedge is the (cancelled, discarded) loser."""
    _rt, _cluster = ray_start_cluster
    cluster = api.get_cluster()
    node_b = cluster.add_node({"CPU": 1})
    node_b._chaos_delay_s = 3.0  # the hedge's destination is the straggler
    wd = cluster.watchdog
    lost0 = wd.hedges_lost

    # occupy B briefly so the primary deterministically lands on the head
    @rt.remote(max_retries=0, scheduling_strategy=NodeAffinitySchedulingStrategy(node_b.node_id))
    def blocker():
        time.sleep(0.3)

    b_ref = blocker.remote()
    time.sleep(0.1)

    @rt.remote(execution="process", max_retries=3)
    def modest():
        time.sleep(0.6)
        return 5

    assert rt.get(modest.options(hedge_after_s=0.15).remote(), timeout=15) == 5
    assert wd.hedges_launched >= 1
    deadline = time.monotonic() + 6
    while time.monotonic() < deadline and wd.hedges_lost == lost0:
        time.sleep(0.05)
    assert wd.hedges_lost >= lost0 + 1
    assert metric_defs.TASK_HEDGES.get(tags={"outcome": "lost"}) >= 1
    node_b._chaos_delay_s = 0.0


def test_hedge_requires_alternative_node(ray_start_regular):
    """Single node: the hedge cannot launch (no different node) and the
    primary still completes normally."""
    cluster = api.get_cluster()
    wd = cluster.watchdog
    launched0 = wd.hedges_launched

    @rt.remote(execution="process", max_retries=3)
    def solo():
        time.sleep(0.4)
        return 9

    assert rt.get(solo.options(hedge_after_s=0.1).remote(), timeout=15) == 9
    assert wd.hedges_launched == launched0


def test_hedge_auto_ewma_mode(ray_start_cluster):
    """Opt-in auto mode: once the per-shape latency EWMA settles, a
    straggler past ewma * multiplier hedges without an explicit option."""
    _rt, _cluster = ray_start_cluster
    cluster = api.get_cluster()
    node_b = cluster.add_node({"CPU": 1})
    cfg = get_config()
    old = (cfg.hedge_auto_enabled, cfg.hedge_auto_min_samples, cfg.hedge_auto_min_s)
    cfg.hedge_auto_enabled = True
    cfg.hedge_auto_min_samples = 5
    cfg.hedge_auto_min_s = 0.05
    try:
        cluster.watchdog.auto_on = True

        @rt.remote(max_retries=3)
        def shape():
            return os.getpid()

        # settle the EWMA on the fast shape — SEQUENTIALLY, so queue wait
        # doesn't inflate the observed latency
        for _ in range(8):
            rt.get(shape.remote(), timeout=30)
        wd = cluster.watchdog
        assert wd._ewma, "EWMA never fed"
        launched0 = wd.hedges_launched
        # every node becomes a straggler: wherever the primary lands it
        # outlives ewma * multiplier, so the auto mode MUST hedge it (the
        # hedge is equally slow — this tests the trigger, not the rescue)
        for node in cluster.nodes.values():
            node._chaos_delay_s = 2.0
        t0 = time.monotonic()
        assert isinstance(rt.get(shape.remote(), timeout=20), int)
        elapsed = time.monotonic() - t0
        assert wd.hedges_launched >= launched0 + 1, "auto mode never hedged"
        assert elapsed < 6.0
        # terminal-exactly-once held across the racing attempts
        terminal = {}
        for ev in cluster.control.task_events.list_events():
            if ev.get("state") in ("FINISHED", "FAILED"):
                key = (ev["task_id"], ev.get("attempt"))
                terminal[key] = terminal.get(key, 0) + 1
        assert all(n == 1 for n in terminal.values()), terminal
    finally:
        for node in cluster.nodes.values():
            node._chaos_delay_s = 0.0
        cfg.hedge_auto_enabled, cfg.hedge_auto_min_samples, cfg.hedge_auto_min_s = old
        cluster.watchdog.auto_on = old[0]


# ==========================================================================
# memory-kill / lease interaction (ISSUE 8 satellite)
# ==========================================================================
def test_memory_kill_unpins_leased_worker(ray_start_regular):
    """RetriableFIFOPolicy killing a lease-pinned warm worker must unpin it
    and the retried task must re-grant onto a live worker."""
    from ray_tpu.runtime.memory_monitor import MemoryMonitor

    cluster = api.get_cluster()
    node = cluster.head_node
    release = threading.Event()

    @rt.remote(execution="process", max_retries=2)
    def leased_sleep(marker):
        import time as _t

        _t.sleep(0.8 if marker == 0 else 0.0)
        return os.getpid()

    # prime the lease: repeat fast dispatches until one lands on an idle
    # worker and pins it (the first may race the async prestart)
    pool = node.worker_pool
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline and not pool._lease_pins:
        rt.get(leased_sleep.remote(1), timeout=30)
        time.sleep(0.05)
    assert pool._lease_pins, "leased dispatch never pinned a warm worker"
    pinned = next(iter(pool._lease_pins.values()))

    # a long leased task occupies the pinned worker; the memory monitor
    # (fed a fake 99% reading) must select and kill it through the normal
    # candidate path — node.kill_candidates -> RetriableFIFOPolicy
    ref = leased_sleep.remote(0)
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and not node.worker_pool.inflight_tasks():
        time.sleep(0.02)
    monitor = MemoryMonitor(
        node.kill_candidates,
        usage_threshold=0.9,
        memory_fn=lambda: (99, 100),
        min_kill_interval_s=0.0,
    )
    assert monitor.check_once(), "monitor never killed the leased task"
    # the kill unpinned the dead worker
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and pinned in pool._lease_pins.values():
        time.sleep(0.02)
    assert pinned not in pool._lease_pins.values()
    # the OOM-killed task retries and completes on a fresh (re-pinned) worker
    assert isinstance(rt.get(ref, timeout=30), int)
    assert isinstance(rt.get(leased_sleep.remote(2), timeout=30), int)


# ==========================================================================
# typed control-plane timeouts + backoff helper (ISSUE 8 satellite)
# ==========================================================================
def test_rpc_timeout_is_typed():
    server = rpc.RpcServer(
        handler_factory=lambda conn: {"slow": lambda c, p, rid: rpc.DEFER},
        name="slow-server",
    )
    conn = rpc.connect(server.address, handlers={})
    try:
        with pytest.raises(rpc.ControlPlaneTimeout) as ei:
            conn.request("slow", {}, timeout=0.2)
        assert isinstance(ei.value, rpc.RpcError)       # transport family
        assert isinstance(ei.value, TimeoutError)       # and a timeout
        assert ei.value.msg_type == "slow"
    finally:
        conn.close()
        server.close()


def test_retry_with_backoff_retries_timeouts():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise rpc.ControlPlaneTimeout("x", 0.1)
        return "ok"

    assert (
        rpc.retry_with_backoff(flaky, attempts=4, base_backoff_s=0.01)
        == "ok"
    )
    assert len(calls) == 3
    # non-retriable errors pass straight through
    def dead():
        raise rpc.RpcError("connection lost")

    with pytest.raises(rpc.RpcError):
        rpc.retry_with_backoff(dead, attempts=3, base_backoff_s=0.01)

    # an exhausted budget re-raises instead of sleeping past the deadline
    calls.clear()

    def always_slow():
        calls.append(1)
        raise rpc.ControlPlaneTimeout("y", 0.1)

    with pytest.raises(rpc.ControlPlaneTimeout):
        rpc.retry_with_backoff(
            always_slow, attempts=10, base_backoff_s=5.0,
            deadline_ts=time.time() + 0.01,
        )
    assert len(calls) == 1  # no second attempt fits the budget


def test_request_with_budget_uses_remaining_deadline():
    from ray_tpu.runtime.context import pop_deadline, push_deadline

    server = rpc.RpcServer(
        handler_factory=lambda conn: {"slow": lambda c, p, rid: rpc.DEFER},
        name="slow-server",
    )
    conn = rpc.connect(server.address, handlers={})
    token = push_deadline(time.time() + 0.3)
    try:
        t0 = time.monotonic()
        with pytest.raises(rpc.ControlPlaneTimeout):
            rpc.request_with_budget(conn, "slow", {}, default_timeout=30.0)
        assert time.monotonic() - t0 < 5.0  # NOT the 30s flat default
    finally:
        pop_deadline(token)
        conn.close()
        server.close()


# ==========================================================================
# chaos schema: the new kinds validate
# ==========================================================================
def test_chaos_validate_new_kinds():
    from ray_tpu.chaos.schedule import validate_schedule

    good = {
        "seed": 1,
        "events": [
            {"t": 0.0, "kind": "slow_node", "index": 0, "delay": 1.5},
            {"t": 0.5, "kind": "partition_node", "index": 0},
            {"t": 1.0, "kind": "heal_partition"},
        ],
    }
    assert validate_schedule(good, num_nodes=1) == []
    assert validate_schedule(
        {"events": [{"t": 0, "kind": "heal_partition"}]}
    )  # heal without partition
    assert validate_schedule(
        {"events": [{"t": 0, "kind": "slow_node", "delay": -1}]}
    )  # negative delay
    assert validate_schedule(
        {"events": [{"t": 0, "kind": "partition_node", "index": 3}]},
        num_nodes=1,
    )  # index out of range
