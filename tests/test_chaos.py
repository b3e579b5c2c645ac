"""Seeded chaos regression suite (ISSUE 2 satellite).

Each entry is a ``(seed, ChaosSchedule, workload)`` triple run through
``ChaosRunner`` TWICE, asserting:

  * the deterministic fault log is identical across the two runs (for the
    workload-driven schedules, where every failpoint hit is caused by the
    workload — frame drops, put faults, spawn faults), and
  * the invariant sweep passes every time: tasks terminal exactly once per
    attempt, no silent object loss, refcounts back at baseline, retries
    visible as spans.

Time-driven entries (heartbeat partition — hits happen per report tick, so
run LENGTH varies with wall clock) assert positional decision consistency
on the common prefix instead of full equality, plus full recovery.

The node-kill entry drives the existing ``cluster.kill_node`` chaos hook
through the new schedule runner.
"""

import os
import time

import pytest

import ray_tpu as rt
from ray_tpu.chaos import ChaosEvent, ChaosRunner, ChaosSchedule
from ray_tpu.runtime import failpoints
from ray_tpu.runtime.scheduler import NodeAffinitySchedulingStrategy


@pytest.fixture(autouse=True)
def _clean_failpoints():
    failpoints.reset()
    yield
    failpoints.reset()


def _assert_prefix_consistent(log_a, log_b):
    """Per failpoint, the injected-fault sequences must agree on the hit
    range both runs reached — the positional determinism contract for
    time-driven failpoints whose total hit counts differ run to run."""
    def by_fp(log):
        out = {}
        for e in log:
            out.setdefault(e["fp"], []).append(e)
        return out

    a_by, b_by = by_fp(log_a), by_fp(log_b)
    for fp_name in set(a_by) | set(b_by):
        a, b = a_by.get(fp_name, []), b_by.get(fp_name, [])
        if not a or not b:
            continue
        horizon = min(a[-1]["hit"], b[-1]["hit"])
        assert [e for e in a if e["hit"] <= horizon] == [
            e for e in b if e["hit"] <= horizon
        ], f"decision streams diverged for {fp_name}"


# --------------------------------------------------------------------------
# 1. frame-drop during push-shuffle (map on node B, reduce on head: every
#    reduce dependency crosses nodes through the in-process data plane)
# --------------------------------------------------------------------------
def test_schedule_frame_drop_during_push_shuffle(ray_start_cluster):
    rt_mod, cluster = ray_start_cluster
    node_b = cluster.add_node({"CPU": 2})
    head_id = cluster.head_node.node_id

    schedule = ChaosSchedule(
        [ChaosEvent(0.0, "arm", spec="data_plane.send_frame=drop(0.3)")],
        seed=21, name="frame-drop-shuffle",
    )

    def workload():
        @rt.remote(execution="thread")
        def map_block(i):
            return [i * 10 + j for j in range(5)]

        @rt.remote(execution="thread")
        def reduce_blocks(*blocks):
            return sorted(x for b in blocks for x in b)

        maps = [
            map_block.options(
                scheduling_strategy=NodeAffinitySchedulingStrategy(node_b.node_id)
            ).remote(i)
            for i in range(8)
        ]
        rt.wait(maps, num_returns=len(maps), timeout=30)
        out = reduce_blocks.options(
            scheduling_strategy=NodeAffinitySchedulingStrategy(head_id)
        ).remote(*maps)
        expected = sorted(i * 10 + j for i in range(8) for j in range(5))
        assert rt.get(out, timeout=60) == expected
        return [out]

    r1 = ChaosRunner(schedule, quiesce_timeout=60).run(workload)
    r2 = ChaosRunner(schedule, quiesce_timeout=60).run(workload)
    assert r1.ok, (r1.workload_error, r1.invariants.violations)
    assert r2.ok, (r2.workload_error, r2.invariants.violations)
    assert r1.faults, "the drop failpoint must actually fire"
    assert r1.same_faults(r2), (r1.faults, r2.faults)
    assert all(f["fp"] == "data_plane.send_frame" for f in r1.faults)


# --------------------------------------------------------------------------
# 2. put-fault + object loss during lineage reconstruction
# --------------------------------------------------------------------------
def test_schedule_put_fault_during_lineage_reconstruction(ray_start_regular):
    schedule = ChaosSchedule(
        [
            ChaosEvent(0.0, "arm", spec="object_store.put=raise(0.4)"),
            ChaosEvent(1.0, "lose_objects", fraction=0.6),
        ],
        seed=33, name="put-fault-lineage",
    )

    def workload():
        from ray_tpu.exceptions import ObjectLostError

        @rt.remote(max_retries=5, execution="thread")
        def produce(i):
            return i * 2

        task_refs = [produce.remote(i) for i in range(12)]
        rt.wait(task_refs, num_returns=len(task_refs), timeout=30)
        put_refs = []
        for i in range(8):
            while True:  # application-level retry: each miss consumes a hit
                try:
                    put_refs.append(rt.put(("blob", i)))
                    break
                except failpoints.FailpointInjected:
                    continue
        # sleep past the lose_objects event, then verify recovery:
        # task-produced objects REBUILD via lineage; put objects have no
        # lineage, so a lost one must RAISE ObjectLostError (loudly)
        time.sleep(1.3)
        assert rt.get(task_refs, timeout=60) == [i * 2 for i in range(12)]
        for i, ref in enumerate(put_refs):
            try:
                assert rt.get(ref, timeout=30) == ("blob", i)
            except ObjectLostError:
                pass
        return task_refs + put_refs

    r1 = ChaosRunner(schedule, quiesce_timeout=60).run(workload)
    r2 = ChaosRunner(schedule, quiesce_timeout=60).run(workload)
    assert r1.ok, (r1.workload_error, r1.invariants.violations)
    assert r2.ok, (r2.workload_error, r2.invariants.violations)
    assert any(f["fp"] == "object_store.put" for f in r1.faults)
    lose = [e for e in r1.events_applied if e["kind"] == "lose_objects"]
    assert lose and lose[0]["lost"] > 0
    assert r1.same_faults(r2), (r1.faults, r2.faults)


# --------------------------------------------------------------------------
# 3. worker-spawn failure during (actor-creation) fan-out — sequential
#    creations make every spawn attempt workload-driven, so the fault log
#    is strictly reproducible
# --------------------------------------------------------------------------
def test_schedule_worker_spawn_failure_during_fanout():
    # 8 CPUs: five 1-CPU actors coexist with headroom — this test is about
    # spawn faults, not resource exhaustion
    rt.init(num_cpus=8, _system_config={"num_prestart_workers": 0})
    try:
        schedule = ChaosSchedule(
            [ChaosEvent(0.0, "arm", spec="worker_pool.spawn=raise(0.35)")],
            seed=47, name="spawn-failure-fanout",
        )

        def workload():
            @rt.remote(max_restarts=25)
            class Echo:
                def __init__(self, tag):
                    self.tag = tag

                def ping(self):
                    return self.tag

            refs, actors = [], []
            for i in range(5):
                a = Echo.remote(i)
                ref = a.ping.remote()
                assert rt.get(ref, timeout=60) == i
                refs.append(ref)
                actors.append(a)
            for a in actors:
                # release the dedicated workers + CPUs: the second run of
                # this workload must not inherit a crowded node
                rt.kill(a)
            return refs

        r1 = ChaosRunner(schedule, quiesce_timeout=90).run(workload)
        r2 = ChaosRunner(schedule, quiesce_timeout=90).run(workload)
        assert r1.ok, (r1.workload_error, r1.invariants.violations)
        assert r2.ok, (r2.workload_error, r2.invariants.violations)
        assert any(f["fp"] == "worker_pool.spawn" for f in r1.faults)
        assert r1.same_faults(r2), (r1.faults, r2.faults)
    finally:
        rt.shutdown()


# --------------------------------------------------------------------------
# 4. heartbeat partition during actor calls (multihost: real agent process;
#    the head's ping rescue must keep the flapping node ALIVE and every
#    call must complete). Hits are per report tick (time-driven), so the
#    determinism assertion is positional consistency on the common prefix
#    of the two runs' agent-side fault logs.
# --------------------------------------------------------------------------
def _spawn_chaos_agent(address, fp_spec, seed):
    import subprocess
    import sys

    env = dict(os.environ)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["RAY_TPU_FAILPOINTS"] = fp_spec
    env["RAY_TPU_FAILPOINT_SEED"] = str(seed)
    log_dir = "/tmp/rt_agent_logs"
    os.makedirs(log_dir, exist_ok=True)
    log = open(os.path.join(log_dir, f"chaos_agent_{os.getpid()}_{time.monotonic_ns()}.log"), "w")
    try:
        return subprocess.Popen(
            [sys.executable, "-m", "ray_tpu.runtime.agent", "--address", address,
             "--num-cpus", "2", "--resources", '{"remote": 4}'],
            env=env, stdout=subprocess.DEVNULL, stderr=log,
        )
    finally:
        log.close()


def _heartbeat_partition_run(seed):
    from ray_tpu.chaos import check_invariants, snapshot_baseline

    rt.init(num_cpus=2)
    proc = None
    try:
        cluster = rt.get_cluster()
        address = cluster.start_head_service()
        proc = _spawn_chaos_agent(
            address, "agent.heartbeat=drop(0.7)", seed
        )
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline:
            if sum(1 for n in cluster.nodes.values() if not n.dead) >= 2:
                break
            time.sleep(0.05)
        else:
            raise TimeoutError("agent never joined")

        baseline = snapshot_baseline()

        @rt.remote(resources={"remote": 1})
        class Counter:
            def __init__(self):
                self.n = 0

            def add(self, k):
                self.n += k
                return self.n

        c = Counter.remote()
        refs = [c.add.remote(1) for _ in range(15)]
        assert rt.get(refs, timeout=90) == list(range(1, 16))

        # evidence the partition is real: with reports every ~0.1s, a
        # >0.5s report gap only happens when heartbeats are being dropped
        handle = next(
            n for n in cluster.nodes.values()
            if not n.dead and n is not cluster.head_node
        )
        max_gap = 0.0
        for _ in range(30):
            max_gap = max(max_gap, time.monotonic() - handle.last_report)
            time.sleep(0.1)
        assert max_gap > 0.4, f"no heartbeat gap observed (max {max_gap:.2f}s)"
        # the ping rescue must have kept the flapping node alive
        assert not handle.dead

        report = check_invariants(refs=refs, baseline=baseline, timeout=60)
        assert report.ok, report.violations

        # the agent piggybacks its fault log on (surviving) reports
        agent_log = []
        settle = time.monotonic() + 10
        while time.monotonic() < settle:
            agent_log = list(getattr(handle, "chaos_faults", []) or [])
            if agent_log:
                break
            time.sleep(0.2)
        assert agent_log, "agent-side fault log never reached the head"
        assert all(f["fp"] == "agent.heartbeat" for f in agent_log)
        # the piggyback accumulates in append order; canonical order is
        # (fp, hit) — sort before cross-run comparison
        return sorted(agent_log, key=lambda e: (e["fp"], e["hit"]))
    finally:
        if proc is not None and proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)
        rt.shutdown()


def test_schedule_heartbeat_partition_during_actor_calls():
    log1 = _heartbeat_partition_run(seed=77)
    log2 = _heartbeat_partition_run(seed=77)
    _assert_prefix_consistent(log1, log2)


# --------------------------------------------------------------------------
# 5. node-kill schedule: the existing kill_node chaos hook driven through
#    the new runner, with the invariant sweep proving recovery
# --------------------------------------------------------------------------
def test_schedule_node_kill_through_runner(ray_start_cluster):
    rt_mod, cluster = ray_start_cluster
    cluster.add_node({"CPU": 2})

    schedule = ChaosSchedule(
        [ChaosEvent(0.4, "kill_node", index=0)],
        seed=5, name="node-kill",
    )

    def workload():
        @rt.remote(max_retries=4, execution="thread")
        def slow_double(i):
            time.sleep(0.8)
            return i * 2

        refs = [
            slow_double.options(scheduling_strategy="SPREAD").remote(i)
            for i in range(8)
        ]
        assert rt.get(refs, timeout=60) == [i * 2 for i in range(8)]
        return refs

    result = ChaosRunner(schedule, quiesce_timeout=90).run(workload)
    assert result.ok, (result.workload_error, result.invariants.violations)
    killed = [e for e in result.events_applied if e["kind"] == "kill_node"]
    assert killed and "node" in killed[0]
    assert sum(1 for n in cluster.nodes.values() if n.dead) == 1


# --------------------------------------------------------------------------
# 6. relay-node kill mid-broadcast (ISSUE 4): a fanout-1 broadcast chain
#    head -> B -> C -> D with B killed by the schedule while it is serving
#    C.  C's failed edge takes the purge-then-retry path and re-parents
#    onto the surviving replica (the head); D — parked under C — completes
#    through the repaired chain.  The armed put failpoint makes every
#    commit attempt a workload-driven decision-stream hit: the broadcast
#    is fully sequential (gated), so same-seed runs produce byte-identical
#    fault logs even THROUGH the kill.
# --------------------------------------------------------------------------
def _relay_kill_run(seed):
    import threading

    import numpy as np

    rt.init(num_cpus=2)
    try:
        cluster = rt.get_cluster()
        node_b = cluster.add_node({"CPU": 1})  # schedule victim (index 0)
        node_c = cluster.add_node({"CPU": 1})
        node_d = cluster.add_node({"CPU": 1})

        schedule = ChaosSchedule(
            [
                ChaosEvent(0.0, "arm", spec="object_store.put=raise(0.4)"),
                ChaosEvent(0.8, "kill_node", index=0),
            ],
            seed=seed, name="relay-kill-broadcast",
        )

        def workload():
            pm = cluster.pull_manager
            old_fanout = pm._fanout
            pm._fanout = 1  # chain topology: B is everyone's relay
            # the broadcast payload; the armed put failpoint may fire —
            # application-level retry consumes hits deterministically
            while True:
                try:
                    ref = rt.put(np.ones(4 << 20, np.uint8))
                    break
                except failpoints.FailpointInjected:
                    continue
            oid = ref.id()
            # hold B's outbound serve: C stays blocked mid-edge until the
            # schedule's kill lands, then the edge fails loudly
            trip = threading.Event()
            orig_get = node_b.store.get

            def tripping_get(o, timeout=None):
                assert trip.wait(60)
                raise RuntimeError("relay node died mid-serve")

            node_b.store.get = tripping_get
            try:
                done = {
                    n.node_id: threading.Event() for n in (node_b, node_c, node_d)
                }
                for n in (node_b, node_c, node_d):
                    cluster.pull_object(oid, n, done[n.node_id].set)
                assert done[node_b.node_id].wait(30)  # B holds a copy; C is
                #                                       blocked inside B's store
                deadline = time.monotonic() + 30
                while not node_b.dead and time.monotonic() < deadline:
                    time.sleep(0.02)
                assert node_b.dead, "schedule kill never landed"
                trip.set()  # C's edge fails -> purge-then-retry -> the head
                assert done[node_c.node_id].wait(60)
                assert done[node_d.node_id].wait(60)
                assert node_c.store.contains(oid)
                assert node_d.store.contains(oid)
            finally:
                node_b.store.get = orig_get
                pm._fanout = old_fanout
            return [ref]

        result = ChaosRunner(schedule, quiesce_timeout=60).run(workload)
        assert result.ok, (result.workload_error, result.invariants.violations)
        killed = [e for e in result.events_applied if e["kind"] == "kill_node"]
        assert killed and killed[0]["node"] == node_b.node_id.hex()[:8]
        assert cluster.pull_manager.retries >= 1  # the re-parenting retry
        return result
    finally:
        rt.shutdown()


def test_schedule_relay_node_kill_mid_broadcast():
    r1 = _relay_kill_run(seed=11)
    r2 = _relay_kill_run(seed=11)
    assert r1.faults, "the put failpoint must actually fire"
    assert all(f["fp"] == "object_store.put" for f in r1.faults)
    assert r1.same_faults(r2), (r1.faults, r2.faults)


# --------------------------------------------------------------------------
# 7. stage-actor kill mid-compiled-plan (ISSUE 5): a 3-stage execution plan
#    spanning two nodes runs iterations through its installed channels while
#    an armed put failpoint generates a workload-driven decision stream;
#    killing the middle stage actor mid-plan must surface a TYPED error
#    (ActorDiedError) and flip the plan to BROKEN — and the same-seed runs'
#    fault logs stay byte-identical THROUGH the kill, because plan traffic
#    rides channels (zero store puts) and never perturbs the hit stream.
# --------------------------------------------------------------------------
def _plan_actor_kill_run(seed):
    rt.init(num_cpus=2)
    try:
        cluster = rt.get_cluster()
        cluster.add_node({"CPU": 2, "stage": 4})

        schedule = ChaosSchedule(
            [ChaosEvent(0.0, "arm", spec="object_store.put=raise(0.4)")],
            seed=seed, name="plan-stage-kill",
        )

        def workload():
            from ray_tpu.dag import InputNode
            from ray_tpu.exceptions import ActorDiedError, RayActorError

            @rt.remote
            class Stage:
                def __init__(self, k):
                    self.k = k

                def step(self, x):
                    return x + self.k

            head = dict(execution="inproc")
            other = dict(execution="inproc", resources={"stage": 1}, num_cpus=0)
            s0 = Stage.options(**head).remote(1)
            s1 = Stage.options(**other).remote(10)
            s2 = Stage.options(**head).remote(100)
            with InputNode() as inp:
                d = s2.step.bind(s1.step.bind(s0.step.bind(inp)))
            plan = d.compile_plan(name="chaos")
            # deterministic failpoint hits: app-retried puts — each attempt
            # consumes exactly one decision-stream index
            refs = []
            for i in range(6):
                while True:
                    try:
                        refs.append(rt.put(("blob", i)))
                        break
                    except failpoints.FailpointInjected:
                        continue
            for i in range(10):
                assert plan.execute(i) == i + 111
            rt.kill(s1)  # mid-plan: installed, channels live, between iters
            deadline = time.monotonic() + 30
            raised = None
            while time.monotonic() < deadline:
                try:
                    plan.execute(0)
                except (ActorDiedError, RayActorError) as exc:
                    raised = exc
                    break
            assert isinstance(raised, (ActorDiedError, RayActorError)), raised
            assert plan.state == "BROKEN"
            plan.teardown()
            return refs

        r1 = ChaosRunner(schedule, quiesce_timeout=90).run(workload)
        assert r1.ok, (r1.workload_error, r1.invariants.violations)
        return r1
    finally:
        rt.shutdown()


def test_schedule_stage_actor_kill_mid_plan():
    r1 = _plan_actor_kill_run(seed=29)
    r2 = _plan_actor_kill_run(seed=29)
    assert r1.faults, "the put failpoint must actually fire"
    assert all(f["fp"] == "object_store.put" for f in r1.faults)
    assert r1.same_faults(r2), (r1.faults, r2.faults)


# --------------------------------------------------------------------------
# 8. drain a relay node mid-broadcast (ISSUE 6): the fanout-1 chain of
#    test 6 (head -> B -> C -> D) with the relay GRACEFULLY drained instead
#    of killed.  B holds no sole-replica objects (its broadcast copy also
#    lives at the head), so the drain's evacuation is a no-op and its
#    terminate lands while C is still blocked mid-edge — C re-parents onto
#    the surviving replica through purge-then-retry, parked D completes
#    through the repaired chain, and the elasticity invariants (drain lost
#    nothing, every ref resolves) hold.  The armed put failpoint makes the
#    decision stream workload-driven: same-seed fault logs are
#    byte-identical THROUGH the drain.
# --------------------------------------------------------------------------
def _relay_drain_run(seed):
    import threading

    import numpy as np

    rt.init(num_cpus=2)
    try:
        cluster = rt.get_cluster()
        node_b = cluster.add_node({"CPU": 1})  # schedule victim (index 0)
        node_c = cluster.add_node({"CPU": 1})
        node_d = cluster.add_node({"CPU": 1})

        schedule = ChaosSchedule(
            [
                ChaosEvent(0.0, "arm", spec="object_store.put=raise(0.4)"),
                ChaosEvent(0.8, "drain_node", index=0, timeout=5.0),
            ],
            seed=seed, name="relay-drain-broadcast",
        )

        def workload():
            pm = cluster.pull_manager
            old_fanout = pm._fanout
            pm._fanout = 1  # chain topology: B is everyone's relay
            while True:
                try:
                    ref = rt.put(np.ones(4 << 20, np.uint8))
                    break
                except failpoints.FailpointInjected:
                    continue
            oid = ref.id()
            # hold B's outbound serve: C stays blocked mid-edge until the
            # schedule's drain terminates B, then the edge fails loudly
            trip = threading.Event()
            orig_get = node_b.store.get

            def tripping_get(o, timeout=None):
                assert trip.wait(60)
                raise RuntimeError("relay node drained mid-serve")

            node_b.store.get = tripping_get
            try:
                done = {
                    n.node_id: threading.Event() for n in (node_b, node_c, node_d)
                }
                for n in (node_b, node_c, node_d):
                    cluster.pull_object(oid, n, done[n.node_id].set)
                assert done[node_b.node_id].wait(30)  # B holds a copy; C is
                #                                       blocked inside B's store
                deadline = time.monotonic() + 30
                while not node_b.dead and time.monotonic() < deadline:
                    time.sleep(0.02)
                assert node_b.dead, "schedule drain never landed"
                trip.set()  # C's edge fails -> purge-then-retry -> the head
                assert done[node_c.node_id].wait(60)
                assert done[node_d.node_id].wait(60)
                assert node_c.store.contains(oid)
                assert node_d.store.contains(oid)
            finally:
                node_b.store.get = orig_get
                pm._fanout = old_fanout
            return [ref]

        result = ChaosRunner(schedule, quiesce_timeout=60).run(workload)
        assert result.ok, (result.workload_error, result.invariants.violations)
        drained = [e for e in result.events_applied if e["kind"] == "drain_node"]
        assert drained and drained[0]["node"] == node_b.node_id.hex()[:8]
        # nothing was sole-replica on B: the drain had nothing to evacuate
        # and nothing to lose (elasticity invariant 6 audited this)
        assert drained[0]["evacuated"] == 0
        assert cluster.drain_reports[-1]["failed_evacuations"] == 0
        assert cluster.pull_manager.retries >= 1  # the re-parenting retry
        return result
    finally:
        rt.shutdown()


def test_schedule_relay_node_drain_mid_broadcast():
    r1 = _relay_drain_run(seed=13)
    r2 = _relay_drain_run(seed=13)
    assert r1.faults, "the put failpoint must actually fire"
    assert all(f["fp"] == "object_store.put" for f in r1.faults)
    assert r1.same_faults(r2), (r1.faults, r2.faults)


# --------------------------------------------------------------------------
# 9. kill_head + restart_head mid-workload (ISSUE 6): a live workload (an
#    actor with in-process state, app-retried puts driving the decision
#    stream) runs across a full head outage.  The kill-time snapshot carries
#    the failpoint hit counters, the restart re-adopts the live node and
#    reconciles the actor instance back to ALIVE, work resumes — and the
#    same-seed fault logs are byte-identical ACROSS the restart boundary.
#    A doomed-incarnation KV write between kill and restart is discarded,
#    exactly what a write to a dying GCS loses.
# --------------------------------------------------------------------------
def _head_outage_run(seed):
    rt.init(num_cpus=2)
    try:
        cluster = rt.get_cluster()
        cluster.add_node({"CPU": 2})

        schedule = ChaosSchedule(
            [
                ChaosEvent(0.0, "arm", spec="object_store.put=raise(0.4)"),
                ChaosEvent(2.0, "kill_head"),
                ChaosEvent(3.5, "restart_head"),
            ],
            seed=seed, name="head-outage",
        )

        def retried_puts(tag, n):
            out = []
            for i in range(n):
                while True:
                    try:
                        out.append(rt.put((tag, i)))
                        break
                    except failpoints.FailpointInjected:
                        continue
            return out

        def workload():
            t0 = time.monotonic()

            @rt.remote
            class Counter:
                def __init__(self):
                    self.n = 0

                def add(self, k):
                    self.n += k
                    return self.n

            c = Counter.options(name="outage-counter", max_restarts=1).remote()
            # ---- phase 1: everything resolves BEFORE the kill lands ----
            refs = retried_puts("pre", 6)
            assert rt.get([c.add.remote(1) for _ in range(5)], timeout=30) == [
                1, 2, 3, 4, 5
            ]
            cluster.control.kv.put(b"outage_marker", b"pre-kill")
            # ---- the outage window: quiesce through kill + restart ----
            while time.monotonic() - t0 < 4.2:
                time.sleep(0.05)
                if cluster._head_down:
                    # doomed-incarnation write: must vanish at restart
                    cluster.control.kv.put(b"doomed_marker", b"lost")
            assert cluster.head_restarts >= 1, "restart_head never landed"
            # ---- phase 2: the fabric works after the restart ----
            assert cluster.control.kv.get(b"outage_marker") == b"pre-kill"
            assert cluster.control.kv.get(b"doomed_marker") is None
            refs += retried_puts("post", 6)
            # the named record survived the outage AND the live instance
            # reconciled: in-process state (n == 5) carried through
            c2 = rt.get_actor("outage-counter")
            assert rt.get(c2.add.remote(1), timeout=30) == 6
            return refs

        result = ChaosRunner(schedule, quiesce_timeout=90).run(workload)
        assert result.ok, (result.workload_error, result.invariants.violations)
        kinds = [e["kind"] for e in result.events_applied]
        assert kinds.count("kill_head") == 1 and kinds.count("restart_head") == 1
        restart = next(e for e in result.events_applied if e["kind"] == "restart_head")
        assert restart["reconciled"] >= 1
        return result
    finally:
        rt.shutdown()


def test_schedule_head_outage_mid_workload():
    r1 = _head_outage_run(seed=61)
    r2 = _head_outage_run(seed=61)
    assert r1.faults, "the put failpoint must actually fire"
    assert r1.same_faults(r2), (r1.faults, r2.faults)


# --------------------------------------------------------------------------
# 10. kill a plan stage node, then auto-repair (ISSUE 6): a compiled plan
#     with a restartable stage actor on a doomed node keeps executing while
#     the schedule kills that node.  The plan flips BROKEN (typed error),
#     the restart FSM revives the actor on the surviving "stage" node, the
#     auto-repair thread reinstalls onto it, and subsequent iterations
#     produce correct outputs — READY -> BROKEN -> READY, audited by the
#     invariant sweep from the cluster's transition log.
# --------------------------------------------------------------------------
def _plan_auto_repair_run(seed):
    rt.init(num_cpus=2)
    try:
        cluster = rt.get_cluster()
        node_b = cluster.add_node({"CPU": 1, "stage": 1})  # victim (index 0)
        cluster.add_node({"CPU": 1, "stage": 1})           # restart target

        schedule = ChaosSchedule(
            [
                ChaosEvent(0.0, "arm", spec="object_store.put=raise(0.4)"),
                ChaosEvent(1.0, "kill_node", index=0),
            ],
            seed=seed, name="plan-node-kill-auto-repair",
        )

        def workload():
            from ray_tpu.dag import InputNode
            from ray_tpu.exceptions import (
                ActorDiedError,
                RayActorError,
                WorkerCrashedError,
            )

            @rt.remote
            class Stage:
                def __init__(self, k):
                    self.k = k

                def step(self, x):
                    return x + self.k

            # s0/s2 pinned to the head: default placement could land them
            # on the doomed node, where max_restarts=0 would (correctly)
            # make the plan unrepairable — not what this test is about
            head = dict(
                execution="inproc",
                scheduling_strategy=NodeAffinitySchedulingStrategy(
                    cluster.head_node.node_id
                ),
            )
            s0 = Stage.options(**head).remote(1)
            s1 = Stage.options(
                execution="inproc", num_cpus=0, resources={"stage": 1},
                max_restarts=1,
                scheduling_strategy=NodeAffinitySchedulingStrategy(
                    node_b.node_id, soft=True
                ),
            ).remote(10)
            s2 = Stage.options(**head).remote(100)
            with InputNode() as inp:
                d = s2.step.bind(s1.step.bind(s0.step.bind(inp)))
            plan = d.compile_plan(name="self-healing", auto_repair=True)
            refs = []
            for i in range(4):
                while True:
                    try:
                        refs.append(rt.put(("blob", i)))
                        break
                    except failpoints.FailpointInjected:
                        continue
            # iterate THROUGH the node kill: broken iterations surface
            # typed errors, auto-repair reinstalls, iterations resume
            saw_break = False
            deadline = time.monotonic() + 45
            completed_after_break = 0
            while time.monotonic() < deadline and completed_after_break < 5:
                try:
                    assert plan.execute(7) == 118
                    if saw_break:
                        completed_after_break += 1
                    elif node_b.dead:
                        # raced: repair finished before an execute failed
                        saw_break = True
                except (ActorDiedError, RayActorError, WorkerCrashedError):
                    saw_break = True
                    time.sleep(0.05)
            assert saw_break, "the stage-node kill never surfaced"
            assert completed_after_break >= 5, "plan never healed"
            assert plan.state == "READY"
            assert "BROKEN" in plan.state_history
            assert plan.state_history[-1] == "READY"
            plan.teardown()
            return refs

        result = ChaosRunner(schedule, quiesce_timeout=90).run(workload)
        assert result.ok, (result.workload_error, result.invariants.violations)
        killed = [e for e in result.events_applied if e["kind"] == "kill_node"]
        assert killed and killed[0]["node"] == node_b.node_id.hex()[:8]
        return result
    finally:
        rt.shutdown()


def test_schedule_plan_stage_node_kill_auto_repair():
    r1 = _plan_auto_repair_run(seed=37)
    r2 = _plan_auto_repair_run(seed=37)
    assert r1.faults, "the put failpoint must actually fire"
    assert r1.same_faults(r2), (r1.faults, r2.faults)


# --------------------------------------------------------------------------
# 11. the full elasticity schedule (ISSUE 6 acceptance): ONE seeded timeline
#     containing add_node, drain_node, kill_head, AND restart_head runs a
#     live workload to completion — the drained node's sole-replica objects
#     evacuate (zero loss with survivors present), the head outage discards
#     doomed writes and reconciles on restart, and the fault log is
#     byte-identical across two same-seed runs INCLUDING across the head
#     restart boundary (every put/transfer hit is workload-driven).
# --------------------------------------------------------------------------
def _elasticity_run(seed):
    import numpy as np

    rt.init(num_cpus=2)
    try:
        cluster = rt.get_cluster()
        node_b = cluster.add_node({"CPU": 1})  # drain victim (index 0)

        schedule = ChaosSchedule(
            [
                ChaosEvent(0.0, "arm", spec="object_store.put=raise(0.4)"),
                ChaosEvent(0.6, "add_node", resources={"CPU": 1}),
                ChaosEvent(1.2, "drain_node", index=0, timeout=10.0),
                ChaosEvent(2.4, "kill_head"),
                ChaosEvent(3.9, "restart_head"),
            ],
            seed=seed, name="full-elasticity",
        )

        def retried_puts(tag, n):
            out = []
            for i in range(n):
                while True:
                    try:
                        out.append(rt.put((tag, i)))
                        break
                    except failpoints.FailpointInjected:
                        continue
            return out

        def workload():
            t0 = time.monotonic()

            @rt.remote(execution="thread", max_retries=4)
            def produce(i):
                return np.full(150_000, i, np.uint8)

            # sole replicas on the doomed node: the drain MUST evacuate them
            refs = [
                produce.options(
                    scheduling_strategy=NodeAffinitySchedulingStrategy(node_b.node_id)
                ).remote(i)
                for i in range(4)
            ]
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline and any(
                not cluster.directory.locations(r.id()) for r in refs
            ):
                time.sleep(0.02)
            put_refs = retried_puts("pre", 4)
            # ---- wait out the drain (t=1.2), then prove zero loss ----
            while time.monotonic() - t0 < 2.2:
                time.sleep(0.05)
            assert node_b.dead, "schedule drain never landed"
            values = rt.get(refs, timeout=30)
            assert all(
                v[0] == i and v.nbytes == 150_000 for i, v in enumerate(values)
            ), "evacuated objects must survive the drain byte-for-byte"
            # ---- wait out the head outage (kill 2.4 -> restart 3.9) ----
            while time.monotonic() - t0 < 4.6:
                time.sleep(0.05)
            assert cluster.head_restarts >= 1, "restart_head never landed"
            # ---- the elastic fabric still works end to end ----
            put_refs += retried_puts("post", 4)
            added = [
                n for n in cluster.nodes.values()
                if not n.dead and n is not cluster.head_node
            ]
            assert added, "the add_node event's node must be live"
            out = produce.options(
                scheduling_strategy=NodeAffinitySchedulingStrategy(
                    added[0].node_id
                )
            ).remote(9)
            assert rt.get(out, timeout=30)[0] == 9
            return refs + put_refs + [out]

        result = ChaosRunner(schedule, quiesce_timeout=90).run(workload)
        assert result.ok, (result.workload_error, result.invariants.violations)
        kinds = [e["kind"] for e in result.events_applied]
        for kind in ("add_node", "drain_node", "kill_head", "restart_head"):
            assert kind in kinds, f"{kind} never applied: {result.events_applied}"
        drained = next(e for e in result.events_applied if e["kind"] == "drain_node")
        assert drained["evacuated"] == 4 and drained["outcome"] == "ok"
        assert cluster.drain_reports[-1]["failed_evacuations"] == 0
        return result
    finally:
        rt.shutdown()


def test_schedule_full_elasticity_byte_identical_through_restart():
    r1 = _elasticity_run(seed=101)
    r2 = _elasticity_run(seed=101)
    assert r1.faults, "the put failpoint must actually fire"
    assert r1.same_faults(r2), (r1.faults, r2.faults)


# --------------------------------------------------------------------------
# 11. leased-worker kill mid-workload (ISSUE 7): dispatch faults hit the
#     LEASED direct-dispatch path (repeat-shape tasks riding one cached
#     lease) and the lease-pinned process worker is SIGKILLed between
#     bursts — retries flow through the normal FSM, the lease machinery
#     re-pins, and the same-seed fault logs stay byte-identical.
# --------------------------------------------------------------------------
def test_schedule_leased_worker_kill_mid_push():
    rt.init(
        num_cpus=2,
        _system_config={
            "num_prestart_workers": 0,
            # keep every "auto" task in process workers so the leased path
            # exercises worker pinning (the kill target)
            "inproc_task_threshold_s": 0.0,
        },
    )
    try:
        schedule = ChaosSchedule(
            [ChaosEvent(0.0, "arm", spec="scheduler.dispatch=raise(0.12)")],
            seed=61, name="leased-worker-kill",
        )

        def workload():
            @rt.remote(max_retries=25)
            def bump():
                return 1

            cluster = rt.get_cluster()
            pool = cluster.head_node.worker_pool
            # burst 1 rides the freshly-granted lease (dispatch faults
            # land on leased submissions; the FSM retries them)
            assert rt.get([bump.remote() for _ in range(15)], timeout=90) == [1] * 15
            assert cluster.lease_manager.reuse_hits >= 10
            # sequential calls land on an IDLE worker, forming the pin
            # (the async burst above arrived before any worker existed)
            for _ in range(3):
                assert rt.get(bump.remote(), timeout=90) == 1
            # kill the lease-pinned worker at a QUIESCENT point (nothing
            # in flight -> the kill adds no nondeterministic retries, so
            # both runs see the identical dispatch-hit sequence)
            with pool._lock:
                pinned = list(pool._lease_pins.values())
            assert pinned, "leased shape never pinned a process worker"
            for w in pinned:
                try:
                    w.proc.kill()
                except OSError:
                    pass
            for w in pinned:
                try:
                    w.proc.wait(timeout=10)
                except Exception:  # noqa: BLE001
                    pass
            # burst 2: the dead pin is detected, the pool re-pins/regrows,
            # every task still completes through the lease path
            refs = [bump.remote() for _ in range(15)]
            assert rt.get(refs, timeout=90) == [1] * 15
            return refs

        r1 = ChaosRunner(schedule, quiesce_timeout=90).run(workload)
        r2 = ChaosRunner(schedule, quiesce_timeout=90).run(workload)
        assert r1.ok, (r1.workload_error, r1.invariants.violations)
        assert r2.ok, (r2.workload_error, r2.invariants.violations)
        assert any(f["fp"] == "scheduler.dispatch" for f in r1.faults)
        assert r1.same_faults(r2), (r1.faults, r2.faults)
    finally:
        rt.shutdown()


# --------------------------------------------------------------------------
# schedule JSON round trip + CLI-facing loader
# --------------------------------------------------------------------------
def test_schedule_json_round_trip(tmp_path):
    sched = ChaosSchedule(
        [
            ChaosEvent(0.0, "arm", spec="rpc.call=delay(0.1,0.2)"),
            ChaosEvent(1.0, "partition", fp="agent.heartbeat", duration=2.0),
            ChaosEvent(2.0, "kill_node", index=1),
        ],
        seed=9, name="round-trip",
    )
    path = str(tmp_path / "sched.json")
    sched.save(path)
    loaded = ChaosSchedule.load(path, seed=123)
    assert loaded.seed == 123  # explicit seed override
    assert loaded.name == "round-trip"
    assert [e.to_dict() for e in loaded.events] == [e.to_dict() for e in sched.events]
    assert loaded.duration() == 3.0
    with pytest.raises(ValueError, match="unknown chaos event kind"):
        ChaosEvent(0.0, "explode")


# --------------------------------------------------------------------------
# 12. partition-heal smoke matrix (ISSUE 8): a gray-partitioned node keeps
#     executing after its death declaration — every commit from the fenced
#     incarnation is rejected, the resubmitted attempts own the results,
#     the healed (fresh) node serves new work, and the same-seed fault logs
#     are byte-identical run to run, at THREE seeds.
# --------------------------------------------------------------------------
_PARTITION_HEAL_SCHEDULE = {
    "name": "partition-heal",
    "events": [
        {"t": 0.0, "kind": "arm", "spec": "scheduler.dispatch=raise(0.08)"},
        {"t": 0.2, "kind": "slow_node", "index": 0, "delay": 0.05},
        {"t": 0.45, "kind": "partition_node", "index": 0},
        {"t": 0.9, "kind": "heal_partition"},
        {"t": 1.1, "kind": "disarm"},
    ],
}


def _partition_heal_run(seed):
    from ray_tpu import api
    from ray_tpu.chaos.schedule import validate_schedule
    from ray_tpu.observability import metric_defs
    from ray_tpu.runtime.scheduler import NodeAffinitySchedulingStrategy

    sched_dict = dict(_PARTITION_HEAL_SCHEDULE, seed=seed)
    assert validate_schedule(sched_dict, num_nodes=1) == []
    rt.init(num_cpus=1)
    try:
        cluster = api.get_cluster()
        victim = cluster.add_node({"CPU": 2})
        fences0 = len(cluster.fence_events)
        schedule = ChaosSchedule.from_dict(sched_dict)

        def workload():
            @rt.remote(max_retries=6)
            def bump(i):
                time.sleep(0.12)
                return i + 1

            # soft affinity onto the victim: tasks are IN FLIGHT there when
            # the partition lands, so the stale incarnation tries to commit
            strat = NodeAffinitySchedulingStrategy(victim.node_id, soft=True)
            return [bump.options(scheduling_strategy=strat).remote(i) for i in range(20)]

        result = ChaosRunner(schedule, quiesce_timeout=90).run(workload)
        assert result.ok, (result.workload_error, result.invariants.violations)
        # the split-brain regression: the fenced incarnation DID try to
        # commit, and every attempt was rejected (invariants 9/10 audited
        # the directory + terminal records inside result.invariants)
        assert len(cluster.fence_events) > fences0, "no fenced commit observed"
        assert metric_defs.FENCED_FRAMES.get(tags={"kind": "task_finished"}) > 0
        # the healed (fresh) node serves new work
        fresh = [
            n for n in cluster.nodes.values()
            if not n.dead and n is not cluster.head_node
        ]
        assert fresh, "heal_partition never produced a fresh node"

        @rt.remote
        def after_heal(x):
            return x * 10

        strat = NodeAffinitySchedulingStrategy(fresh[0].node_id)
        assert rt.get(after_heal.options(scheduling_strategy=strat).remote(4), timeout=30) == 40
        return result
    finally:
        rt.shutdown()


@pytest.mark.parametrize("seed", [101, 202, 303])
def test_schedule_partition_heal_matrix(seed):
    """Seeded smoke matrix: each seed runs TWICE and must produce
    byte-identical fault logs through the partition, the fencing, and the
    heal (the chaos determinism contract survives gray failures)."""
    r1 = _partition_heal_run(seed)
    r2 = _partition_heal_run(seed)
    assert r1.same_faults(r2), (r1.faults, r2.faults)


def test_chaos_validate_cli_partition_heal(tmp_path, capsys):
    """`rt chaos validate` schema-checks the new kinds end to end."""
    import json as _json

    from ray_tpu.chaos.schedule import validate_cli

    path = str(tmp_path / "partition_heal.json")
    with open(path, "w") as f:
        _json.dump(dict(_PARTITION_HEAL_SCHEDULE, seed=1), f)

    class Args:
        schedule = path
        nodes = 1

    assert validate_cli(Args()) == 0
    # a heal without a partition fails validation loudly
    bad = {"seed": 1, "events": [{"t": 0.0, "kind": "heal_partition"}]}
    with open(path, "w") as f:
        _json.dump(bad, f)
    assert validate_cli(Args()) == 1


# --------------------------------------------------------------------------
# 14. kill one SPMD gang member mid-execute_async (ISSUE 11): the plan
#     flips BROKEN with a typed ActorDiedError, repair() waits for the
#     restart FSM and reinstalls the whole group (warmup re-primed), and
#     iterations resume — with same-seed fault logs byte-identical (every
#     failpoint hit is a workload-driven retried put; gang traffic rides
#     the same send_frame failpoint as every other frame, unarmed here).
# --------------------------------------------------------------------------
def _gang_member_kill_run(seed):
    rt.init(num_cpus=2)
    try:
        schedule = ChaosSchedule(
            [ChaosEvent(0.0, "arm", spec="object_store.put=raise(0.4)")],
            seed=seed, name="gang-member-kill",
        )

        def workload():
            import jax
            import jax.numpy as jnp
            import numpy as np

            from ray_tpu.dag import InputNode, StageGroup
            from ray_tpu.exceptions import ActorDiedError, RayActorError

            step_fn = jax.jit(lambda x: x + 1.0)

            @rt.remote
            class Member:
                def step(self, x):
                    return step_fn(x)

            members = [
                Member.options(execution="inproc", max_restarts=1).remote()
                for _ in range(2)
            ]
            gang = StageGroup(members, "step", split_axis=0, warmup=((4, 8), "float32"))
            with InputNode() as inp:
                d = gang.bind(inp)
            plan = d.compile_plan(name="gang-chaos")
            # deterministic failpoint hits: app-retried puts — each attempt
            # consumes exactly one decision-stream index
            refs = []
            for i in range(10):
                while True:
                    try:
                        refs.append(rt.put(("blob", i)))
                        break
                    except failpoints.FailpointInjected:
                        continue
            x = jnp.ones((4, 8), jnp.float32)
            for _ in range(10):
                out = plan.execute(x)
                assert float(np.asarray(out).sum()) == 4 * 8 * 2
            # kill one member with an iteration in flight
            fut = plan.execute_async(x)
            rt.kill(members[1], no_restart=False)
            raised = None
            try:
                fut.result(timeout=30)
            except (ActorDiedError, RayActorError) as exc:
                raised = exc
            deadline = time.monotonic() + 30
            while raised is None and time.monotonic() < deadline:
                try:
                    plan.execute(x)
                except (ActorDiedError, RayActorError) as exc:
                    raised = exc
                    break
            assert isinstance(raised, (ActorDiedError, RayActorError)), raised
            assert plan.state == "BROKEN"
            # the restart FSM revives the member; repair reinstalls the gang
            plan.repair(timeout=30)
            assert plan.state == "READY"
            for _ in range(5):
                out = plan.execute(x)
                assert float(np.asarray(out).sum()) == 4 * 8 * 2
            plan.teardown()
            return refs

        result = ChaosRunner(schedule, quiesce_timeout=90).run(workload)
        assert result.ok, (result.workload_error, result.invariants.violations)
        return result
    finally:
        rt.shutdown()


def test_schedule_gang_member_kill_repair_byte_identical():
    r1 = _gang_member_kill_run(seed=53)
    r2 = _gang_member_kill_run(seed=53)
    assert r1.faults, "the put failpoint must actually fire"
    assert all(f["fp"] == "object_store.put" for f in r1.faults)
    assert r1.same_faults(r2), (r1.faults, r2.faults)


# --------------------------------------------------------------------------
# 15. elastic gang training under chaos (ISSUE 17): the schedule hard-KILLS
#     one gang member mid-step (typed death -> BROKEN -> recover: restore
#     the latest step checkpoint, shrink-rebuild, resume) and then PREEMPTS
#     another gracefully (checkpoint -> shrink -> continue — the serving-
#     burst ladder).  Invariant 12 replays every repair audit against an
#     uninterrupted single-process run from the same checkpoint state and
#     byte-compares the loss trajectories.  Neither injector consumes
#     failpoint decisions, so same-seed fault logs stay byte-identical
#     (every logged fault is a workload-driven retried put).
# --------------------------------------------------------------------------
def _train_gang_chaos_run(seed):
    rt.init(num_cpus=4)
    try:
        schedule = ChaosSchedule(
            [
                ChaosEvent(0.0, "arm", spec="object_store.put=raise(0.4)"),
                ChaosEvent(1.0, "preempt_gang_member", job="chaos_gang",
                           graceful=False),
                ChaosEvent(2.0, "preempt_gang_member", job="chaos_gang",
                           graceful=True),
            ],
            seed=seed, name="train-gang-kill-preempt",
        )

        def workload():
            from ray_tpu.train.controller import TrainController

            ctl = TrainController(
                "chaos_gang", world_size=4, batch_size=8, feature_dim=4,
                seed=29, checkpoint_period=2, preemptible=True,
            )
            # deterministic failpoint hits: app-retried puts — each attempt
            # consumes exactly one decision-stream index
            refs = []
            for i in range(10):
                while True:
                    try:
                        refs.append(rt.put(("train", i)))
                        break
                    except failpoints.FailpointInjected:
                        continue
            # train through both scheduled disruptions; the recovery
            # ladder (checkpoint restore -> repair/shrink) is armed
            deadline = time.monotonic() + 2.6
            while time.monotonic() < deadline:
                ctl.run(1, auto_repair=True)
            # a few post-disruption steps so invariant 12 has a resumed
            # trajectory to replay
            ctl.run(3, auto_repair=True)
            assert ctl.repair_history, "the chaos kill never triggered a repair"
            outcomes = {r["outcome"] for r in ctl.repair_history}
            assert outcomes <= {"repaired", "shrunk"}, outcomes
            assert any(
                r["reason"] == "preempt" for r in ctl.resize_history
            ), "the graceful preempt never resized the gang"
            assert ctl.world_size < 4
            ctl.shutdown()
            return refs

        result = ChaosRunner(schedule, quiesce_timeout=90).run(workload)
        assert result.ok, (result.workload_error, result.invariants.violations)
        preempts = [
            e for e in result.events_applied
            if e["kind"] == "preempt_gang_member"
        ]
        assert len(preempts) == 2 and all(
            e.get("job") == "chaos_gang" for e in preempts
        ), preempts
        assert result.invariants.checked.get("train_repairs", 0) >= 1
        assert result.invariants.checked.get("train_replayed_steps", 0) >= 1
        return result
    finally:
        rt.shutdown()


@pytest.mark.parametrize("seed", [37, 59])
def test_schedule_train_gang_kill_preempt_byte_identical(seed):
    r1 = _train_gang_chaos_run(seed)
    r2 = _train_gang_chaos_run(seed)
    assert r1.faults, "the put failpoint must actually fire"
    assert all(f["fp"] == "object_store.put" for f in r1.faults)
    assert r1.same_faults(r2), (r1.faults, r2.faults)
