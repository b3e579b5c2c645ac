"""Multi-host fabric: a node agent in a separate OS process joins over TCP.

Validates the round-2 'real multi-host runtime' milestone: two processes form
one cluster — the driver submits, tasks/actors run in the agent process,
results transfer back; kill -9 of the agent exercises the node-failure path
(resubmission, actor death) end to end.

Reference parity anchors: cluster_utils.Cluster.add_node spawning real
raylets (python/ray/cluster_utils.py:135), chaos NodeKillerActor
(python/ray/_private/test_utils.py:1497).
"""

import os
import signal
import subprocess
import sys
import time

import pytest

import ray_tpu as rt
from ray_tpu.runtime.scheduler import NodeAffinitySchedulingStrategy

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spawn_agent(address, num_cpus=2, extra_resources='{"remote": 4}'):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    # stderr goes to a per-pid file, not an unread PIPE: when a test fails
    # because an agent silently died, the traceback (or its absence — clean
    # exit vs crash) is the difference between a diagnosis and a shrug
    log_dir = "/tmp/rt_agent_logs"
    os.makedirs(log_dir, exist_ok=True)
    log = open(os.path.join(log_dir, f"agent_{os.getpid()}_{time.monotonic_ns()}.log"), "w")
    try:
        proc = subprocess.Popen(
            [
                sys.executable, "-m", "ray_tpu.runtime.agent",
                "--address", address,
                "--num-cpus", str(num_cpus),
                "--resources", extra_resources,
                "--labels", '{"zone": "agent-zone"}',
            ],
            env=env,
            stdout=subprocess.DEVNULL,
            stderr=log,
        )
    finally:
        log.close()  # Popen duped the fd; keeping ours leaks one per agent
    return proc


def _wait_for_nodes(cluster, n, timeout=90.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if sum(1 for node in cluster.nodes.values() if not node.dead) >= n:
            return
        time.sleep(0.05)
    raise TimeoutError(f"cluster never reached {n} live nodes")


@pytest.fixture
def two_process_cluster():
    rt.init(num_cpus=2)
    cluster = rt.get_cluster()
    address = cluster.start_head_service()
    proc = _spawn_agent(address)
    try:
        _wait_for_nodes(cluster, 2)
        yield cluster, proc
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)
        rt.shutdown()


def _remote_node_id(cluster):
    head_id = cluster.head_node.node_id
    for nid, node in cluster.nodes.items():
        if nid != head_id and not node.dead:
            return nid
    raise AssertionError("no live remote node")


# --------------------------------------------------------------------------
def test_task_runs_in_agent_process(two_process_cluster):
    cluster, proc = two_process_cluster

    @rt.remote(resources={"remote": 1})
    def whoami(x):
        return os.getpid(), x * 2

    pid, doubled = rt.get(whoami.remote(21))
    assert doubled == 42
    assert pid != os.getpid()  # ran outside the driver process


def test_dependency_transfer_both_directions(two_process_cluster):
    import numpy as np

    cluster, proc = two_process_cluster
    arr = np.arange(100_000, dtype=np.float32)
    ref = rt.put(arr)  # lives on the head node

    @rt.remote(resources={"remote": 1})
    def remote_sum(a):
        return float(a.sum())

    # head -> agent dependency push
    remote_ref = remote_sum.remote(ref)

    @rt.remote
    def local_add_one(s):
        return s + 1.0

    # agent -> head result transfer feeding a local task
    assert rt.get(local_add_one.remote(remote_ref)) == pytest.approx(float(arr.sum()) + 1.0)


def test_actor_on_remote_node_ordered_calls(two_process_cluster):
    cluster, proc = two_process_cluster

    @rt.remote(resources={"remote": 1})
    class Counter:
        def __init__(self):
            self.value = 0
            self.pid = os.getpid()

        def add(self, n):
            self.value += n
            return self.value

        def get_pid(self):
            return self.pid

    c = Counter.remote()
    results = rt.get([c.add.remote(1) for _ in range(20)])
    assert results == list(range(1, 21))  # strict per-actor ordering
    assert rt.get(c.get_pid.remote()) != os.getpid()


def test_streaming_generator_from_agent(two_process_cluster):
    cluster, proc = two_process_cluster

    @rt.remote(num_returns="streaming", resources={"remote": 1}, execution="thread")
    def gen(n):
        for i in range(n):
            yield i * i

    out = [rt.get(ref) for ref in gen.remote(5)]
    assert out == [0, 1, 4, 9, 16]


def test_scheduler_spreads_by_resource(two_process_cluster):
    cluster, proc = two_process_cluster

    @rt.remote(resources={"remote": 1})
    def remote_pid():
        return os.getpid()

    @rt.remote
    def local_pid():
        return os.getpid()

    remote_pids = set(rt.get([remote_pid.remote() for _ in range(4)]))
    local_head_pid = os.getpid()
    assert local_head_pid not in remote_pids


def test_node_affinity_targets_agent(two_process_cluster):
    cluster, proc = two_process_cluster
    target = _remote_node_id(cluster)

    @rt.remote
    def where():
        return os.getpid()

    pid = rt.get(
        where.options(
            scheduling_strategy=NodeAffinitySchedulingStrategy(target)
        ).remote()
    )
    assert pid != os.getpid()


def test_kill9_agent_resubmits_inflight_tasks(two_process_cluster):
    cluster, proc = two_process_cluster
    target = _remote_node_id(cluster)

    @rt.remote(max_retries=2)
    def slow(x):
        time.sleep(1.5)
        return x + 1

    # soft affinity: prefers the agent, survives its death by rescheduling
    refs = [
        slow.options(
            scheduling_strategy=NodeAffinitySchedulingStrategy(target, soft=True)
        ).remote(i)
        for i in range(4)
    ]
    time.sleep(0.3)  # let them start on the agent
    proc.send_signal(signal.SIGKILL)
    proc.wait(timeout=10)
    assert rt.get(refs, timeout=60) == [1, 2, 3, 4]


def test_kill9_agent_fails_actors_and_recovers_node_table(two_process_cluster):
    from ray_tpu.exceptions import ActorDiedError, RayActorError

    cluster, proc = two_process_cluster

    @rt.remote(resources={"remote": 1})
    class Holder:
        def poke(self):
            return "ok"

    h = Holder.remote()
    assert rt.get(h.poke.remote()) == "ok"
    proc.send_signal(signal.SIGKILL)
    proc.wait(timeout=10)
    # Tight timeout on purpose: the death sweep must fail the pending call
    # promptly (the former 90 s value masked a submit/death-sweep TOCTOU
    # race where the call was never failed at all).
    with pytest.raises((ActorDiedError, RayActorError)):
        rt.get(h.poke.remote(), timeout=15)
    # node table marks the agent dead
    _wait_for_nodes(cluster, 1)
    dead = [n for n in cluster.nodes.values() if n.dead]
    assert len(dead) == 1


def test_agent_rejoin_after_restart(two_process_cluster):
    cluster, proc = two_process_cluster
    proc.send_signal(signal.SIGKILL)
    proc.wait(timeout=10)
    _wait_for_nodes(cluster, 1)

    proc2 = _spawn_agent(cluster.head_service.address)
    try:
        _wait_for_nodes(cluster, 2)

        @rt.remote(resources={"remote": 1})
        def f():
            return os.getpid()

        assert rt.get(f.remote()) != os.getpid()
    finally:
        proc2.kill()
        proc2.wait(timeout=10)


def test_labels_propagate(two_process_cluster):
    cluster, proc = two_process_cluster
    target = _remote_node_id(cluster)
    assert cluster.nodes[target].labels.get("zone") == "agent-zone"


def test_collective_group_across_processes(two_process_cluster):
    """ray.util.collective parity with ranks in different OS processes
    (round-2 VERDICT item 9): allreduce + send/recv ride the cluster KV
    over the transport."""
    import numpy as np

    cluster, proc = two_process_cluster
    head_id = cluster.head_node.node_id

    @rt.remote(execution="thread")
    class Rank:
        def __init__(self, rank, world):
            from ray_tpu.util import collective

            collective.init_collective_group(world, rank, group_name="xproc")
            self.rank = rank

        def allreduce(self, x):
            from ray_tpu.util import collective

            out = collective.allreduce(
                np.array([x], dtype=np.float32), group_name="xproc", rank=self.rank
            )
            return np.asarray(out).tolist()

        def send_to(self, value, dst):
            from ray_tpu.util import collective

            collective.send(value, dst, group_name="xproc", rank=self.rank)
            return True

        def recv_from(self, src):
            from ray_tpu.util import collective

            return collective.recv(src, group_name="xproc", rank=self.rank, timeout=60)

    r0 = Rank.options(
        scheduling_strategy=NodeAffinitySchedulingStrategy(head_id)
    ).remote(0, 2)
    r1 = Rank.options(resources={"remote": 1}).remote(1, 2)

    a = r0.allreduce.remote(1.0)
    b = r1.allreduce.remote(2.0)
    assert rt.get(a, timeout=90) == [3.0]
    assert rt.get(b, timeout=90) == [3.0]

    # point-to-point across the process boundary, both directions
    sent = r0.send_to.remote("ping", 1)
    got = r1.recv_from.remote(0)
    assert rt.get(sent, timeout=90) is True
    assert rt.get(got, timeout=90) == "ping"

    sent = r1.send_to.remote({"x": 42}, 0)
    got = r0.recv_from.remote(1)
    assert rt.get(sent, timeout=90) is True
    assert rt.get(got, timeout=90) == {"x": 42}


def test_worker_prints_forward_to_driver(two_process_cluster, capsys):
    """Task prints on an agent's workers surface on the driver's stderr
    (log_monitor-to-driver parity across hosts)."""
    cluster, proc = two_process_cluster

    @rt.remote(resources={"remote": 1}, execution="process")
    def chatty():
        print("hello-from-agent-worker")
        return 1

    assert rt.get(chatty.remote(), timeout=60) == 1
    deadline = time.monotonic() + 30
    seen = ""
    while time.monotonic() < deadline:
        seen += capsys.readouterr().err
        if "hello-from-agent-worker" in seen:
            break
        time.sleep(0.2)
    assert "hello-from-agent-worker" in seen
    assert "(node=" in seen  # head prefixes the source node


def test_nested_api_from_agent_worker(two_process_cluster):
    """A worker process ON THE AGENT makes nested rt calls; they relay
    agent -> head over the transport to the owning driver."""
    cluster, proc = two_process_cluster

    @rt.remote
    def child(x):
        return x * 3

    @rt.remote(resources={"remote": 1}, execution="process")
    def parent(x):
        import numpy as np

        ref = rt.put(np.arange(10))
        nested = rt.get(child.remote(x))
        return nested + int(rt.get(ref).sum())

    # child may run anywhere; parent runs in an agent worker process
    assert rt.get(parent.remote(2), timeout=120) == 6 + 45
