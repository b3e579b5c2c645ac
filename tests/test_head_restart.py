"""Head fault tolerance (round-3 VERDICT item 4).

The head is no longer a hard SPOF: agents survive a head outage, reconnect
with backoff, and re-register; a restarted head restores durable control
state (KV, jobs, actor records) from the snapshot and reconciles the
rejoining agents' live actor instances.

Reference parity anchors: GCS restart against Redis
(src/ray/gcs/store_client/redis_store_client.h) and raylet reconnection
(core_worker.proto:443 RayletNotifyGCSRestart).
"""

import pytest
import os
import signal
import socket
import subprocess
import sys
import time

import ray_tpu as rt

from test_multihost import REPO_ROOT, _spawn_agent, _wait_for_nodes

HEAD_RUNNER = """
import os, sys, time
sys.path.insert(0, {repo!r})
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import ray_tpu as rt
rt.init(num_cpus=1, _system_config={{"control_snapshot_path": {snap!r}}}, head_port={port})
cluster = rt.get_cluster()
deadline = time.time() + 90
while sum(1 for n in cluster.nodes.values() if not n.dead) < 2:
    if time.time() > deadline:
        raise SystemExit("agent never joined")
    time.sleep(0.1)

@rt.remote(resources={{"remote": 1}}, execution="thread")
class Keeper:
    def __init__(self):
        self.n = 0

    def bump(self):
        self.n += 1
        return self.n

k = Keeper.options(name="keeper").remote()
assert rt.get(k.bump.remote(), timeout=60) == 1
cluster.control.kv.put(b"restart_marker", b"written-by-head-a")
cluster.control.save_snapshot({snap!r})
print("READY", flush=True)
time.sleep(600)
"""


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_head_restart_from_snapshot_agents_rejoin(tmp_path):
    """Kill -9 the head; a new head on the same address restores the
    snapshot; the agent rejoins (instead of exiting); a resubmitted task
    completes; a named actor's IN-PROCESS state survives the outage."""
    port = _free_port()
    snap = str(tmp_path / "control.snap")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"

    head_a = subprocess.Popen(
        [sys.executable, "-c", HEAD_RUNNER.format(repo=REPO_ROOT, snap=snap, port=port)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
    )
    agent = None
    try:
        # the agent's INITIAL join has no retry (by design — rejoin backoff
        # only covers established sessions): wait for the head to listen
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            try:
                socket.create_connection(("127.0.0.1", port), timeout=1).close()
                break
            except OSError:
                assert head_a.poll() is None, "head A died before listening"
                time.sleep(0.2)
        agent = _spawn_agent(f"127.0.0.1:{port}")
        line = ""
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            line = head_a.stdout.readline()
            if "READY" in line or head_a.poll() is not None:
                break
        assert "READY" in line, f"head A never became ready (exit={head_a.poll()})"

        # ---- the outage: kill -9 the whole head process ----
        head_a.send_signal(signal.SIGKILL)
        head_a.wait(timeout=10)

        # ---- head B: same address, restored from the snapshot ----
        rt.init(
            num_cpus=1,
            _system_config={"control_snapshot_path": snap},
            head_port=port,
        )
        cluster = rt.get_cluster()
        # durable KV survived the restart
        assert cluster.control.kv.get(b"restart_marker") == b"written-by-head-a"

        # the agent reconnects (with backoff) instead of exiting
        _wait_for_nodes(cluster, 2, timeout=90)
        assert agent.poll() is None, "agent process exited instead of rejoining"

        # a driver-resubmitted task completes on the rejoined agent
        @rt.remote(resources={"remote": 1})
        def f(x):
            return os.getpid(), x * 2

        pid, val = rt.get(f.remote(21), timeout=60)
        assert val == 42 and pid != os.getpid()

        # the named actor's record was restored AND its live instance was
        # reconciled at rejoin: in-process state (n == 1) survived the
        # head's death
        k = rt.get_actor("keeper")
        deadline = time.monotonic() + 60
        while True:
            try:
                assert rt.get(k.bump.remote(), timeout=30) == 2
                break
            except AssertionError:
                raise
            except Exception:
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.5)
    finally:
        if head_a.poll() is None:
            head_a.kill()
            head_a.wait(timeout=10)
        if agent is not None and agent.poll() is None:
            agent.kill()
            agent.wait(timeout=10)
        if rt.is_initialized():
            rt.shutdown()


def test_agent_rejoins_same_head_after_transient_disconnect():
    """A dropped control connection (not a dead head) heals: the agent
    reconnects to the SAME head and re-registers; tasks flow again."""
    rt.init(num_cpus=2)
    cluster = rt.get_cluster()
    address = cluster.start_head_service()
    proc = _spawn_agent(address)
    try:
        _wait_for_nodes(cluster, 2)

        @rt.remote(resources={"remote": 1})
        def f():
            return "on-agent"

        assert rt.get(f.remote(), timeout=60) == "on-agent"

        # sever the control connection from the head side
        for conn in cluster.head_service.server.connections():
            conn.close()

        # the agent must rejoin as a live node (same process, same node id)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            live = [n for n in cluster.nodes.values() if not n.dead]
            if len(live) >= 2:
                break
            time.sleep(0.1)
        live = [n for n in cluster.nodes.values() if not n.dead]
        assert len(live) >= 2, "agent never rejoined after the disconnect"
        assert proc.poll() is None, "agent process exited on transient disconnect"

        assert rt.get(f.remote(), timeout=60) == "on-agent"
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)
        rt.shutdown()


HEAD_RUNNER_LOAD = """
import os, sys, time
sys.path.insert(0, {repo!r})
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
import ray_tpu as rt
from ray_tpu.util import collective
rt.init(num_cpus=1, _system_config={{"control_snapshot_path": {snap!r}}}, head_port={port})
cluster = rt.get_cluster()
deadline = time.time() + 90
while sum(1 for n in cluster.nodes.values() if not n.dead) < 3:
    if time.time() > deadline:
        raise SystemExit("agents never joined")
    time.sleep(0.1)

@rt.remote(execution="thread")
class Member:
    def __init__(self):
        self.rounds = 0

    def reduce(self, x, rank):
        out = collective.allreduce(np.array([x], np.float32), group_name="restartg", rank=rank)
        self.rounds += 1
        return float(np.asarray(out)[0])

m0 = Member.options(name="m0", resources={{"a": 1}}).remote()
m1 = Member.options(name="m1", resources={{"b": 1}}).remote()
collective.create_collective_group([m0, m1], 2, [0, 1], group_name="restartg")
a = m0.reduce.remote(1.0, 0)
b = m1.reduce.remote(2.0, 1)
assert rt.get(a, timeout=60) == 3.0 and rt.get(b, timeout=60) == 3.0

@rt.remote
def slow(i):
    time.sleep(0.5)
    return i

# the 50-task stream, half per agent, all in flight when the head dies
refs = [slow.options(resources={{"a" if i % 2 else "b": 0.01}}).remote(i) for i in range(50)]
cluster.control.save_snapshot({snap!r})
print("READY", flush=True)
time.sleep(600)
"""


@pytest.mark.full
def test_head_restart_under_load_5x():
    """Round-4 VERDICT item 7: kill -9 the head while 2 agents run a
    50-task in-flight stream and hold an open collective group; the
    restarted head must (a) get both agents back, (b) run a fresh 50-task
    stream to completion (no wedged state from the orphaned in-flight
    work — their owner died with head A, so the agents must DRAIN them,
    not resubmit work nobody owns), (c) re-rendezvous the surviving named
    actors' collective group under a bumped epoch.  Looped 5x: a restart
    path that works 4 times out of 5 is a restart path that doesn't work."""
    for attempt in range(5):
        _run_restart_under_load(attempt)


def _run_restart_under_load(attempt):
    import numpy as np

    from ray_tpu.util import collective

    port = _free_port()
    import tempfile

    with tempfile.TemporaryDirectory() as tmp:
        snap = os.path.join(tmp, "control.snap")
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
        env["JAX_PLATFORMS"] = "cpu"

        head_a = subprocess.Popen(
            [sys.executable, "-c", HEAD_RUNNER_LOAD.format(repo=REPO_ROOT, snap=snap, port=port)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        )
        agents = []
        try:
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                try:
                    socket.create_connection(("127.0.0.1", port), timeout=1).close()
                    break
                except OSError:
                    assert head_a.poll() is None, "head A died before listening"
                    time.sleep(0.2)
            agents.append(_spawn_agent(f"127.0.0.1:{port}", extra_resources='{"a": 4}'))
            agents.append(_spawn_agent(f"127.0.0.1:{port}", extra_resources='{"b": 4}'))
            line = ""
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                line = head_a.stdout.readline()
                if "READY" in line or head_a.poll() is not None:
                    break
            assert "READY" in line, f"head A never ready (attempt {attempt}, exit={head_a.poll()})"

            # the stream is in flight NOW (50 x 0.5s over 2 agents): kill
            time.sleep(1.0)
            head_a.send_signal(signal.SIGKILL)
            head_a.wait(timeout=10)

            rt.init(
                num_cpus=1,
                _system_config={"control_snapshot_path": snap},
                head_port=port,
            )
            cluster = rt.get_cluster()
            _wait_for_nodes(cluster, 3, timeout=90)
            for agent in agents:
                assert agent.poll() is None, "an agent exited instead of rejoining"

            # (b) a fresh 50-task stream completes on the rejoined agents
            @rt.remote
            def quick(i):
                return i * 2

            refs = [
                quick.options(resources={"a" if i % 2 else "b": 0.01}).remote(i)
                for i in range(50)
            ]
            assert rt.get(refs, timeout=120) == [i * 2 for i in range(50)]

            # (c) the named actors survived (live instances reconciled) and
            # the group re-rendezvouses under a NEW epoch
            m0, m1 = rt.get_actor("m0"), rt.get_actor("m1")
            collective.create_collective_group([m0, m1], 2, [0, 1], group_name="restartg")
            a = m0.reduce.remote(10.0, 0)
            b = m1.reduce.remote(20.0, 1)
            assert rt.get(a, timeout=90) == 30.0, f"attempt {attempt}"
            assert rt.get(b, timeout=90) == 30.0

            # orphaned in-flight tasks drained: agent resources free again
            # (each named Member actor permanently holds 1 of its resource,
            # so fully-drained means 3 of 4 available per agent)
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                avail = rt.available_resources()
                if avail.get("a", 0) >= 2.9 and avail.get("b", 0) >= 2.9:
                    break
                time.sleep(0.5)
            avail = rt.available_resources()
            assert avail.get("a", 0) >= 2.9 and avail.get("b", 0) >= 2.9, avail
        finally:
            if head_a.poll() is None:
                head_a.kill()
                head_a.wait(timeout=10)
            for agent in agents:
                if agent.poll() is None:
                    agent.kill()
                    agent.wait(timeout=10)
            if rt.is_initialized():
                rt.shutdown()
