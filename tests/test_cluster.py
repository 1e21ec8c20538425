"""Multiprocess cluster runtime tests.

Coverage model mirrors the reference's core test suite (reference:
python/ray/tests/test_basic.py, test_actor_failures.py,
test_object_store.py, test_multi_node.py) run against the real runtime:
head + node daemon + worker processes, objects through the C++ shm store,
process kills for fault-tolerance paths.
"""

import os
import signal
import sys
import time
import uuid

import numpy as np
import pytest

import ray_tpu as rt
from ray_tpu.core.worker import global_worker


@pytest.fixture(scope="module")
def cluster_rt():
    rt.init(num_cpus=4, _system_config={
        "object_store_memory_bytes": 128 * 1024 * 1024,
        "worker_pool_prestart": 2,
        "health_check_period_ms": 200,
        "health_check_timeout_ms": 1500,
    })
    yield rt
    rt.shutdown()


# ---------------------------------------------------------------- tasks


def test_task_roundtrip(cluster_rt):
    @rt.remote
    def add(a, b, scale=1):
        return (a + b) * scale

    assert rt.get(add.remote(1, 2), timeout=60) == 3
    assert rt.get(add.remote(1, 2, scale=10), timeout=30) == 30


def test_parallel_tasks(cluster_rt):
    @rt.remote
    def slp(i):
        t0 = time.time()
        time.sleep(0.4)
        return i, t0, time.time()

    out = rt.get([slp.remote(i) for i in range(4)], timeout=60)
    assert [i for i, _, _ in out] == [0, 1, 2, 3]
    # 4 x 0.4s sleeps must overlap across worker processes: by the tasks'
    # own stamps, the time during which ANY of them slept. (The wall time
    # of the get also holds a cold pool's worker spawns, seconds each on a
    # loaded machine: that is the machine's, not the scheduler's.)
    busy, upto = 0.0, 0.0
    for t0, t1 in sorted((t0, t1) for _, t0, t1 in out):
        busy += max(0.0, t1 - max(t0, upto))
        upto = max(upto, t1)
    assert busy < 1.3, f"tasks did not run in parallel: {busy:.2f}s"


def test_parallel_burst_without_cached_leases(cluster_rt):
    """A burst submitted while NO lease is cached must still fan out.

    Regression: transport-level task batching once packed a whole queued
    burst onto the FIRST granted lease, serializing onto one worker what
    belonged on four (a lease is a concurrency slot — the bug survived
    test_parallel_tasks because a warm cached lease changes the timing).
    """
    @rt.remote
    def slp(i):
        time.sleep(0.5)
        return i

    @rt.remote
    def noop(i):
        return i

    # warm the worker POOL to 4 processes (spawn costs seconds on a 1-CPU
    # host and is not what this test measures)...
    rt.get([slp.options(name="warm").remote(i) for i in range(4)],
           timeout=60)
    # ...then let the cached idle leases reap (lease_idle_linger_s=0.5):
    # the workers stay pooled but every task in the next burst depends on
    # a fresh lease grant
    time.sleep(1.2)
    t0 = time.monotonic()
    out = rt.get([slp.remote(i) for i in range(4)], timeout=60)
    dt = time.monotonic() - t0
    assert out == [0, 1, 2, 3]
    assert dt < 1.8, f"burst did not run in parallel: {dt:.2f}s"


def test_large_object_via_shm(cluster_rt):
    arr = np.arange(500_000, dtype=np.float64)
    ref = rt.put(arr)
    oid = ref.id()
    # big values must be sealed in the shm store, not the memory store
    assert global_worker.backend.object_plane.store.contains(oid.binary())
    back = rt.get(ref, timeout=30)
    assert np.array_equal(arr, back)


def test_ref_args_and_nested_refs(cluster_rt):
    @rt.remote
    def double(x):
        return x * 2

    @rt.remote
    def sum_list(refs):
        return sum(rt.get(refs))

    a = rt.put(np.ones(200_000))  # shm-sized
    b = double.remote(a)
    assert float(rt.get(b, timeout=30).sum()) == 400_000.0
    # nested refs inside an inline list argument
    small = [rt.put(i) for i in range(5)]
    assert rt.get(sum_list.remote(small), timeout=30) == 10


def test_task_error_propagation(cluster_rt):
    @rt.remote
    def boom():
        raise ValueError("kapow-task")

    with pytest.raises(Exception, match="kapow-task"):
        rt.get(boom.remote(), timeout=30)


def test_nested_task_submission(cluster_rt):
    @rt.remote
    def inner(x):
        return x + 1

    @rt.remote
    def outer(x):
        return rt.get(inner.remote(x), timeout=30) + 100

    assert rt.get(outer.remote(1), timeout=60) == 102


def test_wait(cluster_rt):
    @rt.remote
    def fast():
        return "fast"

    @rt.remote
    def slow():
        time.sleep(2.0)
        return "slow"

    f, s = fast.remote(), slow.remote()
    ready, pending = rt.wait([f, s], num_returns=1, timeout=10)
    assert ready == [f] and pending == [s]
    assert rt.get(s, timeout=30) == "slow"


def test_refcount_frees_shm_object(cluster_rt):
    arr = np.arange(300_000, dtype=np.float64)
    ref = rt.put(arr)
    key = ref.id().binary()
    store = global_worker.backend.object_plane.store
    rt.get(ref, timeout=30)
    assert store.contains(key)
    del ref
    deadline = time.monotonic() + 10
    while store.contains(key) and time.monotonic() < deadline:
        time.sleep(0.05)
    assert not store.contains(key), "shm object not freed after last ref died"


# ---------------------------------------------------------------- actors


def test_actor_ordered_state(cluster_rt):
    @rt.remote
    class Counter:
        def __init__(self, start=0):
            self.v = start

        def inc(self, d=1):
            self.v += d
            return self.v

    c = Counter.remote(10)
    out = rt.get([c.inc.remote() for _ in range(5)], timeout=60)
    assert out == [11, 12, 13, 14, 15]


def test_named_actor(cluster_rt):
    @rt.remote
    class KV:
        def __init__(self):
            self.d = {}

        def set(self, k, v):
            self.d[k] = v
            return True

        def get(self, k):
            return self.d.get(k)

    name = f"kv-{uuid.uuid4().hex[:6]}"
    a = KV.options(name=name).remote()
    rt.get(a.set.remote("x", 42), timeout=60)
    h = rt.get_actor(name)
    assert rt.get(h.get.remote("x"), timeout=30) == 42
    with pytest.raises(ValueError):
        rt.get_actor("no-such-actor")


def test_actor_handle_in_task(cluster_rt):
    @rt.remote
    class Acc:
        def __init__(self):
            self.v = 0

        def add(self, d):
            self.v += d
            return self.v

    @rt.remote
    def bump(handle, n):
        return rt.get([handle.add.remote(1) for _ in range(n)], timeout=30)

    a = Acc.remote()
    assert rt.get(bump.remote(a, 3), timeout=60) == [1, 2, 3]


def test_actor_creation_error(cluster_rt):
    @rt.remote
    class Broken:
        def __init__(self):
            raise RuntimeError("ctor-fail")

        def m(self):
            return 1

    b = Broken.remote()
    with pytest.raises(rt.exceptions.ActorDiedError, match="ctor-fail"):
        rt.get(b.m.remote(), timeout=60)


def test_actor_method_error(cluster_rt):
    @rt.remote
    class Bad:
        def boom(self):
            raise ValueError("kapow-actor")

        def fine(self):
            return "ok"

    b = Bad.remote()
    with pytest.raises(Exception, match="kapow-actor"):
        rt.get(b.boom.remote(), timeout=60)
    # actor survives an application error
    assert rt.get(b.fine.remote(), timeout=30) == "ok"


def test_kill_actor(cluster_rt):
    @rt.remote
    class P:
        def pid(self):
            return os.getpid()

    p = P.remote()
    rt.get(p.pid.remote(), timeout=60)
    rt.kill(p)
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        try:
            rt.get(p.pid.remote(), timeout=10)
            time.sleep(0.1)
        except rt.exceptions.ActorDiedError:
            return
    pytest.fail("kill() never surfaced ActorDiedError")


# ------------------------------------------------------- fault tolerance


def test_worker_crash_surfaces(cluster_rt):
    @rt.remote(max_retries=0)
    def die():
        os._exit(1)

    with pytest.raises(rt.exceptions.WorkerCrashedError):
        rt.get(die.remote(), timeout=60)


def test_task_retry_on_worker_death(cluster_rt):
    marker = f"/tmp/rtpu_flaky_{uuid.uuid4().hex[:8]}"

    @rt.remote(max_retries=2)
    def flaky(path):
        if not os.path.exists(path):
            open(path, "w").close()
            os._exit(1)
        return "recovered"

    try:
        assert rt.get(flaky.remote(marker), timeout=90) == "recovered"
    finally:
        if os.path.exists(marker):
            os.unlink(marker)


def test_retry_survives_corpse_leases(cluster_rt):
    """Deterministic corpse-window test: kill a pooled worker BEFORE
    submitting, so early leases deterministically name a dead address.
    With per-distinct-address retry accounting + the dead-addr grant
    filter (reference semantics: owner max_retries counts executions,
    task_manager.h:219), max_retries=1 tasks must all still succeed —
    repeated pushes into one corpse must not burn the budget."""
    @rt.remote
    def whoami():
        return os.getpid()

    # warm the pool and learn a victim pid
    pids = set(rt.get([whoami.remote() for _ in range(4)], timeout=90))
    victim = next(iter(pids))
    os.kill(victim, signal.SIGKILL)
    # no settling sleep: submitting IMMEDIATELY is the point — some of
    # these tasks race into the corpse's still-cached leases
    @rt.remote(max_retries=1)
    def ping(i):
        return i * 2

    out = rt.get([ping.remote(i) for i in range(16)], timeout=120)
    assert out == [i * 2 for i in range(16)]


def test_actor_restart_and_exhaustion(cluster_rt):
    @rt.remote(max_restarts=1)
    class Svc:
        def __init__(self):
            self.n = 0

        def pid(self):
            return os.getpid()

        def inc(self):
            self.n += 1
            return self.n

    s = Svc.remote()
    pid1 = rt.get(s.pid.remote(), timeout=60)
    assert rt.get(s.inc.remote(), timeout=30) == 1
    os.kill(pid1, signal.SIGKILL)

    # restarted instance: fresh state, new pid
    val, pid2 = None, None
    deadline = time.monotonic() + 45
    while time.monotonic() < deadline:
        try:
            val = rt.get(s.inc.remote(), timeout=15)
            pid2 = rt.get(s.pid.remote(), timeout=15)
            break
        except rt.exceptions.ActorError:
            time.sleep(0.2)
    assert pid2 is not None and pid2 != pid1
    assert val == 1, "restart must reset actor state"

    # second kill exhausts max_restarts=1 -> permanently dead
    os.kill(pid2, signal.SIGKILL)
    deadline = time.monotonic() + 45
    while time.monotonic() < deadline:
        try:
            rt.get(s.pid.remote(), timeout=15)
            time.sleep(0.2)
        except rt.exceptions.ActorDiedError:
            return
    pytest.fail("actor never became DEAD after exhausting restarts")


def test_chaos_rpc_injection_retries(cluster_rt):
    """First push_task call is chaos-failed; the lease-retry path recovers
    (reference: rpc_chaos.h:23 RAY_testing_rpc_failure)."""
    from ray_tpu.core.config import GlobalConfig
    from ray_tpu.runtime import protocol

    @rt.remote(max_retries=3)
    def ok():
        return "survived"

    GlobalConfig.apply({"testing_rpc_failure": "push_task=1"})
    protocol.reset_chaos()
    try:
        assert rt.get(ok.remote(), timeout=60) == "survived"
    finally:
        GlobalConfig.apply({"testing_rpc_failure": ""})
        protocol.reset_chaos()


# ------------------------------------------------------------ state APIs


def test_cluster_state_apis(cluster_rt):
    res = rt.cluster_resources()
    assert res.get("CPU") == 4.0
    nodes = rt.nodes()
    assert len(nodes) == 1 and nodes[0]["Alive"]
    avail = rt.available_resources()
    assert avail.get("CPU", 0) <= res["CPU"]
    dump = global_worker.backend.state_dump()
    assert "actors" in dump and "nodes" in dump
