"""Async actors + streaming generators.

Mirrors the reference's coverage (reference: python/ray/tests/test_asyncio.py
async actor concurrency, test_streaming_generator.py incremental
consumption): an asyncio actor interleaves many in-flight calls on one
process; a streaming task's yields are consumable before the task ends.
"""

import threading
import time

import pytest


# ---------------------------------------------------------------- async actors

def test_async_actor_concurrent_calls(rtpu_cluster):
    ray_tpu = rtpu_cluster

    @ray_tpu.remote
    class AsyncCounter:
        def __init__(self):
            self.peak = 0
            self.inflight = 0

        async def slow(self, t):
            import asyncio
            self.inflight += 1
            self.peak = max(self.peak, self.inflight)
            await asyncio.sleep(t)
            self.inflight -= 1
            return self.peak

        async def peak_seen(self):
            return self.peak

    a = AsyncCounter.remote()
    ray_tpu.get(a.peak_seen.remote(), timeout=30)  # actor cold-start
    t0 = time.monotonic()
    refs = [a.slow.remote(0.3) for _ in range(10)]
    ray_tpu.get(refs, timeout=30)
    elapsed = time.monotonic() - t0
    # serial execution would take >= 3.0s; concurrent interleave ~0.3s
    assert elapsed < 2.0, f"async calls did not interleave ({elapsed:.2f}s)"
    assert ray_tpu.get(a.peak_seen.remote(), timeout=10) >= 2


def test_async_actor_sync_method_and_errors(rtpu_cluster):
    ray_tpu = rtpu_cluster

    @ray_tpu.remote
    class Mixed:
        async def aget(self):
            return 41

        def sget(self):  # sync method on an async actor runs on the loop
            return 1

        async def boom(self):
            raise ValueError("async-boom")

    m = Mixed.remote()
    assert ray_tpu.get(m.aget.remote(), timeout=30) == 41
    assert ray_tpu.get(m.sget.remote(), timeout=10) == 1
    with pytest.raises(Exception, match="async-boom"):
        ray_tpu.get(m.boom.remote(), timeout=10)


def test_async_actor_max_concurrency_limit(rtpu_cluster):
    ray_tpu = rtpu_cluster

    @ray_tpu.remote(max_concurrency=2)
    class Limited:
        def __init__(self):
            self.inflight = 0
            self.peak = 0

        async def slow(self):
            import asyncio
            self.inflight += 1
            self.peak = max(self.peak, self.inflight)
            await asyncio.sleep(0.1)
            self.inflight -= 1
            return self.peak

    a = Limited.remote()
    peaks = ray_tpu.get([a.slow.remote() for _ in range(8)], timeout=30)
    assert max(peaks) <= 2  # semaphore bounds interleave


# ---------------------------------------------------------------- streaming

def test_streaming_task_incremental(rtpu_cluster):
    ray_tpu = rtpu_cluster

    @ray_tpu.remote(num_returns="streaming")
    def gen():
        import time as _t
        t_yield = _t.time()
        for i in range(3):
            yield i, t_yield
        _t.sleep(5)  # long tail AFTER the yields
        yield 99, t_yield

    g = gen.remote()
    first, t_yield = ray_tpu.get(next(g), timeout=30)
    t_recv = time.time()
    assert first == 0
    # incremental contract: the item is consumable well before the task's
    # 5s tail finishes. Measured from the producer's yield (immune to slow
    # worker spawn under suite load on a 1-CPU host).
    assert t_recv - t_yield < 4.0, f"first item took {t_recv - t_yield:.1f}s"
    assert ray_tpu.get(next(g), timeout=5)[0] == 1
    assert ray_tpu.get(next(g), timeout=5)[0] == 2


def test_streaming_task_completion_and_error(rtpu_cluster):
    ray_tpu = rtpu_cluster

    @ray_tpu.remote(num_returns="streaming")
    def ok():
        yield "a"
        yield "b"

    items = [ray_tpu.get(r, timeout=20) for r in ok.remote()]
    assert items == ["a", "b"]

    @ray_tpu.remote(num_returns="streaming")
    def bad():
        yield 1
        raise RuntimeError("stream-boom")

    g = bad.remote()
    assert ray_tpu.get(next(g), timeout=20) == 1
    with pytest.raises(Exception, match="stream-boom"):
        next(g)


def test_streaming_actor_async_generator(rtpu_cluster):
    ray_tpu = rtpu_cluster

    @ray_tpu.remote
    class Tokens:
        async def stream(self, n):
            import asyncio
            for i in range(n):
                await asyncio.sleep(0.01)
                yield f"tok{i}"

    a = Tokens.remote()
    out = [ray_tpu.get(r, timeout=30)
           for r in a.stream.options(num_returns="streaming").remote(4)]
    assert out == ["tok0", "tok1", "tok2", "tok3"]


# ------------------------------------------------------------------- local mode

def test_async_actor_local_mode(rtpu_local):
    ray_tpu = rtpu_local

    @ray_tpu.remote
    class A:
        async def add(self, x):
            return x + 1

    a = A.remote()
    assert ray_tpu.get(a.add.remote(1), timeout=10) == 2


def test_streaming_local_mode(rtpu_local):
    ray_tpu = rtpu_local
    started = threading.Event()

    @ray_tpu.remote(num_returns="streaming")
    def gen():
        yield 1
        yield 2
        started.set()
        time.sleep(3)
        yield 3

    g = gen.remote()
    assert ray_tpu.get(next(g), timeout=10) == 1
    assert ray_tpu.get(next(g), timeout=10) == 2
    # consumed both items while the task is still sleeping
    assert started.wait(5)
    assert ray_tpu.get(next(g), timeout=10) == 3
    with pytest.raises(StopIteration):
        next(g)


def test_abandoned_stream_items_freed(rtpu_cluster):
    """Dropping a generator mid-stream frees the unconsumed items in the
    owner (memory store entries + refcount records) instead of leaking
    them forever."""
    import gc

    ray_tpu = rtpu_cluster
    from ray_tpu.core.worker import global_worker

    @ray_tpu.remote(num_returns="streaming")
    def burst():
        for i in range(50):
            yield ("x" * 2000, i)

    base_tracked = global_worker.refcounter.num_tracked()
    base_entries = global_worker.memory_store.size()
    for _ in range(3):
        g = burst.remote()
        ray_tpu.get(next(g), timeout=60)  # consume ONE of 50
        # wait for completion so all 50 items have arrived
        deadline = time.monotonic() + 30
        while not g.completed() and time.monotonic() < deadline:
            time.sleep(0.05)
        del g
        gc.collect()
    # allow the cleanup path to run
    time.sleep(0.5)
    gc.collect()
    leaked_tracked = global_worker.refcounter.num_tracked() - base_tracked
    leaked_entries = global_worker.memory_store.size() - base_entries
    assert leaked_tracked <= 6, f"refcount entries leaked: {leaked_tracked}"
    assert leaked_entries <= 6, f"memory-store entries leaked: {leaked_entries}"


def test_streaming_producer_stays_one_item_ahead(rtpu_cluster):
    """Flow control (runtime/worker_main.py: _stream_out): the generator
    is pulled again only once the owner has acknowledged the item before,
    so an owner that is slow to take items in holds the producer at one
    item in flight instead of queueing them all."""
    ray_tpu = rtpu_cluster
    from ray_tpu.core import worker as worker_mod
    server = worker_mod.global_worker.backend.server
    handle = server.handlers["stream_item"]
    gate = threading.Event()
    arrived = []

    def slow(p, ctx):
        arrived.append(p["index"])
        gate.wait(30)
        return handle(p, ctx)

    server.handlers["stream_item"] = slow
    try:
        @ray_tpu.remote(num_returns="streaming")
        def gen():
            import time as _t
            for i in range(4):
                yield i, _t.monotonic()

        g = gen.remote()
        deadline = time.monotonic() + 60
        while not arrived:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        time.sleep(0.5)     # unacknowledged, all four would be here by now
        assert arrived == [1]
        t_open = time.monotonic()
        gate.set()
        items = [ray_tpu.get(r, timeout=30) for r in g]
    finally:
        gate.set()
        server.handlers["stream_item"] = handle
    assert [i for i, _ in items] == [0, 1, 2, 3]
    assert arrived == [1, 2, 3, 4]
    assert items[1][1] >= t_open        # pulled after the acknowledgement
