"""rt.shutdown() returns when the cluster's processes are GONE.

runtime/node.py:stop sends its workers SIGTERM, gives them a grace of 3 s
together, then kills; runtime/cluster_backend.py:shutdown does the same to
the daemons it started. Both then WAIT for what they killed: a worker that
holds a chip and gigabytes of weights takes a while to die, and a caller
that exits right after rt.shutdown() (benchmark/run.py) must not leave it
dying behind (PERF.md, PR 63).
"""

import os
import signal
import time

import ray_tpu as rt


def _descendants(root: int) -> set:
    """pids of every live process below ``root`` (from /proc)."""
    parent = {}
    for name in os.listdir("/proc"):
        if not name.isdigit():
            continue
        try:
            with open(f"/proc/{name}/stat") as f:
                stat = f.read()
        except OSError:
            continue
        state, ppid = stat.rsplit(")", 1)[1].split()[:2]
        if state != "Z":
            parent[int(name)] = int(ppid)
    found, todo = set(), [root]
    while todo:
        at = todo.pop()
        for pid, ppid in parent.items():
            if ppid == at and pid not in found:
                found.add(pid)
                todo.append(pid)
    return found


def test_shutdown_returns_after_a_worker_that_ignores_sigterm_is_gone():
    before = _descendants(os.getpid())
    rt.init(num_cpus=2, _system_config={"worker_pool_prestart": 1})
    try:
        @rt.remote
        def deaf():
            # SIGTERM does nothing here: only the kill after the grace ends
            # it (libc's signal(): a task does not run on the main thread,
            # where alone Python's own may be called)
            import ctypes
            ctypes.CDLL("libc.so.6").signal(int(signal.SIGTERM), 1)  # SIG_IGN
            return os.getpid()

        worker = rt.get(deaf.remote(), timeout=120)
        started = _descendants(os.getpid()) - before
        assert worker in started and len(started) >= 3   # head, node, worker
    finally:
        t0 = time.monotonic()
        rt.shutdown()
        took = time.monotonic() - t0
    alive = _descendants(os.getpid()) & started
    assert not alive, f"still alive after rt.shutdown(): {sorted(alive)}"
    assert took > 2.5, f"the worker did not outlast the grace ({took:.1f} s)"
