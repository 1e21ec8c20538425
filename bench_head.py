"""Head (GCS) scale-ceiling microbench.

The cluster control plane is deliberately single-head (a TPU pod has a
bounded host count — SURVEY §2.1's syncer row is answered with central
accounting instead of P2P gossip). That design has a ceiling; this bench
MEASURES it instead of leaving it unknown (round-2 verdict, Weak #4):

  - node registration rate (how fast a pod's hosts can join),
  - health-heartbeat capacity (pings/s the head absorbs),
  - KV read/write throughput (function export + discovery path),
  - lease grant/release cycle rate over registered fake nodes,

all against a real Head process over real sockets, from T client
threads. Prints one JSON line per metric, so the ceiling is a measured
fact, not a guess.
"""

import json
import os
import threading
import time

from ray_tpu.runtime.head import Head
from ray_tpu.runtime.protocol import RpcClient, RpcServer


def fake_node_server() -> RpcServer:
    """A node daemon stand-in that answers the head's lease RPCs
    instantly, so the lease metric isolates HEAD-side cost."""
    counter = [0]

    def lease_worker(p, ctx):
        counter[0] += 1
        return {"worker_id": counter[0].to_bytes(8, "little"),
                "worker_addr": "127.0.0.1:1"}

    return RpcServer({
        "lease_worker": lease_worker,
        "return_worker": lambda p, c: True,
        "ping": lambda p, c: "pong",
    }, max_workers=2, name="fake-node")


def timed(fn, n_threads: int, seconds: float = 2.0) -> float:
    """Run fn(thread_idx, iter_idx) from n_threads for ~seconds; return
    aggregate calls/s."""
    stop = time.monotonic() + seconds
    counts = [0] * n_threads

    def loop(t):
        i = 0
        while time.monotonic() < stop:
            fn(t, i)
            i += 1
        counts[t] = i

    threads = [threading.Thread(target=loop, args=(t,))
               for t in range(n_threads)]
    t0 = time.monotonic()
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    dt = time.monotonic() - t0
    return sum(counts) / dt


def profiled(label: str, out: list, fn, n_threads: int) -> float:
    """timed() with a concurrent in-process burst capture: Head() lives
    in THIS process, so the burst's hot frames ARE the head policy's —
    the frame-level evidence behind the measured ceiling (stack_profiler
    burst mode; same data 'profile --record' returns cluster-wide)."""
    from ray_tpu.util.stack_profiler import burst_capture, top_frames
    cap: dict = {}

    def _capture():
        cap["export"] = burst_capture(1.5, hz=199.0)

    th = threading.Thread(target=_capture, name=f"profile-{label}")
    th.start()
    rate = timed(fn, n_threads)
    th.join(timeout=10.0)
    e = cap.get("export") or {}
    samples = int(e.get("samples") or 0)
    out.append({"metric": f"head_profile_{label}",
                "samples": samples,
                "top_frames": [
                    {"frame": r["frame"], "self": r["self"],
                     "self_pct": round(100.0 * r["self"] / max(1, samples),
                                       1)}
                    for r in top_frames(e.get("stacks") or {}, 5)]})
    return rate


def main() -> None:
    head = Head()
    addr = head.address
    T = min(8, (os.cpu_count() or 2) * 4)
    clients = [RpcClient(addr, name=f"bench-{t}") for t in range(T)]

    out = []

    # --- heartbeat/ping capacity (before table bloat)
    rate = timed(lambda t, i: clients[t].call("ping"), T)
    out.append({"metric": "head_pings_per_s", "value": round(rate, 1),
                "note": f"{T} concurrent clients; health checks cost one "
                        f"of these per node per period"})

    # --- KV write+read (function export / discovery path)
    def kv_cycle(t, i):
        clients[t].call("kv_put", {"key": f"b:{t}:{i % 64}",
                                   "value": b"x" * 256})
        clients[t].call("kv_get", {"key": f"b:{t}:{i % 64}"})
    rate = profiled("kv_cycle", out, kv_cycle, T)
    out.append({"metric": "head_kv_write_read_cycles_per_s",
                "value": round(rate, 1),
                "note": "256B values; one cycle = put + get (pickle RPC "
                        "path through the Python handlers)"})

    # --- KV via the native fast path (served inside the head's C event
    # loop; no Python, no pickle on the head — how ClusterBackend clients
    # actually talk to a native head)
    if hasattr(clients[0], "call_fast"):
        from ray_tpu.runtime import protocol_native as pn

        def kv_fast_cycle(t, i):
            key = f"f:{t}:{i % 64}".encode()
            clients[t].call_fast(pn.FAST_PUT, key, b"x" * 256, flags=1)
            clients[t].call_fast(pn.FAST_GET, key)
        rate = timed(kv_fast_cycle, T)
        out.append({"metric": "head_kv_fast_write_read_cycles_per_s",
                    "value": round(rate, 1),
                    "note": "same cycle through the C-loop fast path"})

    # --- node registration: M nodes backed by a handful of live fake
    # servers (addresses must answer the health loop + lease RPCs)
    M = 200
    servers = [fake_node_server() for _ in range(8)]
    t0 = time.monotonic()
    for i in range(M):
        clients[i % T].call("register_node", {
            "node_id": f"fake-{i:04d}",
            "address": servers[i % len(servers)].address,
            "shm_name": f"/fake_{i}", "resources": {"CPU": 8.0}})
    reg_rate = M / (time.monotonic() - t0)
    out.append({"metric": "head_node_registrations_per_s",
                "value": round(reg_rate, 1),
                "note": f"{M} node registrations, {T} client conns"})

    # --- lease grant/release across the registered node table
    def lease_cycle(t, i):
        r = clients[t].call("request_lease", {
            "resources": {"CPU": 1.0}, "requester": f"bench-{t}"})
        if r and r.get("lease_id"):
            clients[t].call("release_lease", {"lease_id": r["lease_id"]})
    rate = profiled("lease_cycle", out, lease_cycle, T)
    out.append({"metric": "head_lease_cycles_per_s",
                "value": round(rate, 1),
                "note": f"grant+release cycles over a {M}-node table "
                        "(scheduler + accounting + node lease RPC to a "
                        "stub server on every cycle)"})

    for line in out:
        print(json.dumps(line))
    for c in clients:
        c.close()
    for srv in servers:
        srv.stop()
    head.stop()


if __name__ == "__main__":
    main()
