"""Cluster hardware telemetry: sampler probes over a faked /proc tree,
head-side time-series rings, Prometheus exposition round-trip, and the
/metrics + /api/timeseries + `top` surfaces against a live cluster."""

import json
import time
import urllib.request

import pytest

import ray_tpu as rt
from ray_tpu.runtime.hw_sampler import HardwareSampler
from ray_tpu.util import metrics as metrics_mod
from ray_tpu.util import prometheus
from ray_tpu.util.timeseries import TimeSeriesStore


# --------------------------------------------------------------- sampler

def _write(path, text):
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(text)


def _fake_proc(tmp_path, busy, total, pid_ticks):
    """Minimal /proc with one aggregate cpu line and one worker pid."""
    idle = total - busy
    _write(tmp_path / "proc" / "stat",
           f"cpu  {busy} 0 0 {idle} 0 0 0 0 0 0\n"
           "cpu0 0 0 0 0 0 0 0 0 0 0\n")
    _write(tmp_path / "proc" / "meminfo",
           "MemTotal:       16384 kB\n"
           "MemFree:         4096 kB\n"
           "MemAvailable:    8192 kB\n")
    half = pid_ticks // 2
    _write(tmp_path / "proc" / "4242" / "stat",
           f"4242 (worker main) S 1 1 1 0 -1 4194304 0 0 0 0 "
           f"{half} {pid_ticks - half} 0 0 20 0 1 0 0 0 0\n")
    _write(tmp_path / "proc" / "4242" / "statm",
           "10000 2500 500 1 0 9000 0\n")


def _fake_cgroup(tmp_path, usage_usec):
    cg = tmp_path / "cg"
    _write(cg / "cpu.stat",
           f"usage_usec {usage_usec}\nuser_usec 1\nsystem_usec 1\n")
    _write(cg / "memory.current", "123456\n")
    _write(cg / "cpu.pressure",
           "some avg10=1.50 avg60=0.80 avg300=0.10 total=12345\n")
    _write(cg / "memory.pressure",
           "some avg10=0.25 avg60=0.10 avg300=0.00 total=99\n")
    return str(cg)


def test_hw_sampler_fake_proc_tree(tmp_path):
    import os
    hz = os.sysconf("SC_CLK_TCK")
    page = os.sysconf("SC_PAGE_SIZE")
    clock = [100.0]
    _fake_proc(tmp_path, busy=200, total=1000, pid_ticks=0)
    cg = _fake_cgroup(tmp_path, usage_usec=1_000_000)
    sampler = HardwareSampler(
        procfs=str(tmp_path / "proc"), cgroup_dir=cg,
        workers=lambda: [{"worker_id": "deadbeef" * 4, "pid": 4242,
                          "state": "actor"}],
        arena_stats=lambda: {"bytes_used": 10, "capacity": 100,
                             "num_objects": 2, "total_evicted": 1},
        clock=lambda: clock[0])

    first = {s["metric"]: s for s in sampler.sample()}
    # deltas need a prior pass: no percentages yet, levels present
    assert "node_cpu_percent" not in first
    assert "worker_cpu_percent" not in first
    assert first["node_mem_total_bytes"]["value"] == 16384 * 1024
    assert first["node_mem_used_bytes"]["value"] == (16384 - 8192) * 1024
    assert first["worker_rss_bytes"]["value"] == 2500 * page
    assert first["worker_rss_bytes"]["tags"] == {
        "worker": "deadbeefdead", "state": "actor"}
    assert first["object_store_used_bytes"]["value"] == 10
    assert first["object_store_capacity_bytes"]["value"] == 100
    assert first["object_store_num_objects"]["value"] == 2
    assert first["object_store_evictions"]["value"] == 1
    assert first["cgroup_mem_current_bytes"]["value"] == 123456
    assert first["cgroup_cpu_pressure_avg10"]["value"] == 1.50
    assert first["cgroup_memory_pressure_avg10"]["value"] == 0.25
    assert all("ts" in s for s in first.values())

    # advance 2s of wall clock: node busy +200/+800 ticks -> 25%,
    # worker +hz ticks over 2s -> 50%, cgroup +1s of cpu over 2s -> 50%
    clock[0] += 2.0
    _fake_proc(tmp_path, busy=400, total=1800, pid_ticks=2 * hz)
    _fake_cgroup(tmp_path, usage_usec=2_000_000)
    second = {s["metric"]: s for s in sampler.sample()}
    assert second["node_cpu_percent"]["value"] == 25.0
    assert second["worker_cpu_percent"]["value"] == pytest.approx(
        100.0, abs=0.5)
    assert second["cgroup_cpu_percent"]["value"] == pytest.approx(
        50.0, abs=0.5)

    # a worker that exits is pruned from the delta table
    sampler._workers = lambda: []
    sampler.sample()
    assert sampler._prev_pid_ticks == {}


def test_hw_sampler_probe_isolation(tmp_path, caplog):
    """One raising probe loses only its own gauges for the pass — the
    rest of the batch still lands — and it warns once, not per period."""
    import logging
    _fake_proc(tmp_path, busy=200, total=1000, pid_ticks=0)
    sampler = HardwareSampler(procfs=str(tmp_path / "proc"))

    def boom():
        raise RuntimeError("probe exploded")

    sampler._node_cpu = boom  # injected fault in the first probe
    with caplog.at_level(logging.WARNING, "ray_tpu.runtime.hw_sampler"):
        first = {s["metric"] for s in sampler.sample()}
        second = {s["metric"] for s in sampler.sample()}
    # other probes survived both passes
    assert "node_mem_total_bytes" in first
    assert "node_mem_total_bytes" in second
    warnings = [r for r in caplog.records if "node_cpu" in r.getMessage()]
    assert len(warnings) == 1  # warn-once, repeats suppressed


def test_hw_sampler_pid_reuse_drops_sample(tmp_path):
    """pid reused between passes (cpu tick counter restarts near 0) must
    DROP the sample — never emit a huge-negative or garbage delta — and
    the fresh baseline seeds the next pass normally."""
    import os
    hz = os.sysconf("SC_CLK_TCK")
    clock = [100.0]
    _fake_proc(tmp_path, busy=200, total=1000, pid_ticks=50 * hz)
    sampler = HardwareSampler(
        procfs=str(tmp_path / "proc"),
        workers=lambda: [{"worker_id": "w1", "pid": 4242, "state": "a"}],
        clock=lambda: clock[0])
    sampler.sample()  # baseline at 50*hz ticks

    # new process under the same pid: ticks restarted from ~0
    clock[0] += 2.0
    _fake_proc(tmp_path, busy=400, total=1800, pid_ticks=1 * hz)
    reused = {s["metric"] for s in sampler.sample()}
    assert "worker_cpu_percent" not in reused  # dropped, not garbage
    # but a fresh baseline was recorded: the NEXT delta is valid again
    clock[0] += 2.0
    _fake_proc(tmp_path, busy=600, total=2600, pid_ticks=3 * hz)
    third = {s["metric"]: s for s in sampler.sample()}
    assert third["worker_cpu_percent"]["value"] == pytest.approx(
        100.0, abs=0.5)


def test_hw_sampler_cpu_percent_clamped(tmp_path):
    """A tick-counter hiccup can't graph a 4000%-CPU worker: the emitted
    percentage is clamped to 100 * ncpu."""
    import os
    hz = os.sysconf("SC_CLK_TCK")
    clock = [100.0]
    _fake_proc(tmp_path, busy=200, total=1000, pid_ticks=0)
    sampler = HardwareSampler(
        procfs=str(tmp_path / "proc"),
        workers=lambda: [{"worker_id": "w1", "pid": 4242, "state": "a"}],
        clock=lambda: clock[0])
    sampler.sample()
    # 1000*hz ticks in 2s of wall clock => 50000% uncapped
    clock[0] += 2.0
    _fake_proc(tmp_path, busy=400, total=1800, pid_ticks=1000 * hz)
    got = {s["metric"]: s for s in sampler.sample()}
    assert got["worker_cpu_percent"]["value"] <= 100.0 * sampler._ncpu


# ------------------------------------------------------------------ rings

def test_timeseries_ring_eviction():
    store = TimeSeriesStore(maxlen=4, max_series=3)
    for i in range(10):
        store.append("nodeA", "cpu", float(i), ts=1000.0 + i)
    (series,) = store.dump()
    # ring keeps exactly the newest maxlen points, oldest first
    assert [v for _, v in series["points"]] == [6.0, 7.0, 8.0, 9.0]
    assert [t for t, _ in series["points"]] == [1006.0, 1007.0,
                                                1008.0, 1009.0]

    # distinct tag sets are distinct series; exceeding max_series evicts
    # the least-recently-appended whole series (nodeA/cpu is oldest)
    store.append("nodeB", "cpu", 1.0, ts=2000.0)
    store.append("nodeB", "mem", 2.0, ts=2000.0)
    store.append("nodeB", "cpu", 3.0, ts=2001.0, tags={"worker": "w1"})
    assert store.num_series() == 3
    assert store.dump(node="nodeA") == []
    # filters: node prefix + exact metric + last-N
    assert len(store.dump(node="nodeB", metric="cpu")) == 2
    store.append("nodeB", "cpu", 4.0, ts=2002.0)
    (s,) = [r for r in store.dump(node="nodeB", metric="cpu", last=1)
            if not r["tags"]]
    assert s["points"] == [(2002.0, 4.0)]

    # latest(): newest point per series, age cutoff drops stale series
    latest = store.latest()
    assert {(s["metric"], s["value"]) for s in latest} == {
        ("cpu", 4.0), ("cpu", 3.0), ("mem", 2.0)}
    assert store.latest(max_age_s=0.001) == []  # ts 2002 is ancient

    # ingest skips malformed entries instead of raising
    n = store.ingest("nodeC", [{"metric": "ok", "value": 1.0},
                               {"value": 2.0}, "junk", None,
                               {"metric": "bad", "value": "NaNsense"}])
    assert n >= 1
    assert store.dump(node="nodeC", metric="ok")


# ------------------------------------------------------------- prometheus

def test_prometheus_exposition_golden_round_trip():
    metrics_mod.clear_registry()
    try:
        c = metrics_mod.Counter("reqs_total", description="total requests",
                                tag_keys=("route",))
        c.inc(3, tags={"route": "/a"})
        c.inc(2, tags={"route": '/b "quoted"\nline'})
        g = metrics_mod.Gauge("temp", description="temperature")
        g.set(36.6)
        h = metrics_mod.Histogram("lat", description="latency",
                                  boundaries=(0.1, 1.0, 10.0),
                                  tag_keys=("op",))
        for v in (0.05, 0.5, 0.5, 5.0, 50.0):
            h.observe(v, tags={"op": "get"})
        agg = metrics_mod.aggregate({"w0": metrics_mod.snapshot(),
                                     "w1": metrics_mod.snapshot()})
    finally:
        metrics_mod.clear_registry()
    hw = [{"node": "a" * 32, "metric": "node_cpu_percent", "tags": {},
           "ts": 1.0, "value": 12.5},
          {"node": "a" * 32, "metric": "worker_rss_bytes",
           "tags": {"worker": "w12", "state": "idle"},
           "ts": 1.0, "value": 4096.0}]
    text = prometheus.render(agg, hw)

    fams = prometheus.parse(text)
    assert fams["reqs_total"]["type"] == "counter"
    by_route = {s[1]["route"]: s[2]
                for s in fams["reqs_total"]["samples"]}
    # two-worker aggregate sums counters; escaped label round-trips
    assert by_route["/a"] == 6.0
    assert by_route['/b "quoted"\nline'] == 4.0
    assert fams["temp"]["samples"][0][2] == 36.6

    assert fams["lat"]["type"] == "histogram"
    buckets = {s[1]["le"]: s[2] for s in fams["lat"]["samples"]
               if s[0] == "lat_bucket"}
    # per-bucket counts (1,2,1,1) x2 workers -> CUMULATIVE 2,6,8; +Inf=n
    assert buckets == {"0.1": 2.0, "1": 6.0, "10": 8.0, "+Inf": 10.0}
    le_order = [s[2] for s in fams["lat"]["samples"]
                if s[0] == "lat_bucket"]
    assert le_order == sorted(le_order), "buckets must be cumulative"
    (count,) = [s[2] for s in fams["lat"]["samples"] if s[0] == "lat_count"]
    (total,) = [s[2] for s in fams["lat"]["samples"] if s[0] == "lat_sum"]
    assert count == 10.0
    assert total == pytest.approx(2 * sum((0.05, 0.5, 0.5, 5.0, 50.0)))

    # hardware series render as gauges labeled by node + own tags
    assert fams["node_cpu_percent"]["samples"] == [
        ("node_cpu_percent", {"node": "a" * 12}, 12.5)]
    (rss,) = fams["worker_rss_bytes"]["samples"]
    assert rss[1] == {"node": "a" * 12, "worker": "w12", "state": "idle"}

    # every non-comment line must match the exposition grammar (parse
    # raises otherwise) and names must be prometheus-safe
    assert prometheus.sanitize_name("serve latency (s)") == \
        "serve_latency__s_"


def test_prometheus_histogram_tag_escaping_round_trip():
    """Histogram TAG values with every escape-worthy character survive
    render -> parse intact on bucket/sum/count lines alike (the serving
    histograms carry deployment/outcome tags from user-chosen names)."""
    metrics_mod.clear_registry()
    nasty_dep = 'llm "v2"\\canary\nblue'
    nasty_out = 'time\\out "hard"'
    try:
        h = metrics_mod.Histogram(
            "probe_latency_seconds", description="escape probe",
            boundaries=(0.1, 1.0), tag_keys=("deployment", "outcome"))
        h.observe(0.05, tags={"deployment": nasty_dep,
                              "outcome": nasty_out})
        h.observe(5.0, tags={"deployment": nasty_dep,
                             "outcome": nasty_out})
        agg = metrics_mod.aggregate({"w0": metrics_mod.snapshot()})
    finally:
        metrics_mod.clear_registry()
    fams = prometheus.parse(prometheus.render(agg))
    samples = fams["probe_latency_seconds"]["samples"]
    assert samples, fams
    for name, labels, _ in samples:
        assert labels["deployment"] == nasty_dep, (name, labels)
        assert labels["outcome"] == nasty_out, (name, labels)
    buckets = {s[1]["le"]: s[2] for s in samples
               if s[0] == "probe_latency_seconds_bucket"}
    assert buckets == {"0.1": 1.0, "1": 1.0, "+Inf": 2.0}


# ------------------------------------------------- live cluster surfaces

@pytest.fixture(scope="module")
def cluster_rt():
    rt.init(num_cpus=2, _system_config={
        "object_store_memory_bytes": 64 * 1024 * 1024,
        "metrics_export_period_s": 0.2,
        "hw_sampler_period_s": 0.3,
    })
    yield rt
    rt.shutdown()


def _get(url, timeout=30):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.headers.get("Content-Type", ""), r.read()


def test_metrics_endpoint_smoke(cluster_rt):
    """Acceptance: GET /metrics returns valid exposition text containing
    the submit_to_start histogram (cumulative buckets + _sum/_count) and
    at least one per-node hardware gauge."""
    from ray_tpu.core.worker import global_worker
    from ray_tpu.dashboard import Dashboard

    @rt.remote
    def work(i):
        return i * 2

    assert rt.get([work.remote(i) for i in range(8)], timeout=60) == \
        [i * 2 for i in range(8)]

    dash = Dashboard(global_worker.backend.head_addr)
    base = f"http://127.0.0.1:{dash.port}"
    try:
        # poll: worker telemetry flush (0.2s) and the node's hardware
        # sampler (0.3s, needs 2 passes for CPU%) land asynchronously
        deadline = time.monotonic() + 30
        fams = {}
        while time.monotonic() < deadline:
            ctype, body = _get(f"{base}/metrics")
            assert "text/plain" in ctype and "version=0.0.4" in ctype
            fams = prometheus.parse(body.decode())
            if "submit_to_start" in fams and any(
                    f in fams for f in ("node_cpu_percent",
                                        "worker_rss_bytes",
                                        "node_mem_used_bytes")):
                break
            time.sleep(0.3)
        assert fams.get("submit_to_start", {}).get("type") == "histogram", \
            f"families: {sorted(fams)}"
        samples = fams["submit_to_start"]["samples"]
        buckets = [(s[1]["le"], s[2]) for s in samples
                   if s[0] == "submit_to_start_bucket"]
        assert buckets, samples
        vals = [v for _, v in buckets]
        assert vals == sorted(vals), "bucket counts must be cumulative"
        assert buckets[-1][0] == "+Inf"
        (n,) = [s[2] for s in samples if s[0] == "submit_to_start_count"]
        assert n >= 8 and buckets[-1][1] == n
        assert any(s[0] == "submit_to_start_sum" for s in samples)

        hw = [f for f in ("node_cpu_percent", "worker_rss_bytes",
                          "node_mem_used_bytes") if f in fams]
        assert hw, f"no hardware gauge exported: {sorted(fams)}"
        for fam in hw:
            for s in fams[fam]["samples"]:
                assert s[1].get("node"), s

        # /api/timeseries: full rings as JSON, plus filtered views
        _, body = _get(f"{base}/api/timeseries")
        series = json.loads(body)
        assert isinstance(series, list) and series
        row = series[0]
        assert {"node", "metric", "tags", "points"} <= set(row)
        assert all(len(p) == 2 for p in row["points"])
        metric = row["metric"]
        _, body = _get(f"{base}/api/timeseries?metric={metric}&last=1")
        filtered = json.loads(body)
        assert filtered and all(r["metric"] == metric and
                                len(r["points"]) == 1 for r in filtered)
        _, body = _get(f"{base}/api/timeseries?latest=1")
        latest = json.loads(body)
        assert latest and all("value" in r and "ts" in r for r in latest)
    finally:
        dash.stop()


def test_timeseries_dump_and_top_two_node_e2e():
    """timeseries_dump aggregates rings from BOTH node daemons, and the
    `top` CLI renders a node/worker table against the live cluster."""
    import io
    import os
    from contextlib import redirect_stdout

    from ray_tpu.core import config as config_mod
    from ray_tpu.runtime.cluster_backend import start_head, start_node
    from ray_tpu.runtime.protocol import RpcClient, RpcError
    from ray_tpu.scripts import cli

    session = os.urandom(4).hex()
    head_proc, address = start_head(session)
    # spawned daemons inherit GlobalConfig — tighten the sampler period
    # just for the children, then restore
    old_period = config_mod.GlobalConfig.hw_sampler_period_s
    config_mod.GlobalConfig.hw_sampler_period_s = 0.3
    try:
        nodes = [start_node(address, session, resources={"CPU": 1.0})
                 for _ in range(2)]
    finally:
        config_mod.GlobalConfig.hw_sampler_period_s = old_period
    probe = RpcClient(address, name="telemetry-e2e")
    try:
        deadline = time.monotonic() + 60
        sampled_nodes = set()
        while time.monotonic() < deadline:
            try:
                rows = probe.call("timeseries_dump",
                                  {"metric": "node_mem_used_bytes"},
                                  timeout=5)
                sampled_nodes = {r["node"] for r in rows}
            except RpcError:
                sampled_nodes = set()
            if len(sampled_nodes) >= 2:
                break
            time.sleep(0.3)
        assert len(sampled_nodes) >= 2, \
            f"both daemons must push hardware samples: {sampled_nodes}"
        # ring points accumulate over successive sampler periods
        (ring,) = probe.call("timeseries_dump",
                             {"node": sorted(sampled_nodes)[0],
                              "metric": "node_mem_used_bytes"}, timeout=5)
        assert len(ring["points"]) >= 1

        # a frame is one reading of the head's tables: wait for the one
        # that counts both daemons (how soon is the machine's timing)
        deadline = time.monotonic() + 60
        while True:
            buf = io.StringIO()
            with redirect_stdout(buf):
                assert cli.main(["top", "--address", address]) == 0
            out = buf.getvalue()
            if "nodes 2/2" in out or time.monotonic() > deadline:
                break
            time.sleep(0.3)
        assert "NODE" in out and "MEM" in out
        for nid in sampled_nodes:
            assert nid[:12] in out, out
        assert "nodes 2/2" in out, out
    finally:
        probe.close()
        for p in nodes:
            p.terminate()
        head_proc.terminate()
        # a daemon stops its workers first (seconds for one that a loaded
        # machine is still starting): nothing here asserts how fast
        for p in nodes:
            p.wait(timeout=60)
        head_proc.wait(timeout=60)
