"""State API + CLI tests (reference coverage model:
python/ray/tests/test_state_api.py + CLI smoke in test_cli.py)."""

import json
import subprocess
import sys
import uuid

import pytest

import ray_tpu as rt
from ray_tpu.util import state


@pytest.fixture(scope="module")
def state_rt():
    # this PROCESS's compile tracker outlives the test file that started it
    # (an in-process engine does, llm/model.py:StepPrograms), and the first
    # head the process meets is handed whatever it staged: recompile storms
    # of an earlier file of the same pytest worker would be counted beside
    # the one test_compiles_cli_smoke seeds. The driver starts a new one
    from ray_tpu.util import compile_tracker
    compile_tracker.stop_global()
    rt.init(num_cpus=2, _system_config={
        "object_store_memory_bytes": 64 * 1024 * 1024})
    yield rt
    rt.shutdown()


def _cli(*args, address):
    return subprocess.run(
        [sys.executable, "-m", "ray_tpu", *args, "--address", address],
        capture_output=True, text=True, timeout=60,
        env={**__import__("os").environ,
             "PYTHONPATH": __import__("os").path.dirname(
                 __import__("os").path.dirname(rt.__file__))})


def test_state_api_lists(state_rt):
    @rt.remote
    class Marker:
        def ping(self):
            return "pong"

    name = f"m-{uuid.uuid4().hex[:6]}"
    a = Marker.options(name=name).remote()
    rt.get(a.ping.remote(), timeout=60)

    nodes = state.list_nodes()
    assert len(nodes) == 1 and nodes[0]["alive"]
    actors = state.list_actors(state="ALIVE")
    assert any(x["name"].endswith(name) for x in actors)
    s = state.summarize()
    assert s["nodes_alive"] == 1 and s["actors_alive"] >= 1


def test_hist_quantile_and_top_llm_line():
    """`top` derives TTFT/TPOT quantiles from the aggregated serving
    histograms (bucket upper bounds) and MEANS the SLO-attainment gauges
    across workers instead of summing fractions."""
    from ray_tpu.scripts import cli

    metrics = {
        "llm_ttft_seconds": {
            "type": "histogram", "boundaries": (0.01, 0.05, 0.1),
            "values": {
                "a": {"counts": [6, 2, 1, 1], "sum": 0.3, "n": 10},
                "b": {"counts": [4, 1, 0, 0], "sum": 0.05, "n": 5}}},
    }
    # counts sum ACROSS tag values: totals [10, 3, 1, +Inf 1], n=15
    assert cli._hist_quantile(metrics, "llm_ttft_seconds", 0.5) == 0.01
    # p99 lands in +Inf: report the largest finite bound
    assert cli._hist_quantile(metrics, "llm_ttft_seconds", 0.99) == 0.1
    assert cli._hist_quantile(metrics, "absent", 0.5) is None
    assert cli._hist_quantile(
        {"llm_ttft_seconds": {"type": "histogram", "boundaries": (1.0,),
                              "values": {}}},
        "llm_ttft_seconds", 0.5) is None

    metrics.update({
        "llm_tpot_seconds": {
            "type": "histogram", "boundaries": (0.005, 0.01),
            "values": {"a": {"counts": [3, 1, 0], "sum": 0.02, "n": 4}}},
        "llm_decode_tokens_per_s": {"type": "gauge",
                                    "values": {"w0": 120.0}},
        "llm_engine_device_wait_ratio": {"type": "gauge",
                                         "values": {"w0": 0.9, "w1": 0.6}},
        "llm_slo_ttft_attainment": {"type": "gauge",
                                    "values": {"w0": 0.9, "w1": 0.7}},
        "llm_slo_tpot_attainment": {"type": "gauge",
                                    "values": {"w0": 1.0, "w1": 0.5}},
    })

    class FakeClient:
        def call(self, op, payload=None, timeout=None):
            if op == "state_dump":
                return {"nodes": [{"node_id": "n" * 32, "alive": True}],
                        "leases": 0}
            if op == "timeseries_dump":
                return []
            if op == "metrics_dump":
                return metrics
            raise AssertionError(op)

    out = cli._render_top(FakeClient(), "127.0.0.1:1")
    assert "llm: decode 120 tok/s" in out
    assert "dev_wait 75%" in out            # mean over engines, not sum
    assert "ttft p50<=10ms p99<=100ms" in out
    assert "tpot p50<=5.0ms" in out
    assert "slo ttft 80% tpot 75%" in out  # mean, not sum


def _seed_request_records(probe, trace_id):
    """Push two finished flight-recorder records (built by the REAL
    recorder, so the wire shape is authentic) + the router span of the
    slow one's trace into the head's telemetry tables."""
    import time as time_mod

    from ray_tpu.llm.request_log import FlightRecorder

    fr = FlightRecorder(capacity=8, observe_metrics=False)
    fast = fr.start("req-clifast-0", 8, 4, trace_id="")
    fast.note_admit(fast.t0 + 0.001, 0)
    fast.note_chunk(fast.t0 + 0.003, 8, 11)
    t = fast.t0 + 0.005
    fast.note_decode(t, 1)
    for _ in range(3):
        t += 0.002
        fast.note_decode(t, 1)
    fr.finish(fast, t + 0.001, "length")

    slow = fr.start("req-clislow-0", 16, 8, trace_id=trace_id)
    slow.note_admit(slow.t0 + 0.010, 4)
    slow.note_chunk(slow.t0 + 0.040, 16, 12)
    slow.note_stall(slow.t0 + 0.050)
    slow.note_preempt(slow.t0 + 0.055)
    slow.note_admit(slow.t0 + 0.060, 0)
    t = slow.t0 + 0.100
    slow.note_decode(t, 1)
    for _ in range(7):
        t += 0.020
        slow.note_decode(t, 1)
    fr.finish(slow, t + 0.001, "stop")

    now = time_mod.time()
    probe.call("telemetry_push", {
        "worker": "cliworker" + "0" * 23, "node": "clinode" + "0" * 25,
        "llm_requests": fr.drain_export(),
        "events": [{"name": "serve.router::llm.__call__",
                    "kind": "serve_router", "task_id": "",
                    "start": now - 0.2, "end": now, "ok": True,
                    "trace_id": trace_id, "span_id": "a1" * 8,
                    "parent_span_id": ""}],
    }, timeout=10)


def test_requests_cli_and_trace_request_merge(state_rt):
    """`requests` renders per-request timelines from the head's
    aggregated flight-recorder records; `--slowest N` ranks by e2e;
    `trace --request RID` merges the router span tree with the record's
    timeline (acceptance: trace-linked request view end-to-end)."""
    import io
    from contextlib import redirect_stdout

    from ray_tpu.core.worker import global_worker
    from ray_tpu.scripts import cli

    address = global_worker.backend.head_addr
    trace_id = "feedc0de" * 4
    _seed_request_records(global_worker.backend.head, trace_id)

    buf = io.StringIO()
    with redirect_stdout(buf):
        assert cli.main(["requests", "--address", address]) == 0
    out = buf.getvalue()
    assert "req-clifast-0" in out and "req-clislow-0" in out
    assert "(TTFT)" in out and "enqueue" in out and "tpot" in out
    assert "reason=length" in out and "reason=stop" in out
    # the preempted record shows BOTH phases + the pressure line
    assert "admit #1" in out and "admit #2" in out
    assert "preempts 1" in out and "stalls" in out
    assert "@cliworker" in out

    buf = io.StringIO()
    with redirect_stdout(buf):
        assert cli.main(["requests", "--slowest", "1",
                         "--address", address]) == 0
    out = buf.getvalue()
    assert "req-clislow-0" in out and "req-clifast-0" not in out

    buf = io.StringIO()
    with redirect_stdout(buf):
        assert cli.main(["requests", "--format", "json",
                         "--address", address]) == 0
    rows = json.loads(buf.getvalue())
    by_rid = {r["rid"]: r for r in rows}
    assert by_rid["req-clislow-0"]["trace_id"] == trace_id
    assert by_rid["req-clislow-0"]["preempts"] == 1

    # merged trace view: span tree + timeline in one rendering
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert cli.main(["trace", "--request", "req-clislow-0",
                         "--address", address]) == 0
    out = buf.getvalue()
    assert f"request req-clislow-0  trace {trace_id}" in out
    assert "serve.router::llm.__call__" in out  # the linked span tree
    assert "first tok" in out and "reason=stop" in out

    # unknown rid: exit 1 with a hint on stderr
    assert cli.main(["trace", "--request", "req-missing",
                     "--address", address]) == 1


@pytest.mark.slow
def test_requests_cli_live_watch(state_rt):
    """`requests --live` repaints until interrupted; the hidden --frames
    hook bounds the loop for tests."""
    import io
    from contextlib import redirect_stdout

    from ray_tpu.core.worker import global_worker
    from ray_tpu.scripts import cli

    address = global_worker.backend.head_addr
    _seed_request_records(global_worker.backend.head, "ab" * 16)
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert cli.main(["requests", "--live", "--interval", "0.1",
                         "--frames", "2", "--address", address]) == 0
    out = buf.getvalue()
    assert out.count("\x1b[2J") == 2  # two repaints, then exit
    assert "req-clifast-0" in out


def _seed_object_directory(probe):
    """Push one fabricated owner directory + a worker-originated journal
    event into the head, exactly the wire shape cluster_backend's
    _flush_telemetry emits (dir rows + dir_totals + journal list)."""
    probe.call("telemetry_push", {
        "worker": "memworker" + "0" * 23, "node": "memnode" + "0" * 25,
        "role": "worker",
        "objects": {
            "tracked": 2, "sample": [],
            "dir": [
                {"object_id": "aa" * 14, "size": 1048576,
                 "role": "primary", "owner": "memworker000",
                 "age_s": 999.0,
                 "pins": {"local": 0, "submitted": 0, "borrowers": 0,
                          "owned": True}},
                {"object_id": "bb" * 14, "size": 4096,
                 "role": "secondary", "owner": "elsewhere000",
                 "age_s": 1.0, "pins": None},
            ],
            "dir_totals": {
                "primary": {"count": 1, "bytes": 1048576,
                            "arena_bytes": 1048576},
                "secondary": {"count": 1, "bytes": 4096,
                              "arena_bytes": 4096}},
        },
        "journal": [{"type": "spill_overflow", "object_id": "cc" * 14,
                     "bytes": 2048, "node": "memnode" + "0" * 25}],
    }, timeout=10)


def test_memory_cli(state_rt):
    """`memory` renders the head's aggregated object directory grouped
    by node with per-role totals and flags old unreferenced primaries;
    --format json round-trips the exact rows/totals."""
    import io
    from contextlib import redirect_stdout

    from ray_tpu.core.worker import global_worker
    from ray_tpu.scripts import cli

    address = global_worker.backend.head_addr
    _seed_object_directory(global_worker.backend.head)

    buf = io.StringIO()
    with redirect_stdout(buf):
        assert cli.main(["memory", "--address", address]) == 0
    out = buf.getvalue()
    assert "memnode00000" in out          # node group header
    assert "primary" in out and "secondary" in out
    # the 999s-old zero-pin primary trips the leak heuristic; the fresh
    # secondary does not
    assert "LEAK?" in out and "1 LEAK suspect(s)" in out
    assert "pins=l0/s0/b0" in out

    buf = io.StringIO()
    with redirect_stdout(buf):
        assert cli.main(["memory", "--format", "json",
                         "--address", address]) == 0
    data = json.loads(buf.getvalue())
    t = data["totals"]["memnode" + "0" * 25]
    assert t["primary"] == {"count": 1, "bytes": 1048576,
                            "arena_bytes": 1048576}
    assert t["secondary"]["arena_bytes"] == 4096
    rows = [r for r in data["rows"] if r.get("reporter") == "memworker000"]
    assert {r["role"] for r in rows} == {"primary", "secondary"}

    # grouped by owner: the two rows land in different groups
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert cli.main(["memory", "--group-by", "owner",
                         "--address", address]) == 0
    out = buf.getvalue()
    assert "owner memworker000" in out and "owner elsewhere000" in out


def test_events_cli(state_rt):
    """`events` dumps the head journal in sequence order; --type
    filters; --follow with the hidden --frames hook terminates; json
    output carries strictly increasing seqs."""
    import io
    from contextlib import redirect_stdout

    from ray_tpu.core.worker import global_worker
    from ray_tpu.scripts import cli

    address = global_worker.backend.head_addr
    _seed_object_directory(global_worker.backend.head)

    buf = io.StringIO()
    with redirect_stdout(buf):
        assert cli.main(["events", "--address", address]) == 0
    out = buf.getvalue()
    # the fixture cluster registered its node; the seed pushed a
    # worker-originated spill event sequenced at head arrival
    assert "node_register" in out and "spill_overflow" in out

    buf = io.StringIO()
    with redirect_stdout(buf):
        assert cli.main(["events", "--type", "spill_overflow",
                         "--address", address]) == 0
    lines = [ln for ln in buf.getvalue().splitlines() if ln.strip()]
    assert lines and all("spill_overflow" in ln for ln in lines)
    assert not any("node_register" in ln for ln in lines)

    buf = io.StringIO()
    with redirect_stdout(buf):
        assert cli.main(["events", "--format", "json",
                         "--address", address]) == 0
    evs = json.loads(buf.getvalue())
    seqs = [e["seq"] for e in evs]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    assert all(e.get("ts") for e in evs)

    buf = io.StringIO()
    with redirect_stdout(buf):
        assert cli.main(["events", "--follow", "--interval", "0.05",
                         "--frames", "2", "--address", address]) == 0
    assert "spill_overflow" in buf.getvalue()


def test_object_store_metric_names_follow_convention():
    """Every object-store series name is <subsystem>_<noun>_<unit> with
    the unit one of bytes|seconds|total|count (Prometheus naming; lint
    so new series stay greppable + renderable without special cases)."""
    import re

    from ray_tpu.util import metrics as m

    factories = [
        m.object_store_spill_write_total_counter,
        m.object_store_spill_write_bytes_counter,
        m.object_store_spill_restore_total_counter,
        m.object_store_spill_restore_bytes_counter,
        m.object_store_pull_in_bytes_counter,
        m.object_store_pull_out_bytes_counter,
        m.object_store_pull_seconds_histogram,
        m.object_store_fetch_inflight_count_gauge,
        m.object_store_primary_count_gauge,
        m.object_store_secondary_count_gauge,
        m.object_store_spilled_count_gauge,
    ]
    pat = re.compile(
        r"^[a-z][a-z0-9]*(?:_[a-z0-9]+)*_(bytes|seconds|total|count)$")
    names = set()
    for f in factories:
        inst = f()
        assert pat.match(inst.name), inst.name
        assert inst.name.startswith("object_store_"), inst.name
        names.add(inst.name)
    assert len(names) == len(factories)  # no duplicate registrations


def test_checkpoint_and_storage_metric_names_follow_convention():
    """Same lint for the ISSUE 14 series: train_checkpoint_* (async save
    telemetry) and storage_* (filesystem-seam retries/latency/volume)
    must follow <subsystem>_<noun>_<unit> with a sanctioned unit suffix."""
    import re

    from ray_tpu.util import metrics as m

    factories = [
        m.train_checkpoint_write_seconds_histogram,
        m.train_checkpoint_write_bytes_counter,
        m.train_checkpoint_queue_depth_count,
        m.train_checkpoint_step_hiccup_seconds_gauge,
        m.storage_retry_total_counter,
        m.storage_op_seconds_histogram,
        m.storage_put_bytes_counter,
    ]
    pat = re.compile(
        r"^[a-z][a-z0-9]*(?:_[a-z0-9]+)*_(bytes|seconds|total|count)$")
    names = set()
    for f in factories:
        inst = f()
        assert pat.match(inst.name), inst.name
        assert inst.name.startswith(("train_checkpoint_", "storage_")), \
            inst.name
        names.add(inst.name)
    assert len(names) == len(factories)


def test_profile_and_skew_metric_names_follow_convention():
    """Same lint for the profiler-plane series: profile_* counters carry
    a sanctioned unit suffix; train_phase_skew_s follows the existing
    train gauge `_s` convention (train_step_time_s, train_phase_time_s)
    and is tagged (phase, host) so host 0's comparison can attribute
    skew to one phase on one host."""
    import re

    from ray_tpu.util import metrics as m

    pat = re.compile(
        r"^[a-z][a-z0-9]*(?:_[a-z0-9]+)*_(bytes|seconds|total|count)$")
    names = set()
    for f in (m.profile_samples_total_counter,
              m.profile_dropped_samples_total_counter):
        inst = f()
        assert pat.match(inst.name), inst.name
        assert inst.name.startswith("profile_"), inst.name
        names.add(inst.name)
    assert len(names) == 2

    skew = m.train_phase_skew_gauge()
    assert re.match(r"^train_[a-z0-9_]+_s$", skew.name), skew.name
    assert tuple(skew.tag_keys) == ("phase", "host")


def test_log_metric_names_follow_convention():
    """Same lint for the log-plane series: log_* counters carry a
    sanctioned unit suffix, and the tagged ones declare exactly the tag
    keys the docs promise (level for volume, fingerprint for the error
    dedup series) so Prometheus renders stay stable."""
    import re

    from ray_tpu.util import metrics as m

    pat = re.compile(
        r"^[a-z][a-z0-9]*(?:_[a-z0-9]+)*_(bytes|seconds|total|count)$")
    names = set()
    for f in (m.log_records_total_counter,
              m.log_dropped_records_total_counter,
              m.log_errors_total_counter):
        inst = f()
        assert pat.match(inst.name), inst.name
        assert inst.name.startswith("log_"), inst.name
        names.add(inst.name)
    assert len(names) == 3
    assert tuple(m.log_records_total_counter().tag_keys) == ("level",)
    assert tuple(m.log_errors_total_counter().tag_keys) == ("fingerprint",)
    assert tuple(m.log_dropped_records_total_counter().tag_keys) == ()


def test_task_event_buffer_ring_eviction():
    """Satellite: the span buffer is a ring — at MAX_BUFFER the OLDEST
    spans are evicted (not the newest refused) and the __dropped__
    marker reports the exact eviction count."""
    from ray_tpu.runtime.events import TaskEventBuffer

    buf = TaskEventBuffer()
    n = TaskEventBuffer.MAX_BUFFER + 10
    for i in range(n):
        buf.record(name=f"t{i}", task_id=f"id{i}", kind="task",
                   start=float(i), end=float(i) + 0.5, ok=True)
    out = buf.drain()
    marker = [e for e in out if e["name"] == "__dropped__"]
    assert len(marker) == 1 and marker[0]["dropped"] == 10
    spans = [e for e in out if e["name"] != "__dropped__"]
    assert len(spans) == TaskEventBuffer.MAX_BUFFER
    # oldest went first: the survivors are exactly t10..t(n-1), in order
    assert spans[0]["name"] == "t10" and spans[-1]["name"] == f"t{n - 1}"
    # ring drained + marker reset: the next drain is clean
    assert buf.drain() == []


def test_local_mode_dump_synthesis():
    """Satellite: local mode has no head, so util/state._dump synthesizes
    the state_dump shape in-process — including the empty accounting
    surfaces (objects_dir, events) the cluster path always carries.
    Subprocess because the module fixture holds a cluster connection."""
    code = """
import ray_tpu as rt
rt.init(local_mode=True)
from ray_tpu.util import state
d = state._dump()
assert d["nodes"][0]["node_id"] == "local" and d["nodes"][0]["alive"]
assert d["objects_dir"] == []
assert d["events"] == {"recorded": 0, "kept": 0}
assert d["objects"][0]["owner"] == "local"
objs = state.list_objects()
assert objs and objs[0]["owner"] == "local"   # summary fallback path
s = state.summarize()
assert s["tasks"] == 0 and s["events_recorded"] == 0
assert s["objects_in_directory"] == 0
assert s["nodes_alive"] == 1
print("OK-LOCAL")
"""
    import os
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120,
        env={**os.environ, "PYTHONPATH": os.path.dirname(
            os.path.dirname(rt.__file__))})
    assert out.returncode == 0, out.stderr
    assert "OK-LOCAL" in out.stdout


def test_cli_status_and_list(state_rt):
    from ray_tpu.core.worker import global_worker
    address = global_worker.backend.head_addr

    out = _cli("status", address=address)
    assert out.returncode == 0, out.stderr
    assert "nodes alive" in out.stdout and "CPU" in out.stdout

    out = _cli("list", "nodes", address=address)
    assert out.returncode == 0, out.stderr
    assert "node_id=" in out.stdout

    out = _cli("list", "actors", "--format", "json", address=address)
    assert out.returncode == 0, out.stderr
    import json
    rows = json.loads(out.stdout)
    assert isinstance(rows, list)

    out = _cli("list", "objects", address=address)
    assert out.returncode == 0, out.stderr
    assert "capacity=" in out.stdout


# ----------------------------------------------------------- compile plane


def test_xla_metric_names_follow_convention():
    """Same lint for the compile-plane series: xla_* metrics carry a
    sanctioned unit suffix, the per-kind counter declares exactly the
    (process, kind) tag keys the docs promise, and the recompile
    counter + seconds histogram stay untagged so their cluster sums
    read directly."""
    import re

    from ray_tpu.util import metrics as m

    pat = re.compile(
        r"^[a-z][a-z0-9]*(?:_[a-z0-9]+)*_(bytes|seconds|total|count)$")
    names = set()
    for f in (m.xla_compile_seconds_histogram,
              m.xla_compiles_total_counter,
              m.xla_recompiles_total_counter):
        inst = f()
        assert pat.match(inst.name), inst.name
        assert inst.name.startswith("xla_"), inst.name
        names.add(inst.name)
    assert len(names) == 3
    assert tuple(m.xla_compiles_total_counter().tag_keys) == \
        ("process", "kind")
    assert tuple(m.xla_recompiles_total_counter().tag_keys) == ()
    assert tuple(m.xla_compile_seconds_histogram().tag_keys) == ()


def _seed_compile_records(probe):
    """Push one compile window (built by the REAL tracker, so the wire
    shape is authentic) + its staged storm event into the head. The
    shape-unstable llm.ragged_step sequence yields 2 recompiles, which
    crosses the threshold=2 storm knob exactly once."""
    from ray_tpu.util.compile_tracker import CompileTracker

    tr = CompileTracker(role="worker", node="clinode", worker="cliworker",
                        ring_records=16, storm_threshold=2,
                        storm_window_s=60.0)
    tr.note_compile("llm.ragged_step", ["f32[8,128]", "i32[8]"],
                    wall_s=0.5)
    tr.note_compile("llm.ragged_step", ["f32[9,128]", "i32[8]"],
                    wall_s=0.4)
    tr.note_compile("llm.ragged_step", ["f32[10,128]", "i32[8]"],
                    wall_s=0.3)
    tr.note_compile("train.full_step", ["f32[16,64]"], wall_s=1.0)
    probe.call("telemetry_push", {
        "worker": "cliworker" + "0" * 23, "node": "clinode" + "0" * 25,
        "role": "worker",
        "compiles": tr.export(),
        "journal": tr.drain_journal_events(),
    }, timeout=10)


def test_compiles_cli_smoke(state_rt):
    """`compiles` renders the head's aggregated compile records with
    recompiles flagged and their signature diff attached; --recompiles
    filters, --by-callable aggregates, --storms lists the journal's
    once-per-excursion events."""
    import io
    from contextlib import redirect_stdout

    from ray_tpu.core.worker import global_worker
    from ray_tpu.scripts import cli

    address = global_worker.backend.head_addr
    _seed_compile_records(global_worker.backend.head)

    buf = io.StringIO()
    with redirect_stdout(buf):
        assert cli.main(["compiles", "--address", address]) == 0
    out = buf.getvalue()
    assert "RECOMPILE llm.ragged_step" in out
    assert "diff arg[0]: f32[8,128] -> f32[9,128]" in out
    assert "train.full_step" in out and "cliworker" in out
    assert "process(es)" in out

    buf = io.StringIO()
    with redirect_stdout(buf):
        assert cli.main(["compiles", "--recompiles", "--format", "json",
                         "--address", address]) == 0
    data = json.loads(buf.getvalue())
    recs = [r for r in data["records"]
            if r["name"] == "llm.ragged_step"]
    assert len(recs) >= 2
    assert all(r["recompile"] for r in recs)
    assert data["last_seq"] >= 4

    buf = io.StringIO()
    with redirect_stdout(buf):
        assert cli.main(["compiles", "--by-callable",
                         "--address", address]) == 0
    out = buf.getvalue()
    assert "callable" in out and "recompiles" in out
    assert "llm.ragged_step" in out and "train.full_step" in out

    # the threshold=2 excursion staged exactly one storm journal event
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert cli.main(["compiles", "--storms", "--format", "json",
                         "--address", address]) == 0
    storms = json.loads(buf.getvalue())
    assert len(storms) == 1, storms
    assert storms[0]["type"] == "compile_storm"
    assert storms[0]["callable"] == "llm.ragged_step"


def test_trace_perfetto_cli_smoke(state_rt, tmp_path):
    """`trace --perfetto OUT` writes one multi-plane Chrome/Perfetto
    trace: task-span lanes per node, the train step/phase lane, the XLA
    compile lane (recompiles carrying their diff), and journal
    instants — all on one wall clock."""
    import io
    import time as time_mod
    from contextlib import redirect_stdout

    from ray_tpu.core.worker import global_worker
    from ray_tpu.scripts import cli

    address = global_worker.backend.head_addr
    head = global_worker.backend.head
    _seed_compile_records(head)
    now = time_mod.time()
    head.call("telemetry_push", {
        "worker": "cliworker" + "0" * 23, "node": "clinode" + "0" * 25,
        "events": [
            {"name": "step", "kind": "train_step", "task_id": "tsp",
             "start": now - 0.5, "end": now - 0.2, "ok": True},
            {"name": "forward", "kind": "train_phase", "task_id": "tsp",
             "start": now - 0.5, "end": now - 0.4, "ok": True},
            {"name": "work_task", "kind": "task", "task_id": "t" * 32,
             "start": now - 1.0, "end": now - 0.9, "ok": True},
        ]}, timeout=10)

    out_path = tmp_path / "cluster.perfetto.json"
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert cli.main(["trace", "--perfetto", str(out_path),
                         "--address", address]) == 0
    assert "lanes" in buf.getvalue()

    with open(out_path) as f:
        trace = json.load(f)
    evs = trace["traceEvents"]
    assert trace.get("displayTimeUnit") == "ms"
    lanes = {e["args"]["name"] for e in evs
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    assert "xla: compiles" in lanes, lanes
    assert "train: steps + phases" in lanes, lanes
    assert any(name.startswith("spans: node") for name in lanes), lanes
    # the compile lane carries the recompile with its signature diff
    rec = next(e for e in evs
               if e.get("ph") == "X"
               and str(e.get("name", "")).startswith("RECOMPILE"))
    assert rec["args"]["diff"], rec
    assert any(e.get("cat") == "train_phase" for e in evs)
    assert any(e.get("cat") == "journal" for e in evs)
