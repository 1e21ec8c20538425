"""benchmark/readers/host_gaps.py: from a trace's host spans and device
events to named idle gaps on one clock. Checked on a hand-built trace whose
skew and gaps are known, and on a recorded TPU trace of the spans
(benchmark/fixtures/chat-1chip-spans.xplane.pb.gz). Also: every per-layer
metric of BENCHMARK.json that reads the spans or the new request-log
fields has its metric file and reader."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import trace_reduce  # noqa: E402
from benchmark.readers import host_gaps  # noqa: E402

MS = 1_000_000
FIXTURES = os.path.join(ROOT, "benchmark", "fixtures")
ANCHORS = {"start_after": ["^DoEnqueueProgram$"],
           "end_before": ["^tpu::System::Execute=>Done$"]}
#: (what the entry reads: a word of tests/_readings.py, a cell it reads it
#: for): found by the reading, whatever the entry is called
NEW_METRICS = [
    (reading, cell) for reading in (
        "engine_host_gap_ms", "idle_prep_pct", "idle_post_pct",
        "idle_attributed_pct", "trace_clock_skew_ms")
    for cell in ("chat-1chip", "reason-1chip")] + [
    ("tpot_mixed_stall_pct", "chat-1chip"),
    ("queue_wait_in_flight_p50_ms", "chat-1chip")]


def built_planes(skew_ms: float):
    """Two steps on the host's clock (ms): a mixed step whose program runs
    11.5 -> 111.5, then a decode loop 118 -> 218; the device's own stamps
    are `skew_ms` earlier. Between the programs, 6.5 ms:
      111.5-112 readback after the device ended      0.5  post
      112-113   book                                 1.0  post
      113-113.1 engine.step's own                    0.1
      113.1-113.2 nothing                            0.1  unattributed
      113.2-113.7 serve.publish                      0.5  post
      113.7-114 nothing                              0.3  unattributed
      114-114.2 admit, -115 pack, -117 h2d, -117.6 dispatch   3.6  prep
      117.6-118 readback before the program began    0.4  prep
    """
    host, other = [], []

    def span(name, start, end):
        host.append((name, int(start * MS), int((end - start) * MS)))

    span("engine.step", 8, 113.1)
    for name, a, b in (("admit", 8, 8.1), ("pack", 8.2, 9), ("h2d", 9, 10),
                       ("dispatch", 10, 11), ("readback", 11, 112),
                       ("book", 112, 113)):
        span("engine." + name, a, b)
    span("serve.publish", 113.2, 113.7)
    span("engine.step", 114, 220)
    for name, a, b in (("admit", 114, 114.2), ("pack", 114.2, 115),
                       ("h2d", 115, 117), ("dispatch", 117, 117.6),
                       ("readback", 117.6, 218.4), ("book", 218.4, 219.5)):
        span("engine." + name, a, b)
    host.append(("PjitFunction(_ragged_decode_loop)", 117 * MS, MS // 2))
    for name, at in (("DoEnqueueProgram", 11.2),
                     ("tpu::System::Execute=>Done", 111.8),
                     ("DoEnqueueProgram", 117.7),
                     ("tpu::System::Execute=>Done", 218.3)):
        other.append((name, int(at * MS), 1000))

    def dev(ms):
        return int((ms - skew_ms) * MS)

    device = {
        "XLA Modules": [
            ("jit__ragged_step_body(1)", dev(11.5), 100 * MS),
            ("jit__ragged_decode_loop(2)", dev(118), 100 * MS)],
        "XLA Ops": [
            ("%while.1 = (s32[]) while(...)", dev(11.5), 100 * MS),
            ("%fusion.1 = bf16[8] fusion(...)", dev(11.5), 60 * MS),
            # 1 ms idle INSIDE the program: not a gap between programs
            ("%fusion.2 = bf16[8] fusion(...)", dev(72.5), 39 * MS),
            ("%fusion.1 = bf16[8] fusion(...)", dev(118), 100 * MS)],
        "Async XLA Ops": [("%copy-start.1 = ...", dev(111.4), MS)]}
    return {"/device:TPU:0": device,
            "/host:CPU": {"python3": host, "tfrt-queue/1": other}}


@pytest.mark.parametrize("skew_ms", [1.25, -0.6, 0.0])
def test_built_trace_recovers_skew_and_gaps(skew_ms):
    got = host_gaps.analyze(built_planes(skew_ms), ANCHORS)
    # anchors leave [max(11.2-11.5, 117.7-118), min(111.8-111.5,
    # 218.3-218)] = [-0.3, 0.3] around the true offset: its middle
    assert got["skew_ms"] == pytest.approx(skew_ms, abs=1e-6)
    assert got["skew_hi_ms"] - got["skew_lo_ms"] == pytest.approx(0.6)
    assert got["consistent"] and got["dispatches_paired"] == 2
    assert got["gaps"] == 1 and got["gap_ms"] == pytest.approx(6.5)
    by = got["idle_ms_by_span"]
    assert by["engine.readback:drain"] == pytest.approx(0.5, abs=1e-5)
    assert by["engine.readback:launch"] == pytest.approx(0.4, abs=1e-5)
    assert by["engine.h2d"] == pytest.approx(2.0, abs=1e-5)
    assert by["engine.step"] == pytest.approx(0.1, abs=1e-5)
    assert by["serve.publish"] == pytest.approx(0.5, abs=1e-5)
    assert "engine.readback" not in by          # the device was busy
    assert got["unattributed_ms"] == pytest.approx(0.4, abs=1e-5)
    assert sum(by.values()) + got["unattributed_ms"] \
        == pytest.approx(got["idle_ms"])


def test_without_anchors_the_spans_alone_bound_the_skew():
    got = host_gaps.analyze(built_planes(1.25))
    # dispatch start / readback end: [max(10-11.5, 117-118), min(112-111.5,
    # 218.4-218)] + 1.25 = [0.25, 1.65]
    assert got["skew_lo_ms"] == pytest.approx(0.25)
    assert got["skew_hi_ms"] == pytest.approx(1.65)
    assert got["skew_ms"] == pytest.approx(0.95)
    assert got["gap_ms"] == pytest.approx(6.5)   # a length: no skew in it


def test_read_gives_each_quantity_and_notes_the_analysis(tmp_path,
                                                         monkeypatch):
    planes = built_planes(1.25)
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: d + "/x.pb")
    calls = []
    monkeypatch.setattr(trace_reduce, "read_planes",
                        lambda p: calls.append(p) or planes)
    data = {"trace": {"dir": str(tmp_path)}}

    def read(**args):
        return host_gaps.read(data, dict(args, anchors=ANCHORS))

    assert read(quantity="gap_ms") == pytest.approx(6.5)
    assert read(quantity="skew_ms") == pytest.approx(1.25)
    assert read(quantity="attributed_pct") == pytest.approx(
        100 * 6.1 / 6.5, abs=1e-3)
    prep = read(quantity="share_pct", spans=[
        "engine.admit", "engine.pack", "engine.h2d", "engine.dispatch",
        "engine.readback:launch"])
    post = read(quantity="share_pct", spans=[
        "engine.readback:drain", "engine.book", "engine.metrics",
        "serve.publish"])
    assert prep == pytest.approx(100 * 4.0 / 6.5, abs=1e-3)
    assert post == pytest.approx(100 * 2.0 / 6.5, abs=1e-3)
    assert len(calls) == 1                       # one parse for them all
    assert data["notes"]["host_gaps"]["gaps"] == 1
    with pytest.raises(ValueError):
        read(quantity="nonsense")


@pytest.mark.parametrize("why", ["no trace", "no device plane",
                                 "no engine.step", "no file"])
def test_nothing_to_read_is_none_not_an_error(why, tmp_path, monkeypatch):
    planes = built_planes(0.0)
    if why == "no device plane":                 # a CPU rehearsal
        del planes["/device:TPU:0"]
    if why == "no engine.step":                  # a program without spans
        planes["/host:CPU"]["python3"] = [
            e for e in planes["/host:CPU"]["python3"]
            if not e[0].startswith(("engine.", "serve."))]
    monkeypatch.setattr(trace_reduce, "find_xplane",
                        lambda d: None if why == "no file" else d)
    monkeypatch.setattr(trace_reduce, "read_planes", lambda p: planes)
    data = {} if why == "no trace" else {"trace": {"dir": str(tmp_path)}}
    assert host_gaps.read(data, {"quantity": "gap_ms"}) is None
    assert "notes" not in data


def test_recorded_tpu_trace_reproduces_its_expected_analysis():
    with open(os.path.join(FIXTURES, "expected_host_gaps.json")) as f:
        want = json.load(f)
    planes = trace_reduce.read_planes(os.path.join(FIXTURES, want["trace"]))
    got = host_gaps.analyze(planes, want["anchors"])
    assert got is not None and got["consistent"]
    for key, value in want["analysis"].items():
        if isinstance(value, dict):
            assert set(got[key]) == set(value), key
            for k, v in value.items():
                assert got[key][k] == pytest.approx(v, abs=1e-6), (key, k)
        elif isinstance(value, float):
            assert got[key] == pytest.approx(value, abs=1e-6), key
        else:
            assert got[key] == value, key
    # the existing reduction reads the same file: same modules as ever
    summary = trace_reduce.summarize(os.path.join(FIXTURES, want["trace"]))
    assert set(summary.module_n) == set(want["modules"])


@pytest.mark.parametrize("reading,cell", NEW_METRICS)
def test_new_metric_has_entry_file_and_reader(reading, cell):
    from _readings import entry
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    m, args, reader = entry(reading, cell)
    assert callable(reader.read)
    cells = {w["name"] for w in bench["workloads"]}
    moved = next(e for e in bench["end_to_end"] if e["name"] == m["moves"])
    assert set(m["workloads"]) <= cells
    assert set(m["workloads"]) <= set(moved.get("workloads", cells))
    if reader is host_gaps:
        assert m["source"] == "program_span" and args["anchors"] == ANCHORS
    else:
        # the request log's record must carry the field
        from ray_tpu.llm.request_log import RequestRecord
        assert args["field"] in RequestRecord("r", 1, 1).to_dict()
