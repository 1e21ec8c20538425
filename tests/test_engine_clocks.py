"""Who holds the engine thread between two dispatches: the phase helper of
llm/engine.py (every phase's wall time and the thread's CPU time outside
the two phases that sleep as counters of engine.stats on every run, every
phase's CPU time as cpu_us on the spans of a traced one), the stream lanes' stream.deliver spans (llm/serve_llm.py), the
reader that puts both against the device's idle
(benchmark/readers/host_offcpu.py) and the five metric files.

Nothing here is a timing: the clocks are scripted, the traces are read for
structure and arithmetic."""

import glob
import importlib
import json
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import trace_reduce  # noqa: E402
from benchmark.readers import host_gaps, host_offcpu  # noqa: E402
from ray_tpu.llm import InferenceEngine  # noqa: E402
from ray_tpu.llm import engine as engine_mod  # noqa: E402
from ray_tpu.llm.engine import CPU_KEY, PHASES, WALL_KEYS  # noqa: E402
from ray_tpu.llm.serve_llm import LLMServer  # noqa: E402
from ray_tpu.models.llama import LlamaConfig  # noqa: E402

MS = 1_000_000
METRICS = ["engine_host_ms", "engine_offcpu_pct", "engine_device_wait_pct",
           "idle_offcpu_pct", "idle_lanes_pct"]
CELLS = ["reason-1chip", "reason-moe-1chip", "reason-lfm2-1chip",
         "context-kanana-1chip", "reason-granite-1chip",
         "context-brumby-1chip", "context-mimo-1chip",
         "mixed-trinity-1chip",
         "reason-gigachat-1chip", "reason-phi4flash-1chip",
         "reason-ouro-1chip"]
ENGINE = dict(page_size=8, total_pages=64, max_batch=4, max_seq_len=96,
              prefill_chunk=16, prefill_rows=2, decode_chunk=4)

# ---------------------------------------------------- (a) scripted clocks

#: one turn of the serve loop, as (span, wall ns, CPU ns) of the phase's
#: OWN work; engine.step's own is split in two around its children
SCRIPT = {"wait": (50_000, 40), "admit": (100, 70), "pack": (1000, 90),
          "h2d": (300, 20), "dispatch": (60, 60), "readback": (8000, 5),
          "book": (200, 200), "metrics": (30, 10), "other": (7 + 4, 5 + 3),
          "publish": (500, 110)}


#: the phases whose CPU is the host's: all but the two that sleep
HOST = [p for p in PHASES if p not in ("readback", "wait")]
#: a step's hold (engine.hold: asleep until the flight is due), which is
#: no phase of its own: (wall ns, CPU ns) as SCRIPT's
HOLD = (20_000, 15)


@pytest.fixture
def scripted(monkeypatch):
    """PhaseClocks over clocks that move only when the script says so:
    (stats, run one loop turn, elapsed wall ns, CPU clock reads so far,
    the cpu_us each span was given: [(span, us)], trace on / off)."""
    now = {"wall": 1_000_000, "cpu": 500, "cpu_reads": 0, "trace": False}
    given = []

    def cpu_ns():
        now["cpu_reads"] += 1
        return now["cpu"]

    monkeypatch.setattr(engine_mod, "_wall_ns", lambda: now["wall"])
    monkeypatch.setattr(engine_mod, "_cpu_ns", cpu_ns)
    monkeypatch.setattr(engine_mod._Phase, "is_enabled",
                        staticmethod(lambda: now["trace"]), raising=False)
    monkeypatch.setattr(
        engine_mod._Phase, "set_metadata",
        lambda self, **kw: given.append((self.wall_key, kw["cpu_us"])),
        raising=False)
    stats = {"steps": 3}
    phase = engine_mod.PhaseClocks(stats).phase

    def work(name):
        now["wall"] += SCRIPT[name][0]
        now["cpu"] += SCRIPT[name][1]

    def turn(nested=False, held=False):
        """One loop turn; `nested`: the last step's tokens handed over
        from inside this step, between dispatch and readback (the serve
        loop's order), instead of after it (a flush). `held`: the step
        holds its launch back (llm/engine.py: step): after its admission
        it hands over (if `nested`), sleeps in engine.hold and admits
        again before it packs."""
        with phase("serve.wait"):
            work("wait")
        with phase("engine.step"):
            now["wall"] += 7
            now["cpu"] += 5
            if held:
                with phase("engine.admit"):
                    work("admit")
                if nested:
                    with phase("serve.publish"):
                        work("publish")
                with phase("engine.hold"):
                    now["wall"] += HOLD[0]
                    now["cpu"] += HOLD[1]
            for name in ("admit", "pack", "h2d", "dispatch", "readback",
                         "book", "metrics"):
                with phase("engine." + name):
                    work(name)
                if nested and not held and name == "dispatch":
                    with phase("serve.publish"):
                        work("publish")
            now["wall"] += 4
            now["cpu"] += 3
        if not nested:
            with phase("serve.publish"):
                work("publish")

    start = now["wall"]
    return (stats, turn, lambda: now["wall"] - start,
            lambda: now["cpu_reads"], given,
            lambda on: now.update(trace=on))


@pytest.mark.parametrize("nested", [False, True])
@pytest.mark.parametrize("name", PHASES)
def test_each_phase_lands_in_its_wall_counter(scripted, name, nested):
    """serve.publish inside engine.step (the serve loop's order) or after
    it (a flush): its wall is publish's either way, never `other`'s."""
    stats, turn = scripted[:2]
    assert stats["wall_ns_" + name] == stats[CPU_KEY] == 0
    for _ in range(3):
        turn(nested)
    assert stats["wall_ns_" + name] == 3 * SCRIPT[name][0]
    assert "cpu_ns_" + name not in stats           # one CPU counter, below
    assert stats["steps"] == 3                     # the rest is untouched


@pytest.mark.parametrize("nested", [False, True, "mixed"])
def test_the_ten_wall_counters_partition_the_loops_time(scripted, nested):
    stats, turn, elapsed = scripted[:3]
    for i in range(5):
        turn(i % 2 == 0 if nested == "mixed" else nested)
    assert sum(stats[k] for k in WALL_KEYS) == elapsed() \
        == 5 * sum(w for w, _ in SCRIPT.values())
    assert stats["wall_ns_other"] == 5 * SCRIPT["other"][0]
    assert stats["wall_ns_publish"] == 5 * SCRIPT["publish"][0]
    assert len(WALL_KEYS) == 10


@pytest.mark.parametrize("nested", [False, True])
@pytest.mark.parametrize("traced", [False, True])
def test_host_cpu_is_what_ran_outside_the_two_phases_that_sleep(scripted,
                                                                traced,
                                                                nested):
    """cpu_ns_host: the thread's CPU between the end of one sleeping phase
    (engine.readback, serve.wait) and the start of the next, whatever the
    phases between them; tracing adds reads of the clock, not CPU."""
    stats, turn, _, reads, _, trace = scripted
    trace(traced)
    for _ in range(4):
        turn(nested)
    # the tail of the last turn (book ... and a flush's publish; a nested
    # publish ran before the readback) is booked when the next sleeping
    # phase starts
    tail = sum(SCRIPT[p][1] for p in ("book", "metrics")
               + (() if nested else ("publish",))) + 3
    host = sum(SCRIPT[p][1] for p in HOST)
    assert stats[CPU_KEY] == 4 * host - tail
    before = reads()
    turn(nested)
    assert stats[CPU_KEY] == 5 * host - tail
    # tracing off: the CPU clock is read at the two ends of readback and
    # of wait and nowhere else (two reads a dispatch: wait runs only when
    # there is no work); traced: at both ends of all ten spans
    assert reads() - before == (20 if traced else 4)


@pytest.mark.parametrize("nested", [False, True])
def test_a_traced_span_carries_the_cpu_of_its_own_window(scripted, nested):
    stats, turn, _, _, given, trace = scripted
    turn(nested)
    assert given == []                              # no trace: no cpu_us
    trace(True)
    turn(nested)
    got = dict(given)
    assert len(given) == len(got) == 10
    # engine.step's span holds its children's CPU, a nested publish's too
    outside = ("wait",) if nested else ("publish", "wait")
    for name in PHASES:
        want = SCRIPT[name][1] if name != "other" else sum(
            SCRIPT[p][1] for p in PHASES if p not in outside)
        assert got["wall_ns_" + name] == pytest.approx(want / 1e3), name
    # a trace that starts or stops inside a span: nothing half-read
    trace(False)
    with engine_mod.PhaseClocks(stats).phase("engine.pack"):
        trace(True)
    assert len(given) == 10


@pytest.mark.parametrize("nested", [False, True])
def test_a_hold_is_wall_of_wait_and_the_ten_counters_still_partition(
        scripted, nested):
    """engine.hold sleeps by design, like serve.wait: its wall goes to
    wall_ns_wait (not to `other`, which engine_host_ms sums), the step's
    own time is still what no child covers, and with a hold in every
    other turn the ten counters add up to the thread's time."""
    stats, turn, elapsed = scripted[:3]
    for i in range(6):
        turn(nested, held=i % 2 == 0)
    assert sum(stats[k] for k in WALL_KEYS) == elapsed() \
        == 6 * sum(w for w, _ in SCRIPT.values()) \
        + 3 * (HOLD[0] + SCRIPT["admit"][0])
    assert stats["wall_ns_wait"] == 6 * SCRIPT["wait"][0] + 3 * HOLD[0]
    assert stats["wall_ns_admit"] == 9 * SCRIPT["admit"][0]
    assert stats["wall_ns_other"] == 6 * SCRIPT["other"][0]
    assert stats["wall_ns_publish"] == 6 * SCRIPT["publish"][0]
    assert "wall_ns_hold" not in stats and len(WALL_KEYS) == 10


@pytest.mark.parametrize("traced", [False, True])
def test_host_cpu_leaves_the_hold_out(scripted, traced):
    """cpu_ns_host with a hold in the step: the CPU of both admissions and
    of the hand-over before the hold is the host's, what the thread is
    charged while it sleeps in engine.hold is not; the CPU clock is read
    at the hold's two ends too (two more reads a held step)."""
    stats, turn, _, reads, given, trace = scripted
    trace(traced)
    for _ in range(3):
        turn(True, held=True)
    tail = sum(SCRIPT[p][1] for p in ("book", "metrics")) + 3
    host = sum(SCRIPT[p][1] for p in HOST) + SCRIPT["admit"][1]
    assert stats[CPU_KEY] == 3 * host - tail
    before = reads()
    turn(True, held=True)
    assert stats[CPU_KEY] == 4 * host - tail
    # wait, hold, readback: two reads each; traced: all twelve spans
    assert reads() - before == (24 if traced else 6)
    if traced:
        holds = [us for key, us in given if key == "wall_ns_wait"
                 and us == pytest.approx(HOLD[1] / 1e3)]
        assert len(holds) == 4          # engine.hold's own cpu_us


def test_another_threads_cpu_clock_is_not_subtracted(scripted):
    """thread_time_ns is per thread: a mark left by one thread says nothing
    to another (an engine stepped by a test, then by a serve loop)."""
    stats, turn = scripted[:2]
    turn()
    booked = stats[CPU_KEY]
    t = threading.Thread(target=turn)
    t.start()
    t.join()
    # the other thread's first sleeping phase (wait) books nothing, its
    # readback books what ran since ITS wait ended
    assert stats[CPU_KEY] == booked + 5 + sum(
        SCRIPT[p][1] for p in ("admit", "pack", "h2d", "dispatch"))


def test_empty_phases_add_nothing_and_an_unknown_span_is_refused(scripted):
    stats = scripted[0]
    phase = engine_mod.PhaseClocks(stats).phase     # fresh counters
    with phase("engine.step"):
        with phase("engine.admit"):
            pass
    assert all(stats[k] == 0 for k in WALL_KEYS + (CPU_KEY,))
    with pytest.raises(KeyError):
        phase("engine.nonsense")


def test_the_engine_owns_one_helper_and_its_gauge_reads_it():
    eng = InferenceEngine(LlamaConfig.tiny(n_layers=1, dtype=jnp.float32),
                          page_size=8, total_pages=32, max_batch=2,
                          max_seq_len=64, prefill_chunk=16, decode_chunk=2)
    assert all(eng.stats[k] == 0 for k in WALL_KEYS + (CPU_KEY,))
    eng.generate(list(range(1, 12)), max_new_tokens=6)
    assert eng.stats[CPU_KEY] >= 0
    for name in ("admit", "pack", "h2d", "dispatch", "readback", "book",
                 "other"):
        assert eng.stats["wall_ns_" + name] > 0, name
    assert eng.stats["wall_ns_publish"] == eng.stats["wall_ns_wait"] == 0
    eng._update_metrics(force=True)
    (ratio,) = eng._g_device_wait._export()["values"].values()
    assert 0.0 < ratio < 1.0
    assert eng.stats["wall_ns_metrics"] > 0


# ------------------------------------------- (b) the profiled tiny server

def _consume(server, request, got, limit=None):
    """Drain (or abandon after `limit` items) one stream on this thread."""
    gen = server.stream(request)
    for i, item in enumerate(gen, 1):
        got.append(item)
        if i == limit:
            break
    gen.close()


@pytest.fixture(scope="module")
def streamed(tmp_path_factory):
    """A tiny LLMServer under the profiler answering three whole streams,
    one stream abandoned by its own lane after an item and one closed from
    ANOTHER thread: (threads: [[(name, start, end, metadata)]] per host
    thread, counters before and after, items each whole stream yielded,
    the server)."""
    server = LLMServer(model_config={"n_layers": 2, "dtype": jnp.float32},
                       engine_config=ENGINE)
    server({"prompt_ids": list(range(1, 20)), "max_tokens": 6})   # compile
    time.sleep(0.12)                 # the loop is back in serve.wait
    log_dir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    before = dict(server.engine.stats)
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        whole = [[] for _ in range(3)]
        threads = [threading.Thread(target=_consume, args=(server, {
            "prompt_ids": list(range(3, 3 + n)), "max_tokens": 9}, got))
            for n, got in zip((40, 7, 23), whole)]
        own = []
        threads.append(threading.Thread(target=_consume, args=(server, {
            "prompt_ids": list(range(5, 17)), "max_tokens": 9}, own, 1)))
        for t in threads:
            t.start()
        # a stream whose generator a thread other than its lane closes
        foreign = server.stream({"prompt_ids": list(range(2, 12)),
                                 "max_tokens": 9})
        box = []
        lane = threading.Thread(target=lambda: box.append(next(foreign)))
        lane.start()
        lane.join(timeout=120)
        foreign.close()
        for t in threads:
            t.join(timeout=120)
        assert not lane.is_alive() and not any(t.is_alive() for t in threads)
        deadline = time.monotonic() + 60
        while server.engine.has_work() and time.monotonic() < deadline:
            time.sleep(0.01)
        time.sleep(0.12)
        after = dict(server.engine.stats)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[-1]
    planes, raw = host_offcpu.read_trace(path)
    spans = [[(n, s, s + d, meta) for n, s, d, meta in t] for t in raw]
    return {"threads": spans, "before": before, "after": after,
            "whole": whole, "own": own, "foreign": box, "server": server,
            "planes": planes, "raw": raw}


def _cpu_tick_ns() -> int:
    """What the thread CPU clock moves by (a microsecond here, 10 ms on a
    host whose kernel counts it in scheduler ticks)."""
    a = b = time.thread_time_ns()
    while b == a:
        b = time.thread_time_ns()
    return max(b - a, 20_000)


def _engine_thread(run):
    return next(t for t in run["threads"]
                if any(s[0] == "engine.step" for s in t))


def test_every_phase_span_carries_its_cpu_time(streamed):
    spans = _engine_thread(streamed)
    assert {s[0] for s in spans} >= {"engine.step", "engine.admit",
                                     "engine.pack", "engine.h2d",
                                     "engine.dispatch", "engine.readback",
                                     "engine.book", "serve.publish"}
    tick = _cpu_tick_ns()
    for name, start, end, meta in spans:
        assert "arrays" not in meta                  # nothing read it
        assert 0 <= meta["cpu_us"] * 1e3 <= (end - start) + tick, name


@pytest.mark.parametrize("phase", [p for p in PHASES if p != "wait"])
def test_counters_equal_the_sums_over_the_spans(streamed, phase):
    """The run starts and ends with the loop in serve.wait, so every other
    phase that ran is a span of the trace; a span's duration encloses its
    wall clock's two reads."""
    spans = _engine_thread(streamed)
    name = next(n for n, k in engine_mod._PHASE_KEY.items()
                if k == "wall_ns_" + phase)
    mine = [s for s in spans if s[0] == name]
    wall = sum(s[2] - s[1] for s in mine)
    if phase == "other":
        steps = [s for s in spans if s[0] == "engine.step"]
        wall -= sum(s[2] - s[1] for s in spans if s[0] != "engine.step"
                    and any(t[1] <= s[1] and s[2] <= t[2] for t in steps))
    d_wall = streamed["after"]["wall_ns_" + phase] \
        - streamed["before"]["wall_ns_" + phase]
    n = max(len(mine), 1) * (9 if phase == "other" else 1)
    # enclosed up to the two clocks' rates; more only by what the helper
    # runs between a span's edge and its clock (and a deschedule there).
    # engine.step's own is a difference: its children's edges count against
    slack = 5 * MS * n
    assert (-slack if phase == "other" else -2_000 * n) \
        <= wall - d_wall <= slack
    if phase != "metrics":
        assert mine and d_wall > 0


def test_host_cpu_counter_equals_the_spans_cpu_outside_the_sleeps(streamed):
    """cpu_ns_host against the same thing from the spans: engine.step's
    cpu_us (a serve.publish nested in it is part of that) and a flush's
    serve.publish's, less engine.readback's. The counter also holds what
    runs between two spans (the loop's own few lines)."""
    spans = _engine_thread(streamed)
    steps = [s for s in spans if s[0] == "engine.step"]
    flushes = [s for s in spans if s[0] == "serve.publish" and not any(
        t[1] <= s[1] and s[2] <= t[2] for t in steps)]
    assert flushes and len(flushes) < sum(
        1 for s in spans if s[0] == "serve.publish")
    cpu = {n: sum(s[3]["cpu_us"] * 1e3 for s in spans if s[0] == n)
           for n in ("engine.step", "engine.readback")}
    from_spans = cpu["engine.step"] - cpu["engine.readback"] \
        + sum(s[3]["cpu_us"] * 1e3 for s in flushes)
    d_host = streamed["after"][CPU_KEY] - streamed["before"][CPU_KEY]
    steps = sum(1 for s in spans if s[0] == "engine.step")
    assert d_host > 0 and steps >= 4
    assert -_cpu_tick_ns() <= d_host - from_spans \
        <= 2 * MS * steps + _cpu_tick_ns()


def test_one_deliver_span_an_item_on_the_lanes_own_thread(streamed):
    engine = _engine_thread(streamed)
    lanes = [[s for s in t if s[0] == "stream.deliver"]
             for t in streamed["threads"] if t is not engine]
    lanes = [t for t in lanes if t]
    assert not any(s[0] == "stream.deliver" for s in engine)
    # three whole streams (their token items and the closing one), the one
    # its own lane abandoned after an item (ended by the close, on the
    # lane), and nothing for the one another thread closed
    assert [len(w) for w in streamed["whole"]] == [
        len(w) for w in streamed["whole"] if w[-1].get("done")]
    assert len(streamed["own"]) == len(streamed["foreign"]) == 1
    want = sorted([len(w) for w in streamed["whole"]] + [1])
    assert sorted(len(t) for t in lanes) == want
    for lane, n_items in ((t, len(t)) for t in lanes):
        tokens = [s[3]["tokens"] for s in lane]
        assert all(n > 0 for n in tokens[:-1])
        if n_items > 1:
            assert tokens[-1] == 0               # the {"done": True} item
            assert sum(tokens) == 9
        for a, b in zip(lane, lane[1:]):
            assert a[2] <= b[1]                  # asleep between two items
        assert all(set(s[3]) == {"tokens"} for s in lane)
    assert not any(host_gaps.SPAN.match(s[0])
                   for lane in lanes for s in lane)
    # the span another thread would have ended is kept, not written,
    # until the next item of any stream finds no trace running
    server = streamed["server"]
    assert len(server._orphan_spans) == 1
    _consume(server, {"prompt_ids": list(range(2, 9)), "max_tokens": 3}, [])
    assert server._orphan_spans == []


def test_host_gaps_reads_the_trace_as_if_the_lanes_had_no_spans(streamed):
    """host_gaps takes every engine.* / serve.* event of the host plane as
    ONE nested sequence: stream.deliver must not be among them. The CPU has
    no device plane, so one is built from the dispatches' own spans."""
    planes = {k: dict(v) for k, v in streamed["planes"].items()}
    engine = sorted(_engine_thread(streamed), key=lambda s: s[1])
    starts = [s for s in engine if s[0] == "engine.dispatch"]
    ends = [s for s in engine if s[0] == "engine.readback"]
    assert len(starts) == len(ends) >= 4
    mods = [("jit_step(1)", d[2], r[2] - 1000 - d[2])
            for d, r in zip(starts, ends)]
    planes["/device:TPU:0"] = {
        "XLA Modules": mods,
        "XLA Ops": [("%fusion.1 = f32[8] fusion(...)", s, d)
                    for _, s, d in mods]}
    without = dict(planes)
    without[host_gaps.HOST_PLANE] = {
        line: [e for e in evs if e[0] != "stream.deliver"]
        for line, evs in planes[host_gaps.HOST_PLANE].items()}
    assert sum(len(v) for v in without[host_gaps.HOST_PLANE].values()) \
        < sum(len(v) for v in planes[host_gaps.HOST_PLANE].values())
    got = host_gaps.analyze(planes)
    assert got is not None and got["gaps"] >= 3
    assert got == host_gaps.analyze(without)
    # and the new reader, on the same trace: shares of that same idle
    mine = host_offcpu.analyze(planes, streamed["raw"])
    assert mine["idle_ms"] == pytest.approx(got["idle_ms"])
    assert mine["gaps"] == got["gaps"]
    # no lower bound: where the CPU clock ticks, a short span that caught
    # a whole tick weighs negative (right in a sum over many, wrong in few)
    assert mine["offcpu_ms"] <= mine["idle_ms"]
    assert mine["cpu_ticks"] > 0 and mine["cpu_tick_us"] > 0
    assert 0.0 <= mine["lanes_ms"] <= mine["idle_ms"]
    # a stream's last items leave after the last step ended
    assert 3 <= mine["deliver_spans"] <= sum(
        len(w) for w in streamed["whole"]) + 1


# ------------------------------------------ (c) the reader on built planes

def built(skew_ms=0.0, cpu=True, lanes=True):
    """Two dispatches on the host's clock (ms); the programs run 5 -> 95
    and 112 -> 197, so the device idles 95 -> 112 = 17 ms:
      95-96    readback after the program ended   drain, in neither share
      96-99    book        cpu 3.0 of 3   off 0
      99-100   step's own  cpu 0.5 of 1   off 0.5
      100-102  publish     cpu 1.0 of 2   off 1.0
      102-103  nothing
      103-104  admit       cpu 1.0 of 1   off 0
      104-108  pack        cpu 1.0 of 4   off 3.0
      108-110  h2d         cpu 0.5 of 2   off 1.5
      110-111  dispatch    cpu 1.0 of 1   off 0
      111-112  readback before the program began  (sleeps: not counted)
    = 6.0 ms off the CPU. Lanes awake 94-95.5, 99.5-101, 100.5-106,
    105-107 (7.5 + 0.5 = 8.0 ms of the idle) and 150-151 (device busy)."""
    engine, anchors = [], []

    def span(name, a, b, cpu_ms):
        meta = {"cpu_us": cpu_ms * 1e3} if cpu else {}
        engine.append((name, int(a * MS), int((b - a) * MS), meta))

    span("engine.step", 0, 100, 0.2 + 0.2 + 0.2 + 0.2 + 0.1 + 3.0 + 0.5)
    for name, a, b, c in (("admit", 0, 1, 0.2), ("pack", 1, 2, 0.2),
                          ("h2d", 2, 3, 0.2), ("dispatch", 3, 4, 0.2),
                          ("readback", 4, 96, 0.1), ("book", 96, 99, 3.0)):
        span("engine." + name, a, b, c)
    span("serve.publish", 100, 102, 1.0)
    span("engine.step", 103, 200, 1.0 + 1.0 + 0.5 + 1.0 + 0.1 + 1.0 + 0.9)
    for name, a, b, c in (("admit", 103, 104, 1.0), ("pack", 104, 108, 1.0),
                          ("h2d", 108, 110, 0.5), ("dispatch", 110, 111, 1.0),
                          ("readback", 111, 198, 0.1),
                          ("book", 198, 199, 1.0)):
        span("engine." + name, a, b, c)
    for name, at in (("DoEnqueueProgram", 4.7),
                     ("tpu::System::Execute=>Done", 95.3),
                     ("DoEnqueueProgram", 111.7),
                     ("tpu::System::Execute=>Done", 197.3)):
        anchors.append((name, int(at * MS), 1000))

    def lane(*pieces):
        return [("stream.deliver", int(a * MS), int((b - a) * MS),
                 {"tokens": 8}) for a, b in pieces]

    threads = [engine]
    if lanes:
        threads += [lane((94, 95.5), (99.5, 101), (105, 107)),
                    lane((100.5, 106), (150, 151))]

    def dev(ms):
        return int((ms - skew_ms) * MS)

    planes = {
        "/device:TPU:0": {
            "XLA Modules": [("jit__ragged_step_body(1)", dev(5), 90 * MS),
                            ("jit__ragged_decode_loop(2)", dev(112),
                             85 * MS)],
            "XLA Ops": [("%fusion.1 = bf16[8] fusion(...)", dev(5), 90 * MS),
                        ("%fusion.1 = bf16[8] fusion(...)", dev(112),
                         85 * MS)]},
        host_gaps.HOST_PLANE: {
            "llm-engine": [e[:3] for e in engine],
            "tfrt-queue/1": anchors,
            "lane": [e[:3] for t in threads[1:] for e in t]}}
    return planes, threads


ANCHORS = {"start_after": ["^DoEnqueueProgram$"],
           "end_before": ["^tpu::System::Execute=>Done$"]}


@pytest.mark.parametrize("skew_ms", [0.0, 1.25, -0.6])
def test_built_trace_gives_its_known_shares(skew_ms):
    got = host_offcpu.analyze(*built(skew_ms), ANCHORS)
    assert got["idle_ms"] == pytest.approx(17.0, abs=1e-5)
    assert got["gaps"] == 1 and got["dispatches"] == 2
    assert got["drain_ms"] == pytest.approx(1.0, abs=1e-5)
    assert got["offcpu_ms"] == pytest.approx(6.0, abs=1e-5)
    by = got["offcpu_ms_by_span"]
    assert by == pytest.approx({"engine.step": 0.5, "serve.publish": 1.0,
                                "engine.pack": 3.0, "engine.h2d": 1.5,
                                "engine.admit": 0.0, "engine.book": 0.0,
                                "engine.dispatch": 0.0}, abs=1e-5)
    assert got["lanes_ms"] == pytest.approx(8.0, abs=1e-5)
    # 95-95.5 under the drain, 99.5-107 under step, publish, the hole of
    # 102-103 (no span: in no row), admit and pack
    assert got["lanes_ms_by_span"] == pytest.approx(
        {"engine.readback": 0.5, "engine.book": 0.0, "engine.step": 0.5,
         "serve.publish": 2.0, "engine.admit": 1.0, "engine.pack": 3.0,
         "engine.h2d": 0.0, "engine.dispatch": 0.0}, abs=1e-5)
    assert got["deliver_spans"] == 5
    # 0.5 + 1.5 + 2 + 5.5 lane-ms inside the 17 ms of idle
    assert got["lanes_awake_mean"] == pytest.approx(9.5 / 17, abs=1e-6)
    assert got["deliver_ms_a_dispatch"] == pytest.approx(11.5 / 2)
    assert got["deliver_tokens_a_span"] == 8
    # the smallest CPU reading of the trace is readback's 0.1 ms; the two
    # steps and the publish ran 4.4 + 5.5 + 1.0 ms
    assert got["cpu_tick_us"] == pytest.approx(100.0)
    assert got["cpu_ticks"] == pytest.approx(109.0)
    own = got["own_ms_a_dispatch"]
    assert own["engine.pack"] == pytest.approx({"wall": 2.5, "cpu": 0.6})
    assert own["engine.step"]["wall"] == pytest.approx((1 + 1) / 2)
    assert own["engine.step"]["cpu"] == pytest.approx((0.5 + 0.9) / 2)


@pytest.mark.parametrize("why,offcpu,lanes", [
    ("all there", 100 * 6 / 17, 100 * 8 / 17),
    ("no cpu_us", None, 100 * 8 / 17),       # a program before PhaseClocks
    ("no lanes", 100 * 6 / 17, None),        # nothing streams
    ("no device plane", None, None),         # a CPU rehearsal
    ("no engine.step", None, None),
    ("no file", None, None)])
def test_read_gives_a_share_or_none_never_an_error(why, offcpu, lanes,
                                                   tmp_path, monkeypatch):
    planes, threads = built(0.0, cpu=why != "no cpu_us",
                            lanes=why != "no lanes")
    if why == "no device plane":
        del planes["/device:TPU:0"]
    if why == "no engine.step":
        threads = threads[1:]
        planes[host_gaps.HOST_PLANE]["llm-engine"] = []
    monkeypatch.setattr(trace_reduce, "find_xplane",
                        lambda d: None if why == "no file" else d)
    calls = []
    monkeypatch.setattr(host_offcpu, "read_trace",
                        lambda p: calls.append(p) or (planes, threads))
    data = {"trace": {"dir": str(tmp_path)}}
    for quantity, want in (("offcpu_pct", offcpu), ("lanes_pct", lanes)):
        got = host_offcpu.read(data, {"quantity": quantity,
                                      "anchors": ANCHORS})
        assert got is None if want is None \
            else got == pytest.approx(want, abs=1e-4)
    assert len(calls) <= 1                       # one parse for both
    if offcpu is None and lanes is None:
        assert "notes" not in data
    else:
        assert data["notes"]["host_offcpu"]["idle_ms"] \
            == pytest.approx(17.0, abs=1e-5)
        with pytest.raises(ValueError):
            host_offcpu.read(data, {"quantity": "nonsense",
                                    "anchors": ANCHORS})
    assert host_offcpu.read({}, {"quantity": "offcpu_pct"}) is None


def test_an_idle_that_differs_from_host_gaps_is_an_error(monkeypatch):
    """The reader rebuilds host_gaps's idle intervals: if the two ever
    disagree, it says so instead of reporting shares of another idle."""
    planes, threads = built()
    base = host_gaps.analyze(planes, ANCHORS)
    monkeypatch.setattr(host_gaps, "analyze", lambda *a: dict(
        base, idle_ms=base["idle_ms"] * 1.01))
    with pytest.raises(ValueError, match="idle"):
        host_offcpu.analyze(planes, threads, ANCHORS)


def test_recorded_tpu_trace_of_the_parents_spans_reads_as_nothing():
    """PR 24's recorded trace: spans without cpu_us, no lanes. The reader
    rebuilds host_gaps's own idle from it and reports neither share."""
    with open(os.path.join(ROOT, "benchmark", "fixtures",
                           "expected_host_gaps.json")) as f:
        want = json.load(f)
    planes, threads = host_offcpu.read_trace(os.path.join(
        ROOT, "benchmark", "fixtures", want["trace"]))
    assert planes == trace_reduce.read_planes(os.path.join(
        ROOT, "benchmark", "fixtures", want["trace"]))
    got = host_offcpu.analyze(planes, threads, want["anchors"])
    assert got["idle_ms"] == pytest.approx(want["analysis"]["idle_ms"],
                                           abs=1e-6)
    assert got["gaps"] == want["analysis"]["gaps"]
    assert got["drain_ms"] == pytest.approx(
        want["analysis"]["idle_ms_by_span"]["engine.readback:drain"])
    assert "offcpu_ms" not in got and "lanes_ms" not in got


# -------------------------------------------------- (d) the metric files

@pytest.fixture(scope="module")
def probed():
    """stats_open / stats_close as the benchmark's probe takes them
    (dict(engine.stats)) around some work of a tiny engine."""
    eng = InferenceEngine(LlamaConfig.tiny(n_layers=1, dtype=jnp.float32),
                          page_size=8, total_pages=32, max_batch=2,
                          max_seq_len=64, prefill_chunk=16, decode_chunk=2)
    eng.generate(list(range(1, 10)), max_new_tokens=3)
    a = dict(eng.stats)
    t0 = time.monotonic()
    for n in (11, 7):
        eng.generate(list(range(1, n)), max_new_tokens=8)
    return {"stats_open": a, "stats_close": dict(eng.stats),
            "window_s": time.monotonic() - t0, "config": {}}


@pytest.mark.parametrize("name", METRICS)
def test_metric_resolves_and_reads(name, probed):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry["workloads"] == CELLS and entry["moves"] == "out_tok_per_s"
    assert entry["layer"] == next(
        m for m in bench["per_layer"]
        if m["name"] == "batch_occupancy_pct")["layer"]
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    assert spec["name"] == name
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    data = dict(probed)
    value = reader.read(data, spec["args"])
    if spec["reader"] == "host_offcpu":
        assert entry["source"] == "program_span" and value is None
        return
    assert entry["source"] == "program_counter"
    assert set(spec["args"]["num"] + spec["args"]["den"]) \
        <= set(probed["stats_close"])
    if entry["unit"] == "%":
        # a tiny engine on the CPU never leaves it: its off-CPU share is
        # zero up to the clocks' reads and, under it, the few lines
        # generate() runs between two steps (CPU the host counter holds
        # and no phase's wall does: a few us against steps of ~200 here)
        assert -10.0 <= value <= 100.0
    else:
        # ms of host work a dispatch: under the whole run, over nothing
        assert 0.0 < value < probed["window_s"] * 1e3
    clocks = data["notes"]["engine_clocks"]
    assert set(clocks["wall_ms_a_dispatch"]) == set(PHASES)
    assert clocks["host_cpu_ms_a_dispatch"] >= 0.0
    # generate() drives step() itself: no serve loop, nothing between steps
    assert 90.0 < clocks["covered_pct"] <= 100.0
    # a program without the clocks (the parent): nothing, not an error
    old = {k: {s: v for s, v in probed[k].items()
               if not s.startswith(("wall_ns_", "cpu_ns_"))}
           for k in ("stats_open", "stats_close")}
    assert reader.read(dict(old, config={}), spec["args"]) is None


# --------------------------------- (e) the hand-over, one loop turn at a time

class _Turned(LLMServer):
    """An LLMServer whose engine thread does nothing: the test turns the
    loop itself (LLMServer._turn), so every hand-over has a known place."""

    def _loop(self):
        pass


def test_hand_over_is_one_launch_late_and_at_once_when_the_engine_runs_dry():
    import queue as queue_mod

    server = _Turned(model_config={"n_layers": 1, "dtype": jnp.float32},
                     engine_config=dict(ENGINE, seed=5))
    server._thread.join(timeout=10)
    eng, stats = server.engine, server.engine.stats
    want = InferenceEngine(LlamaConfig.tiny(n_layers=1, dtype=jnp.float32),
                           **dict(ENGINE, seed=5)).generate(
        list(range(1, 12)), max_new_tokens=10)
    q = queue_mod.Queue()
    streamed = eng.add_request(list(range(1, 12)), 10)
    server._token_qs[streamed] = q
    called = eng.add_request(list(range(1, 12)), 10)
    done = threading.Event()
    server._events[called] = done

    def drained():
        out = []
        while not q.empty():
            out.append(q.get_nowait())
        return out

    elapsed0 = time.perf_counter_ns()
    walls0 = sum(stats[k] for k in WALL_KEYS)
    got, booked, turns = [], [], 0
    while eng.has_work():
        server._turn()
        turns += 1
        # what this turn's step booked is held, not delivered ...
        held = server._held
        assert held is not None and held[1].get(streamed)
        booked.append(list(held[1][streamed]))
        # ... and what the turn delivered (from inside its step, after its
        # dispatch) is the step before's, whole and nothing else
        items = drained()
        assert items == ([booked[-2]] if turns > 1 else []), (turns, items)
        got += [t for item in items for t in item]
        # the engine never ran dry: every hand-over rode a dispatch
        assert stats["publishes"] == stats["publishes_overlapped"] \
            == turns - 1
        assert not done.is_set() and called not in server._results
    assert turns >= 3 and streamed in server._held[0]
    # the engine has run dry: the next turn hands over at once, then sleeps
    server._wake.set()
    server._turn()
    assert server._held is None
    items = drained()
    assert items == [booked[-1], None]              # the tokens, then the end
    got += items[0]
    assert got == want
    assert done.is_set() and server._results[called] == want
    assert stats["publishes"] == stats["publishes_overlapped"] + 1 == turns
    # serve.publish ran inside engine.step: the ten walls still cover the
    # turns, each phase once (a turn's own few lines are in none)
    walls = sum(stats[k] for k in WALL_KEYS) - walls0
    elapsed = time.perf_counter_ns() - elapsed0
    assert 0.5 * elapsed < walls <= elapsed
    assert stats["wall_ns_publish"] > 0 and stats["wall_ns_wait"] > 0
    # an idle turn with nothing held publishes nothing
    server._wake.set()
    server._turn()
    assert stats["publishes"] == turns


def test_a_step_that_holds_hands_over_once_and_before_it_sleeps(
        late_decision, monkeypatch):
    """The serve loop under the late decision (llm/engine.py: step), one
    turn at a time on a scripted clock and device: a step that holds its
    launch back hands the step before's tokens over BEFORE it sleeps (the
    clients of the rows that ended can only come back after that), once,
    with a program on the device; a request that comes during the hold is
    admitted when the hold ends and rides the mixed step launched behind
    the flight; every stream still gets its tokens in order."""
    import queue as queue_mod

    from _scripted_device import ScriptedDevice

    device = ScriptedDevice(monkeypatch)
    server = _Turned(model_config={"n_layers": 1, "dtype": jnp.float32},
                     engine_config=dict(ENGINE, seed=5))
    server._thread.join(timeout=10)
    eng, stats = server.engine, server.engine.stats
    device.runs(eng)
    prompts = [list(range(1, 12)), list(range(3, 9))]
    plain = InferenceEngine(LlamaConfig.tiny(n_layers=1, dtype=jnp.float32),
                            **dict(ENGINE, seed=5))
    want = [plain.generate(p, max_new_tokens=n)
            for p, n in zip(prompts, (40, 9))]
    queues = [queue_mod.Queue(), queue_mod.Queue()]
    first = eng.add_request(prompts[0], 40)
    server._token_qs[first] = queues[0]
    seen_in_hold = []

    def in_hold(k):
        # the hand-over is done: nothing is held while the step sleeps
        seen_in_hold.append((server._held, stats["publishes"],
                             stats["publishes_overlapped"]))
        if k == 2:
            rid = eng.add_request(prompts[1], 9)
            server._token_qs[rid] = queues[1]
    device.during_hold = in_hold
    mixed_late = None
    while eng.has_work():
        publishes = stats["publishes"]
        holds = len(device.holds)
        server._turn()
        meta = eng._step_meta
        if len(device.holds) > holds:
            assert meta["held"] and stats["publishes"] - publishes <= 1
            if meta["late"] and meta["launched"] == "mixed":
                mixed_late = len(device.holds) - 1
    server._wake.set()
    server._turn()                                  # the flush
    assert len(device.holds) >= 5 and mixed_late == 2
    assert all(held is None for held, _, _ in seen_in_hold)
    # every hand-over but the last flush rode a program on the device
    assert stats["publishes"] == stats["publishes_overlapped"] + 1
    assert stats["late_mixed_launches"] == 1
    assert stats["held_launches"] >= stats["late_launches"] >= 5
    for q, tokens in zip(queues, want):
        got, item = [], q.get_nowait()
        while item is not None:
            got += item
            item = q.get_nowait()
        assert got == tokens and q.empty()
    # the holds are the wall of `wait`, beside the flush's own sleep
    assert stats["wall_ns_wait"] >= sum(ns for _, ns in device.holds)


def test_publish_overlap_pct_reads_the_two_counters():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"]
                 if m["name"] == "publish_overlap_pct")
    assert entry["workloads"] == CELLS and entry["moves"] == "out_tok_per_s"
    assert entry["source"] == "program_counter" and entry["unit"] == "%"
    assert entry["layer"] == next(
        m for m in bench["per_layer"]
        if m["name"] == "batch_occupancy_pct")["layer"]
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           "publish_overlap_pct.json")) as f:
        spec = json.load(f)
    assert spec["name"] == entry["name"]
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    server = LLMServer(model_config={"n_layers": 1, "dtype": jnp.float32},
                       engine_config=ENGINE)
    a = server.stats()
    for n in (11, 7, 20):
        server({"prompt_ids": list(range(1, n)), "max_tokens": 9})
    b = server.stats()
    data = {"stats_open": a, "stats_close": b, "config": {}, "window_s": 1.0}
    value = reader.read(data, spec["args"])
    # three lone requests: each ends over one flush, the rest overlapped
    n, over = (b[k] - a[k] for k in ("publishes", "publishes_overlapped"))
    assert n - over == 3 and over >= 3
    assert value == pytest.approx(100.0 * over / n) and 0 < value < 100
    # the parent's stats lack the two keys: nothing, not an error
    old = {k: {s: v for s, v in data[k].items()
               if not s.startswith("publishes")}
           for k in ("stats_open", "stats_close")}
    assert reader.read(dict(old, config={}), spec["args"]) is None
    # an engine nobody serves has no such counters either
    eng = InferenceEngine(LlamaConfig.tiny(n_layers=1, dtype=jnp.float32),
                          **ENGINE)
    assert "publishes" not in eng.stats
