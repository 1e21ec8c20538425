"""Start-up clocks (util/startup_clocks.py): a worker's time from its
stamp to "ready", by phase.

Units: the helper's import contract (no jax) and its silence outside a
connected worker; begin / phase / finish partition the interval; the
one-line summary. In process, on the CPU with a tiny configuration: an
LLMServer has every key, written once, summing to the interval from the
stamp to the end of its constructor; load_step_programs leaves one record
a program whose split the compile tracker attributed; a second engine in
the same process finds the programs resident. On a CPU cluster: a served
replica's startup.* spans reach the head's timeline under the creation
task with ONE trace id, which is the controller's serve.replica_start's,
and the summary line is in the worker's log.
"""

import os
import subprocess
import sys
import time

import jax.numpy as jnp
import pytest

from ray_tpu.util import startup_clocks as sc

#: shapes no other test of the suite uses: whatever ran in this process
#: before, the first engine here has to compile its programs
ENGINE = dict(total_pages=56, max_batch=3, max_seq_len=104, prefill_rows=2,
              page_size=8, decode_chunk=3, prefill_chunk=24)
MODEL = dict(n_layers=2, dtype=jnp.float32)
NAMES = ("llm.ragged_step", "llm.decode_loop", "llm.copy_page",
         "llm.init_params", "llm.init_kv")


@pytest.fixture(autouse=True)
def no_open_record():
    sc._record = None
    yield
    sc._record = None


# ------------------------------------------------------------------ helper

def test_helper_imports_no_jax_and_is_silent_without_a_worker():
    code = (
        "import sys, time; from ray_tpu.util import startup_clocks as sc; "
        "into = {}; sc.begin(time.time_ns() - 50_000_000); "
        "\nwith sc.phase('import', into, what='x'): time.sleep(0.01)\n"
        "with sc.program('llm.x', None, rows=2) as rec: pass\n"
        "assert rec['how'] == 'resident' and rec['rows'] == 2, rec; "
        "sc.finish(into, sc.TRAIN_PHASES); "
        "assert into['startup_ns_process'] >= 50_000_000, into; "
        "assert into['startup_ns_import'] >= 10_000_000, into; "
        "assert set(into) == {sc.PREFIX + p for p in sc.TRAIN_PHASES}, "
        "into; "
        "print('jax' in sys.modules)")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False", out.stdout


def test_phases_and_other_partition_the_interval():
    stamp = time.time_ns() - 30_000_000
    sc.begin(stamp)
    sc.begin(time.time_ns())             # a record is open: nothing happens
    into = {}
    with sc.phase("import", into):
        time.sleep(0.005)
    time.sleep(0.005)                            # between two phases: other
    with sc.phase("import", into):               # a phase met twice adds up
        time.sleep(0.005)
    with sc.phase("mesh"):                       # no dict: the record only
        time.sleep(0.002)
    assert "startup_ns_mesh" not in into
    sc.finish(into, sc.TRAIN_PHASES)
    end = time.time_ns()
    assert into["startup_ns_process"] >= 30_000_000
    assert into["startup_ns_import"] >= 10_000_000
    assert into["startup_ns_mesh"] >= 2_000_000
    assert into["startup_ns_other"] >= 5_000_000
    total = sum(into[sc.PREFIX + p] for p in sc.TRAIN_PHASES)
    assert 0.98 <= total / (end - stamp) <= 1.005
    assert sc._record is None                    # closed: the next is fresh
    # without a record a phase still clocks into the dict it is given
    late = {}
    with sc.phase("backend", late):
        pass
    assert set(late) == {"startup_ns_backend"} and sc._record is None


def test_a_record_the_constructor_left_open_is_held_until_begin():
    """pause (the actor's constructor returned) to the next begin (the
    method that finishes the clocks) is idle: no part of the interval."""
    stamp = time.time_ns() - 20_000_000
    sc.begin(stamp)
    with sc.phase("import"):
        time.sleep(0.005)
    held = -time.perf_counter_ns()
    sc.pause()
    sc.pause()                                   # held already: no new stamp
    time.sleep(0.2)                              # waiting for the driver
    sc.begin()                                   # _TrainWorker.run enters
    held += time.perf_counter_ns()
    with sc.phase("mesh"):
        time.sleep(0.005)
    into = {}
    sc.finish(into, sc.TRAIN_PHASES)
    lifetime = time.time_ns() - stamp
    total = sum(into[sc.PREFIX + p] for p in sc.TRAIN_PHASES)
    assert into["startup_ns_process"] >= 20_000_000
    assert held >= 200_000_000
    assert abs(total - (lifetime - held)) < 20_000_000
    assert into["startup_ns_other"] < 100_000_000
    # ... and a record only held, never finished, is finished by whoever
    # asks next, without the time it was held
    sc.begin()
    sc.pause()
    time.sleep(0.1)
    late = {}
    sc.finish(late, sc.TRAIN_PHASES)
    assert sum(late.values()) < 50_000_000


def test_summary_line_names_every_phase_and_program():
    stats = {sc.PREFIX + p: int(1e9 * (i + 1))
             for i, p in enumerate(sc.SERVE_PHASES)}
    programs = [
        {"name": "llm.ragged_step", "rows": 2, "how": "hit", "wall_s": 2.9,
         "trace_s": 0.4, "lower_s": 0.3, "backend_s": 0.1, "run_s": 2.1},
        {"name": "llm.decode_loop", "how": "cold", "wall_s": 4.6,
         "trace_s": 0.5, "lower_s": 0.5, "backend_s": 3.5, "run_s": 0.1}]
    line = sc.summary(stats, sc.SERVE_PHASES, programs)
    assert line.startswith("start-up 28.00 s: process 1.00, import 2.00, ")
    assert "programs 6.00 (llm.ragged_step[2] hit 2.90 = trace 0.40 + " \
        "lower 0.30 + backend 0.10 + run 2.10, llm.decode_loop cold 4.60" \
        in line
    assert line.endswith("), other 7.00")
    totals = sc.program_totals(programs)
    assert totals == {"startup_ns_trace_lower": 1_700_000_000,
                      "startup_ns_backend_compile": 3_600_000_000,
                      "startup_programs_cold": 1}


# ------------------------------------------- in process, a tiny configuration

@pytest.fixture(scope="module")
def servers():
    """Two LLMServers of one configuration, built one after the other in
    this process: (server, the ns its constructor took, the compiles the
    tracker booked for each of NAMES while it was built)."""
    from ray_tpu.llm.serve_llm import LLMServer
    from ray_tpu.util import compile_tracker
    tracker = compile_tracker.ensure_started(role="t")
    sc._record = None

    def compiles():
        return {n: (tracker.callable_stats(n) or {}).get("compiles", 0)
                for n in NAMES}

    out = []
    for _ in range(2):
        before = compiles()
        began = time.time_ns()
        server = LLMServer(dict(MODEL), dict(ENGINE, prefix_cache=True))
        out.append((server, time.time_ns() - began,
                    {n: c - before[n] for n, c in compiles().items()}))
    return out


def test_served_replica_has_every_key_written_once(servers):
    server, took_ns, _ = servers[0]
    stats = server.engine.stats
    first = {k: stats[k] for k in sc.SERVE_KEYS}     # KeyError: one is missing
    assert all(v >= 0 for v in first.values()), first
    assert not [k for k in stats if k.startswith("startup_")
                and k not in sc.SERVE_KEYS]
    # the seven phases partition the interval from the stamp to the end of
    # the constructor (the stamp: begin()'s own at the constructor's entry,
    # no daemon spawned us)
    total = sum(stats[sc.PREFIX + p] for p in sc.SERVE_PHASES)
    assert 0.98 <= total / took_ns <= 1.005
    assert stats["startup_ns_process"] < 1e8 and \
        stats["startup_ns_import"] == 0
    for name in ("backend", "weights", "pool", "programs"):
        assert stats[sc.PREFIX + name] > 0, name
    # ... written once: three requests' worth of steps leave them alone
    out = server({"prompt_ids": [1, 2, 3, 4, 5], "max_tokens": 6})
    assert len(out["token_ids"]) == 6
    assert server.engine.stats["steps"] >= 3
    assert {k: server.engine.stats[k] for k in sc.SERVE_KEYS} == first


def test_one_record_a_program_with_the_trackers_split(servers):
    server, _, _ = servers[0]
    eng = server.engine
    loaded = eng.startup_programs
    shapes = eng._fns.row_shapes
    assert [(p["name"], p.get("rows")) for p in loaded] == \
        [("llm.ragged_step", n) for n in shapes] \
        + [("llm.decode_loop", None), ("llm.copy_page", None)]
    assert len(loaded) == len(shapes) + 2 == eng._fns.program_budget
    for p in loaded:
        # a fresh process compiled each (no persistent cache in the suite)
        assert p["how"] == "cold" and not p["cache_hit"]
        assert p["trace_s"] > 0 and p["lower_s"] > 0 and p["backend_s"] > 0
        assert p["trace_s"] + p["lower_s"] + p["backend_s"] <= p["wall_s"]
        assert p["run_s"] == pytest.approx(
            p["wall_s"] - p["trace_s"] - p["lower_s"] - p["backend_s"])
    stats = eng.stats
    assert stats["startup_programs_cold"] == len(loaded)
    assert stats["startup_ns_trace_lower"] == pytest.approx(
        1e9 * sum(p["trace_s"] + p["lower_s"] for p in loaded), rel=1e-6)
    assert stats["startup_ns_trace_lower"] \
        + stats["startup_ns_backend_compile"] <= stats["startup_ns_programs"]
    # no engine.* clock moved, no request exists: the steady state's books
    # are as load_step_programs found them
    bare = type(eng)(eng.cfg, **dict(ENGINE, prefix_cache=False))
    before = dict(bare.stats)
    bare.load_step_programs()
    moved = {k for k in bare.stats if bare.stats[k] != before.get(k)}
    assert moved == {"startup_ns_programs", *sc.PROGRAM_KEYS}
    assert len(bare.startup_programs) == len(shapes) + 1     # no page copy


def test_second_engine_in_the_process_finds_the_programs_resident(servers):
    (first, _, compiled_1), (second, took_ns, compiled_2) = servers
    loaded = second.engine.startup_programs
    assert [p["how"] for p in loaded] == ["resident"] * len(loaded)
    assert all(p["trace_s"] == p["backend_s"] == 0 for p in loaded)
    stats = second.engine.stats
    assert stats["startup_programs_cold"] == 0
    assert stats["startup_ns_trace_lower"] == 0
    # its own record, stamped when its constructor began: not the first's
    total = sum(stats[sc.PREFIX + p] for p in sc.SERVE_PHASES)
    assert 0.98 <= total / took_ns <= 1.005
    # the tracker's books agree: every callable the seam wrapped compiled
    # once a shape for the first engine and never for the second, and none
    # of it was a persistent-cache hit (the suite runs without one)
    shapes = len(second.engine._fns.row_shapes)
    # (the weights' init is by configuration, the pool's zeros by leaf
    # shape: another test of this process may have left either resident)
    assert compiled_1 == {"llm.ragged_step": shapes, "llm.decode_loop": 1,
                          "llm.copy_page": 1,
                          "llm.init_params": compiled_1["llm.init_params"],
                          "llm.init_kv": compiled_1["llm.init_kv"]}
    assert compiled_1["llm.init_params"] <= 1
    assert compiled_2 == dict.fromkeys(NAMES, 0)
    report = second.engine_report()["compile_seconds"]
    assert set(report) == set(NAMES)
    for name, got in report.items():
        assert set(got) == {"wall_s", "trace_s", "lower_s", "backend_s",
                            "compiles", "cache_hits"}, name
        assert got["cache_hits"] == 0 and got["compiles"] >= compiled_1[name]
        assert got["trace_s"] + got["lower_s"] + got["backend_s"] \
            <= got["wall_s"] + 2e-3 * max(got["compiles"], 1), (name, got)


# ------------------------------------------------------------ a CPU cluster

@pytest.fixture
def served():
    import ray_tpu as rt
    from ray_tpu import serve
    from ray_tpu.llm import LLMServer
    rt.init(num_cpus=4, _system_config={
        "object_store_memory_bytes": 128 * 1024 * 1024,
        "metrics_export_period_s": 0.5})
    try:
        dep = serve.deployment(name="llm", max_ongoing_requests=4)(LLMServer)
        yield serve.run(dep.bind({"n_layers": 1},
                                 dict(ENGINE, prefix_cache=True)),
                        timeout_s=240)
    finally:
        serve.shutdown()
        rt.shutdown()


def test_a_pooled_workers_clocks_start_at_its_lease_and_hold_while_idle():
    """A worker that sat in the idle pool is stamped at the lease that took
    it out (node.py: leased_wall_ns -> become_actor's lease_wall_ns), not at
    its spawn; and the record its constructor left open is held until the
    method that finishes it (what _TrainWorker.run does) is called."""
    import ray_tpu as rt
    # the driver gives an idle lease back at once: the worker of the task
    # below is in the node's pool well before the actor asks for one
    rt.init(num_cpus=2, _system_config={
        "object_store_memory_bytes": 64 * 1024 * 1024,
        "lease_idle_linger_s": 0.05})
    try:
        @rt.remote
        def warm():
            return os.getpid()

        @rt.remote
        class Probe:
            def record(self):
                from ray_tpu.util import startup_clocks
                rec = startup_clocks._record
                return (rec.wall0_ns, startup_clocks.spawn_stamp(),
                        dict(rec.phases), rec.paused_ns is not None)

            def finish(self):
                from ray_tpu.util import startup_clocks
                startup_clocks.begin()
                into = {}
                startup_clocks.finish(into, startup_clocks.TRAIN_PHASES)
                return into, time.time_ns()

        for _ in range(3):               # (a loaded host: the give-back
            rt.get(warm.remote())        # is a few RPCs) a worker exists,
            time.sleep(1.5)              # and goes idle
            before = time.time_ns()
            probe = Probe.remote()
            wall0, spawned, phases, held = rt.get(probe.record.remote())
            if wall0 != spawned:
                break
            rt.kill(probe)               # spawned for its lease: once more
        assert spawned and wall0 - spawned >= 500_000_000   # pooled since
        assert before <= wall0 <= time.time_ns()            # the lease
        assert held and set(phases) == {"process", "import"}
        time.sleep(0.5)                  # the driver takes its time
        into, now = rt.get(probe.finish.remote())
        assert set(into) == {sc.PREFIX + p for p in sc.TRAIN_PHASES}
        assert into["startup_ns_process"] == phases["process"]
        assert sum(into.values()) <= now - wall0 - 400_000_000
    finally:
        rt.shutdown()


def _timeline(want, timeout=30.0):
    from ray_tpu.core.worker import global_worker
    head = global_worker.backend.head
    deadline = time.monotonic() + timeout
    while True:
        events = head.call_retrying("timeline_dump") or []
        if want(events) or time.monotonic() > deadline:
            return events
        time.sleep(0.3)


def test_spans_reach_the_timeline_under_the_creation_task(served):
    from ray_tpu.util import log_plane
    from ray_tpu.util.tracing import assemble_trace

    def complete(events):
        names = [e["name"] for e in events if e.get("kind") == "startup"]
        return "serve.replica_start" in names \
            and "startup.programs" in names \
            and names.count("startup.import") >= 3     # controller's, ours

    events = _timeline(complete)
    starts = [e for e in events if e["name"] == "serve.replica_start"]
    assert len(starts) == 1, [e["name"] for e in events
                              if e.get("kind") == "startup"]
    start = starts[0]
    assert start["deployment"] and start["replica_id"].startswith(
        start["deployment"] + "#") and start["polls"] >= 1
    # ONE trace: the controller's span at the root, the creation task its
    # child, and the replica's phases under the creation task
    roots = assemble_trace(events, trace_id=start["trace_id"])
    assert [r["name"] for r in roots] == ["serve.replica_start"]
    creation = [c for c in roots[0]["children"]
                if c["kind"] == "actor_create"]
    assert len(creation) == 1 and creation[0]["name"].endswith(".__init__")
    phases = [c for c in creation[0]["children"] if c["kind"] == "startup"]
    names = [c["name"] for c in phases]
    for p in ("process", "import", "backend", "weights", "pool", "programs"):
        assert "startup." + p in names, names
    assert names.count("startup.import") == 2     # the actor's class, the user's
    inside = next(c for c in phases if c["name"] == "startup.programs")
    programs = inside["children"]                 # one a program, under it
    assert {c["name"] for c in programs} == {"startup.program"}
    assert [(c["program"], c.get("rows")) for c in programs] == [
        ("llm.ragged_step", 1), ("llm.ragged_step", 2),
        ("llm.decode_loop", None), ("llm.copy_page", None)]
    assert all(c["how"] == "cold" and c["wall_s"] >= c["backend_s"]
               for c in programs)
    span_of = {c["name"]: c for c in phases}
    # the process stamp is the daemon's, from before the worker existed;
    # the controller's span holds the whole of the replica's start
    assert span_of["startup.process"]["start"] >= start["start"] - 0.05
    assert span_of["startup.programs"]["end"] <= start["end"] + 0.05
    assert all(inside["start"] - 1e-3 <= c["start"] and c["end"] <=
               inside["end"] + 1e-3 for c in programs)
    # ... and the one line is in the worker's log
    log_dir = log_plane.session_log_dir(os.environ.get("RTPU_SESSION", ""))
    deadline = time.monotonic() + 20
    found = []
    while not found and time.monotonic() < deadline:
        for name in os.listdir(log_dir):
            if name.startswith("worker-") and name.endswith(".log"):
                with open(os.path.join(log_dir, name),
                          errors="replace") as f:
                    found += [ln for ln in f if "start-up " in ln
                              and "llm.decode_loop cold" in ln]
        time.sleep(0.3)
    assert len(found) == 1, found
    assert "process " in found[0] and "other " in found[0]
