"""The engine host loop's trace annotations and the counts taken at the same
boundaries: a short profiled run of a tiny served engine must yield
engine.step spans whose children partition them and whose kinds equal the
engine's own dispatch counters; the request log must say what a request
waited for; device_report() must be callable while the engine steps.

The span names are a contract with benchmark/readers/host_gaps.py (PERF.md
lists them). Nothing here is a time: the trace is read for structure."""

import glob
import os
import threading

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.llm import InferenceEngine
from ray_tpu.llm.serve_llm import LLMServer
from ray_tpu.models.llama import LlamaConfig

CHILDREN = {"engine.admit", "engine.pack", "engine.h2d", "engine.dispatch",
            "engine.readback", "engine.book", "engine.metrics"}
ENGINE = dict(page_size=8, total_pages=64, max_batch=4, max_seq_len=96,
              prefill_chunk=16, prefill_rows=2, decode_chunk=4)


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """A tiny LLMServer answering five overlapping requests under the
    profiler (host spans only, as the benchmark's start_trace asks):
    ([(name, start, end, {metadata})] in start order, the engine's
    counters before, after)."""
    server = LLMServer(model_config={"n_layers": 2, "dtype": jnp.float32},
                       engine_config=ENGINE)
    server({"prompt_ids": list(range(1, 20)), "max_tokens": 6})   # compile
    log_dir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    before = dict(server.engine.stats)
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        threads = [threading.Thread(target=server, args=({
            "prompt_ids": list(range(3, 3 + n)), "max_tokens": 9},))
            for n in (40, 7, 23, 5, 30)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
        after = dict(server.engine.stats)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[-1]
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("engine.", "serve.")):
                    events.append((ev.name, ev.start_ns,
                                   ev.start_ns + ev.duration_ns,
                                   dict(ev.stats)))
    events.sort(key=lambda e: (e[1], -e[2]))
    return events, before, after


def _steps_with_children(events):
    steps = [e for e in events if e[0] == "engine.step"]
    inside = [[c for c in events if c[0] in CHILDREN
               and s[1] <= c[1] and c[2] <= s[2]] for s in steps]
    return steps, inside


def test_children_nest_inside_steps_and_do_not_overlap(profiled):
    events, _, _ = profiled
    steps, inside = _steps_with_children(events)
    assert len(steps) >= 4
    # every child lies in exactly one step: none is left outside
    assert sum(len(c) for c in inside) \
        == sum(1 for e in events if e[0] in CHILDREN)
    for (_, s0, s1, _), kids in zip(steps, inside):
        for a, b in zip(kids, kids[1:]):
            assert a[2] <= b[1], (a, b)          # in order, no overlap
        assert sum(k[2] - k[1] for k in kids) <= s1 - s0
    for a, b in zip(steps, steps[1:]):
        assert a[2] <= b[1]


def test_a_dispatching_step_has_every_phase_once_in_order(profiled):
    """... and sends ONE host array: engine.h2d opens once a dispatch and
    h2d_arrays counts the descriptors it sent, with the two clocks the
    benchmark's engine_h2d_ms and engine_host_ms read still running. A
    step launches at most one program (`launched`: pack, h2d, dispatch)
    and books at most one (`kind`: readback, book), in that order: the
    one it launched, or the one the step before left in flight, behind
    which this step's is then queued."""
    events, before, after = profiled
    assert after["h2d_arrays"] - before["h2d_arrays"] \
        == sum(after[k] - before[k]
               for k in ("ragged_dispatches", "decode_dispatches")) \
        == sum(e[0] == "engine.h2d" for e in events) > 0
    assert all(after[k] > before[k]
               for k in ("wall_ns_pack", "wall_ns_h2d"))
    steps, inside = _steps_with_children(events)
    order = ["engine.admit", "engine.pack", "engine.h2d", "engine.dispatch",
             "engine.readback", "engine.book"]
    seen = ahead = 0
    for step, kids in zip(steps, inside):
        names = [k[0] for k in kids if k[0] != "engine.metrics"]
        launched = step[3]["launched"] != "none"
        booked = step[3]["kind"] != "none"
        # engine.pack opens before the engine knows it launches nothing
        assert [n for n in names if n != "engine.pack" or launched] \
            == [n for n in order if n == "engine.admit"
                or (launched and n in order[1:4])
                or (booked and n in order[4:])], (names, step[3])
        seen += launched and booked
        ahead += bool(step[3].get("ahead"))
    assert seen >= 4
    assert ahead == after["ahead_dispatches"] - before["ahead_dispatches"] > 0


def test_step_kinds_equal_the_dispatch_counters(profiled):
    events, before, after = profiled
    meta = [e[3] for e in events if e[0] == "engine.step"]
    for kind, counter in (("mixed", "ragged_dispatches"),
                          ("decode", "decode_dispatches")):
        of_kind = [m for m in meta if m["kind"] == kind]
        assert len(of_kind) == after[counter] - before[counter] > 0
        # `dispatch` is the counter's value, the index the request log's
        # chunks carry for a mixed step
        assert [m["dispatch"] for m in of_kind] \
            == list(range(before[counter] + 1, after[counter] + 1))
    assert sum(m["real_tokens"] for m in meta if m["kind"] == "mixed") \
        == after["ragged_real_tokens"] - before["ragged_real_tokens"]
    assert sum(m["slot_tokens"] for m in meta if m["kind"] == "mixed") \
        == after["ragged_slot_tokens"] - before["ragged_slot_tokens"]
    # a mixed step's slot_tokens is the shape it RAN in: max_batch decode
    # slots + the smallest compiled number of chunk rows that held its
    # deal; the steps that ran the smaller one are counted
    shapes = {ENGINE["max_batch"] + r * ENGINE["prefill_chunk"]
              for r in (1, ENGINE["prefill_rows"])}
    slots = [m["slot_tokens"] for m in meta if m["kind"] == "mixed"]
    assert set(slots) <= shapes
    assert sum(s == min(shapes) for s in slots) \
        == after["ragged_small_dispatches"] \
        - before["ragged_small_dispatches"]
    assert all(m["real_tokens"] <= m["slot_tokens"] for m in meta
               if m["kind"] == "mixed")
    # a mixed step's real tokens: one a decode row, the rest its chunk
    # rows', one row at least and prefill_rows at most, a sequence's
    # second row in a step counted as joined
    mixed = [m for m in meta if m["kind"] == "mixed"]
    delta = {k: after[k] - before[k] for k in (
        "prefill_tokens", "chunk_rows", "chunk_rows_joined")}
    assert sum(m["real_tokens"] - m["decode_rows"] for m in mixed) \
        == delta["prefill_tokens"]
    assert len(mixed) <= delta["chunk_rows"] \
        <= ENGINE["prefill_rows"] * len(mixed)
    assert 0 <= delta["chunk_rows_joined"] \
        <= delta["chunk_rows"] - len(mixed)
    assert sum(m["decode_rows"] * (1 if m["kind"] == "mixed"
                                   else ENGINE["decode_chunk"])
               for m in meta if m["kind"] != "none") \
        == after["decode_tokens"] - before["decode_tokens"]
    admitted = sum(e[3]["admitted"] for e in events
                   if e[0] == "engine.admit")
    assert admitted == 5


def _publishes(events):
    """serve.publish spans, split: (nested: [(publish, its step)], flushes:
    those no engine.step overlaps)."""
    steps = [e for e in events if e[0] == "engine.step"]
    nested, flushes = [], []
    for p in (e for e in events if e[0] == "serve.publish"):
        over = [s for s in steps if s[1] < p[2] and p[1] < s[2]]
        if over:
            assert len(over) == 1 and over[0][1] <= p[1] \
                and p[2] <= over[0][2], p       # wholly inside ONE step
            nested.append((p, over[0]))
        else:
            flushes.append(p)
    return nested, flushes


def test_publish_lies_between_dispatch_and_readback_of_the_next_step(
        profiled):
    """A step's tokens go to their waiters from inside the NEXT step,
    with that step's program on the device: after engine.dispatch ended
    and before engine.readback began."""
    events, _, _ = profiled
    nested, _ = _publishes(events)
    assert len(nested) >= 3
    for p, step in nested:
        kids = {c[0]: c for c in events if c[0] in CHILDREN
                and step[1] <= c[1] and c[2] <= step[2]}
        # the step sleeps on a program: the one it launched, or (it
        # launched none) the one in flight, which it books
        assert step[3]["launched"] != "none" or step[3]["kind"] != "none"
        if step[3]["launched"] != "none":
            assert kids["engine.dispatch"][2] <= p[1], (p, kids)
        if step[3]["kind"] != "none":
            assert p[2] <= kids["engine.readback"][1], (p, kids)
    # a run of at least three consecutive steps, each with its publish
    steps = [e for e in events if e[0] == "engine.step"]
    with_publish = {id(step) for _, step in nested}
    run = best = 0
    for step in steps:
        run = run + 1 if id(step) in with_publish else 0
        best = max(best, run)
    assert best >= 3
    # __call__ requests wait on an event, not a stream
    assert all(p[3]["streams"] == 0 for p, _ in nested)


def test_a_publish_outside_a_step_is_a_flush_before_a_sleep(profiled):
    """What no dispatch will carry is handed over at once: the only
    serve.publish outside engine.step is the one the loop makes after a
    step that launched nothing (it booked the program in flight, and the
    pipeline is empty) or when the engine has run dry, followed by
    serve.wait with no step between; and serve.wait never overlaps a
    step."""
    events, _, _ = profiled
    steps = [e for e in events if e[0] == "engine.step"]
    waits = [e for e in events if e[0] == "serve.wait"]
    for w in waits:
        assert not any(s[1] < w[2] and w[1] < s[2] for s in steps), w
    nested, flushes = _publishes(events)
    assert flushes                       # the five requests did end
    for p in flushes:
        after = [e for e in events
                 if e[0] in ("engine.step", "serve.wait") and e[1] >= p[2]]
        before = [s for s in steps if s[2] <= p[1]]
        assert not after or after[0][0] == "serve.wait" \
            or before[-1][3]["launched"] == "none", (p, after[:1])
    # every step that handed nothing over had nothing held: the step
    # before it booked no token (none at all, or a mixed step of prefill
    # chunks only)
    with_publish = {id(step) for _, step in nested}
    for prev, step in zip(steps, steps[1:]):
        if id(step) not in with_publish and (
                step[3]["launched"] != "none" or step[3]["kind"] != "none"):
            chunks_only = prev[3]["kind"] == "none" \
                or prev[3]["kind"] == "mixed" \
                and prev[3]["decode_rows"] == 0
            flushed = any(prev[2] <= f[1] and f[2] <= step[1]
                          for f in flushes)
            assert chunks_only or flushed, (prev, step)


def test_publish_counters_count_the_spans(profiled):
    """engine.stats `publishes` / `publishes_overlapped`: every hand-over
    that had something, and those made under a running program."""
    events, before, after = profiled
    nested, flushes = _publishes(events)
    # the trace stops with the loop asleep: every hand-over is a span
    assert after["publishes"] - before["publishes"] \
        == len(nested) + len(flushes)
    assert after["publishes_overlapped"] - before["publishes_overlapped"] \
        == len(nested)
    assert 0 < after["publishes_overlapped"] <= after["publishes"]


def test_step_without_a_callable_is_unchanged_and_with_one_calls_it_once():
    """generate(), llm/batch.py and every test that steps the engine
    itself pass nothing: the same tokens, the same counters; a callable is
    called once by a step that launches a program (after its dispatch) or
    books the one in flight, before the step reads any program back, and
    never by a step that does neither."""
    def engine():
        return InferenceEngine(
            LlamaConfig.tiny(n_layers=1, dtype=jnp.float32), page_size=8,
            total_pages=32, max_batch=2, max_seq_len=64, prefill_chunk=16,
            decode_chunk=2, seed=3)

    prompts = [list(range(1, 12)), list(range(2, 9)), list(range(4, 30))]
    plain, hooked = engine(), engine()
    calls = []

    def hook():
        s = hooked.stats
        calls.append((s["wall_ns_dispatch"], s["wall_ns_readback"],
                      s["ragged_dispatches"] + s["decode_dispatches"]))

    done_plain, done_hooked = {}, {}
    for eng, done, kw in ((plain, done_plain, {}),
                          (hooked, done_hooked, {"after_dispatch": hook})):
        rids = [eng.add_request(p, max_new_tokens=5) for p in prompts]
        while eng.has_work():
            seen = (eng.stats["wall_ns_dispatch"],
                    eng.stats["wall_ns_readback"],
                    eng.stats["ragged_dispatches"]
                    + eng.stats["decode_dispatches"])
            n_calls = len(calls)
            got = eng.step(**kw)
            done.update({rids.index(r): t for r, t in got.items()})
            booked = eng.stats["ragged_dispatches"] \
                + eng.stats["decode_dispatches"] - seen[2]
            launched = eng.stats["wall_ns_dispatch"] > seen[0]
            assert booked in (0, 1)
            if kw:
                # called once, by a step that launched or booked: after
                # the dispatch (its wall counted), before any readback or
                # booking
                assert len(calls) - n_calls == (launched or booked == 1)
            if kw and len(calls) > n_calls:
                assert (calls[-1][0] > seen[0]) == launched
                assert calls[-1][1:] == seen[1:]
    assert done_plain == done_hooked and len(done_plain) == 3
    counters = ("steps", "decode_steps", "decode_tokens", "prefill_tokens",
                "decode_dispatches", "ragged_dispatches")
    assert {k: plain.stats[k] for k in counters} \
        == {k: hooked.stats[k] for k in counters}
    n_calls = len(calls)
    assert n_calls >= hooked.stats["ragged_dispatches"] \
        + hooked.stats["decode_dispatches"]
    # a step with nothing to launch and nothing to book calls nothing
    assert not hooked.has_work()
    assert hooked.step(after_dispatch=hook) == {}
    assert len(calls) == n_calls
    assert "publishes" not in plain.stats       # the serve loop's counters


def test_request_log_tells_in_flight_wait_from_refusal():
    """One slot, two requests: the second is seen by the first admission
    scan after it arrived and refused until the first one ends."""
    eng = InferenceEngine(LlamaConfig.tiny(n_layers=1, dtype=jnp.float32),
                          page_size=8, total_pages=32, max_batch=1,
                          max_seq_len=64, prefill_chunk=16, decode_chunk=2,
                          request_log=True)
    first = eng.add_request(list(range(1, 12)), max_new_tokens=5)
    second = eng.add_request(list(range(2, 9)), max_new_tokens=5)
    while eng.has_work():
        eng.step()
    a, b = (eng.request_log.get(r).to_dict() for r in (first, second))
    for rec in (a, b):
        assert 0 <= rec["wait_in_flight"] <= rec["queue_wait"]
        assert rec["mixed_tokens"] <= rec["n_generated"] - 1
        assert 0 <= rec["mixed_stall"] <= rec["e2e"] - rec["ttft"]
    assert a["queue_wait"] == a["wait_in_flight"]   # admitted by that scan
    assert b["queue_wait"] > b["wait_in_flight"]    # no slot until a ended
    assert b["queue_wait"] >= a["e2e"] - (b["t0_wall"] - a["t0_wall"]) - 0.05
    # a alone in the batch never shares a step with a prefill after its
    # own; b's prefill rode no step that a decoded in (a had ended)
    assert a["mixed_tokens"] == 0 and a["mixed_stall_share"] == 0.0


def test_device_report_from_another_thread_while_stepping():
    """Each step donates the page pool; the report must not walk it."""
    eng = InferenceEngine(LlamaConfig.tiny(n_layers=1, dtype=jnp.float32),
                          page_size=8, total_pages=32, max_batch=2,
                          max_seq_len=64, prefill_chunk=16, decode_chunk=2)
    want = eng.device_report()
    assert want["kv_bytes"] == sum(x.nbytes for x in eng.kv.values()) > 0
    assert want["param_bytes"] > 0
    assert sum(d["engine_bytes"] for d in want["devices"]) \
        == want["param_bytes"] + want["kv_bytes"]
    eng.generate(list(range(1, 10)), max_new_tokens=3)          # compile
    reports, errors = [], []
    stop = threading.Event()

    def reader():
        try:
            while not stop.is_set():
                reports.append(eng.device_report())
        except Exception as e:  # noqa: BLE001 — the test's verdict
            errors.append(e)

    t = threading.Thread(target=reader)
    t.start()
    try:
        for _ in range(3):
            eng.generate(list(range(1, 14)), max_new_tokens=12)
    finally:
        stop.set()
        t.join(timeout=60)
    assert not t.is_alive() and not errors, errors
    assert len(reports) > 0
    for key in ("platform", "tp", "paged_impl", "param_bytes", "kv_bytes"):
        assert all(r[key] == want[key] for r in reports), key
    assert all(r["devices"][0]["engine_bytes"]
               == want["devices"][0]["engine_bytes"] for r in reports)


def test_a_held_step_sleeps_in_engine_hold_between_two_admissions(
        tmp_path, late_decision, monkeypatch):
    """The late decision's spans (llm/engine.py: step), on a scripted
    clock and device so that every machine sees the same steps: a step
    that holds its launch back is engine.admit, engine.hold, engine.admit
    again, then pack .. dispatch of what it launches and readback, book of
    the flight, all inside the one engine.step, whose metadata says
    `held`, whether it launched `late` (behind the flight still running)
    and what (`launched`); engine.hold opens in no other step, and the
    three counters count what the spans say."""
    from _scripted_device import ScriptedDevice

    device = ScriptedDevice(monkeypatch)
    eng = InferenceEngine(LlamaConfig.tiny(n_layers=1, dtype=jnp.float32),
                          **ENGINE)
    eng.generate(list(range(1, 20)), max_new_tokens=6)      # compile
    eng._program_ns.clear()         # what ran on the CPU is no estimate
    device.runs(eng)
    eng.add_request(list(range(1, 12)), 40)
    device.during_hold = lambda k: k == 2 and eng.add_request(
        list(range(3, 9)), 9)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    before = dict(eng.stats)
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        while eng.has_work():
            eng.step()
    finally:
        jax.profiler.stop_trace()
    after = dict(eng.stats)
    path = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                  "*.xplane.pb"))[-1]
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    events.append((ev.name, ev.start_ns,
                                   ev.start_ns + ev.duration_ns,
                                   dict(ev.stats)))
    events.sort(key=lambda e: (e[1], -e[2]))
    steps = [e for e in events if e[0] == "engine.step"]
    kids = [[c[0] for c in events
             if c[0] not in ("engine.step", "engine.metrics")
             and s[1] <= c[1] and c[2] <= s[2]] for s in steps]
    # every hold lies in a step, and only in steps that say `held`
    assert sum(names.count("engine.hold") for names in kids) \
        == sum(e[0] == "engine.hold" for e in events) \
        == len(device.holds) >= 5
    launch = ["engine.pack", "engine.h2d", "engine.dispatch"]
    booking = ["engine.readback", "engine.book"]
    held = late = late_mixed = 0
    for step, names in zip(steps, kids):
        meta = step[3]
        launched = meta["launched"] != "none"
        if "engine.hold" in names:
            assert meta["held"] and meta["kind"] != "none"
            # pack opens before the engine knows it launches nothing
            assert [n for n in names if n != "engine.pack" or launched] \
                == ["engine.admit", "engine.hold", "engine.admit"] \
                + (launch if launched else []) + booking, (names, meta)
        else:
            assert names.count("engine.admit") == 1
        held += bool(meta["held"])
        late += bool(meta["late"])
        late_mixed += bool(meta["late"]) and meta["launched"] == "mixed"
        if meta["late"]:
            assert meta["held"] and launched and meta["ahead"] is not None
    assert (held, late, late_mixed) == tuple(
        after[k] - before[k] for k in (
            "held_launches", "late_launches", "late_mixed_launches"))
    assert late >= 5 and late_mixed == 1 and held > late
    # where the flight's end was marked, the step says how far off the
    # estimate was: nothing, on a device that runs a kind a fixed time
    assert {m["end_late_us"] for _, _, _, m in steps
            if "end_late_us" in m} == {0.0}
