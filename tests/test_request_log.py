"""LLM request flight recorder: record timing math, ring bounds, SLO
accounting, telemetry export — plus the engine lifecycle end-to-end
(finish reasons, recompute preemption with both record phases, eviction
of unsatisfiable working sets).

The recorder module itself must import (and run) without jax: the
cluster backend's telemetry thread drains it from any worker, and the
pure-record tests here are part of the tier-1 CPU sweep.
"""

import subprocess
import sys

import pytest

from ray_tpu.llm.request_log import (DECODE_ENTRY_CAP, FlightRecorder,
                                     RequestRecord, drain_all_exports)

# ------------------------------------------------------------ pure record


def _rec(**kw):
    kw.setdefault("rid", "r0")
    kw.setdefault("prompt_tokens", 8)
    kw.setdefault("max_new_tokens", 4)
    return RequestRecord(kw.pop("rid"), kw.pop("prompt_tokens"),
                         kw.pop("max_new_tokens"), **kw)


def test_record_timing_math():
    r = _rec(trace_id="t-abc")
    t0 = r.t0
    r.note_admit(t0 + 0.001, cached_tokens=3)
    r.note_chunk(t0 + 0.003, n_tokens=5, dispatch_idx=7)
    r.note_decode(t0 + 0.005, 1)   # first token -> TTFT
    r.note_decode(t0 + 0.006, 1)
    r.note_decode(t0 + 0.007, 1)
    assert r.queue_wait == pytest.approx(0.001)
    assert r.cached_tokens() == 3
    assert r.ttft == pytest.approx(0.005)
    assert r.n_generated == 3
    # TPOT = (last - first) / (n - 1); first token is not an entry
    assert r.tpot == pytest.approx(0.001)
    assert r.decode_entries() == [
        (pytest.approx(0.001), 1), (pytest.approx(0.001), 1)]

    d = r.to_dict()
    assert d["rid"] == "r0" and d["trace_id"] == "t-abc"
    assert d["chunks"] == [[pytest.approx(0.003), 5, 7]]
    assert d["admits"] == [[pytest.approx(0.001), 3]]
    assert not d["done"] and d["finish_reason"] is None


def test_record_single_token_has_no_tpot():
    r = _rec()
    r.note_decode(r.t0 + 0.004, 1)
    assert r.ttft == pytest.approx(0.004)
    assert r.tpot is None and r.n_generated == 1


def test_note_first_idempotent_across_preemption():
    r = _rec()
    r.note_first(r.t0 + 0.002)
    r.note_preempt(r.t0 + 0.003)
    r.note_admit(r.t0 + 0.004, 0)   # re-admit: second phase
    r.note_first(r.t0 + 0.009)      # re-prefill must NOT move TTFT
    assert r.ttft == pytest.approx(0.002)
    assert len(r.admits) == 1 and len(r.preempt_ts) == 1
    assert r.to_dict()["preempts"] == 1


def test_record_decode_entry_cap_overflow_aggregates():
    r = _rec(max_new_tokens=10_000)
    t, n = r.t0, 0
    for i in range(DECODE_ENTRY_CAP + 40):
        t += 0.001
        r.note_decode(t, 2)
        n += 2
    assert r.n_generated == n
    # first call set TTFT (no entry); cap entries kept verbatim
    assert len(r.decode_entries()) == DECODE_ENTRY_CAP
    assert r.to_dict()["decode_overflow_tokens"] == (40 - 1) * 2
    # aggregates stay exact past the cap: TPOT uses last_ts, not entries
    # (2 tokens per dispatch -> per-token latency is half the interval)
    n_calls = DECODE_ENTRY_CAP + 40
    assert r.tpot == pytest.approx((n_calls - 1) * 0.001 / (n - 1),
                                   rel=1e-6)


# events after the first token: ("loop", n) a decode loop's n tokens,
# ("mixed", 1) a mixed step's one, ("preempt",) eviction and re-admission
# (the re-prefill's last chunk samples one more token: a mixed one)
MIXED_CASES = {
    "decode-only": [("loop", 8), ("loop", 8), ("loop", 3)],
    "mixed-only": [("mixed", 1), ("mixed", 1), ("mixed", 1)],
    "interleaved": [("loop", 8), ("mixed", 1), ("loop", 8), ("mixed", 1),
                    ("mixed", 1), ("loop", 2)],
    "single-token": [],
    "preempted-and-readmitted": [("loop", 8), ("mixed", 1), ("preempt",),
                                 ("loop", 8)],
}


@pytest.mark.parametrize("case", sorted(MIXED_CASES))
def test_mixed_step_share_and_in_flight_wait(case):
    """What a token gap is made of: tokens and seconds that came from
    mixed steps, and the wait for the dispatch in flight inside the queue
    wait. A loop dispatch takes 0.24 s, a mixed step 0.32 s."""
    r = _rec(max_new_tokens=64)
    t = r.t0 + 0.100
    r.note_seen(t)
    r.note_seen(t + 5.0)                    # one stamp, the first
    t += 0.040                              # refused once, admitted next
    r.note_admit(t, 0)
    t += 0.32
    r.note_decode(t, 1, mixed=True)         # the first token: TTFT only
    loop_tokens = mixed_tokens = 0
    stall = 0.0
    for ev in MIXED_CASES[case]:
        if ev[0] == "loop":
            t += 0.24
            r.note_decode(t, ev[1])
            loop_tokens += ev[1]
        elif ev[0] == "mixed":
            t += 0.32
            r.note_decode(t, 1, mixed=True)
            mixed_tokens += 1
            stall += 0.32
        else:
            t += 0.05
            r.note_preempt(t)
            t += 0.5                        # waits, then two chunk steps
            r.note_admit(t, 0)
            t += 0.64
            r.note_decode(t, 1, mixed=True)
            mixed_tokens += 1
            stall += 0.05 + 0.5 + 0.64
    d = r.to_dict()
    assert d["mixed_tokens"] == mixed_tokens
    assert d["mixed_tokens"] + loop_tokens == d["n_generated"] - 1
    assert d["mixed_stall"] == pytest.approx(stall)
    assert d["mixed_stall"] <= r.last_ts - r.first_ts + 1e-9
    assert d["wait_in_flight"] == pytest.approx(0.100)
    assert d["wait_in_flight"] <= d["queue_wait"] == pytest.approx(0.140)
    if d["n_generated"] < 2:
        assert d["mixed_stall_share"] is None
    else:
        assert d["mixed_stall_share"] == pytest.approx(
            stall / (r.last_ts - r.first_ts))
        assert 0.0 <= d["mixed_stall_share"] <= 1.0
    if case == "mixed-only":
        assert d["mixed_stall_share"] == pytest.approx(1.0)
    if case == "decode-only":
        assert d["mixed_stall_share"] == 0.0


# ---------------------------------------------------------------- recorder


def _finished(fr, rid, ttft=0.01, tpot=0.001, n=4):
    rec = fr.start(rid, 8, n)
    rec.note_admit(rec.t0 + 0.001, 0)
    t = rec.t0 + ttft
    rec.note_decode(t, 1)
    for _ in range(n - 1):
        t += tpot
        rec.note_decode(t, 1)
    fr.finish(rec, t + 0.001, "length")
    return rec


def test_ring_eviction_prefers_finished():
    fr = FlightRecorder(capacity=3, observe_metrics=False)
    live_a = fr.start("live-a", 4, 4)
    _finished(fr, "fin-b")
    live_c = fr.start("live-c", 4, 4)
    fr.start("live-d", 4, 4)        # over capacity: evicts fin-b first
    assert fr.get("fin-b") is None
    assert fr.get("live-a") is live_a and fr.get("live-c") is live_c
    fr.start("live-e", 4, 4)        # all live: oldest live goes
    assert fr.get("live-a") is None
    assert len(fr) == 3


def test_ring_eviction_over_capacity_bulk():
    fr = FlightRecorder(capacity=8, observe_metrics=False)
    for i in range(50):
        _finished(fr, f"r{i}")
    assert len(fr) == 8
    kept = {d["rid"] for d in fr.snapshot()}
    assert kept == {f"r{i}" for i in range(42, 50)}  # newest survive


def test_finish_idempotent_and_slo_attainment():
    fr = FlightRecorder(capacity=8, observe_metrics=False,
                        slo_ttft_s=0.02, slo_tpot_s=0.002)
    good = _finished(fr, "good", ttft=0.01, tpot=0.001)
    fr.finish(good, good.t0 + 99.0, "stop")  # second finish: no-op
    assert good.finish_reason == "length"
    assert fr.n_finished == 1
    _finished(fr, "slow-ttft", ttft=0.05, tpot=0.001)
    _finished(fr, "slow-tpot", ttft=0.01, tpot=0.01)
    ttft_ok, tpot_ok = fr.slo_attainment()
    assert ttft_ok == pytest.approx(2 / 3)
    assert tpot_ok == pytest.approx(2 / 3)
    # 1-token request: no inter-token latency -> cannot miss TPOT
    one = fr.start("one", 4, 1)
    one.note_decode(one.t0 + 0.01, 1)
    fr.finish(one, one.t0 + 0.011, "length")
    assert fr.slo_attainment()[1] == pytest.approx(3 / 4)


def test_slo_attainment_empty_is_perfect():
    fr = FlightRecorder(capacity=4, observe_metrics=False)
    assert fr.slo_attainment() == (1.0, 1.0)


def test_drain_export_finished_plus_live():
    fr = FlightRecorder(capacity=8, observe_metrics=False)
    _finished(fr, "done-1")
    live = fr.start("live-1", 4, 4)
    live.note_decode(live.t0 + 0.01, 1)
    out = fr.drain_export()
    by_rid = {d["rid"]: d for d in out}
    assert by_rid["done-1"]["done"] and by_rid["done-1"]["e2e"] > 0
    assert not by_rid["live-1"]["done"]
    # finished records drain ONCE; live snapshots re-ship every flush
    again = {d["rid"] for d in fr.drain_export()}
    assert again == {"live-1"}
    assert "live-1" in {d["rid"] for d in drain_all_exports()}


def test_finish_observes_serving_histograms():
    from ray_tpu.util import metrics as metrics_mod
    metrics_mod.clear_registry()
    try:
        fr = FlightRecorder(capacity=4)  # observe_metrics default on
        _finished(fr, "obs-1", ttft=0.01, tpot=0.001, n=4)
        snap = metrics_mod.snapshot()
        for name in ("llm_ttft_seconds", "llm_tpot_seconds",
                     "llm_e2e_seconds", "llm_queue_wait_seconds"):
            fam = snap[name]
            assert fam["type"] == "histogram", name
            (hist,) = fam["values"].values()
            assert hist["n"] == 1, name
        assert snap["llm_ttft_seconds"]["values"][()]["sum"] == \
            pytest.approx(0.01)
    finally:
        metrics_mod.clear_registry()


def test_request_log_imports_without_jax():
    """Tier-1 contract: the recorder (and constructing one, metrics
    included) must not pull the accelerator stack into the process."""
    code = ("import sys; import ray_tpu.llm.request_log as rl; "
            "rl.FlightRecorder(capacity=4); "
            "import ray_tpu.llm; ray_tpu.llm.FlightRecorder; "
            "print('jax' in sys.modules)")
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False", out.stdout


# ------------------------------------------------------- engine lifecycle


@pytest.fixture(scope="module")
def tiny_cfg():
    jnp = pytest.importorskip("jax.numpy")
    from ray_tpu.models.llama import LlamaConfig
    return LlamaConfig.tiny(n_layers=2, dtype=jnp.float32)


def _run(eng):
    done = {}
    while eng.has_work():
        done.update(eng.step())
    return done


def test_engine_records_full_lifecycle(tiny_cfg):
    from ray_tpu.llm import InferenceEngine
    eng = InferenceEngine(tiny_cfg, page_size=8, total_pages=64,
                          max_batch=4, max_seq_len=128, seed=7)
    rids = [eng.add_request([5 + i, 17, 42, 9, 100, 3, 77, i + 1],
                            max_new_tokens=12, trace_id=f"tid{i}")
            for i in range(3)]
    done = _run(eng)
    assert set(done) == set(rids)
    records = {d["rid"]: d for d in eng.request_log.snapshot()}
    for i, rid in enumerate(rids):
        d = records[rid]
        assert d["trace_id"] == f"tid{i}"
        assert d["done"] and d["finish_reason"] == "length"
        assert d["n_generated"] == 12
        assert d["prompt_tokens"] == 8 and d["max_new_tokens"] == 12
        assert len(d["admits"]) == 1 and d["queue_wait"] >= 0
        assert d["chunks"], "prefill chunks must be recorded"
        assert sum(c[1] for c in d["chunks"]) == 8
        assert 0 < d["ttft"] <= d["e2e"]
        assert d["tpot"] is not None and d["tpot"] >= 0
    # SLO gauges follow the recorder
    ttft_ok, tpot_ok = eng.request_log.slo_attainment()
    assert 0.0 <= ttft_ok <= 1.0 and 0.0 <= tpot_ok <= 1.0


def test_engine_finish_reason_stop_records(tiny_cfg):
    from ray_tpu.llm import InferenceEngine
    eng = InferenceEngine(tiny_cfg, page_size=8, total_pages=64,
                          max_batch=4, max_seq_len=128, seed=7)
    probe = eng.generate([5, 17, 42, 9], max_new_tokens=8)
    # eos = the first token NOT emitted earlier in the greedy stream, so
    # the engine stops exactly at its first occurrence
    k = next(i for i, t in enumerate(probe) if t not in probe[:i] and i)
    eng2 = InferenceEngine(tiny_cfg, page_size=8, total_pages=64,
                           max_batch=4, max_seq_len=128, seed=7,
                           eos_token=probe[k])
    rid = eng2.add_request([5, 17, 42, 9], max_new_tokens=12)
    done = _run(eng2)
    assert done[rid] == probe[:k]
    d = {r["rid"]: r for r in eng2.request_log.snapshot()}[rid]
    assert d["finish_reason"] == "stop" and d["done"]
    assert eng2.finish_reason(rid) == "stop"


def test_engine_preemption_recompute_parity_and_record(tiny_cfg):
    """Under a page pool too small for both sequences, the loser is
    recompute-preempted (pages dropped, re-queued, re-prefilled) and its
    record carries BOTH phases; greedy argmax makes the final tokens
    IDENTICAL to an uncontended run."""
    from ray_tpu.llm import InferenceEngine
    kw = dict(page_size=4, max_batch=4, max_seq_len=32, seed=7,
              prefix_cache=False, decode_chunk=4)
    p1, p2 = list(range(1, 9)), list(range(3, 11))

    ref = InferenceEngine(tiny_cfg, total_pages=64, **kw)
    q1 = ref.add_request(list(p1), max_new_tokens=16)
    q2 = ref.add_request(list(p2), max_new_tokens=16)
    ref_done = _run(ref)
    assert ref.stats["preemptions"] == 0

    eng = InferenceEngine(tiny_cfg, total_pages=10, **kw)
    r1 = eng.add_request(list(p1), max_new_tokens=16)
    r2 = eng.add_request(list(p2), max_new_tokens=16)
    done = _run(eng)

    assert eng.stats["preemptions"] >= 1
    assert done[r1] == ref_done[q1] and done[r2] == ref_done[q2]
    records = {d["rid"]: d for d in eng.request_log.snapshot()}
    preempted = [d for d in records.values() if d["preempts"] >= 1]
    assert preempted, records
    for d in preempted:
        # both phases in one record: re-admit after the preempt
        assert len(d["admits"]) == d["preempts"] + 1
        assert d["preempt_ts"] and d["stalls"] >= d["preempts"]
        assert d["finish_reason"] == "length" and d["n_generated"] == 16
    assert eng.request_log.n_preempts >= 1


def test_engine_unsatisfiable_working_set_finishes_evict(tiny_cfg):
    """A sequence whose grown working set can never fit the pool stops
    with reason "evict" instead of ping-ponging forever."""
    from ray_tpu.llm import InferenceEngine
    eng = InferenceEngine(tiny_cfg, page_size=4, total_pages=4,
                          max_batch=2, max_seq_len=32, seed=7,
                          prefix_cache=False, decode_chunk=2)
    rid = eng.add_request([1, 2, 3, 4], max_new_tokens=24)
    done = _run(eng)
    assert rid in done
    assert eng.finish_reason(rid) == "evict"
    d = {r["rid"]: r for r in eng.request_log.snapshot()}[rid]
    assert d["finish_reason"] == "evict" and d["done"]
    assert 0 < d["n_generated"] < 24
    # the caller still gets every token generated before eviction
    assert len(done[rid]) == d["n_generated"]


def test_engine_recorder_disable_flag(tiny_cfg):
    from ray_tpu.llm import InferenceEngine
    eng = InferenceEngine(tiny_cfg, page_size=8, total_pages=64,
                          max_batch=2, max_seq_len=64, seed=7,
                          request_log=False)
    assert eng.request_log is None
    assert eng.generate([5, 17, 42], max_new_tokens=4)
