"""XLA compile/dispatch observability plane (util/compile_tracker.py).

Units: jax-free import contract, shape/dtype signatures + recompile
diffs, the jit cache-miss wrap seam (probed and probeless paths, plus
in-flight attribution of anonymous jax.monitoring phase durations),
ring overflow with EXACT drop accounting (emitted == exported + stored
+ dropped across any export sequence), once-per-excursion compile-storm
journaling with re-arm, the head-side CompileStore (cursor, filters,
per-callable aggregation, LRU), and the multi-plane Perfetto export.

E2E: a two-node cluster where a shape-unstable jitted function run on
both nodes lands per-process compile records — recompiles carrying
their signature diff — at the head's CompileStore, increments
xla_recompiles_total, raises one compile_storm journal event per
process excursion, and exports a `trace --perfetto` file whose compile
+ span + train lanes share one clock.

Reference signal: TorchTitan and the Podracer report both treat silent
recompile storms as the dominant unexplained-latency failure on TPU
pods — this plane makes them cluster events instead.
"""

import json
import subprocess
import sys
import time

import numpy as np
import pytest

from ray_tpu.util import compile_tracker as ct

MiB = 1 << 20


# ----------------------------------------------------------------- lints

def test_compile_tracker_imports_without_jax():
    """Tier-1 contract: the tracker lives in the head and node daemons
    too (the head hosts the CompileStore), which must never pull in the
    accelerator stack. jax hookup is lazy and sys.modules-gated."""
    code = (
        "import sys; from ray_tpu.util import compile_tracker as ct; "
        "t = ct.CompileTracker(role='t'); "
        "t.note_compile('f', ['f32[8]']); "
        "e = t.export(); assert e and e['emitted'] == 1, e; "
        "s = ct.CompileStore(); s.ingest('w', e, role='worker'); "
        "assert s.dump()['records'], 'store empty'; "
        "tr = ct.ensure_started(role='t'); "
        "assert tr is not None and ct.drain_export() is None; "
        "print('jax' in sys.modules)")
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False", out.stdout


def test_ensure_started_respects_disable():
    from ray_tpu.core.config import GlobalConfig
    ct.stop_global()
    old = GlobalConfig.compile_tracker_enabled
    try:
        GlobalConfig.apply({"compile_tracker_enabled": False})
        assert ct.ensure_started(role="t") is None
        assert ct.get_global() is None
        assert ct.drain_export() is None
        assert ct.drain_journal_events() == []
    finally:
        GlobalConfig.apply({"compile_tracker_enabled": old})
        ct.stop_global()


# ----------------------------------------------------------------- units

def test_signature_of_jax_style():
    """Arrays render as the jit cache key's abstract part
    (dtype[shape]); scalars as weak type names; kwargs sorted; long
    arglists fold their tail so records stay bounded."""
    sig = ct.signature_of(
        (np.zeros((8, 16), np.float32), np.zeros((4,), np.int32),
         True, 3, 0.5, None, (np.zeros((2,), np.float16), 1)),
        {"b": np.zeros((1,), np.uint8), "a": 2})
    assert sig == ["f32[8,16]", "i32[4]", "bool", "int", "float",
                   "None", "(f16[2],int)", "a=int", "b=u8[1]"]
    folded = ct.signature_of([1] * 70)
    assert folded[-1] == "+6 more" and len(folded) == 65


def test_signature_diff_and_fingerprint():
    old = ["f32[8,16]", "i32[4]"]
    new = ["f32[9,16]", "i32[4]"]
    assert ct.signature_diff(old, new) == \
        ["arg[0]: f32[8,16] -> f32[9,16]"]
    assert ct.signature_diff(None, new) == []
    assert ct.signature_diff(["f32[8]"], ["f32[8]", "i32[4]"]) == \
        ["arity: 1 -> 2 args"]
    # diff list is capped
    d = ct.signature_diff([f"f32[{i}]" for i in range(20)],
                          [f"f32[{i + 1}]" for i in range(20)])
    assert d[-1] == "..." and len(d) == 9
    fp = ct.fingerprint("f", old)
    assert len(fp) == 12 and fp == ct.fingerprint("f", old)
    assert fp != ct.fingerprint("f", new)
    assert fp != ct.fingerprint("g", old)


def test_recompile_detection_synthetic_signatures():
    """Same callable + new signature == recompile, and the record
    carries the exact arg-level diff that caused it (the acceptance
    invariant for `compiles --recompiles`)."""
    tr = ct.CompileTracker(role="w", storm_threshold=0)
    r1 = tr.note_compile("model.step", ["f32[8,128]", "i32[8]"],
                         wall_s=1.0)
    assert not r1["recompile"] and r1["diff"] == [] and r1["nth"] == 1
    r2 = tr.note_compile("model.step", ["f32[9,128]", "i32[8]"],
                         wall_s=0.5)
    assert r2["recompile"] and r2["nth"] == 2
    assert r2["diff"] == ["arg[0]: f32[8,128] -> f32[9,128]"]
    assert r2["fingerprint"] != r1["fingerprint"]
    # identical signature again: cache hit territory, not a recompile
    r3 = tr.note_compile("model.step", ["f32[9,128]", "i32[8]"])
    assert not r3["recompile"] and r3["nth"] == 3
    # a different callable never cross-contaminates
    r4 = tr.note_compile("model.eval", ["f32[9,128]", "i32[8]"])
    assert not r4["recompile"]

    st = tr.callable_stats("model.step")
    assert st["compiles"] == 3 and st["recompiles"] == 1
    assert st["last_diff"] == r2["diff"]
    assert tr.callable_stats("missing") is None
    lr = tr.last_recompile()
    assert lr["name"] == "model.step" and lr["diff"] == r2["diff"]
    assert tr.last_recompile("model.") is not None
    assert tr.last_recompile("llm.") is None
    counts = tr.stats()["counts"]
    assert counts["jit"] == 4 and counts["recompile"] == 1


def test_ring_overflow_exact_drop_accounting():
    """The acceptance invariant: across any sequence of exports,
    emitted == exported + stored + dropped, to the record."""
    tr = ct.CompileTracker(ring_records=4, storm_threshold=0)
    for i in range(10):
        tr.note_compile("f", [f"f32[{i}]"])
    e = tr.export()
    assert e["emitted"] == 10 and e["dropped"] == 6
    assert len(e["records"]) == 4
    # ring keeps the NEWEST records
    assert e["records"][-1]["signature"] == ["f32[9]"]
    # drained: an immediate re-export is empty
    assert tr.export() is None
    # multi-window: the ledger invariant holds across windows too
    tot_emitted, tot_exported, tot_dropped = 10, 4, 6
    for n in (3, 7, 1):
        for i in range(n):
            tr.note_compile("g", [f"f32[{i},{n}]"])
        e = tr.export()
        tot_emitted += e["emitted"]
        tot_exported += len(e["records"])
        tot_dropped += e["dropped"]
    st = tr.stats()
    assert st["emitted"] == tot_emitted == 21
    assert st["dropped"] == tot_dropped
    assert st["emitted"] == st["exported"] + st["stored"] + st["dropped"]
    assert st["exported"] == tot_exported and st["stored"] == 0


def test_wrap_probed_cache_growth_path():
    """The jit cache-miss seam with a `_cache_size`-style probe: a call
    records a compile iff the cache grew across THAT call — signatures
    are only computed on actual misses."""
    tr = ct.CompileTracker(storm_threshold=0)
    cache = set()

    def fake_jit(x):
        cache.add((x.shape, str(x.dtype)))
        return x

    wrapped = tr.wrap(fake_jit, name="t.fn", probe=lambda: len(cache))
    wrapped(np.zeros((4,), np.float32))
    wrapped(np.zeros((4,), np.float32))      # cache hit: no record
    wrapped(np.zeros((5,), np.float32))      # miss: recompile
    st = tr.callable_stats("t.fn")
    assert st["compiles"] == 2 and st["recompiles"] == 1
    assert st["last_diff"] == ["arg[0]: f32[4] -> f32[5]"]
    assert tr.stats()["emitted"] == 2


def test_wrap_probeless_signature_novelty_path():
    """Without a probe the seam falls back to signature novelty — a
    repeated signature is a cache hit, a new one a compile."""
    tr = ct.CompileTracker(storm_threshold=0)
    wrapped = tr.wrap(lambda *a, **k: None, name="t.nov")
    wrapped(np.zeros((4,), np.float32))
    wrapped(np.zeros((4,), np.float32))
    wrapped(np.zeros((5,), np.float32), flag=True)
    st = tr.callable_stats("t.nov")
    assert st["compiles"] == 2 and st["recompiles"] == 1
    assert st["last_sig"] == ["f32[5]", "flag=bool"]


def _fire(event: str, duration: float, now: float = None) -> None:
    """jax.monitoring reporting one phase as it ends: a span of
    ``duration`` seconds that ends now (by the test's clock, where it has
    one), handed to the tracker's listener."""
    now = time.time() if now is None else now
    ct._on_jax_span(event, now - duration, now)


def test_wrap_attributes_inflight_monitoring_durations():
    """The thread-local attribution stack: /jax/core/compile/* phase
    durations reported DURING a wrapped call are folded into that
    call's record (measured_s/backend_s), and a backend_compile seen in
    flight marks the call compiled even when the probe saw no growth
    (exactly what jax's C++ dispatch cache does to a Python probe)."""
    tr = ct.CompileTracker(role="w", storm_threshold=0)
    ct.stop_global()

    def fn(x):
        # simulate jax.monitoring firing while the call is in flight, each
        # phase as it ends (an event that began before the last one ended
        # would HOLD it: test_callable_stats_keep_the_split_and_the_hits)
        time.sleep(0.05)
        _fire("/jax/core/compile/jaxpr_trace_duration", 0.05)
        time.sleep(0.125)
        _fire("/jax/core/compile/backend_compile_duration", 0.125)
        _fire("/jax/unrelated/event", 99.0)  # ignored
        return x

    wrapped = tr.wrap(fn, name="t.attr", probe=lambda: 0)  # no growth
    wrapped(np.zeros((2, 2), np.float32))
    e = tr.export()
    assert len(e["records"]) == 1
    rec = e["records"][0]
    assert rec["name"] == "t.attr"
    assert rec["backend_s"] == 0.125
    assert rec["measured_s"] == pytest.approx(0.175)
    assert rec["duration_s"] > 0


class _SteppedClock:
    """The ``time`` the tracker's module reads, moved by the test alone:
    ``sleep`` steps it and returns at once, so which event HOLDS which, and
    a call's wall seconds, are what the test wrote and not how long a
    loaded machine overslept."""

    def __init__(self):
        self.now = 1000.0

    def sleep(self, seconds):
        self.now += seconds

    def time(self):
        return self.now

    perf_counter = time

    def __getattr__(self, name):
        return getattr(time, name)


def test_callable_stats_keep_the_split_and_the_hits(monkeypatch):
    """What the listener attributes to a wrapped call is kept by phase
    (trace_s, lower_s, backend_s), per callable and in the ring record,
    with how many compiles were persistent-cache hits and how many cold;
    a trace event that HOLDS earlier ones (jax times a nested jit's trace
    on its own and inside the trace that met it) counts them once; and a
    wrapped call's compile is this callable's alone: no nameless record,
    no backend_compile count beside it."""
    clock = _SteppedClock()
    monkeypatch.setattr(ct, "time", clock)
    tr = ct.CompileTracker(role="w", storm_threshold=0)
    ct.stop_global()
    pre = "/jax/core/compile/"

    def fire(event, duration):
        _fire(event, duration, clock.now)

    def fn(x, hit):
        clock.sleep(0.05)
        fire(pre + "jaxpr_trace_duration", 0.01)       # a nested jit ...
        clock.sleep(0.02)
        fire(pre + "jaxpr_trace_duration", 0.015)      # ... and a second
        fire(pre + "jaxpr_trace_duration", 0.05)       # the trace holding both
        clock.sleep(0.02)
        fire(pre + "jaxpr_to_mlir_module_duration", 0.02)
        if hit:
            ct._on_jax_event("/jax/compilation_cache/cache_hits")
        clock.sleep(0.03)
        fire(pre + "backend_compile_duration", 0.03)
        return x

    # a probe that never grows: the listener's word that the call compiled
    wrapped = tr.wrap(fn, name="t.split", probe=lambda: 0)
    for hit in (False, True):
        wrapped(np.zeros((2,), np.float32), hit)
    st = tr.callable_stats("t.split")
    assert st["compiles"] == 2 and st["recompiles"] == 0
    assert (st["cache_hits"], st["cold"]) == (1, 1)
    assert st["trace_s"] == pytest.approx(0.10)        # 2 x 0.05, not 0.15
    assert st["lower_s"] == pytest.approx(0.04)
    assert st["backend_s"] == pytest.approx(0.06)
    assert st["measured_s"] == pytest.approx(0.20)
    assert st["trace_s"] + st["lower_s"] + st["backend_s"] <= st["wall_s"]
    e = tr.export()
    cold, warm = e["records"]                          # and nothing nameless
    assert (cold["cache_hit"], warm["cache_hit"]) == (False, True)
    for rec in (cold, warm):
        assert rec["name"] == "t.split"
        assert (rec["trace_s"], rec["lower_s"], rec["backend_s"]) == \
            (0.05, 0.02, 0.03)
        assert rec["measured_s"] == pytest.approx(0.10)
    assert e["counts"] == {"jit": 2}, e["counts"]
    # the head's per-callable view adds them up
    store = ct.CompileStore()
    store.ingest("w", e, role="worker", worker="w1")
    agg = store.dump(by_callable=True)["by_callable"]["t.split"]
    assert agg["compiles"] == 2 and agg["cache_hits"] == 1
    assert agg["trace_lower_s"] == pytest.approx(0.14)
    assert agg["backend_s"] == pytest.approx(0.06)
    from ray_tpu.scripts.cli import _fmt_compile_record
    line = _fmt_compile_record(store.dump()["records"][1])
    assert "= trace 50.0 lower 20.0 backend 30.0 hit" in line, line


def test_train_step_builders_go_through_the_seam():
    """make_train_step's two jits are train.init and train.step to the
    tracker: a compile each, their seconds by phase, and a second
    signature is a recompile with its diff; the jit's own surface (lower,
    trace, eval_shape, clear_cache, _cache_size) stays in reach through
    the wrapper. Nothing is ringed nameless for them."""
    import jax.numpy as jnp
    import optax
    from ray_tpu.train.train_step import make_train_step
    ct.stop_global()
    try:
        tr = ct.ensure_started(role="t")
        init_fn, step_fn = make_train_step(
            lambda p, b: jnp.sum((b @ p) ** 2), optax.sgd(0.1),
            donate=False)
        assert init_fn.__rtpu_compile_wrapped__ is not None
        params = jnp.ones((4, 3))
        opt_state = init_fn(params)
        text = step_fn.lower(params, opt_state, jnp.ones((2, 4))).as_text()
        assert "module @jit_step_fn" in text
        batch = jnp.ones((2, 4))
        assert step_fn.trace(params, opt_state, batch).jaxpr is not None
        shapes = step_fn.eval_shape(params, opt_state, batch)
        assert shapes[0].shape == (4, 3) and shapes[2]["loss"].shape == ()
        assert callable(step_fn.clear_cache)
        for rows in (2, 2, 8):
            params, opt_state, m = step_fn(params, opt_state,
                                           jnp.ones((rows, 4)))
        assert np.isfinite(float(m["loss"]))
        assert step_fn._cache_size() == 2
        init, step = (tr.callable_stats(n)
                      for n in ("train.init", "train.step"))
        assert init["compiles"] == 1 and init["recompiles"] == 0
        assert step["compiles"] == 2 and step["recompiles"] == 1
        assert step["last_diff"] == ["arg[2]: f32[2,4] -> f32[8,4]"]
        assert step["cold"] == 2 and step["cache_hits"] == 0
        assert step["trace_s"] > 0 and step["lower_s"] > 0 \
            and step["backend_s"] > 0
        assert step["trace_s"] + step["lower_s"] + step["backend_s"] \
            <= step["wall_s"]
        named = [r for r in tr.export()["records"] if r["name"]]
        assert [r["name"] for r in named] == ["train.init", "train.step",
                                              "train.step"]
        assert named[-1]["recompile"] and named[-1]["trace_s"] > 0
        # with the tracker off the bare jits come back
        from ray_tpu.core.config import GlobalConfig
        ct.stop_global()
        GlobalConfig.apply({"compile_tracker_enabled": False})
        try:
            bare_init, bare_step = make_train_step(
                lambda p, b: jnp.sum(p), optax.sgd(0.1))
            assert not hasattr(bare_step, "__rtpu_compile_wrapped__")
        finally:
            GlobalConfig.apply({"compile_tracker_enabled": True})
    finally:
        ct.stop_global()


def test_train_step_record_says_what_the_remat_boundary_keeps():
    """The names a "full" remat boundary keeps, their bytes across the
    scan and the budget they were held to (models/llama.py:
    full_remat_keeps) ride on the record of the train.step compile that
    traced them, and `ray_tpu compiles` prints them; a compile that traced
    no such boundary carries nothing."""
    import functools
    import jax
    import jax.numpy as jnp
    import optax
    from ray_tpu.models import llama
    from ray_tpu.scripts.cli import _fmt_compile_record
    from ray_tpu.train.train_step import make_train_step
    cfg = llama.LlamaConfig.tiny(n_layers=2)
    B, L, item = 2, 16, jnp.dtype(cfg.dtype).itemsize
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.zeros((B, L), jnp.int32)
    ct.stop_global()
    try:
        tr = ct.ensure_started(role="t")
        init_fn, step_fn = make_train_step(
            functools.partial(llama.loss_fn, cfg=cfg), optax.sgd(0.1),
            donate=False)
        step_fn(params, init_fn(params), tokens)
        init, step = [r for r in tr.export()["records"] if r["name"]]
        assert (init["name"], step["name"]) == ("train.init", "train.step")
        assert "traced" not in init
        assert step["traced"] == {"remat_kept": {
            "names": ("mlp_up",),
            "bytes": cfg.n_layers * B * L * cfg.ffn_dim * item,
            "budget_bytes": sum(w.size for w in jax.tree.leaves(
                params["layers"])) * item}}
        assert '\n           remat_kept {"names": ["mlp_up"], "bytes": ' \
            in _fmt_compile_record(step)
        assert "remat_kept" not in _fmt_compile_record(init)
    finally:
        ct.stop_global()


def test_program_loaded_from_persistent_cache_stays_truthful(tmp_path,
                                                             monkeypatch):
    """A program LOADED from jax's persistent compilation cache instead
    of compiled: the jit's own cache still grows (the engine's
    compiled-program invariant counts resident executables, however they
    got here), the wrap seam still records it as this callable's
    compile — flagged cache_hit, its "backend" seconds being the
    retrieval — and the hit/miss counts tell a cold process from a warm
    one. Real jax, real cache, on the CPU backend."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc
    saved = {k: getattr(jax.config, k) for k in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    ct.stop_global()
    # a telemetry thread an earlier test of this process left behind drains
    # the global tracker's ring on its own clock (cluster_backend's flush):
    # between the two compiles it would take the first record with it
    monkeypatch.setattr(ct, "drain_export", lambda: None)
    try:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        cc.reset_cache()
        tr = ct.ensure_started(role="t")
        assert tr is not None

        def body(x):
            return jnp.tanh(x @ x).sum() * 3.25

        x = jnp.ones((32, 32), jnp.float32)
        cold_jit = jax.jit(body)
        tr.wrap(cold_jit, name="t.cached")(x)
        jax.clear_caches()            # a "new process": memory cache gone
        warm_jit = jax.jit(body)
        assert warm_jit._cache_size() == 0
        tr.wrap(warm_jit, name="t.cached")(x)
        assert warm_jit._cache_size() == 1      # resident, though loaded

        stats = tr.callable_stats("t.cached")
        assert stats["compiles"] == 2 and stats["recompiles"] == 0
        cold, warm = [r for r in tr.export()["records"]
                      if r["name"] == "t.cached"]
        assert (cold["cache_hit"], warm["cache_hit"]) == (False, True)
        counts = tr.stats()["counts"]
        assert counts["cache_miss"] >= 1 and counts["cache_hit"] >= 1
        assert any(n.endswith("-cache") for n in
                   __import__("os").listdir(tmp_path))
    finally:
        ct.stop_global()
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()


def test_unattributed_backend_compile_still_ringed():
    """An un-wrapped jit's backend compile (no call in flight) must not
    vanish: it lands as a nameless record so `compiles` shows it."""
    tr = ct.CompileTracker(storm_threshold=0)
    tr.note_monitor_duration("jaxpr_trace", 0.01)       # counted only
    tr.note_monitor_duration("backend_compile", 0.25)   # ringed
    tr.note_cache_miss()
    e = tr.export()
    assert len(e["records"]) == 1
    assert e["records"][0]["name"] == ""
    assert e["records"][0]["kind"] == "backend_compile"
    assert e["counts"]["jaxpr_trace"] == 1
    assert e["counts"]["backend_compile"] == 1
    assert e["counts"]["cache_miss"] == 1


def test_storm_once_per_excursion_and_rearm():
    """A recompile burst crossing the threshold journals EXACTLY ONE
    compile_storm; the detector re-arms only after the rate falls below
    half the threshold, so a sustained storm cannot spam the journal
    but a second excursion fires again."""
    tr = ct.CompileTracker(role="w", node="n1", worker="w1",
                           storm_threshold=5, storm_window_s=0.2)
    for i in range(8):                       # 7 recompiles in << 0.2s
        tr.note_compile("f", [f"f32[{i},4]"])
    evs = tr.drain_journal_events()
    assert len(evs) == 1, evs
    ev = evs[0]
    assert ev["type"] == "compile_storm" and ev["callable"] == "f"
    assert ev["recompiles"] >= 5 and ev["threshold"] == 5
    assert ev["diff"] and ev["worker"] == "w1"
    assert tr.stats()["storm_active"]
    # still inside the same excursion: more recompiles, no new event
    tr.note_compile("f", ["f32[99,4]"])
    assert tr.drain_journal_events() == []
    time.sleep(0.3)                          # window drains -> re-arm
    for i in range(8):
        tr.note_compile("f", [f"f32[{100 + i},4]"])
    evs = tr.drain_journal_events()
    assert len(evs) == 1 and evs[0]["type"] == "compile_storm"


def test_storm_disabled_at_zero_threshold():
    tr = ct.CompileTracker(storm_threshold=0, storm_window_s=0.2)
    for i in range(50):
        tr.note_compile("f", [f"f32[{i}]"])
    assert tr.drain_journal_events() == []
    assert not tr.stats()["storm_active"]


def test_stage_journal_event_stamps_identity():
    """Arbitrary staged events (the engine's invariant breach) carry
    the process identity without caller plumbing, and staging is
    bounded."""
    from ray_tpu.llm.model import chunk_row_shapes
    # the seam's budget at the default two chunk rows: decode loop, page
    # copy, the mixed step in each of its shapes {1, 2}
    budget = 2 + len(chunk_row_shapes(2))
    tr = ct.CompileTracker(role="worker", node="n1", worker="w1")
    tr.stage_journal_event("llm_compile_invariant_breach",
                           programs=budget + 1, budget=budget)
    evs = tr.drain_journal_events()
    assert len(evs) == 1
    ev = evs[0]
    assert ev["type"] == "llm_compile_invariant_breach"
    assert ev["role"] == "worker" and ev["worker"] == "w1"
    assert ev["programs"] == 5 and ev["budget"] == 4
    for i in range(200):
        tr.stage_journal_event("e", i=i)
    assert len(tr.drain_journal_events()) == ct._MAX_JOURNAL


# ----------------------------------------------------------------- store

def _export_with(names_sigs, **kw):
    tr = ct.CompileTracker(storm_threshold=0, **kw)
    for name, sig in names_sigs:
        tr.note_compile(name, sig)
    return tr.export()


def test_compile_store_cursor_and_filters():
    s = ct.CompileStore()
    s.ingest("w1", _export_with([("llm.step", ["f32[8]"]),
                                 ("llm.step", ["f32[9]"])]),
             role="worker", node="nodeA", worker="w1")
    s.ingest("w2", _export_with([("train.full_step", ["f32[16,64]"])]),
             role="worker", node="nodeB", worker="w2")
    d = s.dump()
    assert len(d["records"]) == 3 and d["procs"] == 2
    seqs = [r["seq"] for r in d["records"]]
    assert seqs == sorted(seqs)
    # records are identity-stamped at ingest
    assert {r["worker"] for r in d["records"]} == {"w1", "w2"}
    # cursor: only records after last_seq on the next poll
    cur = d["last_seq"]
    assert s.dump(after_seq=cur)["records"] == []
    s.ingest("w1", _export_with([("llm.step", ["f32[10]"])]),
             role="worker", node="nodeA", worker="w1")
    follow = s.dump(after_seq=cur)["records"]
    assert len(follow) == 1 and follow[0]["signature"] == ["f32[10]"]
    # substring filters
    assert {r["worker"] for r in s.dump(worker="w2")["records"]} == \
        {"w2"}
    assert all("llm" in r["name"]
               for r in s.dump(callable="llm")["records"])
    assert {r["worker"] for r in s.dump(node="nodeB")["records"]} == \
        {"w2"}
    ron = s.dump(recompiles_only=True)["records"]
    assert len(ron) == 1 and ron[0]["diff"] == \
        ["arg[0]: f32[8] -> f32[9]"]
    # newest-N limit keeps the tail, follow-loop safe
    lim = s.dump(limit=2)["records"]
    assert len(lim) == 2 and lim[-1]["seq"] == s.dump()["last_seq"]


def test_compile_store_by_callable_aggregation():
    s = ct.CompileStore()
    s.ingest("w1", _export_with([("llm.step", ["f32[8]"]),
                                 ("llm.step", ["f32[9]"])]),
             role="worker", worker="w1")
    s.ingest("w2", _export_with([("llm.step", ["f32[8]"])]),
             role="worker", worker="w2")
    agg = s.dump(by_callable=True)["by_callable"]
    a = agg["llm.step"]
    assert a["compiles"] == 3 and a["recompiles"] == 1
    assert a["procs"] == 2
    assert a["last_diff"] == ["arg[0]: f32[8] -> f32[9]"]


def test_compile_store_lru_eviction_counts_drops():
    s = ct.CompileStore(max_procs=2)
    for i in range(3):
        s.ingest(f"w{i}", _export_with([(f"f{i}", ["f32[4]"])]),
                 worker=f"w{i}")
    d = s.dump()
    assert d["procs"] == 2
    # the evicted process's records joined the drop ledger exactly
    assert d["dropped_total"] == 1
    assert {r["worker"] for r in d["records"]} == {"w1", "w2"}
    # process-side ring drops are folded into the same ledger
    s.ingest("w9", _export_with([(f"g{i}", [f"f32[{i}]"])
                                 for i in range(10)], ring_records=4),
             worker="w9")
    assert s.dump()["dropped_total"] == 1 + 6 + 1  # +1: w1 evicted


# -------------------------------------------------------------- perfetto

def test_to_perfetto_multi_plane_schema():
    """The unified timeline: every plane lands in its own named lane
    (ph:'M' process_name metadata), span/compile events are ph:'X' on
    one microsecond wall clock, and the whole object round-trips JSON
    (what ui.perfetto.dev requires)."""
    from ray_tpu.runtime.events import to_perfetto

    now = 1000.0
    events = [
        {"name": "task_a", "kind": "task", "task_id": "t1",
         "start": now, "end": now + 0.5, "ok": True,
         "node": "nodeA", "worker": "w1", "trace_id": "abc"},
        {"name": "step", "kind": "train_step", "task_id": "tsp",
         "start": now, "end": now + 0.3, "ok": True},
        {"name": "forward", "kind": "train_phase", "task_id": "tsp",
         "start": now, "end": now + 0.1, "ok": True},
        {"name": "__dropped__", "kind": "meta", "start": 0, "end": 0},
    ]
    compiles = [
        {"ts": now + 2.0, "name": "llm.step", "duration_s": 1.5,
         "measured_s": 1.2, "worker": "w1", "recompile": True,
         "diff": ["arg[0]: f32[8] -> f32[9]"],
         "signature": ["f32[9]"], "fingerprint": "beef", "kind": "jit"},
        {"ts": now + 3.0, "name": "", "duration_s": 0.2, "pid": 77,
         "recompile": False, "signature": [], "kind": "backend_compile"},
    ]
    requests = [
        {"rid": "req-1", "t0_wall": now, "e2e": 0.8, "ttft": 0.2,
         "admits": [[0.05, 0]], "prompt_tokens": 16, "n_generated": 8,
         "finish_reason": "stop", "trace_id": "abc", "worker": "w1"},
        {"rid": "req-skipped"},  # no t0_wall: skipped, not crashed
    ]
    journal = [
        {"ts": now + 1.0, "type": "compile_storm", "seq": 1,
         "callable": "llm.step", "recompiles": 9,
         "diff": ["arg[0]: f32[8] -> f32[9]"]},
        {"type": "no_ts_skipped"},
    ]
    trace = to_perfetto(events, compiles=compiles, requests=requests,
                        journal=journal)
    json.loads(json.dumps(trace))  # ui.perfetto.dev ingests pure JSON
    evs = trace["traceEvents"]
    lanes = {e["args"]["name"] for e in evs
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    assert lanes == {"spans: node nodeA", "train: steps + phases",
                     "llm: requests", "xla: compiles",
                     "journal: cluster events"}
    # distinct pids per lane: Perfetto renders them as separate tracks
    assert len({e["pid"] for e in evs}) >= 5
    assert not any(e.get("name") == "__dropped__" for e in evs)
    for e in evs:
        if e.get("ph") == "X":
            assert e["dur"] >= 0 and "ts" in e and "pid" in e

    rec = next(e for e in evs if e.get("name") == "RECOMPILE llm.step")
    assert rec["args"]["diff"] == ["arg[0]: f32[8] -> f32[9]"]
    assert rec["ts"] == pytest.approx((now + 2.0 - 1.5) * 1e6)
    assert rec["dur"] == pytest.approx(1.5 * 1e6)
    assert any(e.get("name") == "<unattributed>" for e in evs)
    assert any(e.get("name") == "first_token" and e.get("ph") == "i"
               for e in evs)
    assert any(e.get("name") == "queue_wait" for e in evs)
    storm = next(e for e in evs if e.get("name") == "compile_storm")
    assert storm["ph"] == "i" and storm["s"] == "g"
    assert storm["args"]["callable"] == "llm.step"
    assert sum(1 for e in evs if e.get("cat") == "journal") == 1


# ------------------------------------------------------------------- e2e

@pytest.fixture(scope="module")
def two_node_compiled():
    import ray_tpu as rt
    rt.init(num_cpus=1, resources={"n1": 1.0}, _system_config={
        "object_store_memory_bytes": 64 * MiB,
        "metrics_export_period_s": 0.2,
        "compile_storm_threshold": 5,   # 8 shapes -> 7 recompiles: fires
        "compile_storm_window_s": 30.0,
    })
    from ray_tpu.core.worker import global_worker
    from ray_tpu.runtime.cluster_backend import start_node
    backend = global_worker.backend
    session = backend.head.call("connect_driver", {})["session"]
    proc = start_node(backend.head_addr, session,
                      resources={"CPU": 1.0, "n2": 1.0})
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(f"second node exited rc={proc.returncode}")
        nodes = backend.head.call("list_nodes")
        if sum(1 for n in nodes if n["alive"]) >= 2:
            break
        time.sleep(0.2)
    else:
        raise RuntimeError("second node never registered")
    yield rt, backend, session
    proc.terminate()
    try:
        proc.wait(timeout=10)
    finally:
        rt.shutdown()


def _compiles_until(head, payload, pred, timeout=90):
    deadline = time.monotonic() + timeout
    d = {"records": []}
    while time.monotonic() < deadline:
        d = head.call("compiles_dump", dict(payload), timeout=10)
        if pred(d):
            return d
        time.sleep(0.3)
    return d


def _metric_sum(head, name):
    snap = head.call("metrics_dump", {}, timeout=10) or {}
    entry = snap.get(name) or {}
    total = 0.0
    for v in (entry.get("values") or {}).values():
        if isinstance(v, (int, float)):
            total += v
        elif isinstance(v, dict):
            total += sum(x for x in v.values()
                         if isinstance(x, (int, float)))
    return total


def test_shape_unstable_fn_lands_records_at_head(two_node_compiled):
    """The acceptance scenario: a shape-unstable jitted function run on
    BOTH nodes produces per-process compile records with signature
    diffs at the head, xla_recompiles_total increments, and each
    process's excursion raises exactly one compile_storm."""
    rt_, backend, _session = two_node_compiled
    head = backend.head

    @rt_.remote(num_cpus=1)
    def unstable(tag):
        import jax
        import jax.numpy as jnp
        from ray_tpu.util import compile_tracker
        tr = compile_tracker.get_global()
        assert tr is not None, "worker bootstrap did not start tracker"
        f = tr.wrap(jax.jit(lambda x: x * 2 + 1),
                    name=f"e2e.unstable_{tag}")
        for i in range(8):   # 8 shapes: 7 recompiles > threshold 5
            f(jnp.zeros((i + 1,), jnp.float32))
        return tr.stats()["counts"]

    # one task pinned to each node (n1/n2 custom resources), so the
    # records provably come from two distinct processes on two nodes
    futs = [unstable.options(resources={"n2": 1.0}).remote("b"),
            unstable.options(resources={"n1": 1.0}).remote("a")]
    counts_b, counts_a = rt_.get(futs, timeout=300)
    for c in (counts_a, counts_b):
        assert c.get("jit", 0) >= 8 and c.get("recompile", 0) >= 7, c

    # wait until BOTH processes' full windows landed (records stream
    # across several telemetry flushes)
    def _complete(d):
        agg = d.get("by_callable") or {}
        return {"e2e.unstable_a", "e2e.unstable_b"} <= set(agg) \
            and all(a["compiles"] >= 8 for a in agg.values())

    d = _compiles_until(
        head, {"callable": "e2e.unstable", "by_callable": True},
        _complete)
    workers = {r["worker"] for r in d["records"]}
    assert len(workers) >= 2, (workers, len(d["records"]))
    recompiles = [r for r in d["records"] if r["recompile"]]
    assert recompiles, d["records"][:3]
    for r in recompiles:
        assert r["diff"] and "->" in r["diff"][0], r
        assert r["signature"] and r["role"] == "worker", r
    # per-callable aggregation attributes recompiles to both tasks
    agg = d["by_callable"]
    assert {"e2e.unstable_a", "e2e.unstable_b"} <= set(agg), agg
    assert all(a["recompiles"] >= 7 for a in agg.values()), agg

    # the metric plane saw the recompiles too
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        if _metric_sum(head, "xla_recompiles_total") >= 14:
            break
        time.sleep(0.3)
    assert _metric_sum(head, "xla_recompiles_total") >= 14
    assert _metric_sum(head, "xla_compiles_total") > 0

    # exactly one compile_storm per process excursion (two processes;
    # one if the scheduler reused a single worker for both tasks)
    deadline = time.monotonic() + 60
    storms = []
    while time.monotonic() < deadline:
        storms = head.call("events_dump", {"type": "compile_storm"},
                           timeout=10)
        if len(storms) >= len(workers):
            break
        time.sleep(0.3)
    assert 1 <= len(storms) <= len(workers), storms
    for s in storms:
        assert s["callable"].startswith("e2e.unstable"), s
        assert s["recompiles"] >= 5 and s["diff"], s


def test_perfetto_export_unifies_planes_e2e(two_node_compiled,
                                            tmp_path):
    """`trace --perfetto out.json` against the live 2-node cluster
    writes one file whose compile, task-span and train-phase lanes
    share a clock (the ISSUE's acceptance artifact)."""
    import io
    from contextlib import redirect_stdout

    from ray_tpu.scripts import cli

    rt_, backend, _session = two_node_compiled
    head = backend.head
    address = backend.head_addr

    @rt_.remote(num_cpus=1)
    def compiled_span():
        import jax
        import jax.numpy as jnp
        from ray_tpu.util import compile_tracker
        tr = compile_tracker.get_global()
        f = tr.wrap(jax.jit(lambda x: x + 1), name="e2e.span_fn")
        f(jnp.zeros((3,), jnp.float32))
        return True

    assert rt_.get(compiled_span.remote(), timeout=300)
    # train lane: seed authentic train_step/train_phase spans (the
    # profiler's wire shape) through the same telemetry path
    now = time.time()
    head.call("telemetry_push", {
        "worker": "traincliw" + "0" * 23, "node": "trainnode" + "0" * 23,
        "events": [
            {"name": "train_step", "kind": "train_step", "task_id": "p",
             "start": now - 0.4, "end": now - 0.1, "ok": True},
            {"name": "forward", "kind": "train_phase", "task_id": "p",
             "start": now - 0.4, "end": now - 0.3, "ok": True},
        ]}, timeout=10)
    # wait for the task span AND its compile record to reach the head
    _compiles_until(head, {"callable": "e2e.span_fn"},
                    lambda d: bool(d["records"]))
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        ev = head.call("timeline_dump") or []
        if any(e.get("kind") == "train_phase" for e in ev) and \
                any(e.get("kind") not in ("train_step", "train_phase")
                    for e in ev):
            break
        time.sleep(0.3)

    out = tmp_path / "cluster.perfetto.json"
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert cli.main(["trace", "--perfetto", str(out),
                         "--address", address]) == 0
    assert "lanes" in buf.getvalue()
    with open(out) as f:
        trace = json.load(f)
    evs = trace["traceEvents"]
    lanes = {e["args"]["name"] for e in evs
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    assert "xla: compiles" in lanes, lanes
    assert "train: steps + phases" in lanes, lanes
    assert any(name.startswith("spans: node") for name in lanes), lanes
    assert any(e.get("cat") == "xla_compile" and
               e.get("name") == "e2e.span_fn" for e in evs
               if e.get("ph") == "X")
    assert any(e.get("cat") == "train_phase" for e in evs)
