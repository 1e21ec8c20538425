"""LLM inference tests: engine-vs-oracle greedy decoding, continuous
batching invariance, page recycling.

The reference has no in-tree equivalent (vLLM does this on GPU); the
oracle here is the training-path Llama forward (models/llama.py) run
autoregressively on the full sequence each step — the engine's paged
incremental path must reproduce its greedy choices exactly.

A test file is what a worker of the suite is handed, so the engine's
cases stand in three: here the greedy stream, the books and the chunk
rows' deal and shapes; tests/test_llm_chunked.py chunked prefill beside
decode rows; tests/test_llm_programs.py the prefix cache, the sharded
engine, the count of programs, the transfers and int8 pools. CFG, the
weights and the oracle are this file's.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from _chunk_rows import (CASES, SHAPE_CASES, check, check_descriptor,
                         check_preempted, check_shapes, pin_full_shape,
                         serve, shape_of)
from _readings import entry
from ray_tpu.llm import InferenceEngine
from ray_tpu.llm import model as M
from ray_tpu.llm.cache import PageAllocator
from ray_tpu.models.llama import LlamaConfig, forward, init_params

CFG = LlamaConfig.tiny(n_layers=2, dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(7))


# ------------------------------------------------------------------ engine


def _oracle_greedy(params, prompt, n_tokens):
    """Autoregressive greedy decode via the full training forward."""
    toks = list(prompt)
    for _ in range(n_tokens):
        logits = forward(params, jnp.asarray([toks]), CFG)
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


def test_engine_matches_full_forward_greedy(params):
    eng = InferenceEngine(CFG, params, page_size=8, total_pages=64,
                          max_batch=4, max_seq_len=128)
    prompt = [5, 17, 42, 9, 100, 3, 77]
    got = eng.generate(prompt, max_new_tokens=12)
    want = _oracle_greedy(params, prompt, 12)
    assert got == want, f"paged decode diverged: {got} vs {want}"


def test_engine_prompt_padding_invariance(params):
    # prompt lengths around the bucket/page boundaries must not matter
    eng = InferenceEngine(CFG, params, page_size=8, total_pages=64,
                          max_batch=4, max_seq_len=128)
    for plen in (1, 7, 8, 9, 16, 17):
        prompt = [(3 * i + 1) % CFG.vocab_size for i in range(plen)]
        got = eng.generate(prompt, max_new_tokens=6)
        want = _oracle_greedy(params, prompt, 6)
        assert got == want, f"len {plen}: {got} vs {want}"


def test_continuous_batching_invariance(params):
    """Interleaved requests must produce exactly what each produces alone
    (continuous batching must not leak state across slots)."""
    eng = InferenceEngine(CFG, params, page_size=8, total_pages=128,
                          max_batch=4, max_seq_len=128)
    prompts = [[11, 22, 33], [101, 5], [60, 61, 62, 63, 64]]
    solo = [_oracle_greedy(params, p, 8) for p in prompts]
    rids = [eng.add_request(p, 8) for p in prompts]
    results = {}
    for _ in range(200):
        results.update(eng.step())
        if len(results) == len(rids):
            break
    for rid, want in zip(rids, solo):
        assert results[rid] == want, f"{rid}: {results[rid]} vs {want}"
    # batches actually shared decode dispatches (continuous batching +
    # multi-step chunking: far fewer device round-trips than tokens)
    assert eng.stats["decode_dispatches"] < sum(len(s) for s in solo) // 2


def test_eos_stops_and_pages_recycle(params):
    eng = InferenceEngine(CFG, params, page_size=8, total_pages=16,
                          max_batch=2, max_seq_len=64)
    free0 = eng.allocator.num_free
    prompt = [5, 17, 42]
    first = _oracle_greedy(params, prompt, 3)
    eos = first[2]
    # greedy tiny models repeat tokens: expected output is the oracle
    # stream truncated at the FIRST occurrence of eos
    want = first[:first.index(eos)] if eos in first else first
    eng.eos_token = eos
    got = eng.generate(prompt, max_new_tokens=10)
    assert got == want, f"eos not honored: {got} vs {want}"
    assert eng.allocator.num_free == free0, "pages leaked after finish"


# where each of a lone request's tokens is booked, decode_chunk 4: token 0
# by the mixed step that finishes its prompt; token 1 by the next mixed
# step's decode row when a second prompt arrives in between, else tokens
# 1-4 by one decode block
# (token, the kind of the step that books it, which step that is)
_BOOKED_AT = {"first-token": (0, "mixed", 0), "mixed-row": (1, "mixed", 1),
              "decode-block": (2, "decode", 1)}


@pytest.fixture(scope="module")
def learned(params):
    """(prompt, its first six greedy tokens, no EOS set), the first three
    distinct: a tiny greedy model repeats itself, and a token that came
    before cannot be the EOS that ends the sequence later."""
    eng = InferenceEngine(CFG, params, page_size=8, total_pages=32,
                          max_batch=2, max_seq_len=64, decode_chunk=4)
    for prompt in ([154, 179], [40, 171, 23, 143, 69], [109, 7]):
        toks = eng.generate(prompt, max_new_tokens=6)
        if len(set(toks[:3])) == 3:
            return prompt, toks
    raise AssertionError("no candidate prompt starts with three distinct "
                         "greedy tokens")


@pytest.mark.parametrize("where", list(_BOOKED_AT))
@pytest.mark.parametrize("reason", ["stop", "length"])
def test_token_booking_is_one_rule(params, learned, reason, where):
    """One rule wherever a token is booked: EOS ends the sequence and is
    dropped ("stop"), the token that uses up max_new_tokens ends it and
    is kept ("length"); what was streamed and what the request record
    counted is what was generated; pages and slot come back."""
    prompt, toks = learned
    at, kind, step = _BOOKED_AT[where]
    eng = InferenceEngine(CFG, params, page_size=8, total_pages=32,
                          max_batch=2, max_seq_len=64, decode_chunk=4,
                          prefix_cache=False, request_log=True)
    # _BOOKED_AT's steps are those of one program at a time (running
    # ahead books a step later where a program is left in flight:
    # tests/test_llm_ahead.py holds that order to this one's tokens)
    eng._run_ahead = False
    eng.track_progress = True
    free0 = eng.allocator.num_free
    if reason == "stop":
        eng.eos_token = toks[at]
    rid = eng.add_request(
        prompt, max_new_tokens=len(toks) if reason == "stop" else at + 1)
    done, streamed, kind_at_finish = {}, [], None
    for i in range(50):
        if i == 1 and where == "mixed-row":
            # a prompt to prefill beside the first one's decode row (its
            # own tokens never meet the first one's EOS: one of its own)
            eng.add_request([t + 1 for t in prompt], max_new_tokens=1)
        done.update(eng.step())
        streamed += eng.drain_progress().get(rid, [])
        if rid in done and kind_at_finish is None:
            kind_at_finish = eng._step_meta["kind"], i
        if not eng.has_work():
            break
    want = toks[:at] if reason == "stop" else toks[:at + 1]
    assert done[rid] == want
    assert eng.finish_reason(rid) == reason
    assert streamed == want
    # it ended where the case says: the step kind, and which step
    assert kind_at_finish == (kind, step)
    rec = eng.request_log.get(rid)
    assert rec.n_generated == len(want) and rec.finish_reason == reason
    assert rec.ttft is not None and rec.finish_ts >= rec.first_ts
    assert sum(n for _, n in rec.decode_entries()) \
        == max(0, len(want) - 1)         # the first token is the TTFT's
    assert eng.allocator.num_free == free0, "pages leaked after finish"
    assert eng._slots == [None] * eng.max_batch and not eng.running


def _metric_patterns(reading):
    """The module-name patterns of one of the benchmark's trace metrics,
    read from the data file of the entry that reads it for reason-1chip:
    the test follows the yardstick, not a copy."""
    _, args, _ = entry(reading, "reason-1chip")
    return [re.compile(p) for p in args["patterns"]]


@pytest.mark.parametrize("tp", [1, 2], ids=["tp1", "tp2"])
def test_step_program_names_are_the_benchmarks(params, tp):
    """The names the yardstick matches, pinned: the lowered module names
    of the mixed step and the decode loop (the data files of the entries
    that read trace_module match them in the trace;
    a renamed jit nulls four per-layer metrics in silence), the private
    attribute benchmark/replica.py reads, and the compile tracker's
    names for the step programs."""
    # shapes no other test of this process uses: the warm-up must compile
    eng = InferenceEngine(CFG, params, tp=tp, page_size=8, total_pages=40,
                          max_batch=3, max_seq_len=72, decode_chunk=3,
                          prefill_chunk=24)
    names = ("llm.ragged_step", "llm.decode_loop")
    tracker = eng._fns.tracker

    def compiles():
        return [(tracker.callable_stats(n) or {"compiles": 0})["compiles"]
                for n in names]

    before = compiles()
    eng.generate([5, 17, 42], max_new_tokens=5)
    assert all(b > a for a, b in zip(before, compiles())), \
        (before, compiles())
    assert eng._fns.paged_impl == eng.device_report()["paged_impl"] \
        == "reference"

    layouts = {"ragged_step": eng._fns.step_layouts[eng.prefill_rows],
               "decode_loop": eng._fns.decode_layout}
    for program, metric_file in (("ragged_step", "mixed_step_ms"),
                                 ("decode_loop", "decode_step_ms")):
        jit, statics = eng._fns.jits[program]
        desc = jnp.zeros(M.layout_size(layouts[program]), jnp.int32)
        text = jit.lower(eng.params, desc, eng.kv, eng._last,
                         **statics).as_text()
        module = re.search(r"module @(\S+)", text).group(1)
        assert any(rx.search(module)
                   for rx in _metric_patterns(metric_file)), \
            (module, metric_file)
    if tp > 1:       # its own jits; tp=1 counts the process's shared ones
        assert eng.compiled_step_programs() <= eng._fns.program_budget == 4


def test_page_allocator():
    a = PageAllocator(8)
    assert a.num_free == 7  # page 0 reserved
    got = a.alloc(3)
    assert got is not None and len(got) == 3 and 0 not in got
    assert a.alloc(10) is None
    a.free(got)
    assert a.num_free == 7


def test_page_allocator_refcounts_and_double_free():
    from ray_tpu.llm.cache import DoubleFreeError
    a = PageAllocator(8)            # strict under pytest
    (p,) = a.alloc(1)
    assert a.refcount(p) == 1
    a.incref([p])
    assert a.refcount(p) == 2
    a.free([p])                     # decref only: still allocated
    assert a.refcount(p) == 1 and a.num_free == 6
    a.free([p])                     # last ref: back on the free list
    assert a.refcount(p) == 0 and a.num_free == 7
    with pytest.raises(DoubleFreeError):
        a.free([p])
    with pytest.raises(ValueError):
        a.incref([p])               # unallocated page can't gain sharers
    relaxed = PageAllocator(8, strict_free=False)
    (q,) = relaxed.alloc(1)
    relaxed.free([q])
    relaxed.free([q])               # production mode: logged and skipped
    assert relaxed.num_free == 7


def test_prefix_cache_match_register_evict():
    from ray_tpu.llm.cache import PrefixCache
    a = PageAllocator(16)
    c = PrefixCache(a, page_size=4)
    prompt = list(range(10))        # 2 full blocks + 2-token tail
    pages = a.alloc(3)
    c.register(prompt, pages)       # publishes the 2 full blocks only
    assert c.num_cached == 2
    hit, matched, cow = c.match(prompt)
    assert hit == pages[:2] and matched == 8 and not cow
    # exact page multiple: cap at len-1 cuts into the last shared page
    hit2, matched2, cow2 = c.match(prompt[:8])
    assert matched2 == 7 and cow2
    # different second block: partial (single-block) match
    hit3, matched3, _ = c.match(prompt[:4] + [99, 98, 97, 96])
    assert hit3 == pages[:1] and matched3 == 4
    for h in (hit, hit2, hit3):
        a.free(h)
        c.note_release(h)
    assert c.num_evictable == 0     # original refs still held
    a.free(pages)
    c.note_release(pages)
    assert c.num_evictable == 2     # only the cache references them now
    assert c.evict(5) == 2 and c.num_cached == 0
    assert a.num_free == 15


def test_multi_prompt_single_ragged_dispatch(params):
    """Several waiting prompts admit together and ALL their prefill
    chunks ride ONE ragged step dispatch (the fused argmax hands each
    its first token from the same program) — and each still reproduces
    its solo greedy output exactly."""
    eng = InferenceEngine(CFG, params, page_size=8, total_pages=128,
                          max_batch=4, max_seq_len=128,
                          prefill_chunk=16, prefill_rows=3)
    prompts = [[7 + i for i in range(12)],
               [40 + i for i in range(10)],
               [90 + i for i in range(15)]]
    solo = [_oracle_greedy(params, p, 6) for p in prompts]
    rids = [eng.add_request(p, 6) for p in prompts]
    results = dict(eng.step())   # one step admits + prefills all three
    assert eng.stats["ragged_dispatches"] == 1, \
        "three prompts' prefills should ride ONE ragged dispatch"
    for _ in range(100):
        if len(results) == len(rids):
            break
        results.update(eng.step())
    for rid, want in zip(rids, solo):
        assert results[rid] == want, f"{rid}: {results[rid]} vs {want}"


# ------------------------------------- chunked prefill + prefix caching


@pytest.fixture(scope="module")
def rows_1_and_2(params):
    """The same weights behind one chunk row a step and behind two."""
    return [InferenceEngine(CFG, params, page_size=8, total_pages=128,
                            max_batch=4, max_seq_len=128, prefill_chunk=16,
                            prefill_rows=n) for n in (1, 2)]


@pytest.mark.parametrize("case", CASES)
def test_joined_chunk_rows_compute_what_one_row_a_step_does(rows_1_and_2,
                                                            case):
    """A sequence with rows to spare computes several chunks in ONE mixed
    step (per-head K and V pool): the same tokens as one chunk a step,
    alone, after a prefix hit, beside another prompt, under a budget."""
    check(case, *rows_1_and_2, vocab=CFG.vocab_size)


@pytest.fixture(scope="module", params=[1, 2], ids=["tp1", "tp2"])
def shaped_and_full(params, request):
    """The same weights behind the set of mixed-step shapes and behind
    the full shape alone (what every step ran in before the set); tp=2:
    the shard_map programs on the CPU mesh, a trace a shape too."""
    make = lambda: InferenceEngine(                          # noqa: E731
        CFG, params, page_size=8, total_pages=128, max_batch=4,
        max_seq_len=128, prefill_chunk=16, prefill_rows=2, decode_chunk=4,
        tp=request.param)
    return [make(), pin_full_shape(make())]


@pytest.mark.parametrize("case", SHAPE_CASES)
def test_a_mixed_step_runs_the_smallest_shape_that_holds_its_rows(
        shaped_and_full, case):
    """A lone one-chunk prompt runs the one-row shape, two prompts the
    two-row one, and arrivals beside decode rows both in turn: token for
    token what the full shape alone serves, the books the shapes' run."""
    check_shapes(case, *shaped_and_full, vocab=CFG.vocab_size)
    shaped = shaped_and_full[0]
    if shaped.tp > 1:                       # its own jits: its own count
        assert shaped.compiled_step_programs() \
            <= shaped._fns.program_budget == 4


@pytest.mark.parametrize("prefill_rows,shapes,dealt,ran", [
    (1, (1,), 1, 1), (1, (1,), 3, 1), (4, (1, 2, 4), 1, 1),
    (4, (1, 2, 4), 2, 2), (4, (1, 2, 4), 3, 4), (4, (1, 2, 4), 4, 4),
    (6, (1, 2, 4, 6), 5, 6)])
def test_the_shapes_follow_from_prefill_rows(params, rows_1_and_2,
                                             prefill_rows, shapes, dealt,
                                             ran):
    """1, 2, 4, ... below prefill_rows and prefill_rows itself, no
    setting: a deal of 3 rows of 4 runs the 4-row shape, of 5 of 6 the
    6-row one. ``dealt`` one-chunk prompts arrive together (one row a
    step where prefill_rows is 1) and are served what each is alone."""
    eng = InferenceEngine(CFG, params, page_size=8, total_pages=128,
                          max_batch=6, max_seq_len=128, prefill_chunk=16,
                          prefill_rows=prefill_rows)
    assert eng._fns.row_shapes == shapes
    assert eng._fns.program_budget == 2 + len(shapes)
    assert (eng.ragged_rows, eng.ragged_tokens) \
        == (6 + prefill_rows, 6 + 16 * prefill_rows)
    prompts = [[(7 * i + 3 * j + prefill_rows) % CFG.vocab_size
                for i in range(5 + 2 * j)] for j in range(dealt)]
    # serve() holds each step's arrays and books to shape_of(its deal)
    got, deals = serve(eng, prompts)
    assert len(deals[0]) == min(dealt, prefill_rows)
    assert shape_of(eng, deals[0]) == (ran, 6 + ran, 6 + 16 * ran)
    for p, out in zip(prompts, got):
        assert out == rows_1_and_2[0].generate(p, 6)


_CLOSED_LOOP = ["reason-1chip", "reason-moe-1chip", "reason-lfm2-1chip",
                "context-kanana-1chip", "reason-granite-1chip",
                "context-brumby-1chip", "context-mimo-1chip",
                "mixed-trinity-1chip",
                "reason-gigachat-1chip"]


def _read_for(reading, cells, moves, layer_of):
    """(the reader's module, the data file's arguments) of ``reading``
    (tests/_readings.py), held the same for every one of ``cells``: each
    is IN the workloads of the one entry that reads it, which moves
    ``moves`` off the program's counters, in the layer of the entry that
    reads ``layer_of`` (for reason-1chip: every such entry has it)."""
    found = [entry(reading, cell) for cell in cells]
    layer = entry(layer_of, "reason-1chip")[0]["layer"]
    for m, args, reader in found:
        assert (m["moves"], m["source"], m["layer"]) \
            == (moves, "program_counter", layer)
        assert (args, reader) == found[0][1:]
    return found[0][2], found[0][1]


@pytest.mark.parametrize("cells,moves", [
    (_CLOSED_LOOP, "out_tok_per_s"), (["chat-1chip"], "tpot_p50_ms")],
    ids=["closed-loop", "chat"])
def test_the_small_shapes_metric_reads_the_counter(rows_1_and_2, cells,
                                                   moves):
    """The data file over a prompt of 45 served alone (rows of 16 + 16 in
    the full shape, then 13 in the one-row shape): half the mixed steps
    small; 0 on an engine with one shape (prefill_rows 1: the control
    cell); and a program without the counter (the parent commit, in the
    driver's traced run of it) reads nothing and does not raise."""
    reader, args = _read_for("mixed_small_shape_pct", cells, moves,
                             "batch_occupancy_pct")
    for eng, want in zip(rows_1_and_2, (0.0, 50.0)):
        a = dict(eng.stats)
        eng.generate([(11 * i + len(moves)) % CFG.vocab_size
                      for i in range(45)], 3)
        data = {"stats_open": a, "stats_close": dict(eng.stats),
                "config": {}}
        assert reader.read(data, args) == pytest.approx(want)
    old = {k: {s: v for s, v in data[k].items()
               if s != "ragged_small_dispatches"}
           for k in ("stats_open", "stats_close")}
    assert reader.read(dict(old, config={}), args) is None


@pytest.mark.parametrize("reading", ["mixed_step_ms",
                                     "mixed_step_time_pct"])
def test_the_claimed_cells_mixed_step_metrics_are_granites_readers(reading):
    """reason-1chip's mixed step is read as granite's is: the same reader
    and patterns, so the parent's trace gives both (both shapes are traces
    of one jit: one module name, and the reading is a mean over the shapes
    run)."""
    mine, args, reader = entry(reading, "reason-1chip")
    other, other_args, other_reader = entry(reading, "reason-granite-1chip")
    assert {k: mine[k] for k in ("unit", "better", "source", "layer",
                                 "moves")} \
        == {k: other[k] for k in ("unit", "better", "source", "layer",
                                  "moves")}
    assert (reader, args) == (other_reader, other_args)


@pytest.mark.parametrize("name,cells,want,parent", [
    ("chunk_rows_joined_pct", ["context-kanana-1chip", "reason-moe-1chip",
                               "reason-granite-1chip", "context-mimo-1chip",
                               "mixed-trinity-1chip",
                               "reason-gigachat-1chip"],
     100.0 / 3, None),
    ("chunk_tokens_a_step", ["context-kanana-1chip"], 45 / 2, 45 / 2)])
def test_the_deals_two_metrics_read_the_counters(rows_1_and_2, name, cells,
                                                 want, parent):
    """The benchmark's two data files over a prompt of 45 served alone
    (rows of 16 + 16, then 13): a third of the rows joined, 22.5 tokens a
    step; and a program without the row counters (the parent commit, in
    the driver's traced run of it) reads nothing for the share and does
    not raise, and reads the tokens a step, whose keys are older."""
    reader, args = _read_for(name, cells, "out_tok_per_s",
                             "batch_occupancy_pct")
    eng = rows_1_and_2[1]
    a = dict(eng.stats)
    # a prompt of its own a metric: the prefix cache holds the other's
    eng.generate([(9 * i + len(name)) % CFG.vocab_size for i in range(45)],
                 3)
    data = {"stats_open": a, "stats_close": dict(eng.stats), "config": {}}
    assert reader.read(data, args) == pytest.approx(want)
    old = {k: {s: v for s, v in data[k].items()
               if not s.startswith("chunk_rows")}
           for k in ("stats_open", "stats_close")}
    got = reader.read(dict(old, config={}), args)
    assert got == (None if parent is None else pytest.approx(parent))


@pytest.mark.parametrize("name,want,parent", [
    ("engine_h2d_ms", "wall_ns_h2d", "wall_ns_h2d"),
    ("h2d_arrays_a_dispatch", 1.0, None)])
def test_the_transfers_two_metrics_read_the_counters(rows_1_and_2, name,
                                                     want, parent):
    """The benchmark's two data files over a prompt served alone (mixed
    steps, then decode loops): exactly one host array a dispatch, and the
    engine.h2d clock a dispatch in ms; a program without the array counter
    (the parent commit, in the driver's traced run of it) reads nothing
    for the first and does not raise, and reads the clock, which is
    older."""
    reader, args = _read_for(name, _CLOSED_LOOP, "out_tok_per_s",
                             "engine_host_ms")
    assert entry(name, "reason-1chip")[0]["better"] == "lower"
    eng = rows_1_and_2[1]
    a = dict(eng.stats)
    eng.generate([(13 * i + len(name)) % CFG.vocab_size for i in range(45)],
                 9)
    b = dict(eng.stats)
    n = sum(b[k] - a[k] for k in ("decode_dispatches", "ragged_dispatches"))
    assert b["decode_dispatches"] > a["decode_dispatches"]

    def expect(w):
        return w if not isinstance(w, str) else (b[w] - a[w]) / n * 1e-6
    data = {"stats_open": a, "stats_close": b, "config": {}}
    assert reader.read(data, args) == pytest.approx(expect(want))
    old = {k: {s: v for s, v in data[k].items() if s != "h2d_arrays"}
           for k in ("stats_open", "stats_close")}
    got = reader.read(dict(old, config={}), args)
    assert got == (None if parent is None else pytest.approx(expect(parent)))


@pytest.mark.parametrize("tp", [1, 2], ids=["tp1", "tp2"])
def test_a_descriptor_holds_the_arrays_the_engine_packed_before(params, tp):
    """Per-head K and V pool, one device and the shard_map programs: every
    field of every descriptor is the old packing's array byte for byte,
    and the tokens are those of an engine sent the old arrays."""
    settings = dict(page_size=8, total_pages=128, max_batch=4,
                    max_seq_len=128, prefill_chunk=16, prefill_rows=2,
                    decode_chunk=4, tp=tp)
    check_descriptor(lambda **kw: InferenceEngine(
        CFG, params, **{**settings, **kw}), vocab=CFG.vocab_size)


def test_a_preempted_sequences_re_prefill_takes_both_rows(params):
    check_preempted(lambda **kw: InferenceEngine(
        CFG, params, max_batch=4, decode_chunk=4, **kw))
