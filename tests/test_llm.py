"""LLM inference tests: engine-vs-oracle greedy decoding, continuous
batching invariance, page recycling.

The reference has no in-tree equivalent (vLLM does this on GPU); the
oracle here is the training-path Llama forward (models/llama.py) run
autoregressively on the full sequence each step — the engine's paged
incremental path must reproduce its greedy choices exactly.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _chunk_rows import (CASES, SHAPE_CASES, check, check_descriptor,
                         check_preempted, check_shapes, fields_of,
                         pin_full_shape, serve, shape_of)
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


def test_chunked_prefill_matches_oracle(params):
    """Chunk-by-chunk prefill (chunk attention over prior paged KV) must
    reproduce the one-shot prefill greedy stream exactly."""
    eng = InferenceEngine(CFG, params, page_size=8, total_pages=64,
                          max_batch=4, max_seq_len=128,
                          prefix_cache=False, prefill_chunk=8)
    prompt = [(5 * i + 2) % CFG.vocab_size for i in range(20)]
    got = eng.generate(prompt, max_new_tokens=8)
    # 8 + 8 tokens as the two rows of one step, then 4
    assert eng.stats["ragged_dispatches"] == 2
    assert got == _oracle_greedy(params, prompt, 8)


def test_step_token_budget_slices_chunks(params):
    """A per-step budget below prefill_chunk bounds each step's chunk;
    the output is budget-invariant."""
    eng = InferenceEngine(CFG, params, page_size=8, total_pages=64,
                          max_batch=4, max_seq_len=128,
                          prefix_cache=False, prefill_chunk=8,
                          step_token_budget=4)
    prompt = [(5 * i + 2) % CFG.vocab_size for i in range(20)]
    got = eng.generate(prompt, max_new_tokens=8)
    assert eng.stats["ragged_dispatches"] == 5   # 4-token slices
    assert got == _oracle_greedy(params, prompt, 8)


def test_prefix_cache_hit_and_cached_tokens(params):
    """A repeated prompt reuses its full KV pages: only the tail
    prefills, the output is unchanged, and cached tokens are reported."""
    eng = InferenceEngine(CFG, params, page_size=8, total_pages=64,
                          max_batch=4, max_seq_len=128)
    prompt = [(7 * i + 3) % CFG.vocab_size for i in range(20)]
    want = _oracle_greedy(params, prompt, 8)
    assert eng.generate(prompt, max_new_tokens=8) == want   # cold
    pf0 = eng.stats["prefill_tokens"]
    rid = eng.add_request(prompt, 8)
    done = {}
    for _ in range(100):
        done.update(eng.step())
        if rid in done:
            break
    assert done[rid] == want
    assert eng.stats["cached_tokens"] == 16     # 2 full pages reused
    assert eng.cached_tokens(rid) == 16
    assert eng.cached_tokens(rid) == 0          # accounting pops
    assert eng.stats["prefill_tokens"] - pf0 == 4   # only the tail


def test_prefix_cache_partial_hit(params):
    """Prompts sharing only the first page reuse exactly that page."""
    eng = InferenceEngine(CFG, params, page_size=8, total_pages=64,
                          max_batch=4, max_seq_len=128)
    a = [(3 * i + 2) % CFG.vocab_size for i in range(20)]
    b = a[:8] + [(11 * i + 5) % CFG.vocab_size for i in range(12)]
    assert eng.generate(a, 6) == _oracle_greedy(params, a, 6)
    want = _oracle_greedy(params, b, 6)
    rid = eng.add_request(b, 6)
    done = {}
    for _ in range(100):
        done.update(eng.step())
        if rid in done:
            break
    assert done[rid] == want
    assert eng.cached_tokens(rid) == 8


def test_prefix_cache_cow_on_exact_page_multiple(params):
    """Prompt length an exact page multiple with every block cached: the
    match caps at len-1, which lands the tail INSIDE the last shared
    page — the engine must copy it (COW) and still match the oracle."""
    eng = InferenceEngine(CFG, params, page_size=8, total_pages=64,
                          max_batch=4, max_seq_len=128)
    prompt = [(9 * i + 4) % CFG.vocab_size for i in range(16)]
    want = _oracle_greedy(params, prompt, 6)
    assert eng.generate(prompt, max_new_tokens=6) == want
    rid = eng.add_request(prompt, 6)
    done = {}
    for _ in range(100):
        done.update(eng.step())
        if rid in done:
            break
    assert done[rid] == want
    assert eng.stats["cow_copies"] == 1
    assert eng.cached_tokens(rid) == 15


def test_prefix_cache_evicts_under_pressure(params):
    """Cached pages are free HBM: when a new prompt can't allocate, LRU
    cached pages return to the free list and admission succeeds."""
    eng = InferenceEngine(CFG, params, page_size=8, total_pages=8,
                          max_batch=2, max_seq_len=64)
    small = [(2 * i + 1) % CFG.vocab_size for i in range(16)]
    assert eng.generate(small, 4) == _oracle_greedy(params, small, 4)
    assert eng.prefix.num_evictable == 2        # its 2 full pages cached
    big = [(13 * i + 7) % CFG.vocab_size for i in range(40)]
    assert eng.generate(big, 4) == _oracle_greedy(params, big, 4)
    assert eng.prefix.evictions >= 1


def test_decode_interleaves_with_chunked_prefill(params):
    """A long prompt chunk-prefills WHILE the running batch keeps
    decoding — the decode stream is never stalled for the whole prefill
    (the head-of-line fix this PR is for)."""
    eng = InferenceEngine(CFG, params, page_size=8, total_pages=128,
                          max_batch=4, max_seq_len=256, decode_chunk=4,
                          prefix_cache=False, prefill_chunk=8,
                          step_token_budget=8)
    eng._run_ahead = False      # the books below are read step by step
    a = [9, 4, 33, 2, 71]
    b = [(5 * i + 1) % CFG.vocab_size for i in range(40)]
    wa = _oracle_greedy(params, a, 28)    # 7 decode dispatches of 4:
    wb = _oracle_greedy(params, b, 4)     # outlives b's 5 chunk steps
    results = {}
    ra = eng.add_request(a, 28)
    results.update(eng.step())          # a joins the decode batch
    d0 = eng.stats["decode_tokens"]
    rb = eng.add_request(b, 4)
    for _ in range(20):
        results.update(eng.step())
        if not any(s.request_id == rb for s in eng._chunking):
            break
    # a's prefill rode dispatch 1; b's 40 tokens take 5 more (budget 8)
    assert eng.stats["ragged_dispatches"] == 6
    assert eng.stats["decode_tokens"] > d0, \
        "decode starved while the long prompt prefilled"
    for _ in range(100):
        if ra in results and rb in results:
            break
        results.update(eng.step())
    assert results[ra] == wa and results[rb] == wb


def test_admission_lookahead_avoids_head_of_line(params):
    """A head request that can't get pages must not block an admissible
    request behind it (bounded lookahead) — unless the head has aged
    past admit_age_cap_s, in which case freed pages are reserved for it."""
    def setup(**kw):
        eng = InferenceEngine(CFG, params, page_size=8, total_pages=8,
                              max_batch=3, max_seq_len=64,
                              prefix_cache=False, **kw)
        # decoder holding 5 of the 7 allocatable pages
        eng.add_request([(2 * i + 1) % CFG.vocab_size
                         for i in range(24)], 30)
        eng.step()
        rb = eng.add_request([(3 * i + 2) % CFG.vocab_size
                              for i in range(17)], 4)   # needs 3 pages
        rc = eng.add_request([11, 5, 42, 7, 9, 1, 3], 4)  # needs 1 page
        eng.step()
        waiting = {s.request_id for s in eng.waiting}
        return rb, rc, waiting

    rb, rc, waiting = setup()
    assert rb in waiting, "head shouldn't fit yet"
    assert rc not in waiting, "lookahead should admit the small prompt"

    # aged head (cap 0 -> instantly aged): scan freezes at the head
    rb, rc, waiting = setup(admit_age_cap_s=0.0)
    assert rb in waiting and rc in waiting, \
        "aged memory-blocked head must stop younger requests jumping it"


# ------------------------------------------------------------------- tp


def test_tp_engine_matches_single_chip(params):
    """tp=2 sharded engine (weights Megatron-split, kv-heads sharded over
    a ('tp',) mesh) reproduces the tp=1 greedy stream exactly — single
    AND batched prefill paths (reference capability: vllm_models.py
    tensor_parallel_size; here the mesh IS the worker group)."""
    kw = dict(page_size=8, total_pages=64, max_batch=4, max_seq_len=128,
              decode_chunk=4)
    e1 = InferenceEngine(CFG, params, **kw)
    e2 = InferenceEngine(CFG, params, tp=2, **kw)
    assert e2.mesh is not None and e2.mesh.shape["tp"] == 2
    prompt = [5, 17, 42, 9, 100, 3, 77]
    assert e2.generate(prompt, max_new_tokens=10) == \
        e1.generate(prompt, max_new_tokens=10)
    # multi-prompt ragged prefill under shard_map parity
    prompts = [[11, 22, 33], [101, 5, 9], [60, 61, 62, 63, 64]]
    r1 = [e1.add_request(p, 6) for p in prompts]
    r2 = [e2.add_request(p, 6) for p in prompts]
    d1, d2 = {}, {}
    for _ in range(100):
        d1.update(e1.step())
        d2.update(e2.step())
        if len(d1) == len(r1) and len(d2) == len(r2):
            break
    for a, b in zip(r1, r2):
        assert d1[a] == d2[b], (d1[a], d2[b])
    assert e2.stats["ragged_dispatches"] == e1.stats["ragged_dispatches"]


def test_tp_chunked_prefill_prefix_and_cow(params):
    """The sharded chunk-prefill and COW page-copy programs (shard_map
    over kv-head shards) reproduce the oracle stream: chunked cold
    prefill, a prefix-cache hit, and an exact-page-multiple COW."""
    eng = InferenceEngine(CFG, params, tp=2, page_size=8, total_pages=64,
                          max_batch=2, max_seq_len=128, decode_chunk=4,
                          prefill_chunk=8)
    prompt = [(5 * i + 2) % CFG.vocab_size for i in range(20)]
    want = _oracle_greedy(params, prompt, 6)
    assert eng.generate(prompt, max_new_tokens=6) == want   # chunked cold
    # two rows of 8 in one step (the sharded program reads the first
    # row's tokens from the pool for the second), then 4
    assert eng.stats["ragged_dispatches"] == 2
    assert eng.stats["chunk_rows_joined"] == 1
    rid = eng.add_request(prompt, 6)                        # prefix hit
    done = {}
    for _ in range(100):
        done.update(eng.step())
        if rid in done:
            break
    assert done[rid] == want
    assert eng.cached_tokens(rid) == 16
    p2 = prompt[:16]                      # exact page multiple: COW path
    assert eng.generate(p2, max_new_tokens=4) == \
        _oracle_greedy(params, p2, 4)
    assert eng.stats["cow_copies"] == 1


def test_tp_validation():
    from ray_tpu.llm.tp import validate_tp
    with pytest.raises(ValueError):
        validate_tp(CFG, 3)           # 3 does not divide n_kv_heads=4
    with pytest.raises(ValueError):
        InferenceEngine(CFG, tp=64)   # more shards than devices


def test_mixed_length_prompts_share_one_dispatch(params):
    """Wildly different prompt lengths pack into the SAME ragged
    dispatch — the case the old length-bucketed prefill could never
    batch (different compile buckets forced separate dispatches)."""
    eng = InferenceEngine(CFG, params, page_size=8, total_pages=128,
                          max_batch=4, max_seq_len=128, prefill_chunk=32)
    short = [5, 6, 7]
    long = [20 + i for i in range(20)]
    solo = [_oracle_greedy(params, p, 5) for p in (short, long)]
    rids = [eng.add_request(short, 5), eng.add_request(long, 5)]
    results = dict(eng.step())
    assert eng.stats["ragged_dispatches"] == 1, \
        "3- and 20-token prompts should prefill in one ragged dispatch"
    for _ in range(100):
        if len(results) == 2:
            break
        results.update(eng.step())
    for rid, want in zip(rids, solo):
        assert results[rid] == want


def test_compiled_step_programs_constant(params):
    """The compile-count contract: an engine serving wildly varying
    prompt lengths, chunk boundaries and batch occupancies compiles at
    most 2 + its mixed-step shapes programs (decode loop, COW copy, the
    ragged mixed step once a chunk-row shape: FOUR with two chunk rows)
    — no per-length-bucket program zoo."""
    eng = InferenceEngine(CFG, params, page_size=8, total_pages=64,
                          max_batch=3, max_seq_len=80, decode_chunk=3,
                          prefill_chunk=10)
    assert eng._fns.row_shapes == (1, 2) and eng._fns.program_budget == 4
    before = eng.compiled_step_programs()
    for plen in (1, 4, 9, 10, 11, 23, 30):
        prompt = [(3 * i + 1) % CFG.vocab_size for i in range(plen)]
        eng.generate(prompt, max_new_tokens=4)
    # repeated prompt -> prefix hit; exact-page-multiple -> COW program
    eng.generate([(3 * i + 1) % CFG.vocab_size for i in range(16)], 4)
    eng.generate([(3 * i + 1) % CFG.vocab_size for i in range(16)], 4)
    assert eng.stats["cow_copies"] >= 1
    # two prompts together take both chunk rows: the full shape (a chunk
    # of 10 ends inside a page of 8, so a lone prompt keeps to one row)
    for plen in (5, 12):
        eng.add_request([(7 * i + 2) % CFG.vocab_size for i in range(plen)],
                        4)
    while eng.has_work():
        eng.step()
    assert 0 < eng.stats["ragged_small_dispatches"] \
        < eng.stats["ragged_dispatches"]
    # at most: the module-level jits are the process's, and another test
    # of this file may have compiled one of these shapes already
    compiled = eng.compiled_step_programs() - before
    assert 1 <= compiled <= eng._fns.program_budget, \
        f"expected <=4 compiled step programs, got {compiled}"
    # spot-check parity so the count isn't trivially cheap
    p = [(3 * i + 1) % CFG.vocab_size for i in range(23)]
    assert eng.generate(p, 4) == _oracle_greedy(params, p, 4)


@pytest.mark.parametrize("prefill_rows,budget", [(1, 3), (2, 4), (4, 5)])
def test_a_program_past_the_seams_budget_is_a_breach(params, prefill_rows,
                                                     budget):
    """The gauge pass holds the resident programs to the SEAM's number
    (2 + the mixed step's shapes), not to a literal: at the budget no
    event; one past it (a shape outside the set compiled) ONE
    llm_compile_invariant_breach event that carries the budget; again
    only after the count has come back under it."""
    eng = InferenceEngine(CFG, params, page_size=8, total_pages=64,
                          max_batch=3, max_seq_len=80, decode_chunk=3,
                          prefill_chunk=10, prefill_rows=prefill_rows)
    assert eng._fns.program_budget == budget
    tracker = eng._tracker
    tracker.drain_journal_events()
    resident = [budget, budget + 1, budget + 2, budget, budget + 1]
    eng.compiled_step_programs = lambda: resident.pop(0)
    events = []
    for _ in range(5):
        eng._update_metrics(force=True)
        events.append([e for e in tracker.drain_journal_events()
                       if e["type"] == "llm_compile_invariant_breach"])
    assert [len(e) for e in events] == [0, 1, 0, 0, 1]
    assert [(e[0]["programs"], e[0]["budget"]) for e in events if e] \
        == [(budget + 1, budget), (budget + 1, budget)]


def test_a_replica_compiles_nothing_once_it_is_ready(params):
    """LLMServer brings every program its engine can dispatch to a
    compiled, loaded state before its engine thread starts: both
    mixed-step shapes, the decode loop and the page copy. One-row,
    two-row, decode-only and copy-on-write traffic then moves neither
    the compile tracker's counts nor the program count; and loading
    booked nothing. A bare engine of the same shapes compiles lazily."""
    from ray_tpu.llm.serve_llm import LLMServer
    from ray_tpu.util import compile_tracker
    # shapes no other test of this process uses: the replica must compile
    engine = dict(params=params, page_size=8, total_pages=48, max_batch=5,
                  max_seq_len=88, decode_chunk=5, prefill_chunk=24)
    bare = InferenceEngine(CFG, **engine)
    before = bare.compiled_step_programs()
    assert bare._fns.program_budget == 4
    server = LLMServer(dict(n_layers=2, dtype=jnp.float32), engine)
    eng = server.engine
    assert eng.compiled_step_programs() - before == 4
    assert {k: v for k, v in eng.stats.items()
            if v and not k.startswith(("wall_ns_", "cpu_ns_", "startup_"))
            } == {}                      # ... but its own start-up clocks
    assert eng.request_log is None or len(eng.request_log) == 0
    counts = dict(compile_tracker.get_global().stats()["counts"])

    def ask(n, start=3):
        return server({"prompt_ids": [(5 * i + start) % CFG.vocab_size
                                      for i in range(n)],
                       "max_tokens": 7})["token_ids"]

    import threading
    one_row = ask(9)                                    # alone: one row
    ask(40)                                             # alone: two rows
    threads = [threading.Thread(target=ask, args=(n, n))
               for n in (11, 30, 20)]                   # beside decode rows
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    ask(16)
    ask(16)                     # every page cached: a copy-on-write
    stats = eng.stats
    assert stats["cow_copies"] >= 1 and stats["decode_dispatches"] >= 1
    assert 0 < stats["ragged_small_dispatches"] < stats["ragged_dispatches"]
    # one descriptor went up a dispatch, and none for the loading
    assert stats["h2d_arrays"] \
        == stats["decode_dispatches"] + stats["ragged_dispatches"]
    assert dict(compile_tracker.get_global().stats()["counts"]) == counts
    assert eng.compiled_step_programs() - before == 4
    assert one_row == _oracle_greedy(
        params, [(5 * i + 3) % CFG.vocab_size for i in range(9)], 7)


def test_one_transfer_a_dispatch_and_no_buffer_refilled_under_it(params):
    """Decode-only, one-row, two-row and copy-on-write steps: each
    dispatch sends ONE host array (h2d_arrays + 1 when it is booked,
    engine.pack and engine.h2d opened once and clocked), a step that
    launches nothing sends none, and the descriptor a step was launched
    with still reads what was sent after the NEXT step's is packed and
    sent (device_put may alias the host's buffer, as the CPU backend does:
    the engine fills the other one), with that program still in flight
    where the engine runs ahead."""
    import collections
    eng = InferenceEngine(CFG, params, page_size=8, total_pages=128,
                          max_batch=4, max_seq_len=128, prefill_chunk=16,
                          prefill_rows=2, decode_chunk=4)
    opened, phase = collections.Counter(), eng.phase
    sent = []   # (the device array a program was launched with, a copy)

    def counting(name):
        opened[name] += 1
        return phase(name)

    def holding(run):
        def launch(params, desc, *rest):
            sent.append((desc, np.array(desc), eng._flight is not None))
            return run(params, desc, *rest)
        return launch
    eng.phase = counting
    eng._fns.ragged_step = holding(eng._fns.ragged_step)
    eng._fns.decode_loop = holding(eng._fns.decode_loop)
    prompt = [(7 * i + 1) % CFG.vocab_size for i in range(32)]
    arrivals = {0: prompt, 9: prompt[:9], 10: prompt[3:14],
                20: prompt,         # alone: two rows; two beside decode
                # rows; every page cached: a copy; four chunks: two steps,
                # the second launched behind the first
                30: [(11 * i + 5) % CFG.vocab_size for i in range(52)]}
    kinds = collections.Counter()
    for step in range(60):
        if step in arrivals:
            eng.add_request(arrivals[step], 10)
        before, n_sent = dict(eng.stats), len(sent)
        eng.step()
        d = {k: eng.stats[k] - before[k] for k in (
            "h2d_arrays", "decode_dispatches", "ragged_dispatches",
            "wall_ns_pack", "wall_ns_h2d")}
        booked = d["decode_dispatches"] + d["ragged_dispatches"]
        launched = len(sent) - n_sent
        assert booked in (0, 1) and launched in (0, 1)
        assert d["h2d_arrays"] == booked
        # what is launched and not booked is the ONE program in flight
        assert len(sent) - eng.stats["h2d_arrays"] \
            == (eng._flight is not None)
        assert opened["engine.h2d"] == opened["engine.dispatch"] \
            == len(sent)
        if launched:
            assert d["wall_ns_pack"] > 0 and d["wall_ns_h2d"] > 0
            kinds[eng._step_meta["launched"],
                  fields_of(eng, sent[-1][0])["tokens"].size] += 1
            for dev, was, _ in sent[-2:]:
                assert np.asarray(dev).tobytes() == was.tobytes()
        if step > 30 and not eng.has_work():
            break
    assert not eng.has_work() and eng.stats["cow_copies"] == 1
    assert len(sent) == eng.stats["h2d_arrays"]
    assert 0 < sum(ahead for _, _, ahead in sent) \
        == eng.stats["ahead_dispatches"]
    assert opened["engine.pack"] >= len(sent)   # a dry engine packs nothing
    assert set(kinds) == {("decode", 4), ("mixed", 4 + 16), ("mixed", 4 + 32)}


# ------------------------------------------------------------ int8 KV


def test_int8_kv_engine_greedy_equivalence():
    """kv_dtype="int8" (quantized pages + bf16 scales) must leave the
    greedy stream where the plain path puts it — both the chunked
    prefill writes and the decode appends round-trip through int8.

    Judged as ``plain_greedy_check`` and benchmark/checks.py judge a
    stream: teacher-forced on the engine's own tokens, per position the
    logit GAP between the plain path's top choice and the token the
    engine emitted. int8 round-trip noise (~1e-2 relative) may flip a
    near-tie, after which the two greedy streams are different streams
    and ``got == want`` says nothing (with these weights the second
    prompt's first token sits 0.011 logits from the top, on logits of
    unit spread); a wrong page, slot or scale costs whole logits at
    every position after it."""
    from ray_tpu.llm.model import plain_greedy_check
    p8 = init_params(CFG, jax.random.PRNGKey(1))
    eng = InferenceEngine(CFG, p8, page_size=8, total_pages=64,
                          max_batch=4, max_seq_len=128, prefill_chunk=8,
                          kv_dtype="int8")
    assert eng.kv["k"].dtype == jnp.int8
    assert set(eng.kv) == {"k", "v", "k_scale", "v_scale"}
    gaps, equal = [], []
    for prompt in ([5, 17, 42, 9, 100, 3, 77],
                   [(5 * i + 2) % CFG.vocab_size for i in range(20)]):
        got = eng.generate(prompt, max_new_tokens=10)
        check = plain_greedy_check(p8, CFG, prompt, got, 64)
        gaps += check["gap"]
        equal += [a == b for a, b in zip(got, check["plain_tokens"])]
    assert max(gaps) < 0.05, f"int8 KV left the plain path: gaps {gaps}"
    assert sum(equal) >= 0.9 * len(equal), equal


def test_int8_kv_prefix_hit_cow_and_evict(params):
    """Prefix-cache hit, COW and LRU eviction all operate on quantized
    pages (scales ride the same pytree), with hit-vs-cold invariance."""
    eng = InferenceEngine(CFG, params, page_size=8, total_pages=16,
                          max_batch=2, max_seq_len=64, prefill_chunk=8,
                          kv_dtype="int8")
    base = [(7 * i + 3) % CFG.vocab_size for i in range(16)]
    cold = eng.generate(base + [9], 6)
    rid = eng.add_request(base + [9], 6)         # full 2-page hit
    done = {}
    for _ in range(100):
        done.update(eng.step())
        if rid in done:
            break
    assert done[rid] == cold, "int8 prefix hit changed the stream"
    assert eng.cached_tokens(rid) == 16
    cow_cold = eng.generate(base, 6)             # exact page multiple
    cow0 = eng.stats["cow_copies"]
    cow_hit = eng.generate(base, 6)              # COW on shared page
    assert eng.stats["cow_copies"] == cow0 + 1
    assert cow_hit == cow_cold, "int8 COW changed the stream"
    for m in (11, 13, 17):   # distinct 5-page prompts overflow the pool
        big = [(m * i + 5) % CFG.vocab_size for i in range(40)]
        assert eng.generate(big, 4) == eng.generate(big, 4)
    assert eng.prefix.evictions >= 1, "no eviction under pressure"


def test_int8_kv_capacity_ratio():
    """The capacity claim: at head_dim 64, an int8 pool (pages + bf16
    scales) fits >= 1.9x the sequences of an fp16 pool in the same HBM
    bytes."""
    from ray_tpu.llm.cache import make_kv_cache
    cfg = LlamaConfig(vocab_size=128, dim=512, n_layers=2, n_heads=8,
                      n_kv_heads=4, ffn_dim=1024, dtype=jnp.bfloat16)
    assert cfg.head_dim == 64
    fp = make_kv_cache(cfg, total_pages=8, page_size=32)
    q8 = make_kv_cache(cfg, total_pages=8, page_size=32, kv_dtype="int8")
    fp_bytes = sum(leaf.nbytes for leaf in fp.values())
    q8_bytes = sum(leaf.nbytes for leaf in q8.values())
    assert fp_bytes / q8_bytes >= 1.9, \
        f"int8 KV capacity ratio {fp_bytes / q8_bytes:.3f} < 1.9"


def test_kv_tag_prevents_cross_scheme_hits():
    """Pages written under one KV storage scheme must never hash-match
    a lookup under another: same tokens, incompatible page bytes."""
    from ray_tpu.llm.cache import (PageAllocator, PrefixCache,
                                   hash_token_blocks)
    prompt = list(range(16))
    assert hash_token_blocks(prompt, 8, "float32") != \
        hash_token_blocks(prompt, 8, "int8")
    a = PageAllocator(16)
    c_fp = PrefixCache(a, page_size=8, kv_tag="float32")
    c_q8 = PrefixCache(a, page_size=8, kv_tag="int8")
    pages = a.alloc(2)
    c_fp.register(prompt, pages)
    assert c_fp.match(prompt)[1] > 0
    hit, matched, _ = c_q8.match(prompt)
    assert hit == [] and matched == 0, \
        "int8 lookup matched fp-written pages"
