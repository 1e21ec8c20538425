"""The engine's pool and programs on the CFG and the oracle of
tests/test_llm.py: the prefix cache (full and partial hits, copy on write,
eviction), the tp=2 sharded engine against one chip, the count of compiled
step programs and its budget, a replica that compiles nothing once it is
ready, one transfer a dispatch, and int8 KV pools.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _chunk_rows import fields_of
from ray_tpu.llm import InferenceEngine
from ray_tpu.models.llama import LlamaConfig, init_params
from test_llm import CFG, _oracle_greedy, params  # noqa: F401


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
