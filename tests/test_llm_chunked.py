"""The engine's chunked prefill against the oracle of tests/test_llm.py (its
CFG, weights and autoregressive forward): chunks against a one-shot
prefill, the step's token budget, decode rows beside chunk rows, admission.
"""

from ray_tpu.llm import InferenceEngine
from test_llm import CFG, _oracle_greedy, params  # noqa: F401


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
