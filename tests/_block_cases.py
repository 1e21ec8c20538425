"""The engine-level cases every block the engine serves goes through,
written ONCE: a row of tests/_blocks.py:BLOCKS on seeded weights, tiny
widths on the CPU, float32 compute, against the block's plain reference
under benchmark/ (whole sequences, no page, no cache, no state).

Not a test file. A test FILE is what a worker of the suite is handed
(`--dist loadfile`), so a block's cases are collected under a file of its
own, tests/test_llm_block_<row>.py: a stub that takes the cases from here
and names its row (``BLOCK``); one engine and one set of compiles a block,
and six workers share the blocks. tests/test_llm_blocks_lowering.py holds
the stubs to the rows of BLOCKS.

Chunked prefill and the decode loop, a mixed batch with padding rows, one
prompt at several chunk sizes and decode rows behind a chunk row (LOGITS),
a reused slot, a preemption, the page copy beside the slot state, the
prefix cache where pages are the only state and its refusal where they
are not, and what the training side and llm/tp.py refuse. A block's own
file (tests/test_llm_<block>.py) keeps its operator's arithmetic, its
kernels in interpret mode and the faults its reference must tell apart.

Every case runs on every block: none that the fold newly applied to a
block failed there (PR 60).
"""

import functools
import logging
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from _blocks import (BLOCKS, ENGINE, PS, built, reference_logits,  # noqa: E402
                     run, served_logits, worst_gap)
from ray_tpu.llm import InferenceEngine, tp  # noqa: E402
from ray_tpu.llm import model as M  # noqa: E402
from ray_tpu.llm.cache import (WINDOW_LEAVES, make_kv_cache,  # noqa: E402
                               prefix_cache_supported, window_first_page)
from ray_tpu.models import llama  # noqa: E402


def pytest_generate_tests(metafunc):
    """The file that collects these cases names ONE row: a parameter list
    of one, so a case's id says its block as it did when one file ran every
    row (``[granite-40-13]``)."""
    if "served" in metafunc.fixturenames:
        metafunc.parametrize("served", [metafunc.module.BLOCK],
                             indirect=True, scope="module")


@pytest.fixture(scope="module")
def served(request):
    """(the block's name, its row, its configuration, an engine on seeded
    weights): one engine a block, every case of the block in turn."""
    # compiled_step_programs() counts the process's shared jits: whatever
    # file this worker ran before must not count against this engine
    jax.clear_caches()
    block = request.param
    cfg, params = built(block)
    return block, BLOCKS[block], cfg, InferenceEngine(cfg, params, **ENGINE)


@functools.lru_cache(maxsize=2)
def _reference_of(block: str, tokens: tuple):
    """The reference's logits over ``tokens`` on the block's seeded
    weights: computed once for the cases that serve the same tokens."""
    cfg, params = built(block)
    return reference_logits(block, params, cfg, tokens)


@pytest.mark.parametrize("n_prompt,n_new", [(40, 13), (5, 9), (16, 6),
                                            (70, 13), (5, 20)])
def test_chunked_prefill_and_decode_loop_match_reference(served, n_prompt,
                                                         n_new):
    """A prompt of 40 in chunk rows of 16, two a step (pages, a latent
    row, a conv's inputs and a matrix state all cross chunk boundaries),
    then the decode loop token by token; a prompt shorter than a chunk, a
    conv's reach and a window; one that ends on a chunk's edge; one far
    past a window and the ring of freed pages; a short one whose decode
    crosses the window. FIRST in this file: the count of compiled programs
    is the process's."""
    block, row, cfg, eng = served
    prompt = list(range(1, 1 + n_prompt))
    before = dict(eng.stats)
    got = eng.generate(prompt, n_new)
    assert len(got) == n_new
    assert worst_gap(block, eng, prompt, got) < row.tol
    # every page is back; no page copy where there is no prefix cache
    assert eng.allocator.num_free + (
        eng.prefix.num_cached if eng.prefix else 0) \
        == eng.allocator.total_pages - 1
    assert (eng.prefix is not None) == row.prefix_cache
    assert eng.compiled_step_programs() \
        <= eng._fns.program_budget - (not row.prefix_cache)
    assert eng._fns.program_budget == 4
    if not row.window:
        return
    W = cfg.sliding_window
    assert eng.window_allocator.num_free \
        == eng.window_allocator.total_pages - 1
    freed = eng.stats["window_pages_freed"] - before["window_pages_freed"]
    inside = eng.stats["rows_inside_window"] - before["rows_inside_window"]
    rows = eng.stats["decode_tokens"] - before["decode_tokens"]
    if n_prompt + n_new <= W:
        # nothing freed, and every decode row-step lay inside the window
        assert freed == 0 and inside == rows > 0
    elif n_prompt < W:
        assert 0 < inside < rows
    else:
        assert inside == 0 < rows
    if n_prompt == 70:
        assert freed >= 70 // PS - 3


def test_mixed_batch_with_padding_rows_matches_reference(served):
    """Four sequences of different lengths, short ones that hold their
    whole window beside long ones that free it: two prompts' chunk rows
    in one mixed step beside decode rows, idle slots and padding tokens,
    the mixed step and the decode loop taking turns."""
    block, row, cfg, eng = served
    prompts = [list(range(3, 3 + n)) for n in (37, 9, 52)]
    before = dict(eng.stats)
    rids = [eng.add_request(p, n) for p, n in zip(prompts, (11, 27, 5))]
    eng.step()
    late = list(range(100, 119))
    rids.append(eng.add_request(late, 6))
    done = run(eng)
    for p, r in zip(prompts + [late], rids):
        assert worst_gap(block, eng, p, done[r]) < row.tol
    if row.window:
        assert 0 < eng.stats["page_steps_window"] \
            - before["page_steps_window"] \
            < eng.stats["page_steps_full"] - before["page_steps_full"]
    if cfg.n_experts:
        assert eng.stats["moe_pairs"] > before["moe_pairs"]
    if cfg.experts_held:
        # the share: pairs routed to experts held elsewhere were counted
        assert eng.stats["moe_absent"] > before["moe_absent"]


@pytest.mark.parametrize("chunk", [4, 7, 12, 16, 64])
def test_the_same_prompt_at_several_chunk_sizes(served, chunk):
    """LOGITS, not tokens: a prompt of 61 through the mixed step's forward
    in chunks of 4, of 7 and 12 (a chunk boundary inside a page, inside a
    block of a recurrence's chunk form, a window that starts inside a
    page), of 16 and whole, a window group a ring of pages written over
    and over: after EVERY chunk the row's logits are the reference's at
    that position."""
    block, row, cfg, eng = served
    prompt = tuple(range(9, 70))
    want = _reference_of(block, prompt)
    got, _ = served_logits(cfg, eng.params, prompt, chunk=chunk)
    for i, logits in enumerate(got):
        at = min(len(prompt), (i + 1) * chunk) - 1
        assert float(jnp.abs(logits - want[at]).max()) < row.tol, (chunk, i)


@pytest.mark.parametrize("n_prompt,n_new", [(6, 8), (11, 14), (70, 13)])
def test_decode_rows_continue_a_chunk_row(served, n_prompt, n_new):
    """LOGITS at every served position: the prompt as chunk rows of 16,
    then one-token rows through the mixed step's decode rows (a
    recurrence's UPDATE form): a sequence that stays inside a window of
    16, one that crosses it while decoding, one far beyond it."""
    block, row, cfg, eng = served
    tokens = list(np.random.default_rng(n_prompt).integers(
        0, cfg.vocab_size, n_prompt + n_new))
    want = reference_logits(block, eng.params, cfg, tokens)
    got, _ = served_logits(cfg, eng.params, tokens, n_prompt)
    ends = [min(n_prompt, lo + 16) - 1 for lo in range(0, n_prompt, 16)] \
        + list(range(n_prompt, len(tokens)))
    assert len(got) == len(ends)
    for at, logits in zip(ends, got):
        assert float(jnp.abs(logits - want[at]).max()) < row.tol, at


def test_a_reused_slot_starts_afresh(served):
    """One slot, two sequences in turn. The second finds the first's state
    in its slot (nothing zeroes it) and must not read it; a window row's
    compact table is back at the scratch page from base 0 (a free slot
    still decodes); pages alone leave nothing to reset."""
    block, row, cfg, eng = served
    one = InferenceEngine(cfg, eng.params, **{**ENGINE, "max_batch": 1})
    first, second = list(range(40, 105)), list(range(5, 23))
    one.add_request(first, 6)
    bases = []
    while one.has_work():
        one.step()
        if row.window:
            bases.append(int(one._page_base_win[0]))
    for leaf in row.state:
        left = np.asarray(one.kv[leaf])[:, 0]
        assert np.abs(left).max(axis=tuple(range(1, left.ndim))).min() > 0
    if row.window:
        assert max(bases) >= window_first_page(64, cfg.sliding_window, PS)
        assert one._page_base_win[0] == 0 and not one._page_table_win.any()
    resets = one.stats.get("state_resets")
    got = one.generate(second, 9)
    assert ("state_resets" in one.stats) == bool(row.state)
    if row.state:
        assert one.stats["state_resets"] == resets + 1
    assert worst_gap(block, one, second, got) < row.tol


def test_preemption_gives_the_uninterrupted_continuation(served):
    """A pool of 10 pages of 4 preempts: the sequence gives back its pages
    (of both groups), re-prefills from position 0, its slot's state
    unread, and continues as if never stopped: the tokens of an engine
    with room, and the reference's."""
    block, row, cfg, eng = served
    how = {**ENGINE, "page_size": 4, "max_seq_len": 32}
    small = InferenceEngine(cfg, eng.params, **{**how, "total_pages": 10})
    roomy = InferenceEngine(cfg, eng.params, **how)
    prompts = [list(range(1, 9)), list(range(3, 11))]
    rids = [small.add_request(p, 16) for p in prompts]
    done = run(small)
    assert small.stats["preemptions"] >= 1
    if row.state:
        # a re-prefill starts at position 0: its slot's state is not read
        assert small.stats["state_resets"] >= len(prompts) + 1
    for p, r in zip(prompts, rids):
        assert done[r] == roomy.generate(p, 16)
        assert worst_gap(block, small, p, done[r], pad_to=32) < row.tol
    if row.window:
        assert small.window_allocator.num_free \
            == small.window_allocator.total_pages - 1


def test_the_page_copy_leaves_state_and_window_leaves_alone(served):
    """The COW page copy moves a page of every page leaf of the full group
    (k, and v where the pool has one) and nothing a slot or the window
    group holds."""
    block, row, cfg, _ = served
    kv = make_kv_cache(cfg, 8, PS,
                       **(dict(max_batch=4) if row.state else {}),
                       **(dict(window_pages=5) if row.window else {}))
    assert set(kv) == ({"k"} if cfg.kv_lora_rank else {"k", "v"}) \
        | set(row.state) | (set(WINDOW_LEAVES) if row.window else set())
    kv = {k: jnp.arange(a.size, dtype=a.dtype).reshape(a.shape)
          for k, a in kv.items()}
    out = M._copy_page_body(dict(kv), jnp.int32(3), jnp.int32(5))
    assert set(out) == set(kv)
    for leaf in row.state + (WINDOW_LEAVES if row.window else ()):
        assert np.array_equal(np.asarray(out[leaf]), np.asarray(kv[leaf]))
    for leaf in set(kv) - set(row.state) - set(WINDOW_LEAVES):
        assert np.array_equal(np.asarray(out[leaf][:, 5]),
                              np.asarray(kv[leaf][:, 3]))
        assert np.array_equal(np.asarray(out[leaf][:, :5]),
                              np.asarray(kv[leaf][:, :5]))


def test_the_prefix_cache_hits_where_pages_are_the_only_state(served,
                                                              caplog):
    """Pages the only state: the same page-aligned prompt again takes a
    full hit, whose last token lands inside a shared page (copy on write),
    and a longer prompt with the same first pages a partial one: both the
    reference's. Otherwise no hit is taken (it would restore KV and not
    the state, or pages a window has freed): the engine says so ONCE at
    start-up, and every run matches the run with the cache off."""
    block, row, cfg, eng = served
    assert prefix_cache_supported(cfg) == row.prefix_cache
    rng = np.random.default_rng(3)
    base = rng.integers(0, cfg.vocab_size, 32).tolist()    # 4 whole pages
    with caplog.at_level(logging.WARNING, logger="ray_tpu.llm.engine"):
        on = InferenceEngine(cfg, eng.params, **ENGINE, prefix_cache=True)
    said = [r.message for r in caplog.records
            if "prefix cache off" in r.message]
    off = InferenceEngine(cfg, eng.params, **ENGINE, prefix_cache=False)
    want = off.generate(base, 6)
    assert on.generate(base, 6) == want
    before = dict(on.stats)
    assert on.generate(base, 6) == want
    assert worst_gap(block, on, base, want) < row.tol
    if not row.prefix_cache:
        assert on.prefix is None and len(said) == 1
        # ... and why: the window, or the bytes a slot's state takes
        assert "window" in said[0] if row.window \
            else f"({on.stats['state_bytes_per_slot']} bytes)" in said[0]
        assert on.stats["cached_tokens"] == on.stats["cow_copies"] == 0
        return
    assert not said
    assert on.stats["cached_tokens"] - before["cached_tokens"] == 31
    assert on.stats["cow_copies"] == before["cow_copies"] + 1
    longer = base + rng.integers(0, cfg.vocab_size, 13).tolist()
    before = dict(on.stats)
    got = on.generate(longer, 8)
    assert on.stats["cached_tokens"] - before["cached_tokens"] == 32
    assert worst_gap(block, on, longer, got) < row.tol


def test_training_side_and_tp_refuse_the_block_by_name(served):
    """forward, param_specs, num_params and validate_tp(cfg, 2) each refuse
    what they have not got and name it: every mechanism the configuration
    sets beyond theirs, as models/llama.py:mechanisms_beyond lists them
    (an untied head is llm/tp.py's too). The Llama/Mistral block: none
    refuses."""
    block, _, cfg, _ = served
    calls = {
        "models.llama.forward": lambda: llama.forward(
            None, jnp.zeros((1, 4), jnp.int32), cfg),
        "param_specs": lambda: llama.param_specs(cfg),
        "num_params": lambda: llama.num_params(cfg),
        "tp=2": lambda: tp.validate_tp(cfg, 2)}
    if block == "mistral":
        assert not llama.mechanisms_beyond(cfg)
        assert llama.num_params(cfg) > 0 and llama.param_specs(cfg)
        tp.validate_tp(cfg, 2)
        return
    for what, call in calls.items():
        found = llama.mechanisms_beyond(
            cfg, ("untied head",) if what == "tp=2" else ())
        assert found
        with pytest.raises(NotImplementedError) as e:
            call()
        said = str(e.value)
        assert what in said and llama.named(found) in said
        for mechanism, fields in found.items():
            assert mechanism in said and all(f in said for f in fields)
    # ... and the engine asks llm/tp.py before it builds anything sharded
    with pytest.raises(NotImplementedError, match="tp=2"):
        InferenceEngine(cfg, **ENGINE, tp=2)
