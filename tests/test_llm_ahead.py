"""The engine that runs one program ahead serves the tokens of the one that
does not (llm/engine.py: step, _launch, _take_off, _book).

With a program in flight the engine packs the next one from what the
launched ones WILL leave (positions, pages, who ends by length) and hands
each row's newest token on to it on the device; only the values wait for
the booking. Float32 on the CPU, greedy: whatever the order of launches
and bookings, every request's tokens, finish reason and cached tokens are
those of the same engine held to one program at a time (``_run_ahead``
False, the order every engine had before), in every block the engine
serves (the tiny blocks of tests/_blocks.py), or
something is booked wrong. The cases the late booking has to get right:
a row that stops on EOS after its next program was packed, a pool that
cannot serve the look-ahead, a copy-on-write admission beside a program
in flight, the window group at its size; and the rule that keeps a decode
loop from being queued where an arrival would have to wait for it.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import model as M
from ray_tpu.llm.cache import SCRATCH_PAGE
from ray_tpu.llm.engine import InferenceEngine
from ray_tpu.models.llama import init_params
from _blocks import BLOCKS, config

ENGINE = dict(page_size=8, total_pages=128, max_batch=4, max_seq_len=128,
              prefill_chunk=16, prefill_rows=2, decode_chunk=4)
COUNTERS = ("ahead_dispatches", "late_retired_rows", "ahead_drains")


@functools.lru_cache(maxsize=None)
def _block(block: str):
    cfg = config(block, dtype=jnp.float32)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _requests(seed: int, n: int, vocab: int, longest: int = 50,
              most_new: int = 14):
    """[(prompt, max_new_tokens)]: prompts of one to several chunks of 16,
    budgets that end inside a decode loop of 4 as often as at its end."""
    rng = np.random.default_rng(seed)
    return [(rng.integers(1, vocab, int(rng.integers(3, longest))).tolist(),
             int(rng.integers(1, most_new))) for _ in range(n)]


def _hold_idle_slots_blank(eng):
    """From now on every descriptor ``eng`` packs is held to this: a slot
    that is no decode row of the program (free; prefilling; or held by a
    sequence that has ended and whose last program is still in flight)
    names the scratch page only, its compact window table from base 0.
    The decode loop runs such a slot at length 1 through its tables, and
    on the chip a table that does not fit the length halts the device
    (PERF.md, PR 59): the CPU references clamp, so nothing else here
    would notice."""
    def held(pack, layout_of):
        def packing(active, *rest):
            buf = pack(active, *rest)
            f = M.cut(buf, layout_of(*rest))
            idle = sorted(set(range(eng.max_batch))
                          - {slot for slot, _ in active})
            assert (f["page_table"][idle] == SCRATCH_PAGE).all()
            if eng._window:
                assert (f["page_table_win"][idle] == SCRATCH_PAGE).all()
                assert (f["page_base_win"][idle] == 0).all()
            return buf
        return packing
    eng._pack_decode = held(eng._pack_decode,
                            lambda: eng._fns.decode_layout)
    eng._pack_mixed = held(eng._pack_mixed,
                           lambda rows, n: eng._fns.step_layouts[n])


def _serve(block: str, ahead: bool, requests, at=None, prepare=None,
           **settings):
    """Serve ``requests`` (request i added before step ``at[i]``, default
    all before the first) on a fresh engine of ``block`` (``prepare(eng)``
    first, if given) and drain it.
    Returns ([(tokens, finish reason, cached tokens)], engine)."""
    cfg, params = _block(block)
    eng = InferenceEngine(cfg, params, **{**ENGINE, **settings})
    eng._run_ahead = ahead
    _hold_idle_slots_blank(eng)
    if prepare is not None:
        prepare(eng)
    at = list(at or [0] * len(requests))
    rids, done = {}, {}
    for step in range(4000):
        for i, (prompt, n_new) in enumerate(requests):
            if at[i] == step:
                rids[i] = eng.add_request(prompt, n_new)
        done.update(eng.step())
        if len(rids) == len(requests) and not eng.has_work():
            break
    assert not eng.has_work() and eng._flight is None
    assert all(slot is None for slot in eng._slots)
    if not ahead:
        assert not any(eng.stats[k] for k in COUNTERS)
    return [(done[rids[i]], eng.finish_reason(rids[i]),
             eng.cached_tokens(rids[i])) for i in range(len(requests))], eng


def _free(eng):
    """Pages free in each group: what a leak would lower."""
    return (eng.allocator.num_free,
            eng.window_allocator and eng.window_allocator.num_free)


def _both(block, requests, at=None, **settings):
    """The two engines' results, held equal: (the results, the engine
    that ran ahead, the one held to one program at a time)."""
    got, ahead = _serve(block, True, requests, at, **settings)
    want, sync = _serve(block, False, requests, at, **settings)
    for i, (g, w) in enumerate(zip(got, want)):
        assert g == w, f"request {i}: ahead {g}, one at a time {w}"
    assert _free(ahead) == _free(sync)
    if ahead.window_allocator is not None:
        assert ahead.window_allocator.num_free \
            == ahead.window_allocator.total_pages - 1
    return got, ahead, sync


@pytest.mark.parametrize("block", sorted(BLOCKS))
def test_every_block_serves_the_synchronous_engines_tokens(block):
    """More requests than slots, prompts over several chunks, budgets that
    end inside a decode loop, arrivals beside running rows: mixed steps
    behind mixed steps, decode loops behind decode loops with every slot
    taken, rows that end by length inside a program in flight."""
    cfg, _ = _block(block)
    requests = _requests(7, 11, cfg.vocab_size)
    at = [0, 0, 0, 0, 0, 0, 2, 5, 5, 9, 30]
    got, ahead, sync = _both(block, requests, at)
    assert ahead.stats["ahead_dispatches"] > 2
    assert ahead.stats["late_retired_rows"] == 0        # no EOS
    # the same dispatches' worth of work, however they were cut
    for key in ("prefill_tokens", "preemptions", "cached_tokens"):
        assert ahead.stats[key] == sync.stats[key], key
    assert [len(tokens) for tokens, _, _ in got] \
        == [n_new for _, n_new in requests]


def _an_eos(block, requests):
    """A token several requests emit past their first: as EOS it stops
    them in the middle of a decode loop."""
    streams = [tokens for tokens, _, _ in _serve(block, False, requests)[0]]
    late = [t for tokens in streams for t in tokens[2:]]
    return max(set(late), key=late.count)


def test_a_row_that_stops_on_eos_is_retired_a_program_late():
    """EOS is a value: the engine learns it when the program is booked,
    with the next one packed and the row in it. The request is delivered
    at the first booking with the tokens up to EOS, what the next program
    computed for it is dropped, and its slot and pages come back when
    that one is booked: none leaks."""
    requests = _requests(3, 12, 256, most_new=24)
    eos = _an_eos("mistral", requests)
    got, ahead, sync = _both("mistral", requests, eos_token=eos)
    reasons = [reason for _, reason, _ in got]
    assert reasons.count("stop") >= 2 and "length" in reasons
    assert ahead.stats["late_retired_rows"] > 0
    assert ahead.stats["ahead_dispatches"] > 0
    assert sync.stats["late_retired_rows"] == 0


def test_a_first_token_that_is_eos_ends_a_row_of_the_next_program():
    """The token a prompt's last chunk samples is EOS: by then the
    sequence is a decode row of the program behind."""
    requests = _requests(5, 8, 256, most_new=10)
    firsts = [tokens[0] for tokens, _, _ in
              _serve("mistral", False, requests)[0]]
    late = 0
    for i, eos in enumerate(firsts):
        got, eng = _serve("mistral", True, requests, eos_token=eos)
        assert got[i][:2] == ([], "stop")
        if eng.stats["late_retired_rows"]:
            late += 1
            _both("mistral", requests, eos_token=eos)
    assert late >= 2


def test_a_pool_too_small_for_the_look_ahead_drains_and_preempts_in_turn():
    """The pool cannot give every row its next page: with a program in
    flight the engine preempts nobody (it does not have the tokens a
    preemption folds into the prompt), books that program and goes on one
    program at a time, where it preempts as the synchronous engine
    does."""
    requests = _requests(11, 8, 256, longest=40, most_new=30)
    _, ahead, sync = _both("mistral", requests, total_pages=14,
                           prefix_cache=False)
    assert ahead.stats["ahead_drains"] > 0
    assert ahead.stats["preemptions"] > 0 and sync.stats["preemptions"] > 0
    assert ahead.stats["ahead_dispatches"] > 0


def test_a_copy_on_write_admission_beside_a_program_in_flight():
    """A prompt whose every page is cached is admitted while a program
    runs: the page copy and the chunk that writes into the copy queue
    behind that program on the device; the hit is the synchronous
    engine's."""
    long_ones = [(p + p, n) for p, n in
                 _requests(13, 3, 256, longest=50, most_new=15)]
    hit = (list(range(1, 33)), 6)           # four whole pages
    requests = [hit] + long_ones + [hit, hit]
    at = [0, 40, 40, 40, 43, 45]
    copies = []     # (the engine runs ahead, a program was in flight)

    def watch_copies(eng):
        copy = eng._fns.copy_page

        def copying(*a):
            copies.append((eng._run_ahead, eng._flight is not None))
            return copy(*a)
        eng._fns.copy_page = copying
    got, ahead, sync = _both("mistral", requests, at, prepare=watch_copies)
    assert ahead.stats["cow_copies"] == sync.stats["cow_copies"] == 2
    # every page cached: the last token is left to compute, in the copy
    assert got[4][2] == got[5][2] == 31 and got[0][2] == 0
    assert got[4][0] == got[5][0] == got[0][0]
    # ... and one of the copies at least was queued behind a program
    assert len(copies) == 4 and (True, True) in copies
    assert (False, True) not in copies


@pytest.mark.parametrize("block", ["mimo", "trinity"])
def test_the_window_group_at_its_size_serves_the_look_ahead_or_drains(block):
    """The window group holds what one program's rows need and no more
    (llm/cache.py: window_group_pages). A program launched ahead takes
    its rows' pages before the one in flight has given back those behind
    the window: where the group cannot spare them the engine books first
    instead of raising. Every slot taken, contexts several windows long."""
    cfg, _ = _block(block)
    requests = _requests(17, 10, cfg.vocab_size, longest=90, most_new=30)
    _, ahead, sync = _both(block, requests, decode_chunk=8)
    assert ahead.stats["ahead_dispatches"] > 0
    assert ahead.stats["window_pages_freed"] \
        == sync.stats["window_pages_freed"] > 0


def _launches(eng):
    """Record, for every program ``eng`` launches from now on, (its kind,
    whether a program was in flight, whether a slot was free, whether
    every slot held a sequence that goes on after the one in flight,
    whether a request waited)."""
    seen, take_off = [], eng._take_off

    def recording(active, rows, n_rows, ahead):
        assert ahead == (eng._flight is not None)
        seen.append(("mixed" if rows else "decode", ahead,
                     None in eng._slots,
                     all(map(eng._goes_on, eng._slots)),
                     bool(eng.waiting)))
        return take_off(active, rows, n_rows, ahead)
    eng._take_off = recording
    return seen


def test_no_decode_loop_is_queued_while_a_slot_is_free():
    """Rule 4. Two rows of four decode, nothing prefills: a request that
    arrived now would be admitted at the next step, so no decode loop is
    launched with a program unbooked. The mixed steps of a prompt of
    several chunks are."""
    cfg, params = _block("mistral")
    eng = InferenceEngine(cfg, params, **ENGINE)
    seen = _launches(eng)
    for prompt, _ in _requests(19, 2, cfg.vocab_size, longest=60):
        eng.add_request(prompt + prompt, 40)
    while eng.has_work():
        eng.step()
    loops = [ahead for kind, ahead, *_ in seen if kind == "decode"]
    assert len(loops) >= 8 and not any(loops)
    assert any(ahead for kind, ahead, *_ in seen if kind == "mixed")
    assert eng.stats["ahead_dispatches"] \
        == sum(ahead for _, ahead, *_ in seen) > 0


def test_decode_loops_queue_with_every_slot_taken_until_one_is_free():
    """Every slot taken and nobody waiting: decode loops run one behind
    the other, also behind a program in which a row ends by length (no
    request is there to take its slot when that is booked); none once a
    slot is free."""
    cfg, params = _block("mistral")
    eng = InferenceEngine(cfg, params, **ENGINE)
    seen = _launches(eng)
    for (prompt, _), n_new in zip(_requests(23, 4, cfg.vocab_size, 16),
                                  (19, 33, 33, 41)):
        eng.add_request(prompt, n_new)
    while eng.has_work():
        eng.step()
    loops = [rest for kind, *rest in seen if kind == "decode"]
    assert sum(ahead for ahead, *_ in loops) >= 3
    assert not any(ahead and free for ahead, free, _, _ in loops)
    # ... one of them behind the program that ended the first row
    assert any(ahead and not all_on for ahead, _, all_on, _ in loops)
    freed = next(i for i, (_, free, _, _) in enumerate(loops) if free)
    assert not any(ahead for ahead, *_ in loops[freed:])
    assert len(loops) - freed >= 3


def test_no_decode_loop_is_queued_over_a_request_that_waits_for_a_slot():
    """Every slot taken and a fifth request waiting: a row that ends by
    length inside the program in flight gives its slot back when that is
    booked, and the request takes it in the next step. No decode loop is
    queued behind such a program: the request would wait a loop longer
    than with one program at a time. The same tokens either way."""
    cfg, params = _block("mistral")
    requests = [(prompt, n_new) for (prompt, _), n_new in zip(
        _requests(23, 5, cfg.vocab_size, 16), (19, 33, 33, 41, 9))]
    eng = InferenceEngine(cfg, params, **ENGINE)
    seen = _launches(eng)
    for prompt, n_new in requests:
        eng.add_request(prompt, n_new)
    admitted_at = None
    while eng.has_work():
        eng.step()
        if admitted_at is None and not eng.waiting:
            admitted_at = len(seen) - 1         # the launch that took it
    loops = [rest for kind, *rest in seen[:admitted_at]
             if kind == "decode"]
    assert all(waiting for *_, waiting in loops) and len(loops) >= 3
    assert sum(ahead for ahead, *_ in loops) >= 2
    assert all(all_on for ahead, _, all_on, _ in loops if ahead)
    # the program after the one that freed the slot is the mixed step
    # that prefills the fifth request, launched on an empty pipeline
    assert seen[admitted_at][:2] == ("mixed", False)
    _both("mistral", requests)


def test_forced_synchronous_order_books_what_it_launched():
    """``_run_ahead`` False: every step that launches books its own
    program, as before the engine ran ahead."""
    cfg, params = _block("mistral")
    eng = InferenceEngine(cfg, params, **ENGINE)
    eng._run_ahead = False
    for prompt, n_new in _requests(29, 6, cfg.vocab_size):
        eng.add_request(prompt, n_new)
    while eng.has_work():
        before = dict(eng.stats)
        eng.step()
        assert eng._flight is None
        assert eng.stats["h2d_arrays"] - before["h2d_arrays"] == 1
    assert not any(eng.stats[k] for k in COUNTERS)
