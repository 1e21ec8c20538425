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

That rule is a LATE decision: a launch held back behind a free slot is
made when the program in flight is due (its expected end less the host's
stretch), with whoever arrived by then. The suite's engines never clock a
program (tests/conftest.py), so the cases up to "the late decision" below
see the rule as it stands before a (kind, shape) has been seen; the cases
after it ask for the real estimate (``late_decision``) and run on a clock
and a device that the script moves (tests/_scripted_device.py).
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
from _scripted_device import ScriptedDevice

ENGINE = dict(page_size=8, total_pages=128, max_batch=4, max_seq_len=128,
              prefill_chunk=16, prefill_rows=2, decode_chunk=4)
COUNTERS = ("ahead_dispatches", "late_retired_rows", "ahead_drains",
            "held_launches", "late_launches", "late_mixed_launches")


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
           device=None, **settings):
    """Serve ``requests`` (request i added before step ``at[i]``, default
    all before the first; ``("hold", k)``: inside the k-th hold of
    ``device``, the scripted one its programs then run on) on a fresh
    engine of ``block`` (``prepare(eng)`` first, if given) and drain it.
    Every step's engine.step metadata goes to ``eng.metas``.
    Returns ([(tokens, finish reason, cached tokens)], engine)."""
    cfg, params = _block(block)
    eng = InferenceEngine(cfg, params, **{**ENGINE, **settings})
    eng._run_ahead = ahead
    _hold_idle_slots_blank(eng)
    if prepare is not None:
        prepare(eng)
    at = list(at or [0] * len(requests))
    rids, done, eng.metas = {}, {}, []

    def arrive(when):
        for i, (prompt, n_new) in enumerate(requests):
            if at[i] == when:
                rids[i] = eng.add_request(prompt, n_new)
    if device is not None:
        device.runs(eng)
        device.during_hold = lambda k: arrive(("hold", k))
    for step in range(4000):
        arrive(step)
        done.update(eng.step())
        eng.metas.append(eng._step_meta)
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


# ------------------------------------------------------- the late decision

#: blocks whose slot state or window tables a loop over a FREE slot walks
#: (blank: _hold_idle_slots_blank), and the plain block beside them
LATE_BLOCKS = sorted(name for name, block in BLOCKS.items()
                     if block.state or block.window) + ["mistral"]


def _held(eng):
    """(steps that came to the hold-back, those that launched behind the
    running flight, the mixed steps among them), from the steps' own
    metadata: what the three counters must say."""
    held = [m for m in eng.metas if m["held"]]
    late = [m for m in held if m["late"]]
    assert all(m["launched"] != "none" for m in late)
    assert not any(m["late"] for m in eng.metas if not m["held"])
    return (len(held), len(late),
            sum(m["launched"] == "mixed" for m in late))


def _late_equals_sync(block, requests, at, device, **settings):
    """Serve on the scripted device, deciding late, and one program at a
    time: the same tokens, reasons and free pages."""
    got, late = _serve(block, True, requests, at, device=device, **settings)
    plain = [when if isinstance(when, int) else 0 for when in at]
    want, sync = _serve(block, False, requests, plain, **settings)
    for i, (g, w) in enumerate(zip(got, want)):
        assert g == w, f"request {i}: late {g}, one at a time {w}"
    assert _free(late) == _free(sync)
    stats = late.stats
    assert (stats["held_launches"], stats["late_launches"],
            stats["late_mixed_launches"]) == _held(late)
    return late


@pytest.mark.parametrize("block", LATE_BLOCKS)
def test_a_held_launch_is_made_when_the_flight_is_due(
        block, late_decision, monkeypatch):
    """Three rows on four slots, then arrivals inside holds: once a kind
    of program has been clocked, a step that finds one in flight and only
    a decode loop to queue behind a FREE slot hands over, sleeps until the
    flight is due, admits again and launches behind it: a mixed step if a
    request came during the hold, else the loop, free slot or not (every
    descriptor's idle slots blank: _serve). The tokens are the synchronous
    engine's."""
    device = ScriptedDevice(monkeypatch)
    cfg, _ = _block(block)
    requests = _requests(7, 7, cfg.vocab_size, most_new=30)
    at = [0, 0, 0, ("hold", 1), ("hold", 3), ("hold", 3), 25]
    seen = []
    eng = _late_equals_sync(block, requests, at, device,
                            prepare=lambda eng: seen.append(_launches(eng)))
    held, late, late_mixed = _held(eng)
    assert late >= 8 and late_mixed == 2 and held > late
    # a loop was queued behind a running program with a slot free ...
    assert any(kind == "decode" and ahead and free
               for kind, ahead, free, _, _ in seen[0])
    # ... and every hold was a sleep by design: the wall of `wait`
    assert len(device.holds) >= late
    assert eng.stats["wall_ns_wait"] >= sum(ns for _, ns in device.holds)
    # where both ends of the flight were clocked, it ended when expected
    # (the scripted device runs a kind for a fixed time)
    assert {m["end_late_us"] for m in eng.metas
            if "end_late_us" in m} == {0.0}


def test_no_launch_on_an_empty_pipeline_once_the_kinds_are_clocked(
        late_decision, monkeypatch):
    """The point of it. Two rows of four decode for a long time: after
    the first loop was clocked (one drain), every loop is launched behind
    the one before it, and the device does not wait for the host again."""
    device = ScriptedDevice(monkeypatch)
    cfg, _ = _block("mistral")
    requests = [(prompt, 60) for prompt, _ in
                _requests(19, 2, cfg.vocab_size, longest=30)]
    got, eng = _serve("mistral", True, requests, device=device)
    loops = eng.stats["decode_dispatches"]
    assert loops >= 14
    # held: the mixed step's drain (no loop clocked yet), the first loop's
    # return unbooked is no hold; from then on every step holds and
    # launches late, but the last (nothing is left to launch)
    assert eng.stats["late_launches"] >= loops - 3
    assert eng.stats["held_launches"] - eng.stats["late_launches"] <= 3
    idle_at_the_start = device.idle_ns
    # the same again with no program ever clocked (the rule as it was):
    # every loop is launched and booked in one step, on an empty pipeline,
    # and the device waits a stretch before each
    device2 = ScriptedDevice(monkeypatch)
    monkeypatch.setattr(InferenceEngine, "_expected_end_ns",
                        lambda self, flight: None)
    _, drained = _serve("mistral", True, requests, device=device2)
    assert drained.stats["late_launches"] == 0 and not device2.holds
    assert drained.stats["decode_dispatches"] == loops
    assert device2.idle_ns > 4 * max(idle_at_the_start, 1)
    assert device2.idle_ns >= (loops - 1) * 6 * device2.TICK_NS


def test_a_flight_that_has_landed_is_booked_at_once_with_no_sleep(
        late_decision, monkeypatch):
    """Programs that take no time (the CPU's tiny ones, to the engine's
    eye), on an engine that believes they run for milliseconds: whenever
    a step looks, its flight has landed, whatever the estimate says. It
    never sleeps, launches nothing late, and books at once, as before."""
    device = ScriptedDevice(monkeypatch, run_ns={"mixed": 0, "decode": 0})
    cfg, _ = _block("mistral")
    requests = _requests(7, 6, cfg.vocab_size, most_new=30)

    def believe(eng):
        eng._program_ns.update({("mixed", 1): [4_000_000],
                                ("mixed", 2): [4_000_000],
                                ("decode", 0): [9_000_000]})
    eng = _late_equals_sync("mistral", requests, [0, 0, 0, 6, 9, 30],
                            device, prepare=believe)
    assert eng.stats["held_launches"] > 5
    assert eng.stats["late_launches"] == 0 and device.holds == []
    assert eng.stats["wall_ns_wait"] == 0


def test_a_program_never_clocked_is_booked_at_once_with_no_sleep(
        monkeypatch):
    """No ``late_decision``: the suite's engines (tests/conftest.py), and
    any engine before it has seen a (kind, shape) once. Nothing sleeps,
    nothing is launched behind a free slot: a program in flight with only
    a loop to follow it is booked at once (the hold-back, counted), and a
    loop is launched and booked in one step."""
    device = ScriptedDevice(monkeypatch)
    cfg, _ = _block("mistral")
    requests = _requests(7, 6, cfg.vocab_size, most_new=30)
    seen = []
    eng = _late_equals_sync(
        "mistral", requests, [0, 0, 0, 6, 9, 30], device,
        prepare=lambda eng: seen.append(_launches(eng)))
    assert eng.stats["held_launches"] >= 2
    assert eng.stats["late_launches"] == 0 and device.holds == []
    loops = [rest for kind, *rest in seen[0] if kind == "decode"]
    assert len(loops) >= 8
    assert not any(ahead and free for ahead, free, _, _ in loops)


def test_an_estimate_that_is_too_long_comes_down_in_a_few_programs(
        late_decision, monkeypatch):
    """The estimate fails: from one launch on a loop runs a third of the
    time the loops before it ran. The hold looks at the flight between
    its slices and ends with it; the launch is made all the same (the
    chip is idle), the booking keeps how long the flight can have run at
    most, and after a few such programs the lower quartile of the newest
    runs is the new time: the launches are late again."""
    device = ScriptedDevice(monkeypatch,
                            run_ns={"decode": 27_000_000})
    cfg, params = _block("mistral")
    requests = [(prompt, 90) for prompt, _ in
                _requests(19, 2, cfg.vocab_size, longest=30)]
    eng = InferenceEngine(cfg, params, **ENGINE)
    _hold_idle_slots_blank(eng)
    device.runs(eng)
    rids = [eng.add_request(prompt, n_new) for prompt, n_new in requests]
    done, metas, changed_at = {}, [], None
    while eng.has_work():
        if changed_at is None and eng.stats["late_launches"] == 4:
            device.run_ns["decode"] = 9_000_000     # from the next launch
            changed_at = len(metas)
        done.update(eng.step())
        metas.append(eng._step_meta)
    # before the change every hold of a loop was three slices long ...
    slices = [ns for _, ns in device.holds]
    assert slices.count(10_000_000) >= 6
    # ... after it the flight lands inside the first slice: the step
    # launches behind a landed flight (held, not late), a few times
    missed = [i for i, m in enumerate(metas)
              if i > changed_at and m["held"] and not m["late"]
              and m["launched"] == "decode"]
    assert 1 <= len(missed) <= 4
    assert all(m["held"] and m["late"] for m in metas[missed[-1] + 1:-1])
    assert len(metas) - missed[-1] > 6
    runs = sorted(eng._program_ns["decode", 0])
    assert runs[len(runs) // 4] <= 9_100_000
    # the device waited for the host at each miss, and only there
    want, _ = _serve("mistral", False, requests)
    assert [(done[r], eng.finish_reason(r)) for r in rids] \
        == [(tokens, reason) for tokens, reason, _ in want]


def test_a_waiter_at_a_full_batch_still_drains(late_decision, monkeypatch):
    """Every slot taken, a fifth request waiting, a row ending by length
    in the flight: no loop is queued behind it and nothing is held (the
    hold-back is for a slot that is free while NOBODY waits): the booking
    frees the slot and the request takes it in the next step, on an empty
    pipeline, as before."""
    device = ScriptedDevice(monkeypatch)
    cfg, params = _block("mistral")
    requests = [(prompt, n_new) for (prompt, _), n_new in zip(
        _requests(23, 5, cfg.vocab_size, 16), (19, 33, 33, 41, 9))]
    eng = InferenceEngine(cfg, params, **ENGINE)
    device.runs(eng)
    seen = _launches(eng)
    for prompt, n_new in requests:
        eng.add_request(prompt, n_new)
    admitted_at = None
    while eng.has_work():
        eng.step()
        if admitted_at is None and not eng.waiting:
            admitted_at = len(seen) - 1
            assert eng.stats["held_launches"] == 0 and not device.holds
    loops = [rest for kind, *rest in seen[:admitted_at] if kind == "decode"]
    assert sum(ahead for ahead, *_ in loops) >= 2
    assert all(all_on for ahead, _, all_on, _ in loops if ahead)
    assert seen[admitted_at][:2] == ("mixed", False)
    # later, slots free and nobody waiting: the late decision
    assert eng.stats["late_launches"] > 0
    _late_equals_sync("mistral", requests, [0] * 5,
                      ScriptedDevice(monkeypatch))


def test_the_hook_is_called_once_and_before_the_hold(late_decision,
                                                     monkeypatch):
    """after_dispatch in a step that holds: once, BEFORE the sleep (the
    finished rows' clients can only come back after the hand-over), and
    not again after the launch the step then makes."""
    device = ScriptedDevice(monkeypatch)
    cfg, params = _block("mistral")
    eng = InferenceEngine(cfg, params, **ENGINE)
    device.runs(eng)
    for prompt, _ in _requests(19, 2, cfg.vocab_size, longest=30):
        eng.add_request(prompt, 40)
    calls = []
    device.during_hold = lambda k: calls.append("hold")
    slept = 0
    while eng.has_work():
        n = len(calls)
        eng.step(lambda: calls.append("hook"))
        new = calls[n:]
        meta = eng._step_meta
        assert new.count("hook") == (
            meta["launched"] != "none" or meta["kind"] != "none")
        if "hold" in new:
            assert new == ["hook", "hold"]
            slept += 1
    assert slept >= 5 and slept == len(device.holds)    # one slice each


@pytest.mark.parametrize("block", ["mistral", "lfm2", "mimo"])
def test_the_real_clocks_serve_the_synchronous_engines_tokens(
        block, late_decision):
    """Nothing scripted: the machine's clock, the backend's is_ready, a
    real sleep. Which steps hold and which find their flight landed is
    this machine's timing; the tokens, the reasons and the free pages are
    not, and the three counters stay consistent."""
    cfg, _ = _block(block)
    requests = _requests(7, 9, cfg.vocab_size, most_new=40)
    got, eng, _ = _both(block, requests, [0, 0, 0, 3, 8, 8, 20, 40, 41])
    stats = eng.stats
    assert stats["held_launches"] >= stats["late_launches"] \
        >= stats["late_mixed_launches"] >= 0
    assert (stats["held_launches"], stats["late_launches"],
            stats["late_mixed_launches"]) == _held(eng)


def test_late_launch_pct_reads_the_two_counters(late_decision, monkeypatch):
    """The per-layer metric of the late decision, found by what it reads
    (tests/_readings.py): late_launches over held_launches of the window,
    in the cells dispatch_ahead_pct is read in; nothing, not an error, for
    a program without the counters (the parent of the PR that brought
    them)."""
    from _readings import entry

    cell = "reason-lfm2-1chip"
    got, args, reader = entry("engine_clocks", cell, num=["late_launches"])
    ahead, _, _ = entry("engine_clocks", cell, num=["ahead_dispatches"])
    assert args["den"] == ["held_launches"] and args["scale"] == 100
    for key in ("unit", "better", "source", "layer", "moves", "workloads"):
        assert got[key] == ahead[key], key
    assert got["source"] == "program_counter" and got["unit"] == "%"
    device = ScriptedDevice(monkeypatch)
    cfg, params = _block("mistral")
    eng = InferenceEngine(cfg, params, **ENGINE)
    device.runs(eng)
    for prompt, _ in _requests(19, 2, cfg.vocab_size, longest=30):
        eng.add_request(prompt, 40)
    for _ in range(6):
        eng.step()
    before = dict(eng.stats)
    while eng.has_work():
        eng.step()
    after = dict(eng.stats)
    held, late = (after[k] - before[k]
                  for k in ("held_launches", "late_launches"))
    assert held > late >= 5
    data = {"stats_open": before, "stats_close": after, "config": {},
            "window_s": 1.0}
    assert reader.read(data, args) == pytest.approx(100.0 * late / held)
    old = {k: {s: v for s, v in data[k].items() if "_launches" not in s}
           for k in ("stats_open", "stats_close")}
    assert reader.read(dict(old, config={}), args) is None
