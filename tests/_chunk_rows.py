"""Cases of the mixed step's chunk-row deal (llm/engine.py:
_deal_chunk_rows), written once and run by each block's test file on its
own tiny engines: one with prefill_rows 1 (a sequence's chunks one a step,
what the engine did before rows were joined) and one with prefill_rows 2,
the same weights, page_size 8 and prefill_chunk 16. Float32 on the CPU, so
the two engines give the same greedy tokens or something is wrong: which
step a row rides in must not change what it computes.

And cases of the SHAPE a mixed step runs in (llm/engine.py:
_launch): the smallest of the seam's compiled shapes that holds
the rows dealt. serve() holds every mixed step of every case, old and new,
to that rule and the books to the shapes run; SHAPE_CASES feed an engine
with two chunk rows one-row and two-row steps in turn and hold it, token
for token, to the same engine pinned to its full shape (the one static
shape every mixed step ran in before the set existed).

And the DESCRIPTOR a dispatch sends (llm/engine.py: _descriptor_turns; the
layouts are llm/model.py's): one flat int32 buffer filled in place, cut by
the program. reference_mixed / reference_decode are the packing the engine
did before (PR 42's _pack_mixed and _upload_decode: fresh arrays a step,
a Python loop over the decode rows), kept here as the plain reference;
check_descriptor holds every field of every descriptor of a scripted mix
to them byte for byte, and the served tokens to an engine that dispatches
the reference's arrays. fields_of cuts a dispatched descriptor for the
block files that read what a step was sent.

Not a test file: tests/test_llm.py (per-head pool) and
tests/test_llm_kanana.py (latent pool) parametrise over CASES;
tests/test_llm_lfm2.py, tests/test_llm_granite.py and
tests/test_llm_brumby.py (recurrent state) hold their engines to one row a
sequence; all of them, and tests/test_llm_moe.py, over SHAPE_CASES and
through check_descriptor.
"""

import collections

import numpy as np

from ray_tpu.llm import model as M
from ray_tpu.llm.cache import SCRATCH_PAGE

CHUNK = 16
CASES = ("alone-2", "alone-3", "alone-5", "prefix-hit", "copy-on-write",
         "together", "budget")
SHAPE_CASES = ("lone", "pair", "in-turn")


def watch(eng):
    """Record every deal of ``eng`` from now on: [[(rid, start, n)]], one
    entry a mixed step."""
    deals, deal = [], eng._deal_chunk_rows

    def recording():
        rows = deal()
        if rows:
            deals.append([(s.request_id, start, n) for s, start, n in rows])
        return rows
    eng._deal_chunk_rows = recording
    return deals


def spans(deals):
    """The deals without their request ids: [[(start, n)]]."""
    return [[(start, n) for _, start, n in d] for d in deals]


def pin_full_shape(eng):
    """Hold ``eng`` to ONE mixed-step shape, its full one, whatever it is
    dealt: what every engine did before a step chose among shapes."""
    eng._fns.row_shapes = (eng.prefill_rows,)
    return eng


def shape_of(eng, deal):
    """(chunk rows, rows, token slots) of the shape ``eng`` must run
    ``deal`` in: the smallest of its seam's that holds the deal's rows."""
    n = min(r for r in eng._fns.row_shapes if r >= len(deal))
    return n, eng.max_batch + n, eng.max_batch + n * eng.prefill_chunk


def serve(eng, prompts, n_new=6, at=None):
    """Add the prompts (together, or prompt i before step ``at[i]``),
    drain the engine, and check each mixed step's books against its deal
    and the arrays it dispatched against the shape the deal asks for.
    The engine is held to ONE program at a time (_run_ahead False): a
    step's books are then its own deal's, which is what is checked here;
    tests/test_llm_ahead.py holds the engine that runs ahead to this one's
    tokens. Returns ([tokens a prompt], deals)."""
    eng._run_ahead = False
    deals = watch(eng)
    before = dict(eng.stats)
    at = list(at or [0] * len(prompts))
    rids, dispatched, run = {}, [], eng._fns.ragged_step

    def recording(params, desc, *rest):
        f = fields_of(eng, desc)
        dispatched.append((f["page_table"].shape[0], f["tokens"].shape[0]))
        return run(params, desc, *rest)
    eng._fns.ragged_step = recording
    done, seen, slots = {}, 0, []
    for step in range(400):
        for i, p in enumerate(prompts):
            if at[i] == step:
                rids[i] = eng.add_request(list(p), n_new)
        done.update(eng.step())
        if len(deals) > seen:                   # this step was a mixed one
            seen = len(deals)
            meta = eng._step_meta               # the engine.step span's
            assert meta["kind"] == "mixed"
            assert meta["real_tokens"] - meta["decode_rows"] \
                == sum(n for _, _, n in deals[-1])
            n, R, T = shape_of(eng, deals[-1])
            assert dispatched[-1] == (R, T) and len(dispatched) == seen
            assert meta["slot_tokens"] == T
            slots.append((n, T))
        if len(rids) == len(prompts) and not eng.has_work():
            break
    del eng._deal_chunk_rows                    # the class's again
    eng._fns.ragged_step = run
    rids = [rids[i] for i in range(len(prompts))]
    assert set(rids) <= set(done)
    rows = [r for d in deals for r in d]
    joined = sum(len(d) - len({rid for rid, _, _ in d}) for d in deals)
    got = {k: eng.stats[k] - before[k] for k in (
        "chunk_rows", "chunk_rows_joined", "prefill_tokens",
        "ragged_dispatches", "ragged_small_dispatches",
        "ragged_slot_tokens")}
    assert got == {"chunk_rows": len(rows), "chunk_rows_joined": joined,
                   "prefill_tokens": sum(n for _, _, n in rows),
                   "ragged_dispatches": len(deals),
                   "ragged_small_dispatches": sum(
                       n < eng.prefill_rows for n, _ in slots),
                   "ragged_slot_tokens": sum(T for _, T in slots)}
    budget = eng.step_token_budget or 1 << 30
    for d in deals:
        assert len(d) <= eng.prefill_rows
        assert sum(n for _, _, n in d) <= budget
        ends = {}
        for rid, start, n in d:
            assert 0 < n <= eng.prefill_chunk
            if rid in ends:                     # a joined row: where the
                assert start == ends[rid]       # last one ended, on a
                assert start % eng.page_size == 0       # page's edge
            ends[rid] = start + n
    return [done[r] for r in rids], deals


def check(case, one, two, vocab=256):
    """Run ``case`` on both engines (``one``: prefill_rows 1, ``two``:
    prefill_rows 2) and hold the second to the first and to the deal the
    case expects."""
    rng = np.random.default_rng(sum(map(ord, case)))
    new = lambda n: rng.integers(0, vocab, n).tolist()       # noqa: E731
    if case.startswith("alone-"):
        # 2, 3 (the last one short) and 5 chunks, nobody else prefilling:
        # two chunks a step, so half the mixed steps, rounded up
        n = {"2": 32, "3": 45, "5": 80}[case[-1]]
        prompt = new(n)
        (want,), d1 = serve(one, [prompt])
        (got,), d2 = serve(two, [prompt])
        assert got == want
        chunks = -(-n // CHUNK)
        assert (len(d1), len(d2)) == (chunks, -(-chunks // 2))
    elif case == "prefix-hit":
        # a hit of 3 pages: the tail starts at 24, a page's edge past 0
        # that is no multiple of the chunk, and its rows join there
        base = new(40)
        prompt = base[:24] + new(50)
        for eng in (one, two):
            serve(eng, [base])
        hits = two.stats["cached_tokens"]
        (want,), _ = serve(one, [prompt])
        (got,), deals = serve(two, [prompt])
        assert got == want
        assert two.stats["cached_tokens"] - hits == 24
        assert spans(deals) == [
            [(24, 16), (40, 16)], [(56, 16), (72, 2)]]
    elif case == "copy-on-write":
        # every page of the prompt cached: ONE token is left to compute,
        # inside the copied page; there is no second row to give
        prompt = new(32)
        for eng in (one, two):
            serve(eng, [prompt])
        cows = two.stats["cow_copies"]
        (want,), _ = serve(one, [prompt])
        (got,), deals = serve(two, [prompt])
        assert got == want
        assert two.stats["cow_copies"] == cows + 1
        assert spans(deals) == [[(31, 1)]]
    elif case == "together":
        # two prompts that arrive together get one row each for as long
        # as both prefill: the shorter one is not a step later than with
        # one row a sequence; the longer one's tail then takes both rows
        prompts = [new(75), new(30)]
        want, _ = serve(one, prompts[:1])
        want += serve(one, prompts[1:])[0]
        got, deals = serve(two, prompts)
        assert got == want
        assert spans(deals) == [
            [(0, 16), (0, 16)], [(16, 16), (16, 14)],
            [(32, 16), (48, 16)], [(64, 11)]]
    elif case == "budget":
        # a budget of 20: the second row is cut to 4 tokens and ends
        # inside a page, so the next step's row starts there and, ending
        # inside a page too, stays alone; serve() holds every step to the
        # budget and every joined row to a page's edge
        prompt = new(70)
        budgets = [eng.step_token_budget for eng in (one, two)]
        one.step_token_budget = two.step_token_budget = 20
        try:
            (want,), _ = serve(one, [prompt])
            (got,), deals = serve(two, [prompt])
        finally:
            one.step_token_budget, two.step_token_budget = budgets
        assert got == want
        assert spans(deals) == [
            [(0, 16), (16, 4)], [(20, 16)], [(36, 16)], [(52, 16)],
            [(68, 2)]]
    else:
        raise ValueError(case)


def check_shapes(case, shaped, full, vocab=256):
    """Run ``case`` on ``shaped`` (prefill_rows 2: a step runs the
    one-row shape or the two-row one) and on ``full`` (the same weights
    and settings behind pin_full_shape): the same deals, the same tokens,
    and each step of ``shaped`` in the shape its deal asks for, which
    serve() checks."""
    assert shaped.prefill_rows == full.prefill_rows == 2
    assert shaped._fns.row_shapes == (1, 2) and full._fns.row_shapes == (2,)
    rng = np.random.default_rng(sum(map(ord, case)) + 1)
    new = lambda n: rng.integers(0, vocab, n).tolist()       # noqa: E731
    if case == "lone":
        # a prompt of one chunk, nobody else: ONE row, the small shape
        prompts, at, rows = [new(11)], None, [1]
    elif case == "pair":
        # two one-chunk prompts together: both rows, the full shape
        prompts, at, rows = [new(9), new(14)], None, [2]
    elif case == "in-turn":
        # arrivals while others decode: full and small steps in turn,
        # beside decode rows
        prompts = [new(40), new(10), new(12), new(7), new(20)]
        at, rows = [0, 0, 3, 6, 6], None
    else:
        raise ValueError(case)
    want, d_full = serve(full, prompts, at=at)
    small = shaped.stats["ragged_small_dispatches"]
    got, deals = serve(shaped, prompts, at=at)
    assert got == want and deals_of(deals) == deals_of(d_full)
    n_small = shaped.stats["ragged_small_dispatches"] - small
    assert n_small == sum(len(d) == 1 for d in deals)
    if rows is None:                            # both shapes, in turn
        assert 0 < n_small < len(deals)
    else:
        assert [len(d) for d in deals] == rows
    assert full.stats["ragged_small_dispatches"] == 0


def deals_of(deals):
    """The deals with each request id replaced by its order of arrival."""
    order = {}
    return [[(order.setdefault(rid, len(order)), start, n)
             for rid, start, n in d] for d in deals]


def check_state_keeps_one_row(eng):
    """``eng``: a block with conv or state-space layers and two chunk
    rows. A chunk row starts from its slot's state and stores it at its
    end: two rows of one slot in one step would both start from the old
    state, so the deal stays one row a sequence whatever rows are free."""
    assert eng._has_state and eng.prefill_rows == 2
    _, deals = serve(eng, [list(range(7, 47))], n_new=3)
    assert spans(deals) == [[(0, 16)], [(16, 16)], [(32, 8)]]
    assert eng.stats["chunk_rows_joined"] == 0 < eng.stats["chunk_rows"]


def check_preempted(make):
    """``make(prefill_rows=n, **pool)`` builds the block's engine. A pool
    too small for two sequences preempts one; its re-prefill of prompt +
    generated tokens, folded into one prompt of several chunks, takes
    both rows when it prefills alone and continues as the engine with
    one row a sequence does (which continues as the uninterrupted one:
    each block's own preemption test)."""
    # no prefix cache: the re-prefill computes every token again, from 0
    pool = dict(page_size=4, total_pages=10, max_seq_len=32, prefill_chunk=8,
                prefix_cache=False)
    prompts = [list(range(1, 9)), list(range(3, 11))]
    want, _ = serve(make(prefill_rows=1, **pool), prompts, n_new=16)
    two = make(prefill_rows=2, **pool)
    got, deals = serve(two, prompts, n_new=16)
    assert got == want
    assert two.stats["preemptions"] >= 1
    # past the first step, two rows from 0 on are a folded re-prefill
    refilled = [d for d in deals[1:] if len(d) == 2
                and d[0][0] == d[1][0] and d[0][1] == 0]
    assert refilled, deals


# ------------------------------------------------------- the descriptor


def fields_of(eng, desc):
    """{field: numpy array} of a descriptor ``eng`` dispatched (a device
    array or the host's buffer), by the layout of its length."""
    desc = np.asarray(desc)
    return M.cut(desc, M.layout_of(desc, (
        eng._fns.decode_layout, *eng._fns.step_layouts.values())))


def as_descriptor(**fields):
    """(desc, layout) of arrays handed over by name, in the order given:
    a step program's inputs for a test that makes its own."""
    fields = {n: np.asarray(a, np.int32) for n, a in fields.items()}
    return (np.concatenate([a.reshape(-1) for a in fields.values()]),
            tuple((n, a.shape) for n, a in fields.items()))


def reference_mixed(eng, active, rows, n_rows):
    """The mixed step's arrays as the engine packed them before it kept a
    descriptor: fresh arrays, a decode row at a time."""
    ps = eng.page_size
    R, Tcap = eng._mixed_shape(n_rows)
    f = {name: np.zeros(Tcap, np.int32)
         for name in ("tokens", "token_pos", "token_slot")}
    f["token_page"] = np.full(Tcap, SCRATCH_PAGE, np.int32)
    f.update({name: np.zeros(R, np.int32)
              for name in ("q_start", "q_len", "kv_len")})
    # the slot whose newest token a row's sampled token is: none's
    f["newest_slot"] = np.full(R, eng.max_batch, np.int32)
    f["page_table"] = np.full((R, eng.max_pages_per_seq), SCRATCH_PAGE,
                              np.int32)
    token_state = np.full(Tcap, eng.max_batch, np.int32)
    f["q_start"][:eng.max_batch] = np.arange(eng.max_batch, dtype=np.int32)
    f["page_table"][:eng.max_batch] = eng._page_table
    for i, s in active:
        pos = int(eng._positions[i])
        f["tokens"][i] = eng._tokens[i]
        f["token_pos"][i] = pos
        f["token_page"][i] = eng._page_table[i, pos // ps]
        f["token_slot"][i] = pos % ps
        f["q_len"][i] = 1
        f["kv_len"][i] = s.num_tokens
        f["newest_slot"][i] = i
        token_state[i] = i
    t0 = eng.max_batch
    for j, (seq, start, C) in enumerate(rows):
        r = eng.max_batch + j
        pos = np.arange(start, start + C, dtype=np.int32)
        f["tokens"][t0:t0 + C] = seq.prompt[start:start + C]
        f["token_pos"][t0:t0 + C] = pos
        pages = np.asarray(seq.pages, np.int32)
        f["token_page"][t0:t0 + C] = pages[pos // ps]
        f["token_slot"][t0:t0 + C] = pos % ps
        f["page_table"][r, :len(seq.pages)] = pages
        f["q_start"][r] = t0
        f["q_len"][r] = C
        f["kv_len"][r] = start + C
        if start + C >= len(seq.prompt):        # its first token
            f["newest_slot"][r] = seq.slot
        token_state[t0:t0 + C] = seq.slot
        t0 += C
    if eng._has_state:
        f["token_state"] = token_state
    return f


def reference_decode(eng, active):
    """The decode loop's arrays as the engine sent them before: the
    slots' arrays as held, and each decode row's length."""
    seq_lens = np.ones(eng.max_batch, np.int32)
    for i, s in active:
        seq_lens[i] = s.num_tokens
    return {"tokens": eng._tokens.copy(), "positions": eng._positions.copy(),
            "seq_lens": seq_lens, "page_table": eng._page_table.copy()}


def hold_to_reference(eng, dispatch_reference=False):
    """From now on every descriptor ``eng`` packs is held, field by field
    and byte for byte, to the reference packing of the same step; with
    ``dispatch_reference`` the step is SENT the reference's arrays (laid
    out one after another), not the engine's buffer. Returns a Counter of
    the steps seen: "decode", "mixed-<chunk rows dealt>"."""
    seen = collections.Counter()
    pack_mixed, pack_decode = eng._pack_mixed, eng._pack_decode

    def held(buf, layout, want):
        got = M.cut(buf, layout)
        assert buf.dtype == np.int32 and list(got) == [n for n, _ in layout]
        assert set(want) == set(got)
        for name, shape in layout:
            assert want[name].dtype == np.int32 \
                and want[name].shape == shape, name
            assert got[name].tobytes() == want[name].tobytes(), name
        if dispatch_reference:
            return as_descriptor(**{n: want[n] for n, _ in layout})[0]
        return buf

    def mixed(active, rows, n_rows):
        want = reference_mixed(eng, active, rows, n_rows)
        seen[f"mixed-{len(rows)}"] += 1
        return held(pack_mixed(active, rows, n_rows),
                    eng._fns.step_layouts[n_rows], want)

    def decode(active):
        want = reference_decode(eng, active)
        seen["decode"] += 1
        return held(pack_decode(active), eng._fns.decode_layout, want)

    eng._pack_mixed, eng._pack_decode = mixed, decode
    return seen


def check_descriptor(make, vocab=256):
    """``make(**settings)`` builds the block's engine over the same
    weights. A scripted mix (a prompt of three chunks alone: two joined
    rows, or one a step where the block keeps state; two arrivals beside
    its tail: two sequences' rows and decode rows; a lone short prompt:
    one row; decode loops between and after; the first prompt's first
    four pages again: a copy-on-write hit where the block has a prefix
    cache; two short prompts together, in buffers that longer rows left
    their tokens in; then a pool too small for two sequences: a
    preemption and its folded re-prefill) served twice: by the engine as
    it is, every field of every descriptor it fills held to the reference
    packing, and by an engine that dispatches the reference's arrays. The
    same tokens."""
    rng = np.random.default_rng(43)
    new = lambda n: rng.integers(0, vocab, n).tolist()       # noqa: E731
    first = new(40)
    roomy = ([first, new(10), new(12), new(7), first[:32], new(20), new(5)],
             [0, 1, 1, 6, 14, 20, 20], 6, {})
    tight = ([list(range(1, 9)), list(range(3, 11))], None, 16,
             dict(page_size=4, total_pages=10, max_seq_len=32,
                  prefix_cache=False))
    for prompts, at, n_new, settings in (roomy, tight):
        eng, parent = make(**settings), make(**settings)
        seen = hold_to_reference(eng)
        hold_to_reference(parent, dispatch_reference=True)
        got, deals = serve(eng, prompts, n_new=n_new, at=at)
        want, _ = serve(parent, prompts, n_new=n_new, at=at)
        assert got == want
        assert seen["decode"] and seen["mixed-1"], seen
        assert sum(seen.values()) == eng.stats["h2d_arrays"] \
            == eng.stats["decode_dispatches"] \
            + eng.stats["ragged_dispatches"]
        if settings:
            assert eng.stats["preemptions"] >= 1
            continue
        joined = any(len({rid for rid, _, _ in d}) < len(d) for d in deals)
        assert joined == (not eng._has_state), deals
        assert seen["mixed-2"], seen
        assert any(len({rid for rid, _, _ in d}) == 2 for d in deals)
        assert eng.stats["cow_copies"] == (eng.prefix is not None)
