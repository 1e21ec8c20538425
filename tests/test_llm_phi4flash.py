"""The Phi-4-mini-flash block in the serving engine: a decoder-hybrid-decoder
(Mamba-1 layers beside window attention, ONE full-attention layer whose
pages the cross layers read, gated memory units on a Mamba-1 layer's scan
output, differential attention throughout, LayerNorm, biases, no
positions), against the benchmark's plain reference
(benchmark/reference_phi4flash.py) on seeded weights. Tiny widths on the
CPU, float32 compute. The engine-level cases are tests/test_llm_blocks.py's
(the row `phi4flash` of tests/_blocks.py); here: the operator's arithmetic
(ops/selective_scan.py), the pair packing, the walk in segments, and the
faults the reference must tell apart.

TOL: everything runs in float32 here, so the two sides differ by summation
order only (~1e-5 on logits of unit spread). 1e-4 leaves room and fails
every fault below by a factor of a hundred and more.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from _blocks import (BLOCKS, _step, built, config, seeded,  # noqa: E402
                     served_logits)
from benchmark import reference_phi4flash as ref  # noqa: E402
from ray_tpu.llm import InferenceEngine  # noqa: E402
from ray_tpu.llm import model as M  # noqa: E402
from ray_tpu.models.llama import LlamaConfig  # noqa: E402
from ray_tpu.ops import selective_scan as ss  # noqa: E402
from ray_tpu.ops.paged_attention import \
    ragged_paged_attention_reference  # noqa: E402

TOL = 1e-4
N, CH = 16, 256


# ------------------------------------------------- ops/selective_scan.py

def _operands(T, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    return dict(
        state=jax.random.normal(ks[0], (2, 6, N, CH)),
        x=jax.random.normal(ks[1], (T, CH)),
        dt=jnp.exp(jax.random.uniform(ks[2], (T, CH), minval=np.log(1e-3),
                                      maxval=np.log(1e-1))),
        A=-jnp.arange(1, N + 1, dtype=jnp.float32)[:, None]
        * jax.random.uniform(ks[3], (N, CH), minval=0.5, maxval=1.5),
        B=jax.random.normal(ks[4], (T, N)), C=jax.random.normal(ks[5], (T, N)),
        D=jax.random.normal(ks[6], (CH,)))


def test_the_one_token_update_is_the_formula():
    """The update's reference against the recurrence written out for one
    row, and the kernel (interpreted) against the reference: rows from
    their slots' states and a fresh one, two rows on the scratch slot."""
    o = _operands(5)
    slots = jnp.asarray([2, 0, 5, 4, 5])
    fresh = jnp.asarray([False, True, False, False, False])
    y, state = ss.selective_decode_reference(
        o["state"], o["x"], o["dt"], o["A"], o["B"], o["C"], o["D"], slots,
        fresh, 1)
    s = jnp.exp(o["dt"][0][None] * o["A"]) * o["state"][1, 2] \
        + (o["dt"][0] * o["x"][0])[None] * o["B"][0][:, None]
    np.testing.assert_allclose(state[1, 2], s, rtol=1e-6)
    np.testing.assert_allclose(y[0], o["C"][0] @ s + o["D"] * o["x"][0],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(     # the fresh row took no state
        state[1, 0], (o["dt"][1] * o["x"][1])[None] * o["B"][1][:, None],
        rtol=1e-6)
    assert jnp.array_equal(state[0], o["state"][0])     # the other layer
    y2, state2 = ss.selective_decode_update(
        o["state"], o["x"], o["dt"], o["A"], o["B"], o["C"], o["D"], slots,
        fresh, layer=1, interpret=True)
    np.testing.assert_allclose(y2, y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(state2[:, :5], state[:, :5], rtol=1e-5,
                               atol=1e-6)


#: (q_start, q_len, first position) of each chunk row over a flat axis of
#: T tokens: rows that start anywhere (not at a multiple of 8, nor of the
#: kernel's block of 64), cross its blocks, are empty, or fill the axis
RAGGED = {
    "two rows and padding": (150, [(3, 60, 0), (70, 70, 100), (140, 0, 0)]),
    "one row over three blocks": (200, [(5, 190, 7)]),
    "rows back to back": (64, [(0, 13, 0), (13, 51, 0)]),
    "one token": (8, [(2, 1, 9)]),
}


@pytest.mark.parametrize("case", sorted(RAGGED))
def test_the_chunk_scan_is_the_sequential_scan(case):
    """The scan kernel (interpreted) against `lax.scan` over the tokens: a
    row from its slot's state, a row from zeros (first position 0), tokens
    no row owns."""
    T, rows = RAGGED[case]
    o = _operands(T, seed=len(case))
    q_start, q_len, first = (jnp.asarray(a, jnp.int32) for a in zip(*rows))
    pos = np.zeros(T, np.int32)
    for a, n, p in rows:
        pos[a:a + n] = p + np.arange(n)
    row_slot = jnp.asarray([1, 3, 5][:len(rows)])
    args = (o["state"], o["x"], o["dt"], o["A"], o["B"], o["C"], o["D"],
            jnp.asarray(pos), q_start, q_len, row_slot)
    y, state = ss.selective_scan_reference(*args, 0)
    y2, state2 = ss.selective_chunk_scan(*args, layer=0, interpret=True)
    np.testing.assert_allclose(y2, y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(state2[:, :5], state[:, :5], rtol=1e-5,
                               atol=1e-6)


def test_a_sequence_is_the_same_however_it_falls_into_rows():
    """40 tokens as one chunk row, and as a row of 17, a row of 22 from
    the slot's state, and one decode token: the same outputs and the same
    last state."""
    o = _operands(40, seed=3)
    zeros = jnp.zeros_like(o["state"])

    def run(state, lo, n):
        take = {k: o[k][lo:lo + n] for k in ("x", "dt", "B", "C")}
        return ss.selective_chunk_scan(
            state, take["x"], take["dt"], o["A"], take["B"], take["C"],
            o["D"], lo + jnp.arange(n, dtype=jnp.int32),
            jnp.asarray([0]), jnp.asarray([n]), jnp.asarray([2]), layer=1,
            interpret=True)

    whole, last = run(zeros, 0, 40)
    a, state = run(o["state"], 0, 17)       # position 0: the slot is not read
    b, state = run(state, 17, 22)
    c, state = ss.selective_decode_update(
        state, o["x"][39:], o["dt"][39:], o["A"], o["B"][39:], o["C"][39:],
        o["D"], jnp.asarray([2]), jnp.asarray([False]), layer=1,
        interpret=True)
    np.testing.assert_allclose(jnp.concatenate([a, b, c]), whole, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(state[1, 2], last[1, 2], rtol=1e-5, atol=1e-6)


# ------------------------------------------------------ the pair packing

def test_a_pair_as_one_128_lane_head_is_the_four_published_products():
    """[q1 | 0] and [0 | q2] against key rows [k1 | k2] and value rows
    [v1 | v2], through the paged reference at sm_scale dh ** -0.5: the two
    softmaxes of a pair on dh-wide heads, each over the joined values."""
    T, H, G, dh, ps = 12, 8, 4, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (jax.random.normal(key, (T, n, dh))
               for key, n in zip(ks, (H, G, G)))
    pages = T // ps
    kp = k.reshape(pages, ps, G // 2, 2 * dh).transpose(0, 2, 1, 3)
    vp = v.reshape(pages, ps, G // 2, 2 * dh).transpose(0, 2, 1, 3)
    got = ragged_paged_attention_reference(
        M._pair_queries(q, 2 * dh), kp, vp, jnp.arange(pages)[None],
        jnp.asarray([0]), jnp.asarray([T]), jnp.asarray([T]),
        sm_scale=dh ** -0.5)
    for h in range(H):
        # query head h is q_(h % 2) of query pair h // 2, on key/value pair
        # g: its key is that pair's k_(h % 2), its value the pair's both
        g = (h // 2) // (H // G)
        s = (q[:, h] @ k[:, 2 * g + h % 2].T) * dh ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
        pair = jnp.concatenate([v[:, 2 * g], v[:, 2 * g + 1]], axis=-1)
        np.testing.assert_allclose(got[:, h], jax.nn.softmax(s, -1) @ pair,
                                   rtol=1e-5, atol=1e-5)


# -------------------------------------------------- the walk in segments

def _published():
    from benchmark.runners import serve_phi4flash
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "phi4-mini-flash-serve-1chip.json")) as f:
        return LlamaConfig.tiny(**serve_phi4flash.model_fields(json.load(f)))


def test_the_published_depth_is_three_segments():
    """(Mamba-1, window) x 8, (Mamba-1, full) x 1, (gated memory unit,
    cross) x 7: a scan each, six layer bodies traced where one period of
    all the rest would unroll 32."""
    assert M._pattern(_published()) == (
        [], [("mamba1", "dense"), ("sliding_attention", "dense")], 8,
        [("mamba1", "dense"), ("full_attention", "dense")], 1,
        [("gmu", "dense"), ("cross_attention", "dense")], 7)


@pytest.mark.parametrize("block", sorted(set(BLOCKS) - {"phi4flash"}))
def test_an_accepted_block_is_one_segment_of_its_shortest_period(block):
    """What `_pattern` returned before it knew segments: leading layers,
    the shortest period of ALL the rest, how many."""
    cfg = config(block)
    lead, *segments = M._pattern(cfg)
    period, n = segments
    kinds = [(op, "moe" if cfg.n_experts and i >= len(lead) else "dense")
             for i, op in enumerate(cfg.layer_types
                                    or ("full_attention",) * cfg.n_layers)]
    rest = kinds[len(lead):]
    assert lead == kinds[:cfg.n_dense_layers if cfg.n_experts else 0]
    assert period * n == rest
    assert not any(rest == rest[:p] * (len(rest) // p)
                   for p in range(1, len(period)) if len(rest) % p == 0)


DEEP = dict(BLOCKS["phi4flash"].fields, n_layers=28, layer_types=(
    ["mamba1", "sliding_attention"] * 6 + ["mamba1", "full_attention"]
    + ["gmu", "cross_attention"] * 7), dtype=jnp.float32)


def test_three_scans_hand_the_memory_and_the_pages_across():
    """28 layers are three segments: the memory rides the carry from the
    second scan's Mamba-1 layer into the third's gated memory units, the
    cross layers read the second scan's full layer's pages, and every
    kind's ordinal goes on counting across the scans. Chunks of 16 across
    the window's edge, then decode rows, against the reference."""
    cfg = LlamaConfig.tiny(**DEEP)
    assert list(M._pattern(cfg)[2::2]) == [6, 1, 7]
    params = seeded(cfg)
    tokens = np.random.default_rng(2).integers(0, cfg.vocab_size, 44)
    out, _ = served_logits(cfg, params, tokens, n_prompt=36, chunk=16)
    with jax.default_matmul_precision("highest"):
        want = ref.forward_logits(params, jnp.asarray(tokens, jnp.int32),
                                  ref.dims_of(cfg))
    at = [15, 31, 35] + list(range(36, 44))
    assert float(jnp.abs(jnp.stack(out) - want[jnp.asarray(at)]).max()) < TOL


# ------------------------------------------- the walk is cut at its tail

#: decode rows beside chunk rows: A and B decode while C, D and E prefill
#: in chunks of 16, so one mixed step (three rows dealt: the shape of four)
#: holds decode rows, idle decode rows, a chunk row that ends its prompt
#: (C's 4 tokens, E's 14), one that does not (D's second 16) and an empty
#: one
_MIXED = dict(page_size=8, total_pages=64, max_batch=5, max_seq_len=64,
              prefill_chunk=16, prefill_rows=4, decode_chunk=4)


@pytest.fixture(scope="module")
def mixed_steps():
    """(cfg, params, [every mixed step the engine packed: its
    descriptor's fields, the pool before it, each row's sequence so far])
    of an engine held to one program at a time (every token of a
    descriptor is then a value)."""
    cfg, params = built("phi4flash")
    eng = InferenceEngine(cfg, params, **_MIXED)
    eng._run_ahead = False
    steps, pack = [], eng._pack_mixed

    def packing(active, rows, n_rows):
        buf = pack(active, rows, n_rows)
        B = eng.max_batch
        seen = {slot: seq.prompt + seq.generated for slot, seq in active}
        seen.update({B + j: seq.prompt[:start + C]
                     for j, (seq, start, C) in enumerate(rows)})
        fields = M.cut(buf.copy(), eng._fns.step_layouts[n_rows])
        fields.pop("newest_slot")
        steps.append((fields, jax.tree.map(jnp.copy, eng.kv), seen))
        return buf
    eng._pack_mixed = packing
    rng = np.random.default_rng(4)
    prompt = lambda n: rng.integers(1, cfg.vocab_size, n).tolist()  # noqa
    for n in (5, 9):
        eng.add_request(prompt(n), 12)
    eng.step()
    for n in (20, 40, 30):
        eng.add_request(prompt(n), 3)
    for _ in range(60):
        eng.step()
        if not eng.has_work():
            break
    assert not eng.has_work()
    return cfg, params, steps


def _run_step(cfg, params, fields, kv):
    return _step(params, **{k: jnp.asarray(a) for k, a in fields.items()},
                 kv=kv, cfg=cfg, paged_impl="reference",
                 max_q_len=_MIXED["prefill_chunk"],
                 decode_rows=_MIXED["max_batch"])


def _the_mix(steps):
    """The steps that hold every kind of row: decode rows, an idle one, a
    chunk row that ends its prompt, one that does not, an empty one."""
    B, C = _MIXED["max_batch"], _MIXED["prefill_chunk"]
    found = []
    for fields, kv, seen in steps:
        q_len = fields["q_len"]
        n_decode = int(q_len[:B].sum())
        ends = [r for r in seen if r >= B and q_len[r] < C]
        if 0 < n_decode < B and ends and (q_len[B:] == C).any() \
                and (q_len[B:] == 0).any():
            found.append((fields, kv, seen))
    return found


def test_every_rows_last_token_has_the_references_logits(mixed_steps):
    """Through the cut, in every mixed step the engine packed: each row
    that holds a token gets, at its last one, the logits the plain
    reference gives its sequence there."""
    cfg, params, steps = mixed_steps
    assert _the_mix(steps)
    for fields, kv, seen in steps:
        logits, _, _ = _run_step(cfg, params, fields, kv)
        for row, tokens in seen.items():
            assert fields["kv_len"][row] == len(tokens)
            with jax.default_matmul_precision("highest"):
                want = ref.forward_logits(
                    params, jnp.asarray(tokens, jnp.int32),
                    ref.dims_of(cfg))[-1]
            assert float(jnp.abs(logits[row] - want).max()) < TOL, row


def test_the_pool_is_what_the_self_decoder_alone_writes(mixed_steps):
    """Every page leaf and every SLOT_STATE leaf after a mixed step, as
    the first six layers alone (no tail: nothing is cut) leave them: the
    tail writes nothing, and leaving it early changes nothing kept."""
    cfg, params, steps = mixed_steps
    at = M.tail_start(cfg)
    alone = config("phi4flash", dtype=jnp.float32, n_layers=at,
                   layer_types=list(cfg.layer_types[:at]))
    layers = params["layers"]
    head = {**params, "layers": {
        **{k: layers[k] for k in ("attn", "attn_window", "mamba1")},
        "dense": jax.tree.map(lambda w: w[:at], layers["dense"])}}
    assert M.tail_start(alone) == alone.n_layers == 6
    for fields, kv, _ in _the_mix(steps):
        _, got, _ = _run_step(cfg, params, fields, kv)
        _, want, _ = _run_step(alone, head, fields, kv)
        assert set(got) == set(want) == {"k", "v", "k_win", "v_win",
                                         "ssm1", "ssm1_conv"}
        for leaf in want:
            np.testing.assert_array_equal(got[leaf], want[leaf], leaf)


def _dot_shapes(jaxpr):
    """The result shape of every dot_general of ``jaxpr`` at any depth, a
    kernel's body not entered."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out.append(eqn.outvars[0].aval.shape)
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                out += _dot_shapes(getattr(sub, "jaxpr", sub))
    return out


@pytest.mark.parametrize("impl", ["reference", "kernel"])
def test_the_tails_products_have_a_row_a_row_not_a_row_a_token(
        mixed_steps, impl):
    """The mechanism, in the program's text: in the tail's scan (the last
    of the walk's two) every product has R rows, the feed-forward's [1, R,
    ffn] among them; in the scan before it, T."""
    fields = mixed_steps[2][-1][0]
    cfg = config("phi4flash")
    T, R = fields["tokens"].shape[0], fields["q_len"].shape[0]
    assert T != R and cfg.ffn_dim not in (T, R)
    params, kv = jax.eval_shape(lambda: (
        M.init_params(cfg, jax.random.PRNGKey(0)),
        M.make_kv_cache(cfg, 16, 8, max_batch=_MIXED["max_batch"],
                        lane_pad=impl == "kernel", window_pages=9)))
    jaxpr = jax.make_jaxpr(lambda p, f, kv: M._ragged_logits(
        p, **f, kv=kv, cfg=cfg, paged_impl=impl,
        max_q_len=_MIXED["prefill_chunk"],
        decode_rows=_MIXED["max_batch"]))(
        params, {k: jax.ShapeDtypeStruct(a.shape, jnp.int32)
                 for k, a in fields.items()}, kv).jaxpr
    before, tail = (_dot_shapes(eqn.params["jaxpr"].jaxpr)
                    for eqn in jaxpr.eqns if eqn.primitive.name == "scan")
    assert (1, R, cfg.ffn_dim) in tail and (1, T, cfg.ffn_dim) in before
    assert not [s for s in tail if T in s]
    assert not [s for s in before if R in s and len(s) == 3]
    # the head: R rows, as ever
    assert [eqn.outvars[0].aval.shape for eqn in jaxpr.eqns
            if eqn.primitive.name == "dot_general"][-1] \
        == (R, cfg.vocab_size)


def test_running_ahead_serves_the_synchronous_engines_tokens_and_counts():
    """tests/test_llm_ahead.py's method on this block, chunk rows that do
    and do not end their prompts beside decode rows: the same tokens from
    the engine that runs one program ahead, and in both engines every
    token of a chunk row but its last counted as having left the walk."""
    from test_llm_ahead import _both, _requests
    cfg = config("phi4flash")
    requests = _requests(11, 9, cfg.vocab_size, longest=60)
    got, ahead, sync = _both("phi4flash", requests,
                             at=[0, 0, 0, 1, 3, 3, 8, 8, 20])
    assert ahead.stats["ahead_dispatches"] > 2
    for eng in (ahead, sync):
        st = eng.stats
        assert st["walk_tokens"] == st["ragged_real_tokens"]
        assert st["walk_tokens_left"] \
            == st["prefill_tokens"] - st["chunk_rows"] > 0


# ------------------------------------ what the reference must tell apart

@pytest.fixture(scope="module")
def served():
    """The row's configuration and weights, 40 tokens served (a prompt of
    30 in chunks of 16, then decode rows: the window of 16 is crossed both
    ways), and the positions the logits belong to."""
    cfg, params = built("phi4flash")
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, 40)
    out, _ = served_logits(cfg, params, tokens, n_prompt=30, chunk=16)
    return cfg, params, tokens, jnp.stack(out), \
        jnp.asarray([15, 29] + list(range(30, 40)))


def _reference(served, faults=()):
    cfg, params, tokens, _, at = served
    with jax.default_matmul_precision("highest"):
        return ref.forward_logits(params, jnp.asarray(tokens, jnp.int32),
                                  ref.dims_of(cfg), faults)[at]


def test_served_logits_are_the_references(served):
    assert float(jnp.abs(served[3] - _reference(served)).max()) < TOL


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_the_reference_tells_a_fault_apart(served, fault):
    """A cross layer reading a window layer's keys and values, or keys and
    values of its own input; a gated memory unit fed the Mamba-1 layer
    before the newest; lambda_init from the ordinal among the attention
    layers; the window one short and one long; the biases left out; the
    memory taken after the gate; lambda left at lambda_init; the
    recurrence's carry rounded to bfloat16: each moves the reference's
    logits off the served ones by a hundred tolerances and more."""
    off = float(jnp.abs(served[3] - _reference(served, (fault,))).max())
    assert off > 100 * TOL, (fault, off)


# ------------------------------------------------------ the engine's say

def test_the_engine_counts_one_full_layer_eight_windows_nine_states():
    """At the published depth and tiny widths: what a token costs in the
    ONE layer of the full group, how many layers read it, and what a slot
    owns of state (9 float32 states and 9 x 3 conv inputs)."""
    cfg = LlamaConfig.tiny(**dict(
        BLOCKS["phi4flash"].fields, n_layers=32,
        layer_types=_published().layer_types))
    eng = InferenceEngine(cfg, page_size=8, total_pages=16, max_batch=2,
                          max_seq_len=32, prefill_chunk=8, prefill_rows=1,
                          decode_chunk=2, prefix_cache=True)
    di, hd = cfg.ssm1_channels, cfg.head_dim
    assert eng.stats["shared_kv_readers"] == 8
    # K and V of 4 key/value heads of 8 (2 pairs of 16), bfloat16
    assert eng.stats["kv_token_layer_bytes"] == 2 * 4 * hd * 2
    assert eng.stats["state_bytes_per_slot"] == 9 * (16 * di * 4
                                                     + 3 * di * 2)
    assert eng.kv["k"].shape[0] == 1 and eng.kv["k_win"].shape[0] == 8
    assert eng.kv["k"].shape[2:] == (2, 8, 2 * hd)
    assert eng.prefix is None and eng.window_allocator is not None
    assert "window_pages_freed" in eng.stats and "state_resets" in eng.stats
