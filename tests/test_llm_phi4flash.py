"""The Phi-4-mini-flash block in the serving engine: a decoder-hybrid-decoder
(Mamba-1 layers beside window attention, ONE full-attention layer whose
pages the cross layers read, gated memory units on a Mamba-1 layer's scan
output, differential attention throughout, LayerNorm, biases, no
positions), against the benchmark's plain reference
(benchmark/reference_phi4flash.py) on seeded weights. Tiny widths on the
CPU, float32 compute. The engine-level cases are tests/_block_cases.py's
(the row `phi4flash` of tests/_blocks.py); here: the operator's arithmetic
(ops/selective_scan.py), the pair packing, the walk in segments, and the
faults the reference must tell apart; tests/test_llm_phi4flash_tail.py has
the walk cut at its tail (a test file is what a worker of the suite is
handed, and this block's cases are long).

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

from _blocks import (BLOCKS, built, config, seeded,  # noqa: E402
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
