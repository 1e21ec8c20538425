"""The LFM2 block in the serving engine: gated short-conv layers with a
state per batch slot beside the paged KV of every fourth layer, two leading
dense feed-forwards, sigmoid-and-bias routing, a q/k norm over each head,
through the one ragged step and the decode loop, against the benchmark's
plain reference (benchmark/reference_lfm2.py) on seeded weights. Tiny widths
on the CPU, float32 compute; the pattern is the published one's first ten
layers (conv conv | attn conv conv conv | attn conv conv conv).

TOL: everything runs in float32 here (cfg.dtype and the reference), so the
two sides differ by summation order only: ~1e-6 on unit-variance logits.
1e-4 leaves two orders of room and still fails a bf16 computation (~1e-2:
test_conv_operator_over_a_ragged_batch runs one), a conv that starts from
another sequence's state or from zeros mid-sequence, a bias that leaks into
the routing weights, a softmax where the sigmoid is, a norm over the whole
vector where it is over each head (whole logits, or tenths).
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from _blocks import seeded, worst_gap  # noqa: E402
from _chunk_rows import (check_descriptor,  # noqa: E402
                         check_state_keeps_one_row, SHAPE_CASES,
                         check_shapes, pin_full_shape)
from benchmark import reference_lfm2 as ref  # noqa: E402
from benchmark import reference_olmoe  # noqa: E402
from ray_tpu.llm import InferenceEngine  # noqa: E402
from ray_tpu.llm import model as M  # noqa: E402
from ray_tpu.llm.cache import STATE_LEAF, make_kv_cache  # noqa: E402
from ray_tpu.models.llama import LlamaConfig, init_params  # noqa: E402
from ray_tpu.ops import moe  # noqa: E402

TOL = 1e-4
D, E, K = 64, 8, 2
PATTERN = ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 2
LFM2 = dict(n_layers=10, n_heads=8, n_kv_heads=2, ffn_dim=32,
            dense_ffn_dim=96, n_dense_layers=2, n_experts=E,
            experts_per_token=K, norm_topk_prob=True, layer_types=PATTERN,
            qk_norm_per_head=True, router_score="sigmoid", router_bias=True,
            router_eps=1e-6, dtype=jnp.float32)
ENGINE = dict(page_size=8, total_pages=64, max_batch=4, max_seq_len=128,
              prefill_chunk=16, prefill_rows=2, decode_chunk=4, seed=3)


@pytest.fixture(scope="module")
def lfm2():
    # compiled_step_programs() counts the process's shared jits: whatever
    # file this worker ran before must not count against this engine
    jax.clear_caches()
    cfg = LlamaConfig.tiny(**LFM2)
    return cfg, InferenceEngine(cfg, seeded(cfg), **ENGINE)


# ------------------------------------------------------------- ops/moe.route

def _router_inputs(T=40):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    return (jax.random.normal(ks[0], (T, D)),
            jax.random.normal(ks[1], (D, E)) * D ** -0.5,
            0.3 * jax.random.normal(ks[2], (E,)))


@pytest.mark.parametrize("case", ["sigmoid_bias_eps_scale", "sigmoid",
                                  "softmax_renorm", "softmax"])
def test_route_against_the_references_routing(case):
    """route's score function, selection bias, epsilon and scale against
    the LFM2 reference's routing; with none of them it is OLMoE's routing,
    against that reference and bit for bit what it was (the same ops)."""
    m, router, bias = _router_inputs()
    valid = jnp.arange(m.shape[0]) % 5 != 0
    renorm = case != "softmax"
    with jax.default_matmul_precision("highest"):
        if case.startswith("sigmoid"):
            full = case == "sigmoid_bias_eps_scale"
            how = dict(score="sigmoid", bias=bias if full else None,
                       eps=1e-6 if full else 0.0,
                       scale=2.5 if full else 1.0)
            w, e = moe.route(m, valid, router, K, renorm, **how)
            want, chosen = ref.routing(m, router, how["bias"], K, renorm,
                                       score="sigmoid", eps=how["eps"],
                                       scale=how["scale"])
        else:
            w, e = moe.route(m, valid, router, K, renorm)
            want, chosen = reference_olmoe.routing(m, router, K, renorm)
    keep = np.asarray(valid)
    assert np.array_equal(np.asarray(e)[keep], np.asarray(chosen)[keep])
    assert (np.asarray(e)[~keep] == E).all()
    assert float(jnp.abs(w[~keep]).max()) == 0.0
    got = jnp.zeros_like(want).at[jnp.arange(m.shape[0])[:, None],
                                  jnp.minimum(e, E - 1)].add(w)
    assert float(jnp.abs(got - want)[keep].max()) < 1e-6
    if case == "sigmoid_bias_eps_scale":
        # the bias moved the choice for some token, and never the weights
        _, plain = ref.routing(m, router, None, K, renorm)
        assert not np.array_equal(np.asarray(plain), np.asarray(chosen))
    if case.startswith("softmax"):
        def as_it_was(m, valid, router):
            logits = jnp.dot(m.astype(jnp.float32),
                             router.astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
            w, e = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), K)
            if renorm:
                w = w / jnp.sum(w, axis=-1, keepdims=True)
            keep = valid[:, None]
            return jnp.where(keep, w, 0.0), \
                jnp.where(keep, e.astype(jnp.int32), router.shape[-1])
        assert str(jax.make_jaxpr(as_it_was)(m, valid, router)) == str(
            jax.make_jaxpr(lambda *a: moe.route(*a, K, renorm))(
                m, valid, router))


def test_route_refuses_an_unknown_score():
    m, router, _ = _router_inputs(4)
    with pytest.raises(ValueError, match="softmax"):
        moe.route(m, jnp.ones(4, bool), router, K, True, score="tanh")


# ------------------------------------------------------- the conv operator

@pytest.mark.parametrize("dtype,within", [(jnp.float32, True),
                                          (jnp.bfloat16, False)])
def test_conv_operator_over_a_ragged_batch(dtype, within):
    """_short_conv on a ragged batch (two decode rows mid-sequence, an
    empty row, one chunk that continues a sequence and one that starts
    one, padding) against the reference's conv over each whole sequence.
    In bfloat16 the same comparison is NOT within TOL: the tolerance
    tells the precisions apart."""
    cfg = LlamaConfig.tiny(**{**LFM2, "dtype": dtype})
    lp = {k: w[3] for k, w in seeded(cfg)["layers"]["conv"].items()}
    key = jax.random.PRNGKey(7)
    seqs = [jax.random.normal(jax.random.fold_in(key, i), (n, D))
            for i, n in enumerate((9, 6, 14, 5))]
    with jax.default_matmul_precision("highest"):
        f32 = {k: w.astype(jnp.float32) for k, w in lp.items()}
        want = [ref.short_conv(
            reference_olmoe._rmsnorm(s, f32["conv_norm"], cfg.norm_eps),
            f32["w_in"], f32["w_conv"], f32["w_out"]) for s in seqs]

        def step(state, spans, R=5):
            """spans: [(sequence, from, to, slot)]: the first R - 2 rows
            are one-token rows in token order, the others chunks."""
            T = 16
            x = np.zeros((T, D), np.float32)
            pos = np.zeros(T, np.int32)
            slot = np.full(T, 4, np.int32)
            q_start, q_len = np.zeros(R, np.int32), np.zeros(R, np.int32)
            t = R - 2
            for r, span in enumerate(spans):
                if span is None:
                    continue
                i, lo, hi, sl = span
                t0 = r if r < R - 2 else t
                x[t0:t0 + hi - lo] = seqs[i][lo:hi]
                pos[t0:t0 + hi - lo] = np.arange(lo, hi)
                slot[t0:t0 + hi - lo] = sl
                q_start[r], q_len[r] = t0, hi - lo
                if r >= R - 2:
                    t += hi - lo
            rows = M._Rows(*map(jnp.asarray, (pos, slot, q_start, q_len)))
            y, kv = M._short_conv(lp, 3, jnp.asarray(x, dtype)[None],
                                  {STATE_LEAF: state}, rows, cfg)
            return (y[0] - jnp.asarray(x, dtype)).astype(jnp.float32), \
                kv[STATE_LEAF], q_start

        state = 9.0 + make_kv_cache(cfg, 4, 8, max_batch=4)[STATE_LEAF]
        # sequences 0 and 1 prefill into slots 0 and 1 (over stale state)
        _, state, _ = step(state, [None, None, None, (0, 0, 8, 0),
                                   (1, 0, 5, 1)])
        # then: both decode one token, slot 2 idle, sequence 2 starts in
        # slot 3, sequence 3 takes slot 2 whole
        y, state, q0 = step(state, [(0, 8, 9, 0), (1, 5, 6, 1), None,
                                    (2, 0, 7, 3), (3, 0, 5, 2)])
        worst = max(
            float(jnp.abs(y[0] - want[0][8]).max()),
            float(jnp.abs(y[1] - want[1][5]).max()),
            float(jnp.abs(y[q0[3]:q0[3] + 7] - want[2][:7]).max()),
            float(jnp.abs(y[q0[4]:q0[4] + 5] - want[3]).max()))
        # the decode loop's layout: token t is slot t's (sequence 2 in
        # slot 3 goes on at position 7; slot 2's sequence 3 ended)
        x = np.zeros((4, D), np.float32)
        x[0], x[1], x[3] = seqs[0][8], seqs[1][5], seqs[2][7]
        rows = M._Rows(jnp.asarray([9, 6, 0, 7], jnp.int32), None, None,
                       None)
        y2, _ = M._short_conv(lp, 3, jnp.asarray(x, dtype)[None],
                              {STATE_LEAF: state}, rows, cfg)
        worst = max(worst, float(jnp.abs(
            (y2[0, 3] - jnp.asarray(x[3], dtype)).astype(jnp.float32)
            - want[2][7]).max()))
    assert (worst < TOL) == within, worst


# ------------------------------------------------------------ the engine

def test_param_tree_and_pool_follow_the_pattern(lfm2):
    cfg, eng = lfm2
    shapes = jax.tree.map(lambda a: a.shape, eng.params["layers"])
    assert set(shapes) == {"attn", "conv", "dense", "moe"}
    assert shapes["attn"]["wq"] == (2, D, D)
    assert shapes["attn"]["q_norm"] == shapes["attn"]["k_norm"] == (2, 8)
    assert shapes["conv"]["w_in"] == (8, D, 3 * D)
    assert shapes["conv"]["w_conv"] == (8, 3, D)
    assert shapes["dense"]["w_gate"] == (2, D, 96)
    assert shapes["moe"]["w_gate"] == (8, E, D, 32)
    assert shapes["moe"]["router_bias"] == (8, E)
    assert eng.params["layers"]["moe"]["router_bias"].dtype == jnp.float32
    assert float(jnp.abs(eng.params["layers"]["moe"]["router_bias"]).min()) \
        > 0.0
    # pages for the two attention layers only; state a slot + scratch
    assert eng.kv["k"].shape == (2, 64, 2, 8, 8)
    assert eng.kv[STATE_LEAF].shape == (8, ENGINE["max_batch"] + 1, 2, D)
    report = eng.device_report()
    assert report["state_bytes"] == eng.kv[STATE_LEAF].nbytes \
        == eng.stats["state_bytes"]
    assert report["kv_bytes"] == sum(a.nbytes for a in eng.kv.values())
    assert M._pattern(cfg) == (
        [("conv", "dense")] * 2,
        [("full_attention", "moe")] + [("conv", "moe")] * 3, 2)


def test_a_descriptor_holds_the_arrays_the_engine_packed_before():
    """Conv layers beside attention and experts: token_state is a field of
    the descriptor, a sequence keeps one row a step; every field the old
    packing's."""
    cfg = LlamaConfig.tiny(**LFM2)
    params = seeded(cfg)
    check_descriptor(lambda **kw: InferenceEngine(
        cfg, params, **{**ENGINE, **kw}))


def test_a_sequence_that_prefills_alone_keeps_one_row_a_step(lfm2):
    check_state_keeps_one_row(lfm2[1])


@pytest.fixture(scope="module")
def shaped_and_full():
    """The same weights behind the set of mixed-step shapes and behind
    the full shape alone (what every step ran in before the set)."""
    cfg = LlamaConfig.tiny(**LFM2)
    params = seeded(cfg)
    return [InferenceEngine(cfg, params, **ENGINE),
            pin_full_shape(InferenceEngine(cfg, params, **ENGINE))]


@pytest.mark.parametrize("case", SHAPE_CASES)
def test_a_mixed_step_runs_the_smallest_shape_that_holds_its_rows(
        shaped_and_full, case):
    """One-row and two-row steps in turn through the CONV layers and the
    experts: the tokens of the full shape alone, each step in the shape
    its deal asks for."""
    check_shapes(case, *shaped_and_full)


def test_step_counters_equal_the_references_routing(lfm2):
    """moe_pairs / moe_hits / moe_hot against a numpy count of the
    reference's routing over the EXPERT layers only (8 of 10), step by
    step: two prefill chunks, then eight one-token decode steps."""
    cfg, eng = lfm2
    before = dict(eng.stats)
    prompt, n_new = list(range(50, 79)), 9       # 29 = chunks of 16 + 13
    served = eng.generate(prompt, n_new)
    fed = prompt + served[:-1]
    with jax.default_matmul_precision("highest"):
        _, chosen = ref.forward(eng.params, jnp.asarray(fed, jnp.int32),
                                ref.dims_of(cfg))
    chosen = np.asarray(chosen)                  # [8, S, k]
    assert chosen.shape[0] == cfg.n_layers - cfg.n_dense_layers
    steps = [(0, 16), (16, 29)] + [(i, i + 1) for i in range(29, len(fed))]
    pairs = hits = hot = 0
    for lo, hi in steps:
        for layer in chosen:
            counts = np.bincount(layer[lo:hi].reshape(-1), minlength=E)
            pairs += counts.sum()
            hits += (counts > 0).sum()
            hot += counts.max()
    got = {k: eng.stats[k] - before[k] for k in moe.COUNTERS}
    assert got == {"moe_pairs": pairs, "moe_hits": hits, "moe_hot": hot}
    assert pairs == len(fed) * K * 8


@pytest.mark.parametrize("field,other", [
    ("router_score", "softmax"), ("router_bias", False),
    ("qk_norm_per_head", False), ("n_dense_layers", 0),
    ("layer_types", ["full_attention"] * 10)])
def test_each_new_field_is_told_apart_by_the_reference(lfm2, field, other):
    """The engine follows each new field, and the reference with that one
    field set otherwise does NOT score the same tokens within TOL."""
    cfg, eng = lfm2
    prompt = list(range(11, 40))
    served = eng.generate(prompt, 9)
    assert worst_gap("lfm2", eng, prompt, served) < TOL
    if field in ("n_dense_layers", "layer_types"):
        # another tree: serve it, and score it against this one's fields
        over = {field: other}
        if field == "n_dense_layers":
            over.update(dense_ffn_dim=0, layer_types=PATTERN[2:] + PATTERN[:2])
        cfg2 = LlamaConfig.tiny(**{**LFM2, **over})
        eng2 = InferenceEngine(cfg2, seeded(cfg2), **ENGINE)
        served2 = eng2.generate(prompt, 9)
        assert worst_gap("lfm2", eng2, prompt, served2) < TOL
        return
    dims = ref.dims_of(dataclasses.replace(cfg, **{field: other}))
    params = eng.params
    if field != "router_score":
        drop = {"router_bias": ("moe", ["router_bias"]),
                "qk_norm_per_head": ("attn", ["q_norm", "k_norm"])}[field]
        params = {**params, "layers": {
            **params["layers"], drop[0]: {
                k: w for k, w in params["layers"][drop[0]].items()
                if k not in drop[1]}}}
    got = ref.score_greedy(params, dims, prompt, served, 96)
    assert max(got["gap"]) > 10 * TOL or got["reference_tokens"] != served


def test_lane_padded_pool_serves_the_same_tokens(lfm2):
    """The pool the kernels take (rows of 128 lanes for a head of 8 here,
    of 64 at the published widths): q, k and v are zero-padded to the
    pool's width and the scale stays head_dim ** -0.5."""
    cfg, eng = lfm2
    padded = InferenceEngine(cfg, eng.params, **ENGINE)
    padded.kv = make_kv_cache(cfg, ENGINE["total_pages"],
                              ENGINE["page_size"],
                              max_batch=ENGINE["max_batch"], lane_pad=True)
    assert padded.kv["k"].shape[-1] == 128
    prompt = list(range(20, 55))
    served = padded.generate(prompt, 10)
    assert served == eng.generate(prompt, 10)
    assert worst_gap("lfm2", padded, prompt, served) < TOL


# --------------------------------------------------------------- refusals

@pytest.mark.parametrize("over,match", [
    (dict(layer_types=["conv", "attention"], n_layers=2), "layer_types"),
    (dict(layer_types=["conv"], n_layers=2), "layer_types"),
    (dict(router_score="tanh"), "router_score"),
    (dict(n_dense_layers=1), "n_dense_layers"),
    (dict(n_dense_layers=2, n_experts=4, experts_per_token=2),
     "dense_ffn_dim"),
    (dict(conv_L_cache=3), "conv_L_cache")])
def test_config_refuses_what_it_cannot_build(over, match):
    with pytest.raises(ValueError, match=match):
        LlamaConfig.tiny(**over)


# ------------------------ a configuration without the new fields is as it was

@pytest.mark.parametrize("block", ["dense", "olmoe"])
def test_configurations_without_the_new_fields_are_untouched(block,
                                                             monkeypatch):
    """The Llama/Mistral and OLMoE blocks build the parameter tree they
    built before the pattern existed and run the one walk as a pattern of
    period 1: no other operator's body is reached, the pool has its two
    leaves, every layer one entry, and the mixed step takes no state
    argument."""
    over = dict(n_layers=2, dtype=jnp.float32)
    if block == "olmoe":
        over.update(n_kv_heads=8, n_experts=E, experts_per_token=K,
                    qk_norm=True, tie_embeddings=False)
    cfg = LlamaConfig.tiny(**over)
    assert not cfg.hybrid

    def unreachable(*a, **k):
        raise AssertionError("another block's operator ran for a plain one")
    monkeypatch.setattr(M, "OPERATORS", {
        kind: (stack, body if kind == "full_attention" else unreachable)
        for kind, (stack, body) in M.OPERATORS.items()})
    monkeypatch.setattr(M, "_latent_attention", unreachable)
    params = init_params(cfg, jax.random.PRNGKey(0))
    assert set(params["layers"]) == {
        "attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate", "w_up",
        "w_down"} | ({"router", "q_norm", "k_norm"} if block == "olmoe"
                     else set())
    kv = make_kv_cache(cfg, 16, 8)
    assert set(kv) == {"k", "v"} and kv["k"].shape[0] == cfg.n_layers
    T, R, mp = 12, 5, 4
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)    # noqa: E731
    jaxpr = jax.make_jaxpr(
        lambda *a: M._ragged_step_body(*a, cfg=cfg, paged_impl="reference",
                                       max_q_len=8, decode_rows=3))(
        params, i32(T), i32(T), i32(T), i32(T), i32(R, mp), i32(R), i32(R),
        i32(R), kv)
    n_leaves = len(jax.tree.leaves((params, kv)))
    assert len(jaxpr.jaxpr.invars) == n_leaves + 8
    text = str(jaxpr)
    assert "logistic" not in text.split("top_k")[0] or block == "dense"
    eng = InferenceEngine(cfg, params, **ENGINE)
    assert "state_bytes" not in eng.stats
    assert eng.device_report()["state_bytes"] == 0
