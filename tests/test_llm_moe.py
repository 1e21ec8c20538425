"""The OLMoE block in the serving engine: dropless routed experts, q/k
norm and an untied head through the one ragged step, against the
benchmark's plain reference (benchmark/reference_olmoe.py) on seeded
weights. Tiny widths on the CPU, float32 compute.

TOL: everything runs in float32 here (cfg.dtype and the reference), so the
two sides differ by summation order only: ~1e-6 on unit-variance logits.
1e-4 leaves two orders of room and still fails a bf16 computation (~1e-2),
a wrong or missing expert, a renormalised weight, a norm left out or an
embedding used as the head (whole logits).
"""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from _blocks import worst_gap  # noqa: E402
from _chunk_rows import (SHAPE_CASES, check_descriptor,  # noqa: E402
                         check_shapes, pin_full_shape)
from benchmark import reference_olmoe as ref  # noqa: E402
from ray_tpu.llm import InferenceEngine  # noqa: E402
from ray_tpu.llm.engine import CPU_KEY, WALL_KEYS  # noqa: E402
from ray_tpu.util import startup_clocks  # noqa: E402
from ray_tpu.models.llama import LlamaConfig, init_params  # noqa: E402
from ray_tpu.ops import moe  # noqa: E402

TOL = 1e-4
D, F, E, K = 64, 128, 8, 2
OLMOE = dict(n_layers=2, n_kv_heads=8, n_experts=E, experts_per_token=K,
             qk_norm=True, tie_embeddings=False, dtype=jnp.float32)
ENGINE = dict(page_size=8, total_pages=64, max_batch=4, max_seq_len=128,
              prefill_chunk=16, prefill_rows=2, decode_chunk=4, seed=3)


# ---------------------------------------------------------------- ops/moe

def _expert_inputs(seed, T, bias_to=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    m = jax.random.normal(ks[0], (T, D))
    router = jax.random.normal(ks[1], (D, E)) * D ** -0.5
    if bias_to is not None:
        # every token's top-k is the same k experts: one group takes all
        router = router.at[:, bias_to].add(
            50.0 * jnp.sign(m.sum(axis=0))[:, None] / D)
        m = jnp.abs(m) * jnp.sign(m.sum(axis=0))[None]
    gate = jax.random.normal(ks[2], (E, D, F)) * D ** -0.5
    up = jax.random.normal(ks[3], (E, D, F)) * D ** -0.5
    down = jax.random.normal(ks[4], (E, F, D)) * F ** -0.5
    return m, router, gate, up, down


def _reference_layer(m, router, gate, up, down, renorm):
    with jax.default_matmul_precision("highest"):
        return ref.expert_layer(m, router, gate, up, down, K, renorm)


@pytest.mark.parametrize("impl", ["reference", "kernel"])
@pytest.mark.parametrize("case", ["full", "padding", "one_group"])
@pytest.mark.parametrize("renorm", [False, True])
def test_moe_ffn_matches_reference_expert_layer(impl, case, renorm):
    T = 40
    m, router, gate, up, down = _expert_inputs(
        1, T, bias_to=jnp.array([2, 5]) if case == "one_group" else None)
    valid = jnp.arange(T) % 3 != 0 if case == "padding" \
        else jnp.ones(T, bool)
    with jax.default_matmul_precision("highest"):
        y, counters = moe.moe_ffn(m, valid, router, gate, up, down, K, renorm,
                                  impl=impl, interpret=impl == "kernel")
    want, chosen = _reference_layer(m, router, gate, up, down, renorm)
    assert float(jnp.abs(y - jnp.where(valid[:, None], want, 0)).max()) < TOL
    chosen = np.asarray(chosen)[np.asarray(valid)]
    counts = np.bincount(chosen.reshape(-1), minlength=E)
    if case == "one_group":
        assert sorted(np.flatnonzero(counts)) == [2, 5]    # all in K groups
    assert counters.tolist() == [counts.sum(), (counts > 0).sum(),
                                 counts.max()]


def test_moe_ffn_is_dropless_a_token_ignores_its_batch():
    """A real token's output does not change with what else is in the
    batch: alone, among padding, among tokens that crowd its experts."""
    m, router, gate, up, down = _expert_inputs(2, 24)
    args = (router, gate, up, down, K, False)
    with jax.default_matmul_precision("highest"):
        alone, _ = moe.moe_ffn(m[:1], jnp.ones(1, bool), *args)
        padded, _ = moe.moe_ffn(m, jnp.arange(24) == 0, *args)
        crowd = jnp.concatenate([m[:1]] * 23 + [m[1:2]])
        crowded, _ = moe.moe_ffn(crowd, jnp.ones(24, bool), *args)
    assert float(jnp.abs(padded[0] - alone[0]).max()) < 1e-6
    assert float(jnp.abs(crowded[0] - alone[0]).max()) < 1e-6
    assert float(jnp.abs(padded[1:]).max()) == 0.0


def test_tiling_follows_the_static_shapes():
    assert moe._tiling(32 * 8, 64, 1024, 2048) == (16, 1024)      # decode loop
    assert moe._tiling(1056 * 8, 64, 1024, 2048)[0] == 128        # mixed step
    assert moe._tiling(40 * 2, 8, 128, 64) == (16, 128)


# ------------------------------------------------------------ the engine

@pytest.fixture(scope="module")
def olmoe():
    # compiled_step_programs() counts the process's shared jits: whatever
    # file this worker ran before must not count against this engine
    jax.clear_caches()
    cfg = LlamaConfig.tiny(**OLMOE)
    return cfg, InferenceEngine(cfg, **ENGINE)


def test_param_tree_has_the_blocks_leaves(olmoe):
    cfg, eng = olmoe
    shapes = jax.tree.map(lambda a: a.shape, eng.params)
    L, hd = cfg.n_layers, cfg.n_heads * cfg.head_dim
    assert shapes["lm_head"] == shapes["embed"] == (256, D)
    assert shapes["layers"]["router"] == (L, D, E)
    assert shapes["layers"]["q_norm"] == shapes["layers"]["k_norm"] == (L, hd)
    assert shapes["layers"]["w_gate"] == shapes["layers"]["w_up"] \
        == (L, E, D, F)
    assert shapes["layers"]["w_down"] == (L, E, F, D)


@pytest.mark.parametrize("renorm", [False, True])
@pytest.mark.parametrize("tied", [False, True])
@pytest.mark.parametrize("qk_norm", [False, True])
def test_each_variation_point_against_the_reference(renorm, tied, qk_norm):
    cfg = LlamaConfig.tiny(**{**OLMOE, "norm_topk_prob": renorm,
                              "tie_embeddings": tied, "qk_norm": qk_norm})
    params = init_params(cfg, jax.random.PRNGKey(5))
    # norms of ones would hide a norm that is skipped or misplaced
    lay = params["layers"]
    for k in ("q_norm", "k_norm"):
        if k in lay:
            lay[k] = 1.0 + 0.5 * jax.random.normal(
                jax.random.PRNGKey(len(k)), lay[k].shape)
    eng = InferenceEngine(cfg, params, **ENGINE)
    assert ("lm_head" in eng.params) == (not tied)
    assert ("q_norm" in eng.params["layers"]) == qk_norm
    prompt = list(range(11, 40))
    served = eng.generate(prompt, 9)
    assert worst_gap("olmoe", eng, prompt, served) < TOL
    # ... and the other setting of each point is NOT within tolerance:
    # the reference told apart what the flag changes
    dims = ref.dims_of(dataclasses.replace(cfg, norm_topk_prob=not renorm))
    other = ref.score_greedy(eng.params, dims, prompt, served, 96)
    assert max(other["gap"]) > 10 * TOL or \
        other["reference_tokens"] != served


def test_step_counters_equal_the_references_routing(olmoe):
    """moe_pairs / moe_hits / moe_hot against a numpy count of the
    reference's routing, step by step: ONE mixed step that holds both
    prefill chunks as its two rows, then eight one-token decode steps
    (9 tokens asked: the first comes from the prefill, the other eight
    fill two decode loops of 4 exactly, so no step runs past the
    request's end)."""
    cfg, eng = olmoe
    before = dict(eng.stats)
    prompt, n_new = list(range(50, 79)), 9       # 29 = rows of 16 + 13
    served = eng.generate(prompt, n_new)
    assert eng.stats["ragged_dispatches"] - before["ragged_dispatches"] == 1
    fed = prompt + served[:-1]                   # every token the engine ran
    with jax.default_matmul_precision("highest"):
        _, chosen = ref.forward(eng.params, jnp.asarray(fed, jnp.int32),
                                ref.dims_of(cfg))
    chosen = np.asarray(chosen)                  # [L, S, k]
    steps = [(0, 29)] + [(i, i + 1) for i in range(29, len(fed))]
    pairs = hits = hot = 0
    for lo, hi in steps:
        for layer in chosen:
            counts = np.bincount(layer[lo:hi].reshape(-1), minlength=E)
            pairs += counts.sum()
            hits += (counts > 0).sum()
            hot += counts.max()
    got = {k: eng.stats[k] - before[k] for k in moe.COUNTERS}
    assert got == {"moe_pairs": pairs, "moe_hits": hits, "moe_hot": hot}
    assert pairs == len(fed) * K * cfg.n_layers


def test_config_refuses_unknown_keys_and_bad_expert_counts():
    with pytest.raises(ValueError, match="num_experts"):
        LlamaConfig.tiny(num_experts=8)
    with pytest.raises(ValueError, match="experts_per_token"):
        LlamaConfig.tiny(n_experts=4, experts_per_token=5)
    from ray_tpu.llm.serve_llm import LLMServer
    with pytest.raises(ValueError, match="hidden_size"):
        LLMServer(model_config={"hidden_size": 64})


@pytest.fixture(scope="module")
def shaped_and_full():
    """The same weights behind the set of mixed-step shapes and behind
    the full shape alone (what every step ran in before the set)."""
    cfg = LlamaConfig.tiny(**OLMOE)
    params = init_params(cfg, jax.random.PRNGKey(5))
    return [InferenceEngine(cfg, params, **ENGINE),
            pin_full_shape(InferenceEngine(cfg, params, **ENGINE))]


@pytest.mark.parametrize("case", SHAPE_CASES)
def test_a_mixed_step_runs_the_smallest_shape_that_holds_its_rows(
        shaped_and_full, case):
    """One-row and two-row steps in turn through the EXPERTS' block: the
    routing counters ride behind the tokens of whichever shape ran, and
    the tokens are the full shape's."""
    check_shapes(case, *shaped_and_full)


def test_a_descriptor_holds_the_arrays_the_engine_packed_before():
    """Routed experts over a per-head pool: the routing counters ride
    behind the tokens whatever the step was sent in; every field of every
    descriptor the old packing's."""
    cfg = LlamaConfig.tiny(**OLMOE)
    params = init_params(cfg, jax.random.PRNGKey(5))
    check_descriptor(lambda **kw: InferenceEngine(
        cfg, params, **{**ENGINE, **kw}))


# ------------------------------------------- a dense configuration is as it was

def test_dense_configuration_is_untouched():
    cfg = LlamaConfig.tiny(n_layers=2, dtype=jnp.float32)
    eng = InferenceEngine(cfg, **ENGINE)
    assert set(eng.params) == {"embed", "layers", "final_norm"}
    assert set(eng.params["layers"]) == {
        "attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate", "w_up",
        "w_down"}
    assert eng.params["layers"]["w_gate"].shape == (2, 64, 128)
    assert set(eng.stats) == {
        "steps", "prefill_tokens", "decode_steps", "decode_tokens",
        "decode_dispatches", "cached_tokens", "ragged_dispatches",
        "ragged_real_tokens", "ragged_slot_tokens", "cow_copies",
        "preemptions", "chunk_rows", "chunk_rows_joined", "walk_tokens",
        "walk_tokens_left", "ragged_small_dispatches", "h2d_arrays",
        "ahead_dispatches",
        "late_retired_rows", "ahead_drains", "held_launches",
        "late_launches", "late_mixed_launches"} \
        | set(WALL_KEYS + (CPU_KEY,)) \
        | set(startup_clocks.ENGINE_KEYS)            # every model's clocks
    # the step programs' outputs keep their shapes: [R] and [K, B]
    from ray_tpu.llm import model as M
    kv = eng.kv
    B, mp = ENGINE["max_batch"], eng.max_pages_per_seq
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)    # noqa: E731
    loop = jax.eval_shape(
        functools.partial(M._ragged_decode_loop, num_steps=4, cfg=cfg),
        eng.params, i32(B), i32(B), kv, i32(B, mp), i32(B))
    assert loop[0].shape == (4, B)
    T, R = eng.ragged_tokens, eng.ragged_rows
    step = jax.eval_shape(
        functools.partial(M._ragged_step_body, cfg=cfg),
        eng.params, i32(T), i32(T), i32(T), i32(T), i32(R, mp), i32(R),
        i32(R), i32(R), kv)
    assert step[0].shape == (R,)
    # and the same request, served, matches the reference's dense branch
    prompt = list(range(1, 30))
    served = eng.generate(prompt, 9)
    assert worst_gap("olmoe", eng, prompt, served) < TOL
