"""The granite-4.0-h block in the serving engine: Mamba-2 state-space layers
whose matrix state and conv inputs live per batch slot beside the paged KV
of the attention layers, attention with no positional embedding and a score
scale that is a field, and the four multipliers, through the one ragged
step and the decode loop, against the benchmark's plain SEQUENTIAL
reference (benchmark/reference_granite.py) on seeded weights. Tiny widths on
the CPU, float32 compute; the pattern is two periods of (mamba mamba
attention mamba).

TOL: everything runs in float32 here (cfg.dtype, the state as held and the
reference), so the two sides differ by summation order only: ~1e-6 on
logits of spread ~0.1 (they are divided by logits_divisor 8). 1e-4 leaves
room and still fails a bf16 computation (~1e-3 and more:
test_ragged_scan_and_update_against_the_sequential_form runs one), a row
that starts from another sequence's state or from zeros mid-sequence, a
conv without its bias, a D that is left out, a norm before the gate, a
rotary embedding, a score scale of head_dim ** -0.5, a multiplier of 1.
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

from _blocks import chunked_logits, reference_logits, seeded  # noqa: E402
from _chunk_rows import (check_descriptor,  # noqa: E402
                         check_state_keeps_one_row, SHAPE_CASES,
                         check_shapes, pin_full_shape)
from ray_tpu.llm import InferenceEngine  # noqa: E402
from ray_tpu.llm import model as M  # noqa: E402
from ray_tpu.llm.cache import (SSM_CONV_LEAF, SSM_LEAF,  # noqa: E402
                               make_kv_cache, prefix_cache_supported)
from ray_tpu.models.llama import LlamaConfig, init_params  # noqa: E402
from ray_tpu.ops import ssm  # noqa: E402

TOL = 1e-4
D, H, P, N = 64, 8, 16, 16
PATTERN = ["mamba", "mamba", "full_attention", "mamba"] * 2
GRANITE = dict(n_layers=8, n_heads=8, n_kv_heads=2, ffn_dim=96,
               layer_types=PATTERN, ssm_heads=H, ssm_head_dim=P, ssm_state=N,
               ssm_chunk=8, rope=False, attn_scale=1 / 64, embed_scale=12.0,
               residual_scale=0.22, logits_divisor=8.0, dtype=jnp.float32)
ENGINE = dict(page_size=8, total_pages=64, max_batch=4, max_seq_len=128,
              prefill_chunk=16, prefill_rows=2, decode_chunk=4, seed=3)


@pytest.fixture(scope="module")
def granite():
    jax.clear_caches()
    cfg = LlamaConfig.tiny(**GRANITE)
    return cfg, InferenceEngine(cfg, seeded(cfg), **ENGINE)


# ----------------------------------------------------------- ops/ssm.py

def _ragged_case(dtype=jnp.float32):
    """Three one-token rows (one of them empty) and two chunk rows, one
    continuing a sequence at position 5 and one starting at 0, padding
    after; over a state leaf that holds garbage."""
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    L, S = 2, 5
    T = 3 + 20 + 9 + 4
    case = dict(
        state=jax.random.normal(ks[0], (L, S + 1, N, H * P)).astype(dtype),
        x=jax.random.normal(ks[1], (T, H, P)),
        dt=jax.nn.softplus(jax.random.normal(ks[2], (T, H)) - 2.0),
        A=-jnp.exp(jax.random.uniform(ks[3], (H,), maxval=2.7)),
        B=jax.random.normal(ks[4], (T, N)), C=jax.random.normal(ks[5], (T, N)),
        D=jax.random.normal(ks[6], (H,)),
        pos=jnp.concatenate([jnp.asarray([7, 0, 3]), jnp.arange(20),
                             5 + jnp.arange(9), jnp.zeros(4, jnp.int32)]
                            ).astype(jnp.int32),
        q_start=jnp.asarray([0, 1, 2, 3, 23], jnp.int32),
        q_len=jnp.asarray([1, 0, 1, 20, 9], jnp.int32),
        row_slot=jnp.asarray([0, 5, 2, 3, 1], jnp.int32))
    return case, L, S


def _sequences(case, layer):
    """The same rows, each as a sequence of its own through the published
    token-by-token recurrence in numpy float64."""
    c = {k: np.asarray(v, np.float64) for k, v in case.items()}
    y = np.zeros(c["x"].shape)
    # the leaf holds a slot's [H, P, N] state as [N, H P]
    state = c["state"].reshape(c["state"].shape[:3] + (H, P)).transpose(
        0, 1, 3, 4, 2).copy()
    for r in range(len(c["q_len"])):
        lo, n = int(c["q_start"][r]), int(c["q_len"][r])
        if not n:
            continue
        slot = int(c["row_slot"][r])
        s = np.zeros((H, P, N)) if c["pos"][lo] == 0 else state[layer, slot]
        for t in range(lo, lo + n):
            s = np.exp(c["dt"][t] * c["A"])[:, None, None] * s \
                + (c["dt"][t][:, None] * c["x"][t])[:, :, None] \
                * c["B"][t][None, None, :]
            y[t] = s @ c["C"][t] + c["D"][:, None] * c["x"][t]
        state[layer, slot] = s
    return y, state.transpose(0, 1, 4, 2, 3).reshape(c["state"].shape)


@pytest.mark.parametrize("dtype,within", [(jnp.float32, True),
                                          (jnp.bfloat16, False)])
@pytest.mark.parametrize("chunk", [256, 8, 5])
def test_ragged_scan_and_update_against_the_sequential_form(chunk, dtype,
                                                            within):
    """ssm_chunk_scan over a ragged batch of one-token rows and chunk rows,
    at a block of the whole batch, one that divides a row and one that
    divides nothing, against the sequential recurrence of each sequence
    (and against ssm_scan_reference, lax.scan over the tokens): y on owned
    tokens and the state every row leaves; slots no row names, the other
    layer and, for padding tokens, D x alone, stay what they were. With
    the state HELD in bfloat16 the same comparison is not within TOL."""
    case, L, S = _ragged_case(dtype)
    want_y, want_s = _sequences(case, 1)
    owned = np.r_[0, 2, 3:32]
    for fn in (ssm.ssm_scan_reference,
               lambda *a: ssm.ssm_chunk_scan(*a[:-1], layer=a[-1],
                                             chunk=chunk, impl="kernel")):
        y, state = fn(*(case[k] for k in (
            "state", "x", "dt", "A", "B", "C", "D", "pos", "q_start",
            "q_len", "row_slot")), 1)
        assert state.dtype == dtype
        worst = max(np.abs(np.asarray(y)[owned] - want_y[owned]).max(),
                    np.abs(np.asarray(state, np.float64)[1, :S]
                           - want_s[1, :S]).max())
        assert (worst < TOL) == within, worst
        # layer 0 and slot 4 (no row's) bit for bit; padding gives D x
        assert np.array_equal(np.asarray(state[0]), np.asarray(
            case["state"][0]))
        assert np.array_equal(np.asarray(state[1, 4]),
                              np.asarray(case["state"][1, 4]))
        pad = np.asarray(y)[32:]
        assert np.allclose(pad, np.asarray(
            case["D"][None, :, None] * case["x"][32:]), atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_update_kernel_in_interpret_mode(dtype):
    """The Pallas update through the interpreter against the formula: four
    rows, one fresh (its slot's garbage is not read), two on the scratch
    slot; y, the rows' slots, and EVERY other slot and layer bit for bit
    what it was (the state is aliased: only the rows' slots move). In
    bfloat16 the state is rounded once, after the float32 update: equal
    to the reference's rounding bit for bit."""
    case, L, S = _ragged_case(dtype)
    slots = jnp.asarray([2, 0, 5, 5], jnp.int32)
    fresh = jnp.asarray([False, True, False, False])
    args = [case["state"]] + [case[k][:4] if k in ("x", "dt", "B", "C")
                              else case[k] for k in ("x", "dt", "A", "B",
                                                     "C", "D")]
    want_y, want_s = ssm.ssm_decode_reference(*args, slots, fresh, 1)
    y, state = ssm.ssm_decode_update(*args, slots, fresh, layer=1,
                                     interpret=True)
    assert float(jnp.abs(y - want_y).max()) < 1e-5
    got, want = np.asarray(state, np.float32), np.asarray(want_s, np.float32)
    assert np.abs(got[1, [0, 2]] - want[1, [0, 2]]).max() < \
        (1e-6 if dtype == jnp.float32 else 1e-2)
    untouched = np.asarray(case["state"], np.float32)
    assert np.array_equal(got[0], untouched[0])
    assert np.array_equal(got[1, [1, 3, 4]], untouched[1, [1, 3, 4]])
    # a fresh row's result does not depend on what its slot held
    poisoned = case["state"].at[1, 0].set(jnp.nan)
    y2, _ = ssm.ssm_decode_update(poisoned, *args[1:], slots, fresh,
                                  layer=1, interpret=True)
    assert np.array_equal(np.asarray(y2[1]), np.asarray(y[1]))


# ------------------------------------------------------------ the block

def test_param_tree_pool_and_pattern(granite):
    cfg, eng = granite
    shapes = jax.tree.map(lambda a: a.shape, eng.params["layers"])
    assert set(shapes) == {"attn", "mamba", "dense"}
    ch = H * P + 2 * N
    assert cfg.ssm_channels == ch
    assert shapes["mamba"]["w_gate"] == (6, D, H * P)
    assert shapes["mamba"]["w_xbc"] == (6, D, ch)
    assert shapes["mamba"]["w_dt"] == (6, D, H)
    assert shapes["mamba"]["w_conv"] == (6, 4, ch)
    assert shapes["mamba"]["b_conv"] == (6, ch)
    assert shapes["attn"]["wq"] == (2, D, D) and "q_norm" not in shapes["attn"]
    assert shapes["dense"]["w_gate"] == (8, D, 96)
    for k in ("w_conv", "b_conv", "dt_bias", "A_log", "D"):
        assert eng.params["layers"]["mamba"][k].dtype == jnp.float32
    # the family's initialisation: A in -[1, 16], dt in [1e-3, 1e-1]
    fresh = init_params(cfg, jax.random.PRNGKey(0))["layers"]["mamba"]
    a, dt = np.exp(fresh["A_log"]), np.asarray(jax.nn.softplus(
        fresh["dt_bias"]))
    assert 1.0 <= a.min() and a.max() <= 16.0
    assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 1e-1 * 1.001
    assert float(jnp.abs(fresh["b_conv"]).min()) > 0.0
    # pages for the two attention layers only; both state leaves a slot
    assert eng.kv["k"].shape == (2, 64, 2, 8, 8)
    assert eng.kv[SSM_LEAF].shape == (6, ENGINE["max_batch"] + 1, N, H * P)
    assert eng.kv[SSM_CONV_LEAF].shape == (6, ENGINE["max_batch"] + 1, 3, ch)
    per_slot = 6 * (H * P * N + 3 * ch) * 4
    report = eng.device_report()
    assert report["state_bytes_per_slot"] == per_slot \
        == eng.stats["state_bytes_per_slot"]
    assert report["state_bytes"] == per_slot * 5 == eng.stats["state_bytes"]
    assert report["kv_bytes"] == sum(a.nbytes for a in eng.kv.values())
    assert not prefix_cache_supported(cfg)
    # ONE scan over the periods, its body one period
    assert M._pattern(cfg) == ([], [
        ("mamba", "dense"), ("mamba", "dense"), ("full_attention", "dense"),
        ("mamba", "dense")], 2)


def test_a_descriptor_holds_the_arrays_the_engine_packed_before():
    """State-space layers: token_state is a field of the descriptor, a
    sequence keeps one row a step, no prefix cache; every field the old
    packing's."""
    cfg = LlamaConfig.tiny(**GRANITE)
    params = seeded(cfg)
    check_descriptor(lambda **kw: InferenceEngine(
        cfg, params, **{**ENGINE, **kw}))


def test_a_sequence_that_prefills_alone_keeps_one_row_a_step(granite):
    check_state_keeps_one_row(granite[1])


@pytest.fixture(scope="module")
def shaped_and_full():
    """The same weights behind the set of mixed-step shapes and behind
    the full shape alone (what every step ran in before the set)."""
    cfg = LlamaConfig.tiny(**GRANITE)
    params = seeded(cfg)
    return [InferenceEngine(cfg, params, **ENGINE),
            pin_full_shape(InferenceEngine(cfg, params, **ENGINE))]


@pytest.mark.parametrize("case", SHAPE_CASES)
def test_a_mixed_step_runs_the_smallest_shape_that_holds_its_rows(
        shaped_and_full, case):
    """One-row and two-row steps in turn through the STATE-SPACE layers:
    a row's slot and the scratch slot are addressed through token_state
    in either shape, and the tokens are the full shape's."""
    check_shapes(case, *shaped_and_full)


@pytest.mark.parametrize("field,other", [
    ("embed_scale", 1.0), ("residual_scale", 1.0), ("logits_divisor", 1.0),
    ("attn_scale", 8 ** -0.5)])
def test_each_multiplier_is_told_apart_by_the_reference(granite, field,
                                                        other):
    """LOGITS of the mixed step's forward against the reference layer by
    layer's sum: within TOL with the multipliers as configured, and NOT
    within it against the reference with that one multiplier at 1 (the
    score scale at head_dim ** -0.5)."""
    cfg, eng = granite
    prompt = list(range(11, 40))
    got, _ = chunked_logits(cfg, eng.params, prompt, 16)
    want = reference_logits("granite", eng.params, cfg, prompt)[-1]
    assert float(jnp.abs(got - want).max()) < TOL
    wrong = reference_logits("granite", eng.params, dataclasses.replace(
        cfg, **{field: other}), prompt)[-1]
    assert float(jnp.abs(got - wrong).max()) > 10 * TOL
    # and the program follows the field: served with it at 1, it differs
    cfg2 = dataclasses.replace(cfg, **{field: 0.0 if field == "attn_scale"
                                       else other})
    got2, _ = chunked_logits(cfg2, eng.params, prompt, 16)
    assert float(jnp.abs(got2 - got).max()) > 10 * TOL
    assert float(jnp.abs(got2 - wrong).max()) < TOL


def test_attention_applies_no_rotary_embedding(granite):
    """rope=False against the reference (which has none): within TOL; the
    same weights served WITH the rotary embedding are not."""
    cfg, eng = granite
    prompt = list(range(30, 62))
    want = reference_logits("granite", eng.params, cfg, prompt)[-1]
    got, _ = chunked_logits(cfg, eng.params, prompt, 16)
    assert float(jnp.abs(got - want).max()) < TOL
    roped, _ = chunked_logits(dataclasses.replace(cfg, rope=True),
                               eng.params, prompt, 16)
    assert float(jnp.abs(roped - want).max()) > 5 * TOL


@pytest.mark.parametrize("leaf", ["b_conv", "D", "dt_bias", "A_log",
                                  "gate_norm"])
def test_no_part_of_the_operator_is_left_out(granite, leaf):
    """Each small leaf of the operator moves the served logits: the
    reference with that leaf neutralised (zeros; ones for the norm) does
    not agree with what was served."""
    cfg, eng = granite
    prompt = list(range(2, 30))
    got, _ = chunked_logits(cfg, eng.params, prompt, 16)
    stack = eng.params["layers"]["mamba"]
    flat = jnp.ones_like(stack[leaf]) if leaf == "gate_norm" \
        else jnp.zeros_like(stack[leaf])
    params = {**eng.params, "layers": {**eng.params["layers"], "mamba": {
        **stack, leaf: flat}}}
    wrong = reference_logits("granite", params, cfg, prompt)[-1]
    assert float(jnp.abs(got - wrong).max()) > 10 * TOL


def test_lane_padded_pool_keeps_the_configured_scale(granite):
    """The pool the kernels take (rows of 128 lanes): q, k and v are
    zero-padded to the pool's width and the scale stays attn_scale."""
    cfg, eng = granite
    prompt = list(range(20, 55))
    kv = make_kv_cache(cfg, 16, 8, max_batch=3, lane_pad=True)
    assert kv["k"].shape[-1] == 128
    padded, _ = chunked_logits(cfg, eng.params, prompt, 16, kv=kv)
    plain, _ = chunked_logits(cfg, eng.params, prompt, 16)
    assert float(jnp.abs(padded - plain).max()) < 1e-6


# ------------------------------------------------------------ refusals

def test_config_refuses_half_a_state_space_block():
    with pytest.raises(ValueError, match="ssm_state"):
        LlamaConfig.tiny(n_layers=2, layer_types=["mamba", "mamba"])
    with pytest.raises(ValueError, match="layer_types names none"):
        LlamaConfig.tiny(ssm_state=16, ssm_heads=8, ssm_head_dim=16)
    with pytest.raises(ValueError, match="mamba.*beside experts"):
        LlamaConfig.tiny(**{**GRANITE, "n_experts": 4,
                            "experts_per_token": 2})

