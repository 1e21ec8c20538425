"""The GigaChat3.5 block in the serving engine: gated-delta-rule layers whose
float32 matrix state and conv inputs live per batch slot BESIDE a latent
page leaf (every fourth layer latent attention with a low-rank query, YaRN
frequencies, an output gate), sigmoid-gated norms before and after every
branch, clamped SwiGLUs, a leading dense layer, then a held share of the
routed experts beside a shared one; through the one ragged step and the
decode loop, against the benchmark's plain SEQUENTIAL reference
(benchmark/reference_gigachat.py) on seeded weights. Tiny widths on the CPU,
float32 compute; the pattern is the served cut's: a dense delta layer, then
one period (delta, delta, delta, latent) of expert layers.

TOL: everything runs in float32 here, so the two sides differ by summation
order, by the chunk form's solve and by the absorbed products: ~1e-5 on
logits of spread ~1 (a norm AFTER each branch rescales a small branch to
unit size and its rounding with it), the worst of some 40 positions read
1.4e-4. 3e-4 leaves room and still fails each part left out
(test_no_part_is_left_out: every fault moves a logit by 3e-2 and more), a
row that starts from another sequence's state, a conv that forgets its
saved inputs, a state held in bf16.
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

from _blocks import served_logits  # noqa: E402
from _chunk_rows import check_state_keeps_one_row  # noqa: E402
from benchmark import reference_gigachat as ref  # noqa: E402
from ray_tpu.llm import InferenceEngine  # noqa: E402
from ray_tpu.llm import model as M  # noqa: E402
from ray_tpu.llm.cache import (DELTA_CONV_LEAF, DELTA_LEAF,  # noqa: E402
                               SLOT_STATE, STATE_LEAVES, keeps_slot_state,
                               make_kv_cache, prefix_cache_supported)
from ray_tpu.models import llama  # noqa: E402
from ray_tpu.models.llama import DELTA, LlamaConfig, init_params  # noqa: E402
from ray_tpu.ops import moe  # noqa: E402

TOL = 3e-4
HK, HV, DK, DV = 2, 4, 8, 16
CH = 2 * HK * DK + HV * DV
GIGA = dict(
    vocab_size=128, dim=64, n_layers=5, n_heads=4, n_kv_heads=4, ffn_dim=32,
    dense_ffn_dim=96, n_dense_layers=1, n_experts=8, experts_per_token=3,
    norm_topk_prob=True, router_score="sigmoid", router_bias=True,
    router_scale=2.5, router_eps=1e-20, shared_ffn_dim=32,
    tie_embeddings=False, rope_theta=100000.0, norm_eps=1e-6,
    layer_types=[DELTA] * 4 + ["full_attention"],
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    q_lora_rank=24, rope_yarn=(8, 16, 32, 1, 1, 1),
    attn_scale=24 ** -0.5 * llama.yarn_mscale(8, 1) ** 2, attn_gate=True,
    post_norms=True, norm_gate=2.0, ffn_clamp=2.0, delta_key_heads=HK,
    delta_value_heads=HV, delta_key_dim=DK, delta_value_dim=DV,
    delta_chunk=8, experts_held=(2, 4), dtype=jnp.float32)
ENGINE = dict(page_size=8, total_pages=64, max_batch=4, max_seq_len=128,
              prefill_chunk=16, prefill_rows=2, decode_chunk=4, seed=3)


@pytest.fixture(scope="module")
def giga():
    jax.clear_caches()
    cfg = LlamaConfig.tiny(**GIGA)
    return cfg, InferenceEngine(cfg, init_params(cfg, jax.random.PRNGKey(5)),
                                **ENGINE)


def _reference_logits(params, cfg, tokens, **how):
    with jax.default_matmul_precision("highest"):
        return ref.forward(params, jnp.asarray(tokens, jnp.int32),
                           ref.dims_of(cfg), **how)[0]


def test_param_tree_pool_and_pattern(giga):
    cfg, eng = giga
    layers = eng.params["layers"]
    assert sorted(layers) == ["attn", "delta", "dense", "moe"]
    assert layers["delta"]["w_qkv"].shape == (4, 64, CH)
    assert layers["delta"]["w_ba"].shape == (4, 64, 2 * HV)
    assert layers["delta"]["w_conv"].dtype == jnp.float32
    assert layers["attn"]["wq_a"].shape == (1, 64, 24)
    assert layers["attn"]["wq"].shape == (1, 24, 4 * 24)
    assert layers["attn"]["w_og"].shape == (1, 64, 4 * 16)
    assert layers["moe"]["w_gate"].shape == (4, 4, 64, 32)   # 4 of 8 held
    assert layers["moe"]["router"].shape == (4, 64, 8)
    assert "w_shared_gate" in layers["moe"]
    # every norm's weight is drawn around 0: 2 sigmoid(w) around 1
    for kind, stack in layers.items():
        for k, w in stack.items():
            if k.endswith("norm"):
                assert float(jnp.abs(w).max()) <= 0.5 < 2 * float(
                    jnp.abs(w).max()), (kind, k)
    a = np.exp(np.asarray(layers["delta"]["A_log"]))
    assert 1.0 <= a.min() and a.max() <= 16.0
    # ONE pool: the latent leaf (one row a token, no "v") AND both delta
    # leaves, the matrix state float32 whatever the model's dtype
    assert sorted(eng.kv) == sorted(["k", DELTA_LEAF, DELTA_CONV_LEAF])
    assert eng.kv["k"].shape == (1, 64, 1, 8, 32 + 8)
    assert eng.kv[DELTA_LEAF].shape == (4, 5, HV, DK, DV)
    assert eng.kv[DELTA_CONV_LEAF].shape == (4, 5, 3, CH)
    half = LlamaConfig.tiny(**{**GIGA, "dtype": jnp.bfloat16})
    kv = make_kv_cache(half, 4, 8, max_batch=1)
    assert kv[DELTA_LEAF].dtype == jnp.float32
    assert kv[DELTA_CONV_LEAF].dtype == kv["k"].dtype == jnp.bfloat16
    assert set(SLOT_STATE[DELTA]) == {DELTA_LEAF, DELTA_CONV_LEAF} \
        <= set(STATE_LEAVES)
    per_slot = 4 * (HV * DK * DV + 3 * CH) * 4
    assert eng.stats["state_bytes_per_slot"] == per_slot
    assert eng.stats["kv_row_width"] == 40
    assert keeps_slot_state(cfg) and not prefix_cache_supported(cfg)
    assert eng.prefix is None
    assert M.step_counters(cfg) == moe.COUNTERS + (moe.COUNTER_ABSENT,)
    assert DELTA in M.OPERATORS
    # the leading dense layer, then ONE scan over one period of four
    assert M._pattern(cfg) == (
        [(DELTA, "dense")],
        [(DELTA, "moe")] * 3 + [("full_attention", "moe")], 1)


def test_a_sequence_that_prefills_alone_keeps_one_row_a_step(giga):
    check_state_keeps_one_row(giga[1])


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_no_part_is_left_out(giga, fault):
    """The reference with ONE part left out stands a hundred tolerances off
    the program: the comparison can see the read of the delta rule, the
    decay, the YaRN frequencies and scale, the output gate and the sigmoid
    in the norms."""
    cfg, eng = giga
    prompt = list(np.random.default_rng(6).integers(0, 128, 40))
    got, _ = served_logits(cfg, eng.params, prompt, chunk=64)
    whole = _reference_logits(eng.params, cfg, prompt)[-1]
    broken = _reference_logits(eng.params, cfg, prompt, fault=fault)[-1]
    assert float(jnp.abs(got[-1] - whole).max()) < TOL
    assert float(jnp.abs(got[-1] - broken).max()) > 100 * TOL


def test_the_clamp_is_seen(giga):
    """ffn_clamp 2 cuts seeded activations; without it the logits move."""
    cfg, eng = giga
    prompt = list(np.random.default_rng(6).integers(0, 128, 24))
    loose = dataclasses.replace(cfg, ffn_clamp=0.0)
    a, _ = served_logits(cfg, eng.params, prompt, chunk=32)
    b, _ = served_logits(loose, eng.params, prompt, chunk=32)
    assert float(jnp.abs(a[-1] - b[-1]).max()) > 100 * TOL


def test_a_state_held_in_bf16_is_told_apart(giga):
    cfg, eng = giga
    prompt = list(np.random.default_rng(6).integers(0, 128, 40))
    whole = _reference_logits(eng.params, cfg, prompt)[-1]
    held = _reference_logits(eng.params, cfg, prompt,
                             state_hold=jnp.bfloat16)[-1]
    assert float(jnp.abs(whole - held).max()) > 10 * TOL


def test_correct_tells_a_pool_that_holds_the_state_in_bf16(giga, monkeypatch):
    """What a replica's reference check reads of a scored request
    (benchmark/replica_gigachat.py): the PROGRAM's two forms of the
    recurrence, over a state leaf made as the pool makes it, against the
    reference's recurrence on the same inputs. As served it lies far inside
    the cell's limit; with the pool's leaf in bf16 (what a later change
    could do to halve the step's largest stream) far outside."""
    from benchmark import checks_gigachat
    from benchmark.replica_gigachat import served_state
    cfg, eng = giga
    toks = np.random.default_rng(8).integers(0, 128, 200).astype(np.int32)
    inputs, want = ref.first_layer_state(eng.params, toks, 150,
                                         ref.dims_of(cfg))
    got = served_state(cfg, inputs, 100, 150, 16, "reference")
    assert ref.state_error(got, want) < checks_gigachat.MAX_STATE_ERROR / 100
    shape, _ = SLOT_STATE[DELTA][DELTA_LEAF](cfg)
    monkeypatch.setitem(SLOT_STATE[DELTA], DELTA_LEAF,
                        lambda c: (shape, jnp.bfloat16))
    held = served_state(cfg, inputs, 100, 150, 16, "reference")
    assert held.dtype == jnp.bfloat16
    assert ref.state_error(held, want) > checks_gigachat.MAX_STATE_ERROR


def test_the_shares_add_up_to_the_uncut_layer(giga):
    """The share ties to the model: the routed parts that the FOUR shares
    of 2 experts give (the program's expert layer, told which it holds),
    plus the shared expert counted ONCE, are what the uncut reference
    gives for the whole layer of 8."""
    cfg, _ = giga
    whole = dataclasses.replace(cfg, experts_held=())
    params = init_params(whole, jax.random.PRNGKey(9))
    stack = params["layers"]["moe"]
    z = jax.random.normal(jax.random.PRNGKey(1), (23, cfg.dim))
    dims = ref.dims_of(whole)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.expert_ffn(
            z, {k: stack[k][1] for k in ("router", "router_bias")}, stack,
            1, dims, lambda a: a)
        routed, absent = 0.0, []
        for first in range(0, 8, 2):
            y, counters = moe.moe_ffn(
                z, jnp.ones(23, bool), stack["router"][1],
                *(stack[k][1][first:first + 2]
                  for k in ("w_gate", "w_up", "w_down")),
                cfg.experts_per_token, True, impl="reference",
                held=(first, 2), clamp=cfg.ffn_clamp, score="sigmoid",
                bias=stack["router_bias"][1], eps=cfg.router_eps,
                scale=cfg.router_scale)
            routed = routed + y
            absent.append(int(counters[3]))
        shared = (moe.gate_half(z @ stack["w_shared_gate"][1],
                                cfg.ffn_clamp)
                  * moe.up_half(z @ stack["w_shared_up"][1],
                                cfg.ffn_clamp)) @ stack["w_shared_down"][1]
    assert float(jnp.abs(routed + shared - want).max()) < TOL
    # every pair is served by exactly one share
    assert sum(absent) == 3 * 23 * 3


def test_config_refuses_half_a_delta_rule_block():
    with pytest.raises(ValueError, match="delta_key_heads"):
        LlamaConfig.tiny(n_layers=2, layer_types=[DELTA, DELTA])
    with pytest.raises(ValueError, match="layer_types names none"):
        LlamaConfig.tiny(delta_key_heads=2, delta_value_heads=4,
                         delta_key_dim=8, delta_value_dim=8)
    with pytest.raises(ValueError, match="power of two"):
        LlamaConfig.tiny(**{**GIGA, "delta_chunk": 24})
    with pytest.raises(ValueError, match="linear_attention.*beside conv"):
        LlamaConfig.tiny(n_layers=2, layer_types=[DELTA, "conv"],
                         delta_key_heads=2, delta_value_heads=4,
                         delta_key_dim=8, delta_value_dim=8)
    with pytest.raises(ValueError, match="need kv_lora_rank"):
        LlamaConfig.tiny(q_lora_rank=8)
    with pytest.raises(ValueError, match="rope_yarn"):
        LlamaConfig.tiny(**{**GIGA, "rope_yarn": (8, 16)})
    # a gate on every norm: not on an operator that norms without it
    with pytest.raises(ValueError, match="EVERY norm"):
        LlamaConfig.tiny(norm_gate=2.0)


