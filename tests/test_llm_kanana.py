"""The Kanana-2 block in the serving engine: latent attention (MLA) over a
paged LATENT pool of one leaf, read once for all heads in the absorbed
form, a shared expert beside routed experts (sigmoid scores, selection
bias, renormalised, scaled by 2.448), one leading dense layer, an untied
head, through the one ragged step and the decode loop, against the
benchmark's plain reference (benchmark/reference_kanana.py: the PUBLISHED,
expanded form) on seeded weights. Tiny widths on the CPU, float32 compute.

TOL: everything runs in float32 here (cfg.dtype and the reference), so the
two sides differ by summation order only (the absorbed products associate
differently from the expanded ones): ~1e-6 on unit-variance logits. 1e-4
leaves two orders of room and still fails a bf16 computation (~1e-2:
test_absorbed_attention_equals_the_expanded_form runs one), a value taken
from the wrong lanes of the row, a rotary over half-split pairs, a shared
expert counted per routed expert or not at all, a routing scale left out,
a prefix hit or a copy on write that misses the latent leaf (whole logits,
or tenths).
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

from _blocks import seeded  # noqa: E402
from _chunk_rows import (CASES, check, check_descriptor,  # noqa: E402
                         check_preempted, SHAPE_CASES, check_shapes,
                         pin_full_shape)
from benchmark import reference_kanana as ref  # noqa: E402
from benchmark import reference_lfm2  # noqa: E402
from ray_tpu.llm import InferenceEngine  # noqa: E402
from ray_tpu.llm import model as M  # noqa: E402
from ray_tpu.llm.cache import (kv_cache_tag, latent_row_width,  # noqa: E402
                               make_kv_cache)
from ray_tpu.models import llama  # noqa: E402
from ray_tpu.models.llama import LlamaConfig  # noqa: E402
from ray_tpu.ops import paged_attention as pa  # noqa: E402

TOL = 1e-4
D, E, K, H = 64, 8, 3, 4
RANK, NOPE, ROPE, V = 32, 16, 8, 16
KANANA = dict(dim=D, n_layers=3, n_heads=H, n_kv_heads=H, ffn_dim=16,
              dense_ffn_dim=96, n_dense_layers=1, n_experts=E,
              experts_per_token=K, norm_topk_prob=True,
              router_score="sigmoid", router_bias=True, router_eps=1e-20,
              router_scale=2.448, kv_lora_rank=RANK, qk_nope_head_dim=NOPE,
              qk_rope_head_dim=ROPE, v_head_dim=V,
              shared_ffn_dim=32, tie_embeddings=False, norm_eps=1e-6,
              dtype=jnp.float32)
ENGINE = dict(page_size=8, total_pages=64, max_batch=4, max_seq_len=128,
              prefill_chunk=16, prefill_rows=2, decode_chunk=4, seed=3)


@pytest.fixture(scope="module")
def kanana():
    jax.clear_caches()
    cfg = LlamaConfig.tiny(**KANANA)
    return cfg, InferenceEngine(cfg, seeded(cfg), **ENGINE)


# ------------------------------------------------------------ the operators

def test_adjacent_pair_rotary_is_a_complex_rotation():
    """Pair j of the last axis, as the complex number x[2j] + i x[2j+1],
    is multiplied by exp(i * position * theta^(-2j/D)): the program's
    _rope_pairs and the reference's rope_pairs against that statement."""
    theta, S, Hh, Dr = 1e4, 11, 3, 8
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (S, Hh, Dr)))
    pos = np.arange(5, 5 + S)
    z = x[..., 0::2] + 1j * x[..., 1::2]
    ang = pos[:, None, None] * theta ** (-2.0 * np.arange(Dr // 2) / Dr)
    want = z * np.exp(1j * ang)
    want = np.stack([want.real, want.imag], axis=-1).reshape(S, Hh, Dr)
    got = llama._rope_pairs(jnp.asarray(x)[None], jnp.asarray(pos), theta)[0]
    assert float(np.abs(np.asarray(got) - want).max()) < 1e-5
    # the reference's positions start at 0
    want0 = z * np.exp(1j * np.arange(S)[:, None, None]
                       * theta ** (-2.0 * np.arange(Dr // 2) / Dr))
    want0 = np.stack([want0.real, want0.imag], axis=-1).reshape(S, Hh, Dr)
    got0 = ref.rope_pairs(jnp.asarray(x), theta)
    assert float(np.abs(np.asarray(got0) - want0).max()) < 1e-5
    # and it is NOT the half-split pairing of the other blocks
    half = llama._rope(jnp.asarray(x)[None], jnp.asarray(pos), theta)[0]
    assert float(np.abs(np.asarray(half) - want).max()) > 0.1


def _one_layer(cfg, S, seed=2):
    params = seeded(cfg, seed)
    lp = {k: w[0] for k, w in params["layers"]["attn"].items()}
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (1, S, cfg.dim))
    return lp, x.astype(cfg.dtype)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, TOL),
                                       (jnp.bfloat16, None)])
def test_absorbed_attention_equals_the_expanded_form(dtype, tol):
    """The program's operator (w_uk folded into the query, the kernel's
    reference over the latent rows, w_uv on the way out) against the
    reference's expanded attention (the latent up-projected to per-head
    keys and values) on the same weights: one prompt of 21 tokens as one
    chunk row over three pages. In float32 they agree to TOL; the same
    operator computed in bf16 is off by two orders more, which TOL
    therefore refuses."""
    cfg = LlamaConfig.tiny(**{**KANANA, "dtype": dtype})
    S, ps = 21, 8
    lp, x = _one_layer(cfg, S)
    kv = make_kv_cache(cfg, 8, ps)
    pos = jnp.arange(S, dtype=jnp.int32)
    pages = jnp.asarray([3, 5, 6], jnp.int32)
    rows = M._Rows(
        pos, None, jnp.zeros(1, jnp.int32), jnp.full(1, S, jnp.int32),
        token_page=pages[pos // ps], token_slot=pos % ps,
        page_table=pages[None], kv_len=jnp.full(1, S, jnp.int32),
        max_q_len=S)
    got, kv = M._latent_attention(lp, 0, x, kv, rows, cfg, "reference")
    f32 = jnp.float32
    lp32 = {k: w.astype(f32) for k, w in lp.items()}
    with jax.default_matmul_precision("highest"):
        z = reference_lfm2._rmsnorm(x[0].astype(f32), lp32["attn_norm"],
                                    cfg.norm_eps)
        want = x[0].astype(f32) + ref.attention(z, lp32, ref.dims_of(cfg))
    err = float(jnp.abs(got[0].astype(f32) - want).max())
    if tol is not None:
        assert err < tol, err
    else:
        assert err > 20 * TOL, err          # bf16 where float32 is stated
    # what is cached: the normed latent, then the rotated shared key part
    row = np.asarray(kv["k"][0, 3, 0, 2]).astype(np.float32)
    assert row.shape == (RANK + ROPE,)


@pytest.mark.parametrize("what", ["attention", "write"])
def test_latent_kernels_in_interpret_mode(what):
    """The kernel form with V inside the K block (one fetch, one buffer)
    and the one-leaf write kernel, through the Pallas interpreter, against
    the gather reference and the scatter: mixed decode rows and chunk rows,
    a chunk that starts mid-page, an empty row, a row past its hint."""
    ps, P, mp, W, vw, Hq = 8, 25, 6, 48, 32, 4
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    pool = jax.random.normal(ks[0], (2, P, 1, ps, W), jnp.float32)
    page_table = jnp.asarray(
        np.random.default_rng(1).permutation(np.arange(1, P))[:4 * mp]
        .reshape(4, mp), jnp.int32)
    # rows: two decode rows (one empty), two chunk rows
    q_len = jnp.asarray([1, 0, 13, 5], jnp.int32)
    kv_len = jnp.asarray([19, 0, 30, 5], jnp.int32)
    q_start = jnp.asarray([0, 1, 2, 15], jnp.int32)
    T = 20
    q = jax.random.normal(ks[1], (T, Hq, W), jnp.float32)
    hints = dict(max_q_len=13, decode_rows=2, layer=1)
    if what == "attention":
        want = pa.ragged_paged_attention_reference(
            q, pool, None, page_table, q_start, q_len, kv_len, sm_scale=0.2,
            v_width=vw, **hints)
        got = pa.ragged_paged_attention(
            q, pool, None, page_table, q_start, q_len, kv_len, sm_scale=0.2,
            v_width=vw, interpret=True, **hints)
        assert got.shape == (T, Hq, vw)
        assert float(jnp.abs(got - want).max()) < 1e-5
        # the value is the row's leading lanes, not a second leaf
        other = pa.ragged_paged_attention_reference(
            q, pool, pool[..., ::-1], page_table, q_start, q_len, kv_len,
            sm_scale=0.2, **hints)
        assert float(jnp.abs(other[..., :vw] - want).max()) > 0.1
        return
    rows = jax.random.normal(ks[2], (T, 1, W), jnp.float32)
    # each token's destination: its row's pages from its position on
    tok_row = np.asarray([0] + [2] * 13 + [3] * 5 + [1])       # token 19: pad
    tok_pos = np.asarray([18] + list(range(17, 30)) + list(range(5)) + [0])
    token_page = np.asarray(page_table)[tok_row, tok_pos // ps]
    token_page[19] = 0                                         # scratch
    token_slot = tok_pos % ps
    q_start_w = jnp.asarray([0, 19, 1, 14], jnp.int32)
    args = (jnp.asarray(token_page, jnp.int32),
            jnp.asarray(token_slot, jnp.int32))
    want, none, _, _ = pa.write_ragged_kv(
        pool, None, rows, None, *args, layer=1, impl="reference")
    got, none2, _, _ = pa.write_ragged_kv(
        pool, None, rows, None, *args, layer=1, q_start=q_start_w,
        q_len=q_len, max_q_len=13, decode_rows=2, interpret=True)
    assert none is None and none2 is None
    live = np.ones(P, bool)
    live[0] = False                     # the scratch page: garbage
    assert np.array_equal(np.asarray(got)[:, live], np.asarray(want)[:, live])
    assert not np.array_equal(np.asarray(got)[1, live],
                              np.asarray(pool)[1, live])
    assert np.array_equal(np.asarray(got)[0], np.asarray(pool)[0])


def test_shared_expert_counted_once_beside_the_scaled_routed_sum():
    """One expert layer of the program (ops/moe.py's dropless layer, then
    the shared SwiGLU on the same normed input) against the reference
    layer: routed sum scaled by 2.448 + the shared expert ONCE; without
    the scale, or with the shared expert left out, it is off by tenths."""
    cfg = LlamaConfig.tiny(**KANANA)
    params = seeded(cfg)
    moe = params["layers"]["moe"]
    lp = {k: w[1] for k, w in moe.items() if k not in M._EXPERT_LEAVES}
    experts = {k: moe[k] for k in M._EXPERT_LEAVES}
    T = 24
    x = jax.random.normal(jax.random.PRNGKey(4), (1, T, D))
    valid = jnp.ones(T, bool)
    with jax.default_matmul_precision("highest"):
        got, counters = M._moe_mlp(lp, experts, 1, x, valid, cfg,
                                   "reference")
        z = reference_lfm2._rmsnorm(x[0], lp["mlp_norm"], cfg.norm_eps)
        how = dict(score="sigmoid", eps=1e-20)
        routed, _ = reference_lfm2.expert_layer(
            z, lp["router"], lp["router_bias"], experts["w_gate"],
            experts["w_up"], experts["w_down"], K, True, layer=1,
            scale=2.448, **how)
        shared = (jax.nn.silu(z @ lp["w_shared_gate"])
                  * (z @ lp["w_shared_up"])) @ lp["w_shared_down"]
        unscaled, _ = reference_lfm2.expert_layer(
            z, lp["router"], lp["router_bias"], experts["w_gate"],
            experts["w_up"], experts["w_down"], K, True, layer=1, **how)
    want = x[0] + routed + shared
    assert float(jnp.abs(got[0] - want).max()) < TOL
    assert float(jnp.abs(routed - 2.448 * unscaled).max()) < TOL
    assert float(jnp.abs(got[0] - (x[0] + routed)).max()) > 0.05
    assert float(jnp.abs(got[0] - (x[0] + unscaled + shared)).max()) > 0.05
    assert int(counters[0]) == T * K


# ------------------------------------------------- the served path, end to end

@pytest.fixture(scope="module")
def rows_1_and_2():
    """The same weights behind one chunk row a step and behind two."""
    cfg = LlamaConfig.tiny(**KANANA)
    params = seeded(cfg)
    return [InferenceEngine(cfg, params, **{**ENGINE, "prefill_rows": n})
            for n in (1, 2)]


@pytest.mark.parametrize("case", CASES)
def test_joined_chunk_rows_compute_what_one_row_a_step_does(rows_1_and_2,
                                                            case):
    """tests/_chunk_rows.py's cases on the LATENT pool: a later row of a
    sequence reads the rows the step's earlier row wrote into the one
    leaf, through the absorbed form, as it would a step later."""
    check(case, *rows_1_and_2)


@pytest.fixture(scope="module")
def shaped_and_full():
    """The same weights behind the set of mixed-step shapes and behind
    the full shape alone (what every step ran in before the set)."""
    cfg = LlamaConfig.tiny(**KANANA)
    params = seeded(cfg)
    return [InferenceEngine(cfg, params, **ENGINE),
            pin_full_shape(InferenceEngine(cfg, params, **ENGINE))]


@pytest.mark.parametrize("case", SHAPE_CASES)
def test_a_mixed_step_runs_the_smallest_shape_that_holds_its_rows(
        shaped_and_full, case):
    """One-row and two-row steps in turn on the LATENT pool: the tokens
    of the full shape alone, each step in the shape its deal asks for."""
    check_shapes(case, *shaped_and_full)


def test_a_descriptor_holds_the_arrays_the_engine_packed_before():
    """A latent pool (one leaf, no token_state): joined rows, a
    copy-on-write hit and a preemption, every field the old packing's."""
    cfg = LlamaConfig.tiny(**KANANA)
    params = seeded(cfg)
    check_descriptor(lambda **kw: InferenceEngine(
        cfg, params, **{**ENGINE, **kw}))


def test_a_preempted_sequences_re_prefill_takes_both_rows():
    cfg = LlamaConfig.tiny(**KANANA)
    params = seeded(cfg)
    check_preempted(lambda **kw: InferenceEngine(
        cfg, params, **{**ENGINE, **kw}))


# -------------------------------------------------------- pool, report, tags

def test_latent_pool_is_one_leaf_and_the_engine_reports_it(kanana):
    cfg, eng = kanana
    kv = make_kv_cache(cfg, 16, 8)
    assert set(kv) == {"k"}
    assert kv["k"].shape == (3, 16, 1, 8, RANK + ROPE)
    # on a TPU the row is held in whole lanes: 576 -> 640
    wide = dataclasses.replace(cfg, kv_lora_rank=512, qk_rope_head_dim=64)
    assert latent_row_width(wide) == 576
    assert latent_row_width(wide, lane_pad=True) == 640
    assert make_kv_cache(wide, 4, 16, lane_pad=True)["k"].shape[-1] == 640
    with pytest.raises(ValueError, match="int8"):
        make_kv_cache(cfg, 16, 8, kv_dtype="int8")
    plain = LlamaConfig.tiny(dtype=jnp.float32)
    assert kv_cache_tag(cfg, None) != kv_cache_tag(plain, None)
    assert "latent" in kv_cache_tag(cfg, None)
    report = eng.device_report()
    row_bytes = (RANK + ROPE) * 4
    assert report["kv_row_width"] == RANK + ROPE
    assert report["kv_token_layer_bytes"] == row_bytes
    assert report["kv_bytes"] == 3 * 64 * 8 * row_bytes
    assert eng.stats["kv_token_layer_bytes"] == row_bytes
    dense = InferenceEngine(plain, **ENGINE)
    assert "kv_row_width" not in dense.stats
    assert dense.device_report()["kv_token_layer_bytes"] \
        == 2 * plain.n_kv_heads * plain.head_dim * 4


# ------------------------------------------------ what refuses the new fields

@pytest.mark.parametrize("over,match", [
    (dict(kv_lora_rank=32), "qk_nope_head_dim"),
    (dict(kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=7,
          v_head_dim=16), "even qk_rope_head_dim"),
    (dict(qk_rope_head_dim=8), "kv_lora_rank"),
    (dict(v_head_dim=16), "kv_lora_rank"),
    (dict(shared_ffn_dim=32), "n_experts"),
    (dict(kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
          v_head_dim=16, qk_norm=True), "latent attention.*beside qk_norm"),
    (dict(kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
          v_head_dim=16, n_layers=2,
          layer_types=["conv", "full_attention"]),
     "latent attention.*beside .*conv layers")])
def test_config_refuses_what_it_cannot_build(over, match):
    with pytest.raises(ValueError, match=match):
        LlamaConfig.tiny(**over)
