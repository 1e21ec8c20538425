"""The MiMo-V2-Flash block in the serving engine: full-attention layers and
window-attention layers on different numbers of key/value heads, a score
head wider than the value head, a partial rotary embedding, a value scale,
a learned sink in the window layers' softmax, a SECOND PAGE GROUP that
frees behind the window, and routed experts of which the engine holds a
share, through the one ragged step and the decode loop, against the
benchmark's plain reference (benchmark/reference_mimo.py: whole sequences,
no page, no cache) on seeded weights. Tiny widths on the CPU, float32
compute: 8 query heads on 2 (full) and 4 (window) key/value heads, score
head 24 of which 8 values rotate, value head 16, a window of 16 tokens over
pages of 8, experts 4..11 of 16 held.

TOL: everything runs in float32 here, so the two sides differ by summation
order only (measured: 3e-6 at worst on logits of unit spread). 1e-4 leaves
room and still fails a window off by one, a sink left out, a value scale
left out, a base of the compact table off by a page.
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

from _blocks import chunked_logits, seeded, worst_gap  # noqa: E402
from benchmark import reference_mimo as ref  # noqa: E402
from ray_tpu.llm import InferenceEngine  # noqa: E402
from ray_tpu.llm import model as M  # noqa: E402
from ray_tpu.llm.cache import (kv_cache_tag, make_kv_cache,  # noqa: E402
                               window_first_page, window_group_pages,
                               window_table_width)
from ray_tpu.models.llama import (LlamaConfig, init_params,  # noqa: E402
                                  mechanisms_beyond)
from ray_tpu.ops import moe  # noqa: E402
from ray_tpu.ops.paged_attention import ragged_paged_attention  # noqa: E402

TOL = 1e-4
W, PS = 16, 8
FULL, WIN = "full_attention", "sliding_attention"
MIMO = dict(vocab_size=128, dim=64, n_layers=5, n_heads=8, n_kv_heads=2,
            window_kv_heads=4, ffn_dim=32, dense_ffn_dim=96, n_dense_layers=1,
            n_experts=16, experts_per_token=4, norm_topk_prob=True,
            router_score="sigmoid", router_bias=True, experts_held=(4, 8),
            tie_embeddings=False, layer_types=[FULL, WIN, FULL, WIN, FULL],
            score_head_dim=24, value_head_dim=16, rotary_dim=8,
            value_scale=0.707, sliding_window=W, window_rope_theta=1e4,
            rope_theta=5e6, attn_sink=True, dtype=jnp.float32,
            param_dtype=jnp.float32)
ENGINE = dict(page_size=PS, total_pages=64, max_batch=4, max_seq_len=128,
              prefill_chunk=16, prefill_rows=2, decode_chunk=4, seed=3)


@pytest.fixture(scope="module")
def mimo():
    jax.clear_caches()
    cfg = LlamaConfig.tiny(**MIMO)
    return cfg, InferenceEngine(cfg, seeded(cfg), **ENGINE)


_forward = jax.jit(ref.forward, static_argnames=("dims", "hold", "fault"))


def _reference_logits(params, cfg, tokens, fault=None):
    with jax.default_matmul_precision("highest"):
        return _forward(params, jnp.asarray(tokens, jnp.int32),
                        ref.dims_of(cfg), fault=fault)[0]



# ----------------------------------------------------------- the kernel

def _kernel_case(window, sink, qpk, rows, compact=True, dk=256, dv=128,
                 hkv=2, seed=0):
    """Ragged rows [(q_len, kv_len)] (one-token rows first) over a seeded
    pool: the reference path's result and the kernel's in interpret mode."""
    rng = np.random.default_rng(seed)
    hq = hkv * qpk
    decode_rows = next((i for i, (q, _) in enumerate(rows) if q > 1),
                       len(rows))
    C, R = max(q for q, _ in rows), len(rows)
    T = sum(q for q, _ in rows) + 3
    cols = window_table_width(window, C, PS) if window and compact \
        else max(-(-k // PS) for _, k in rows) + 1
    P = 1 + sum(-(-k // PS) for _, k in rows)
    kp = jnp.asarray(rng.standard_normal((1, P, hkv, PS, dk)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((1, P, hkv, PS, dv)), jnp.float32)
    table, base = np.zeros((R, cols), np.int32), np.zeros(R, np.int32)
    q_start, nxt, t = [], 1, 0
    for r, (ql, kl) in enumerate(rows):
        n = -(-kl // PS)
        own = np.arange(nxt, nxt + n)
        nxt += n
        if window and compact:
            base[r] = window_first_page(kl - ql, window, PS)
        held = own[base[r]:base[r] + cols]
        table[r, :len(held)] = held
        q_start.append(t)
        t += ql
    q = jnp.asarray(rng.standard_normal((T, hq, dk)), jnp.float32)
    how = dict(max_q_len=C, decode_rows=decode_rows, layer=0,
               sm_scale=192 ** -0.5)
    if window:
        how.update(window=window, page_base=jnp.asarray(base),
                   sink=jnp.asarray(rng.uniform(3, 6, hq), jnp.float32)
                   if sink else None)
    args = (q, kp, vp, jnp.asarray(table), jnp.asarray(q_start, jnp.int32),
            jnp.asarray([a for a, _ in rows], jnp.int32),
            jnp.asarray([b for _, b in rows], jnp.int32))
    return (ragged_paged_attention(*args, impl="reference", **how),
            ragged_paged_attention(*args, interpret=True, **how))


@pytest.mark.parametrize("window,sink,qpk,rows", [
    # rows shorter than the window, a decode row far past it, a chunk that
    # starts at 0 and one that crosses the window inside the chunk
    (16, True, 4, [(1, 5), (1, 40), (0, 0), (24, 24), (24, 61)]),
    (16, False, 2, [(1, 17), (20, 37)]),
    (16, True, 8, [(1, 100), (1, 16), (30, 30)]),
    (None, False, 4, [(1, 5), (1, 40), (24, 61)]),      # two widths alone
])
def test_kernel_window_sink_and_two_widths_in_interpret_mode(
        window, sink, qpk, rows):
    want, got = _kernel_case(window, sink, qpk, rows)
    assert want.shape[-1] == 128
    assert float(jnp.abs(want - got).max()) < 2e-5


def test_kernel_reads_a_table_that_is_not_compact_the_same():
    """The window form over a whole page table (base 0 for every row) and
    over the compact one: the same numbers."""
    rows = [(1, 100), (30, 70)]
    whole, _ = _kernel_case(16, True, 4, rows, compact=False)
    compact, kernel = _kernel_case(16, True, 4, rows, compact=True)
    assert float(jnp.abs(whole - compact).max()) < 1e-6
    assert float(jnp.abs(whole - kernel).max()) < 2e-5


def test_a_sink_or_a_base_without_a_window_is_refused():
    q = jnp.zeros((2, 4, 8))
    kp = jnp.zeros((3, 2, 8, 8))
    z = jnp.zeros(1, jnp.int32)
    with pytest.raises(ValueError, match="need a window"):
        ragged_paged_attention(q, kp, kp, jnp.zeros((1, 2), jnp.int32), z, z,
                               z, sink=jnp.zeros(4))


# ------------------------------------------------ the tree and the pool

def test_param_tree_pool_and_pattern(mimo):
    cfg, eng = mimo
    layers = eng.params["layers"]
    assert layers["attn"]["wq"].shape == (3, 64, 8 * 24)
    assert layers["attn"]["wk"].shape == (3, 64, 2 * 24)
    assert layers["attn"]["wv"].shape == (3, 64, 2 * 16)
    assert layers["attn"]["wo"].shape == (3, 8 * 16, 64)
    win = layers["attn_window"]
    assert win["wk"].shape == (2, 64, 4 * 24)
    assert win["sink"].shape == (2, 8) and win["sink"].dtype == jnp.float32
    assert float(win["sink"].min()) >= 3 and float(win["sink"].max()) <= 6
    assert "sink" not in layers["attn"]
    # the held experts' matrices only; the router keeps every column
    assert layers["moe"]["w_gate"].shape == (4, 8, 64, 32)
    assert layers["moe"]["router"].shape == (4, 64, 16)
    assert M._pattern(cfg) == (
        [(FULL, "dense")], [(WIN, "moe"), (FULL, "moe")], 2)
    # the published 5 : 1 is one period of six after the dense layer
    assert M._pattern(dataclasses.replace(
        cfg, n_layers=7, layer_types=[FULL] + [WIN] * 5 + [FULL])) == (
        [(FULL, "dense")], [(WIN, "moe")] * 5 + [(FULL, "moe")], 1)
    group = window_group_pages(cfg, PS, 4, 4, 16, 2)
    assert group == 4 * window_table_width(W, 4, PS) \
        + 2 * window_table_width(W, 16, PS) + 1 == 4 * 4 + 2 * 5 + 1
    assert eng.kv["k"].shape == (3, 64, 2, PS, 24)
    assert eng.kv["v"].shape == (3, 64, 2, PS, 16)
    assert eng.kv["k_win"].shape == (2, group, 4, PS, 24)
    assert eng.kv["v_win"].shape == (2, group, 4, PS, 16)
    assert eng.window_allocator.total_pages == group
    padded = make_kv_cache(dataclasses.replace(
        cfg, score_head_dim=192, value_head_dim=128, dim=512), 4, PS,
        window_pages=3, lane_pad=True)
    assert padded["k"].shape[-1] == padded["k_win"].shape[-1] == 256
    assert padded["v"].shape[-1] == padded["v_win"].shape[-1] == 128
    assert WIN in mechanisms_beyond(cfg) and cfg.hybrid
    assert M.step_counters(cfg) == moe.COUNTERS + ("moe_absent",)
    assert kv_cache_tag(cfg, None) == "float32-k24v16-window16x4"
    # the descriptor carries the second table and its base, at a width
    # that does not grow with the context
    fields = dict(eng._fns.step_layouts[2])
    assert fields["page_table_win"] == (6, 5)
    assert fields["page_base_win"] == (6,)
    assert fields["token_page_win"] == fields["tokens"]
    assert dict(eng._fns.decode_layout)["page_table_win"] == (4, 4)
    more = InferenceEngine(cfg, eng.params, **{**ENGINE, "max_seq_len": 512,
                                               "total_pages": 200})
    assert dict(more._fns.step_layouts[2])["page_table_win"] == (6, 5)
    assert more.kv["k_win"].shape == eng.kv["k_win"].shape


# ------------------------------------------------------------ the engine

def test_window_pages_are_freed_and_reused_while_the_first_still_decodes():
    """One long sequence decodes on; what it frees behind its window a
    second sequence takes, in a group too small to hold both lifetimes."""
    cfg = LlamaConfig.tiny(**MIMO)
    eng = InferenceEngine(cfg, seeded(cfg), **{**ENGINE, "max_batch": 2})
    long, short = list(range(1, 61)), list(range(70, 100))
    first = eng.add_request(long, 40)
    held = []
    for _ in range(6):                    # prefill and some decode blocks
        eng.step()
        held.append(eng.window_allocator.total_pages - 1
                    - eng.window_allocator.num_free)
    seq = eng._slots[0]
    assert seq is not None and not seq.prefilling
    used = set(range(1, eng.window_allocator.total_pages)) \
        - set(eng.window_allocator._free)
    assert seq.win_base == window_first_page(seq.num_tokens - 1, W, PS) > 4
    assert max(held) <= window_table_width(W, 16, PS) * 2
    second = eng.add_request(short, 12)
    pages = set()
    done = {}
    while eng.has_work():
        done.update(eng.step())
        other = eng._slots[1]
        if other is not None:
            pages |= set(other.win_pages)
    # the second sequence was given pages the first had held and freed
    freed_by_first = set(range(1, 1 + seq.win_base)) - used
    assert eng.stats["window_pages_freed"] > 10
    assert pages & (set(range(1, eng.window_allocator.total_pages)) - used)
    del freed_by_first
    assert worst_gap("mimo", eng, long, done[first]) < TOL
    assert worst_gap("mimo", eng, short, done[second]) < TOL


# ------------------------------------------- nothing may be left out

@pytest.mark.parametrize("kind,leaf", [
    ("attn_window", "sink"), ("attn_window", "wk"), ("attn", "wv"),
    ("moe", "router_bias")])
def test_no_leaf_of_either_operator_is_left_out(mimo, kind, leaf):
    """Each leaf moves the served logits as it moves the reference's:
    changed on both sides they still agree, changed on one they do not."""
    cfg, eng = mimo
    prompt = list(range(9, 50))
    stack = dict(eng.params["layers"][kind])
    stack[leaf] = stack[leaf] + 0.5 * jax.random.normal(
        jax.random.PRNGKey(1), stack[leaf].shape).astype(stack[leaf].dtype)
    other = {**eng.params, "layers": {**eng.params["layers"], kind: stack}}
    want = _reference_logits(other, cfg, prompt)[-1]
    got, _ = chunked_logits(cfg, other, prompt, 16)
    assert float(jnp.abs(got - want).max()) < TOL
    stale, _ = chunked_logits(cfg, eng.params, prompt, 16)
    assert float(jnp.abs(stale - want).max()) > 100 * TOL


@pytest.mark.parametrize("change", [
    dict(value_scale=1.0), dict(rope_theta=1e4),
    dict(window_rope_theta=5e6), dict(rotary_dim=24),
    dict(sliding_window=17)])
def test_no_scalar_of_either_operator_is_left_out(mimo, change):
    """The value scale, each rotary base, the partial rotary embedding, the
    window's width and the sink: the served logits follow the field, and
    the reference with the field's old value is told apart."""
    cfg, eng = mimo
    prompt = list(range(9, 50))
    other = dataclasses.replace(cfg, **change)
    params = eng.params
    got, _ = chunked_logits(other, params, prompt, 16)
    want = _reference_logits(params, other, prompt)[-1]
    assert float(jnp.abs(got - want).max()) < TOL
    old = _reference_logits(eng.params, cfg, prompt)[-1]
    assert float(jnp.abs(got - old).max()) > 100 * TOL


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_the_reference_with_a_fault_is_told_apart(mimo, fault):
    """hold_mimo.py's faults, at a tiny size: each moves the reference's
    own logits by far more than TOL (the study on the chip shows the
    cell's limits see them)."""
    cfg, eng = mimo
    prompt = list(range(9, 50))
    good = _reference_logits(eng.params, cfg, prompt)
    bad = _reference_logits(eng.params, cfg, prompt, fault=fault)
    assert float(jnp.abs(good - bad).max()) > 100 * TOL


# ---------------------------------------------------------- the share

def test_the_shares_expert_outputs_add_up_to_the_uncut_layers():
    """Guide section 4: the 16 experts cut four ways. Each share routes
    over all 16 (the router, its bias and the renormalisation over all 4
    chosen are what every chip computes alike: counted once), computes its
    4 experts' part, and the parts add up to the uncut layer's output; the
    pairs a share did not serve are the other shares' pairs."""
    T, d, f, E, k = 24, 32, 16, 16, 4
    keys = jax.random.split(jax.random.PRNGKey(2), 6)
    m = jax.random.normal(keys[0], (T, d))
    valid = jnp.arange(T) < T - 3
    router = jax.random.normal(keys[1], (d, E)) * d ** -0.5
    bias = 0.02 * jax.random.normal(keys[2], (E,))
    gate, up = (jax.random.normal(kk, (E, d, f)) * d ** -0.5
                for kk in keys[3:5])
    down = jax.random.normal(keys[5], (E, f, d)) * f ** -0.5
    how = dict(impl="reference", score="sigmoid", bias=bias)
    whole, counted = moe.moe_ffn(m, valid, router, gate, up, down, k, True,
                                 **how)
    assert counted.shape == (3,)
    total, pairs, absent = 0.0, 0, []
    for first in range(0, E, 4):
        part, c = moe.moe_ffn(
            m, valid, router, gate[first:first + 4], up[first:first + 4],
            down[first:first + 4], k, True, held=(first, 4), **how)
        assert c.shape == (4,)
        total = total + part
        pairs += int(c[0])
        absent.append(int(c[3]))
        assert int(c[0]) + int(c[3]) == (T - 3) * k
    assert pairs == int(counted[0]) == (T - 3) * k
    assert float(jnp.abs(total - whole).max()) < 1e-5
    assert float(jnp.abs(whole[T - 3:]).max()) == 0
    with pytest.raises(ValueError, match="the weights hold"):
        moe.moe_ffn(m, valid, router, gate, up, down, k, True, held=(0, 4),
                    **how)


def test_the_expert_kernel_over_a_share_in_interpret_mode():
    T, d, f, E, k = 16, 128, 128, 8, 2
    keys = jax.random.split(jax.random.PRNGKey(4), 5)
    m = jax.random.normal(keys[0], (T, d))
    valid = jnp.ones(T, bool)
    router = jax.random.normal(keys[1], (d, E)) * d ** -0.5
    gate, up = (jax.random.normal(kk, (4, d, f)) * d ** -0.5
                for kk in keys[2:4])
    down = jax.random.normal(keys[4], (4, f, d)) * f ** -0.5
    want, c0 = moe.moe_ffn(m, valid, router, gate, up, down, k, True,
                           held=(2, 4), impl="reference")
    got, c1 = moe.moe_ffn(m, valid, router, gate, up, down, k, True,
                          held=(2, 4), impl="kernel", interpret=True)
    assert np.array_equal(np.asarray(c0), np.asarray(c1))
    assert float(jnp.abs(want - got).max()) < 1e-4


@pytest.mark.parametrize("shape,want", [
    ((8704, 64, 1024, 2048), (128, 1024)),      # OLMoE's mixed step
    ((8704, 64, 2048, 2048), (128, 1024)),      # a wider expert there
    ((100, 32, 1792, 2048), (16, 896)),         # LFM2's decode loop
    ((100, 128, 768, 2048), (16, 768)),         # Kanana-2's
    ((560, 16, 2048, 4096), (64, 512)),         # this block's mixed step
    ((48, 16, 2048, 4096), (16, 512)),          # ... and its decode loop
])
def test_the_width_block_is_cut_by_the_model_width_too(shape, want):
    assert moe._tiling(*shape) == want


# ------------------------------------------------------ what is refused

def test_config_refuses_what_is_not_built():
    def tiny(**kw):
        return LlamaConfig.tiny(**{**MIMO, **kw})
    with pytest.raises(ValueError, match="window_kv_heads that divide"):
        tiny(window_kv_heads=3)
    with pytest.raises(ValueError, match="window_kv_heads that divide"):
        tiny(sliding_window=0)
    with pytest.raises(ValueError, match="sliding_attention.*beside conv"):
        tiny(layer_types=[FULL, WIN, FULL, WIN, "conv"])
    # a norm over the whole projected vector stays refused beside window
    # layers; the norm over each head is served since PR 49 (compared with
    # its reference in tests/test_llm_trinity.py), a head's weight as wide
    # as the score head in both stacks
    with pytest.raises(ValueError, match="sliding_attention.*beside qk_norm"):
        tiny(qk_norm=True, score_head_dim=0, value_head_dim=0, rotary_dim=0)
    served = jax.eval_shape(lambda: init_params(
        tiny(qk_norm_per_head=True), jax.random.PRNGKey(0)))["layers"]
    assert served["attn"]["q_norm"].shape == (3, 24)
    assert served["attn_window"]["k_norm"].shape == (2, 24)
    with pytest.raises(ValueError, match="sliding_attention.*beside no positions"):
        tiny(rope=False)
    with pytest.raises(ValueError, match="retention layers: not built beside sliding_att"):
        tiny(layer_types=[FULL, WIN, FULL, WIN, "retention"], n_experts=0,
             experts_per_token=0, n_dense_layers=0, experts_held=(),
             router_bias=False)
    with pytest.raises(ValueError, match="layer_types names none"):
        tiny(layer_types=[FULL] * 5)
    with pytest.raises(ValueError, match="even rotary_dim"):
        tiny(rotary_dim=26)
    with pytest.raises(ValueError, match="chip's share"):
        tiny(experts_held=(12, 8))
    with pytest.raises(ValueError, match="chip's share"):
        LlamaConfig.tiny(dim=64, experts_held=(0, 2))
    with pytest.raises(ValueError, match="head widths.*beside latent attention"):
        LlamaConfig.tiny(dim=64, value_scale=0.5, kv_lora_rank=32,
                         qk_nope_head_dim=8, qk_rope_head_dim=8,
                         v_head_dim=8)
    cfg = tiny()
    with pytest.raises(ValueError, match="two page groups"):
        make_kv_cache(cfg, 8, PS, kv_dtype="int8", window_pages=4)
    with pytest.raises(ValueError, match="window_pages"):
        make_kv_cache(cfg, 8, PS)

