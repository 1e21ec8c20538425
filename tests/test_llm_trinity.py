"""The Trinity-Mini block in the serving engine: window-attention layers
that rotate beside a full-attention layer that carries no position, a q/k
norm over each head (a head that is NOT dim / n_heads wide), a sigmoid gate
on the attention output, a norm after each branch as well as before it, a
leading dense layer, sigmoid-routed experts with a selection bias and a
routing scale beside a shared expert, a scaled embedding: through the one
ragged step and the decode loop over both page groups, against the
benchmark's plain reference (benchmark/reference_trinity.py: whole
sequences, no page, no cache) on seeded weights. Tiny widths on the CPU,
float32 compute: 8 query heads of 16 (dim / n_heads is 8) on 2 key/value
heads, a window of 16 tokens over pages of 8, 16 experts top-4.

TOL: everything runs in float32 here, so the two sides differ by summation
order only. 1e-4 on LOGITS leaves room and still fails every fault of the
hold study (benchmark/hold_trinity.py), the window's width off by one
either way among them.
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

from _blocks import seeded, served_logits, worst_gap  # noqa: E402
from benchmark import reference_trinity as ref  # noqa: E402
from ray_tpu.llm import InferenceEngine  # noqa: E402
from ray_tpu.llm import model as M  # noqa: E402
from ray_tpu.llm.cache import (window_first_page,  # noqa: E402
                               window_group_pages, window_table_width)
from ray_tpu.models.llama import (LlamaConfig,  # noqa: E402
                                  mechanisms_beyond)
from ray_tpu.ops import paged_attention as PA  # noqa: E402

TOL = 1e-4
W, PS = 16, 8
FULL, WIN = "full_attention", "sliding_attention"
TRINITY = dict(vocab_size=128, dim=64, n_layers=5, n_heads=8, n_kv_heads=2,
               window_kv_heads=2, ffn_dim=32, dense_ffn_dim=96,
               n_dense_layers=1, n_experts=16, experts_per_token=4,
               norm_topk_prob=True, router_score="sigmoid", router_bias=True,
               router_eps=1e-20, router_scale=2.826, shared_ffn_dim=32,
               tie_embeddings=False, layer_types=[WIN] * 4 + [FULL],
               score_head_dim=16, value_head_dim=16, sliding_window=W,
               window_rope_theta=1e4, rope_theta=1e4, qk_norm_per_head=True,
               attn_gate=True, post_norms=True, full_rope=False,
               embed_scale=8.0, dtype=jnp.float32, param_dtype=jnp.float32)
ENGINE = dict(page_size=PS, total_pages=64, max_batch=4, max_seq_len=128,
              prefill_chunk=16, prefill_rows=2, decode_chunk=4, seed=3)


@pytest.fixture(scope="module")
def trinity():
    jax.clear_caches()
    cfg = LlamaConfig.tiny(**TRINITY)
    return cfg, InferenceEngine(cfg, seeded(cfg), **ENGINE)


_forward = jax.jit(ref.forward, static_argnames=("dims", "hold", "fault"))


def _reference_logits(params, cfg, tokens, fault=None):
    with jax.default_matmul_precision("highest"):
        return _forward(params, jnp.asarray(tokens, jnp.int32),
                        ref.dims_of(cfg), fault=fault)[0]


def _served_logits(cfg, params, tokens, n_prompt, chunk=16):
    """``tokens`` through the mixed step's forward (tests/_blocks.py:
    served_logits): logits [len(tokens) - n_prompt + 1, vocab] at the
    prompt's last token and at every later one."""
    out, _ = served_logits(cfg, params, tokens, n_prompt, chunk)
    return jnp.stack(out[n_prompt - len(tokens) - 1:])


# ------------------------------------------------ the tree and the pool

def test_param_tree_pool_and_pattern(trinity):
    cfg, eng = trinity
    layers = eng.params["layers"]
    assert cfg.head_dim == 8 and cfg.qk_head_dim == cfg.v_dim == 16
    for kind, n in (("attn", 1), ("attn_window", 4)):
        stack = layers[kind]
        assert stack["wq"].shape == (n, 64, 8 * 16)
        assert stack["wk"].shape == stack["wv"].shape == (n, 64, 2 * 16)
        assert stack["wo"].shape == (n, 8 * 16, 64)
        # the gate is as wide as the heads' output; a norm after the branch
        assert stack["w_og"].shape == (n, 64, 8 * 16)
        assert stack["attn_post_norm"].shape == (n, 64)
        # over each head: as wide as a head IS, not dim / n_heads
        assert stack["q_norm"].shape == stack["k_norm"].shape == (n, 16)
        assert "sink" not in stack
    assert layers["dense"]["mlp_post_norm"].shape == (1, 64)
    assert layers["moe"]["mlp_post_norm"].shape == (4, 64)
    assert layers["moe"]["w_gate"].shape == (4, 16, 64, 32)
    assert layers["moe"]["w_shared_gate"].shape == (4, 64, 32)
    assert layers["moe"]["router_bias"].dtype == jnp.float32
    assert M._pattern(cfg) == (
        [(WIN, "dense")], [(WIN, "moe")] * 3 + [(FULL, "moe")], 1)
    group = window_group_pages(cfg, PS, 4, 4, 16, 2)
    assert eng.kv["k"].shape == eng.kv["v"].shape == (1, 64, 2, PS, 16)
    assert eng.kv["k_win"].shape == eng.kv["v_win"].shape \
        == (4, group, 2, PS, 16)
    assert cfg.gated_block and cfg.hybrid
    assert {"gated block", WIN} <= set(mechanisms_beyond(cfg))
    # a block that sets none of the three is no gated block
    assert not LlamaConfig.tiny().gated_block
    assert eng.prefix is None
    assert "rows_inside_window" in eng.stats


def test_the_published_geometry_of_the_window_group():
    """At the published window and the cell's engine settings: 34 entries
    a decode row, 49 a chunk row of 1024 (41 of 512), 4451 pages."""
    cfg = LlamaConfig.tiny(**{**TRINITY, "sliding_window": 2048})
    assert window_table_width(2048, 8, 64) == 34
    assert window_table_width(2048, 512, 64) == 41
    assert window_table_width(2048, 1024, 64) == 49
    assert window_group_pages(cfg, 64, 128, 8, 512, 2) == 4435
    assert window_group_pages(cfg, 64, 128, 8, 1024, 2) == 4451
    # the tiles: a one-token tile takes its window as ONE block of 34
    # pages (8.9 MB of pages, twice over), a chunk tile blocks of 1024
    # slots (16 pages) where one block would be 2176; a one-token tile
    # whose pages would not fit walks blocks too; MiMo's window of 128
    # keeps its one block
    assert PA._ragged_tiling(1, 8, 64, 34, None, 2048, 4, 256) \
        == (1, 1, 16, 34)
    assert PA._ragged_tiling(1024, 8, 64, 49, None, 2048, 4, 256) \
        == (64, 16, 512, 16)
    assert PA._ragged_tiling(1, 8, 64, 66, None, 4096, 4, 256) \
        == (1, 1, 16, 16)
    assert PA._ragged_tiling(1, 8, 64, 4, None, 128, 8, 384) == (1, 1, 16, 4)
    assert PA._ragged_tiling(512, 8, 64, 11, None, 128, 8, 384) \
        == (64, 8, 512, 4)


# ----------------------------------------------------------- the kernel

@pytest.mark.parametrize("rows", [
    # one-token rows inside the window, at its edge and far past it; chunk
    # rows that start at 0, cross the window inside the chunk, lie past it
    [(1, 5), (1, 601), (1, 1500), (0, 0), (40, 40), (40, 630)],
    [(1, 600), (70, 1400)],
])
def test_kernel_walks_a_long_window_in_blocks_in_interpret_mode(
        rows, monkeypatch):
    """A window of 600 positions over pages of 8 is more than one block of
    _WINDOW_BLOCK slots (256 here, for both kinds of tile): the window form
    walks its blocks from the first visible page, both edges of the mask in
    each."""
    monkeypatch.setattr(PA, "_WINDOW_BLOCK", 256)
    monkeypatch.setattr(PA, "_WINDOW_ROW_BYTES", 0)
    window, hkv, qpk, d = 600, 2, 8, 128
    rng = np.random.default_rng(0)
    decode_rows = next((i for i, (q, _) in enumerate(rows) if q > 1),
                       len(rows))
    C, R = max(q for q, _ in rows), len(rows)
    T = sum(q for q, _ in rows) + 3
    for n in (1, C):
        assert PA._ragged_tiling(n, qpk, PS, 99, None, window, hkv,
                                 2 * d)[3] * PS == PA._WINDOW_BLOCK < window
    cols = window_table_width(window, C, PS)
    P = 1 + sum(-(-k // PS) for _, k in rows)
    kp = jnp.asarray(rng.standard_normal((1, P, hkv, PS, d)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((1, P, hkv, PS, d)), jnp.float32)
    table, base = np.zeros((R, cols), np.int32), np.zeros(R, np.int32)
    q_start, nxt, t = [], 1, 0
    for r, (ql, kl) in enumerate(rows):
        n = -(-kl // PS)
        own = np.arange(nxt, nxt + n)
        nxt += n
        base[r] = window_first_page(kl - ql, window, PS)
        held = own[base[r]:base[r] + cols]
        table[r, :len(held)] = held
        q_start.append(t)
        t += ql
    q = jnp.asarray(rng.standard_normal((T, hkv * qpk, d)), jnp.float32)
    how = dict(max_q_len=C, decode_rows=decode_rows, layer=0, window=window,
               page_base=jnp.asarray(base))
    args = (q, kp, vp, jnp.asarray(table), jnp.asarray(q_start, jnp.int32),
            jnp.asarray([a for a, _ in rows], jnp.int32),
            jnp.asarray([b for _, b in rows], jnp.int32))
    want = PA.ragged_paged_attention(*args, impl="reference", **how)
    got = PA.ragged_paged_attention(*args, interpret=True, **how)
    live = np.zeros(T, bool)
    for s, (ql, _) in zip(q_start, rows):
        live[s:s + ql] = True
    assert float(jnp.abs(want - got)[live].max()) < 2e-5


# ------------------------------------------------------------ the engine

def test_inside_the_window_a_window_layer_is_a_full_layer():
    """A sequence wholly inside the window gives the same logits with
    every window layer declared full (and rotated, as the window layers
    are): one page group or the other, the same numbers."""
    as_window = LlamaConfig.tiny(**{**TRINITY, "layer_types": [WIN] * 5,
                                    "full_rope": True})
    as_full = LlamaConfig.tiny(**{
        **TRINITY, "layer_types": [FULL] * 5, "full_rope": True,
        "sliding_window": 0, "window_kv_heads": 0, "window_rope_theta": 0.0})
    params = seeded(as_window)
    assert "attn" not in params["layers"]
    renamed = {**params, "layers": {
        **{k: v for k, v in params["layers"].items() if k != "attn_window"},
        "attn": params["layers"]["attn_window"]}}
    tokens = list(np.random.default_rng(1).integers(0, 128, W))
    a = _served_logits(as_window, params, tokens, 9, chunk=8)
    b = _served_logits(as_full, renamed, tokens, 9, chunk=8)
    assert float(jnp.abs(a - b).max()) < 1e-5
    assert float(jnp.abs(a - _reference_logits(
        params, as_window, tokens)[8:]).max()) < TOL
    # ... and one token more, past the window, they part
    tokens = tokens + [7, 9]
    a = _served_logits(as_window, params, tokens, 9, chunk=8)
    b = _served_logits(as_full, renamed, tokens, 9, chunk=8)
    assert float(jnp.abs(a - b)[:-2].max()) < 1e-5
    assert float(jnp.abs(a - b)[-1].max()) > 100 * TOL


def test_engine_preempts_and_readmits_a_sequence_that_freed_window_pages():
    """A full group too small for both sequences preempts one AFTER it has
    freed pages behind its window: it gives back what it holds of both
    groups, re-prefills from position 0 and continues as if never
    stopped."""
    cfg = LlamaConfig.tiny(**TRINITY)
    params = seeded(cfg)
    how = {**ENGINE, "page_size": 4, "max_seq_len": 64, "max_batch": 2}
    small = InferenceEngine(cfg, params, **{**how, "total_pages": 18})
    roomy = InferenceEngine(cfg, params, **how)
    prompts = [list(range(1, 23)), list(range(3, 27))]
    rids = [small.add_request(p, 16) for p in prompts]
    freed_at_preemption = None
    done = {}
    for _ in range(400):
        before = small.stats["preemptions"]
        done.update(small.step())
        if small.stats["preemptions"] > before \
                and freed_at_preemption is None:
            freed_at_preemption = small.stats["window_pages_freed"]
        if not small.has_work():
            break
    assert small.stats["preemptions"] >= 1
    assert freed_at_preemption and freed_at_preemption > 0
    for p, r in zip(prompts, rids):
        assert done[r] == roomy.generate(p, 16)
        assert worst_gap("trinity", small, p, done[r], pad_to=64) < TOL
    assert small.window_allocator.num_free \
        == small.window_allocator.total_pages - 1
    assert small.allocator.num_free == small.allocator.total_pages - 1


# ------------------------------------------- nothing may be left out

@pytest.mark.parametrize("kind,leaf", [
    ("attn_window", "w_og"), ("attn", "w_og"), ("attn_window", "q_norm"),
    ("attn", "k_norm"), ("attn_window", "attn_post_norm"),
    ("dense", "mlp_post_norm"), ("moe", "mlp_post_norm"),
    ("moe", "router_bias"), ("moe", "w_shared_up")])
def test_no_leaf_of_the_block_is_left_out(trinity, kind, leaf):
    """Each leaf moves the served logits as it moves the reference's:
    changed on both sides they still agree, changed on one they do not."""
    cfg, eng = trinity
    tokens = list(range(9, 50))
    stack = dict(eng.params["layers"][kind])
    stack[leaf] = stack[leaf] + 0.5 * jax.random.normal(
        jax.random.PRNGKey(1), stack[leaf].shape).astype(stack[leaf].dtype)
    other = {**eng.params, "layers": {**eng.params["layers"], kind: stack}}
    want = _reference_logits(other, cfg, tokens)[-1]
    got = _served_logits(cfg, other, tokens, len(tokens))[-1]
    assert float(jnp.abs(got - want).max()) < TOL
    stale = _served_logits(cfg, eng.params, tokens, len(tokens))[-1]
    assert float(jnp.abs(stale - want).max()) > 100 * TOL


@pytest.mark.parametrize("change", [
    dict(attn_gate=False), dict(qk_norm_per_head=False),
    dict(full_rope=True), dict(post_norms=False), dict(router_scale=1.0),
    dict(embed_scale=1.0), dict(sliding_window=W + 1),
    dict(sliding_window=W - 1), dict(window_rope_theta=5e5)])
def test_no_field_of_the_block_is_left_out(trinity, change):
    """Each field switched off or wrong: the served logits follow the
    field (the reference told the same agrees), and the reference with the
    field as published is told apart."""
    cfg, eng = trinity
    tokens = list(range(9, 50))
    other = dataclasses.replace(cfg, **change)
    got = _served_logits(other, eng.params, tokens, len(tokens))[-1]
    want = _reference_logits(eng.params, other, tokens)[-1]
    assert float(jnp.abs(got - want).max()) < TOL
    old = _reference_logits(eng.params, cfg, tokens)[-1]
    assert float(jnp.abs(got - old).max()) > 100 * TOL


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_the_reference_with_a_fault_is_told_apart(trinity, fault):
    """hold_trinity.py's faults, at a tiny size: each moves the
    reference's own logits by far more than TOL, and so away from the
    served ones (the study on the chip shows the cell's limits see
    them)."""
    cfg, eng = trinity
    tokens = list(range(9, 50))
    good = _reference_logits(eng.params, cfg, tokens)
    bad = _reference_logits(eng.params, cfg, tokens, fault=fault)
    assert float(jnp.abs(good - bad).max()) > 100 * TOL
    served = _served_logits(cfg, eng.params, tokens, len(tokens))[-1]
    assert float(jnp.abs(served - good[-1]).max()) < TOL
    assert float(jnp.abs(served - bad[-1]).max()) > 100 * TOL


# ------------------------------------------------------ what is refused

def test_config_refuses_what_is_not_built():
    def tiny(**kw):
        return LlamaConfig.tiny(**{**TRINITY, **kw})
    # window layers beside a norm over the whole projected vector, a
    # latent pool, or conv / mamba / retention layers
    with pytest.raises(ValueError, match="sliding_attention.*beside qk_norm"):
        tiny(qk_norm_per_head=False, qk_norm=True, score_head_dim=0,
             value_head_dim=0)
    with pytest.raises(ValueError, match="sliding_attention.*beside no positions"):
        tiny(rope=False)
    for kind in ("conv", "retention"):
        with pytest.raises(ValueError, match=f"not built beside|{kind} layers"):
            tiny(layer_types=[WIN] * 4 + [kind], n_experts=0,
                 experts_per_token=0, n_dense_layers=0, shared_ffn_dim=0,
                 router_bias=False)
    # a position-free full kind needs window layers that rotate
    with pytest.raises(ValueError, match="full_rope=False describes"):
        tiny(layer_types=[FULL] * 5, sliding_window=0, window_kv_heads=0,
             window_rope_theta=0.0)
    # the gate and the second norm are the attention operators' (the latent
    # one's too since PR 55) and the delta rule's, and no other operator's
    assert LlamaConfig.tiny(dim=64, attn_gate=True, kv_lora_rank=32,
                            qk_nope_head_dim=8, qk_rope_head_dim=8,
                            v_head_dim=8).gated_block
    with pytest.raises(ValueError, match="gated block.*beside conv"):
        LlamaConfig.tiny(dim=64, post_norms=True,
                         layer_types=["conv", FULL, "conv", FULL])
    with pytest.raises(ValueError, match="mamba layers: not built beside gated block"):
        LlamaConfig.tiny(dim=64, attn_gate=True, ssm_state=8, ssm_heads=4,
                         ssm_head_dim=8,
                         layer_types=["mamba", FULL, "mamba", FULL])

