"""The Ouro block in the serving engine (a LOOPED stack: the same layers
walked ``ut_steps`` times, each pass on page planes of its own, the final
norm after every pass and an exit gate on each pass's normed stream):
through the one ragged step and the decode loop, against the benchmark's
plain reference (benchmark/reference_ouro.py: whole sequences, no page, no
cache) on seeded weights. Tiny widths on the CPU, float32 compute: 2 layers
x 3 passes (6 planes), so that a pass / layer mix-up cannot cancel.

TOL: everything runs in float32 here, so the two sides differ by summation
order only. 1e-4 on LOGITS and on the GATES leaves room and still fails
every fault of the hold study (benchmark/hold_ouro.py): passes that share
one plane, the final norm applied once, and (on the gates) a gate without
its bias.

The engine-level cases every block goes through (chunked prefill and the
decode loop, a mixed batch, a reused slot, preemption, the page copy over
EVERY plane, a prefix hit that restores every plane, the refusals by name)
run on this block's row from tests/test_llm_block_ouro.py.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from _blocks import (BLOCKS, ENGINE, PS, _step, built,  # noqa: E402
                     served_logits)
from benchmark import reference_ouro as ref  # noqa: E402
from ray_tpu.llm import InferenceEngine  # noqa: E402
from ray_tpu.llm import model as M  # noqa: E402
from ray_tpu.llm.cache import (kv_cache_tag, make_kv_cache,  # noqa: E402
                               page_planes, prefix_cache_supported)
from ray_tpu.models.llama import LlamaConfig  # noqa: E402

TOL = 1e-4
FIELDS = BLOCKS["ouro"].fields
N_PROMPT, N_NEW, CHUNK = 37, 9, 16


@pytest.fixture(scope="module")
def ouro():
    """(cfg, params, tokens, the reference's logits and gates over them)."""
    cfg, params = built("ouro")
    tokens = np.random.default_rng(11).integers(
        0, cfg.vocab_size, N_PROMPT + N_NEW)
    with jax.default_matmul_precision("highest"):
        logits, gates = ref.forward(params, jnp.asarray(tokens, jnp.int32),
                                    ref.dims_of(cfg))
    return cfg, params, tokens, logits, gates


def _ends():
    """The positions whose logits served_logits returns: each chunk's last
    token, then every decoded one."""
    return [min(N_PROMPT, lo + CHUNK) - 1
            for lo in range(0, N_PROMPT, CHUNK)] \
        + list(range(N_PROMPT, N_PROMPT + N_NEW))


def _worst(got, want) -> float:
    return max(float(jnp.abs(g - want[at]).max())
               for at, g in zip(_ends(), got))


# ------------------------------------------------ the tree and the pool

def test_param_tree_pool_and_stats():
    cfg, params = built("ouro")
    assert cfg.hybrid and cfg.gated_block and cfg.ut_steps == 3
    assert params["exit_w"].shape == (64,)
    assert params["exit_b"].shape == (1,) and float(params["exit_b"][0]) != 0
    assert set(params["layers"]) == {"attn", "dense"}
    assert params["layers"]["attn"]["attn_post_norm"].shape == (2, 64)
    # ONE pass's pattern: the passes are a scan around the walk
    assert M._pattern(cfg) == ([], [("full_attention", "dense")], 2)
    assert M.step_counters(cfg) == ("ut_exit_at_1", "ut_exit_at_2",
                                    "ut_exit_at_3")
    assert page_planes(cfg) == 6 and prefix_cache_supported(cfg)
    kv = make_kv_cache(cfg, 16, PS)
    assert kv["k"].shape == kv["v"].shape == (6, 16, 4, PS, 16)
    eng = InferenceEngine(cfg, params, **ENGINE)
    assert eng.kv["k"].shape[0] == 6 and eng.prefix is not None
    row = 2 * 4 * 16 * 4                     # K and V, 4 heads of 16, f32
    assert eng.stats["kv_token_layer_bytes"] == row
    assert eng.stats["kv_planes"] == 6
    assert eng.stats["kv_token_bytes"] == 6 * row
    # pages of one scheme never match a lookup under another
    once = LlamaConfig.tiny(**{**FIELDS, "ut_steps": 1})
    assert kv_cache_tag(cfg, None) != kv_cache_tag(once, None)
    assert "kv_planes" not in InferenceEngine(once, **ENGINE).stats


def test_the_published_sizes():
    """192 planes and 1,572,864 B a token at the published widths."""
    cfg = LlamaConfig.tiny(
        vocab_size=49152, dim=2048, n_layers=48, n_heads=16, n_kv_heads=16,
        ffn_dim=5632, post_norms=True, tie_embeddings=False, ut_steps=4)
    kv = jax.eval_shape(lambda: make_kv_cache(cfg, 350, 16, lane_pad=True))
    assert kv["k"].shape == (192, 350, 16, 16, 128)
    token = sum(a.dtype.itemsize * a.size
                for a in kv.values()) // (350 * 16)
    assert token == 1_572_864 == 192 * 8192


def test_one_pass_is_every_other_blocks_configuration():
    """ut_steps = 1 is the default: the same static argument of the step
    programs' jits, no gate leaf, no counter, no words of the looped
    stack in a text (tests/test_llm_blocks_lowering.py holds the rest)."""
    gated = dict(n_layers=2, post_norms=True, tie_embeddings=False)
    cfg = LlamaConfig.tiny(**gated, ut_steps=1)
    assert cfg == LlamaConfig.tiny(**gated) and not M.step_counters(cfg)
    params = jax.eval_shape(
        lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    assert "exit_w" not in params and "exit_b" not in params


@pytest.mark.parametrize("beside", [
    dict(n_experts=4, experts_per_token=2),
    dict(layer_types=["mamba", "full_attention"], ssm_state=8, ssm_heads=4,
         ssm_head_dim=8),
    dict(layer_types=["sliding_attention", "full_attention"],
         sliding_window=8, window_kv_heads=2, window_rope_theta=1e4),
    dict(kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
         v_head_dim=8),
    dict(qk_norm=True), dict(attn_gate=True), dict(ut_steps=0)])
def test_what_the_loop_is_not_built_beside_is_refused(beside):
    with pytest.raises(ValueError, match="looped layers|ut_steps"):
        LlamaConfig.tiny(**{**FIELDS, **beside})


# --------------------------------------- logits and gates, to the reference

def test_chunked_prefill_and_decode_match_the_reference_on_logits(ouro):
    cfg, params, tokens, logits, _ = ouro
    got, _ = served_logits(cfg, params, tokens, N_PROMPT, CHUNK)
    assert len(got) == len(_ends())
    assert _worst(got, logits) < TOL


_passes = jax.jit(
    lambda params, tok, pos, page, at, table, q_start, q_len, kv_len, kv,
    cfg, decode_rows, max_q_len: M._passes(
        params, params["embed"].astype(cfg.dtype)[tok][None], kv,
        M._Rows(pos, None, q_start, q_len, decode_rows, page, at, table,
                kv_len, max_q_len), cfg, "reference"),
    static_argnames=("cfg", "decode_rows", "max_q_len"))


def _served_gates(cfg, params, tokens):
    """The program's gates [passes] at every position ``_ends`` names: the
    prompt as ONE row of chunk tokens a step, then one-token rows, through
    ``_passes`` over a pool of a plane a pass and layer."""
    pages = 8
    kv = make_kv_cache(cfg, pages + 1, PS)
    table = (1 + np.arange(pages, dtype=np.int32))[None]
    pieces = [(lo, min(CHUNK, N_PROMPT - lo))
              for lo in range(0, N_PROMPT, CHUNK)] \
        + [(t, 1) for t in range(N_PROMPT, len(tokens))]
    out = []
    for lo, n in pieces:
        T = CHUNK if n > 1 or lo < N_PROMPT else 1
        tok, pos, page, at = (np.zeros(T, np.int32) for _ in range(4))
        where = np.arange(lo, lo + n)
        tok[:n], pos[:n] = tokens[lo:lo + n], where
        page[:n], at[:n] = 1 + where // PS, where % PS
        _, kv, gates = _passes(
            params, *map(jnp.asarray, (
                tok, pos, page, at, table, np.zeros(1, np.int32),
                np.asarray([n], np.int32), np.asarray([lo + n], np.int32))),
            kv, cfg, decode_rows=int(T == 1), max_q_len=T)
        out.append(gates[:, 0])
    return jnp.stack(out, axis=1)                           # [passes, ends]


def test_the_gates_match_the_reference_and_need_their_bias(ouro):
    """The gate of every pass at every served position is the reference's;
    the reference WITHOUT the bias (drawn N(0, 0.02), not zero) is told
    apart at the same tolerance."""
    cfg, params, tokens, _, gates = ouro
    got = _served_gates(cfg, params, tokens)
    assert got.shape == (3, len(_ends()))
    assert float(jnp.abs(got - gates[:, _ends()]).max()) < TOL
    with jax.default_matmul_precision("highest"):
        _, unbiased = ref.forward(params, jnp.asarray(tokens, jnp.int32),
                                  ref.dims_of(cfg), faults=("no_gate_bias",))
    assert float(jnp.abs(got - unbiased[:, _ends()]).max()) > 10 * TOL
    # the gates differ by pass and position: a gate read off the wrong
    # pass's stream could not match
    assert float(jnp.abs(gates[0] - gates[1]).max()) > 0.05


@pytest.mark.parametrize("fault", ["shared_kv", "norm_once"])
def test_the_reference_tells_each_fault_of_the_loop_apart(ouro, fault):
    """Passes that attend over the first pass's keys and values (a pool
    of one plane a layer), and a final norm applied after the last pass
    only: each moves the logits by far more than the tolerance."""
    cfg, params, tokens, logits, _ = ouro
    with jax.default_matmul_precision("highest"):
        wrong, _ = ref.forward(params, jnp.asarray(tokens, jnp.int32),
                               ref.dims_of(cfg), faults=(fault,))
    got, _ = served_logits(cfg, params, tokens, N_PROMPT, CHUNK)
    assert _worst(got, wrong) > 100 * TOL
    assert float(jnp.abs(wrong - logits).max()) > 100 * TOL


# ----------------------------------------------- a pass reads its own planes

def test_a_pass_reads_its_own_planes_only(ouro):
    """The prompt's planes, written by the prefill, are read back by the
    decode rows: garbage in every plane of the pages the sequence does NOT
    hold (freed pages, another sequence's) moves nothing; two planes
    swapped (pass 0's and pass 1's of layer 0; pass 1's two layers) move
    the logits of every later token."""
    cfg, params, tokens, logits, _ = ouro
    _, kv = served_logits(cfg, params, tokens[:N_PROMPT], N_PROMPT, CHUNK)
    held = 1 + (N_PROMPT + N_NEW - 1) // PS        # pages 1 .. held

    def decode(kv):
        """The decoded positions' logits from a prefilled pool."""
        got, _ = served_logits(cfg, params, tokens, N_PROMPT, CHUNK,
                               kv=dict(kv))
        return got[-N_NEW:]

    def worst(got):
        return max(float(jnp.abs(g - logits[N_PROMPT + i]).max())
                   for i, g in enumerate(got))

    # served_logits re-runs the prefill into the pool it is handed: the
    # prompt's pages are written again, every other page keeps what it held
    assert worst(decode(kv)) < TOL
    noise = jax.random.normal(jax.random.PRNGKey(0), kv["k"].shape)
    dirty = {leaf: a.at[:, held + 1:].set(
        100.0 * noise[:, held + 1:].astype(a.dtype)) for leaf, a in kv.items()}
    assert worst(decode(dirty)) < TOL

    def next_logits(kv):
        """One decode row at position N_PROMPT over a prefilled pool."""
        i32 = lambda *a: jnp.asarray(a, jnp.int32)          # noqa: E731
        table = jnp.asarray((1 + np.arange(16))[None], jnp.int32)
        out, _, _ = _step(
            params, i32(tokens[N_PROMPT]), i32(N_PROMPT),
            i32(1 + N_PROMPT // PS), i32(N_PROMPT % PS), table, i32(0),
            i32(1), i32(N_PROMPT + 1), dict(kv), cfg,
            paged_impl="reference", max_q_len=1, decode_rows=1)
        return out[0]

    assert float(jnp.abs(next_logits(kv) - logits[N_PROMPT]).max()) < TOL
    for a, b in ((0, 2), (2, 3)):
        order = np.arange(6)
        order[[a, b]] = b, a
        swapped = {leaf: arr[order] for leaf, arr in kv.items()}
        assert float(jnp.abs(next_logits(swapped)
                             - logits[N_PROMPT]).max()) > 100 * TOL, (a, b)


# ------------------------------------------------------------- the counters

def test_exit_counts_arithmetic():
    """The pass at which the cumulative exit mass first reaches 0.5, by
    hand: lambda (0.6, ., .) leaves at 1; (0.2, 0.5, .) has mass 0.2 then
    0.6: at 2; (0.1, 0.1, 0.9) has 0.1, 0.19: at 3, the last, where the
    mass is 1 whatever the gate says; an invalid row counts nowhere."""
    gates = jnp.asarray([[0.6, 0.2, 0.1, 0.9], [0.0, 0.5, 0.1, 0.9],
                         [0.0, 0.0, 0.9, 0.9]])
    valid = jnp.asarray([True, True, True, False])
    assert M._exit_counts(gates, valid).tolist() == [1, 1, 1]
    p = ref.exit_distribution(gates)
    assert np.allclose(np.asarray(p.sum(axis=0)), 1.0)
    assert np.allclose(np.asarray(p[:, 1]), [0.2, 0.4, 0.4])


def test_the_step_programs_count_the_rows_by_exit_pass(ouro):
    """The mixed step's counters are the reference's gates counted: a
    chunk row's last token and a decode row beside an idle slot; and the
    engine adds up one count a valid row of every dispatch."""
    cfg, params, tokens, _, gates = ouro
    counted = []
    kv = make_kv_cache(cfg, 9, PS)
    table = jnp.asarray(np.stack([np.zeros(8), 1 + np.arange(8)]), jnp.int32)
    for lo, n in [(0, 16), (16, 16), (32, 5)] + [
            (t, 1) for t in range(N_PROMPT, N_PROMPT + N_NEW)]:
        tok, pos, page, at = (np.zeros(17, np.int32) for _ in range(4))
        where = np.arange(lo, lo + n)
        tok[1:1 + n], pos[1:1 + n] = tokens[lo:lo + n], where
        page[1:1 + n], at[1:1 + n] = 1 + where // PS, where % PS
        _, kv, counts = _step(
            params, *map(jnp.asarray, (tok, pos, page, at)), table,
            jnp.asarray([0, 1], jnp.int32), jnp.asarray([0, n], jnp.int32),
            jnp.asarray([0, lo + n], jnp.int32), kv, cfg,
            paged_impl="reference", max_q_len=16, decode_rows=1)
        counted.append(np.asarray(counts))
    want = M._exit_counts(gates[:, _ends()], jnp.ones(len(_ends()), bool))
    assert np.sum(counted, axis=0).tolist() == want.tolist()
    assert all(c.sum() == 1 for c in counted)
    assert sum(want.tolist()) == len(_ends()) and max(want.tolist()) < 12

    eng = InferenceEngine(cfg, params, **ENGINE)
    eng.generate(list(tokens[:N_PROMPT]), N_NEW)
    counts = [eng.stats[k] for k in M.step_counters(cfg)]
    # one a chunk row and one a decode row-step, idle slots counted nowhere
    rows = eng.stats["chunk_rows"] + eng.stats["decode_tokens"]
    assert sum(counts) == rows >= 3 + N_NEW - 1
