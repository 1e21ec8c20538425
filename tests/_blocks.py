"""The blocks the engine serves, declared ONCE for the tests: a row of
BLOCKS a block, at tiny widths, with what a test needs to serve it and to
compare what was served (its plain reference under benchmark/, the
tolerance, the state it keeps beside its pages), and the helpers every
engine-level comparison is written with.

Not a test file. The engine-level cases are written once, in
tests/_block_cases.py, and run over every row, each row under a collected
file of its own (tests/test_llm_block_<row>.py: a file is what a worker of
the suite is handed, so no file runs every row);
tests/test_llm_blocks_lowering.py reads the programs' texts,
tests/test_llm_ahead.py the engine that runs ahead; a block's own file
(tests/test_llm_<block>.py) keeps its operator's arithmetic and the faults
its reference must tell apart, on a configuration of its own.

A new block is a row here: it gets every engine case the day it is added,
and a change that breaks an older block fails a case that names it. The
row's file is three lines, a docstring and

    from _block_cases import *  # noqa: F401,F403
    BLOCK = "<row>"

and tests/test_llm_blocks_lowering.py fails, with these lines in its
message, while a row has no file or a file names a row that is gone.
"""

import dataclasses
import functools
import importlib
import zlib
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.llm import model as M
from ray_tpu.llm.cache import (keeps_slot_state, make_kv_cache,
                               window_first_page, window_table_width)
from ray_tpu.models.llama import (WINDOW, LlamaConfig, init_params,
                                  yarn_mscale)


@dataclasses.dataclass(frozen=True)
class Block:
    #: LlamaConfig.tiny's keywords
    fields: dict
    #: the module under benchmark/ that is its plain reference: dims_of,
    #: forward_logits and score_greedy
    reference: str
    #: on float32 logits of unit spread, program against reference: the
    #: two differ by summation order only (~1e-6), and 1e-4 still fails a
    #: bf16 computation (~1e-2) and every part left out. The delta block's
    #: norm AFTER each branch rescales a small branch and its rounding with
    #: it (worst of some 40 positions 1.4e-4: tests/test_llm_gigachat.py)
    tol: float = 1e-4
    #: the pool's leaves a batch slot (llm/cache.py: SLOT_STATE), none
    #: where pages are the only state
    state: Tuple[str, ...] = ()
    #: a second page group that frees behind a window
    window: bool = False

    @property
    def prefix_cache(self) -> bool:
        """Pages are the only state and none frees behind a window."""
        return not self.state and not self.window


_LFM2_PATTERN = ["conv", "conv"] + ["full_attention", "conv", "conv",
                                    "conv"] * 2
BLOCKS = {
    "mistral": Block(dict(n_layers=2), "reference"),
    "olmoe": Block(
        dict(n_layers=2, n_kv_heads=8, n_experts=8, experts_per_token=2,
             qk_norm=True, tie_embeddings=False), "reference_olmoe"),
    "lfm2": Block(
        dict(n_layers=10, n_heads=8, n_kv_heads=2, ffn_dim=32,
             dense_ffn_dim=96, n_dense_layers=2, n_experts=8,
             experts_per_token=2, norm_topk_prob=True,
             layer_types=_LFM2_PATTERN, qk_norm_per_head=True,
             router_score="sigmoid", router_bias=True, router_eps=1e-6),
        "reference_lfm2", state=("conv",)),
    "kanana": Block(
        dict(n_layers=3, n_heads=4, n_kv_heads=4, ffn_dim=16,
             dense_ffn_dim=96, n_dense_layers=1, n_experts=8,
             experts_per_token=3, norm_topk_prob=True,
             router_score="sigmoid", router_bias=True, router_eps=1e-20,
             router_scale=2.448, kv_lora_rank=32, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16, shared_ffn_dim=32,
             tie_embeddings=False), "reference_kanana"),
    "granite": Block(
        dict(n_layers=8, n_heads=8, n_kv_heads=2, ffn_dim=96,
             layer_types=["mamba", "mamba", "full_attention", "mamba"] * 2,
             ssm_heads=8, ssm_head_dim=16, ssm_state=16, ssm_chunk=8,
             rope=False, attn_scale=1 / 64, embed_scale=12.0,
             residual_scale=0.22, logits_divisor=8.0),
        "reference_granite", state=("ssm", "ssm_conv")),
    "brumby": Block(
        dict(n_layers=2, n_heads=4, n_kv_heads=2, ffn_dim=96,
             layer_types=["retention"] * 2, qk_norm_per_head=True,
             tie_embeddings=False, retention_chunk=8),
        "reference_brumby", state=("retention", "retention_norm")),
    "mimo": Block(
        dict(n_layers=5, n_heads=8, n_kv_heads=2, window_kv_heads=4,
             ffn_dim=32, dense_ffn_dim=96, n_dense_layers=1, n_experts=16,
             experts_per_token=4, norm_topk_prob=True,
             router_score="sigmoid", router_bias=True, experts_held=(4, 8),
             tie_embeddings=False,
             layer_types=["full_attention", "sliding_attention"] * 2
             + ["full_attention"], score_head_dim=24, value_head_dim=16,
             rotary_dim=8, value_scale=0.707, sliding_window=16,
             window_rope_theta=1e4, attn_sink=True),
        "reference_mimo", window=True),
    "trinity": Block(
        dict(n_layers=5, n_heads=8, n_kv_heads=2, window_kv_heads=2,
             ffn_dim=32, dense_ffn_dim=96, n_dense_layers=1, n_experts=16,
             experts_per_token=4, norm_topk_prob=True,
             router_score="sigmoid", router_bias=True, router_eps=1e-20,
             router_scale=2.826, shared_ffn_dim=32, tie_embeddings=False,
             layer_types=["sliding_attention"] * 4 + ["full_attention"],
             score_head_dim=16, value_head_dim=16, sliding_window=16,
             window_rope_theta=1e4, rope_theta=1e4, qk_norm_per_head=True,
             attn_gate=True, post_norms=True, full_rope=False,
             embed_scale=8.0), "reference_trinity", window=True),
    "gigachat": Block(
        dict(n_layers=5, n_heads=4, n_kv_heads=4, ffn_dim=32,
             dense_ffn_dim=96, n_dense_layers=1, n_experts=8,
             experts_per_token=3, norm_topk_prob=True,
             router_score="sigmoid", router_bias=True, router_eps=1e-20,
             router_scale=2.5, shared_ffn_dim=32, experts_held=(2, 4),
             tie_embeddings=False,
             layer_types=["linear_attention"] * 4 + ["full_attention"],
             kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, q_lora_rank=24, rope_yarn=(8, 16, 32, 1, 1, 1),
             # the score scale the reference derives: the head's, times
             # YaRN's temperature term twice (mscale_all_dim 1 at factor 8)
             attn_scale=24 ** -0.5 * yarn_mscale(8, 1) ** 2, attn_gate=True,
             post_norms=True, norm_gate=2.0,
             ffn_clamp=10.0, delta_key_heads=2, delta_value_heads=4,
             delta_key_dim=8, delta_value_dim=16, delta_chunk=8),
        "reference_gigachat", tol=3e-4, state=("delta", "delta_conv")),
    # a decoder-hybrid-decoder: two periods of (Mamba-1, window), the
    # (Mamba-1, full) pair whose memory and pages the cross-decoder reads,
    # and one period of it (gated memory unit, cross attention)
    "phi4flash": Block(
        dict(n_layers=8, n_heads=8, n_kv_heads=4, window_kv_heads=4,
             ffn_dim=96,
             layer_types=["mamba1", "sliding_attention"] * 2
             + ["mamba1", "full_attention", "gmu", "cross_attention"],
             ssm1_state=16, ssm1_dt_rank=4, sliding_window=16,
             window_rope_theta=1e4, rope=False, diff_attention=True,
             layer_norm=True, attn_bias=True),
        "reference_phi4flash", state=("ssm1", "ssm1_conv"), window=True),
    # a looped stack: two layers walked three times, a page plane a pass
    # and layer (a pass / layer mix-up cannot cancel at 2 x 3)
    "ouro": Block(
        dict(n_layers=2, n_heads=4, n_kv_heads=4, ffn_dim=96,
             post_norms=True, tie_embeddings=False, ut_steps=3),
        "reference_ouro")}

#: the engine every case serves on: pages of 8, chunk rows of 16, two a
#: step, decode loops of 4
ENGINE = dict(page_size=8, total_pages=64, max_batch=4, max_seq_len=128,
              prefill_chunk=16, prefill_rows=2, decode_chunk=4, seed=3)
PS = ENGINE["page_size"]


def config(block: str, **over) -> LlamaConfig:
    """``block``'s tiny configuration (bfloat16, as LlamaConfig.tiny makes
    it), ``over`` on top."""
    return LlamaConfig.tiny(**{**BLOCKS[block].fields, **over})


def reference(block: str):
    """``block``'s plain reference, imported when a case first needs it (a
    checkout from before the block has no such module)."""
    return importlib.import_module(f"benchmark.{BLOCKS[block].reference}")


def seeded(cfg: LlamaConfig, seed: int = 5):
    """Weights whose norms (and a state-space layer's D) are not ones: ones
    would hide a norm that is skipped, misplaced or over the wrong axis,
    and a D that is left out would read as x. A block whose norms are
    gated is drawn off one already (models/llama.py: norm_gate)."""
    params = init_params(cfg, jax.random.PRNGKey(seed))
    if cfg.norm_gate:
        return params

    def off_one(path, leaf):
        name = path[-1].key
        if not (name.endswith("norm") or name == "D"):
            return leaf
        key = jax.random.PRNGKey(zlib.crc32(str(path).encode()))
        return (1.0 + 0.5 * jax.random.normal(key, leaf.shape)).astype(
            leaf.dtype)
    return jax.tree_util.tree_map_with_path(off_one, params)


def run(eng) -> dict:
    """Step ``eng`` until it has no work: request id -> tokens."""
    done = {}
    for _ in range(400):
        done.update(eng.step())
        if not eng.has_work():
            return done
    raise AssertionError("engine did not drain")


def worst_gap(block: str, eng, prompt, served, pad_to: int = 128) -> float:
    """How far under the reference's top LOGIT the served tokens sit,
    teacher-forced over prompt + served: logits, not token identity."""
    ref = reference(block)
    with jax.default_matmul_precision("highest"):
        got = ref.score_greedy(eng.params, ref.dims_of(eng.cfg),
                               list(prompt), list(served), pad_to)
    return max(got["gap"])


def reference_logits(block: str, params, cfg: LlamaConfig, tokens):
    """[len(tokens), vocab]: the reference's whole forward."""
    ref = reference(block)
    with jax.default_matmul_precision("highest"):
        return ref.forward_logits(params, jnp.asarray(tokens, jnp.int32),
                                  ref.dims_of(cfg))


_step = jax.jit(M._ragged_logits, static_argnames=(
    "cfg", "paged_impl", "max_q_len", "decode_rows"))
#: pages of the hand-built window group at least: a RING, so that a
#: logical page lands on a physical page an earlier one used (freed behind
#: the window)
RING = 6


def served_logits(cfg: LlamaConfig, params, tokens, n_prompt=None,
                  chunk: int = 16, kv=None, slot: int = 1):
    """``tokens`` through the mixed step's forward, teacher-forced: the
    first ``n_prompt`` (default: all) as ONE chunk row of at most ``chunk``
    tokens a step (row 2, behind two decode rows and before padding), the
    rest one a step as a DECODE row (row ``slot``; the other idle).
    Returns ([the row's logits after each step], the pool).

    The full group's table names pages 1.. in order; a window group's is
    COMPACT (its width the seam's) over a ring of pages; a block that
    keeps slot state runs in slot ``slot``, every other token on the
    scratch slot."""
    n_prompt = len(tokens) if n_prompt is None else n_prompt
    pages, T, R = 16, 2 + chunk + 3, 3
    has_state = keeps_slot_state(cfg)
    windowed = WINDOW in cfg.layer_types
    cols = window_table_width(cfg.sliding_window, chunk, PS) \
        if windowed else 0
    ring = max(RING, cols)
    if kv is None:
        kv = make_kv_cache(
            cfg, pages + 1, PS, **(dict(max_batch=3) if has_state else {}),
            **(dict(window_pages=ring + 1) if windowed else {}))
    table = np.zeros((R, pages), np.int32)
    table[slot] = table[2] = 1 + np.arange(pages)
    pieces = [(lo, min(chunk, n_prompt - lo), 2)
              for lo in range(0, n_prompt, chunk)] \
        + [(t, 1, slot) for t in range(n_prompt, len(tokens))]
    out = []
    for lo, n, row in pieces:
        t0 = 2 if row == 2 else row
        tok, pos = np.zeros(T, np.int32), np.zeros(T, np.int32)
        page, at, wpage = (np.zeros(T, np.int32) for _ in range(3))
        where = np.arange(lo, lo + n)
        tok[t0:t0 + n], pos[t0:t0 + n] = tokens[lo:lo + n], where
        page[t0:t0 + n], at[t0:t0 + n] = 1 + where // PS, where % PS
        q_len, kv_len = np.zeros(R, np.int32), np.zeros(R, np.int32)
        q_len[row], kv_len[row] = n, lo + n
        extra = {}
        if has_state:
            state = np.full(T, 3, np.int32)
            state[t0:t0 + n] = slot
            extra["token_state"] = jnp.asarray(state)
        if windowed:
            wpage[t0:t0 + n] = 1 + (where // PS) % ring
            base = window_first_page(lo, cfg.sliding_window, PS)
            wtable = np.zeros((R, cols), np.int32)
            wbase = np.zeros(R, np.int32)
            wtable[row], wbase[row] = \
                1 + (base + np.arange(cols)) % ring, base
            extra.update(token_page_win=jnp.asarray(wpage),
                         page_table_win=jnp.asarray(wtable),
                         page_base_win=jnp.asarray(wbase))
        logits, kv, _ = _step(
            params, *map(jnp.asarray, (
                tok, pos, page, at, table, np.asarray([0, 1, 2], np.int32),
                q_len, kv_len)), kv, cfg, paged_impl="reference",
            max_q_len=chunk, decode_rows=2, **extra)
        out.append(logits[row])
    return out, kv


def chunked_logits(cfg: LlamaConfig, params, prompt, chunk: int, **how):
    """(the logits after the prompt's last chunk, the pool): ``prompt``
    through served_logits as chunk rows only."""
    out, kv = served_logits(cfg, params, prompt, chunk=chunk, **how)
    return out[-1], kv


@functools.lru_cache(maxsize=None)
def built(block: str):
    """(cfg, params): ``block`` in float32 on seeded weights, built once a
    process."""
    cfg = config(block, dtype=jnp.float32)
    return cfg, seeded(cfg)
