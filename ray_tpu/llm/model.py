"""The serving step: one cache-aware decoder forward for inference.

Role-equivalent to the reference's vLLM model executor (reference:
llm/_internal/serve/deployments/llm/vllm/ — the reference ships no model
code in-tree), rebuilt on ray_tpu's functional decoder (models/llama.py —
same params pytree, so training checkpoints serve directly).

ONE WALK over the layers, for every block (``_layers``). ``_pattern``
reads the configuration as (leading layers, then one period and how many
periods for each SEGMENT of the rest), each layer an (operator kind,
feed-forward kind): the leading layers run once, then ONE ``lax.scan`` a
segment over its periods, its body one period, so depth does not unroll.
Every block but one is ONE segment (the Llama / Mistral and OLMoE blocks
patterns of period 1); a decoder-hybrid-decoder, whose depth is not one
period repeated, is three. A token LEAVES the walk where nothing more is
kept of it: the longest suffix of layers whose kind holds no page and no
state a slot (``tail_start``: a decoder-hybrid-decoder's cross-decoder;
empty in every other block) starts a segment, and in a step whose rows
hold more than one token that segment runs on each row's LAST token only
(``_layers``). Weights are one stack per KIND
(models/llama.py; the Llama tree holds every layer's leaves flat and is
split by name, ``_stacks``), closed over and indexed by a layer's ordinal
among the layers of its kind; the routed experts' [layer, expert] weights
go to their kernel whole. The feed-forward is a dense SwiGLU (``_mlp``) or
dropless routed experts (``_moe_mlp``, ops/moe.py; the router's score,
selection bias, epsilon and scale are fields), with a shared expert
beside them where ``shared_ffn_dim`` says so. The logits come from the
embedding table or an ``lm_head`` (``tie_embeddings``); ``embed_scale``,
``residual_scale`` and ``logits_divisor`` multiply the embedding, both
branches of every layer and the logits (1 everywhere but the granite
family). A configuration lowers to the code of its own fields and to no
other block's (tests/test_llm_blocks_lowering.py).

ONE TABLE of layer operators (``OPERATORS``): kind -> (its stack of
weights, its body), every body ``(lp, l, x, kv, rows, cfg, impl) ->
(x, kv)``: the layer's weights, its ordinal ``l``, the stream, the pool,
the ragged batch (``_Rows``) and the kernel-or-reference choice. What a
kind keeps per batch slot is declared beside it in llm/cache.py
(``SLOT_STATE``), by the same key:

  - attention (``_attention``; the only operator of the Llama, Mistral
    and OLMoE blocks): pages, and nothing a slot. Variation points: an
    RMSNorm on the projected q and k before the rotary embedding
    (``qk_norm`` over the whole vector, ``qk_norm_per_head`` over each
    head), no positional embedding at all (``rope``), a score scale of
    its own (``attn_scale``). With ``kv_lora_rank`` it is latent attention
    (``_latent_attention``; Kanana-2 is the first such block): no wk /
    wv, a token's cache in a layer ONE row of kv_lora_rank +
    qk_rope_head_dim values for all heads in a pool of ONE leaf {"k"},
    decode rows and chunks both in the ABSORBED form (w_uk folded into
    the query, w_uv into the output), so the kernel runs multi-query
    attention over the latent rows, the value a lane slice of the K block
    it holds, and the cached prefix is never expanded in HBM.
  - the gated short convolution (``_short_conv``; LFM2): no pages; the
    last ``conv_kernel - 1`` inputs of its depthwise conv, kilobytes a
    slot, gathered and scattered by slot.
  - the selective state-space recurrence (``_mamba``; granite-4.0-h): a
    MATRIX state [ssm_heads, ssm_head_dim, ssm_state] a layer, megabytes
    a slot, beside the last inputs of its own conv over x, B and C
    (``_SsmConvState``: one block, no gather).
  - power retention of degree 2 (``_retention``; Brumby-14B, which has
    NO attention layer: the page leaves then have no layer): q, k and v
    projected, normed and rotated as attention's, and per slot and
    key/value head a matrix state over the degree-2 expansion of the key
    [D, head_dim], decayed a token by a sigmoid gate, beside its
    normaliser.

  - window attention (``_window_attention``; MiMo-V2-Flash, whose full
    layers take a score head wider than the value head, a partial rotary
    embedding and a value scale through ``_attention``): the attention
    operator on its own key/value heads and rotary base over a SECOND page
    group (llm/cache.py), a token seeing the last ``sliding_window``
    positions through its row's compact page table, a float32 sink a query
    head in the softmax's denominator; nothing a slot.
  - a GATED block (Trinity-Mini; ``cfg.gated_block``): both attention
    operators' output times sigmoid(h w_og) before wo (``attn_gate``),
    every branch through a norm of its own before it joins the stream
    (``post_norms``: ``_residual``), and the rotary embedding on the window
    layers only (``full_rope=False``), beside the per-head q/k norm.

  - gated-delta-rule linear attention (``_delta``; GigaChat3.5, whose
    every fourth layer is LATENT attention with a low-rank query, YaRN
    frequencies, the gated block's output gate and second norm, every norm
    a sigmoid-gated one (``_norm``) and every SwiGLU clamped): no pages;
    per slot and VALUE head a FLOAT32 matrix state [dk, dv] that a token
    decays and then corrects along its key (it reads what it is about to
    write), beside the last inputs of its conv over q, k and v. The first
    block whose pool holds a latent page leaf AND slot-state leaves.

  - a DECODER-HYBRID-DECODER (Phi-4-mini-flash; models/llama.py: MAMBA1,
    GMU, CROSS): the Mamba-1 selective scan (``_mamba1``: every (channel,
    state index) pair of a float32 state [N, channels] a slot decays on its
    own, beside the last inputs of its conv; its scan output, before the
    gate, rides the walk's carry as the MEMORY); the gated memory unit
    (``_gmu``: W2(m * silu(h W1)) on the newest memory; nothing kept);
    DIFFERENTIAL attention on the full and the window operator
    (``_diff_attention``: a pair of adjacent heads ONE 128-lane head of
    the pool, its two queries [q1 | 0] and [0 | q2], so the paged kernels
    run as they are; the combine after them) and on cross attention
    (``_cross_attention``: own queries over the pages the newest full
    layer wrote, no write, nothing kept); every norm a LayerNorm with a
    bias (``_norm``), biases on the attention projections; no positions.
    The first block with state a slot, a window page group AND a full
    group at once, and the first whose full group has ONE layer that
    eight operators read.

  - a LOOPED stack (Ouro-2.6B; ``cfg.ut_steps`` > 1, ``_passes``): the
    walk is re-entered a pass by ONE scan over the passes around the
    segments' scans (one layer body traced, not passes x layers), over the
    SAME weights; a layer's ordinal still finds its weights, and its pages
    are plane pass x layers + ordinal of the pool's leading axis, which the
    operator is handed where it was handed the ordinal; the final norm runs
    after EVERY pass and its output is what the next pass takes; the exit
    gate (one linear map with a bias, shared by the passes) reads each
    pass's normed stream at each row's last token, and the step programs
    count the rows by the pass at which the exit distribution's cumulative
    mass first reaches ``EXIT_MASS`` (``step_counters``: what an early exit
    WOULD have done; every token runs every pass). The norm and the gate
    between passes run under ``SCOPE_UT_EXIT``.

The four recurrences take the rows of a ragged batch by ONE protocol
(``_slot_rows``): the leading one-token rows update their slots in place
(a Pallas kernel each, ops/ssm.py, ops/retention.py, ops/delta.py,
ops/selective_scan.py), chunk rows start from their slot's state and leave
their last state there.

ONE step program for everything (`_ragged_step_body`): the engine packs
decode tokens and prefill-chunk tokens into a single RAGGED batch
(`ops.paged_attention.ragged_paged_attention`), so prefill chunks and
decode steps share one compiled program instead of a per-length-bucket
zoo. Per attention layer the step writes every ragged token's K/V into
the paged pool (`write_ragged_kv` — quantizing when the pool is int8) and
then attends; per row the last valid token's logits argmax fuses
in-program, so a finishing prefill chunk's first token and every decode
row's next token come back in ONE readback. `_ragged_decode_loop` is the
same step with every batch slot a one-token row, scanned ``num_steps``
times in one program.

The pool is a dict pytree (llm/cache.py: ``make_kv_cache``): page leaves
{"k", "v"[, "k_scale", "v_scale"]} (a latent pool: {"k"}), the ATTENTION
layers stacked on the leading axis, and the leaves ``SLOT_STATE``
declares, [layers of the kind, slots + 1, ...], a slot a batch slot and a
scratch slot last. ONE buffer each in ONE layout, donated to the step
program and updated in place. The pool is a CARRY of the layer scan (and
so of the decode loop's step scan), never a scanned input or a stacked
output: a layer is written and read by its INDEX, the write
(`_kv_write_pallas`, aliased in to out) and the attention kernel both
taking the whole stacked pool, so no layer is sliced out, converted to
another layout or stacked back, and no step copies the pool (threaded as
scan xs/ys it moved ~4 times a step: PERF.md, PR 27). Only the int8
pool's scale leaves, which XLA reads, are scattered by XLA.

ONE DESCRIPTOR a dispatch: the two step programs take (params, desc, kv,
last), ``desc`` ONE flat int32 array that holds every integer input of the
dispatch (``step_layout`` / ``decode_layout``: the fields, each with its
shape, one after another; ``token_state`` is a field where some layer
keeps state a slot), and ``cut`` it at static offsets into the arrays
their bodies take (``_on_descriptor``). The engine fills the same layout's
numpy views on the host and makes one host-to-device transfer where it
made one a field (llm/engine.py: _descriptor_turns).

THE SLOTS' NEWEST TOKENS stay on the device from one program to the next:
each step program returns, last, every batch slot's newest token
([decode_rows] int32: the decode loop's last step; the mixed step's decode
rows and, at its slot, the first token of a prompt whose last chunk the
step ran), and takes the array the program before it returned as ``last``.
Where the descriptor's token of a decode row is NEGATIVE the program reads
that slot's entry of ``last`` instead (``_newest_from``): the one input of
a dispatch that depends on the program before it never has to pass through
the host, so the engine can pack and launch a program while the one before
it still runs (llm/engine.py).

Tensor parallelism (``tp_axis``, in ``_Rows``): the same walk also runs
INSIDE a ``shard_map`` block whose weights arrive pre-sliced
Megatron-style (wq/wk/wv/w_gate/w_up column-sharded, wo/w_down
row-sharded; llm/tp.py says which blocks). Head counts derive from the
LOCAL weight shapes, attention runs on the local kv-head shard of the
pool with zero communication, and the two row-parallel projections psum
over ``tp_axis`` — two collectives per layer, the textbook Megatron
schedule, riding ICI.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import math
from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.llm import tp as TP
from ray_tpu.llm.cache import (DELTA_CONV_LEAF, DELTA_LEAF, RET_LEAF,
                               RET_NORM_LEAF, SCRATCH_PAGE, SSM1_CONV_LEAF,
                               SSM1_LEAF, SSM_CONV_LEAF, SSM_LEAF,
                               STATE_LEAF, STATE_LEAVES, WINDOW_LEAVES,
                               keeps_nothing, keeps_slot_state,
                               make_kv_cache, window_table_width)
from ray_tpu.models.llama import (ATTENTION, CONV, CROSS, DELTA, GMU, MAMBA,
                                  MAMBA1, RETENTION, WINDOW, LlamaConfig,
                                  Params, _rmsnorm, _rope, _rope_pairs,
                                  init_params)
from ray_tpu.ops import delta, moe, retention, selective_scan, ssm
from ray_tpu.ops.paged_attention import (kernels_supported,
                                         ragged_paged_attention,
                                         write_ragged_kv)
from ray_tpu.parallel.mesh import shard_map_compat
from ray_tpu.util import compile_tracker

# {"k", "v"[, "k_scale", "v_scale"][, "k_win", "v_win"][, "conv"][, "ssm",
# "ssm_conv"][, "retention", "retention_norm"][, "ssm1", "ssm1_conv"]}, or a
# latent pool's {"k"}[, "delta", "delta_conv"]
KVCache = dict  # (llm/cache.py)


def _maybe_psum(x, tp_axis):
    return lax.psum(x, tp_axis) if tp_axis else x


def _norm(x, w, cfg: LlamaConfig, b=None):
    """The block's RMSNorm: x / rms(x) * w, or with ``norm_gate`` (a
    zero-centred gated norm) * norm_gate * sigmoid(w), which is 1 at w = 0
    where the gate is 2; with ``layer_norm`` a LayerNorm, (x - mean) /
    sqrt(var + eps) * w + ``b`` (the weight's ``*_b`` leaf)."""
    if cfg.layer_norm:
        xf = x.astype(jnp.float32)
        xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
        xf = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                            + cfg.norm_eps)
        return (xf * w.astype(jnp.float32)
                + b.astype(jnp.float32)).astype(x.dtype)
    if cfg.norm_gate:
        w = cfg.norm_gate * jax.nn.sigmoid(w.astype(jnp.float32))
    return _rmsnorm(x, w, cfg.norm_eps)


def _residual(x, y, cfg: LlamaConfig, post_norm=None):
    """x + residual_scale * y: a branch of a layer joins the stream,
    through a norm of its own where the block has one (``post_norm``: the
    branch's ``*_post_norm`` leaf, or None)."""
    if post_norm is not None:
        y = _norm(y, post_norm, cfg)
    if cfg.residual_scale != 1.0:
        y = y * jnp.asarray(cfg.residual_scale, y.dtype)
    return x + y


def _project_qkv(lp, h, cfg: LlamaConfig):
    """Head counts come from the (possibly tp-sliced) weight shapes, not
    cfg — under shard_map each device projects its local head shard."""
    cd = cfg.dtype
    hd, vd = cfg.qk_head_dim, cfg.v_dim      # head_dim, unless fields say
    B, L, _ = h.shape
    q = h @ lp["wq"].astype(cd)
    k = h @ lp["wk"].astype(cd)
    v = h @ lp["wv"].astype(cd)
    if cfg.qk_norm and not cfg.qk_norm_per_head:
        # over the whole projected vector, before the split into heads
        q = _rmsnorm(q, lp["q_norm"], cfg.norm_eps)
        k = _rmsnorm(k, lp["k_norm"], cfg.norm_eps)
    q = q.reshape(B, L, q.shape[-1] // hd, hd)
    k = k.reshape(B, L, k.shape[-1] // hd, hd)
    v = v.reshape(B, L, v.shape[-1] // vd, vd)
    if cfg.qk_norm_per_head:
        # over each head's own head_dim, one [head_dim] weight for all
        q = _rmsnorm(q, lp["q_norm"], cfg.norm_eps)
        k = _rmsnorm(k, lp["k_norm"], cfg.norm_eps)
    return q, k, v


def _mlp(lp, x, cfg: LlamaConfig, tp_axis=None):
    cd = cfg.dtype
    h = _norm(x, lp["mlp_norm"], cfg, lp.get("mlp_norm_b"))
    gate = moe.gate_half(h @ lp["w_gate"].astype(cd), cfg.ffn_clamp)
    up = moe.up_half(h @ lp["w_up"].astype(cd), cfg.ffn_clamp)
    # w_down is row-parallel under tp: each shard holds ffn/tp rows, the
    # partial products sum across the axis (Megatron second collective)
    return _residual(
        x, _maybe_psum((gate * up) @ lp["w_down"].astype(cd), tp_axis), cfg,
        lp["mlp_post_norm"] if cfg.post_norms else None)


def _moe_mlp(lp, experts, layer, x, valid, cfg: LlamaConfig, impl):
    """The feed-forward as routed experts (ops/moe.py): ``experts`` is the
    stacked [L, E, ...] weights, whole, and ``layer`` the index into
    them; ``impl`` is the step's kernel-or-reference choice, the paged
    attention's. Returns (x', the layer's routing counters)."""
    h = _norm(x, lp["mlp_norm"], cfg)
    y, counters = moe.moe_ffn(
        h[0], valid, lp["router"], experts["w_gate"], experts["w_up"],
        experts["w_down"], cfg.experts_per_token, cfg.norm_topk_prob,
        layer=layer, impl=impl, score=cfg.router_score,
        bias=lp.get("router_bias"), eps=cfg.router_eps,
        scale=cfg.router_scale,
        clamp=cfg.ffn_clamp,
        **(dict(held=cfg.experts_held) if cfg.experts_held else {}))
    y = y[None]
    if cfg.shared_ffn_dim:
        # the expert every token takes: no gate of its own, counted once
        # (a padding token's is garbage like the rest of its row)
        cd = cfg.dtype
        with jax.named_scope(SCOPE_SHARED):
            gate = moe.gate_half(h @ lp["w_shared_gate"].astype(cd),
                                 cfg.ffn_clamp)
            up = moe.up_half(h @ lp["w_shared_up"].astype(cd), cfg.ffn_clamp)
            y = y + (gate * up) @ lp["w_shared_down"].astype(cd)
    return _residual(x, y, cfg, lp["mlp_post_norm"] if cfg.post_norms
                     else None), counters


#: the routed experts' weights: handed to the expert kernel whole, [layer,
#: expert, ...], with the layer's index, never a layer's slice of them (a
#: slice handed to the kernel is a copy of a layer's experts)
_EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
#: the feed-forward's leaves of the Llama tree (models/llama.py:
#: init_params), which holds every layer's leaves flat: the rest are the
#: attention's
_FFN_LEAVES = ("mlp_norm", "router") + _EXPERT_LEAVES


#: a looped stack's counters: ``EXIT_COUNTER + "<pass>"`` (1-based) counts
#: the valid rows whose exit distribution's cumulative mass first reaches
#: EXIT_MASS at that pass
EXIT_COUNTER, EXIT_MASS = "ut_exit_at_", 0.5


def step_counters(cfg: LlamaConfig) -> Tuple[str, ...]:
    """Names of the counters the step programs append to their tokens:
    the routing counters and, where a layer holds a share of its experts,
    the pairs routed to experts held elsewhere; a looped stack's rows by
    the pass at which they would have left (``_exit_counts``)."""
    if cfg.ut_steps > 1:
        return tuple(f"{EXIT_COUNTER}{u + 1}" for u in range(cfg.ut_steps))
    if not cfg.n_experts:
        return ()
    return moe.COUNTERS + ((moe.COUNTER_ABSENT,) if cfg.experts_held else ())


#: jax.named_scope names inside the step programs: they reach the device
#: trace in each op's name path, where the benchmark's readers match them
#: (the router's, "moe_router", is ops/moe.py's). Renaming one changes a
#: metric.
SCOPE_ATTENTION, SCOPE_CONV = "attention", "short_conv"
#: ... inside "attention", everything of the latent operator but the write
#: and the kernel (wq, w_kva, the latent's norm, the rotary part, the two
#: absorbed products, wo); and the shared expert
SCOPE_MLA_PROJ, SCOPE_SHARED = "mla_proj", "moe_shared"
#: ... the state-space operator: everything around the recurrence (in_proj,
#: the conv, the gate and norm, out_proj); the one-token update (the Pallas
#: kernel); the chunk rows' scan
SCOPE_SSM_PROJ, SCOPE_SSM_UPDATE, SCOPE_SSM_SCAN = \
    "ssm_proj", "ssm_update", "ssm_scan"
#: ... the retention operator: everything around the recurrence (the
#: projections, the norms, the rotary embedding, the gate, wo); the
#: one-token update (the Pallas kernel); the chunk rows' chunk form
SCOPE_RET_PROJ, SCOPE_RET_UPDATE, SCOPE_RET_CHUNK = \
    "retention_proj", "retention_update", "retention_chunk"
#: ... a block with window layers: the whole window operator (inside
#: "attention"), and of each of the two operators everything but the write
#: and the kernel (the projections, the rotary embedding, the value scale,
#: wo)
SCOPE_WINDOW, SCOPE_WINDOW_PROJ, SCOPE_FULL_PROJ = \
    "attn_window", "attn_window_proj", "attn_full_proj"
#: ... a gated block (models/llama.py: gated_block): the output gate
#: (inside its operator's projection scope: sigmoid(h w_og) and the
#: product with the attention output), and the head with the greedy choice
#: over its logits (no other block's head is named: its ops keep the
#: names they had)
SCOPE_GATE, SCOPE_HEAD = "attn_gate", "lm_head"
#: ... the gated-delta-rule operator: everything around the recurrence (the
#: projections, the conv, the L2 norm, the gates, the output norm, w_out);
#: the one-token update (the Pallas kernel); the chunk rows' chunk form
SCOPE_DELTA_PROJ, SCOPE_DELTA_UPDATE, SCOPE_DELTA_CHUNK = \
    "delta_proj", "delta_update", "delta_chunk"


#: ... a decoder-hybrid-decoder (models/llama.py: MAMBA1, GMU, CROSS): the
#: whole Mamba-1 operator, and inside it everything around the recurrence
#: (the projections, the conv, dt, the gate, w_out), the one-token update
#: and the chunk rows' scan (a Pallas kernel each); the gated memory unit;
#: inside "attention" the whole cross operator, its projections, and its
#: reading of the newest full layer's pages (the kernel alone); and the
#: differential combine of every attention operator (the two softmaxes'
#: difference, the norm over a pair, 1 - lambda_init), inside the
#: operator's projection scope
SCOPE_SSM1, SCOPE_SSM1_PROJ, SCOPE_SSM1_UPDATE, SCOPE_SSM1_SCAN = \
    "ssm1", "ssm1_proj", "ssm1_update", "ssm1_scan"
SCOPE_GMU, SCOPE_CROSS, SCOPE_CROSS_PROJ, SCOPE_SHARED_KV, SCOPE_DIFF = \
    "gmu", "attn_cross", "attn_cross_proj", "shared_kv", "attn_diff"
#: ... a looped stack: what runs BETWEEN two passes of the walk (the final
#: norm over every token, the exit gate at each row's last)
SCOPE_UT_EXIT = "ut_exit"
#: what one layer hands a later one that is neither a page nor a slot's
#: state rides the walk's carry beside the pool, under this key (``_layers``
#: puts it there and takes it out): the newest Mamba-1 layer's scan output
#: [T, channels], after the D skip and before the gate
MEMORY = "memory"


class _Rows(NamedTuple):
    """What a step hands every operator of its ragged batch. For all: each
    token's position and the rows' spans, and how many leading rows hold at
    most one token (static). For an operator with state per batch slot:
    each token's state slot (None: token t is slot t's one token). For
    attention: each token's page and place in it, the rows' pages and
    lengths, the longest row (static), and the mesh axis its heads are
    sharded over, if any (static: the step's ``tp_axis``). For window
    attention, the same of the second page group: each token's page there,
    the rows' COMPACT tables and the logical page each starts at."""
    token_pos: jax.Array
    token_state: Optional[jax.Array]
    q_start: jax.Array
    q_len: jax.Array
    decode_rows: int = 0
    token_page: Optional[jax.Array] = None
    token_slot: Optional[jax.Array] = None
    page_table: Optional[jax.Array] = None
    kv_len: Optional[jax.Array] = None
    max_q_len: Optional[int] = None
    tp_axis: Optional[str] = None
    token_page_win: Optional[jax.Array] = None
    page_table_win: Optional[jax.Array] = None
    page_base_win: Optional[jax.Array] = None


def _shift(a, n: int, fill):
    """a[t - n] at t, ``fill`` where t < n."""
    if n == 0:
        return a
    pad = jnp.full((n,) + a.shape[1:], fill, a.dtype)
    return jnp.concatenate([pad, a[:-n]])


def _conv_window(v, fetch, rows: _Rows, K: int):
    """[v[t], v[t-1], .., v[t-(K-1)]] of a depthwise causal conv's input v
    [T, ch] over a RAGGED batch: a token's earlier inputs are the tokens
    before it in its own row where the row reaches back far enough, and
    otherwise come from its slot's saved inputs, ``fetch()`` [T, K-1, ch]:
    for each token the last K-1 inputs of its sequence before this step
    (oldest first). A row whose first token has position 0 reads zeros,
    whatever its slot holds: a new sequence, or one re-prefilled after a
    preemption, needs no reset."""
    T = v.shape[0]
    pos, slot = rows.token_pos, rows.token_state
    if slot is None:
        # the decode loop: every token is a row of its own, in slot t
        saved = fetch()
        saved = jnp.where((pos > 0)[:, None, None], saved, 0)
        return [v] + [saved[:, K - 1 - s] for s in range(1, K)]
    # reach[t]: how many tokens before t are t's own row's, up to
    # K-1 (a slot is in one row a step, at consecutive positions)
    same = (slot == _shift(slot, 1, -1)) \
        & (pos == _shift(pos, 1, -1) + 1)
    chain, chains = jnp.ones(T, bool), []
    for s in range(1, K):
        chain = chain & _shift(same, s - 1, False)
        chains.append(chain)
    reach = sum(c.astype(jnp.int32) for c in chains)
    saved = fetch()                                # [T, K-1, ch]
    saved = jnp.where((pos - reach > 0)[:, None, None], saved, 0)
    prev = [v]
    for s in range(1, K):
        # v[t - s]: in the chunk, or entry K-1-(s-reach) of the slot
        at = jnp.clip(K - 1 - s + reach, 0, K - 2)
        old = jnp.take_along_axis(
            saved, at[:, None, None], axis=1)[:, 0]
        prev.append(jnp.where(chains[s - 1][:, None],
                              _shift(v, s, 0), old))
    return prev


def _conv_upto(prev):
    """[T, K-1, ch]: the K-1 inputs up to and including each token, oldest
    first (``prev``: ``_conv_window``'s): what a row leaves in its slot is
    its last token's."""
    return jnp.stack(prev[len(prev) - 2:0:-1] + [prev[0]], axis=1)


def _short_conv(lp, l, x, kv, rows: _Rows, cfg: LlamaConfig, impl=None):
    """The gated short convolution of one layer, on entry ``l`` of
    ``STATE_LEAF`` (the layer's ordinal among the conv layers):

        (B, C, u) = split3(rms(x) W_in);  v = B * u
        c[t] = sum_j w[j] * v[t - (K-1) + j]     depthwise, causal
        x' = x + (C * c) W_out

    over a RAGGED batch (``_conv_window``): the state [n_conv, slots + 1,
    K-1, d] holds a sequence's last K-1 inputs v (oldest first). Each
    row's last K-1 inputs go back to its slot; rows without tokens, and
    padding, go to the scratch slot (the last). v is rounded to the
    compute dtype before it is used or stored, so a sequence computes the
    same values however its tokens fall into chunks; the taps are summed
    in float32; no kernel, so ``impl`` chooses nothing. Returns (x', kv)."""
    cd = cfg.dtype
    K = cfg.conv_kernel
    T = x.shape[1]
    state = kv[STATE_LEAF]
    scratch = state.shape[1] - 1
    slot = rows.token_state
    with jax.named_scope(SCOPE_CONV):
        h = _rmsnorm(x, lp["conv_norm"], cfg.norm_eps)[0]
        B, C, u = jnp.split(h @ lp["w_in"].astype(cd), 3, axis=-1)
        v = B * u                                          # [T, d]
        prev = _conv_window(
            v, lambda: lax.dynamic_slice_in_dim(state[l], 0, T, axis=0)
            if slot is None else state[l, slot], rows, K)
        w = lp["w_conv"].astype(jnp.float32)               # [K, d]
        c = sum(w[K - 1 - s] * prev[s].astype(jnp.float32)
                for s in range(K))
        y = (C.astype(jnp.float32) * c).astype(cd) @ lp["w_out"].astype(cd)
        upto = _conv_upto(prev)
        if slot is None:
            state = lax.dynamic_update_slice(
                state, upto[None].astype(state.dtype), (l, 0, 0, 0))
        else:
            last = jnp.clip(rows.q_start + rows.q_len - 1, 0, T - 1)
            row_slot = jnp.where(rows.q_len > 0, slot[last], scratch)
            state = state.at[l, row_slot].set(
                upto[last].astype(state.dtype))
    return _residual(x, y[None], cfg), {**kv, STATE_LEAF: state}


class _SsmConvState:
    """A state-space layer's conv inputs in ``SSM_CONV_LEAF`` [layers,
    slots + 1, K-1, ch], read and written as ONE block of a layer's slots,
    in the mixed step as in the decode loop: the step's leading
    ``decode_rows`` tokens are slots 0.. in order (a row without a token
    there keeps what its slot holds: a sequence between two chunks owns
    it), and a chunk row's slot is picked out of the block, and put back
    into it, by a one-hot product and a select. No gather, scatter or
    one-slot slice touches the leaf: those want the slots outside the
    tiles, the block wants them on the sublanes beside the tokens, and
    XLA re-laid all 121 MB of it twice a layer between the two (PERF.md,
    PR 37)."""

    def __init__(self, state, l, rows: _Rows, T: int):
        self.state, self.l, self.rows, self.T = state, l, rows, T
        slot = rows.token_state
        S = state.shape[1]
        self.n = T if slot is None else S       # slots the block holds
        if slot is None:
            return
        Rd = self.Rd = min(rows.decode_rows, rows.q_start.shape[0])
        self.active = (slot[:Rd] != S - 1)[:, None, None]
        q_start, q_len = rows.q_start[Rd:], rows.q_len[Rd:]
        self.last = jnp.clip(q_start + q_len - 1, 0, T - 1)
        # [Rc, S]: the slot of each chunk row that has tokens
        self.holds = (jnp.arange(S)[None] == slot[self.last][:, None]) \
            & (q_len > 0)[:, None]
        t = jnp.arange(Rd, T, dtype=jnp.int32)[:, None]
        self.own = (t >= q_start[None]) & (t < (q_start + q_len)[None])

    def fetch(self):
        """For each token, its slot's saved inputs [T, K-1, ch]."""
        self.block = lax.dynamic_slice(
            self.state, (self.l, 0, 0, 0),
            (1, self.n) + self.state.shape[2:])[0]
        if self.rows.token_state is None:
            return self.block
        # one-hot products: exact in any dtype at the highest precision
        pick = functools.partial(jnp.einsum, precision="highest")
        dtype = self.block.dtype
        per_row = pick("rs,skc->rkc", self.holds.astype(dtype), self.block)
        return jnp.concatenate([
            self.block[:self.Rd],
            pick("tr,rkc->tkc", self.own.astype(dtype), per_row)])

    def store(self, upto):
        """The leaf with each row's last inputs (``upto`` [T, K-1, ch],
        ``_conv_upto``'s) in its slot."""
        upto = upto.astype(self.state.dtype)
        block = upto
        if self.rows.token_state is not None:
            block = jnp.concatenate([
                jnp.where(self.active, upto[:self.Rd],
                          self.block[:self.Rd]), self.block[self.Rd:]])
            for r in range(self.holds.shape[0]):
                block = jnp.where(self.holds[r][:, None, None],
                                  upto[self.last[r]][None], block)
        return lax.dynamic_update_slice(self.state, block[None],
                                        (self.l, 0, 0, 0))


def _slot_rows(rows: _Rows, state, operands, scopes, update, chunk):
    """A recurrent operator's rows of a RAGGED batch, once for all such
    operators. ``state`` is the tuple of its leaves [layers, slots + 1,
    ...], ``operands`` its per-token inputs [T, ...]. The leading
    ``rows.decode_rows`` rows are one token each, token t in the slot it
    names (or naming the scratch slot, the last: the engine's packing, the
    batch slots in order), and take ``update(*state, *operands, slots,
    fresh)``, the in-place form, ``fresh`` where the position is 0. The
    rest are chunk rows and take ``chunk(*state, *operands, pos, q_start,
    q_len, row_slot)``: each starts from its slot's state (zeros where its
    first position is 0: the form's own business, by ``pos``) and leaves
    its last state there, a row without tokens in the scratch slot. In the
    decode loop (no ``rows.token_state``) every token is a one-token row,
    token t in slot t. Both return (y, *state). ``scopes`` names the
    update's, the chunk form's and the scope the two outputs are joined
    in. Returns (y [T, ...], state)."""
    pos, slot = rows.token_pos, rows.token_state
    T, scratch = pos.shape[0], state[0].shape[1] - 1
    Rd = T if slot is None else min(rows.decode_rows, rows.q_start.shape[0])
    ys = []
    if Rd:
        with jax.named_scope(scopes[0]):
            y, *state = update(
                *state, *(a[:Rd] for a in operands),
                jnp.arange(Rd, dtype=jnp.int32) if slot is None
                else slot[:Rd], pos[:Rd] == 0)
            ys.append(y)
    if T - Rd:
        with jax.named_scope(scopes[1]):
            q_start, q_len = rows.q_start[Rd:] - Rd, rows.q_len[Rd:]
            first = jnp.clip(q_start, 0, T - Rd - 1)
            y, *state = chunk(
                *state, *(a[Rd:] for a in operands), pos[Rd:], q_start,
                q_len, jnp.where(q_len > 0, slot[Rd:][first], scratch))
            ys.append(y)
    with jax.named_scope(scopes[2]):
        return jnp.concatenate(ys), state


def _mamba(lp, l, x, kv, rows: _Rows, cfg: LlamaConfig, impl):
    """The state-space operator (Mamba-2) of one layer, on entry ``l`` of
    both of its state leaves (the layer's ordinal among the mamba layers);
    H heads of P, state N, conv over ch = H P + 2 N channels:

        [g (H P), u (ch), dt_raw (H)] = rms(x) [W_gate, W_xbc, W_dt]
        c[t] = silu(b + sum_j w[j] * u[t - (K-1) + j])    depthwise, causal
        [xs (H, P), B (N), C (N)] = split(c[t])
        dt = softplus(dt_raw + dt_bias);  A = -exp(A_log)
        S_h[t] = exp(dt_h A_h) S_h[t-1] + dt_h xs_h[t] B[t]^T
        y_h[t] = S_h[t] C[t] + D_h xs_h[t]
        x' = x + residual_scale * rms(y * silu(g); gate_norm) W_out

    over a RAGGED batch. The conv takes its earlier inputs as the short
    conv does (``_conv_window``, from ``_SsmConvState``: u in the compute
    dtype, before the bias and the SiLU). The recurrence (ops/ssm.py, over
    ``SSM_LEAF``) takes the rows as ``_slot_rows`` deals them: the
    in-place update kernel for the one-token rows, the chunked scan for the
    chunk rows. dt, A, the conv and the recurrence are float32. Returns
    (x', kv)."""
    cd, f32 = cfg.dtype, jnp.float32
    H, P, N, K = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_conv
    di, T = H * P, x.shape[1]
    with jax.named_scope(SCOPE_SSM_PROJ):
        h = _rmsnorm(x, lp["mamba_norm"], cfg.norm_eps)[0]
        g, u, dt = (h @ lp[k].astype(cd)
                    for k in ("w_gate", "w_xbc", "w_dt"))
        conv = _SsmConvState(kv[SSM_CONV_LEAF], l, rows, T)
        prev = _conv_window(u, conv.fetch, rows, K)
        w = lp["w_conv"].astype(f32)                       # [K, ch]
        c = jax.nn.silu(lp["b_conv"].astype(f32) + sum(
            w[K - 1 - s] * prev[s].astype(f32) for s in range(K)))
        conv_state = conv.store(_conv_upto(prev))
        xs, B, C = jnp.split(c, [di, di + N], axis=-1)
        xs = xs.reshape(T, H, P)
        dt = jax.nn.softplus(dt.astype(f32) + lp["dt_bias"].astype(f32))
        A, D = -jnp.exp(lp["A_log"].astype(f32)), lp["D"].astype(f32)
    y, (state,) = _slot_rows(
        rows, (kv[SSM_LEAF],), (xs, dt, B, C),
        (SCOPE_SSM_UPDATE, SCOPE_SSM_SCAN, SCOPE_SSM_PROJ),
        lambda state, xs, dt, B, C, *slots: ssm.ssm_decode_update(
            state, xs, dt, A, B, C, D, *slots, layer=l, impl=impl),
        lambda state, xs, dt, B, C, *spans: ssm.ssm_chunk_scan(
            state, xs, dt, A, B, C, D, *spans, layer=l, chunk=cfg.ssm_chunk,
            impl=impl))
    with jax.named_scope(SCOPE_SSM_PROJ):
        y = y.reshape(T, di) * jax.nn.silu(g.astype(f32))
        y = _rmsnorm(y.astype(cd), lp["gate_norm"], cfg.norm_eps)
        y = y @ lp["w_out"].astype(cd)
    return _residual(x, y[None], cfg), \
        {**kv, SSM_LEAF: state, SSM_CONV_LEAF: conv_state}


def _retention(lp, l, x, kv, rows: _Rows, cfg: LlamaConfig, impl):
    """Power retention (degree 2) of one layer, on entry ``l`` of both of
    its state leaves (the layer's ordinal among the retention layers); H
    query heads and G key/value heads of d, query head j reading group
    j // (H / G):

        q_j = rope(rms(h Wq[j]; q_norm));  k_g = rope(rms(h Wk[g]; k_norm))
        v_g = h Wv[g];  a_g = log sigmoid(h Wg[g] + b_g)      (float32)
        w_j(t, s) = exp(a_g(s+1) + .. + a_g(t)) (d^-1/2 q_j(t) . k_g(s))^2
        o_j(t) = sum_{s <= t} w_j(t, s) v_g(s) / (sum_{s <= t} w_j(t, s) + eps)
        x' = x + residual_scale * concat_j(o_j) Wo

    with h = rms(x; attn_norm), served as the recurrence it equals
    (ops/retention.py: the scale goes on q and k as d^-1/4 each). The q/k
    norm and the rotary embedding are the attention layers'
    (``_project_qkv``, ``cfg.rope``), applied here, outside any kernel. Over
    a RAGGED batch as ``_slot_rows`` deals it: the in-place update kernel
    for the one-token rows, the chunk form for the chunk rows, both over
    ``RET_LEAF`` and ``RET_NORM_LEAF``. Returns (x', kv)."""
    cd, f32 = cfg.dtype, jnp.float32
    T = x.shape[1]
    pos = rows.token_pos
    with jax.named_scope(SCOPE_RET_PROJ):
        h = _rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
        q, k, v = _project_qkv(lp, h, cfg)                # [1, T, H | G, d]
        if cfg.rope:
            q = _rope(q, pos, cfg.rope_theta)
            k = _rope(k, pos, cfg.rope_theta)
        a = jax.nn.log_sigmoid(jnp.einsum(
            "td,dg->tg", h[0], lp["w_g"].astype(cd),
            preferred_element_type=f32) + lp["b_g"].astype(f32))
        root = q.shape[-1] ** -0.25
        q, k, v = q[0].astype(f32) * root, k[0].astype(f32) * root, v[0]
    o, (state, norm) = _slot_rows(
        rows, (kv[RET_LEAF], kv[RET_NORM_LEAF]), (q, k, v, a),
        (SCOPE_RET_UPDATE, SCOPE_RET_CHUNK, SCOPE_RET_PROJ),
        functools.partial(retention.retention_decode_update, layer=l,
                          impl=impl),
        functools.partial(retention.retention_chunk_scan, layer=l,
                          chunk=cfg.retention_chunk, impl=impl))
    with jax.named_scope(SCOPE_RET_PROJ):
        o = o.astype(cd).reshape(1, T, -1)
        x = _residual(x, o @ lp["wo"].astype(cd), cfg)
    return x, {**kv, RET_LEAF: state, RET_NORM_LEAF: norm}


def _delta(lp, l, x, kv, rows: _Rows, cfg: LlamaConfig, impl):
    """Gated-delta-rule linear attention of one layer, on entry ``l`` of
    both of its state leaves (the layer's ordinal among the delta layers);
    Hk key heads and Hv value heads, key head j serving value heads
    j Hv/Hk .. (j+1) Hv/Hk - 1; conv over ch = 2 Hk dk + Hv dv channels:

        [u (ch), z (Hv dv), b (Hv), a (Hv)] = h [W_qkv, W_z, W_ba]
        [q, k, v] = split(silu(sum_j w[j] * u[t - (K-1) + j]))  depthwise
        q_j <- q_j / |q_j| dk^-1/2;  k_j <- k_j / |k_j|
        beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)  (float32)
        S_h <- e^g S_h;  S_h <- S_h + k (x) beta (v_h - S_h^T k);  o_h = S_h^T q
        y_h = rms(o_h; eps) * s sigmoid(w~) * s sigmoid(z_h)   s = gate scale
        x' = x + post(concat_h(y_h) W_out)

    with h the pre-norm of x, over a RAGGED batch. The conv takes its
    earlier inputs as the state-space operator's does (``_conv_window``,
    from ``_SsmConvState`` over ``DELTA_CONV_LEAF``: u in the compute
    dtype, before the SiLU; no bias). The recurrence (ops/delta.py, over
    ``DELTA_LEAF``, float32) takes the rows as ``_slot_rows`` deals them:
    the in-place update kernel for the one-token rows, the chunk form for
    the chunk rows. The gates, the conv, the L2 norm and the output norm
    are float32. Returns (x', kv)."""
    cd, f32 = cfg.dtype, jnp.float32
    Hk, Hv = cfg.delta_key_heads, cfg.delta_value_heads
    dk, dv, K = cfg.delta_key_dim, cfg.delta_value_dim, cfg.delta_conv
    T = x.shape[1]
    with jax.named_scope(SCOPE_DELTA_PROJ):
        h = _norm(x, lp["delta_norm"], cfg)[0]
        u, z, ba = (h @ lp[k].astype(cd) for k in ("w_qkv", "w_z", "w_ba"))
        conv = _SsmConvState(kv[DELTA_CONV_LEAF], l, rows, T)
        prev = _conv_window(u, conv.fetch, rows, K)
        w = lp["w_conv"].astype(f32)                       # [K, ch]
        c = jax.nn.silu(sum(w[K - 1 - s] * prev[s].astype(f32)
                            for s in range(K)))
        conv_state = conv.store(_conv_upto(prev))
        q, k, v = jnp.split(c, [Hk * dk, 2 * Hk * dk], axis=-1)

        def unit(a):
            a = a.reshape(T, Hk, dk)
            return a * lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True)
                                 + 1e-6)

        q, k, v = unit(q) * dk ** -0.5, unit(k), v.reshape(T, Hv, dv)
        b, a = jnp.split(ba.astype(f32), 2, axis=-1)
        beta = jax.nn.sigmoid(b)
        g = -jnp.exp(lp["A_log"].astype(f32)) \
            * jax.nn.softplus(a + lp["dt_bias"].astype(f32))
    o, (state,) = _slot_rows(
        rows, (kv[DELTA_LEAF],), (q, k, v, g, beta),
        (SCOPE_DELTA_UPDATE, SCOPE_DELTA_CHUNK, SCOPE_DELTA_PROJ),
        functools.partial(delta.delta_decode_update, layer=l, impl=impl),
        functools.partial(delta.delta_chunk_scan, layer=l,
                          chunk=cfg.delta_chunk, impl=impl))
    with jax.named_scope(SCOPE_DELTA_PROJ):
        s = cfg.delta_gate_scale
        y = _rmsnorm(o, s * jax.nn.sigmoid(lp["gate_norm"].astype(f32)),
                     cfg.delta_norm_eps) \
            * (s * jax.nn.sigmoid(z.astype(f32).reshape(T, Hv, dv)))
        y = y.reshape(T, Hv * dv).astype(cd) @ lp["w_out"].astype(cd)
    return _residual(x, y[None], cfg,
                     lp["attn_post_norm"] if cfg.post_norms else None), \
        {**kv, DELTA_LEAF: state, DELTA_CONV_LEAF: conv_state}


def _latent_attention(lp, l, x, kv, rows: _Rows, cfg: LlamaConfig, impl):
    """Latent attention (MLA) of one layer in its ABSORBED form, for
    decode rows and chunk rows alike, on entry ``l`` of the latent
    pool. Per token: q = z wq, per head [q_nope, q_pe]; a = z w_kva,
    c = rms(a[:rank]), k_pe = a[rank:], ONE for all heads; adjacent-
    pair rotary on q_pe and k_pe. The row (c, k_pe) goes to the pool.
    The published form expands c with kv_b_proj into per-head k_nope
    and v; here q~_h = q_nope_h w_uk_h^T is scored against the rows
    themselves (score_h = (q~_h . c + q_pe_h . k_pe) / sqrt(nope +
    rope)), the kernel returns o~_h = sum_s p_h c[s], and o_h = o~_h
    w_uv_h: equal in exact arithmetic, and the cache is read once for
    all heads and never expanded in HBM. Variation points (GigaChat3.5 is
    the first block with them): a low-rank query (``q_lora_rank``: q =
    rms(z wq_a) wq), YaRN frequencies on the rotary part (``rope_yarn``),
    a score scale of its own (``attn_scale``: the family's mscale^2 on
    1 / sqrt(nope + rope)), and the gated block's output gate and second
    norm (``attn_gate``: o_h * sigmoid(z w_og) after w_uv;
    ``post_norms``)."""
    cd = cfg.dtype
    r, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    T, W = x.shape[1], kv["k"].shape[-1]
    token_pos, q_start, q_len = rows.token_pos, rows.q_start, rows.q_len
    rope = functools.partial(_rope_pairs, positions=token_pos,
                             theta=cfg.rope_theta, yarn=cfg.rope_yarn)
    with jax.named_scope(SCOPE_ATTENTION):
        with jax.named_scope(SCOPE_MLA_PROJ):
            h = _norm(x, lp["attn_norm"], cfg)
            hq = lp["w_uk"].shape[0]
            hq_in = _norm(h @ lp["wq_a"].astype(cd), lp["q_a_norm"], cfg) \
                if cfg.q_lora_rank else h
            q = (hq_in @ lp["wq"].astype(cd)).reshape(1, T, hq, -1)
            a = h @ lp["w_kva"].astype(cd)            # [1, T, r + rope]
            c = _norm(a[..., :r], lp["kv_norm"], cfg)
            q_pe = rope(q[..., dn:])
            k_pe = rope(a[:, :, None, r:])            # [1, T, 1, rope]
            q_lat = jnp.einsum("thn,hnr->thr", q[0, ..., :dn],
                               lp["w_uk"].astype(cd))
            # rows of the pool's width: zeros past rank + rope add
            # nothing to a score
            pad = ((0, 0), (0, 0), (0, W - r - q_pe.shape[-1]))
            qq = jnp.pad(jnp.concatenate([q_lat, q_pe[0]], axis=-1), pad)
            row = jnp.pad(jnp.concatenate([c[0][:, None], k_pe[0]],
                                          axis=-1), pad)   # [T, 1, W]
        hints = dict(max_q_len=rows.max_q_len, decode_rows=rows.decode_rows,
                     impl=impl, layer=l)
        pool, _, _, _ = write_ragged_kv(
            kv["k"], None, row, None, rows.token_page, rows.token_slot,
            q_start=q_start, q_len=q_len, **hints)
        o = ragged_paged_attention(
            qq, pool, None, rows.page_table, q_start, q_len, rows.kv_len,
            v_width=r, sm_scale=cfg.attn_scale or q.shape[-1] ** -0.5,
            **hints)
        with jax.named_scope(SCOPE_MLA_PROJ):
            o = jnp.einsum("thr,hrv->thv", o.astype(cd),
                           lp["w_uv"].astype(cd)).reshape(1, T, -1)
            if cfg.attn_gate:
                with jax.named_scope(SCOPE_GATE):
                    g = jax.nn.sigmoid(
                        (h @ lp["w_og"].astype(cd)).astype(jnp.float32))
                    o = (o * g).astype(cd)
            x = _residual(x, o @ lp["wo"].astype(cd), cfg,
                          lp["attn_post_norm"] if cfg.post_norms else None)
    return x, {**kv, "k": pool}


def _attention(lp, l, x, kv, rows: _Rows, cfg: LlamaConfig, impl):
    """The attention operator of one layer, on entry ``l`` of the page
    pool (the layer's ordinal among the attention layers): project and
    rotate the ragged tokens, write their K/V into that layer of the pool
    in place (quantizing to int8 + scales when the pool carries scale
    leaves), then ragged attention over it: each token causally sees its
    row's pages up to its own position, so a chunk's tokens see the prefix
    AND earlier tokens of the same chunk (just written). Returns
    (x', kv)."""
    if cfg.kv_lora_rank:
        return _latent_attention(lp, l, x, kv, rows, cfg, impl)
    if cfg.diff_attention:
        return _diff_attention(lp, l, x, kv, rows, cfg, impl)
    return _paged_attention(lp, l, x, kv, rows, cfg, impl)


def _window_attention(lp, l, x, kv, rows: _Rows, cfg: LlamaConfig, impl):
    """Window attention of one layer, on entry ``l`` of the SECOND page
    group (``WINDOW_LEAVES``; the layer's ordinal among the window layers):
    the attention operator on its own key/value heads and rotary base, a
    token at position t seeing t - sliding_window < s <= t of its row's
    pages, found in the row's compact table from its base on, with the
    layer's sinks (one float32 logit a query head) in the softmax's
    denominator. Returns (x', kv)."""
    with jax.named_scope(SCOPE_ATTENTION):
        if cfg.diff_attention:
            return _diff_attention(lp, l, x, kv, rows, cfg, impl,
                                   window=True)
        return _paged_attention(lp, l, x, kv, rows, cfg, impl, window=True)


def _rotate(a, pos, theta, cfg: LlamaConfig):
    """The rotary embedding of q or k [1, T, H, D]: over the whole head,
    or over its leading ``rotary_dim`` values (half-split pairs (i, i +
    rotary_dim / 2)), the rest passing unchanged."""
    if not cfg.rotary_dim or cfg.rotary_dim == a.shape[-1]:
        return _rope(a, pos, theta)
    r = cfg.rotary_dim
    return jnp.concatenate([_rope(a[..., :r], pos, theta), a[..., r:]],
                           axis=-1)


def _paged_attention(lp, l, x, kv, rows: _Rows, cfg: LlamaConfig, impl,
                     window: bool = False):
    """``_attention``'s body, for both page groups: the full group's
    leaves {"k", "v"} with ``cfg.rope_theta``, or (``window``) the second
    group's with the window kind's rotary base, its window and its sinks.
    A block with window layers names its two operators' projections in the
    trace (SCOPE_FULL_PROJ, SCOPE_WINDOW_PROJ; the window operator whole:
    SCOPE_WINDOW); every other block's program is what it was."""
    cd = cfg.dtype
    T = x.shape[1]
    token_pos, q_start, q_len = rows.token_pos, rows.q_start, rows.q_len
    kl, vl = WINDOW_LEAVES if window else ("k", "v")
    named = bool(cfg.layers_of(WINDOW))
    proj = jax.named_scope(SCOPE_WINDOW_PROJ if window else SCOPE_FULL_PROJ) \
        if named else contextlib.nullcontext()
    with jax.named_scope(SCOPE_WINDOW if window else SCOPE_ATTENTION):
        with proj:
            h = _rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
            q, k, v = _project_qkv(lp, h, cfg)        # [1, T, H, D]
            if cfg.rope and (window or cfg.full_rope):
                # (a gated block's full layers carry no position)
                theta = cfg.window_rope_theta if window else cfg.rope_theta
                q = _rotate(q, token_pos, theta, cfg)
                k = _rotate(k, token_pos, theta, cfg)
            if cfg.value_scale != 1.0:
                # on v: by linearity the same number as on the output
                v = v * jnp.asarray(cfg.value_scale, v.dtype)
        hints = dict(layer=l, max_q_len=rows.max_q_len,
                     decode_rows=rows.decode_rows, impl=impl)
        hd, vd = q.shape[-1], v.shape[-1]
        scale = dict(sm_scale=cfg.attn_scale) if cfg.attn_scale else {}
        if kv[kl].shape[-1] != hd:
            # a pool whose rows are padded to whole lanes
            # (make_kv_cache, lane_pad): zeros past head_dim add
            # nothing to a score and come back as zeros
            q, k, v = (jnp.pad(a, ((0, 0),) * 3 + (
                (0, kv[leaf].shape[-1] - a.shape[-1]),))
                for a, leaf in ((q, kl), (k, kl), (v, vl)))
            scale = scale or dict(sm_scale=hd ** -0.5)
        token_page, page_table = rows.token_page, rows.page_table
        if window:
            token_page, page_table = rows.token_page_win, rows.page_table_win
            scale.update(window=cfg.sliding_window, sink=lp.get("sink"),
                         page_base=rows.page_base_win)
        kc, vc, ksc, vsc = write_ragged_kv(
            kv[kl], kv[vl], k[0], v[0], token_page, rows.token_slot,
            kv.get("k_scale"), kv.get("v_scale"), q_start=q_start,
            q_len=q_len, **hints)
        o = ragged_paged_attention(
            q[0], kc, vc, page_table, q_start, q_len, rows.kv_len,
            k_scale=ksc, v_scale=vsc, **hints, **scale)
        with proj:
            o = o[..., :vd].reshape(1, T, -1).astype(cd)
            if cfg.attn_gate:
                # one sigmoid a value of every head's output, from the
                # layer's normed input
                with jax.named_scope(SCOPE_GATE):
                    g = jax.nn.sigmoid(
                        (h @ lp["w_og"].astype(cd)).astype(jnp.float32))
                    o = (o * g).astype(cd)
            # wo is row-parallel under tp (Megatron first collective)
            x = _residual(
                x, _maybe_psum(o @ lp["wo"].astype(cd), rows.tp_axis), cfg,
                lp["attn_post_norm"] if cfg.post_norms else None)
    kv = {**kv, kl: kc, vl: vc}
    if "k_scale" in kv:
        kv["k_scale"], kv["v_scale"] = ksc, vsc
    return x, kv


def _mamba1(lp, l, x, kv, rows: _Rows, cfg: LlamaConfig, impl):
    """The Mamba-1 operator (a selective scan) of one layer, on entry ``l``
    of both of its state leaves (the layer's ordinal among the mamba1
    layers); C = ssm1_expand * dim channels, state N, dt of rank R:

        [u | z] = ln(x) [W_x | W_z]
        xs[t] = silu(b + sum_j w[j] * u[t - (K-1) + j])   depthwise, causal
        [delta (R) | B (N) | C (N)] = xs W_xproj
        dt = softplus(delta W_dt + b_dt);  A = -exp(A_log)        [N, C]
        S[n, c] <- exp(dt[c] A[n, c]) S[n, c] + dt[c] xs[c] B[n]
        m[c] = sum_n S[n, c] C[n] + D[c] xs[c]
        x' = x + (m * silu(z)) W_out

    over a RAGGED batch. The conv takes its earlier inputs as the
    state-space operator's does (``_conv_window``, from ``_SsmConvState``
    over ``SSM1_CONV_LEAF``: u in the compute dtype, before the bias and
    the SiLU). The recurrence (ops/selective_scan.py, over ``SSM1_LEAF``,
    float32) takes the rows as ``_slot_rows`` deals them: the in-place
    update kernel for the one-token rows, the scan kernel for the chunk
    rows. dt, A, the conv and the recurrence are float32. m, in the
    compute dtype, is handed on as the MEMORY: whatever gated memory unit
    comes next reads the newest. Returns (x', kv)."""
    cd, f32 = cfg.dtype, jnp.float32
    N, R, K = cfg.ssm1_state, cfg.ssm1_dt_rank, cfg.ssm1_conv
    T = x.shape[1]
    with jax.named_scope(SCOPE_SSM1):
        with jax.named_scope(SCOPE_SSM1_PROJ):
            h = _norm(x, lp["mamba1_norm"], cfg, lp.get("mamba1_norm_b"))[0]
            u, z = h @ lp["w_x"].astype(cd), h @ lp["w_z"].astype(cd)
            conv = _SsmConvState(kv[SSM1_CONV_LEAF], l, rows, T)
            prev = _conv_window(u, conv.fetch, rows, K)
            w = lp["w_conv"].astype(f32)                   # [K, C]
            xs = jax.nn.silu(lp["b_conv"].astype(f32) + sum(
                w[K - 1 - s] * prev[s].astype(f32) for s in range(K)))
            conv_state = conv.store(_conv_upto(prev))
            delta_, B, C = jnp.split(xs.astype(cd) @ lp["w_xproj"].astype(cd),
                                     [R, R + N], axis=-1)
            dt = jax.nn.softplus(
                (delta_ @ lp["w_dt"].astype(cd)).astype(f32)
                + lp["b_dt"].astype(f32))
            A, D = -jnp.exp(lp["A_log"].astype(f32)), lp["D"].astype(f32)
        m, (state,) = _slot_rows(
            rows, (kv[SSM1_LEAF],), (xs, dt, B, C),
            (SCOPE_SSM1_UPDATE, SCOPE_SSM1_SCAN, SCOPE_SSM1_PROJ),
            lambda state, xs, dt, B, C, *slots:
            selective_scan.selective_decode_update(
                state, xs, dt, A, B, C, D, *slots, layer=l, impl=impl),
            lambda state, xs, dt, B, C, *spans:
            selective_scan.selective_chunk_scan(
                state, xs, dt, A, B, C, D, *spans, layer=l, impl=impl))
        with jax.named_scope(SCOPE_SSM1_PROJ):
            y = (m * jax.nn.silu(z.astype(f32))).astype(cd) \
                @ lp["w_out"].astype(cd)
    return _residual(x, y[None], cfg), \
        {**kv, SSM1_LEAF: state, SSM1_CONV_LEAF: conv_state,
         MEMORY: m.astype(cd)}


def _gmu(lp, l, x, kv, rows: _Rows, cfg: LlamaConfig, impl=None):
    """A gated memory unit: x' = x + (m * silu(ln(x) W_in)) W_out, m the
    MEMORY the newest Mamba-1 layer before it left, at the same token.
    Keeps nothing, so its ordinal ``l`` indexes nothing; no kernel, so
    ``impl`` chooses nothing. Returns (x', kv)."""
    cd, f32 = cfg.dtype, jnp.float32
    with jax.named_scope(SCOPE_GMU):
        h = _norm(x, lp["gmu_norm"], cfg, lp.get("gmu_norm_b"))
        g = jax.nn.silu((h @ lp["w_in"].astype(cd)).astype(f32))
        y = (kv[MEMORY].astype(f32)[None] * g).astype(cd) \
            @ lp["w_out"].astype(cd)
    return _residual(x, y, cfg), kv


def _biased(lp, h, name: str, cfg: LlamaConfig):
    """h W_name (+ b_name where the block has biases), [T, out]."""
    y = h @ lp["w" + name].astype(cfg.dtype)
    return y + lp["b" + name].astype(cfg.dtype) if cfg.attn_bias else y


def _pair_queries(q, width: int):
    """The queries of a differential pair as the paged kernels take them:
    q [T, H, d], heads (2j, 2j+1) pair j's q1 and q2, to [T, H, width]:
    head 2j as [q1 | 0], head 2j+1 as [0 | q2] (then zeros up to a padded
    pool's ``width``). Against a key row [k1 | k2] the zeros add exactly 0
    to a score, so head 2j's softmax is q1 k1's and head 2j+1's q2 k2's,
    each over the joined value row [v1 | v2]."""
    T, H, d = q.shape
    eye = jnp.eye(2, dtype=q.dtype)[None, None, :, :, None]
    q = (q.reshape(T, H // 2, 2, 1, d) * eye).reshape(T, H, 2 * d)
    return jnp.pad(q, ((0, 0), (0, 0), (0, width - 2 * d)))


def _before(cfg: LlamaConfig, kind: str, of: str) -> np.ndarray:
    """For each layer of ``kind``, in order: the ordinal among the ``of``
    layers of the newest one before it (a static table a layer indexes
    with its own ordinal)."""
    return np.asarray([sum(j < i for j in cfg.layers_of(of)) - 1
                       for i in cfg.layers_of(kind)], np.int32)


def _diff_combine(o, lp, l, kind: str, cfg: LlamaConfig):
    """The differential combine of one layer: o [T, H, 2 dv] the kernel's
    output, heads (2j, 2j+1) pair j's two softmaxes over the pair's joined
    values; lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init,
    lambda_init = 0.8 - 0.6 exp(-0.3 i) from the layer's index i IN THE
    MODEL (a static table, read at the layer's ordinal ``l`` among the
    layers of its ``kind``); rms(a1 - lambda a2) over the pair's 2 dv
    values times (1 - lambda_init). float32. Returns [T, H dv]."""
    f32 = jnp.float32
    T, H, w = o.shape
    with jax.named_scope(SCOPE_DIFF):
        init = jnp.asarray(np.asarray(
            [0.8 - 0.6 * math.exp(-0.3 * i) for i in cfg.layers_of(kind)],
            np.float32))[l]
        lam = jnp.exp(jnp.sum(lp["lambda_q1"].astype(f32)
                              * lp["lambda_k1"].astype(f32))) \
            - jnp.exp(jnp.sum(lp["lambda_q2"].astype(f32)
                              * lp["lambda_k2"].astype(f32))) + init
        a = o.astype(f32).reshape(T, H // 2, 2, w)
        d = _rmsnorm(a[:, :, 0] - lam * a[:, :, 1], lp["subln"].astype(f32),
                     cfg.norm_eps) * (1.0 - init)
        return d.astype(cfg.dtype).reshape(T, -1)


def _diff_attention(lp, l, x, kv, rows: _Rows, cfg: LlamaConfig, impl,
                    window: bool = False):
    """DIFFERENTIAL attention of one layer over the full page group or
    (``window``) the second one, on entry ``l`` of it; heads dh wide, query
    heads (2j, 2j+1) pair j's q1 and q2, key/value heads likewise, query
    pair j on key/value pair j // (query pairs a key/value pair):

        [q | k | v] = ln(x) [Wq | Wk | Wv] + b
        a_i = softmax(q_i k_i^T / sqrt(dh) + mask) [v1 | v2]       i = 1, 2
        x' = x + concat_j(rms(a1 - lambda a2) (1 - lambda_init)) Wo + bo

    no positional embedding. The pool holds a key/value PAIR as one head
    [k1 | k2], [v1 | v2] (llm/cache.py: page_heads) and the kernels take
    the queries as ``_pair_queries`` lays them: the published four products
    a pair through the paged kernels as they are, at 128 lanes where the
    published head is 64. The combine is ``_diff_combine``'s, after the
    kernel. Returns (x', kv)."""
    cd = cfg.dtype
    T = x.shape[1]
    kl, vl = WINDOW_LEAVES if window else ("k", "v")
    kind = WINDOW if window else ATTENTION
    proj = jax.named_scope(SCOPE_WINDOW_PROJ if window else SCOPE_FULL_PROJ)
    with jax.named_scope(SCOPE_WINDOW if window else SCOPE_ATTENTION):
        with proj:
            h = _norm(x, lp["attn_norm"], cfg, lp.get("attn_norm_b"))[0]
            q, k, v = (_biased(lp, h, name, cfg) for name in "qkv")
            dh, wk, wv = cfg.qk_head_dim, kv[kl].shape[-1], kv[vl].shape[-1]
            q = _pair_queries(q.reshape(T, -1, dh), wk)
            # a pair of adjacent heads is one row of the pool, as they lie
            k = k.reshape(T, -1, 2 * dh)
            v = v.reshape(T, -1, 2 * cfg.v_dim)
            k, v = (jnp.pad(a, ((0, 0), (0, 0), (0, width - a.shape[-1])))
                    for a, width in ((k, wk), (v, wv)))
        hints = dict(layer=l, max_q_len=rows.max_q_len,
                     decode_rows=rows.decode_rows, impl=impl)
        token_page, page_table, seen = rows.token_page, rows.page_table, {}
        if window:
            token_page, page_table = rows.token_page_win, rows.page_table_win
            seen = dict(window=cfg.sliding_window,
                        page_base=rows.page_base_win)
        kc, vc, _, _ = write_ragged_kv(
            kv[kl], kv[vl], k, v, token_page, rows.token_slot,
            q_start=rows.q_start, q_len=rows.q_len, **hints)
        o = ragged_paged_attention(
            q, kc, vc, page_table, rows.q_start, rows.q_len, rows.kv_len,
            sm_scale=dh ** -0.5, **hints, **seen)
        with proj:
            o = _diff_combine(o[..., :2 * cfg.v_dim], lp, l, kind, cfg)
            x = _residual(x, _biased(lp, o, "o", cfg)[None], cfg)
    return x, {**kv, kl: kc, vl: vc}


def _cross_attention(lp, l, x, kv, rows: _Rows, cfg: LlamaConfig, impl):
    """Differential CROSS attention of one layer: its own queries (and
    lambdas, norm weight and lambda_init) over the pages the NEWEST full-
    attention layer before it wrote, of the same sequence, all positions up
    to the token's own. It projects no key and no value, writes no page and
    keeps nothing: ``l`` is its ordinal among the cross layers, and the
    entry of the pool it reads is ``_before``'s. Returns (x', kv)."""
    T = x.shape[1]
    source = jnp.asarray(_before(cfg, CROSS, ATTENTION))[l]
    with jax.named_scope(SCOPE_ATTENTION), jax.named_scope(SCOPE_CROSS):
        with jax.named_scope(SCOPE_CROSS_PROJ):
            h = _norm(x, lp["attn_norm"], cfg, lp.get("attn_norm_b"))[0]
            q = _pair_queries(
                _biased(lp, h, "q", cfg).reshape(T, -1, cfg.qk_head_dim),
                kv["k"].shape[-1])
        with jax.named_scope(SCOPE_SHARED_KV):
            o = ragged_paged_attention(
                q, kv["k"], kv["v"], rows.page_table, rows.q_start,
                rows.q_len, rows.kv_len, sm_scale=cfg.qk_head_dim ** -0.5,
                layer=source, max_q_len=rows.max_q_len,
                decode_rows=rows.decode_rows, impl=impl)
        with jax.named_scope(SCOPE_CROSS_PROJ):
            o = _diff_combine(o[..., :2 * cfg.v_dim], lp, l, CROSS, cfg)
            x = _residual(x, _biased(lp, o, "o", cfg)[None], cfg)
    return x, kv


#: THE table of layer operators: kind -> (its stack in params["layers"],
#: its body ``(lp, l, x, kv, rows, cfg, impl) -> (x, kv)``: the layer's
#: weights, its ordinal among the layers of its kind, the stream, the
#: pool, the ragged batch, and the kernel-or-reference choice). What a
#: kind keeps in the pool beside pages is llm/cache.py's ``SLOT_STATE``,
#: by the same key.
OPERATORS = {ATTENTION: ("attn", _attention), CONV: ("conv", _short_conv),
             MAMBA: ("mamba", _mamba), RETENTION: ("retention", _retention),
             WINDOW: ("attn_window", _window_attention),
             DELTA: ("delta", _delta), MAMBA1: ("mamba1", _mamba1),
             GMU: ("gmu", _gmu), CROSS: ("attn_cross", _cross_attention)}


#: what a scan of its own costs, counted in layer bodies traced: a run of
#: layers becomes a segment of the walk where that saves more bodies than
#: this. At 8 a cut model's one period that is run once stays one scan
#: (Trinity-Mini's four layers, MiMo-V2-Flash's six), and a depth that is
#: not one period (a decoder-hybrid-decoder's 32 layers) is three
_SEGMENT_COST = 8


def _kinds(cfg: LlamaConfig):
    """(how many leading dense layers run before the scans, every layer as
    (operator kind, feed-forward kind))."""
    L = cfg.n_layers
    ops = cfg.layer_types or (ATTENTION,) * L
    lead = cfg.n_dense_layers if cfg.n_experts else 0
    return lead, [(ops[i], "moe" if cfg.n_experts and i >= lead else "dense")
                  for i in range(L)]


def tail_start(cfg: LlamaConfig) -> int:
    """The first layer of the walk's TAIL: the longest suffix of the layers
    (behind the leading ones) whose operator keeps nothing (llm/cache.py:
    ``keeps_nothing``: no page, no leaf of ``SLOT_STATE``) and whose
    feed-forward is dense (row-wise, and counts nothing). What a tail
    layer computes for a token, only that token's later layers and the
    head read, and the head reads a row's LAST token: every other token of
    a row may leave the walk here (``_layers``). ``cfg.n_layers`` where
    the tail is empty: every block but a decoder-hybrid-decoder, whose
    cross-decoder it is."""
    lead, kinds = _kinds(cfg)
    at = len(kinds)
    while at > lead and keeps_nothing(kinds[at - 1][0]) \
            and kinds[at - 1][1] == "dense":
        at -= 1
    return at


def _cover(rest):
    """``rest`` (layers, each (operator kind, feed-forward kind)) as
    SEGMENTS, [one period, how many periods, ...]: each whole repeats of
    its period and a scan of its own, so that the layer bodies traced, and
    ``_SEGMENT_COST`` a segment, come to the least (fewest segments, then
    the shortest first period, where two covers cost the same)."""
    n = len(rest)
    # best[i]: (cost, segments, their (period, repeats)) of rest[i:]
    best = {n: (0, 0, ())}
    for i in range(n - 1, -1, -1):
        covers = []
        for p in range(1, n - i + 1):
            r = 1
            while True:
                cost, segs, tail = best[i + p * r]
                covers.append((p + _SEGMENT_COST + cost, segs + 1, p,
                               ((p, r),) + tail))
                if rest[i + p * r:i + p * (r + 1)] != rest[i:i + p]:
                    break
                r += 1
        cost, segs, _, cover = min(covers)
        best[i] = (cost, segs, cover)
    out, i = [], 0
    for p, r in best[0][2]:
        out += [rest[i:i + p], r]
        i += p * r
    return out


def _pattern(cfg: LlamaConfig):
    """(leading layers, then for each SEGMENT of the rest: one period, how
    many periods): each layer is (operator kind, feed-forward kind). The
    leading dense layers run before the scans; the rest is covered by
    segments (``_cover``), the layers before the tail and the tail
    (``tail_start``) each on their own, so that a segment STARTS at the
    tail. A depth that is one period repeated is one segment, of its
    shortest period."""
    lead, kinds = _kinds(cfg)
    cut = tail_start(cfg)
    return (kinds[:lead], *_cover(kinds[lead:cut]), *_cover(kinds[cut:]))


def _stacks(layers, cfg: LlamaConfig):
    """``params["layers"]`` as one stack of leaves per kind. The tree of a
    block whose layers differ is that already (models/llama.py:
    _init_hybrid_params). The Llama tree (init_params: every layer
    attention and ONE kind of feed-forward; training, the references and
    llm/tp.py's specs share it) holds every layer's leaves flat, and is
    split by name, the same arrays: no copy."""
    if "mlp_norm" not in layers:
        return layers
    return {"attn": {k: w for k, w in layers.items()
                     if k not in _FFN_LEAVES},
            "moe" if cfg.n_experts else "dense":
                {k: w for k, w in layers.items() if k in _FFN_LEAVES}}


def _last_tokens(rows: _Rows, T: int):
    """[R]: each row's last token among the step's ``T`` (an empty row's:
    some token, which nothing reads)."""
    return jnp.clip(rows.q_start + rows.q_len - 1, 0, T - 1)


def leaves_early(cfg: LlamaConfig, max_q_len: Optional[int]) -> bool:
    """Whether a step whose rows hold up to ``max_q_len`` tokens carries
    one token a row through the walk's tail: there is a tail, and a row
    may hold more than one token (in the decode loop every token IS its
    row's last)."""
    return tail_start(cfg) < cfg.n_layers and max_q_len != 1


def _layers(layers, x, kv, rows: _Rows, valid, cfg: LlamaConfig, impl,
            plane=None):
    """THE walk over the layers, of every block: the weights one stack per
    kind (``_stacks``), a layer finding its own by its ordinal among the
    layers of its kind: its operator's entry of the page pool or of its
    state leaves, the expert layers' [layer, expert] weights, which stay
    closed over and whole. Depth does not unroll: the leading layers run
    once, then ONE scan a segment over the periods of its pattern
    (``_pattern``), its body one period; the Llama block is one segment of
    period 1, and a kind's ordinals go on counting from scan to scan. The
    pool is a carry, whole, updated in place at the ordinals; the weights
    are closed over and indexed by the scan's counter.

    A token leaves the walk where nothing more is kept of it: at the tail
    (``tail_start``; ``leaves_early``) the stream and the memory go on at
    each row's LAST token only, as R one-token rows over the pages and
    lengths the step has (a chunk row's last token sees its whole chunk:
    the layers before wrote it into the carried pool), so the tail's
    attention takes the kernel's one-token tile for every row and its
    products have R rows where they had T.

    ``plane`` (a looped stack's pass, ``_passes``): the first page plane of
    this pass. An attention layer then finds its weights at its ordinal, as
    ever, and its pages at ``plane`` + ordinal: the operator is handed the
    plane where it was handed the ordinal, and the kernels under it index
    the pool's leading axis without a care for what it counts.

    Returns (x, kv, counters summed over the expert layers, or None); x is
    [1, T, d], or [1, R, d] (a row's last token) where the walk was cut."""
    lead, *segments = _pattern(cfg)
    layers = _stacks(layers, cfg)
    if cfg.layers_of(MAMBA1):
        # what a Mamba-1 layer hands the gated memory units after it
        kv = {**kv, MEMORY: jnp.zeros((x.shape[1], cfg.ssm1_channels),
                                      cfg.dtype)}
    experts = {k: layers["moe"][k] for k in _EXPERT_LEAVES} \
        if cfg.n_experts else None

    def at(kind, i):
        """Layer i's leaves of one kind's stack (not the experts')."""
        return {k: w[i] for k, w in layers[kind].items()
                if not (kind == "moe" and k in _EXPERT_LEAVES)}

    def one(x, kv, counters, rows, kinds, ordinal):
        op, ffn = kinds
        stack, body = OPERATORS[op]
        entry = ordinal[op]       # of the operator's pages or slot state
        if plane is not None and op == ATTENTION:
            entry = plane + entry
        x, kv = body(at(stack, ordinal[op]), entry, x, kv, rows, cfg, impl)
        if ffn == "moe":
            x, c = _moe_mlp(at("moe", ordinal[ffn]), experts, ordinal[ffn],
                            x, valid, cfg, impl)
            counters = counters + c
        else:
            x = _mlp(at("dense", ordinal[ffn]), x, cfg, rows.tp_axis)
        return x, kv, counters

    def run(carry, rows, some, first, j=0, per=None):
        """``some`` layers in turn; a layer's ordinal among its kind is
        ``first`` + those before it here (+ j whole periods of ``per``)."""
        x, kv, counters = carry
        first = dict(first)
        for kinds in some:
            ordinal = {k: first[k] + (j * per[k] if per else 0)
                       for k in kinds}
            x, kv, counters = one(x, kv, counters, rows, kinds, ordinal)
            for k in kinds:
                first[k] += 1
        return (x, kv, counters), first

    def leave(carry, rows):
        """(carry, rows) at each row's last token: R rows of one token."""
        x, kv, counters = carry
        R = rows.q_start.shape[0]
        last = _last_tokens(rows, x.shape[1])
        if MEMORY in kv:
            kv = {**kv, MEMORY: kv[MEMORY][last]}
        return (x[:, last], kv, counters), _Rows(
            rows.token_pos[last], None, jnp.arange(R, dtype=jnp.int32),
            jnp.minimum(rows.q_len, 1), decode_rows=R,
            page_table=rows.page_table, kv_len=rows.kv_len, max_q_len=1,
            tp_axis=rows.tp_axis)

    carry = (x, kv, jnp.zeros(
        len(moe.COUNTERS) + bool(cfg.experts_held), jnp.int32))
    carry, seen = run(carry, rows, lead, dict.fromkeys(
        (*OPERATORS, "dense", "moe"), 0))
    done = len(lead)
    cut = tail_start(cfg) if leaves_early(cfg, rows.max_q_len) else None
    for period, n_periods in zip(segments[::2], segments[1::2]):
        if done == cut:
            carry, rows = leave(carry, rows)
        per = collections.Counter(k for kinds in period for k in kinds)
        carry, _ = lax.scan(
            lambda carry, j, rows=rows, period=period, seen=seen, per=per:
            (run(carry, rows, period, seen, j, per)[0], None),
            carry, jnp.arange(n_periods, dtype=jnp.int32))
        seen = {k: at + n_periods * per[k] for k, at in seen.items()}
        done += len(period) * n_periods
    x, kv, counters = carry
    kv = {k: leaf for k, leaf in kv.items() if k != MEMORY}
    return x, kv, counters if cfg.n_experts else None


def _exit_gate(params: Params, h, cfg: LlamaConfig):
    """A looped stack's exit gate on normed rows h [.., d]: sigmoid(h . w_e
    + b_e), float32, ONE linear map for every pass."""
    f32 = jnp.float32
    return jax.nn.sigmoid(h.astype(f32) @ params["exit_w"].astype(f32)
                          + params["exit_b"].astype(f32)[0])


def _exit_counts(gates, valid):
    """[passes] int32: the ``valid`` rows [R] by the pass at which the exit
    distribution's cumulative mass first reaches EXIT_MASS. With gates
    lambda [passes, R], a row leaves at pass u with probability lambda_u
    prod_{j<u} (1 - lambda_j), at the last pass with what is left: the
    mass by pass u is 1 - prod_{j<=u} (1 - lambda_j), and 1 at the last."""
    stays = jnp.cumprod(1.0 - gates, axis=0)[:-1]           # [passes-1, R]
    left_at = jnp.sum(stays > 1.0 - EXIT_MASS, axis=0)      # 0-based pass
    return jnp.sum((left_at[None] == jnp.arange(gates.shape[0])[:, None])
                   & valid[None], axis=1).astype(jnp.int32)


def _passes(params: Params, x, kv, rows: _Rows, cfg: LlamaConfig, impl):
    """A looped stack's forward: ``cfg.ut_steps`` passes of the walk
    (``_layers``) over the same weights, pass u on page planes u * layers
    .. (u + 1) * layers - 1, ONE scan over the passes around the walk's own
    scans; after EVERY pass the final norm, whose output the next pass
    takes, and the exit gate at each row's last token. Returns (the last
    pass's normed stream [1, T, d], kv, the gates [passes, R] float32)."""
    per_pass = len(cfg.layers_of(ATTENTION))      # planes a pass
    last = _last_tokens(rows, x.shape[1])

    def one(carry, u):
        x, kv = carry
        x, kv, _ = _layers(params["layers"], x, kv, rows, None, cfg, impl,
                           plane=u * per_pass)
        with jax.named_scope(SCOPE_UT_EXIT):
            x = _norm(x, params["final_norm"], cfg)
            gate = _exit_gate(params, x[0][last], cfg)
        return (x, kv), gate

    (x, kv), gates = lax.scan(
        one, (x, kv), jnp.arange(cfg.ut_steps, dtype=jnp.int32))
    return x, kv, gates


def _ragged_logits(params: Params, tokens: jax.Array,
                   token_pos: jax.Array, token_page: jax.Array,
                   token_slot: jax.Array, page_table: jax.Array,
                   q_start: jax.Array, q_len: jax.Array,
                   kv_len: jax.Array, kv: KVCache, cfg: LlamaConfig,
                   tp_axis: Optional[str] = None,
                   paged_impl: Optional[str] = None,
                   max_q_len: Optional[int] = None,
                   decode_rows: int = 0,
                   token_state: Optional[jax.Array] = None,
                   token_page_win: Optional[jax.Array] = None,
                   page_table_win: Optional[jax.Array] = None,
                   page_base_win: Optional[jax.Array] = None):
    """ONE forward over a ragged mixed prefill+decode batch.

    tokens/token_pos: [T] the ragged token ids and absolute positions;
    token_page/token_slot: [T] each token's destination in the page pool
    (padding tokens -> the scratch page); page_table [R, max_pages] +
    q_start/q_len/kv_len [R]: the per-row ragged descriptors
    (ops.paged_attention). kv: the pool dict — DONATED by every caller
    (an undonated pool copies multi-GB per step). token_state [T], where
    some layer keeps state per batch slot: each token's STATE slot (its
    sequence's batch slot; the scratch slot, max_batch, for padding).
    None there means the decode loop's layout: token t is slot t's one
    token. token_page_win [T], page_table_win [R, a window row's pages],
    page_base_win [R], where some layer is a window layer: each token's
    page in the SECOND page group, the rows' compact tables there and the
    logical page each starts at (llm/cache.py).

    Returns (logits [R, vocab] float32, kv, counters): per row, the logits
    at its LAST valid token (``_ragged_forward`` takes their argmax).
    ``counters`` is None for a dense configuration; with experts it is
    the step's routing counters (ops.moe.COUNTERS, summed over layers,
    valid tokens only: a padding token is one whose page is the scratch
    page); of a looped stack, the valid rows by the pass at which they
    would have left (``_exit_counts``; a row is valid where it holds a
    token whose page is not the scratch page).

    The layers are ``_layers``'s: one walk for every block (a looped
    stack's several times: ``_passes``).
    """
    T = tokens.shape[0]
    cd = cfg.dtype
    x = params["embed"].astype(cd)[tokens][None]          # [1, T, d]
    if cfg.embed_scale != 1.0:
        x = x * jnp.asarray(cfg.embed_scale, cd)
    # a padding token is one whose page is the scratch page
    valid = token_page != SCRATCH_PAGE if cfg.n_experts else None
    rows = _Rows(token_pos, token_state, q_start, q_len, decode_rows,
                 token_page, token_slot, page_table, kv_len, max_q_len,
                 tp_axis, token_page_win, page_table_win, page_base_win)
    if cfg.ut_steps > 1:
        x, kv, gates = _passes(params, x, kv, rows, cfg, paged_impl)
        counters = _exit_counts(gates, (q_len > 0) & (
            token_page[_last_tokens(rows, T)] != SCRATCH_PAGE))
    else:
        x, kv, counters = _layers(params["layers"], x, kv, rows, valid, cfg,
                                  paged_impl)
        x = _norm(x, params["final_norm"], cfg, params.get("final_norm_b"))
    if leaves_early(cfg, max_q_len):
        xl = x[0]                     # [R, d] already: the walk was cut
    else:
        last = _last_tokens(rows, T)                      # [R]
        xl = x[0][last]
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    with _head_scope(cfg):
        logits = jnp.einsum("rd,vd->rv", xl.astype(cd), head.astype(cd),
                            preferred_element_type=jnp.float32)
    if cfg.logits_divisor != 1.0:
        logits = logits / cfg.logits_divisor
    return logits, kv, counters


def _head_scope(cfg: LlamaConfig):
    """SCOPE_HEAD for a gated block's head and greedy choice; no scope for
    any other block's."""
    return jax.named_scope(SCOPE_HEAD) if cfg.gated_block \
        else contextlib.nullcontext()


def _ragged_forward(params, tokens, token_pos, token_page, token_slot,
                    page_table, q_start, q_len, kv_len, kv,
                    cfg: LlamaConfig, *hints, **kwargs):
    """``_ragged_logits`` with the argmax fused in-program, so the whole
    mixed step is ONE dispatch + ONE readback: (next_tok [R], kv,
    counters), per row the next decode token for q_len == 1 rows, the
    first sampled token for a prefill chunk that just finished its
    prompt."""
    logits, kv, counters = _ragged_logits(
        params, tokens, token_pos, token_page, token_slot, page_table,
        q_start, q_len, kv_len, kv, cfg, *hints, **kwargs)
    with _head_scope(cfg):
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return nxt, kv, counters


def _newest_from(tokens: jax.Array, last: Optional[jax.Array], n: int):
    """``tokens`` with each of its first ``n`` entries that is NEGATIVE
    replaced by that batch slot's entry of ``last``, the [n] newest tokens
    the program before this one returned: a decode row whose token the
    host had not read when it packed this program (llm/engine.py runs one
    program ahead) takes it on the device. ``last`` None: as they are."""
    if last is None:
        return tokens
    head = tokens[:n]
    return tokens.at[:n].set(jnp.where(head < 0, last, head))


def _ragged_step_body(params: Params, tokens: jax.Array,
                      token_pos: jax.Array, token_page: jax.Array,
                      token_slot: jax.Array, page_table: jax.Array,
                      q_start: jax.Array, q_len: jax.Array,
                      kv_len: jax.Array, kv: KVCache, cfg: LlamaConfig,
                      tp_axis: Optional[str] = None,
                      paged_impl: Optional[str] = None,
                      max_q_len: Optional[int] = None,
                      decode_rows: int = 0,
                      token_state: Optional[jax.Array] = None,
                      newest_slot: Optional[jax.Array] = None,
                      last: Optional[jax.Array] = None,
                      **window_pages) -> Tuple[jax.Array, KVCache, jax.Array]:
    """The mixed step's program: ``_ragged_forward``, with a step's
    counters (if its configuration has any) appended to the tokens, so
    both ride the one device->host transfer: (out [R (+ n counters)], kv,
    newest [decode_rows]).
    ``window_pages``: the second page group's fields of the descriptor
    (``_ragged_logits``'s token_page_win, page_table_win, page_base_win).

    ``last`` [decode_rows] and ``newest``: every batch slot's newest
    token, as the program before this one left it and as this one does.
    A decode row whose ``tokens`` entry is negative reads its slot's entry
    of ``last`` (``_newest_from``); ``newest`` is ``last`` with each row's
    sampled token written to the slot ``newest_slot`` [R] names for that
    row: a decode row's own slot, the sequence's slot for the chunk row
    that ends a prompt, ``decode_rows`` (past the end: dropped) for every
    other row. Without ``newest_slot`` the decode rows (q_len 1) write
    theirs and no chunk row does; without ``last`` no token is negative
    and ``newest`` starts from zeros."""
    B = decode_rows
    nxt, kv, counters = _ragged_forward(
        params, _newest_from(tokens, last, B), token_pos, token_page,
        token_slot, page_table, q_start, q_len, kv_len, kv, cfg, tp_axis,
        paged_impl, max_q_len, decode_rows, token_state, **window_pages)
    if newest_slot is None:
        rows = jnp.arange(q_len.shape[0], dtype=jnp.int32)
        newest_slot = jnp.where((rows < B) & (q_len == 1), rows, B)
    newest = (jnp.zeros(B, jnp.int32) if last is None else last) \
        .at[newest_slot].set(nxt, mode="drop")
    if counters is not None:
        nxt = jnp.concatenate([nxt, counters])
    return nxt, kv, newest


def _ragged_decode_loop(params: Params, tokens: jax.Array,
                        positions: jax.Array, kv: KVCache,
                        page_table: jax.Array, seq_lens: jax.Array,
                        num_steps: int, cfg: LlamaConfig,
                        tp_axis: Optional[str] = None,
                        paged_impl: Optional[str] = None,
                        page_table_win: Optional[jax.Array] = None,
                        page_base_win: Optional[jax.Array] = None,
                        last: Optional[jax.Array] = None):
    """``num_steps`` greedy decode steps in ONE device program.

    The pure-decode fast path: every batch slot is one ragged decode row
    (q_start = slot index, q_len = 1), so this is the ragged step
    degenerated to T == R == max_batch, scanned num_steps times with
    on-device sampling and a single [num_steps, B] readback (each
    host<->device round-trip costs real latency, so K steps ride one
    trip — vLLM multi-step scheduling). Sequences that hit EOS mid-block
    keep decoding garbage
    into their OWN pages; the host truncates on readback.

    Returns (tokens_out [num_steps, B], kv, final_positions,
    final_seq_lens, newest [B]) — positions/seq_lens advance by num_steps
    so the next block chains without host recomputation; ``newest`` is
    the last step's tokens, every slot's newest, for the NEXT program's
    ``last``: a slot whose ``tokens`` entry is negative starts from its
    entry of this program's ``last`` (``_newest_from``). With counters
    (``step_counters``: experts, a looped stack), tokens_out is flat
    [num_steps * B + n counters]: the tokens, then the dispatch's counters
    summed over its steps (one transfer, as above).

    page_table_win [B, a window row's pages over the block] and
    page_base_win [B], where some layer is a window layer: the slots'
    compact tables in the second page group, which cover the window of the
    block's first token up to its last token's page, and the logical page
    each starts at; both stay as they are over the block's steps.
    """
    R = tokens.shape[0]
    ps = kv["k"].shape[3]
    max_pages = page_table.shape[1]
    ar = jnp.arange(R, dtype=jnp.int32)
    ones = jnp.ones(R, jnp.int32)

    def one(carry, _):
        tok, pos, kv, lens = carry
        page_idx = jnp.clip(pos // ps, 0, max_pages - 1)
        token_page = page_table[ar, page_idx]
        token_slot = pos % ps
        window = {}
        if page_table_win is not None:
            at = jnp.clip(pos // ps - page_base_win, 0,
                          page_table_win.shape[1] - 1)
            window = dict(token_page_win=page_table_win[ar, at],
                          page_table_win=page_table_win,
                          page_base_win=page_base_win)
        nxt, kv, counters = _ragged_forward(
            params, tok, pos, token_page, token_slot, page_table,
            ar, ones, lens, kv, cfg, tp_axis, paged_impl,
            max_q_len=1, decode_rows=R, **window)
        out = nxt if counters is None else (nxt, counters)
        return (nxt, pos + 1, kv, lens + 1), out

    (newest, positions, kv, seq_lens), toks_out = lax.scan(
        one, (_newest_from(tokens, last, R), positions, kv, seq_lens), None,
        length=num_steps)
    if step_counters(cfg):
        toks, counters = toks_out
        toks_out = jnp.concatenate([toks.reshape(-1), counters.sum(axis=0)])
    return toks_out, kv, positions, seq_lens, newest


#: a descriptor's layout: its fields in order, each (name, shape); the
#: names are the bodies' argument names
Layout = Tuple[Tuple[str, Tuple[int, ...]], ...]
#: the mixed step's fields with an entry a ROW; the others have one a token
ROW_FIELDS = ("q_start", "q_len", "kv_len", "newest_slot", "page_table",
              "page_base_win", "page_table_win")


def step_layout(decode_rows: int, chunk_rows: int, max_q_len: int,
                max_pages: int, has_state: bool,
                window_pages: int = 0) -> Layout:
    """The mixed step's descriptor in its shape of ``chunk_rows`` chunk
    rows: what ``_ragged_step_body`` takes per token (``token_state``
    only where the block has state per batch slot), per row, and the rows'
    page table. ``window_pages`` (a block with window layers: the entries
    of a row's compact table in the second page group): each token's page
    there, the rows' tables and the logical page each starts at; the
    table's width does not grow with the context."""
    R = decode_rows + chunk_rows
    T = decode_rows + chunk_rows * max_q_len
    per_token = ("tokens", "token_pos", "token_page", "token_slot") \
        + (("token_state",) if has_state else ()) \
        + (("token_page_win",) if window_pages else ())
    per_row = ("q_start", "q_len", "kv_len", "newest_slot") \
        + (("page_base_win",) if window_pages else ())
    return (*((name, (T,)) for name in per_token),
            *((name, (R,)) for name in per_row),
            ("page_table", (R, max_pages)),
            *((("page_table_win", (R, window_pages)),)
              if window_pages else ()))


def decode_layout(decode_rows: int, max_pages: int,
                  window_pages: int = 0) -> Layout:
    """The decode loop's descriptor: what ``_ragged_decode_loop`` takes a
    batch slot, and the slots' page table (with window layers: their
    compact tables in the second group and the page each starts at)."""
    per_slot = ("tokens", "positions", "seq_lens") \
        + (("page_base_win",) if window_pages else ())
    return (*((name, (decode_rows,)) for name in per_slot),
            ("page_table", (decode_rows, max_pages)),
            *((("page_table_win", (decode_rows, window_pages)),)
              if window_pages else ()))


def layout_size(layout: Layout) -> int:
    return sum(math.prod(shape) for _, shape in layout)


def layout_of(desc, layouts: Tuple[Layout, ...]) -> Layout:
    """The one of ``layouts`` (of different lengths) that ``desc`` is in:
    the one of its length."""
    layout, = (lay for lay in layouts if layout_size(lay) == desc.shape[0])
    return layout


def cut(desc, layout: Layout) -> dict:
    """{field: array} of ``layout`` out of the flat ``desc``, at static
    offsets: views of a numpy array (the host's side), static slices of a
    traced one (the program's)."""
    fields, at = {}, 0
    for name, shape in layout:
        n = math.prod(shape)
        fields[name] = desc[at:at + n].reshape(shape)
        at += n
    return fields


def _on_descriptor(body):
    """``body`` as a program of (params, desc, kv, last): the descriptor
    cut into the arrays the body takes, the slots' newest tokens as the
    program before left them (``_newest_from``; None: none is read), and
    nothing else. ``layouts`` are
    the layouts the caller may send, of different lengths: the trace
    takes the one of ``desc``'s length (a shape, so static). It goes by
    the body's NAME, so the compiled module is jit_<body> as it was when
    the arrays came one by one (benchmark/readers match the two modules
    by that name)."""
    def program(params, desc, kv, last=None, *, layouts, **statics):
        return body(params, kv=kv, last=last,
                    **cut(desc, layout_of(desc, layouts)), **statics)
    program.__name__ = program.__qualname__ = body.__name__
    return program


#: module-level jits (shared compile cache across engine instances with
#: equal shapes/statics — many short-lived engines, e.g. a test suite,
#: must not each pay the XLA compile). Over a mesh, StepPrograms wraps the
#: raw bodies in shard_map instead.
ragged_step = functools.partial(jax.jit, static_argnames=(
    "layouts", "cfg", "tp_axis", "paged_impl", "max_q_len", "decode_rows"),
    donate_argnames=("kv",))(_on_descriptor(_ragged_step_body))

ragged_decode_loop = functools.partial(jax.jit, static_argnames=(
    "layouts", "num_steps", "cfg", "tp_axis", "paged_impl"),
    donate_argnames=("kv",))(_on_descriptor(_ragged_decode_loop))


def _copy_page_body(kv: KVCache, src, dst) -> KVCache:
    """Copy-on-write: duplicate one page across all layers — pages AND
    their int8 scales, one tree_map (a prefix-hit sequence about to
    write into a shared page copies it first). The state leaves, whose
    second axis is batch slots and not pages, pass through, and so do a
    second page group's leaves, whose pages are numbered on their own (a
    configuration with such a group has no prefix cache, so nothing is
    ever copied for it: every leaf comes back whole). Plain body so
    StepPrograms can shard_map it over local head shards."""
    pages = jax.tree.map(
        lambda leaf: leaf.at[:, dst].set(
            lax.dynamic_index_in_dim(leaf, src, axis=1, keepdims=False)),
        {k: leaf for k, leaf in kv.items()
         if k not in STATE_LEAVES + WINDOW_LEAVES})
    return {**kv, **pages}


copy_page = functools.partial(jax.jit, donate_argnames=("kv",))(
    _copy_page_body)


#: one-device weights are BORN on the device by a jitted init (module-level:
#: engines with equal configs share the compile). Fused, the f32 draw of a
#: bf16 weight never exists in HBM — the eager init's largest temporary is
#: what OOMs an 8B-width model. (The pool is plain zeros: eager has none.)
_init_params = jax.jit(init_params, static_argnums=(0,))


def chunk_row_shapes(prefill_rows: int) -> Tuple[int, ...]:
    """The chunk-row counts the mixed step compiles for: 1, 2, 4, ...
    below ``prefill_rows``, and ``prefill_rows`` itself ({1} at 1, {1, 2}
    at 2, {1, 2, 4} at 4, {1, 2, 4, 6} at 6). A step runs the smallest
    that holds the rows it was dealt."""
    shapes, r = [], 1
    while r < prefill_rows:
        shapes.append(r)
        r *= 2
    return (*shapes, prefill_rows)


class StepPrograms:
    """The ONE seam between the engine and its device programs: the mixed
    ragged step, the multi-step decode loop and the COW page copy, the
    static hints they compile under, the kernel-or-reference choice, and
    where weights and the page pool are born (in their final layout: no
    device ever stages a whole sharded model).

    ``mesh=None``: the module-level jits above. A ('tp',) mesh: one
    ``shard_map`` jit each over the raw bodies, built once per (cfg,
    mesh), specs from llm/tp.py. Either way the decode loop and the page
    copy have ONE static shape each and the mixed step ``row_shapes`` of
    them (``chunk_row_shapes(prefill_rows)``: decode_rows + r rows,
    decode_rows + r * max_q_len token slots; one jit, one body, a trace a
    shape); each compiles once, ``program_budget`` programs in all, and
    where a compile tracker runs, the three callables record their
    compiles with it (llm.ragged_step, llm.decode_loop, llm.copy_page).

    The two step programs are called ``(params, desc, kv, last)``:
    ``desc`` one flat int32 array in ``decode_layout`` or
    ``step_layouts[chunk rows]`` (a mixed step's shape IS its descriptor's
    length: the trace finds its layout by it), ``last`` the [decode_rows]
    newest tokens the program before returned (``init_last`` for the
    first), which each returns anew as its LAST result: the engine hands
    it on without reading it, so a program may be launched before the one
    before it has been read back. Over a mesh both are replicated
    operands.
    """

    def __init__(self, cfg: LlamaConfig, *, decode_chunk: int,
                 max_q_len: int, decode_rows: int, max_pages: int,
                 kv_quantized: bool, prefill_rows: int = 1, mesh=None,
                 page_size: int = 16):
        self.cfg = cfg
        self.mesh = mesh
        #: chunk rows of each shape the mixed step may be called in, and
        #: what the engine's programs may number: more is a breach
        self.row_shapes = chunk_row_shapes(prefill_rows)
        self.program_budget = 2 + len(self.row_shapes)
        #: the paged-attention (and expert) implementation the programs
        #: compile: the Pallas kernels on a TPU, the references elsewhere
        #: — observed, never configured (device_report()), and from the
        #: platform the programs RUN on: a CPU test mesh in a TPU-default
        #: worker takes the reference
        impl = self.paged_impl = "kernel" if kernels_supported(
            None if mesh is None else mesh.devices.flat[0]) else "reference"
        #: the descriptors: the decode loop's, and the mixed step's by its
        #: chunk rows; token_state rides where a layer keeps state a slot
        #: entries of a row's compact table in the second page group (0: no
        #: window layer), in the mixed step and over a decode block
        ps = page_size
        self.window_pages = {
            "step": window_table_width(cfg.sliding_window, max_q_len, ps),
            "decode": window_table_width(cfg.sliding_window, decode_chunk,
                                         ps)} \
            if cfg.layers_of(WINDOW) else {"step": 0, "decode": 0}
        self.decode_layout = decode_layout(decode_rows, max_pages,
                                           self.window_pages["decode"])
        self.step_layouts = {
            n: step_layout(decode_rows, n, max_q_len, max_pages,
                           keeps_slot_state(cfg), self.window_pages["step"])
            for n in self.row_shapes}
        #: whether a mixed step's tokens that are not their row's last
        #: leave the walk before its tail (the engine counts them)
        self.leaves_early = leaves_early(cfg, max_q_len)
        step_statics = dict(
            layouts=tuple(self.step_layouts.values()), cfg=cfg,
            paged_impl=impl, max_q_len=max_q_len, decode_rows=decode_rows)
        loop_statics = dict(
            layouts=(self.decode_layout,), num_steps=decode_chunk, cfg=cfg,
            paged_impl=impl)
        #: name -> (the jit itself, the static arguments of every call)
        if mesh is None:
            self.jits = {"ragged_step": (ragged_step, step_statics),
                         "decode_loop": (ragged_decode_loop, loop_statics),
                         "copy_page": (copy_page, {})}
        else:
            self.jits = self._shard_mapped(step_statics, loop_statics,
                                           kv_quantized)
        self.tracker = compile_tracker.ensure_started()
        #: what the seam wrapped, as the tracker names it: the three step
        #: programs and, once called, the births of weights and pool
        self.tracked: List[str] = []
        self.ragged_step = self._callable("ragged_step")
        self.decode_loop = self._callable("decode_loop")
        self.copy_page = self._callable("copy_page")

    def _callable(self, name: str, call=None, probe=None):
        """``call`` (the step program ``name`` with its statics, without
        one) as the engine calls it: where a compile tracker runs, every
        compile is recorded as ``llm.<name>`` with its arg signature, the
        ground truth for the O(1)-compile invariant in production.
        ``probe`` counts compiled programs, and growth across one call is
        that call's: the step programs' count without one."""
        if call is None:
            jit, statics = self.jits[name]
            call = functools.partial(jit, **statics) if statics else jit
        if self.tracker is None:
            return call
        if "llm." + name not in self.tracked:
            self.tracked.append("llm." + name)
        return self.tracker.wrap(call, name="llm." + name,
                                 probe=probe or self.compiled_step_programs)

    def _shard_mapped(self, step_statics, loop_statics, kv_quantized):
        """The three programs over ``self.mesh`` (and, kept for the
        birth of weights and pool, the shardings of both)."""
        cfg, mesh = self.cfg, self.mesh
        TP.validate_tp(cfg, mesh.shape[TP.TP_AXIS])
        pspecs, kvs = TP.tp_param_specs(cfg), TP.kv_specs(kv_quantized)
        rep = P()
        self._param_sharding, self._kv_sharding = jax.tree.map(
            lambda spec: NamedSharding(mesh, spec), (pspecs, kvs),
            is_leaf=lambda x: isinstance(x, P))

        # per-shard: local kv-heads write their ragged K/V slice in place
        # into, and attend over, the local head slice of the stacked page
        # pool (the scans' carry); the two psums per layer inside
        # _ragged_step_body close the TP seam
        step = functools.partial(_on_descriptor(_ragged_step_body),
                                 tp_axis=TP.TP_AXIS, **step_statics)
        loop = functools.partial(_on_descriptor(_ragged_decode_loop),
                                 tp_axis=TP.TP_AXIS, **loop_statics)

        def sharded(fn, in_specs, out_specs, donate):
            return jax.jit(shard_map_compat(
                fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs),
                donate_argnums=(donate,)), {}

        return {
            "ragged_step": sharded(step, (pspecs, rep, kvs, rep),
                                   (rep, kvs, rep), 2),
            "decode_loop": sharded(loop, (pspecs, rep, kvs, rep),
                                   (rep, kvs, rep, rep, rep), 2),
            "copy_page": sharded(_copy_page_body, (kvs, rep, rep), kvs, 0)}

    def compiled_step_programs(self) -> int:
        """Resident compiled step programs: the O(1) compile budget the
        ragged design promises (``program_budget``). Without a mesh the
        three module jits share their cache across engines, so the count
        is process-wide (in a fresh process running one engine, exactly
        that engine's)."""
        return sum(jit._cache_size() for jit, _ in self.jits.values())

    def init_params(self, seed: int) -> Params:
        if self.mesh is None:
            jit = functools.partial(_init_params, self.cfg)
            probe = _init_params._cache_size
        else:
            jit = jax.jit(functools.partial(init_params, self.cfg),
                          out_shardings=self._param_sharding)
            probe = jit._cache_size
        # the key is made inside the tracked call: its small programs are
        # this name's compiles too, not nameless ring records
        return self._callable(
            "init_params", lambda seed: jit(jax.random.PRNGKey(seed)),
            probe)(seed)

    def place_params(self, params: Params) -> Params:
        if self.mesh is None:
            return params
        return jax.device_put(params, self._param_sharding)

    def init_last(self, decode_rows: int) -> jax.Array:
        """The first program's ``last``: no slot has a token yet."""
        zeros = np.zeros(decode_rows, np.int32)
        if self.mesh is None:
            return jax.device_put(zeros)
        return jax.device_put(zeros, NamedSharding(self.mesh, P()))

    def init_kv(self, total_pages: int, page_size: int, kv_dtype,
                max_batch: int = 0, window_pages: int = 0) -> KVCache:
        # the kernels move pages by DMA, in rows of whole lanes
        make = functools.partial(make_kv_cache, self.cfg, total_pages,
                                 page_size, kv_dtype=kv_dtype,
                                 max_batch=max_batch,
                                 lane_pad=self.paged_impl == "kernel",
                                 window_pages=window_pages)
        if self.mesh is None:
            # eager zeros, a small program a leaf shape: no jit's cache to
            # probe, so a call compiled if the tracker's listener saw the
            # backend compile during it (a probe that never grows)
            return self._callable("init_kv", make, lambda: 0)()
        jit = jax.jit(make, out_shardings=self._kv_sharding)
        return self._callable("init_kv", jit, jit._cache_size)()


# ---------------------------------------------------------------------------
# Plain-path check: the engine's greedy tokens against models.llama.forward
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg",))
def _plain_logits(params: Params, tokens: jax.Array, cfg: LlamaConfig):
    from ray_tpu.models.llama import forward
    return forward(params, tokens, cfg)[0]                # [S, V] fp32


def plain_greedy_check(params: Params, cfg: LlamaConfig, prompt, generated,
                       seq_len: int) -> dict:
    """Score a greedy continuation against the PLAIN path on the same
    weights: ``models.llama.forward`` with attention="full" — no page
    pool, no ragged batch, no kernel, the training-side forward.

    Teacher-forced: one forward over prompt + generated (right-padded to
    ``seq_len``; causal, so padding cannot reach back), then per generated
    position the plain path's own argmax and the logit GAP between its
    top choice and the token the engine emitted. gap == 0 where the two
    agree; where bf16 rounding flipped a near-tie the gap is small, and
    a wrong page, mask or position shows up as a gap of whole logits.
    """
    import dataclasses
    n_p, n_g = len(prompt), len(generated)
    if n_p + n_g > seq_len:
        raise ValueError(f"{n_p} + {n_g} tokens exceed seq_len {seq_len}")
    toks = jnp.zeros((1, seq_len), jnp.int32).at[0, :n_p + n_g].set(
        jnp.asarray(list(prompt) + list(generated), jnp.int32))
    logits = _plain_logits(params, toks,
                           dataclasses.replace(cfg, attention="full"))
    rows = logits[n_p - 1:n_p - 1 + n_g]       # row i predicts generated[i]
    emitted = jnp.asarray(list(generated), jnp.int32)
    gap = rows.max(axis=-1) - rows[jnp.arange(n_g), emitted]
    return {"plain_tokens": jnp.argmax(rows, axis=-1).tolist(),
            "gap": [float(g) for g in gap]}
