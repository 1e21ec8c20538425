"""Llama-family decoder-only transformer, TPU-first.

Design (contrast reference: models live outside the tree in torch/vLLM —
SURVEY.md §2.5 Ray LLM row):
  * pure functions: `init_params` → pytree, `forward(params, tokens)` → logits
  * `param_specs` returns a PartitionSpec pytree aligned leaf-for-leaf with
    params — fsdp shards the embed/ffn input dims, tp shards heads/ffn
    hidden, pp shards the stacked layer dim
  * layers are STACKED on axis 0 and applied with `lax.scan` + remat: one
    compiled layer body regardless of depth (XLA-friendly, constant compile
    time), and the stack shards over `pp` for pipeline parallelism. The
    remat boundary ("full") keeps a layer's input and, where the attention
    is the flash kernel, the kernel's output and log-sum-exp (the tags
    ops.flash_attention.FLASH_SAVE_NAMES, read by remat_policy_fn): the
    one thing in a layer dearer to recompute than to hold. Beside them it
    keeps ONE MLP projection's output (FULL_KEEPS_PROJECTION) where the
    shapes say that what it then holds across the scan is no more than
    the compute-dtype copy of the whole stack of weights, which it held
    through both loops until _in_its_turn (full_remat_keeps: the budget is
    read off the stacked leaves, the cost off the body's own trace; the
    same decision on the CPU, for a described chip and on the chip). Each
    layer's weights are cast to the compute dtype in that layer's turn of
    the loop (_in_its_turn): one copy a turn, none of the whole stack
  * attention: "full" (GSPMD auto-sharded), "ring" (manual `sp` ring over
    ICI — ray_tpu.parallel.ring_attention), or "ulysses" (all-to-all)
  * bf16 activations/compute, fp32 params & softmax/logit accumulators
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import itertools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ray_tpu.ops.flash_attention import FLASH_SAVE_NAMES, kernel_calls
from ray_tpu.parallel.attention import causal_attention
from ray_tpu.parallel.mesh import shard_map_compat
from ray_tpu.parallel.pipeline import pipeline_apply
from ray_tpu.parallel.ring_attention import (ring_attention,
                                             ring_attention_sharded)
from ray_tpu.parallel.ulysses import ulysses_attention_sharded
from ray_tpu.util import compile_tracker

Params = Dict[str, Any]

#: a layer's operator, as the published configurations name it
ATTENTION, CONV, MAMBA, RETENTION, WINDOW, DELTA = \
    "full_attention", "conv", "mamba", "retention", "sliding_attention", \
    "linear_attention"
#: ... and a decoder-hybrid-decoder's (Phi-4-mini-flash, of the phi4flash
#: family, is the first such block; its published file names no layer's
#: kind, the names are the program's): a Mamba-1 selective scan, a gated
#: memory unit that reads the newest scan's output, and attention whose keys
#: and values are the newest full-attention layer's pages
MAMBA1, GMU, CROSS = "mamba1", "gmu", "cross_attention"
LAYER_KINDS = (ATTENTION, CONV, MAMBA, RETENTION, WINDOW, DELTA, MAMBA1, GMU,
               CROSS)
#: range of the seeded attention sinks (float32, one a query head of a
#: window layer): a full window's 128 scores under seeded weights sum to
#: about e^4.9, so a sink in [3, 6] takes between a sixth and three quarters
#: of a row's mass (benchmark/configs/mimo-v2-flash-serve-1chip.json has the
#: reading); one seeded at 0 would take under 1 % and no check could see it
#: left out
SINK_RANGE = (3.0, 6.0)
#: spread of the seeded router selection bias. The top 4 of 64 sigmoid
#: scores lie ~0.013 apart, so 0.02 changes the chosen set for about half
#: the tokens and leaves the load near even (busiest expert 2.3 x the mean
#: at 107 rows; 1.9 with no bias). A trained bias BALANCES load; at 0.1 a
#: drawn one decided the choice by itself (a fifth of the experts never
#: hit, busiest 5.4 x the mean: PERF.md, PR 30)
ROUTER_BIAS_STD = 0.02

#: The Llama/Mistral block's fields and the run options: what every consumer
#: of a LlamaConfig takes. layer_types is the switch of the layer kinds below.
LLAMA_BLOCK = (
    "vocab_size", "dim", "n_layers", "n_heads", "n_kv_heads", "ffn_dim",
    "rope_theta", "norm_eps", "attention", "dtype", "param_dtype", "remat",
    "remat_policy", "pp_microbatches", "fsdp_overlap", "int8_mlp",
    "layer_types")
#: Every other field, under the ONE mechanism it describes. A mechanism is ON
#: where layer_types names it (a layer kind) or a field of it is set off its
#: default (mechanisms_beyond); the configuration's cross-checks and both
#: refusals (the training side's below, llm/tp.py's) are read off this
#: table, and a field that is in neither tuple fails
#: tests/test_llama_mechanisms.py.
MECHANISMS = {
    "experts": ("n_experts", "experts_per_token", "norm_topk_prob",
                "router_score", "router_bias", "router_eps", "router_scale",
                "n_dense_layers", "dense_ffn_dim", "shared_ffn_dim"),
    "experts_held": ("experts_held",),
    "qk_norm": ("qk_norm",),
    "qk_norm_per_head": ("qk_norm_per_head",),
    "untied head": ("tie_embeddings",),
    "latent attention": ("kv_lora_rank", "qk_nope_head_dim",
                         "qk_rope_head_dim", "v_head_dim", "q_lora_rank",
                         "rope_yarn"),
    ATTENTION: (),
    CONV: ("conv_kernel",),
    MAMBA: ("ssm_state", "ssm_heads", "ssm_head_dim", "ssm_conv",
            "ssm_chunk"),
    RETENTION: ("retention_chunk",),
    WINDOW: ("sliding_window", "window_kv_heads", "window_rope_theta",
             "attn_sink"),
    DELTA: ("delta_key_heads", "delta_value_heads", "delta_key_dim",
            "delta_value_dim", "delta_conv", "delta_chunk", "delta_norm_eps",
            "delta_gate_scale"),
    "head widths": ("score_head_dim", "value_head_dim", "rotary_dim",
                    "value_scale"),
    "multipliers": ("embed_scale", "residual_scale", "logits_divisor"),
    "attn_scale": ("attn_scale",),
    "no positions": ("rope",),
    "gated block": ("attn_gate", "post_norms", "full_rope"),
    "norm_gate": ("norm_gate",),
    "ffn_clamp": ("ffn_clamp",),
    MAMBA1: ("ssm1_state", "ssm1_expand", "ssm1_conv", "ssm1_dt_rank"),
    GMU: (),
    CROSS: (),
    "differential attention": ("diff_attention",),
    "layer norm": ("layer_norm",),
    "biases": ("attn_bias",),
    "looped layers": ("ut_steps",)}
#: Constants of a mechanism's arithmetic (taps, a chunk, an epsilon, the
#: router's score) that nothing reads where the mechanism is off: set alone
#: they do not turn it on, and a configuration that sets them is accepted as
#: it always was
IDLE_WHERE_OFF = frozenset((
    "experts_per_token", "norm_topk_prob", "router_score", "router_bias",
    "router_eps", "router_scale", "dense_ffn_dim", "conv_kernel", "ssm_conv",
    "ssm_chunk", "retention_chunk", "delta_conv", "delta_chunk",
    "delta_norm_eps", "delta_gate_scale", "ssm1_expand", "ssm1_conv"))
#: What adds to any block: no mechanism below is refused beside these
_ANYWHERE = (ATTENTION, "untied head", "multipliers", "ffn_clamp",
             "experts_held", "norm_gate")
#: What a decoder-hybrid-decoder is made of (Phi-4-mini-flash): each is
#: built beside the others, position-free, and beside nothing else yet
_SAMBAY = (MAMBA1, WINDOW, GMU, CROSS, "differential attention",
           "layer norm", "biases", "no positions")
#: mechanism -> the mechanisms it is BUILT beside (llm/model.py has the
#: program, a reference under benchmark/ the comparison); one with no row
#: here is built beside whatever lists it. A new mechanism is in no row:
#: refused beside these until a row says it is served
BUILT_BESIDE = {
    MAMBA: _ANYWHERE + (CONV, RETENTION, "qk_norm", "qk_norm_per_head",
                        "head widths", "attn_scale", "no positions"),
    RETENTION: _ANYWHERE + (CONV, MAMBA, "qk_norm", "qk_norm_per_head",
                            "attn_scale", "no positions"),
    WINDOW: _ANYWHERE + ("experts", "qk_norm_per_head", "head widths",
                         "gated block")
    + tuple(m for m in _SAMBAY if m != "no positions"),
    DELTA: _ANYWHERE + ("experts", "latent attention", "qk_norm",
                        "qk_norm_per_head", "head widths", "attn_scale",
                        "no positions", "gated block"),
    "gated block": _ANYWHERE + (
        WINDOW, DELTA, "experts", "latent attention", "qk_norm",
        "qk_norm_per_head", "head widths", "attn_scale", "no positions",
        "looped layers"),
    # the walk re-entered a pass, attention layers only: a kind that keeps
    # state a slot, a window group, experts (their counters) and a latent
    # pool are each refused until someone builds them beside it
    "looped layers": (ATTENTION, "untied head", "gated block"),
    "head widths": _ANYWHERE + (
        CONV, MAMBA, WINDOW, DELTA, "experts", "qk_norm_per_head",
        "attn_scale", "no positions", "gated block"),
    "latent attention": _ANYWHERE + (DELTA, "experts", "attn_scale",
                                     "no positions", "gated block"),
    **{name: _ANYWHERE + _SAMBAY for name in _SAMBAY
       if name not in (WINDOW, "no positions")}}
#: ... and what a mechanism is built beside only WHERE another is on too:
#: window attention without positions is the differential body's
#: (llm/model.py:_diff_attention); the plain window operator's is refused as
#: it was (no reference computes it)
BUILT_BESIDE_WHERE = {"differential attention": {WINDOW: ("no positions",)}}


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 14336
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    attention: str = "full"          # full | flash | ring | ulysses
    dtype: Any = jnp.bfloat16        # activation/compute dtype
    param_dtype: Any = jnp.float32   # master weights
    remat: bool = True
    remat_policy: str = "full"       # full | dots | dots_no_batch | selective
    pp_microbatches: int = 4         # microbatch count when pp > 1
    fsdp_overlap: bool = False       # explicit prefetch-scheduled fsdp step
    int8_mlp: bool = False           # dynamic-W8A8 MLP matmuls (ops.int8)
    # The block's variation points. The defaults are the Llama/Mistral
    # block; the serving step (llm/model.py) follows them, the training
    # forward below refuses what it has not got (MECHANISMS,
    # _require_llama_block).
    n_experts: int = 0               # 0 = dense SwiGLU; else routed experts
    experts_per_token: int = 0       #   of width ffn_dim, this many a token
    norm_topk_prob: bool = False     # renormalise the chosen experts' weights
    qk_norm: bool = False            # RMSNorm on the projected q and k
    tie_embeddings: bool = True      # logits from embed, else from lm_head
    # A block whose layers DIFFER (LFM2: gated short convolutions with
    # attention every fourth layer, two leading dense feed-forwards before
    # the expert ones). Empty layer_types = every layer is attention.
    layer_types: Tuple[str, ...] = ()    # per layer: LAYER_KINDS
    conv_kernel: int = 3             # taps of the depthwise causal conv
    n_dense_layers: int = 0          # leading layers with a dense SwiGLU
    dense_ffn_dim: int = 0           #   of this width (experts: ffn_dim)
    qk_norm_per_head: bool = False   # q/k RMSNorm over each head, not all
    router_score: str = "softmax"    # softmax | sigmoid
    router_bias: bool = False        # per-expert bias, for the choice only
    router_eps: float = 0.0          # added to the renormalising sum
    router_scale: float = 1.0        # on the routing weights
    # Latent attention (MLA; Kanana-2, of the deepseek_v3 family, is the
    # first such block): no wk / wv; a token's cache in a layer is ONE
    # vector of kv_lora_rank + qk_rope_head_dim values for all heads, and
    # the head sizes are fields, not dim / n_heads (head_dim below stays
    # what it is for the blocks that have it). Its rotary part turns
    # ADJACENT pairs (_rope_pairs: the family's published rope_interleave
    # true), the one pairing built: a constant of the block, not a field.
    kv_lora_rank: int = 0            # 0 = per-head K and V; else the rank
    qk_nope_head_dim: int = 0        #   of the latent; a score head's part
    qk_rope_head_dim: int = 0        #   from it, and its rotary part (ONE
    v_head_dim: int = 0              #   key for all heads); a value head
    shared_ffn_dim: int = 0          # a SwiGLU every token takes, added to
    #                                  the routed experts' sum (0 = none)
    # A selective state-space operator (Mamba-2; granite-4.0-h is the
    # first such block): layer_types names "mamba" layers, whose cache in
    # a layer is a MATRIX state [ssm_heads, ssm_head_dim, ssm_state] and
    # the last ssm_conv - 1 inputs of a depthwise conv over
    # heads * head_dim + 2 * state channels (x, B and C together), both per
    # batch slot. B and C are shared by all heads (one group).
    ssm_state: int = 0               # N: columns of a head's state
    ssm_heads: int = 0               # H
    ssm_head_dim: int = 0            # P: rows of a head's state
    ssm_conv: int = 4                # taps of its depthwise causal conv
    ssm_chunk: int = 256             # tokens a block of the chunked scan
    # Multipliers of the granite family; 1 (and rope on, and a score
    # scale of head_dim ** -0.5) is every other block.
    embed_scale: float = 1.0         # on the embedding's output
    residual_scale: float = 1.0      # on both branches of every layer
    attn_scale: float = 0.0          # on q . k; 0 = head_dim ** -0.5
    logits_divisor: float = 1.0      # logits = (x embed^T) / this
    rope: bool = True                # False: no positional embedding
    # Power retention of degree 2 (Brumby-14B is the first such block):
    # layer_types names "retention" layers, which project q, k and v as
    # attention does (n_heads, n_kv_heads, head_dim, the q/k norm, the
    # rotary embedding) and keep no page: per batch slot and key/value head
    # a MATRIX state [D, head_dim] over the degree-2 expansion of the key
    # (D = ops/retention.py:expanded_dim(head_dim)), decayed a token by a
    # gate sigmoid(h w_g + b_g), beside its normaliser.
    retention_chunk: int = 256       # tokens a block of its chunk form
    # Attention whose score head is not its value head, nor dim / n_heads
    # (MiMo-V2-Flash is the first such block outside the latent one: 192
    # and 128), rotated over its leading rotary_dim values only, the values
    # scaled. 0 / 1.0 = the Llama block's.
    score_head_dim: int = 0          # 0 = head_dim; a q / k head's width
    value_head_dim: int = 0          # 0 = head_dim; a v head's width
    rotary_dim: int = 0              # 0 = the whole score head is rotated
    value_scale: float = 1.0         # on v (by linearity: on the output)
    # Window attention: layer_types names "sliding_attention" layers, which
    # see the last sliding_window positions (the token itself counted), on
    # their own number of key/value heads and their own rotary base, with a
    # learned sink a query head in the softmax's denominator (attn_sink).
    # Their pages are a SECOND page group that frees behind the window
    # (llm/cache.py).
    sliding_window: int = 0          # W: a token at t sees t - W < s <= t
    window_kv_heads: int = 0         # key/value heads of a window layer
    window_rope_theta: float = 0.0   # its rotary base
    attn_sink: bool = False          # a float32 logit a head, no value
    # An attention operator whose output is gated and whose branches are
    # normed twice, in a block whose window layers rotate and whose full
    # layers carry no position (Trinity-Mini, of the afmoe family, is the
    # first such block). Off = every other block's program, text for text.
    attn_gate: bool = False          # sigmoid(h w_og) on o, before wo
    post_norms: bool = False         # x + rms(branch; *_post_norm), both
    full_rope: bool = True           # False: full layers are not rotated
    # The chip's share of an expert layer: (first, n) = this chip holds
    # experts first .. first + n - 1 of n_experts. The router keeps all
    # n_experts outputs; pairs routed elsewhere go nowhere (ops/moe.py).
    experts_held: Tuple[int, ...] = ()
    # Gated-delta-rule linear attention beside latent attention
    # (GigaChat3.5, model_type gigachat3_5, is the first such block):
    # layer_types names "linear_attention" layers, which keep no page: per
    # batch slot and VALUE head a float32 matrix state [delta_key_dim,
    # delta_value_dim] that each token decays and then CORRECTS along its
    # key (ops/delta.py), beside the last delta_conv - 1 inputs of a
    # depthwise conv over q, k and v together. Key head j serves value
    # heads j Hv/Hk .. (j+1) Hv/Hk - 1.
    delta_key_heads: int = 0         # Hk
    delta_value_heads: int = 0       # Hv
    delta_key_dim: int = 0           # dk: a q / k head, rows of the state
    delta_value_dim: int = 0         # dv: a v head, columns of the state
    delta_conv: int = 4              # taps of its depthwise causal conv
    delta_chunk: int = 64            # tokens a block of its chunk form
    delta_norm_eps: float = 1e-6     # of the per-head norm on its output
    delta_gate_scale: float = 2.0    # y = rms(o) s sigmoid(w) s sigmoid(z)
    # ... and what that block's latent attention and feed-forward add. Off
    # (0 / ()) = every other block's program.
    q_lora_rank: int = 0             # q = rms(h wq_a) wq: a low-rank query
    #: YaRN on the latent operator's rotary part: (factor, original
    #: positions, beta_fast, beta_slow, mscale, mscale_all_dim)
    rope_yarn: Tuple[float, ...] = ()
    norm_gate: float = 0.0           # every norm x/rms * this * sigmoid(w)
    ffn_clamp: float = 0.0           # silu(min(g, c)) * clip(u, -c, c)
    # A decoder-hybrid-decoder (SambaY; Phi-4-mini-flash-reasoning is the
    # first such block): layer_types names "mamba1" layers (a Mamba-1
    # selective scan: every (channel, state index) of a [ssm1_expand * dim,
    # ssm1_state] state decays on its own, dt from a rank-ssm1_dt_rank
    # projection; per batch slot that state and the last ssm1_conv - 1
    # inputs of a depthwise conv; ops/selective_scan.py), "gmu" layers (a
    # gated memory unit: W2(m * silu(h W1)), m the NEWEST mamba1 layer's
    # scan output at the same token, before that layer's gate; nothing
    # kept) and "cross_attention" layers (own queries over the NEWEST
    # full_attention layer's pages; nothing written, nothing kept). Off =
    # every other block's program.
    ssm1_state: int = 0              # N: state values a channel
    ssm1_expand: int = 2             # channels = this * dim
    ssm1_conv: int = 4               # taps of its depthwise causal conv
    ssm1_dt_rank: int = 0            # R: dt = softplus(delta W_dt + b_dt)
    # ... whose attention of every kind is DIFFERENTIAL: heads come in
    # adjacent pairs (2j, 2j+1), a pair's two softmaxes each over the
    # pair's joined values, o = rms(a1 - lambda a2; 2 head_dim) (1 -
    # lambda_init), lambda from four learned vectors a layer and
    # lambda_init from the layer's index in the model
    diff_attention: bool = False
    layer_norm: bool = False         # every norm a LayerNorm with a bias
    attn_bias: bool = False          # biases on the attention projections
    # A LOOPED stack (a universal transformer; Ouro-2.6B, model_type ouro,
    # is the first such block): the n_layers layers run ut_steps times over
    # the SAME weights, the final norm after EVERY pass (its output is what
    # the next pass starts from), and each pass keeps keys and values of
    # its own: pass u, layer l writes and reads page plane u * n_layers + l
    # (llm/cache.py). More than one pass IMPLIES the exit gate, one linear
    # map dim -> 1 with a bias on each pass's normed stream, shared by the
    # passes (no switch of its own: the published family has none without
    # it). Every token runs every pass (the published early_exit_threshold
    # of 1); the gates are counted, nothing acts on them. 1 = every other
    # block's program, text for text.
    ut_steps: int = 1

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        object.__setattr__(self, "experts_held", tuple(self.experts_held))
        object.__setattr__(self, "rope_yarn",
                           tuple(float(v) for v in self.rope_yarn))
        bad = sorted(set(self.layer_types) - set(LAYER_KINDS))
        if bad or (self.layer_types
                   and len(self.layer_types) != self.n_layers):
            raise ValueError(
                f"layer_types must name one of {LAYER_KINDS} for each of "
                f"the {self.n_layers} layers, got {self.layer_types}")
        if self.router_score not in ("softmax", "sigmoid"):
            raise ValueError(f"router_score must be 'softmax' or "
                             f"'sigmoid', got {self.router_score!r}")
        if self.n_dense_layers and not (
                self.n_experts and self.dense_ffn_dim
                and self.n_dense_layers < self.n_layers):
            raise ValueError(
                f"n_dense_layers={self.n_dense_layers} leading dense "
                f"layers need n_experts, a dense_ffn_dim and fewer than "
                f"n_layers={self.n_layers}")
        if self.n_experts and not \
                0 < self.experts_per_token <= self.n_experts:
            raise ValueError(
                f"n_experts={self.n_experts} needs 0 < experts_per_token "
                f"<= n_experts, got {self.experts_per_token}")
        if self.shared_ffn_dim and not self.n_experts:
            raise ValueError(
                f"shared_ffn_dim={self.shared_ffn_dim} is the width of the "
                f"expert every token takes BESIDE the routed ones: it "
                f"needs n_experts")
        # the cross-checks, off the table: a mechanism whose switch is off
        # and whose fields are set; one beside what it is not built beside
        on = mechanisms_beyond(self)
        switches = {kind: kind in self.layer_types for kind in LAYER_KINDS}
        switches["latent attention"] = bool(self.kv_lora_rank)
        for name, switched in switches.items():
            if name in on and not switched:
                raise ValueError(
                    f"{', '.join(on[name])} describe {named([name])}: "
                    + ("layer_types names none" if name in LAYER_KINDS
                       else "they need kv_lora_rank"))
        for name, beside in BUILT_BESIDE.items():
            beside = beside + tuple(
                m for where, rows in BUILT_BESIDE_WHERE.items() if where in on
                for m in rows.get(name, ()))
            others = [m for m in on if m != name and m not in beside]
            if name in on and others:
                raise ValueError(
                    f"{named([name])}: not built beside {named(others)}")
        if not self.full_rope and WINDOW not in self.layer_types:
            raise ValueError(
                "full_rope=False describes a block whose window layers "
                "rotate and whose full layers do not: layer_types "
                "names no sliding_attention layer (rope=False is the "
                "block with no positions at all)")
        if self.ut_steps < 1 or (self.ut_steps > 1 and self.attn_gate):
            raise ValueError(
                f"ut_steps={self.ut_steps} passes over the layers: at "
                f"least 1, and built beside a gated block's post_norms "
                f"only (no reference computes attn_gate in a looped stack)")
        if self.norm_gate and not self.kv_lora_rank:
            raise ValueError(
                "norm_gate gates EVERY norm of a block, and only latent "
                "attention (kv_lora_rank) and the linear_attention layers "
                "beside it take their norms through it: per-head K and V "
                "attention would be served with a plain norm")
        # ... and the values a mechanism that is on needs
        ssm = (self.ssm_state, self.ssm_heads, self.ssm_head_dim)
        if MAMBA in self.layer_types and (
                min(ssm) <= 0 or self.ssm_conv < 2 or self.ssm_chunk < 1):
            raise ValueError(
                f"mamba layers need ssm_state, ssm_heads, ssm_head_dim, "
                f"at least 2 conv taps and a chunk, got {ssm}, "
                f"{self.ssm_conv}, {self.ssm_chunk}")
        if RETENTION in self.layer_types and (
                self.n_heads % self.n_kv_heads or self.head_dim % 8
                or self.retention_chunk < 1):
            raise ValueError(
                f"retention layers need n_kv_heads to divide n_heads, "
                f"a head_dim of whole blocks of 8 and a chunk, got "
                f"{self.n_heads}, {self.n_kv_heads}, {self.head_dim}, "
                f"{self.retention_chunk}")
        window = (self.sliding_window, self.window_kv_heads,
                  self.window_rope_theta)
        if WINDOW in self.layer_types and (
                min(window) <= 0 or self.n_heads % self.window_kv_heads):
            raise ValueError(
                f"sliding_attention layers need a sliding_window, "
                f"window_kv_heads that divide n_heads and a "
                f"window_rope_theta, got {window}")
        if "head widths" in on:
            dk, dv = self.qk_head_dim, self.v_dim
            if min(dk, dv) <= 0 or dk % 2 or self.rotary_dim % 2 \
                    or not 0 <= self.rotary_dim <= dk:
                raise ValueError(
                    f"need an even score head and an even rotary_dim "
                    f"within it, got {dk}, {dv}, {self.rotary_dim}")
        if self.experts_held:
            first, n = self.experts_held if len(self.experts_held) == 2 \
                else (-1, 0)
            if not self.n_experts or first < 0 or n < 1 \
                    or first + n > self.n_experts:
                raise ValueError(
                    f"experts_held = (first, n) names a chip's share of "
                    f"n_experts={self.n_experts} routed experts (a shared "
                    f"expert beside them is whole on every chip); got "
                    f"{self.experts_held}")
        heads = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                 self.v_head_dim)
        if self.kv_lora_rank and (
                min(heads) <= 0 or self.qk_rope_head_dim % 2):
            raise ValueError(
                f"kv_lora_rank={self.kv_lora_rank} (latent attention) "
                f"needs qk_nope_head_dim, an even qk_rope_head_dim and "
                f"v_head_dim, got {heads}")
        if self.rope_yarn and (len(self.rope_yarn) != 6
                               or self.rope_yarn[0] <= 1.0):
            raise ValueError(
                f"rope_yarn = (factor > 1, original positions, beta_fast, "
                f"beta_slow, mscale, mscale_all_dim), got {self.rope_yarn}")
        ssm1 = (self.ssm1_state, self.ssm1_expand, self.ssm1_dt_rank)
        if MAMBA1 in self.layer_types and (
                min(ssm1) <= 0 or self.ssm1_conv < 2):
            raise ValueError(
                f"mamba1 layers need ssm1_state, ssm1_expand, ssm1_dt_rank "
                f"and at least 2 conv taps, got {ssm1}, {self.ssm1_conv}")
        for kind, source in ((GMU, MAMBA1), (CROSS, ATTENTION)):
            if kind in self.layer_types and source not in \
                    self.layer_types[:self.layer_types.index(kind)]:
                raise ValueError(
                    f"a {kind} layer reads what the newest {source} layer "
                    f"before it left: layer_types names none before layer "
                    f"{self.layer_types.index(kind)}")
        if CROSS in self.layer_types and self.kv_lora_rank:
            raise ValueError("cross_attention layers read per-head K and V "
                             "pages, not a latent pool (kv_lora_rank)")
        if self.diff_attention and (
                self.n_heads % 2 or self.n_kv_heads % 2
                or self.n_heads % self.n_kv_heads
                or (WINDOW in self.layer_types
                    and self.window_kv_heads % 2)):
            raise ValueError(
                f"diff_attention pairs adjacent heads: n_heads, n_kv_heads "
                f"(and window_kv_heads) must be even, got {self.n_heads}, "
                f"{self.n_kv_heads}, {self.window_kv_heads}")
        for field in ("layer_norm", "attn_bias"):
            if getattr(self, field) and not self.diff_attention:
                raise ValueError(
                    f"{field} is built in the differential operators and "
                    f"the layers beside them only: it needs diff_attention")
        delta = (self.delta_key_heads, self.delta_value_heads,
                 self.delta_key_dim, self.delta_value_dim)
        chunk = self.delta_chunk
        if DELTA in self.layer_types and (
                min(delta) <= 0 or self.delta_value_heads
                % self.delta_key_heads or self.delta_conv < 2
                or chunk < 1 or chunk & (chunk - 1)):
            raise ValueError(
                f"linear_attention layers need delta_key_heads that "
                f"divide delta_value_heads, delta_key_dim, "
                f"delta_value_dim, at least 2 conv taps and a chunk "
                f"that is a power of two, got {delta}, "
                f"{self.delta_conv}, {chunk}")

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def qk_head_dim(self) -> int:
        """A q / k head's width of per-head K and V attention."""
        return self.score_head_dim or self.head_dim

    @property
    def v_dim(self) -> int:
        """A v head's width of per-head K and V attention."""
        return self.value_head_dim or self.head_dim

    @property
    def gated_block(self) -> bool:
        """An output gate on attention, a norm after each branch, or full
        layers without positions beside window layers that rotate
        (Trinity-Mini is the first such block)."""
        return self.attn_gate or self.post_norms or not self.full_rope

    @property
    def ssm_channels(self) -> int:
        """Channels of a mamba layer's conv: x, then B, then C."""
        return self.ssm_heads * self.ssm_head_dim + 2 * self.ssm_state

    @property
    def ssm1_channels(self) -> int:
        """Channels of a mamba1 layer: its conv's, its state's rows."""
        return self.ssm1_expand * self.dim

    @property
    def hybrid(self) -> bool:
        """The layers differ in kind (operator or feed-forward), or the
        block has leaves the Llama tree has no place for (latent
        attention, a shared expert, an output gate, a norm after a branch,
        a differential layer's lambdas, a looped stack's exit gate):
        weights are stacked per kind and the serving step runs the
        pattern."""
        return bool(self.layer_types or self.n_dense_layers
                    or self.kv_lora_rank or self.shared_ffn_dim
                    or self.attn_gate or self.post_norms
                    or self.diff_attention or self.ut_steps > 1)

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        """Indices of the layers whose operator is ``kind``."""
        types = self.layer_types or (ATTENTION,) * self.n_layers
        return tuple(i for i, t in enumerate(types) if t == kind)

    @staticmethod
    def tiny(**kw) -> "LlamaConfig":
        """Test-scale config for the virtual CPU mesh; the serving entry
        points build theirs here from a user's ``model_config``, so a key
        that is no field is refused by name."""
        names = [f.name for f in dataclasses.fields(LlamaConfig)]
        unknown = sorted(set(kw) - set(names))
        if unknown:
            raise ValueError(f"unknown model_config key(s) {unknown}; "
                             f"LlamaConfig has {names}")
        base = dict(vocab_size=256, dim=64, n_layers=4, n_heads=8,
                    n_kv_heads=4, ffn_dim=128, rope_theta=10000.0)
        base.update(kw)
        return LlamaConfig(**base)

    @staticmethod
    def llama3_8b(**kw) -> "LlamaConfig":
        base = dict(vocab_size=128256, dim=4096, n_layers=32, n_heads=32,
                    n_kv_heads=8, ffn_dim=14336)
        base.update(kw)
        return LlamaConfig(**base)


#: a field's default: what "set" is measured against
_DEFAULTS = {f.name: f.default for f in dataclasses.fields(LlamaConfig)}


def mechanisms_beyond(cfg: LlamaConfig, served=()) -> Dict[str, Tuple]:
    """mechanism -> those of its fields ``cfg`` sets off their defaults,
    for every mechanism of MECHANISMS that is ON in ``cfg`` (layer_types
    names it, or a field that is no idle constant is set) and that the
    consumer does not name in ``served``. Empty: the consumer has all of
    ``cfg``. The one question behind the cross-checks above, the training
    side's refusal below and llm/tp.py's."""
    found = {}
    for name, fields in MECHANISMS.items():
        differ = tuple(f for f in fields
                       if getattr(cfg, f) != _DEFAULTS[f])
        if name not in served and (name in cfg.layer_types
                                   or not IDLE_WHERE_OFF.issuperset(differ)):
            found[name] = differ
    return found


def named(mechanisms) -> str:
    """Mechanisms (mechanisms_beyond's answer, or their names) as a
    message names them: layer kinds as the published configurations do,
    each with the fields found set."""
    fields = mechanisms if isinstance(mechanisms, dict) else {}
    parts = []
    for m in mechanisms:
        # a mechanism of one field is named for it: not said twice
        found = [f for f in fields.get(m, ()) if f != m]
        parts.append((f"{m} layers" if m in LAYER_KINDS else m)
                     + (f" ({', '.join(found)})" if found else ""))
    return ", ".join(parts)


def init_params(cfg: LlamaConfig, key: jax.Array) -> Params:
    if cfg.hybrid:
        return _init_hybrid_params(cfg, key)
    ks = jax.random.split(key, 8)
    d, L = cfg.dim, cfg.n_layers
    hq, hkv, hd, f = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.ffn_dim
    pd = cfg.param_dtype

    def norm_init(*shape):
        return jnp.ones(shape, pd)

    def dense(k, *shape, fan_in=None):
        fan_in = fan_in if fan_in is not None else shape[-2]
        return (jax.random.normal(k, shape) * (fan_in ** -0.5)).astype(pd)

    # experts: the three MLP leaves gain an expert axis after the layer axis
    E = (cfg.n_experts,) if cfg.n_experts else ()
    params = {
        "embed": dense(ks[0], cfg.vocab_size, d, fan_in=d),
        "layers": {
            "attn_norm": norm_init(L, d),
            "wq": dense(ks[1], L, d, hq * hd),
            "wk": dense(ks[2], L, d, hkv * hd),
            "wv": dense(ks[3], L, d, hkv * hd),
            "wo": dense(ks[4], L, hq * hd, d),
            "mlp_norm": norm_init(L, d),
            "w_gate": dense(ks[5], L, *E, d, f),
            "w_up": dense(ks[6], L, *E, d, f),
            "w_down": dense(ks[7], L, *E, f, d),
        },
        "final_norm": norm_init(d),
    }
    # the leaves the variation points add, keyed off the same key without
    # moving the dense tree's draws
    if cfg.n_experts:
        params["layers"]["router"] = dense(
            jax.random.fold_in(key, 8), L, d, cfg.n_experts)
    if cfg.qk_norm_per_head:
        params["layers"]["q_norm"] = norm_init(L, hd)
        params["layers"]["k_norm"] = norm_init(L, hd)
    elif cfg.qk_norm:
        params["layers"]["q_norm"] = norm_init(L, hq * hd)
        params["layers"]["k_norm"] = norm_init(L, hkv * hd)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(jax.random.fold_in(key, 9),
                                  cfg.vocab_size, d, fan_in=d)
    return params


def _init_hybrid_params(cfg: LlamaConfig, key: jax.Array) -> Params:
    """The tree of a block whose layers differ: ``layers`` holds one
    stack per KIND, each on its own leading axis, a layer's entry at its
    ordinal among the layers of that kind. Operators: "attn" (the
    attention layers: a q / k head ``qk_head_dim`` wide and a v head
    ``v_dim``), "attn_window" (the window layers: the same leaves on
    window_kv_heads key/value heads and, with attn_sink, "sink" [n_heads]
    float32, seeded in SINK_RANGE; both stacks, in a gated block: "w_og"
    [d, n_heads * v_dim], the output gate's projection (attn_gate), and
    "attn_post_norm" [d] beside "mlp_post_norm" in both feed-forward
    stacks (post_norms)), "retention" (the power-retention
    layers: the
    attention layers' leaves and the gate's projection w_g [d, n_kv_heads]
    with its bias b_g, float32), "conv" (the gated short convolutions: w_in
    [d, 3d] to B, C and u, the depthwise taps w_conv [taps, d], w_out)
    and "mamba" (the state-space layers: the input projection [d, H P +
    (H P + 2 N) + H] as its column groups w_gate, w_xbc and w_dt, to the
    gate, the conv's input and dt: one matrix of 8512 columns is no whole
    number of 128-lane tiles, and XLA kept a second, re-laid copy of all
    its layers beside it; the depthwise taps w_conv
    [taps, H P + 2 N] with their bias b_conv, dt_bias, A_log and D a
    head, all five float32 whatever the weights are held in; the gated
    norm's weight gate_norm [H P]; w_out).
    Feed-forwards: "dense" (the leading n_dense_layers, or all without
    experts) and "moe" (the rest). The router's selection bias is float32
    whatever the weights are held in, and drawn, not zero: a zero bias
    would leave the choice and the weights the same experts.

    With latent attention (kv_lora_rank) "attn" holds no wk / wv: wq
    [d, H (nope + rope)], the down-projection w_kva [d, rank + rope], the
    latent's norm kv_norm [rank], wo [H v, d], and the published
    up-projection kv_b_proj [rank, H (nope + v)] split per head into w_uk
    [H, nope, rank] and w_uv [H, rank, v]: the two batched products of the
    absorbed form read them as they lie, with no transposed copy for XLA
    to hoist out of the step scan (PERF.md, "Left by PR 27"). A shared
    expert (shared_ffn_dim) is three more leaves of "moe", sliced by the
    layer scan like the router; the routed experts stay closed over. With
    experts_held = (first, n) the three expert leaves hold those n experts
    only and the router every expert's column. With q_lora_rank the query
    is two matrices and a norm between them: wq_a [d, rank], q_a_norm
    [rank], and wq [rank, H (nope + rope)]; a gated block's w_og and
    attn_post_norm join the latent stack as they join the others.

    "delta" (the gated-delta-rule layers): delta_norm; the projection to q,
    k and v as ONE matrix w_qkv [d, 2 Hk dk + Hv dv] (its columns the
    conv's channels, in that order: 128 whole lane tiles at the published
    sizes), to the output gate w_z [d, Hv dv] and to the write strength and
    the decay w_ba [d, 2 Hv] (b, then a); the depthwise taps w_conv [taps,
    channels], A_log and dt_bias a value head, the three float32 whatever
    the weights are held in; the output norm's weight gate_norm [dv], one
    for all heads; w_out [Hv dv, d].

    With norm_gate every norm's weight w enters as norm_gate * sigmoid(w)
    and is drawn uniform in [-0.5, 0.5] (a factor of 0.76 to 1.24 at 2:
    at w = 0 the factor is 1 and no check could see the sigmoid left out);
    without it every norm's weight is one.

    A looped stack (ut_steps > 1) adds the exit gate beside final_norm:
    "exit_w" [d] at the spread of its fan-in and "exit_b" [1], N(0, 0.02)
    (drawn: a zero bias would hide one that is left out), both held as the
    norms are."""
    d, L, pd = cfg.dim, cfg.n_layers, cfg.param_dtype
    hq, hd = cfg.n_heads, cfg.head_dim
    dk, dv = cfg.qk_head_dim, cfg.v_dim
    # 24 keys split as they always were (a block's draws do not move when
    # another block gains a leaf), then as many more as a block asks for
    keys = itertools.chain(
        jax.random.split(key, 24),
        (jax.random.fold_in(key, 24 + i) for i in itertools.count()))

    def dense(*shape, fan_in=None, dtype=pd):
        fan_in = fan_in if fan_in is not None else shape[-2]
        return (jax.random.normal(next(keys), shape)
                * (fan_in ** -0.5)).astype(dtype)

    def norm(*shape):
        if not cfg.norm_gate:
            return jnp.ones(shape, pd)
        return jax.random.uniform(next(keys), shape, jnp.float32, -0.5,
                                  0.5).astype(pd)

    def bias(*shape, std=0.02, dtype=pd):
        """A drawn bias (or a differential layer's lambda vector): zero
        would hide one that is left out."""
        return (std * jax.random.normal(next(keys), shape)).astype(dtype)

    def lnorm(stack, name, n, width=d):
        """... a LayerNorm's bias beside the weight ``name`` (layer_norm)."""
        if cfg.layer_norm:
            stack[name + "_b"] = bias(n, width)
        return stack

    def swiglu(n, *E, f):
        stack = {"mlp_norm": norm(n, d),
                 "w_gate": dense(n, *E, d, f), "w_up": dense(n, *E, d, f),
                 "w_down": dense(n, *E, f, d)}
        if cfg.post_norms:
            stack["mlp_post_norm"] = norm(n, d)
        return lnorm(stack, "mlp_norm", n)

    def differential(stack, n):
        """... what a differential layer adds: the four lambda vectors a
        layer, float32, N(0, 0.1) (lambda then strays ~0.1 off lambda_init:
        one left at lambda_init is seen), and the weight of the norm over a
        pair's joined output."""
        if cfg.diff_attention:
            for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
                stack[name] = bias(n, dk, std=0.1, dtype=jnp.float32)
            stack["subln"] = jnp.ones((n, 2 * dv), pd)
        return stack

    def qkv(n, hkv=cfg.n_kv_heads):
        """The leaves attention, window and retention layers share: the
        four projections (a q / k head dk wide, a v head dv) and the q/k
        norm the configuration names."""
        stack = {
            "attn_norm": jnp.ones((n, d), pd),
            "wq": dense(n, d, hq * dk), "wk": dense(n, d, hkv * dk),
            "wv": dense(n, d, hkv * dv), "wo": dense(n, hq * dv, d)}
        if cfg.qk_norm_per_head:
            # over each head: as wide as a q / k head IS (dk, which is
            # head_dim only where no field says otherwise)
            stack["q_norm"] = jnp.ones((n, dk), pd)
            stack["k_norm"] = jnp.ones((n, dk), pd)
        elif cfg.qk_norm:
            stack["q_norm"] = jnp.ones((n, hq * hd), pd)
            stack["k_norm"] = jnp.ones((n, hkv * hd), pd)
        if cfg.attn_bias:
            stack.update(bq=bias(n, hq * dk), bk=bias(n, hkv * dk),
                         bv=bias(n, hkv * dv), bo=bias(n, d))
        return differential(lnorm(stack, "attn_norm", n), n)

    def gated(stack, n, dv=dv):
        """... and what the attention and window operators of a gated
        block add: the gate's projection, as wide as the heads' output,
        and the norm after the branch."""
        if cfg.attn_gate:
            stack["w_og"] = dense(n, d, hq * dv)
        if cfg.post_norms:
            stack["attn_post_norm"] = norm(n, d)
        return stack

    hkv = cfg.n_kv_heads
    layers = {}
    A, C = len(cfg.layers_of(ATTENTION)), len(cfg.layers_of(CONV))
    if A and cfg.kv_lora_rank:
        r, dn, dr, dv = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim)
        rq = cfg.q_lora_rank
        layers["attn"] = gated({
            "attn_norm": norm(A, d),
            **({"wq_a": dense(A, d, rq), "q_a_norm": norm(A, rq)}
               if rq else {}),
            "wq": dense(A, rq or d, hq * (dn + dr)),
            "w_kva": dense(A, d, r + dr), "kv_norm": norm(A, r),
            "w_uk": dense(A, hq, dn, r, fan_in=r),
            "w_uv": dense(A, hq, r, dv), "wo": dense(A, hq * dv, d)}, A, dv)
    elif A:
        layers["attn"] = gated(qkv(A), A)
    Wn = len(cfg.layers_of(WINDOW))
    if Wn:
        # the window layers: their own key/value heads and, with attn_sink,
        # one float32 logit a query head (SINK_RANGE)
        layers["attn_window"] = gated(qkv(Wn, cfg.window_kv_heads), Wn)
        if cfg.attn_sink:
            layers["attn_window"]["sink"] = jax.random.uniform(
                next(keys), (Wn, hq), jnp.float32, *SINK_RANGE)
    if C:
        layers["conv"] = {
            "conv_norm": jnp.ones((C, d), pd),
            "w_in": dense(C, d, 3 * d),
            "w_conv": dense(C, cfg.conv_kernel, d, fan_in=cfg.conv_kernel),
            "w_out": dense(C, d, d)}
    S = len(cfg.layers_of(MAMBA))
    if S:
        H, di, ch = cfg.ssm_heads, cfg.ssm_heads * cfg.ssm_head_dim, \
            cfg.ssm_channels
        f32 = jnp.float32

        def uniform(lo, hi):
            return jax.random.uniform(next(keys), (S, H), f32, lo, hi)

        # the family's initialisation: A = -exp(A_log) in -[1, 16] and
        # softplus(dt_bias) log-uniform in [1e-3, 1e-1], so a head forgets
        # over 1 to 1000 tokens: the state is alive and bounded
        dt = jnp.exp(uniform(jnp.log(1e-3), jnp.log(1e-1)))
        layers["mamba"] = {
            "mamba_norm": jnp.ones((S, d), pd),
            # the published in_proj [d, H P + ch + H], held as its three
            # column groups: to the gate, the conv's input (x, B, C), dt
            "w_gate": dense(S, d, di), "w_xbc": dense(S, d, ch),
            "w_dt": dense(S, d, H),
            "w_conv": dense(S, cfg.ssm_conv, ch, fan_in=cfg.ssm_conv,
                            dtype=f32),
            "b_conv": dense(S, ch, fan_in=cfg.ssm_conv, dtype=f32),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),   # softplus^-1(dt)
            "A_log": jnp.log(uniform(1.0, 16.0)),
            "D": jnp.ones((S, H), f32),
            "gate_norm": jnp.ones((S, di), pd),
            "w_out": dense(S, di, d)}
    Dn = len(cfg.layers_of(DELTA))
    if Dn:
        Hk, Hv = cfg.delta_key_heads, cfg.delta_value_heads
        wide = Hv * cfg.delta_value_dim
        ch = 2 * Hk * cfg.delta_key_dim + wide
        f32 = jnp.float32

        def per_head(lo, hi):
            return jax.random.uniform(next(keys), (Dn, Hv), f32, lo, hi)

        # the state-space layers' draw, for its reason: A = exp(A_log) in
        # [1, 16] and softplus(dt_bias) log-uniform in [1e-3, 1e-1], so
        # g = -A softplus(a + dt_bias) lets a head forget over 1 to 1000
        # tokens: the state is alive, and old writes still weigh in it
        dt = jnp.exp(per_head(jnp.log(1e-3), jnp.log(1e-1)))
        layers["delta"] = {
            "delta_norm": norm(Dn, d),
            "w_qkv": dense(Dn, d, ch), "w_z": dense(Dn, d, wide),
            "w_ba": dense(Dn, d, 2 * Hv),
            "w_conv": dense(Dn, cfg.delta_conv, ch, fan_in=cfg.delta_conv,
                            dtype=f32),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),   # softplus^-1(dt)
            "A_log": jnp.log(per_head(1.0, 16.0)),
            "gate_norm": norm(Dn, cfg.delta_value_dim),
            "w_out": dense(Dn, wide, d)}
        if cfg.post_norms:
            layers["delta"]["attn_post_norm"] = norm(Dn, d)
    S1 = len(cfg.layers_of(MAMBA1))
    if S1:
        di, N, R = cfg.ssm1_channels, cfg.ssm1_state, cfg.ssm1_dt_rank
        f32 = jnp.float32
        # the family's initialisation: A[c, n] = -(n + 1) for every channel
        # and softplus(b_dt) log-uniform in [1e-3, 1e-1], so a (channel,
        # state index) pair decays by 0.2 to 0.9999 a token: a state
        # remembers between one and thousands of tokens. W_dt at a tenth of
        # its fan-in spread: the token moves dt by a third, b_dt sets its
        # range (at the full spread dt reaches 2 and a state forgets within
        # the token)
        dt = jnp.exp(jax.random.uniform(
            next(keys), (S1, di), f32, jnp.log(1e-3), jnp.log(1e-1)))
        layers["mamba1"] = lnorm({
            "mamba1_norm": norm(S1, d),
            # the published in_proj [d, 2 di] as its halves: to the conv's
            # input, to the gate
            "w_x": dense(S1, d, di), "w_z": dense(S1, d, di),
            "w_conv": dense(S1, cfg.ssm1_conv, di, fan_in=cfg.ssm1_conv,
                            dtype=f32),
            "b_conv": dense(S1, di, fan_in=cfg.ssm1_conv, dtype=f32),
            # x_proj: to delta (R), B (N) and C (N), in that order
            "w_xproj": dense(S1, di, R + 2 * N),
            "w_dt": 0.1 * dense(S1, R, di),
            "b_dt": dt + jnp.log(-jnp.expm1(-dt)),      # softplus^-1(dt)
            # [N, channels]: transposed, as the state lies (llm/cache.py)
            "A_log": jnp.broadcast_to(jnp.log(jnp.arange(
                1, N + 1, dtype=f32))[None, :, None], (S1, N, di)),
            "D": jnp.ones((S1, di), f32),
            "w_out": dense(S1, di, d)}, "mamba1_norm", S1)
    Gn = len(cfg.layers_of(GMU))
    if Gn:
        di = cfg.ssm1_channels
        layers["gmu"] = lnorm({
            "gmu_norm": norm(Gn, d), "w_in": dense(Gn, d, di),
            "w_out": dense(Gn, di, d)}, "gmu_norm", Gn)
    Cn = len(cfg.layers_of(CROSS))
    if Cn:
        # a query and an output projection only: keys and values are the
        # newest full-attention layer's
        stack = {"attn_norm": norm(Cn, d), "wq": dense(Cn, d, hq * dk),
                 "wo": dense(Cn, hq * dv, d)}
        if cfg.attn_bias:
            stack.update(bq=bias(Cn, hq * dk), bo=bias(Cn, d))
        layers["attn_cross"] = differential(
            lnorm(stack, "attn_norm", Cn), Cn)
    Rt = len(cfg.layers_of(RETENTION))
    if Rt:
        # sigmoid(b_g) log-uniform in 1 - [1e-3, 1e-1]: a head forgets over
        # 10 to 1000 tokens (a zero-mean gate forgets in three: no fault in
        # the state could then be seen). w_g at a tenth of the fan-in
        # spread: the token moves the gate, the bias sets its range
        u = jnp.exp(jax.random.uniform(
            next(keys), (Rt, hkv), jnp.float32, jnp.log(1e-3),
            jnp.log(1e-1)))
        layers["retention"] = {**qkv(Rt), "w_g": 0.1 * dense(Rt, d, hkv),
                               "b_g": jnp.log1p(-u) - jnp.log(u)}
    n_dense = cfg.n_dense_layers if cfg.n_experts else L
    if n_dense:
        layers["dense"] = swiglu(
            n_dense, f=cfg.dense_ffn_dim if cfg.n_experts else cfg.ffn_dim)
    if cfg.n_experts:
        M = L - n_dense
        # a chip's share: the held experts' matrices only; the router keeps
        # every expert's column
        layers["moe"] = swiglu(
            M, cfg.experts_held[1] if cfg.experts_held else cfg.n_experts,
            f=cfg.ffn_dim)
        layers["moe"]["router"] = dense(M, d, cfg.n_experts)
        if cfg.router_bias:
            layers["moe"]["router_bias"] = ROUTER_BIAS_STD * \
                jax.random.normal(next(keys), (M, cfg.n_experts), jnp.float32)
        if cfg.shared_ffn_dim:
            fs = cfg.shared_ffn_dim
            layers["moe"].update(
                w_shared_gate=dense(M, d, fs), w_shared_up=dense(M, d, fs),
                w_shared_down=dense(M, fs, d))
    params = {"embed": dense(cfg.vocab_size, d, fan_in=d),
              "layers": layers, "final_norm": norm(d)}
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(cfg.vocab_size, d, fan_in=d)
    if cfg.layer_norm:
        params["final_norm_b"] = bias(d)
    if cfg.ut_steps > 1:
        params["exit_w"] = dense(d, fan_in=d)
        params["exit_b"] = bias(1)
    return params


def _require_llama_block(cfg: LlamaConfig, what: str) -> None:
    found = mechanisms_beyond(cfg)
    if found:
        raise NotImplementedError(
            f"{what} is written for the Llama/Mistral block, not for "
            f"{named(found)}: served by llm/model.py only")


def param_specs(cfg: LlamaConfig) -> Params:
    """PartitionSpec pytree aligned with init_params' output.

    Stacked layer dim shards over pp; matmul input dims over fsdp (ZeRO-3
    gather), head/ffn-hidden dims over tp (Megatron) — the §2.6 inventory's
    TPU-native equivalents.
    """
    _require_llama_block(cfg, "param_specs")
    return {
        "embed": P("tp", "fsdp"),
        "layers": {
            "attn_norm": P("pp", None),
            "wq": P("pp", "fsdp", "tp"),
            "wk": P("pp", "fsdp", "tp"),
            "wv": P("pp", "fsdp", "tp"),
            "wo": P("pp", "tp", "fsdp"),
            "mlp_norm": P("pp", None),
            "w_gate": P("pp", "fsdp", "tp"),
            "w_up": P("pp", "fsdp", "tp"),
            "w_down": P("pp", "tp", "fsdp"),
        },
        "final_norm": P(None),
    }


def _rmsnorm(x, w, eps):
    xf = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * rms).astype(x.dtype) * w.astype(x.dtype)


def _rope(x, positions, theta):
    """Rotary embedding; x: [B, L, H, D_even], positions: [L] or [B, L]."""
    d2 = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, d2, dtype=jnp.float32) / d2)
    ang = positions[..., None].astype(jnp.float32) * freqs  # [..., L, d2]
    if ang.ndim == 2:  # [L, d2] → broadcast over batch
        ang = ang[None]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :d2].astype(jnp.float32), x[..., d2:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature term: 0.1 mscale ln(factor) + 1."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_freqs(dim: int, theta: float, yarn: Tuple[float, ...]):
    """The dim / 2 rotary frequencies under YaRN (``LlamaConfig.rope_yarn``):
    pair j turns at theta^(-2j/dim) where it completes more than beta_fast
    turns over the original positions, at 1 / factor of that where fewer
    than beta_slow, and at a linear blend of the two between. Returns
    (freqs [dim / 2] float32, the factor on cos and sin: mscale's term over
    mscale_all_dim's, 1 where the two are equal)."""
    factor, original, fast, slow, mscale, mscale_all = yarn

    def turns_at(n):
        return dim * math.log(original / (n * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(turns_at(fast)), 0)
    high = min(math.ceil(turns_at(slow)), dim - 1)
    j = jnp.arange(0, dim // 2, dtype=jnp.float32)
    plain = theta ** (-2.0 * j / dim)
    ramp = jnp.clip((j - low) / max(high - low, 1e-3), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp), \
        yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all)


def _rope_pairs(x, positions, theta, yarn: Tuple[float, ...] = ()):
    """Rotary embedding over ADJACENT pairs (2j, 2j+1) of the last axis,
    pair j turning at theta^(-2j/D) (the published rope_interleave layout
    of the deepseek_v3 family); x: [B, L, H, D_even], positions as
    _rope's. As a complex number, pair j is multiplied by
    exp(i * position * theta^(-2j/D))."""
    d2 = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, d2, dtype=jnp.float32) / d2)
    scale = 1.0
    if yarn:
        freqs, scale = yarn_freqs(x.shape[-1], theta, yarn)
    ang = positions[..., None].astype(jnp.float32) * freqs  # [..., L, d2]
    if ang.ndim == 2:
        ang = ang[None]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    xp = x.astype(jnp.float32).reshape(x.shape[:-1] + (d2, 2))
    x1, x2 = xp[..., 0], xp[..., 1]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape).astype(x.dtype)


def _full_attention(q, k, v):
    """Causal attention (shared fp32 kernel), output in q's dtype."""
    return causal_attention(q, k, v).astype(q.dtype)


#: checkpoint_name tags on the 7 projection-matmul outputs per layer —
#: what remat_policy="selective" saves (and nothing else)
SELECTIVE_SAVE_NAMES = ("attn_q", "attn_k", "attn_v", "attn_o",
                        "mlp_gate", "mlp_up", "mlp_down",
                        "moe_out")  # mixtral's combined expert output


#: the ONE projection output remat_policy="full" keeps beside the kernel's
#: two, where full_remat_keeps finds the room. mlp_gate costs the same
#: bytes and saves the same product, and a third of the time: XLA then
#: reads the stack in five fusions of the backward turn, not three, and
#: two of its products slow down (PERF.md, PR 56: both on the chip)
FULL_KEEPS_PROJECTION = "mlp_up"


def remat_policy_fn(name: str):
    """Config string → jax.checkpoint policy (shared with mixtral).

    What each keeps a layer, beside the layer's input:
      "full"       the flash forward kernel's two outputs, o [B·H, L, D]
                   and lse [B·H, 8, L] float32 (FLASH_SAVE_NAMES), where
                   the program has them; everything else is recomputed.
                   With any other attention (attention="full", the
                   blockwise fallback off the TPU, ulysses) no value
                   carries the names and nothing is kept: the rule follows
                   what the program contains, not a knob. That is the
                   name alone. The boundary of a layer scan
                   (remat_scan_body, which sees the shapes) keeps ONE
                   named projection beside them, FULL_KEEPS_PROJECTION,
                   where full_remat_keeps says the shapes pay for it
      "selective"  those, and the 7 named projection outputs per layer
                   (all [B, L, ·]), recomputing norms/rope/attention — the
                   TorchTitan-style middle ground
      "dots"       EVERY dot output — including the [B, H, L, L] attention
                   scores of attention="full", whose save cost scales L²;
                   the kernel is no dot and runs twice ("dots_no_batch"
                   likewise)
    The kernel's outputs are kept because nothing else can rebuild them:
    2.73 ms a layer at [2, 4096, 32 x 128] for 72 MiB, twice the time a
    byte of any projection "selective" keeps (PERF.md, PR 51). Under ring
    attention the block kernel runs once a ROTATION and each rotation's
    o / lse is kept: sp x those bytes a layer (no benchmark cell runs it;
    whoever adds the long-context training cell sizes it first)."""
    if name == "full":
        return jax.checkpoint_policies.save_only_these_names(
            *FLASH_SAVE_NAMES)
    if name == "dots":
        return jax.checkpoint_policies.checkpoint_dots
    if name == "dots_no_batch":
        return jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
    if name == "selective":
        return jax.checkpoint_policies.save_only_these_names(
            *SELECTIVE_SAVE_NAMES, *FLASH_SAVE_NAMES)
    raise ValueError(f"unknown remat_policy {name!r}")


def _named_bytes(jaxpr, names) -> collections.Counter:
    """Bytes of the checkpoint_name-tagged values of a jaxpr by tag,
    sub-jaxprs included (as ops.flash_attention.kernel_calls walks them),
    for the tags in ``names``."""
    found: collections.Counter = collections.Counter()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "name" and eqn.params["name"] in names:
            aval = eqn.outvars[0].aval
            found[eqn.params["name"]] += aval.size * aval.dtype.itemsize
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _named_bytes(sub, names)
    return found


def full_remat_keeps(body, args, stacked, cd) -> Dict[str, Any]:
    """What a remat_policy="full" boundary around ``body`` keeps a layer,
    decided from shapes and dtypes alone (nothing here asks a device, a
    flag or a model's name, so the CPU, a described chip and the chip
    build the same program). ``args``: what the body is handed in one turn
    (arrays, tracers or ShapeDtypeStructs); ``stacked``: the layer leaves
    as the scan holds them, [n_layers, ...] each; ``cd``: the compute
    dtype.

    The kernel's two outputs always (FLASH_SAVE_NAMES). Beside them
    FULL_KEEPS_PROJECTION, [B, L, ffn_dim], where the body has a value of
    that name and

        n_layers x (its bytes + the kernel's kept bytes, as the body's
        own trace gives them) <= the stacked leaves' bytes in ``cd``

    The right side is what "full" held across both scans anyway until
    _in_its_turn (PR 51): XLA's compute-dtype copy of the whole stack. So
    "full", the floor a user at the HBM limit has nothing below, never
    holds more beyond the carried x than it did then, and where the
    activations outgrow the weights (more rows, longer rows) it is what it
    was. Both sides are read as the scan sees them, global shapes under
    GSPMD and a shard's under shard_map: they shrink together under fsdp /
    tp. At mistral7b-train-1chip, 6 x (235 + 75.5 MB) = 1.86 GB against
    2.62 GB: kept, one MLP product a layer-turn less; at 3 rows of 4096
    (2.79 GB) or 2 of 8192 (3.72 GB) not.

    Returns {"names": what the policy saves, "bytes": n_layers x their
    bytes, "budget_bytes": the right side}."""
    n = jax.tree.leaves(stacked)[0].shape[0]
    budget = sum(w.size for w in jax.tree.leaves(stacked)) \
        * jnp.dtype(cd).itemsize
    names = (*FLASH_SAVE_NAMES, FULL_KEEPS_PROJECTION)
    # under vjp: the kernel's tags are placed by its forward RULE
    sizes = _named_bytes(
        jax.make_jaxpr(lambda *a: jax.vjp(body, *a)[0])(*args).jaxpr, names)
    if n * sum(sizes.values()) > budget:
        del sizes[FULL_KEEPS_PROJECTION]
    return {"names": tuple(t for t in names if sizes[t]),
            "bytes": n * sum(sizes.values()), "budget_bytes": budget}


def remat_scan_body(body, cfg, stacked=None):
    """body, the function a lax.scan runs once a layer, behind cfg's remat
    boundary (shared with mixtral): the ONE place a layer's boundary is
    built. With ``stacked`` (the layer leaves the scan runs over) a "full"
    boundary keeps what full_remat_keeps allows at the shapes the body is
    handed in its first turn, and says so to the compile tracker
    (`remat_kept` on the record of the compile in flight: `python -m
    ray_tpu compiles`); without, the names alone decide, as before.
    prevent_cse=False, always, because the body is a scan's.
    jax.checkpoint's default puts an optimization_barrier on everything
    the rematted computation is handed, so that XLA cannot merge the
    recomputation with the first computation; in a scan the two are in two
    different while loops and cannot be merged anyway (jax's documentation
    names this setting for a checkpointed scan body), and the fence only
    costs copies: every operand of the backward turn has to exist as a
    buffer of its own before the turn starts, so XLA copies a float32
    slice of each master and the kept x out of their stacks before it
    reads them (eight passes a layer-turn in the train cell, and layouts
    the rope's fusions are held to: PERF.md, PR 53). Without it the
    backward's casts read the stack in place, as the forward's do. A
    caller that checkpoints something that is NOT a scan body has no place
    here and keeps jax's default."""
    if not cfg.remat:
        return body
    if cfg.remat_policy != "full" or stacked is None:
        return jax.checkpoint(body, policy=remat_policy_fn(cfg.remat_policy),
                              prevent_cse=False)

    def bounded(*args):
        kept = full_remat_keeps(body, args, stacked, cfg.dtype)
        compile_tracker.note_traced(remat_kept=kept)
        return jax.checkpoint(
            body, prevent_cse=False,
            policy=jax.checkpoint_policies.save_only_these_names(
                *kept["names"]))(*args)

    return bounded


def _layer(lp: Params, x, cfg: LlamaConfig, positions, attn_fn):
    """One transformer block; lp leaves have the layer axis removed."""
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    B, L, _ = x.shape
    cd = cfg.dtype

    if cfg.int8_mlp:
        from ray_tpu.ops.int8 import int8_matmul

        def mlp_mm(a, w):
            return int8_matmul(a, w.astype(cd))
    else:
        def mlp_mm(a, w):
            return a @ w.astype(cd)

    h = _rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
    q = checkpoint_name(h @ lp["wq"].astype(cd), "attn_q")
    k = checkpoint_name(h @ lp["wk"].astype(cd), "attn_k")
    v = checkpoint_name(h @ lp["wv"].astype(cd), "attn_v")
    q = _rope(q.reshape(B, L, hq, hd), positions, cfg.rope_theta)
    k = _rope(k.reshape(B, L, hkv, hd), positions, cfg.rope_theta)
    v = v.reshape(B, L, hkv, hd)
    if hkv != hq:  # GQA: repeat KV groups to full head count
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    o = attn_fn(q, k, v).reshape(B, L, hq * hd)
    x = x + checkpoint_name(o @ lp["wo"].astype(cd), "attn_o")

    h = _rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)
    gate = jax.nn.silu(checkpoint_name(mlp_mm(h, lp["w_gate"]), "mlp_gate"))
    up = checkpoint_name(mlp_mm(h, lp["w_up"]), "mlp_up")
    x = x + checkpoint_name(mlp_mm(gate * up, lp["w_down"]), "mlp_down")
    return x


@jax.custom_vjp
def _held(ws):
    """ws behind an optimization_barrier: XLA makes the values once, where
    this stands, and everything after reads them. The cotangents are handed
    straight back (a barrier's own transpose would hold them too, and a
    weight gradient would be written out in the compute dtype before it
    reaches the masters' stack)."""
    return lax.optimization_barrier(ws)


_held.defvjp(lambda ws: (lax.optimization_barrier(ws), None),
             lambda _, g: (g,))


def _in_its_turn(lp: Params, turn, cd) -> Params:
    """The layer's weights in the compute dtype, cast in THIS turn of the
    scan and held (_layer's own casts then do nothing; they stay for the
    callers that hand it masters). Two things XLA does otherwise, both
    read off the train cell (PERF.md, PR 51):
      * it moves the cast of a slice of a loop-invariant stack out of the
        loop: compute-dtype copies of EVERY layer's weights, alive through
        the forward and the backward scan (2.6 GB of the chip's 16.9, for
        which its own rematerialisation then pays in time). A zero that
        depends on the scan's counter, added before the cast, keeps the
        cast in its turn (w + 0.0 is w: no value changes); an
        optimization_barrier alone does not (compiled for v5e);
      * it then fuses the cast into every product that reads the weight,
        a fifth slower each than one that reads the compute dtype: _held
        makes one copy a turn instead.
    The weight gradients reach the masters' dtype stack straight from the
    products' float32 accumulators, as they did."""
    zero = turn.astype(jnp.float32) * 0.0
    return _held(jax.tree.map(
        lambda w: w if w.dtype == cd
        else (w + zero.astype(w.dtype)).astype(cd), lp))


def _scan_layers(layers: Params, x, cfg: LlamaConfig, positions, attn_fn):
    layer = functools.partial(_layer, cfg=cfg, positions=positions,
                              attn_fn=attn_fn)

    def body(lp_turn, x):
        return layer(_in_its_turn(*lp_turn, cfg.dtype), x)

    body = remat_scan_body(body, cfg, layers)

    def step(x, lp_turn):
        return body(lp_turn, x), None

    n = jax.tree.leaves(layers)[0].shape[0]
    x, _ = lax.scan(step, x, (layers, jnp.arange(n)))
    return x


def forward(params: Params, tokens: jax.Array, cfg: LlamaConfig,
            mesh=None) -> jax.Array:
    """tokens [B, L] int32 → logits [B, L, vocab] (fp32).

    mesh is required for ring/ulysses attention and for pp > 1 (the stacked
    layer axis sharded over 'pp'); with attention='full' and pp==1 the whole
    forward is a single GSPMD program.
    """
    _require_llama_block(cfg, "models.llama.forward")
    B, L = tokens.shape
    cd = cfg.dtype
    x = params["embed"].astype(cd)[tokens]
    positions = jnp.arange(L)

    pp = mesh.shape.get("pp", 1) if mesh is not None else 1
    if pp > 1:
        x = _forward_pipelined(params, x, cfg, mesh, positions)
    else:
        attn_fn = _make_attn_fn(cfg, mesh)
        x = _scan_layers(params["layers"], x, cfg, positions, attn_fn)

    x = _rmsnorm(x, params["final_norm"], cfg.norm_eps)
    # Tied embeddings: logits = x · embedᵀ. bf16 operands on the MXU with
    # fp32 ACCUMULATION (f32 operands would leave the MXU fast path).
    return jnp.einsum("bld,vd->blv", x.astype(cd),
                      params["embed"].astype(cd),
                      preferred_element_type=jnp.float32)


def flash_impl() -> str:
    """What attention="flash" resolves to in this process: "kernel" (the
    Pallas fwd+bwd kernels, on a TPU) or "blockwise" (the portable
    lax.scan equivalent, anywhere else). Chosen from the platform, never
    configured — a training loop reports it so a chip run can assert it
    did not take the portable branch."""
    from ray_tpu.ops.flash_attention import kernels_supported
    return "kernel" if kernels_supported() else "blockwise"


def flash_forward_calls(cfg: LlamaConfig, seq_len: int) -> int:
    """Calls of the flash FORWARD kernel in one layer's value-and-grad at
    this sequence length, counted in the traced program and not read off
    a flag: 1 where the remat boundary keeps the kernel's output and
    log-sum-exp (or there is no remat), 2 where the backward runs the
    kernel again to rebuild them, 0 where the attention is not the kernel
    (flash_impl() "blockwise", any other cfg.attention)."""
    one = dataclasses.replace(cfg, n_layers=1)
    params = jax.eval_shape(functools.partial(init_params, one),
                            jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((1, seq_len), jnp.int32)
    grad = jax.grad(functools.partial(loss_fn, cfg=one))
    return kernel_calls(jax.make_jaxpr(grad)(params, tokens).jaxpr)[
        "_fwd_kernel"]


def _make_attn_fn(cfg: LlamaConfig, mesh):
    if cfg.attention == "full":
        return _full_attention
    if cfg.attention == "flash":
        from ray_tpu.ops import flash_attention
        from ray_tpu.ops.flash_attention import (blockwise_attention,
                                                 flash_attention_sharded)
        if flash_impl() == "blockwise":
            # CPU test meshes: same blockwise numerics.
            return lambda q, k, v: blockwise_attention(q, k, v).astype(q.dtype)
        if mesh is not None:
            return functools.partial(flash_attention_sharded, mesh=mesh)
        # blk=None: the kernels' blocks come from the static shape
        # (ops.flash_attention.flash_tiling)
        return functools.partial(flash_attention, blk_q=None, blk_k=None)
    if mesh is None:
        raise ValueError(f"attention={cfg.attention!r} needs a mesh")
    if cfg.attention == "ring":
        # pp > 1 runs attention inside the PARTIAL-manual pipeline
        # shard_map where fsdp/tp stay GSPMD-auto — a Mosaic pallas_call
        # cannot be auto-partitioned there, so force the einsum ring path
        # (full-manual single-stage meshes keep the fused auto-default)
        use_kernel = False if mesh.shape.get("pp", 1) > 1 else None
        return functools.partial(ring_attention_sharded, mesh=mesh,
                                 use_kernel=use_kernel)
    if cfg.attention == "ulysses":
        return functools.partial(ulysses_attention_sharded, mesh=mesh)
    raise ValueError(f"unknown attention {cfg.attention!r}")


def _forward_pipelined(params: Params, x, cfg: LlamaConfig, mesh, positions):
    """pp > 1: microbatch the batch dim, run stages over the 'pp' axis.

    The stacked layer axis is ALREADY sharded over pp (param_specs), so each
    stage's shard_map block holds n_layers/pp layers; activations hop via
    ppermute inside pipeline_apply. Embedding/head stay outside the pipeline
    (they are not stage-shaped — same trick as classic GPipe embeddings).
    Manual axes: {'pp'} (+'sp' for ring attention); fsdp/tp stay GSPMD-auto.
    """
    B, L, D = x.shape
    M = min(cfg.pp_microbatches, B)
    if B % M:
        raise ValueError(f"batch {B} not divisible by pp_microbatches {M}")
    xm = x.reshape(M, B // M, L, D)

    manual = {"pp"}
    if cfg.attention == "ring":
        manual.add("sp")

        def attn_fn(q, k, v):
            return ring_attention(q, k, v, axis_name="sp")
    elif cfg.attention == "full":
        attn_fn = _full_attention
    else:
        raise ValueError("pp>1 supports attention in {'full','ring'}")

    seq_dim_spec = "sp" if "sp" in manual else None

    def run(layers, xm):
        def stage_fn(layers, xb):
            Lloc = xb.shape[1]
            if "sp" in manual:
                off = lax.axis_index("sp") * Lloc
            else:
                off = 0
            pos = off + jnp.arange(Lloc)
            return _scan_layers(layers, xb, cfg, pos, attn_fn)

        return pipeline_apply(stage_fn, layers, xm, axis_name="pp")

    # Partial-manual shard_map: specs may ONLY name the manual axes; the
    # dp/fsdp batch sharding stays GSPMD-auto and flows through untouched.
    xspec = P(None, None, seq_dim_spec, None)
    lspec = jax.tree.map(lambda _: P("pp"), params["layers"])
    out = shard_map_compat(run, mesh=mesh,
                           in_specs=(lspec, xspec), out_specs=xspec,
                           axis_names=manual)(params["layers"], xm)
    return out.reshape(B, L, D)


def _nll_mean(logits, tokens):
    """Shifted next-token NLL mean; logits [B, L, V] fp32, tokens [B, L]."""
    logits = logits[:, :-1]
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean()


def _loss_overlap(params: Params, tokens: jax.Array, cfg: LlamaConfig,
                  mesh) -> jax.Array:
    """fsdp_overlap=True loss: full-manual shard_map over (dp, fsdp) with
    the prefetch-scheduled layer scan (parallel.fsdp_overlap) instead of
    GSPMD-placed gathers. Numerics match loss_fn exactly (parity-tested);
    only the collective schedule differs. Requires pp == sp == tp == 1:
    the block is manual over EVERY mesh axis and only (dp, fsdp) appear
    in its specs, so any other axis would just replicate the work.
    """
    from ray_tpu.parallel.fsdp_overlap import (drop_leading_dim,
                                               gather_params, overlap_scan,
                                               project_specs)

    for ax in ("pp", "sp", "tp"):
        if mesh.shape.get(ax, 1) > 1:
            raise ValueError(
                f"fsdp_overlap runs full-manual over (dp, fsdp); mesh axis "
                f"{ax!r} has size {mesh.shape[ax]} > 1")
    if cfg.attention not in ("full", "flash"):
        raise ValueError(
            f"fsdp_overlap supports attention in {{'full','flash'}}, got "
            f"{cfg.attention!r}")
    attn_fn = _make_attn_fn(cfg, None)  # per-shard, batch-only sharding
    specs = project_specs(param_specs(cfg), ("fsdp",))
    lspecs = drop_leading_dim(specs["layers"])
    cd = cfg.dtype

    def block(params, tokens):
        L = tokens.shape[1]
        positions = jnp.arange(L)
        embed = gather_params(params["embed"], specs["embed"], "fsdp")
        x = embed.astype(cd)[tokens]
        body = remat_scan_body(
            functools.partial(_layer, cfg=cfg, positions=positions,
                              attn_fn=attn_fn), cfg, params["layers"])
        x = overlap_scan(params["layers"], lspecs, x, body, cfg.n_layers,
                         axis_name="fsdp")
        x = _rmsnorm(x, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum("bld,vd->blv", x.astype(cd), embed.astype(cd),
                            preferred_element_type=jnp.float32)
        # equal-size batch shards → pmean of shard means == global mean
        return lax.pmean(_nll_mean(logits, tokens), ("dp", "fsdp"))

    fn = shard_map_compat(block, mesh=mesh,
                          in_specs=(specs, P(("dp", "fsdp"), None)),
                          out_specs=P())
    return fn(params, tokens)


def loss_fn(params: Params, tokens: jax.Array, cfg: LlamaConfig,
            mesh=None) -> jax.Array:
    """Next-token cross-entropy (mean over B×(L-1) positions), fp32.

    The FULL sequence goes through forward (keeps L divisible by the sp
    axis for ring/ulysses); the shift happens on logits afterwards.

    cfg.fsdp_overlap routes to the explicit prefetch-scheduled manual
    step (same numerics, overlap-friendly collective placement) whenever
    the mesh actually shards fsdp.
    """
    if cfg.fsdp_overlap and mesh is not None \
            and mesh.shape.get("fsdp", 1) > 1:
        return _loss_overlap(params, tokens, cfg, mesh)
    logits = forward(params, tokens, cfg, mesh)
    return _nll_mean(logits, tokens)


def num_params(cfg: LlamaConfig) -> int:
    _require_llama_block(cfg, "num_params")
    d, L, f = cfg.dim, cfg.n_layers, cfg.ffn_dim
    hd = cfg.head_dim
    per_layer = (d * cfg.n_heads * hd + 2 * d * cfg.n_kv_heads * hd
                 + cfg.n_heads * hd * d + 3 * d * f + 2 * d)
    return cfg.vocab_size * d + L * per_layer + d


def flops_per_token(cfg: LlamaConfig, seq_len: int) -> float:
    """Approx training FLOPs/token: 6·N_params + attention score term.

    The embed matrix counts: it is tied as the LM head, so its matmul runs.
    The attention term uses the full (non-causal) 12·L·d·s convention
    (PaLM appendix B); causal kernels do ~half that score work.
    """
    attn = 12 * cfg.n_layers * cfg.dim * seq_len  # fwd+bwd qk+pv scores
    return 6.0 * num_params(cfg) + attn
