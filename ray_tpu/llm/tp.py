"""Tensor-parallel serving: shard the inference engine over a ``tp`` mesh.

Role-equivalent to the reference's multi-worker LLM deployment, where
tensor_parallel_size drives both the engine sharding and the placement
bundles (reference: python/ray/llm/_internal/serve/deployments/llm/vllm/
vllm_models.py:128-153 — worker count and STRICT_PACK/PACK groups derive
from TP×PP degrees). TPU-first redesign: instead of one Ray worker
process per shard coordinating over NCCL, ONE engine process drives a
``jax.sharding.Mesh`` over the host's chips and each of the THREE step
programs (ragged mixed step, multi-step decode loop, COW page copy) is a
single ``shard_map`` jit (``llm.model.StepPrograms`` builds them from
this module's specs and mesh) — XLA lays the two psums per layer
(Megatron schedule) on ICI, and the ragged paged-attention kernel runs
per-shard on local heads (head-sliced attention needs no communication).

Layout (classic Megatron, weights arrive pre-sliced inside shard_map):
  - wq/wk/wv, w_gate/w_up: column-sharded (output dim over tp)
  - wo, w_down:            row-sharded (input dim over tp) + psum
  - embed, norms:          replicated (the 8B embed is ~1 GB bf16 —
                           small next to the sharded layers + KV pool)
  - paged KV pool:         kv-head axis sharded — each chip holds
                           Hkv/tp heads of EVERY page (int8 scale
                           arrays shard the same axis), so the page
                           allocator stays global and unchanged
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.models.llama import LlamaConfig, Params

TP_AXIS = "tp"

#: paged KV pool [n_layers, pages, Hkv, page_size, D] — heads sharded.
#: Written as a program's result comes back (no trailing unsharded axes):
#: the pool as it is born and as a step returns it are then ONE entry of
#: a jit's cache, and compiled_step_programs() counts a program once
CACHE_SPEC = P(None, None, TP_AXIS)
#: int8 KV scale arrays [n_layers, pages, Hkv, page_size] — same axis
SCALE_SPEC = P(None, None, TP_AXIS)


def kv_specs(quantized: bool) -> dict:
    """PartitionSpec tree matching cache.make_kv_cache's pytree."""
    specs = {"k": CACHE_SPEC, "v": CACHE_SPEC}
    if quantized:
        specs["k_scale"] = SCALE_SPEC
        specs["v_scale"] = SCALE_SPEC
    return specs


def tp_param_specs(cfg: LlamaConfig) -> Params:
    """PartitionSpec tree for SERVING (single tp axis) — distinct from
    models.llama.param_specs, which targets the training mesh
    (pp/fsdp/tp)."""
    col = P(None, None, TP_AXIS)   # [L, d, out] — shard out
    row = P(None, TP_AXIS, None)   # [L, in, d]  — shard in, psum after
    rep2 = P(None, None)
    head = {} if cfg.tie_embeddings else {"lm_head": rep2}
    return {
        **head,
        "embed": rep2,
        "layers": {
            "attn_norm": rep2,
            "wq": col, "wk": col, "wv": col, "wo": row,
            "mlp_norm": rep2,
            "w_gate": col, "w_up": col, "w_down": row,
        },
        "final_norm": P(None),
    }


def validate_tp(cfg: LlamaConfig, tp: int) -> None:
    if tp < 2:
        raise ValueError(f"tp must be >= 2 for a sharded engine, got {tp}")
    if cfg.delta_block:
        raise NotImplementedError(
            f"tp={tp} is not served for this block: linear_attention "
            f"layers (delta_key_heads, delta_value_heads, delta_key_dim, "
            f"delta_value_dim) keep a float32 matrix state and their "
            f"conv's inputs per batch slot with no partition spec here "
            f"(the state would shard by value head with w_qkv's, w_z's and "
            f"w_ba's columns, two value heads to a key head), beside a "
            f"latent pool that has no head axis to shard; q_lora_rank, "
            f"rope_yarn, norm_gate and ffn_clamp are refused with them, "
            f"untested under a shard (ROADMAP R10b)")
    if cfg.window_block:
        raise NotImplementedError(
            f"tp={tp} is not served for this block: sliding_attention "
            f"layers keep their pages in a second page group with its own "
            f"key/value heads (window_kv_heads), for whose leaves there is "
            f"no partition spec here, and score_head_dim / value_head_dim, "
            f"rotary_dim, value_scale, experts_held (a share of the "
            f"experts is the other way a layer is divided among chips: the "
            f"exchange between the shares is not built), attn_gate (w_og "
            f"would shard by head with wq's columns), post_norms (a norm "
            f"over the whole width AFTER the row-parallel sum) and "
            f"full_rope=False are refused with them, untested under a "
            f"shard (ROADMAP R5a, R10b)")
    if cfg.beyond_llama_block:
        raise NotImplementedError(
            f"tp={tp} is not served for this block: mamba layers keep a "
            f"matrix state [ssm_heads, ssm_head_dim, ssm_state] per batch "
            f"slot with no partition spec here (its heads would shard with "
            f"w_in's gate and x columns, while B, C and the conv over them "
            f"are shared by all heads), retention layers keep a matrix "
            f"state and a normaliser per batch slot and key/value head "
            f"with none either (they would shard by key/value head with "
            f"wq / wk / wv / w_g's columns), and rope=False, attn_scale, "
            f"embed_scale, residual_scale and logits_divisor are refused "
            f"with them, untested under a shard (ROADMAP R10b)")
    if cfg.kv_lora_rank or cfg.shared_ffn_dim:
        raise NotImplementedError(
            f"tp={tp} is not served for this block: kv_lora_rank (latent "
            f"attention) keeps ONE cache row a token for all heads, so "
            f"the pool has no kv-head axis to shard (every shard would "
            f"hold the whole latent and w_kva, and split w_uk / w_uv / wq "
            f"/ wo by head: no spec here says so), and shared_ffn_dim "
            f"comes with n_experts, which is refused below (ROADMAP R8)")
    if cfg.hybrid or cfg.qk_norm_per_head:
        raise NotImplementedError(
            f"tp={tp} is not served for this block: layer_types / "
            f"n_dense_layers stack the weights per kind of layer and keep "
            f"a conv state per batch slot, and neither has a partition "
            f"spec here (the conv operator's w_in would split B, C and u "
            f"each over the axis); qk_norm_per_head is refused with them, "
            f"untested under a head shard")
    if cfg.n_experts or cfg.qk_norm:
        raise NotImplementedError(
            f"tp={tp} is not served for this block: qk_norm normalises "
            f"over all heads, which a head shard can only do with a "
            f"collective the step does not have, and n_experts="
            f"{cfg.n_experts} needs an expert-parallel layout, not the "
            f"Megatron column/row split (ROADMAP R5)")
    if cfg.n_kv_heads % tp or cfg.n_heads % tp:
        raise ValueError(
            f"tp={tp} must divide n_heads={cfg.n_heads} and "
            f"n_kv_heads={cfg.n_kv_heads}")


def build_tp_mesh(tp: int,
                  devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """1-D ('tp',) mesh over the first tp devices — adjacent ICI
    neighbours on TPU (jax.devices() is torus-ordered)."""
    import numpy as np
    devices = list(devices if devices is not None else jax.devices())
    if len(devices) < tp:
        raise ValueError(f"tp={tp} needs {tp} devices, have {len(devices)}")
    return Mesh(np.asarray(devices[:tp]), (TP_AXIS,))
