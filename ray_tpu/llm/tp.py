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

from ray_tpu.models.llama import (LlamaConfig, Params,
                                  mechanisms_beyond, named)

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
    # an untied head is the one mechanism beyond the Llama/Mistral block
    # with a spec above (tp_param_specs)
    found = mechanisms_beyond(cfg, served=("untied head",))
    if found:
        raise NotImplementedError(
            f"tp={tp} is not served for this block: {named(found)} have no "
            f"partition spec here")
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
