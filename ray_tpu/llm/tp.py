"""Tensor-parallel serving: shard the inference engine over a ``tp`` mesh.

Role-equivalent to the reference's multi-worker LLM deployment, where
tensor_parallel_size drives both the engine sharding and the placement
bundles (reference: python/ray/llm/_internal/serve/deployments/llm/vllm/
vllm_models.py:128-153 — worker count and STRICT_PACK/PACK groups derive
from TP×PP degrees). TPU-first redesign: instead of one Ray worker
process per shard coordinating over NCCL, ONE engine process drives a
``jax.sharding.Mesh`` over the host's chips and each of the THREE step
programs (ragged mixed step, multi-step decode loop, COW page copy) is a
single ``shard_map`` jit — XLA lays the two psums per layer (Megatron
schedule) on ICI, and the ragged paged-attention kernel runs per-shard
on local heads (head-sliced attention needs no communication).

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

import functools
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.models.llama import LlamaConfig, Params
from ray_tpu.parallel.mesh import shard_map_compat

TP_AXIS = "tp"

#: paged KV pool [n_layers, pages, Hkv, page_size, D] — heads sharded
CACHE_SPEC = P(None, None, TP_AXIS, None, None)
#: int8 KV scale arrays [n_layers, pages, Hkv, page_size] — same axis
SCALE_SPEC = P(None, None, TP_AXIS, None)


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


class TPEngineFns:
    """The three device programs the engine dispatches, tp-sharded.

    Call signatures mirror _SingleChipFns in llm/engine.py so the engine
    swaps implementations behind one seam. Built once per (cfg, mesh);
    every program has ONE static shape, so each compiles exactly once.
    """

    def __init__(self, cfg: LlamaConfig, mesh: Mesh, *,
                 decode_chunk: int, max_q_len: int, decode_rows: int,
                 kv_quantized: bool = False):
        from ray_tpu.llm import model as M
        validate_tp(cfg, mesh.shape[TP_AXIS])
        self.cfg = cfg
        self.mesh = mesh
        self.tp = mesh.shape[TP_AXIS]
        pspecs = tp_param_specs(cfg)
        rep = P()
        kvs = self._kv_specs = kv_specs(kv_quantized)

        # the kernel/reference choice follows the MESH platform, not the
        # process default backend — a CPU test mesh inside a TPU-default
        # worker must take the gather reference, and vice versa
        from ray_tpu.ops.paged_attention import kernels_supported
        paged_impl = self.paged_impl = "kernel" \
            if kernels_supported(mesh.devices.flat[0]) else "reference"

        def step(params, tokens, token_pos, token_page, token_slot,
                 page_table, q_start, q_len, kv_len, kv):
            # per-shard: local kv-heads write their ragged K/V slice in
            # place into, and attend over, the local head slice of the
            # stacked page pool (the scans' carry); the two psums per
            # layer inside _ragged_step_body close the TP seam
            return M._ragged_step_body(
                params, tokens, token_pos, token_page, token_slot,
                page_table, q_start, q_len, kv_len, kv, cfg, TP_AXIS,
                paged_impl, max_q_len, decode_rows)

        self.ragged_step = jax.jit(shard_map_compat(
            step, mesh=mesh,
            in_specs=(pspecs, P(None), P(None), P(None), P(None),
                      P(None, None), P(None), P(None), P(None), kvs),
            out_specs=(rep, kvs)),
            donate_argnums=(9,))

        def loop(params, tokens, positions, kv, page_table, seq_lens):
            return M._ragged_decode_loop(
                params, tokens, positions, kv, page_table, seq_lens,
                decode_chunk, cfg, TP_AXIS, paged_impl)

        self.decode_loop = jax.jit(shard_map_compat(
            loop, mesh=mesh,
            in_specs=(pspecs, P(None), P(None), kvs, P(None, None),
                      P(None)),
            out_specs=(rep, kvs, rep, rep)),
            donate_argnums=(3,))

        self.copy_page = jax.jit(shard_map_compat(
            M._copy_page_body, mesh=mesh,
            in_specs=(kvs, rep, rep),
            out_specs=kvs),
            donate_argnums=(0,))
        # the jits themselves, for the program count: the engine rebinds
        # the three attributes above to compile-tracker wrappers
        self._jits = (self.ragged_step, self.decode_loop, self.copy_page)

    def compiled_step_programs(self) -> int:
        """Resident compiled step programs for this mesh's fns."""
        return sum(f._cache_size() for f in self._jits)

    # ------------------------------------------------------------ placement
    # Weights and pool are created sharded (jit out_shardings): staging
    # them whole on one device first would cap the model at ONE chip's
    # HBM — the very limit tp exists to lift.

    def _shardings(self, specs):
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs,
                            is_leaf=lambda x: isinstance(x, P))

    def init_params(self, seed: int) -> Params:
        from ray_tpu.models.llama import init_params
        return jax.jit(
            functools.partial(init_params, self.cfg),
            out_shardings=self._shardings(tp_param_specs(self.cfg)))(
                jax.random.PRNGKey(seed))

    def place_params(self, params: Params) -> Params:
        return jax.device_put(
            params, self._shardings(tp_param_specs(self.cfg)))

    def init_kv(self, total_pages: int, page_size: int, kv_dtype) -> dict:
        from ray_tpu.llm.cache import make_kv_cache
        return jax.jit(
            functools.partial(make_kv_cache, self.cfg, total_pages,
                              page_size, kv_dtype=kv_dtype),
            out_shardings=self._shardings(self._kv_specs))()
