"""Mixtral-family sparse-MoE decoder transformer, TPU-first.

Second model family next to models/llama.py (reference scope: Ray serves
Mixtral through vLLM out-of-tree — SURVEY.md §2.5 Ray LLM row; the
architecture here follows the public Mixtral-8x7B description: Llama-style
GQA attention + top-2 routed expert FFN per layer).

Same design rules as llama.py: pure init/forward functions, stacked layers
applied with `lax.scan` + remat (one compiled layer body), `param_specs`
aligned leaf-for-leaf for pjit — with the expert dimension sharded over the
`ep` mesh axis (parallel/moe.py all_to_all dispatch) on top of llama's
fsdp/tp/pp axes. The router's load-balancing auxiliary loss (Switch-style
f·P term) accumulates through the scan carry.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ray_tpu.models.llama import (_full_attention, _nll_mean, _rmsnorm,
                                  _rope, remat_scan_body)
from ray_tpu.parallel.mesh import shard_map_compat
from ray_tpu.parallel.moe import _routing, moe_ffn, moe_ffn_sharded

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class MixtralConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 14336
    n_experts: int = 8
    top_k: int = 2
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-5
    aux_loss_coef: float = 0.01
    capacity_factor: float = 1.25  # ep dispatch buckets (overflow drops)
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    remat_policy: str = "full"     # full | dots | dots_no_batch | selective
    fsdp_overlap: bool = False     # explicit prefetch-scheduled fsdp step

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @staticmethod
    def tiny(**kw) -> "MixtralConfig":
        """Test-scale config for the virtual CPU mesh."""
        base = dict(vocab_size=256, dim=64, n_layers=2, n_heads=8,
                    n_kv_heads=4, ffn_dim=96, n_experts=4, top_k=2,
                    rope_theta=10000.0)
        base.update(kw)
        return MixtralConfig(**base)

    @staticmethod
    def mixtral_8x7b(**kw) -> "MixtralConfig":
        base = dict(vocab_size=32000, dim=4096, n_layers=32, n_heads=32,
                    n_kv_heads=8, ffn_dim=14336, n_experts=8, top_k=2)
        base.update(kw)
        return MixtralConfig(**base)


def init_params(cfg: MixtralConfig, key: jax.Array) -> Params:
    ks = jax.random.split(key, 9)
    d, L, E, f = cfg.dim, cfg.n_layers, cfg.n_experts, cfg.ffn_dim
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    pd = cfg.param_dtype

    def dense(k, *shape, fan_in):
        return (jax.random.normal(k, shape) * (fan_in ** -0.5)).astype(pd)

    return {
        "embed": dense(ks[0], cfg.vocab_size, d, fan_in=d),
        "layers": {
            "attn_norm": jnp.ones((L, d), pd),
            "wq": dense(ks[1], L, d, hq * hd, fan_in=d),
            "wk": dense(ks[2], L, d, hkv * hd, fan_in=d),
            "wv": dense(ks[3], L, d, hkv * hd, fan_in=d),
            "wo": dense(ks[4], L, hq * hd, d, fan_in=hq * hd),
            "moe_norm": jnp.ones((L, d), pd),
            "router": dense(ks[5], L, d, E, fan_in=d),
            "w_gate": dense(ks[8], L, E, d, f, fan_in=d),
            "w_in": dense(ks[6], L, E, d, f, fan_in=d),
            "w_out": dense(ks[7], L, E, f, d, fan_in=f),
        },
        "final_norm": jnp.ones((d,), pd),
    }


def param_specs(cfg: MixtralConfig) -> Params:
    """Stacked layer dim over pp; attention matmuls over fsdp/tp exactly as
    llama; the EXPERT dim over ep (parallel/moe.py holds E/ep experts per
    device and all_to_alls tokens to them)."""
    return {
        "embed": P("tp", "fsdp"),
        "layers": {
            "attn_norm": P("pp", None),
            "wq": P("pp", "fsdp", "tp"),
            "wk": P("pp", "fsdp", "tp"),
            "wv": P("pp", "fsdp", "tp"),
            "wo": P("pp", "tp", "fsdp"),
            "moe_norm": P("pp", None),
            "router": P("pp", None, None),
            "w_gate": P("pp", "ep", "fsdp", None),
            "w_in": P("pp", "ep", "fsdp", None),
            "w_out": P("pp", "ep", None, "fsdp"),
        },
        "final_norm": P(None),
    }


def _aux_loss(router_probs: jnp.ndarray, topk_idx: jnp.ndarray,
              n_experts: int) -> jnp.ndarray:
    """Switch-transformer load-balance term: E · Σ_e f_e · P_e where f_e is
    the fraction of routed assignments to expert e and P_e the mean router
    probability — minimized when routing is uniform."""
    f = jnp.mean(
        jax.nn.one_hot(topk_idx, n_experts, dtype=jnp.float32), axis=(0, 1))
    p = jnp.mean(router_probs, axis=0)
    return n_experts * jnp.sum(f * p)


def _layer(lp: Params, x, cfg: MixtralConfig, positions, mesh):
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    B, L, d = x.shape
    cd = cfg.dtype

    h = _rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
    q = checkpoint_name(h @ lp["wq"].astype(cd), "attn_q")
    k = checkpoint_name(h @ lp["wk"].astype(cd), "attn_k")
    v = checkpoint_name(h @ lp["wv"].astype(cd), "attn_v")
    q = _rope(q.reshape(B, L, hq, hd), positions, cfg.rope_theta)
    k = _rope(k.reshape(B, L, hkv, hd), positions, cfg.rope_theta)
    v = v.reshape(B, L, hkv, hd)
    if hkv != hq:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    o = _full_attention(q, k, v).reshape(B, L, hq * hd)
    x = x + checkpoint_name(o @ lp["wo"].astype(cd), "attn_o")

    h = _rmsnorm(x, lp["moe_norm"], cfg.norm_eps)
    flat = h.reshape(B * L, d)
    moe_p = {"router": lp["router"], "w_gate": lp["w_gate"],
             "w_in": lp["w_in"], "w_out": lp["w_out"]}
    if mesh is not None and mesh.shape.get("ep", 1) > 1:
        y = moe_ffn_sharded(moe_p, flat, mesh, top_k=cfg.top_k,
                            capacity_factor=cfg.capacity_factor)
    else:
        y = moe_ffn(moe_p, flat, top_k=cfg.top_k)
    # aux term from the same routing the FFN used (dense math — tiny)
    logits = flat @ lp["router"].astype(flat.dtype)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    topk_idx, _ = _routing(moe_p, flat, cfg.top_k)
    aux = _aux_loss(probs, topk_idx, cfg.n_experts)
    # "moe_out" rides SELECTIVE_SAVE_NAMES: selective remat saves the
    # combined expert output and recomputes the dispatch in backward
    return x + checkpoint_name(y, "moe_out").reshape(B, L, d), aux


def forward(params: Params, tokens: jax.Array, cfg: MixtralConfig,
            mesh=None, return_aux: bool = False):
    """tokens [B, L] int32 → logits [B, L, vocab] fp32 (+ mean aux loss)."""
    B, L = tokens.shape
    cd = cfg.dtype
    x = params["embed"].astype(cd)[tokens]
    positions = jnp.arange(L)

    body = remat_scan_body(
        functools.partial(_layer, cfg=cfg, positions=positions, mesh=mesh),
        cfg)

    def step(x, lp):
        x, aux = body(lp, x)
        return x, aux

    x, aux = lax.scan(step, x, params["layers"])
    x = _rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bld,vd->blv", x.astype(cd),
                        params["embed"].astype(cd),
                        preferred_element_type=jnp.float32)
    if return_aux:
        return logits, jnp.mean(aux)
    return logits


def _loss_overlap(params: Params, tokens: jax.Array, cfg: MixtralConfig,
                  mesh) -> jax.Array:
    """fsdp_overlap=True loss: full-manual (dp, fsdp) shard_map with the
    prefetch-scheduled layer scan (see llama._loss_overlap). Experts run
    the dense moe_ffn path per shard, so ep must be 1 here."""
    from ray_tpu.parallel.fsdp_overlap import (drop_leading_dim,
                                               gather_params, overlap_scan,
                                               project_specs)

    for ax in ("pp", "sp", "tp", "ep"):
        if mesh.shape.get(ax, 1) > 1:
            raise ValueError(
                f"fsdp_overlap runs full-manual over (dp, fsdp); mesh axis "
                f"{ax!r} has size {mesh.shape[ax]} > 1")
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
                              mesh=None), cfg)
        x, aux = overlap_scan(params["layers"], lspecs, x, body,
                              cfg.n_layers, axis_name="fsdp", has_aux=True)
        x = _rmsnorm(x, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum("bld,vd->blv", x.astype(cd), embed.astype(cd),
                            preferred_element_type=jnp.float32)
        loss = _nll_mean(logits, tokens) + cfg.aux_loss_coef * jnp.mean(aux)
        # equal-size batch shards → pmean of shard means == global mean
        return lax.pmean(loss, ("dp", "fsdp"))

    fn = shard_map_compat(block, mesh=mesh,
                          in_specs=(specs, P(("dp", "fsdp"), None)),
                          out_specs=P())
    return fn(params, tokens)


def loss_fn(params: Params, tokens: jax.Array, cfg: MixtralConfig,
            mesh=None) -> jax.Array:
    """Next-token CE + aux load-balance term (Mixtral training objective).

    cfg.fsdp_overlap routes to the explicit prefetch-scheduled manual
    step whenever the mesh actually shards fsdp (same numerics)."""
    if cfg.fsdp_overlap and mesh is not None \
            and mesh.shape.get("fsdp", 1) > 1:
        return _loss_overlap(params, tokens, cfg, mesh)
    logits, aux = forward(params, tokens, cfg, mesh, return_aux=True)
    return _nll_mean(logits, tokens) + cfg.aux_loss_coef * aux


def num_params(cfg: MixtralConfig) -> int:
    d, L, E, f = cfg.dim, cfg.n_layers, cfg.n_experts, cfg.ffn_dim
    hd = cfg.head_dim
    per_layer = (d * cfg.n_heads * hd + 2 * d * cfg.n_kv_heads * hd
                 + cfg.n_heads * hd * d          # attention
                 + d * E                          # router
                 + 3 * E * d * f                  # gated SwiGLU experts
                 + 2 * d)                         # norms
    return cfg.vocab_size * d + L * per_layer + d


def active_params(cfg: MixtralConfig) -> int:
    """Params touched per token (top-k experts only) — the MoE efficiency
    headline (Mixtral: ~13B active of ~47B total)."""
    d, L, f = cfg.dim, cfg.n_layers, cfg.ffn_dim
    hd = cfg.head_dim
    per_layer = (d * cfg.n_heads * hd + 2 * d * cfg.n_kv_heads * hd
                 + cfg.n_heads * hd * d + d * cfg.n_experts
                 + 3 * cfg.top_k * d * f + 2 * d)
    return cfg.vocab_size * d + L * per_layer + d


def flops_per_token(cfg: MixtralConfig, seq_len: int) -> float:
    """6·N_active + attention score term (same convention as llama)."""
    attn = 12 * cfg.n_layers * cfg.dim * seq_len
    return 6.0 * active_params(cfg) + attn
