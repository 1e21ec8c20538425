"""The plain reference: the decoder's forward pass in straightforward
jax.numpy, float32, matmul precision "highest"; no kernel, no page pool, no
batching tricks, nothing imported from ray_tpu.

It follows the published description of the Mistral-7B / Llama family block
(RMSNorm -> grouped-query causal attention with rotary embedding in the
half-split layout -> residual -> RMSNorm -> SwiGLU MLP -> residual; final
RMSNorm; logits), with one departure shared by every configuration here and
listed in their files: the output head is the embedding table (the program's
models/llama.py has no untied head). It reads the program's parameter tree
({"embed", "layers": {wq, wk, wv, wo, w_gate, w_up, w_down, attn_norm,
mlp_norm} stacked on a leading layer axis, "final_norm"}) because those ARE
the weights under test; it casts each layer to float32 as it goes.
"""

from __future__ import annotations

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def dims_of(cfg) -> tuple:
    """(n_heads, n_kv_heads, head_dim, rope_theta, norm_eps): hashable."""
    return (int(cfg.n_heads), int(cfg.n_kv_heads), int(cfg.head_dim),
            float(cfg.rope_theta), float(cfg.norm_eps))


def _rmsnorm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [S, H, D]; rotate pairs (i, i + D/2) by position * theta^(-2i/D)."""
    S, _, D = x.shape
    half = D // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def forward_logits(params, tokens, dims) -> jax.Array:
    """tokens [S] int32 -> logits [S, vocab] float32."""
    hq, hkv, hd, theta, eps = dims
    f32 = jnp.float32
    S = tokens.shape[0]
    embed = params["embed"].astype(f32)
    x = embed[tokens]
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, lp):
        lp = jax.tree.map(lambda a: a.astype(f32), lp)
        h = _rmsnorm(x, lp["attn_norm"], eps)
        q = _rope((h @ lp["wq"]).reshape(S, hq, hd), theta)
        k = _rope((h @ lp["wk"]).reshape(S, hkv, hd), theta)
        v = (h @ lp["wv"]).reshape(S, hkv, hd)
        g = hq // hkv
        q = q.reshape(S, hkv, g, hd)
        s = jnp.einsum("skgd,tkd->kgst", q, k) * (hd ** -0.5)
        s = jnp.where(causal[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgst,tkd->skgd", p, v).reshape(S, hq * hd)
        x = x + o @ lp["wo"]
        h = _rmsnorm(x, lp["mlp_norm"], eps)
        x = x + (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) \
            @ lp["w_down"]
        return x, None

    x, _ = lax.scan(layer, x, params["layers"])
    return _rmsnorm(x, params["final_norm"].astype(f32), eps) @ embed.T


@functools.partial(jax.jit, static_argnames=("dims",))
def _next_token_scores(params, tokens, dims):
    """Per position of tokens [S]: the reference's argmax for the NEXT
    token, and how far under its top logit the actual next token sits. One
    program per padded length, whatever the request's own lengths are."""
    with jax.default_matmul_precision("highest"):
        logits = forward_logits(params, tokens, dims)
    nxt = jnp.roll(tokens, -1)
    took = jnp.take_along_axis(logits, nxt[:, None], axis=-1)[:, 0]
    return jnp.argmax(logits, axis=-1), logits.max(axis=-1) - took


@functools.partial(jax.jit, static_argnames=("dims",))
def mean_nll(params, batch, dims):
    """Next-token cross-entropy, mean over every row's S-1 positions, one
    row at a time (the [heads, S, S] float32 scores of one row are as much
    as a chip should be asked to hold)."""
    def row(tokens):
        logits = forward_logits(params, tokens, dims)[:-1]
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, tokens[1:, None], axis=-1).mean()
    with jax.default_matmul_precision("highest"):
        return lax.map(row, batch).mean()


def score_greedy(params, dims, prompt: List[int], generated: List[int],
                 pad_to: int) -> Dict:
    """Teacher-forced: one forward over prompt + generated (right-padded
    to `pad_to`; causal, so padding cannot reach back). Per generated
    position: the reference's own argmax, and the GAP between its top
    logit and its logit for the token that was served. 0 where they agree;
    small where bf16 rounding flipped a near-tie; whole logits for a wrong
    page, mask or position."""
    n_p, n_g = len(prompt), len(generated)
    if n_p + n_g > pad_to:
        raise ValueError(f"{n_p} + {n_g} tokens exceed pad_to {pad_to}")
    toks = np.zeros((pad_to,), np.int32)
    toks[:n_p + n_g] = prompt + generated
    # sliced on the host: a device slice of a new length is a new program
    top, gap = (np.asarray(a)[n_p - 1:n_p - 1 + n_g]
                for a in _next_token_scores(params, jnp.asarray(toks), dims))
    return {"reference_tokens": top.tolist(), "gap": gap.tolist()}
