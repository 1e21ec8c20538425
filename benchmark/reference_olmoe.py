"""The plain reference for the OLMoE block: the decoder's forward pass in
straightforward jax.numpy, float32, matmul precision "highest"; no kernel,
no page pool, no grouping of tokens by expert, nothing imported from
ray_tpu.

The layer, as published (HF transformers `modeling_olmoe.py`, model_type
"olmoe"; allenai/OLMoE-1B-7B-0125-Instruct). For hidden states x [S, d]:

    u  = rmsnorm(x, w_in)
    q  = rmsnorm(u Wq, w_qn)      k = rmsnorm(u Wk, w_kn)     v = u Wv
         each norm over the WHOLE projected vector, before the split into
         heads (weights [heads * head_dim]); no clipping
    q, k split into heads of head_dim; rotary embedding in the half-split
         layout, theta = rope_theta, on q and k
    o  = causal softmax attention, scale head_dim ** -0.5
    h  = x + o Wo
    m  = rmsnorm(h, w_post)
    p  = softmax(m Wr) over the experts, float32
    (w, e) = top_k(p), NOT renormalised (norm_topk_prob false; true
         divides w by its sum)
    y  = sum_j w_j * ((silu(m G_e_j) * (m U_e_j)) D_e_j)
    x' = h + y

After the last layer rmsnorm, then logits = x W_head^T with W_head a matrix
of its own (tie_word_embeddings false; true takes the embedding table). All
norms eps = rms_norm_eps. No shared expert, no bias, no capacity, no dropped
token. Every switch is written so that the Llama/Mistral block falls out
with all of them off, which the tier-1 tests use.

Here every expert is computed densely for every token and combined with
the top-k weights (zero elsewhere): T x E expert passes, which is what the
program's grouped layer must equal. It reads the program's parameter tree
({"embed", "lm_head", "layers": {attn_norm, wq, wk, wv, wo, q_norm, k_norm,
mlp_norm, router, w_gate, w_up, w_down [L, E, ...]}, "final_norm"}) because
those ARE the weights under test, and casts a layer (an expert) at a time.
"""

from __future__ import annotations

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reference import _rmsnorm, _rope   # the same two formulas

EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def dims_of(cfg) -> tuple:
    """(n_heads, n_kv_heads, head_dim, rope_theta, norm_eps, experts per
    token, renormalise): hashable. Whether there are experts, a q/k norm
    or a head of its own is read from the parameter tree."""
    return (int(cfg.n_heads), int(cfg.n_kv_heads), int(cfg.head_dim),
            float(cfg.rope_theta), float(cfg.norm_eps),
            int(cfg.experts_per_token), bool(cfg.norm_topk_prob))


def routing(m, router, top_k: int, renorm: bool):
    """(weights [S, E] with zeros off the top-k, chosen experts [S, k])."""
    p = jax.nn.softmax(m @ router, axis=-1)
    w, e = lax.top_k(p, top_k)
    if renorm:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    rows = jnp.arange(m.shape[0])[:, None]
    return jnp.zeros_like(p).at[rows, e].set(w), e


def expert_layer(m, router, gate, up, down, top_k: int, renorm: bool):
    """(y [S, d]: every expert on every token, combined by the weights;
    the experts chosen [S, k])."""
    full, chosen = routing(m, router, top_k, renorm)

    def one(acc, ew):
        *gud, w = ew                        # one expert, its weight column
        g, u, d = (a.astype(m.dtype) for a in gud)
        return acc + w[:, None] * ((jax.nn.silu(m @ g) * (m @ u)) @ d), None

    y, _ = lax.scan(one, jnp.zeros_like(m), (gate, up, down, full.T))
    return y, chosen


def forward(params, tokens, dims):
    """tokens [S] int32 -> (logits [S, vocab] float32, the experts each
    layer chose [L, S, k], or None for a dense block)."""
    hq, hkv, hd, theta, eps, top_k, renorm = dims
    f32 = jnp.float32
    S = tokens.shape[0]
    x = params["embed"].astype(f32)[tokens]
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, lp):
        # the experts' matrices are cast one expert at a time, below
        lp = {k: a if "router" in lp and k in EXPERT_LEAVES
              else a.astype(f32) for k, a in lp.items()}
        u = _rmsnorm(x, lp["attn_norm"], eps)
        q, k, v = u @ lp["wq"], u @ lp["wk"], u @ lp["wv"]
        if "q_norm" in lp:
            q = _rmsnorm(q, lp["q_norm"], eps)
            k = _rmsnorm(k, lp["k_norm"], eps)
        q = _rope(q.reshape(S, hq, hd), theta)
        k = _rope(k.reshape(S, hkv, hd), theta)
        v = v.reshape(S, hkv, hd)
        q = q.reshape(S, hkv, hq // hkv, hd)
        s = jnp.einsum("skgd,tkd->kgst", q, k) * (hd ** -0.5)
        s = jnp.where(causal[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgst,tkd->skgd", p, v).reshape(S, hq * hd)
        h = x + o @ lp["wo"]
        m = _rmsnorm(h, lp["mlp_norm"], eps)
        if "router" in lp:
            y, chosen = expert_layer(m, lp["router"], lp["w_gate"],
                                     lp["w_up"], lp["w_down"], top_k, renorm)
        else:
            y = (jax.nn.silu(m @ lp["w_gate"]) * (m @ lp["w_up"])) \
                @ lp["w_down"]
            chosen = None
        return h + y, chosen

    x, chosen = lax.scan(layer, x, params["layers"])
    head = params["lm_head"] if "lm_head" in params else params["embed"]
    return _rmsnorm(x, params["final_norm"].astype(f32), eps) \
        @ head.astype(f32).T, chosen


def forward_logits(params, tokens, dims) -> jax.Array:
    return forward(params, tokens, dims)[0]


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


def score_greedy(params, dims, prompt: List[int], generated: List[int],
                 pad_to: int) -> Dict:
    """Teacher-forced, the contract of reference.py's score_greedy: one
    forward over prompt + generated (right-padded to `pad_to`; causal, so
    padding cannot reach back). Per generated position: the reference's
    own argmax, and the GAP between its top logit and its logit for the
    token that was served."""
    n_p, n_g = len(prompt), len(generated)
    if n_p + n_g > pad_to:
        raise ValueError(f"{n_p} + {n_g} tokens exceed pad_to {pad_to}")
    toks = np.zeros((pad_to,), np.int32)
    toks[:n_p + n_g] = prompt + generated
    # sliced on the host: a device slice of a new length is a new program
    top, gap = (np.asarray(a)[n_p - 1:n_p - 1 + n_g]
                for a in _next_token_scores(params, jnp.asarray(toks), dims))
    return {"reference_tokens": top.tolist(), "gap": gap.tolist()}
