"""The plain reference for the LFM2 block (model_type "lfm2_moe"): the
decoder's forward pass in straightforward jax.numpy, float32, matmul
precision "highest"; no kernel, no page pool, no conv state carried from
chunk to chunk, no grouping of tokens by expert, nothing imported from
ray_tpu.

The layers differ. `layer_types[i]` names layer i's OPERATOR ("conv" or
"full_attention"); its FEED-FORWARD is a dense SwiGLU for the first
`num_dense_layers` layers and routed experts after. For hidden states
x [S, d] of ONE sequence, RMSNorm eps = norm_eps throughout:

    h  = x + op_i(rmsnorm(x, w_op))
    x' = h + ffn_i(rmsnorm(h, w_ffn))

  op "conv" (the gated short convolution):
    (B, C, u) = split3(z W_in)          W_in [d, 3d], no bias
    v  = B * u
    c[t] = sum_j w[j] * v[t - (K-1) + j]      depthwise, causal, K taps
           (conv_L_cache = 3), v before the sequence's first token = 0
    op = (C * c) W_out                  W_out [d, d]
  op "full_attention":
    q, k, v projections without bias; an RMSNorm over each HEAD of q and
    of k (weights [head_dim], one for all heads) before the rotary
    embedding; rotary over the whole head in the half-split pairing,
    theta = rope_parameters.rope_theta; causal softmax attention, scale
    head_dim ** -0.5, grouped queries; output projection
  ffn dense:  (silu(z W1) * (z W3)) W2
  ffn experts:
    s = sigmoid(z W_r)                  float32, W_r [d, E]
    chosen = top_k(s + b)               b [E]: the per-expert bias enters
                                        the CHOICE only (use_expert_bias)
    g = s[chosen];  g = g / (sum(g) + 1e-6)   (norm_topk_prob);
    g = g * routed_scaling_factor
    ffn = sum_j g_j * ((silu(z G_j) * (z U_j)) D_j)

After the last layer rmsnorm (the family's `embedding_norm`), then logits
from the embedding table.

Departures from, and readings of, the published description (each is in the
configuration file's `assumed` too): the head is the embedding table (the
config has no tie key; the family ties); SiLU (no `hidden_act` key); the
per-head q/k norm and the half-split rotary pairing are the family's code,
not keys of the config; the 1e-6 in the renormalisation is that code's
constant; b is drawn from the seed, not trained to balance load.

Every expert is computed densely for every token and combined with the
routing weights (zero off the top-k): T x E expert passes, which is what
the program's grouped layer must equal. It reads the program's parameter
tree because those ARE the weights under test: {"embed", "final_norm",
"layers": {"attn": {attn_norm, wq, wk, wv, wo, q_norm, k_norm}, "conv":
{conv_norm, w_in, w_conv [taps, d], w_out}, "dense": {mlp_norm, w_gate,
w_up, w_down}, "moe": {mlp_norm, router, router_bias, w_gate, w_up, w_down
[n, E, ...]}}}, each stack indexed by a layer's ordinal among the layers
of its kind. A layer (an expert) is cast to float32 at a time, so the
whole fits beside the served weights at the published widths.
"""

from __future__ import annotations

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reference import _rmsnorm, _rope   # the same two formulas

CONV, ATTENTION = "conv", "full_attention"


def dims_of(cfg) -> tuple:
    """Hashable: (heads, kv heads, head_dim, rope theta, norm eps, experts
    per token, renormalise, layer types, leading dense layers, router
    score, renormalisation epsilon, routing scale)."""
    return (int(cfg.n_heads), int(cfg.n_kv_heads), int(cfg.head_dim),
            float(cfg.rope_theta), float(cfg.norm_eps),
            int(cfg.experts_per_token), bool(cfg.norm_topk_prob),
            tuple(cfg.layer_types), int(cfg.n_dense_layers),
            str(cfg.router_score), float(cfg.router_eps),
            float(cfg.router_scale))


def short_conv(z, w_in, w_conv, w_out):
    """z [S, d] (normed) -> the operator's output [S, d]."""
    S, K = z.shape[0], w_conv.shape[0]
    B, C, u = jnp.split(z @ w_in, 3, axis=-1)
    v = jnp.pad(B * u, ((K - 1, 0), (0, 0)))        # zeros before token 0
    c = sum(w_conv[j] * v[j:j + S] for j in range(K))
    return (C * c) @ w_out


def attention(z, lp, dims):
    hq, hkv, hd, theta, eps = dims[:5]
    S = z.shape[0]
    q = (z @ lp["wq"]).reshape(S, hq, hd)
    k = (z @ lp["wk"]).reshape(S, hkv, hd)
    v = (z @ lp["wv"]).reshape(S, hkv, hd)
    if "q_norm" in lp:                              # over each head
        q = _rmsnorm(q, lp["q_norm"], eps)
        k = _rmsnorm(k, lp["k_norm"], eps)
    q, k = _rope(q, theta), _rope(k, theta)
    q = q.reshape(S, hkv, hq // hkv, hd)
    s = jnp.einsum("skgd,tkd->kgst", q, k) * (hd ** -0.5)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("kgst,tkd->skgd", p, v).reshape(S, hq * hd) @ lp["wo"]


def routing(z, router, bias, top_k: int, renorm: bool,
            score: str = "sigmoid", eps: float = 1e-6, scale: float = 1.0):
    """(weights [S, E] with zeros off the chosen, chosen experts [S, k])."""
    logits = z @ router
    s = jax.nn.sigmoid(logits) if score == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    _, e = lax.top_k(s if bias is None else s + bias, top_k)
    g = jnp.take_along_axis(s, e, axis=-1)
    if renorm:
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + eps)
    g = g * scale
    rows = jnp.arange(z.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, e].set(g), e


def _held(dtype):
    """Rounding to the precision a value is HELD in (None: float32, no
    rounding): what `forward(hold=...)` applies to weights and to the
    values that pass from one operator to the next."""
    if dtype is None:
        return lambda a: a
    return lambda a: a.astype(dtype).astype(jnp.float32)


def expert_layer(z, router, bias, gate, up, down, top_k: int, renorm: bool,
                 layer=None, hold=None, **how):
    """(y [S, d]: every expert on every token, combined by the weights;
    the experts chosen [S, k]). gate / up [E, d, f] and down [E, f, d], or
    the stacked [n, E, ...] trees with ``layer`` the entry to use: one
    expert's matrices are read out of the stack and cast at a time (a
    layer sliced out whole is a copy of its 64 experts)."""
    full, chosen = routing(z, router, bias, top_k, renorm, **how)
    if layer is None:
        gate, up, down, layer = gate[None], up[None], down[None], 0
    q = _held(hold)

    def one(acc, ew):
        e, w = ew                           # one expert, its weight column
        g, u, d = (q(a[layer, e].astype(z.dtype)) for a in (gate, up, down))
        h = q(jax.nn.silu(z @ g) * (z @ u))
        return acc + w[:, None] * (h @ d), None

    y, _ = lax.scan(one, jnp.zeros_like(z),
                    (jnp.arange(gate.shape[1]), full.T))
    return y, chosen


def forward(params, tokens, dims, hold=None):
    """tokens [S] int32 -> (logits [S, vocab] float32, the experts each
    expert layer chose [n_expert_layers, S, k]).

    ``hold`` (a dtype; None is the reference proper) rounds every weight,
    and every value that passes from one operator to the next (the
    residual stream, each normed input, the feed-forwards' inner
    product), to that precision, the arithmetic staying float32: what the
    reference gives "computed in" a lower precision, for setting and
    checking the limits of the comparison (PERF.md, PR 30). The values
    inside an operator (attention scores, the conv's taps) are not
    rounded, so it errs on the side of the lower precision."""
    (_, _, _, _, eps, top_k, renorm, layer_types, n_dense, score, r_eps,
     r_scale) = dims
    f32 = jnp.float32
    q = _held(hold)
    embed = q(params["embed"].astype(f32))
    x = embed[tokens]
    stacks = params["layers"]

    def entry(kind, i, skip=()):
        return {k: q(a[i].astype(f32)) for k, a in stacks[kind].items()
                if k not in skip}

    def normed(x, w):
        return q(_rmsnorm(x, w, eps))

    seen = {CONV: 0, ATTENTION: 0}
    chosen = []
    for i, op in enumerate(layer_types):
        if op == CONV:
            lp = entry("conv", seen[op])
            x = q(x + short_conv(normed(x, lp["conv_norm"]),
                                 lp["w_in"], lp["w_conv"], lp["w_out"]))
        else:
            lp = entry("attn", seen[op])
            x = q(x + attention(normed(x, lp["attn_norm"]), lp, dims))
        seen[op] += 1
        if i < n_dense:
            lp = entry("dense", i)
            z = normed(x, lp["mlp_norm"])
            x = q(x + q(jax.nn.silu(z @ lp["w_gate"]) * (z @ lp["w_up"]))
                  @ lp["w_down"])
        else:
            m = i - n_dense
            # the experts' matrices are cast one expert at a time
            lp = entry("moe", m, skip=("w_gate", "w_up", "w_down"))
            moe = stacks["moe"]
            y, e = expert_layer(
                normed(x, lp["mlp_norm"]), lp["router"],
                lp.get("router_bias"), moe["w_gate"], moe["w_up"],
                moe["w_down"], top_k, renorm, layer=m, hold=hold,
                score=score, eps=r_eps, scale=r_scale)
            x = q(x + y)
            chosen.append(e)
    logits = normed(x, params["final_norm"].astype(f32)) @ embed.T
    return logits, jnp.stack(chosen)


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
    forward over prompt + generated (right-padded to `pad_to`; causal in
    the attention and in the conv, so padding cannot reach back). Per
    generated position: the reference's own argmax, and the GAP between its
    top logit and its logit for the token that was served."""
    n_p, n_g = len(prompt), len(generated)
    if n_p + n_g > pad_to:
        raise ValueError(f"{n_p} + {n_g} tokens exceed pad_to {pad_to}")
    toks = np.zeros((pad_to,), np.int32)
    toks[:n_p + n_g] = prompt + generated
    # sliced on the host: a device slice of a new length is a new program
    top, gap = (np.asarray(a)[n_p - 1:n_p - 1 + n_g]
                for a in _next_token_scores(params, jnp.asarray(toks), dims))
    return {"reference_tokens": top.tolist(), "gap": gap.tolist()}
