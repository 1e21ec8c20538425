"""The plain reference for the Trinity-Mini block (model_type "afmoe":
window-attention layers that rotate beside full-attention layers that carry
no position, a q/k norm over each head, a sigmoid gate on the attention
output, a norm after each branch as well as before it, leading dense
layers, then sigmoid-routed experts with a selection bias and a routing
scale beside a shared expert, the embedding scaled by sqrt(hidden)): the
decoder's forward pass in straightforward jax.numpy, float32, matmul
precision "highest"; no kernel, no page pool, no cache of any kind, no
grouping of tokens by expert, nothing imported from ray_tpu.

For hidden states x [S, d] of ONE sequence (token t, position t), RMSNorm
rms(a; w) = a * rsqrt(mean(a^2) + eps) * w with eps = rms_norm_eps
throughout, no bias anywhere. A layer's kind is window: (W, rotated) =
(sliding_window, yes), or full: (none, no):

    x_0  = E[token] * sqrt(d)                        (mup_enabled)
    h    = rms(x; attn_norm)
    q_j  = h Wq[j] in R^dk, j < H;  k_g = h Wk[g] in R^dk;  v_g = h Wv[g]
           in R^dk, g < G;  gate = sigmoid(h Wog) in R^(H dk)
    q_j  = rms(q_j; q_norm in R^dk);  k_g = rms(k_g; k_norm in R^dk)
           (one weight for all heads of a layer)
    window kind only: q_j, k_g rotated at position t, theta = rope_theta,
           the whole head, half-split pairs (i, i + dk / 2)
    a_j(t, s) = q_j(t) . k_g(s) / sqrt(dk),  g = j // (H / G),
           for s <= t and t - s < W
    o_j(t) = sum_s softmax_s(a_j(t, .)) v_g(s)          (no sink)
    y    = (concat_j o_j(t) * gate) Wo
    x'   = x + rms(y; attn_post_norm)
    u    = rms(x'; mlp_norm)
  layers < num_dense_layers:  z = SwiGLU(u), width intermediate_size
  the rest:  r = sigmoid(u Wr) in R^E;  C = top-k of (r + b)
           w_e = route_scale * r_e / (sum_{c in C} r_c + 1e-20)
           z = SwiGLU^shared(u) + sum_{e in C} w_e SwiGLU^e(u)
    x''  = x' + rms(z; mlp_post_norm)

After the last layer rmsnorm, then logits from an lm_head of its own.

Departures from, and readings of, the published description (each is in
the configuration file's `assumed` or `departures` too). The row's config
carries the sizes, the routing and the multipliers; FOUR things are the
family's published modelling code and no key, written here as ISSUE 49's
author knows them: the sigmoid gate on the attention output (from the
layer's normed input, one value a value of every head's output, before
wo), the q/k norm over each head, the rotary embedding on the window layers
ONLY, and the four norms a layer. Each is a field of `dims_of` (and of the
program's configuration), so a correction is one value. Further: the window
counts the token itself (t - W + 1 .. t); the rotary pairs are half-split;
the selection bias b enters the CHOICE only and is seeded, not zero; the
scale is on the routed weights and not on the shared expert.

``fault`` names ONE part left out or done wrong, for the study that shows
the limits of `correct` can see each (hold_trinity.py): FAULTS below.

It reads the program's parameter tree because those ARE the weights under
test: {"embed", "lm_head", "final_norm", "layers": {"attn" and
"attn_window": {attn_norm, wq, wk, wv, wo, q_norm, k_norm, w_og,
attn_post_norm}, "dense": {mlp_norm, w_gate, w_up, w_down, mlp_post_norm},
"moe": {mlp_norm, router [n, d, E], router_bias [n, E], w_gate, w_up [n, E,
d, f], w_down [n, E, f, d], w_shared_gate, w_shared_up, w_shared_down,
mlp_post_norm}}}.

Computed in blocks so that 12 k tokens fit beside the served weights at the
published widths: a layer (an expert, a quarter of the dense layer's width)
is cast to float32 at a time, attention runs one query head at a time and
in blocks of queries ([block, S] scores, never [H, S, S]), and the scoring
never holds [S, vocab] logits (200192 rows: sixteen blocks of 12512).
"""

from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reference import _rmsnorm            # the same formula
from benchmark.reference_kanana import _vocab_blocks
from benchmark.reference_lfm2 import _held
from benchmark.reference_mimo import (dense_ffn, held_experts, rope_leading,
                                      routing)

FAULTS = ("no_gate", "no_qk_norm", "full_rotated", "window_not_rotated",
          "no_window", "window_off_by_one", "no_post_norm", "route_scale_1",
          "no_shared", "embed_scale_1")
#: queries a block of the attention (a block's scores are [block, S])
Q_BLOCK = 1024


class Dims(NamedTuple):
    """What the forward needs beside the weights (hashable: a static
    argument). The four items the published config has no key for are
    fields here: gate, qk_norm, post_norms, full_rope."""
    heads: int
    kv_heads: int
    window_kv_heads: int
    head: int                      # a q / k / v head's width
    theta: float                   # the window layers' rotary base
    window: int
    eps: float
    kinds: Tuple[bool, ...]        # per layer: True = window
    top_k: int
    renorm: bool
    n_dense: int
    score: str
    router_eps: float
    router_scale: float
    embed_scale: float
    gate: bool
    qk_norm: bool
    post_norms: bool
    full_rope: bool


def dims_of(cfg) -> Dims:
    """From the program's configuration (a LlamaConfig)."""
    return Dims(
        int(cfg.n_heads), int(cfg.n_kv_heads), int(cfg.window_kv_heads),
        int(cfg.qk_head_dim), float(cfg.window_rope_theta),
        int(cfg.sliding_window), float(cfg.norm_eps),
        tuple(t == "sliding_attention" for t in cfg.layer_types),
        int(cfg.experts_per_token), bool(cfg.norm_topk_prob),
        int(cfg.n_dense_layers), str(cfg.router_score),
        float(cfg.router_eps), float(cfg.router_scale),
        float(cfg.embed_scale), bool(cfg.attn_gate),
        bool(cfg.qk_norm_per_head), bool(cfg.post_norms),
        bool(cfg.full_rope))


def attention(z, lp, dims: Dims, window: bool, fault=None):
    """z [S, d] (normed) -> the operator's output [S, d] before the norm
    after the branch: one query head and one block of queries at a time."""
    H, dk, W = dims.heads, dims.head, dims.window
    G = dims.window_kv_heads if window else dims.kv_heads
    rotated = window or dims.full_rope
    if fault == "full_rotated" and not window:
        rotated = True
    if fault == "window_not_rotated" and window:
        rotated = False
    if fault == "window_off_by_one":
        W = W + 1
    S = z.shape[0]
    q = (z @ lp["wq"]).reshape(S, H, dk)
    k = (z @ lp["wk"]).reshape(S, G, dk)
    v = (z @ lp["wv"]).reshape(S, G, dk)
    if dims.qk_norm and fault != "no_qk_norm":
        q = _rmsnorm(q, lp["q_norm"], dims.eps)
        k = _rmsnorm(k, lp["k_norm"], dims.eps)
    if rotated:
        q, k = (rope_leading(a, dims.theta, dk) for a in (q, k))
    windowed = window and fault != "no_window"
    nb = -(-S // Q_BLOCK)
    qb = jnp.pad(q, ((0, nb * Q_BLOCK - S), (0, 0), (0, 0))).reshape(
        nb, Q_BLOCK, H, dk)
    s_pos = jnp.arange(S)

    def head(_, j):
        g = j // (H // G)
        kj, vj = k[:, g], v[:, g]                          # [S, dk]

        def block(_, b):
            t_pos = b * Q_BLOCK + jnp.arange(Q_BLOCK)
            a = (qb[b, :, j] @ kj.T) * dk ** -0.5          # [block, S]
            ok = s_pos[None, :] <= t_pos[:, None]
            if windowed:
                ok = ok & (t_pos[:, None] - s_pos[None, :] < W)
            a = jnp.where(ok, a, -jnp.inf)
            p = jnp.exp(a - a.max(axis=-1, keepdims=True))
            return None, (p / p.sum(axis=-1, keepdims=True)) @ vj

        _, o = lax.scan(block, None, jnp.arange(nb))
        return None, o.reshape(nb * Q_BLOCK, dk)[:S]

    _, o = lax.scan(head, None, jnp.arange(H))              # [H, S, dk]
    o = o.transpose(1, 0, 2).reshape(S, H * dk)
    if dims.gate and fault != "no_gate":
        o = o * jax.nn.sigmoid(z @ lp["w_og"])
    return o @ lp["wo"]


def swiglu(z, gate, up, down, q):
    return q(jax.nn.silu(z @ gate) * (z @ up)) @ down


def hidden(params, tokens, dims: Dims, hold=None, fault=None):
    """tokens [S] int32 -> (the last norm's output [S, d] float32, the
    experts each expert layer chose [n_expert_layers, S, k]). ``hold``: a
    dtype the weights and the values passed between operators are rounded
    to (None is the reference proper); ``fault``: the module docstring's."""
    f32 = jnp.float32
    q = _held(hold)
    scale = 1.0 if fault == "embed_scale_1" else dims.embed_scale
    x = q(params["embed"][tokens].astype(f32) * scale)   # gather, then cast
    stacks = params["layers"]
    post = dims.post_norms and fault != "no_post_norm"

    def entry(kind, i, only=None):
        return {k: q(a[i].astype(f32)) for k, a in stacks[kind].items()
                if only is None or k in only}

    def normed(x, w):
        return q(_rmsnorm(x, w, dims.eps))

    def joined(x, y, lp, name):
        """The branch's output through its own norm, where the block has
        one, then into the stream."""
        return q(x + (normed(y, lp[name]) if post else y))

    chosen, seen = [], {"attn": 0, "attn_window": 0}
    for i, window in enumerate(dims.kinds):
        kind = "attn_window" if window else "attn"
        lp = entry(kind, seen[kind])
        seen[kind] += 1
        x = joined(x, attention(normed(x, lp["attn_norm"]), lp, dims,
                                window, fault), lp, "attn_post_norm")
        small = ("mlp_norm", "mlp_post_norm", "router", "router_bias",
                 "w_shared_gate", "w_shared_up", "w_shared_down")
        if i < dims.n_dense:
            lp = entry("dense", i, small)
            z = dense_ffn(normed(x, lp["mlp_norm"]),
                          lambda k, i=i: stacks["dense"][k][i], q)
            x = joined(x, z, lp, "mlp_post_norm")
            continue
        m = i - dims.n_dense
        moe = stacks["moe"]
        lp = entry("moe", m, small)
        u = normed(x, lp["mlp_norm"])
        full, e = routing(
            u, lp["router"], lp.get("router_bias"), dims.top_k, dims.renorm,
            dims.score, dims.router_eps,
            1.0 if fault == "route_scale_1" else dims.router_scale)
        z = held_experts(u, full, moe["w_gate"], moe["w_up"], moe["w_down"],
                         m, 0, q)
        if "w_shared_gate" in lp and fault != "no_shared":
            # the expert every token takes: no gate of its own, no scale
            z = z + swiglu(u, lp["w_shared_gate"], lp["w_shared_up"],
                           lp["w_shared_down"], q)
        x = joined(x, z, lp, "mlp_post_norm")
        chosen.append(e)
    out = normed(x, q(params["final_norm"].astype(f32)))
    return out, (jnp.stack(chosen) if chosen else None)


def forward(params, tokens, dims: Dims, hold=None, fault=None):
    """tokens [S] int32 -> (logits [S, vocab] float32, chosen experts):
    the whole logits, for sizes at which they fit (the tests)."""
    x, chosen = hidden(params, tokens, dims, hold, fault)
    return x @ _held(hold)(params["lm_head"].astype(jnp.float32)).T, chosen


def forward_logits(params, tokens, dims: Dims) -> jax.Array:
    with jax.default_matmul_precision("highest"):
        return forward(params, tokens, dims)[0]


@functools.partial(jax.jit, static_argnames=("dims", "hold", "precision",
                                             "fault"))
def token_scores(params, tokens, nxt, dims: Dims, hold=None,
                 precision="highest", fault=None):
    """Per position of tokens [S]: the reference's argmax for the next
    token, and how far under its top logit the token ``nxt`` [S] sits
    there. One program per padded length; reference_kanana.token_scores'
    walk of the head in blocks of vocabulary rows. ``precision`` is
    "highest" for the reference proper; hold_trinity.py asks for
    "bfloat16" beside a ``hold`` to read what computing in the stated
    precision costs."""
    with jax.default_matmul_precision(precision):
        x, _ = hidden(params, tokens, dims, hold, fault)
        head = params["lm_head"]
        V = head.shape[0]
        nb = _vocab_blocks(V)
        q = _held(hold)

        def block(carry, b):
            best, arg, took = carry
            n = V // nb
            rows = q(lax.dynamic_slice_in_dim(head, b * n, n)
                     .astype(jnp.float32))
            logits = x @ rows.T                             # [S, V / nb]
            top, at = logits.max(axis=-1), jnp.argmax(logits, axis=-1)
            local = nxt - b * n
            got = jnp.take_along_axis(
                logits, jnp.clip(local, 0, n - 1)[:, None], axis=-1)[:, 0]
            better = top > best
            return (jnp.where(better, top, best),
                    jnp.where(better, at + b * n, arg),
                    jnp.where((local >= 0) & (local < n), got, took)), None

        S = tokens.shape[0]
        (best, arg, took), _ = lax.scan(
            block, (jnp.full((S,), -jnp.inf), jnp.zeros((S,), jnp.int32),
                    jnp.zeros((S,))), jnp.arange(nb))
    return arg, best - took


def score_greedy(params, dims: Dims, prompt: List[int],
                 generated: List[int], pad_to: int, hold=None) -> Dict:
    """Teacher-forced, the contract of reference.py's score_greedy: one
    forward over prompt + generated (right-padded to `pad_to`; causal, so
    padding cannot reach back). Per generated position: the reference's own
    argmax, and the GAP between its top logit and its logit for the token
    that was served."""
    n_p, n_g = len(prompt), len(generated)
    if n_p + n_g > pad_to:
        raise ValueError(f"{n_p} + {n_g} tokens exceed pad_to {pad_to}")
    toks = np.zeros((pad_to,), np.int32)
    toks[:n_p + n_g] = prompt + generated
    # sliced on the host: a device slice of a new length is a new program
    toks = jnp.asarray(toks)
    top, gap = (np.asarray(a)[n_p - 1:n_p - 1 + n_g]
                for a in token_scores(params, toks, jnp.roll(toks, -1), dims,
                                      hold))
    return {"reference_tokens": top.tolist(), "gap": gap.tolist()}
