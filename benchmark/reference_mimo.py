"""The plain reference for the MiMo-V2-Flash block (model_type
"mimo_v2_flash": full-attention and window-attention layers on different
numbers of key/value heads, a score head of 192 beside a value head of 128,
a partial rotary embedding, a value scale, a learned sink in the window
layers' softmax, a leading dense layer and routed experts of which this chip
holds a share): the decoder's forward pass in straightforward jax.numpy,
float32, matmul precision "highest"; no kernel, no page pool, no cache of
any kind, no grouping of tokens by expert, nothing imported from ray_tpu.

For hidden states x [S, d] of ONE sequence (token t, position t), RMSNorm
eps = rms_norm_eps throughout, no bias anywhere, no q/k norm. A layer's kind
is full: (G, theta, W, sink) = (num_key_value_heads, rope_theta, none,
none), or window: (swa_num_key_value_heads, swa_rope_theta, sliding_window,
s in R^H):

    h  = rms(x; attn_norm)
    q_j = h Wq[j] in R^dk, j < H;  k_g = h Wk[g] in R^dk;  v_g = h Wv[g] in
          R^dv, g < G
    q_j, k_g: the LEADING r = int(dk * partial_rotary_factor) values rotated
          at position t with the kind's theta, half-split pairs (i, i +
          r / 2); the other dk - r pass unchanged
    a_j(t, s) = q_j(t) . k_g(s) / sqrt(dk),  g = j // (H / G),
          for s <= t and t - s < W
    m_j(t) = max(max_s a_j(t, s), s_j)       (window; full: no s_j)
    p_j(t, s) = exp(a_j(t, s) - m) / (sum_s' exp(a_j(t, s') - m)
          + exp(s_j - m))       the sink takes mass and gives no value
    o_j(t) = attention_value_scale * sum_s p_j(t, s) v_g(s)
    x' = x + concat_j o_j(t) Wo
  layer 0 (moe_layer_freq[0] = 0):  x'' = x' + SwiGLU(rms(x'; mlp_norm))
  layers > 0: u = rms(x'; mlp_norm);  r = sigmoid(u Wr) in R^E;
          C = top-k of (r + b)     b enters the CHOICE only (noaux_tc)
          w_e = r_e / sum_{c in C} r_c   (norm_topk_prob; no scaling factor)
          x'' = x' + sum_{e in C, e HELD here} w_e SwiGLU^e(u)

After the last layer rmsnorm, then logits from an lm_head of its own, over
the rows of the vocabulary this chip holds.

Departures from, and readings of, the published description (each is in
the configuration file's `assumed` or `departures` too): the window counts
the token itself (t - 127 .. t); the rotary embedding turns the LEADING r
values in the half-split layout; the value scale multiplies the output
(the program scales v: the same number by linearity); the sink joins the
denominator only; the experts this chip does not hold contribute nothing
(``held`` = (first, n): the program's weights hold those n only, and so
does this reference: what it computes is the chip's share of the layer,
renormalised over ALL k chosen); the vocabulary is the held rows; the 3
multi-token-prediction layers are not served.

``fault`` names ONE part left out or done wrong, for the study that shows
the limits of `correct` can see each (hold_mimo.py): "no_sink", "no_window",
"window_off_by_one" (t - s <= W), "no_value_scale", "theta_swapped",
"rotary_all" (the rotary embedding over all dk values).

It reads the program's parameter tree because those ARE the weights under
test: {"embed", "lm_head", "final_norm", "layers": {"attn": {attn_norm, wq,
wk, wv, wo}, "attn_window": {the same, sink [n, H] float32}, "dense":
{mlp_norm, w_gate, w_up, w_down}, "moe": {mlp_norm, router [n, d, E],
router_bias [n, E], w_gate, w_up [n, held, d, f], w_down [n, held, f, d]}}}.

Computed in blocks so that 9 k tokens fit beside the served weights at the
published widths: a layer (an expert, a quarter of the dense layer's width)
is cast to float32 at a time, attention runs one query head at a time and
in blocks of queries ([block, S] scores, never [H, S, S]), and the scoring
never holds [S, vocab] logits.
"""

from __future__ import annotations

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reference import _rmsnorm            # the same formula
from benchmark.reference_kanana import _vocab_blocks
from benchmark.reference_lfm2 import _held

FAULTS = ("no_sink", "no_window", "window_off_by_one", "no_value_scale",
          "theta_swapped", "rotary_all")
#: queries a block of the attention (a block's scores are [block, S])
Q_BLOCK = 1024


def dims_of(cfg) -> tuple:
    """Hashable: (heads, full kv heads, window kv heads, score head, value
    head, rotary values, full theta, window theta, window, value scale,
    norm eps, layer kinds (True = window), experts per token, renormalise,
    leading dense layers, router score, renormalisation epsilon, routing
    scale, (first, n) held)."""
    return (int(cfg.n_heads), int(cfg.n_kv_heads), int(cfg.window_kv_heads),
            int(cfg.qk_head_dim), int(cfg.v_dim),
            int(cfg.rotary_dim or cfg.qk_head_dim), float(cfg.rope_theta),
            float(cfg.window_rope_theta), int(cfg.sliding_window),
            float(cfg.value_scale), float(cfg.norm_eps),
            tuple(t == "sliding_attention" for t in cfg.layer_types),
            int(cfg.experts_per_token), bool(cfg.norm_topk_prob),
            int(cfg.n_dense_layers), str(cfg.router_score),
            float(cfg.router_eps), float(cfg.router_scale),
            tuple(cfg.experts_held) or (0, int(cfg.n_experts)))


def rope_leading(x, theta: float, r: int):
    """x [S, H, D]: the leading r values of position s turned, pairs (i, i
    + r / 2) by the angle s * theta^(-2i/r); the rest unchanged."""
    S, half = x.shape[0], r // 2
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] \
        * theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:r]
    return jnp.concatenate(
        [a * cos - b * sin, a * sin + b * cos, x[..., r:]], axis=-1)


def attention(z, lp, dims, window: bool, fault=None):
    """z [S, d] (normed) -> the operator's output [S, d]: one query head
    and one block of queries at a time."""
    H, G_full, G_win, dk, dv, r, th_full, th_win, W, vscale = dims[:10]
    G = G_win if window else G_full
    theta = th_win if window else th_full
    if fault == "theta_swapped":
        theta = th_full if window else th_win
    if fault == "rotary_all":
        r = dk
    if fault == "no_value_scale":
        vscale = 1.0
    if fault == "window_off_by_one":
        W = W + 1
    S = z.shape[0]
    q = rope_leading((z @ lp["wq"]).reshape(S, H, dk), theta, r)
    k = rope_leading((z @ lp["wk"]).reshape(S, G, dk), theta, r)
    v = (z @ lp["wv"]).reshape(S, G, dv)
    sink = lp.get("sink") if window and fault != "no_sink" else None
    windowed = window and fault != "no_window"
    nb = -(-S // Q_BLOCK)
    qb = jnp.pad(q, ((0, nb * Q_BLOCK - S), (0, 0), (0, 0))).reshape(
        nb, Q_BLOCK, H, dk)
    s_pos = jnp.arange(S)

    def head(_, j):
        g = j // (H // G)
        kj, vj = k[:, g], v[:, g]                          # [S, dk | dv]

        def block(_, b):
            t_pos = b * Q_BLOCK + jnp.arange(Q_BLOCK)
            a = (qb[b, :, j] @ kj.T) * dk ** -0.5          # [block, S]
            ok = s_pos[None, :] <= t_pos[:, None]
            if windowed:
                ok = ok & (t_pos[:, None] - s_pos[None, :] < W)
            a = jnp.where(ok, a, -jnp.inf)
            m = a.max(axis=-1, keepdims=True)
            if sink is not None:
                m = jnp.maximum(m, sink[j])
            p = jnp.exp(a - m)
            den = p.sum(axis=-1, keepdims=True)
            if sink is not None:
                den = den + jnp.exp(sink[j] - m)
            return None, (p / den) @ vj                    # [block, dv]

        _, o = lax.scan(block, None, jnp.arange(nb))
        return None, o.reshape(nb * Q_BLOCK, dv)[:S]

    _, o = lax.scan(head, None, jnp.arange(H))              # [H, S, dv]
    o = vscale * o.transpose(1, 0, 2).reshape(S, H * dv)
    return o @ lp["wo"]


def routing(z, router, bias, top_k: int, renorm: bool, score: str,
            eps: float, scale: float):
    """(weights [S, E] float32, zero where not chosen; chosen [S, k])."""
    logits = z @ router
    s = jax.nn.sigmoid(logits) if score == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    _, chosen = lax.top_k(s if bias is None else s + bias, top_k)
    g = jnp.take_along_axis(s, chosen, axis=-1)
    if renorm:
        g = g / (g.sum(axis=-1, keepdims=True) + eps)
    g = g * scale
    full = jnp.zeros_like(s).at[jnp.arange(z.shape[0])[:, None],
                                chosen].set(g)
    return full, chosen


def held_experts(z, full, gate, up, down, layer: int, first: int, q):
    """sum over the HELD experts e of full[:, first + e] SwiGLU^e(z): every
    held expert on every token (a token that did not choose it has weight
    0), one expert's matrices cast at a time."""
    def one(acc, e):
        g, u, d = (q(a[layer, e].astype(z.dtype)) for a in (gate, up, down))
        h = q(jax.nn.silu(z @ g) * (z @ u))
        w = lax.dynamic_index_in_dim(full, first + e, axis=1)
        return acc + w * (h @ d), None

    y, _ = lax.scan(one, jnp.zeros_like(z), jnp.arange(gate.shape[1]))
    return y


def dense_ffn(z, lp_of, q, blocks: int = 4):
    """(silu(z W1) * (z W3)) W2, the width a quarter at a time
    (``lp_of(name)`` gives the raw leaf)."""
    f = lp_of("w_gate").shape[-1]
    n = f // blocks if f % blocks == 0 else f

    def part(acc, b):
        g, u = (q(lax.dynamic_slice_in_dim(lp_of(k), b * n, n, axis=1)
                  .astype(z.dtype)) for k in ("w_gate", "w_up"))
        d = q(lax.dynamic_slice_in_dim(lp_of("w_down"), b * n, n, axis=0)
              .astype(z.dtype))
        return acc + q(jax.nn.silu(z @ g) * (z @ u)) @ d, None

    y, _ = lax.scan(part, jnp.zeros_like(z), jnp.arange(f // n))
    return y


def hidden(params, tokens, dims, hold=None, fault=None):
    """tokens [S] int32 -> (the last norm's output [S, d] float32, the
    experts each expert layer chose [n_expert_layers, S, k]). ``hold``: a
    dtype the weights and the values passed between operators are rounded
    to (None is the reference proper); ``fault``: the module docstring's."""
    eps, kinds, top_k, renorm, n_dense, score, r_eps, r_scale, held = \
        dims[10:]
    f32 = jnp.float32
    q = _held(hold)
    x = q(params["embed"][tokens].astype(f32))   # gather, then cast
    stacks = params["layers"]

    def entry(kind, i, only=None):
        return {k: q(a[i].astype(f32)) for k, a in stacks[kind].items()
                if only is None or k in only}

    def normed(x, w):
        return q(_rmsnorm(x, w, eps))

    chosen, seen = [], {"attn": 0, "attn_window": 0}
    for i, window in enumerate(kinds):
        kind = "attn_window" if window else "attn"
        lp = entry(kind, seen[kind])
        seen[kind] += 1
        x = q(x + attention(normed(x, lp["attn_norm"]), lp, dims, window,
                            fault))
        if i < n_dense:
            norm = entry("dense", i, ("mlp_norm",))["mlp_norm"]
            x = q(x + dense_ffn(normed(x, norm),
                                lambda k, i=i: stacks["dense"][k][i], q))
            continue
        m = i - n_dense
        moe = stacks["moe"]
        lp = entry("moe", m, ("mlp_norm", "router", "router_bias"))
        z = normed(x, lp["mlp_norm"])
        full, e = routing(z, lp["router"], lp.get("router_bias"), top_k,
                          renorm, score, r_eps, r_scale)
        x = q(x + held_experts(z, full, moe["w_gate"], moe["w_up"],
                               moe["w_down"], m, held[0], q))
        chosen.append(e)
    out = normed(x, q(params["final_norm"].astype(f32)))
    return out, (jnp.stack(chosen) if chosen else None)


def forward(params, tokens, dims, hold=None, fault=None):
    """tokens [S] int32 -> (logits [S, vocab] float32, chosen experts):
    the whole logits, for sizes at which they fit (the tests)."""
    x, chosen = hidden(params, tokens, dims, hold, fault)
    return x @ _held(hold)(params["lm_head"].astype(jnp.float32)).T, chosen


def forward_logits(params, tokens, dims) -> jax.Array:
    with jax.default_matmul_precision("highest"):
        return forward(params, tokens, dims)[0]


@functools.partial(jax.jit, static_argnames=("dims", "hold", "precision",
                                             "fault"))
def token_scores(params, tokens, nxt, dims, hold=None, precision="highest",
                 fault=None):
    """Per position of tokens [S]: the reference's argmax for the next
    token, and how far under its top logit the token ``nxt`` [S] sits
    there. One program per padded length; reference_kanana.token_scores'
    walk of the head in blocks of vocabulary rows. ``precision`` is
    "highest" for the reference proper; hold_mimo.py asks for "bfloat16"
    beside a ``hold`` to read what computing in the stated precision
    costs."""
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


def score_greedy(params, dims, prompt: List[int], generated: List[int],
                 pad_to: int, hold=None) -> Dict:
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
