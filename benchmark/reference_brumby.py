"""The plain reference for the Brumby block (model_type "brumby": every
layer a power-retention layer of degree 2 and a dense SwiGLU): the decoder's
forward pass with the retention in its QUADRATIC form, straightforward
jax.numpy, float32, matmul precision "highest"; explicit [queries, T]
weights a block of queries at a time, no expansion of keys or queries, no
state, no cache, no chunking of the recurrence, no batching, nothing
imported from ray_tpu. Agreement with the served recurrence therefore also
proves the program's expansion phi.

d = hidden, H query heads and G key/value heads of `head_dim`, RMSNorm eps
= rms_norm_eps throughout, no bias but the gate's. For hidden states of ONE
sequence, token t:

    h      = rmsnorm(x_t, attn_norm)
    q_j    = rope(rmsnorm(h Wq[j], q_norm))     j < H     (per head, weights
    k_g    = rope(rmsnorm(h Wk[g], k_norm))     g < G      [head_dim]; rotary
    v_g    = h Wv[g]                                       half-split, theta)
    a_g    = log sigmoid(h Wg[g] + b_g)         <= 0: the gate, one a
                                                key/value head
    w_j(t,s) = exp(a_g(s+1) + .. + a_g(t)) * (head_dim^-1/2 q_j(t) . k_g(s))^2
                                                g = j // (H / G),  s <= t
    o_j(t) = sum_s w_j(t,s) v_g(s) / (sum_s w_j(t,s) + 1e-6)
    x'     = x_t + concat_j(o_j(t)) Wo
    x''    = x' + (silu(z W_gate) * (z W_up)) W_down,  z = rmsnorm(x', mlp_norm)

then rmsnorm (final_norm) and logits from an lm_head of their own (untied).

Readings of the published description (each is in the configuration file's
`assumed` too): degree 2; the gate log-sigmoid of a projection [d, G] with a
bias, float32; q/k norm per head and rotary embedding before the power;
the scale inside the power; eps 1e-6 on the normaliser.

``forward(hold=...)`` is for the hold study (hold_brumby.py) and not the
reference proper: weights and the values between operators rounded to that
precision, and the retention computed as the RECURRENCE it equals, its
matrix state rounded to that precision after every token (a quadratic form
has no state to hold). That recurrence keeps the state under the plainest
expansion there is, phi(x) = x (x) x flattened (phi(a) . phi(b) = (a . b)^2
term by term), not the program's.

It reads the program's parameter tree because those ARE the weights under
test: {"embed", "lm_head", "final_norm", "layers": {"retention":
{attn_norm, wq, wk, wv, wo, q_norm, k_norm, w_g, b_g}, "dense": {mlp_norm,
w_gate, w_up, w_down}}}, each stack indexed by the layer. A layer's
operator, then its feed-forward in column blocks, are cast to float32 at a
time, the embedding's rows are gathered before the cast and the head is
taken in vocabulary blocks: at the published widths the whole scoring
stays under a gigabyte beside the served weights and state.
"""

from __future__ import annotations

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reference import _rmsnorm            # the same formula

EPS = 1e-6
#: queries whose [G, M, queries, T] weights exist at a time
_Q_BLOCK = 256
#: columns of the feed-forward, and rows of the head, cast at a time
_FFN_BLOCKS = 4
_VOCAB_BLOCK = 8192


def dims_of(cfg) -> tuple:
    """Hashable: (heads, kv heads, head_dim, norm eps, rope theta,
    layers)."""
    return (int(cfg.n_heads), int(cfg.n_kv_heads), int(cfg.head_dim),
            float(cfg.norm_eps), float(cfg.rope_theta), int(cfg.n_layers))


def _held(dtype):
    """Rounding to the precision a value is HELD in (None: float32, no
    rounding)."""
    if dtype is None:
        return lambda a: a
    return lambda a: a.astype(dtype).astype(jnp.float32)


def _rope(x, theta):
    """x [S, H, D]; rotate pairs (i, i + D/2) by position * theta^(-2i/D)."""
    S, _, D = x.shape
    half = D // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def retention(q, k, v, a):
    """The quadratic form: q [S, G, M, D] (scaled), k, v [S, G, D], a [S, G]
    -> o [S, G, M, D]. A block of queries against every key, masked."""
    S = q.shape[0]
    B = min(_Q_BLOCK, S)
    pad = -S % B
    cum = jnp.cumsum(a, axis=0)                     # [S, G], inclusive
    at = jnp.arange(S)

    def block(inp):
        qb, cb, tb = inp                            # [B, G, M, D] [B, G] [B]
        keep = (at[None, :] <= tb[:, None])[None]   # [1, B, S]
        decay = jnp.where(keep, jnp.exp(jnp.where(
            keep, cb.T[:, :, None] - cum.T[:, None, :], 0.0)), 0.0)
        w = jnp.einsum("tgmd,sgd->gmts", qb, k) ** 2 * decay[:, None]
        num = jnp.einsum("gmts,sgd->tgmd", w, v)
        return num / (w.sum(axis=-1).transpose(2, 0, 1)[..., None] + EPS)

    def blocks(x, fill=0):
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1),
                    constant_values=fill)
        return x.reshape((-1, B) + x.shape[1:])

    o = lax.map(block, (blocks(q), blocks(cum), blocks(at, S)))
    return o.reshape((-1,) + o.shape[2:])[:S]


def retention_recurrent(q, k, v, a, hold):
    """The same numbers token after token, the matrix state [G, D * D, D]
    (over x (x) x) rounded to ``hold`` after every token, the normaliser
    float32: the hold study's form."""
    S, G, M, D = q.shape
    keep = _held(hold)

    def flat(x):
        return (x[..., :, None] * x[..., None, :]).reshape(x.shape[:-1]
                                                           + (D * D,))

    def token(carry, inp):
        s, z = carry
        qt, kt, vt, at = inp
        g = jnp.exp(at)
        fk = flat(kt)
        s = g[:, None, None] * s + fk[..., None] * vt[:, None, :]
        z = g[:, None] * z + fk
        fq = flat(qt)                               # [G, M, D D]
        num = jnp.einsum("gme,ged->gmd", fq, s)
        den = jnp.einsum("gme,ge->gm", fq, z)
        return (keep(s), z), num / (den[..., None] + EPS)

    init = (jnp.zeros((G, D * D, D), jnp.float32),
            jnp.zeros((G, D * D), jnp.float32))
    return lax.scan(token, init, (q, k, v, a))[1]


def _entry(stack, i, keep):
    """Entry i of a stack in float32, held as ``keep`` holds (the gate's
    bias stays float32, as the program holds it)."""
    return {k: w[i].astype(jnp.float32) if k == "b_g"
            else keep(w[i].astype(jnp.float32)) for k, w in stack.items()}


@functools.partial(jax.jit, static_argnames=("dims", "hold"))
def _operator(x, stack, i, dims, hold):
    """x + the retention operator of layer i."""
    hq, hkv, hd, eps, theta = dims[:5]
    keep = _held(hold)
    lp = _entry(stack, i, keep)
    S = x.shape[0]
    h = keep(_rmsnorm(x, lp["attn_norm"], eps))
    q = (h @ lp["wq"]).reshape(S, hq, hd)
    k = (h @ lp["wk"]).reshape(S, hkv, hd)
    q = keep(_rope(keep(_rmsnorm(q, lp["q_norm"], eps)), theta))
    k = keep(_rope(keep(_rmsnorm(k, lp["k_norm"], eps)), theta))
    v = keep(h @ lp["wv"]).reshape(S, hkv, hd)
    a = jax.nn.log_sigmoid(h @ lp["w_g"] + lp["b_g"])
    q = q.reshape(S, hkv, hq // hkv, hd) * hd ** -0.5
    o = retention(q, k, v, a) if hold is None \
        else retention_recurrent(q, k, v, a, hold)
    return keep(x + keep(o).reshape(S, hq * hd) @ lp["wo"])


@functools.partial(jax.jit, static_argnames=("dims", "hold", "n"))
def _ffn_block(z, stack, i, b, n, dims, hold):
    """Column block b of n of layer i's feed-forward, on z (normed)."""
    keep = _held(hold)
    f = stack["w_gate"].shape[-1] // n

    def cols(name, axis):
        return keep(lax.dynamic_slice_in_dim(
            stack[name][i], b * f, f, axis=axis).astype(jnp.float32))

    inner = keep(jax.nn.silu(z @ cols("w_gate", 1)) * (z @ cols("w_up", 1)))
    return inner @ cols("w_down", 0)


@functools.partial(jax.jit, static_argnames=("dims", "hold"))
def _normed(x, w, dims, hold):
    return _held(hold)(_rmsnorm(x, w.astype(jnp.float32), dims[3]))


@functools.partial(jax.jit, static_argnames=("hold",))
def _embedded(embed, tokens, hold):
    return _held(hold)(embed[tokens].astype(jnp.float32))


def hidden(params, tokens, dims, hold=None):
    """tokens [S] int32 -> the final normed hidden states [S, d] float32,
    layer after layer (a Python loop over jitted pieces: the compiler sees
    one layer, and a piece of it is cast to float32 at a time)."""
    stacks = params["layers"]
    keep = _held(hold)
    x = _embedded(params["embed"], tokens, hold)
    f = stacks["dense"]["w_gate"].shape[-1]
    n = _FFN_BLOCKS if f % _FFN_BLOCKS == 0 else 1
    for i in range(dims[5]):
        x = _operator(x, stacks["retention"], i, dims, hold)
        z = _normed(x, stacks["dense"]["mlp_norm"][i], dims, hold)
        for b in range(n):
            # a piece at a time: the runtime allocates a queued piece's
            # output and temporaries when it is ENQUEUED, and left to run
            # ahead the pieces of several layers held 3 GB at once beside
            # the served weights and state (PERF.md, PR 41)
            x = x + _ffn_block(z, stacks["dense"], i, b, n, dims, hold)
            x.block_until_ready()
        x = keep(x)
    return _normed(x, params["final_norm"], dims, hold)


@functools.partial(jax.jit, static_argnames=("size", "hold"))
def _head_block(x, head, start, size, nxt, hold):
    """Rows start .. start + size of the head against x [S, d]: the block's
    top logit and its index, and each position's logit for the token
    ``nxt`` names where that token lies in the block (else -inf)."""
    rows = _held(hold)(lax.dynamic_slice_in_dim(
        head, start, size, axis=0).astype(jnp.float32))
    logits = x @ rows.T                             # [S, size]
    at = nxt - start
    inside = (at >= 0) & (at < size)
    took = jnp.take_along_axis(
        logits, jnp.clip(at, 0, size - 1)[:, None], axis=-1)[:, 0]
    return logits.max(axis=-1), start + jnp.argmax(logits, axis=-1), \
        jnp.where(inside, took, -jnp.inf)


def head_scores(params, x, nxt, hold=None):
    """Over the head in vocabulary blocks (the last one overlaps the one
    before it where the vocabulary is no whole number of blocks: harmless to
    a maximum): per position the top logit, its index, the logit of the
    token ``nxt`` names."""
    head = params["lm_head"]
    V = head.shape[0]
    size = min(_VOCAB_BLOCK, V)
    best = idx = took = None
    for b in range(-(-V // size)):
        start = min(b * size, V - size)
        m, i, t = _head_block(x, head, jnp.int32(start), size, nxt, hold)
        if best is None:
            best, idx, took = m, i, t
        else:
            idx = jnp.where(m > best, i, idx)
            best, took = jnp.maximum(best, m), jnp.maximum(took, t)
        best.block_until_ready()        # a block at a time, as in hidden()
    return best, idx, took


def forward(params, tokens, dims, hold=None):
    """tokens [S] int32 -> logits [S, vocab] float32, whole: for the tests
    and small widths (the scoring below never holds them whole)."""
    x = hidden(params, tokens, dims, hold)
    return x @ _held(hold)(params["lm_head"].astype(jnp.float32)).T


def forward_logits(params, tokens, dims) -> jax.Array:
    with jax.default_matmul_precision("highest"):
        return forward(params, tokens, dims)


def next_token_scores(params, tokens, dims, hold=None, took=None,
                      precision: str = "highest"):
    """Per position of tokens [S]: the reference's argmax for the NEXT
    token, and how far under its top logit the token ``took`` names (the
    actual next token unless given) sits. ``precision`` is the reference's
    own but in the hold study, which also rounds every matmul's operands."""
    with jax.default_matmul_precision(precision):
        x = hidden(params, tokens, dims, hold)
        best, idx, at = head_scores(
            params, x, jnp.roll(tokens, -1) if took is None else took, hold)
    return idx, best - at


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
    top, gap = (np.asarray(a)[n_p - 1:n_p - 1 + n_g] for a in
                next_token_scores(params, jnp.asarray(toks), dims, hold))
    return {"reference_tokens": top.tolist(), "gap": gap.tolist()}
