"""The plain reference for the granite-4.0-h block (model_type
"granitemoehybrid" with no routed experts): the decoder's forward pass in
its published SEQUENTIAL form, straightforward jax.numpy, float32, matmul
precision "highest"; no kernel, no page pool, no state carried from chunk to
chunk, no chunked scan, no batching, nothing imported from ray_tpu.

`layer_types[i]` names layer i's operator ("mamba" or "full_attention": the
published file says "attention"). d = hidden, RMSNorm eps = rms_norm_eps
throughout, no bias in any projection. For hidden states of ONE sequence:

    x_0 = embedding_multiplier * embed[token]
    h   = x + residual_multiplier * op_i(rmsnorm(x, w_op))
    x'  = h + residual_multiplier * mlp(rmsnorm(h, w_mlp))
    logits = (rmsnorm(x_L, w_final) embed^T) / logits_scaling

  mlp: (silu(z W_gate) * (z W_up)) W_down   (the family's shared MLP, the
    whole feed-forward when num_local_experts is 0; published as ONE input
    matrix [d, 2 f] split in two, held here as its halves)
  op "full_attention": q, k, v projections; NO positional embedding
    (position_embedding_type "nope"); score = (q . k) * attention_multiplier
    (not head_dim ** -0.5); causal softmax, grouped queries; output
    projection. No q/k norm.
  op "mamba" (Mamba-2: H heads of P, state N, one group, ch = H P + 2 N):
    [g (H P), u (ch), dt_raw (H)] = split(z W_in)   (published as ONE
           matrix, held here as its column groups w_gate, w_xbc, w_dt)
    c[t] = silu(b + sum_j w[j] * u[t - (K-1) + j])   depthwise, causal, K
           taps (mamba_d_conv), u before the sequence's first token = 0
    [xs (H, P), B (N), C (N)] = split(c[t]);  B, C shared by all heads
    dt_h = softplus(dt_raw_h + dt_bias_h);  A_h = -exp(A_log_h)
    S_h[t] = exp(dt_h A_h) S_h[t-1] + dt_h xs_h[t] B[t]^T,  S_h[-1] = 0
    y_h[t] = S_h[t] C[t] + D_h xs_h[t]
    op = rmsnorm(concat_h(y_h) * silu(g), w_gate_norm) W_out
    token after token (`lax.scan`): the recurrence as it is published, not
    the chunked form the program's chunk rows compute.

Departures from, and readings of, the published description (each is in the
configuration file's `assumed` too): head_dim 64 = hidden / heads; the gate
is applied BEFORE the norm, which is over all H P values as one group; the
split order of W_in (gate, conv input, dt) and of the conv's channels (x, B,
C); SiLU; A_log, dt_bias, D, the taps and their bias are float32.

It reads the program's parameter tree because those ARE the weights under
test: {"embed", "final_norm", "layers": {"attn": {attn_norm, wq, wk, wv,
wo}, "mamba": {mamba_norm, w_gate, w_xbc, w_dt, w_conv [taps, ch], b_conv,
dt_bias, A_log, D, gate_norm, w_out}, "dense": {mlp_norm, w_gate, w_up, w_down}}}, each
stack indexed by a layer's ordinal among the layers of its kind. A layer is
cast to float32 at a time, so the whole fits beside the served weights at
the published widths.
"""

from __future__ import annotations

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reference import _rmsnorm            # the same formula

MAMBA, ATTENTION = "mamba", "full_attention"


def dims_of(cfg) -> tuple:
    """Hashable: (heads, kv heads, head_dim, norm eps, layer types, state
    heads, their width, state size, embedding multiplier, residual
    multiplier, attention multiplier, logits scaling)."""
    return (int(cfg.n_heads), int(cfg.n_kv_heads), int(cfg.head_dim),
            float(cfg.norm_eps), tuple(cfg.layer_types), int(cfg.ssm_heads),
            int(cfg.ssm_head_dim), int(cfg.ssm_state),
            float(cfg.embed_scale), float(cfg.residual_scale),
            float(cfg.attn_scale), float(cfg.logits_divisor))


def attention(z, lp, dims):
    """z [S, d] (normed) -> the operator's output [S, d]: no rotary
    embedding, the score scaled by the attention multiplier."""
    hq, hkv, hd = dims[:3]
    S = z.shape[0]
    q = (z @ lp["wq"]).reshape(S, hkv, hq // hkv, hd)
    k = (z @ lp["wk"]).reshape(S, hkv, hd)
    v = (z @ lp["wv"]).reshape(S, hkv, hd)
    s = jnp.einsum("skgd,tkd->kgst", q, k) * dims[10]
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("kgst,tkd->skgd", p, v).reshape(S, hq * hd) @ lp["wo"]


def _held(dtype):
    """Rounding to the precision a value is HELD in (None: float32, no
    rounding): what `forward(hold=...)` applies to weights, to the values
    that pass from one operator to the next, and to what the block caches
    (the conv's input and the state, token after token)."""
    if dtype is None:
        return lambda a: a
    return lambda a: a.astype(dtype).astype(jnp.float32)


def mamba(z, lp, dims, hold=None):
    """z [S, d] (normed) -> the operator's output [S, d]."""
    H, P, N = dims[5:8]
    S, K = z.shape[0], lp["w_conv"].shape[0]
    di = H * P
    q = _held(hold)
    g, u, dt = z @ lp["w_gate"], z @ lp["w_xbc"], z @ lp["w_dt"]
    u = jnp.pad(q(u), ((K - 1, 0), (0, 0)))         # zeros before token 0
    c = jax.nn.silu(lp["b_conv"] + sum(lp["w_conv"][j] * u[j:j + S]
                                       for j in range(K)))
    xs, B, C = jnp.split(c, [di, di + N], axis=-1)
    xs = xs.reshape(S, H, P)
    dt = jax.nn.softplus(dt + lp["dt_bias"])        # [S, H]
    A = -jnp.exp(lp["A_log"])

    def token(state, inp):
        x, dt, b, c = inp                           # [H, P] [H] [N] [N]
        state = jnp.exp(dt * A)[:, None, None] * state \
            + (dt[:, None] * x)[:, :, None] * b[None, None, :]
        y = state @ c + lp["D"][:, None] * x
        return q(state), y

    _, y = lax.scan(token, jnp.zeros((H, P, N), jnp.float32),
                    (xs, dt, B, C))
    y = y.reshape(S, di) * jax.nn.silu(g)           # the gate, then the norm
    return _rmsnorm(q(y), lp["gate_norm"], dims[3]) @ lp["w_out"]


_KEEP = ("w_conv", "b_conv", "dt_bias", "A_log", "D")


def _entry(stack, i, q):
    """Entry i of a kind's stack in float32, held as ``q`` holds."""
    return {k: a[i].astype(jnp.float32) if k in _KEEP
            else q(a[i].astype(jnp.float32)) for k, a in stack.items()}


@functools.partial(jax.jit, static_argnames=("op", "dims", "hold"))
def _layer(x, op_stack, dense_stack, j, i, op, dims, hold):
    """Layer i of one sequence: its operator (entry j of its kind's stack),
    then its feed-forward (entry i of the dense stack). One program a KIND
    of operator, whatever the depth: j and i are arguments."""
    eps, res_scale = dims[3], dims[9]
    q = _held(hold)

    def normed(x, w):
        return q(_rmsnorm(x, w, eps))

    lp = _entry(op_stack, j, q)
    if op == MAMBA:
        y = mamba(normed(x, lp["mamba_norm"]), lp, dims, hold)
    else:
        y = attention(normed(x, lp["attn_norm"]), lp, dims)
    x = q(x + res_scale * y)
    lp = _entry(dense_stack, i, q)
    z = normed(x, lp["mlp_norm"])
    return q(x + res_scale * (
        q(jax.nn.silu(z @ lp["w_gate"]) * (z @ lp["w_up"])) @ lp["w_down"]))


@functools.partial(jax.jit, static_argnames=("dims", "hold"))
def _embedded(embed, tokens, dims, hold):
    q = _held(hold)
    return q(dims[8] * q(embed.astype(jnp.float32))[tokens])


@functools.partial(jax.jit, static_argnames=("dims", "hold"))
def _logits(x, final_norm, embed, dims, hold):
    q = _held(hold)
    x = q(_rmsnorm(x, final_norm.astype(jnp.float32), dims[3]))
    return x @ q(embed.astype(jnp.float32)).T / dims[11]


def forward(params, tokens, dims, hold=None):
    """tokens [S] int32 -> logits [S, vocab] float32, layer after layer (a
    Python loop over ``_layer``: the compiler sees one layer of a kind, not
    forty, and a layer is cast to float32 at a time).

    ``hold`` (a dtype; None is the reference proper) rounds every weight
    that is held in the model's dtype, every value that passes from one
    operator to the next (the residual stream, each normed input, the
    feed-forward's inner product, the gated read-out) and what the block
    caches, to that precision, the arithmetic staying float32: what the
    reference gives "computed in" a lower precision, for setting and
    checking the limits of the comparison (PERF.md, PR 37). A_log, dt_bias,
    D and the conv's taps and bias stay float32, as the program holds
    them."""
    stacks = params["layers"]
    x = _embedded(params["embed"], tokens, dims, hold)
    seen = {MAMBA: 0, ATTENTION: 0}
    for i, op in enumerate(dims[4]):
        x = _layer(x, stacks["mamba" if op == MAMBA else "attn"],
                   stacks["dense"], seen[op], i, op, dims, hold)
        seen[op] += 1
    return _logits(x, params["final_norm"], params["embed"], dims, hold)


def forward_logits(params, tokens, dims) -> jax.Array:
    return forward(params, tokens, dims)


@jax.jit
def _scored(logits, tokens):
    nxt = jnp.roll(tokens, -1)
    took = jnp.take_along_axis(logits, nxt[:, None], axis=-1)[:, 0]
    return jnp.argmax(logits, axis=-1), logits.max(axis=-1) - took


def _next_token_scores(params, tokens, dims, hold=None):
    """Per position of tokens [S]: the reference's argmax for the NEXT
    token, and how far under its top logit the actual next token sits. One
    set of programs per padded length, whatever the request's own lengths
    are."""
    with jax.default_matmul_precision("highest"):
        return _scored(forward(params, tokens, dims, hold), tokens)


def score_greedy(params, dims, prompt: List[int], generated: List[int],
                 pad_to: int, hold=None) -> Dict:
    """Teacher-forced, the contract of reference.py's score_greedy: one
    forward over prompt + generated (right-padded to `pad_to`; causal in
    the attention, in the conv and in the recurrence, so padding cannot
    reach back). Per generated position: the reference's own argmax, and
    the GAP between its top logit and its logit for the token that was
    served."""
    n_p, n_g = len(prompt), len(generated)
    if n_p + n_g > pad_to:
        raise ValueError(f"{n_p} + {n_g} tokens exceed pad_to {pad_to}")
    toks = np.zeros((pad_to,), np.int32)
    toks[:n_p + n_g] = prompt + generated
    # sliced on the host: a device slice of a new length is a new program
    top, gap = (np.asarray(a)[n_p - 1:n_p - 1 + n_g] for a in
                _next_token_scores(params, jnp.asarray(toks), dims, hold))
    return {"reference_tokens": top.tolist(), "gap": gap.tolist()}
