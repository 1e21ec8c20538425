"""The plain reference for the Phi-4-mini-flash block (model_type
"phi4flash": a decoder-hybrid-decoder, SambaY, arXiv:2507.06607): the
decoder's forward pass as it is published, straightforward jax.numpy,
float32, matmul precision "highest"; no kernel, no page pool, no state
carried from chunk to chunk, no batching, nothing imported from ray_tpu.

`layer_types[i]` names layer i's mixer (the program's names: the published
file names none; `mb_per_layer` 2 makes every even layer state-space and the
second half of the depth the cross-decoder). d = hidden, LN = LayerNorm with
weight and bias, eps = layer_norm_eps; no positional embedding anywhere. For
the hidden states of ONE sequence:

    x_0 = embed[token]
    x'  = x + mixer_i(LN_1(x));   x'' = x' + W_down(silu(g) * u),
    [g | u] = LN_2(x') [W_gate | W_up];   logits = LN_f(x_L) embed^T

  "mamba1" (Mamba-1: C = expand * d channels, state N, dt of rank R, K taps):
    [u | z] = h [W_x | W_z]
    xs[t] = silu(b + sum_j w[j] * u[t - (K-1) + j])   depthwise, causal,
            u before the sequence's first token = 0
    [delta (R) | B (N) | C (N)] = xs W_xproj
    dt = softplus(delta W_dt + b_dt) [C];  A = -exp(A_log) [C, N]
    S_t[c, n] = exp(dt_t[c] A[c, n]) S_{t-1}[c, n] + dt_t[c] xs_t[c] B_t[n]
    m_t[c] = sum_n S_t[c, n] C_t[n] + D[c] xs_t[c],   S_{-1} = 0
    mixer = (m_t * silu(z_t)) W_out, token after token (`lax.scan`).
    m, AFTER the D skip and BEFORE the gate, is the MEMORY a later "gmu"
    layer reads: the newest mamba1 layer's before it (layer 16's, in the
    published depth).
  "gmu" (a gated memory unit): mixer = (m_t * silu(h_t W_in)) W_out.
  "full_attention" / "sliding_attention" (DIFFERENTIAL attention; heads dh
    wide; query heads 2j, 2j+1 are pair j's q1, q2; key/value heads
    likewise; query pair j on key/value pair j // (query pairs a key/value
    pair)):
    [q | k | v] = h [W_q | W_k | W_v] + b
    a_i = softmax(q_i k_i^T / sqrt(dh) + mask) [v1 | v2]        i = 1, 2
    lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init,
    lambda_init = 0.8 - 0.6 exp(-0.3 l),  l the layer's index in the MODEL
    o = rms(a1 - lambda a2; over 2 dh, weight `subln`, eps) (1 - lambda_init)
    mixer = concat_pairs(o) W_o + b_o
    mask: causal; a sliding layer also t - window < s <= t.
  "cross_attention": q = h W_q + b_q only; k and v are the NEWEST
    full_attention layer's before it (layer 17's), all positions <= t; its
    own lambdas, `subln` and lambda_init (by its own index); else as above.

Readings that no key of the published file carries are in the
configuration file's `assumed`, one line each.

It reads the program's parameter tree because those ARE the weights under
test: {"embed", "final_norm", "final_norm_b", "layers": {"mamba1",
"attn_window", "attn", "gmu", "attn_cross", "dense"}}, each stack indexed
by a layer's ordinal among the layers of its kind (`A_log` lies [N, C],
transposed). Computed a layer at a time (one program a KIND of layer, the
weights cast to float32 a layer at a time), attention a key/value pair at a
time and the head in blocks of vocabulary rows, so that it fits beside the
served weights at the published widths.

`faults` (a tuple of names from FAULTS) computes ONE part wrong, for the
tests and for benchmark/hold_phi4flash.py: what the comparison must tell
apart.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reference_kanana import _vocab_blocks    # the same walk

MAMBA1, GMU, FULL, WINDOW, CROSS = ("mamba1", "gmu", "full_attention",
                                    "sliding_attention", "cross_attention")
STACKS = {MAMBA1: "mamba1", GMU: "gmu", FULL: "attn", WINDOW: "attn_window",
          CROSS: "attn_cross"}
#: one part of the block done wrong: a cross layer reading the newest WINDOW
#: layer's keys and values; a cross layer making keys and values of its own
#: input (with the full layer's projections) as if it wrote pages; a gated
#: memory unit fed the mamba1 layer BEFORE the newest one's output;
#: lambda_init from a layer's ordinal among the attention layers; the window
#: one short and one long; every bias left out; the memory taken after the
#: gate; lambda left at lambda_init; the recurrence's decay and carry
#: rounded to bfloat16
FAULTS = ("cross_reads_window", "cross_writes_own", "gmu_reads_older",
          "lambda_by_ordinal", "window_minus_1", "window_plus_1", "no_bias",
          "memory_after_gate", "lambda_at_init", "carry_bf16")
#: parameters the program holds in float32 whatever the model's dtype
_KEEP = ("w_conv", "b_conv", "b_dt", "A_log", "D", "lambda_q1", "lambda_k1",
         "lambda_q2", "lambda_k2")


class Dims(NamedTuple):
    heads: int
    kv_heads: int
    head_dim: int
    eps: float
    layer_types: Tuple[str, ...]
    state: int
    dt_rank: int
    window: int


def dims_of(cfg) -> Dims:
    return Dims(int(cfg.n_heads), int(cfg.n_kv_heads), int(cfg.head_dim),
                float(cfg.norm_eps), tuple(cfg.layer_types),
                int(cfg.ssm1_state), int(cfg.ssm1_dt_rank),
                int(cfg.sliding_window))


def _bits(a, exponent: int, mantissa: int):
    return lax.reduce_precision(a, exponent_bits=exponent,
                                mantissa_bits=mantissa)


def _held(hold):
    """Rounding to the precision a value is HELD in (None: float32, no
    rounding; "bf16"; "fp8": e4m3), by `lax.reduce_precision` (a pair of
    casts is the compiler's to drop): what `hidden(hold=...)` applies to
    the weights and to the values that pass from one operator to the
    next."""
    if hold is None:
        return lambda a: a
    e, m = {"bf16": (8, 7), "fp8": (4, 3)}[hold]
    return lambda a: _bits(a, e, m)


def layernorm(x, w, b, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w + b


def mamba1(h, lp, dims: Dims, faults=()):
    """h [S, d] (normed) -> (the mixer's output [S, d], the memory m [S,
    C])."""
    N, R = dims.state, dims.dt_rank
    S, K = h.shape[0], lp["w_conv"].shape[0]
    u, z = h @ lp["w_x"], h @ lp["w_z"]
    u = jnp.pad(u, ((K - 1, 0), (0, 0)))            # zeros before token 0
    xs = jax.nn.silu(lp["b_conv"] + sum(lp["w_conv"][j] * u[j:j + S]
                                        for j in range(K)))
    delta, B, C = jnp.split(xs @ lp["w_xproj"], [R, R + N], axis=-1)
    dt = jax.nn.softplus(delta @ lp["w_dt"] + lp["b_dt"])     # [S, C]
    A = -jnp.exp(lp["A_log"])                                 # [N, C]
    low = (lambda a: _bits(a, 8, 7)) if "carry_bf16" in faults \
        else (lambda a: a)

    def token(state, inp):
        x, dt, b, c = inp                           # [C] [C] [N] [N]
        state = low(jnp.exp(dt[None] * A)) * state \
            + (dt * x)[None] * b[:, None]
        return low(state), c @ state + lp["D"] * x

    _, m = lax.scan(token, jnp.zeros(A.shape, jnp.float32), (xs, dt, B, C))
    gated = m * jax.nn.silu(z)
    return gated @ lp["w_out"], gated if "memory_after_gate" in faults else m


def lambda_init(index: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * index)


def differential(q, k, v, lp, dims: Dims, init, window: int, faults=()):
    """q [S, H, dh], k and v [S, G, dh] (H query and G key/value heads of
    the published width) -> concat_pairs(o) [S, H dh]: the four products a
    pair on dh-wide heads, a key/value pair at a time; ``init`` the layer's
    lambda_init."""
    S, H, dh = q.shape
    G = k.shape[1]
    q = q.reshape(S, G // 2, H // G, 2, dh)         # [kv pair, q pair, i]
    k, v = k.reshape(S, G // 2, 2, dh), v.reshape(S, G // 2, 2 * dh)
    t = jnp.arange(S)
    seen = t[None, :] <= t[:, None]
    if window:
        seen = seen & (t[None, :] > t[:, None] - window)

    def pair(_, kv_pair):
        qg, kg, vg = kv_pair                        # [S, P, 2, dh] ...
        s = jnp.einsum("spid,tid->pist", qg, kg) * dh ** -0.5
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return None, jnp.einsum("pist,tv->spiv", p, vg)

    _, a = lax.scan(pair, None, (q.transpose(1, 0, 2, 3, 4),
                                 k.transpose(1, 0, 2, 3),
                                 v.transpose(1, 0, 2)))
    a = a.transpose(1, 0, 2, 3, 4).reshape(S, H // 2, 2, 2 * dh)
    lam = init if "lambda_at_init" in faults else \
        jnp.exp(jnp.sum(lp["lambda_q1"] * lp["lambda_k1"])) \
        - jnp.exp(jnp.sum(lp["lambda_q2"] * lp["lambda_k2"])) + init
    o = a[:, :, 0] - lam * a[:, :, 1]
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + dims.eps) \
        * lp["subln"] * (1.0 - init)
    return o.reshape(S, H * dh)


def _entry(stack, i, q, faults=()):
    """Entry i of a kind's stack in float32, held as ``q`` holds; biases
    zero under the fault that leaves them out."""
    out = {k: a[i].astype(jnp.float32) if k in _KEEP
           else q(a[i].astype(jnp.float32)) for k, a in stack.items()}
    if "no_bias" in faults:
        out = {k: jnp.zeros_like(a) if k in ("bq", "bk", "bv", "bo")
               or k.endswith("_b") else a for k, a in out.items()}
    return out


@functools.partial(jax.jit, static_argnames=("op", "dims", "hold", "faults"))
def _layer(x, op_stack, dense_stack, j, i, init, given, op, dims: Dims, hold,
           faults):
    """Layer i of one sequence: its mixer (entry j of its kind's stack),
    then its feed-forward (entry i of the dense stack). One program a kind
    of mixer, whatever the depth: j, i and an attention layer's lambda_init
    ``init`` are arguments. ``given``: what earlier layers left that this one reads (the
    memory for "gmu"; keys, values and the projections they came from for
    "cross_attention"). Returns (x, what this layer leaves)."""
    q = _held(hold)
    lp = _entry(op_stack, j, q, faults)
    H, G, dh = dims.heads, dims.kv_heads, dims.head_dim
    S = x.shape[0]
    left = {}

    def ln(x, name, p):
        return q(layernorm(x, p[name], p[name + "_b"], dims.eps))

    def bias(name):
        return lp[name] if name in lp else 0.0

    if op == MAMBA1:
        y, m = mamba1(ln(x, "mamba1_norm", lp), lp, dims, faults)
        left["memory"] = q(m)
    elif op == GMU:
        h = ln(x, "gmu_norm", lp)
        y = q(given["memory"] * jax.nn.silu(h @ lp["w_in"])) @ lp["w_out"]
    else:
        h = ln(x, "attn_norm", lp)
        qs = (h @ lp["wq"] + bias("bq")).reshape(S, H, dh)
        if op == CROSS:
            k, v = given["k"], given["v"]
            if "cross_writes_own" in faults:
                # keys and values of ITS OWN input, by the full layer's
                # projections: what a cross layer that wrote pages would read
                src = given["projections"]
                k = (h @ src["wk"] + src["bk"]).reshape(S, G, dh)
                v = (h @ src["wv"] + src["bv"]).reshape(S, G, dh)
            window = 0
        else:
            k = (h @ lp["wk"] + bias("bk")).reshape(S, G, dh)
            v = (h @ lp["wv"] + bias("bv")).reshape(S, G, dh)
            left.update(k=k, v=v, projections={
                "wk": lp["wk"], "wv": lp["wv"], "bk": bias("bk")
                + jnp.zeros(G * dh), "bv": bias("bv") + jnp.zeros(G * dh)})
            window = dims.window if op == WINDOW else 0
            window += ("window_plus_1" in faults) - ("window_minus_1"
                                                     in faults) \
                if window else 0
        y = q(differential(qs, k, v, lp, dims, init, window, faults)) \
            @ lp["wo"] + bias("bo")
    x = q(x + y)
    lp = _entry(dense_stack, i, q, faults)
    z = ln(x, "mlp_norm", lp)
    return q(x + q(jax.nn.silu(z @ lp["w_gate"]) * (z @ lp["w_up"]))
             @ lp["w_down"]), left


def hidden(params, tokens, dims: Dims, hold=None, faults=()):
    """tokens [S] int32 -> LN_f(x_L) [S, d] float32, layer after layer (a
    Python loop over ``_layer``: the compiler sees one layer of a kind at a
    time). ``hold`` rounds every weight held in the model's dtype and every
    value that passes from one operator to the next to that precision, the
    arithmetic staying float32."""
    faults = tuple(faults)
    q = _held(hold)
    stacks = params["layers"]
    x = q(params["embed"][tokens].astype(jnp.float32))
    seen = dict.fromkeys(STACKS, 0)
    memories, pages = [], {}
    attention = [i for i, op in enumerate(dims.layer_types)
                 if op in (FULL, WINDOW, CROSS)]
    for i, op in enumerate(dims.layer_types):
        given = {}
        if op == GMU:
            given["memory"] = memories[
                -2 if "gmu_reads_older" in faults else -1]
        if op == CROSS:
            kind = WINDOW if "cross_reads_window" in faults else FULL
            given = pages[kind]
        x, left = _layer(
            x, stacks[STACKS[op]], stacks["dense"], seen[op], i,
            lambda_init(attention.index(i) if "lambda_by_ordinal" in faults
                        and i in attention else i), given, op, dims, hold,
            faults)
        seen[op] += 1
        if "memory" in left:
            memories.append(left["memory"])
        if "k" in left:
            pages[op] = left
    w, b = (params[k].astype(jnp.float32)
            for k in ("final_norm", "final_norm_b"))
    if "no_bias" in faults:
        b = jnp.zeros_like(b)
    return q(layernorm(x, q(w), q(b), dims.eps))


def forward(params, tokens, dims: Dims, hold=None, faults=()):
    """tokens [S] int32 -> logits [S, vocab] float32: the whole logits, for
    sizes at which they fit (the tests)."""
    x = hidden(params, tokens, dims, hold, faults)
    return x @ _held(hold)(params["embed"].astype(jnp.float32)).T


def forward_logits(params, tokens, dims: Dims, faults=()) -> jax.Array:
    with jax.default_matmul_precision("highest"):
        return forward(params, tokens, dims, faults=faults)


@functools.partial(jax.jit, static_argnames=("hold",))
def _head_scores(x, head, nxt, hold):
    """Per position: the argmax over the tied head of x [S, d], and how far
    under the top logit the token ``nxt`` sits, the head walked in blocks
    of vocabulary rows ([S, vocab] float32 logits are a gigabyte at 1280 x
    200064)."""
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

    S = x.shape[0]
    (best, arg, took), _ = lax.scan(
        block, (jnp.full((S,), -jnp.inf), jnp.zeros((S,), jnp.int32),
                jnp.zeros((S,))), jnp.arange(nb))
    return arg, best - took


def token_scores(params, tokens, nxt, dims: Dims, hold=None,
                 precision="highest", faults=()):
    """Per position of tokens [S]: the reference's argmax for the next
    token, and how far under its top logit the token ``nxt`` [S] sits
    there. ``precision`` is "highest" for the reference proper;
    hold_phi4flash.py asks for "bfloat16" beside a ``hold`` to read what
    computing in the stated precision costs."""
    with jax.default_matmul_precision(precision):
        x = hidden(params, tokens, dims, hold, faults)
        return _head_scores(x, params["embed"], nxt, hold)


def score_greedy(params, dims: Dims, prompt: List[int],
                 generated: List[int], pad_to: int, hold=None) -> Dict:
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
    toks = jnp.asarray(toks)
    top, gap = (np.asarray(a)[n_p - 1:n_p - 1 + n_g]
                for a in token_scores(params, toks, jnp.roll(toks, -1), dims,
                                      hold))
    return {"reference_tokens": top.tolist(), "gap": gap.tolist()}
