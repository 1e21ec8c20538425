"""The plain reference for the Ouro block (model_type "ouro", a looped
language model: ONE stack of layers walked `total_ut_steps` times over the
same weights, the final norm after every pass, an exit gate on each pass's
normed stream): the decoder's forward pass in straightforward jax.numpy,
float32, matmul precision "highest"; no kernel, no page pool, no cache of
any kind, no batching, nothing imported from ray_tpu.

For hidden states x [S, d] of ONE sequence (token t, position t), RMSNorm
rms(a; w) = a * rsqrt(mean(a^2) + eps) * w with eps = rms_norm_eps
throughout, no bias on any projection:

    h^0 = E[tokens]
    for u = 1 .. U:                               (U = total_ut_steps; the
        x = h^(u-1)                                SAME L layers' weights)
        for l = 0 .. L-1:
            z    = rms(x; attn_norm_l)
            q_j  = z Wq_l[j], j < H;  k_g = z Wk_l[g], v_g = z Wv_l[g], g < G
                   q_j, k_g rotated at position t (the same in every pass),
                   theta = rope_theta, the whole head, half-split pairs
                   (i, i + D / 2)
            a_j(t, s) = q_j(t) . k_g(s) / sqrt(D),  g = j // (H / G),  s <= t
            o_j(t) = sum_s softmax_s(a_j(t, .)) v_g(s)
                   the keys and values are those THIS pass computed: pass u
                   never sees another pass's
            x    = x + rms(concat_j o_j Wo_l; attn_post_norm_l)
            m    = rms(x; mlp_norm_l)
            x    = x + rms((silu(m Wg_l) * (m Wu_l)) Wd_l; mlp_post_norm_l)
        h^u      = rms(x; final_norm)             after EVERY pass
        lambda_u = sigmoid(h^u . w_e + b_e)       ONE gate for all passes
    logits = h^U W_head^T                         (early_exit_threshold 1:
                                                   every token runs U passes)

and the exit distribution p_u = lambda_u prod_{j<u} (1 - lambda_j) for
u < U, p_U = prod_{j<U} (1 - lambda_j) (``exit_distribution``), which sums
to one.

Readings of the published description that no key of the configuration
states (each is in the configuration file's `assumed` too): the four norms
a layer and where they sit (the published input_layernorm and
input_layernorm_2 around the attention branch, post_attention_layernorm and
post_attention_layernorm_2 around the feed-forward); the final norm INSIDE
the loop, its output carried into the next pass; the gate as one
Linear(d, 1) WITH a bias on the normed stream; no bias on any projection;
the half-split rotary pairing.

``faults`` names parts done wrong, for the study that shows the limits of
`correct` can see each (hold_ouro.py) and for the tests: FAULTS below.

It reads the program's parameter tree because those ARE the weights under
test: {"embed", "lm_head" (absent: the embedding), "final_norm", "exit_w"
[d], "exit_b" [1], "layers": {"attn": {attn_norm, wq, wk, wv, wo,
attn_post_norm}, "dense": {mlp_norm, w_gate, w_up, w_down,
mlp_post_norm}}}, every leaf stacked [L, ...].

Computed in blocks so that 768 positions of the whole model fit beside the
idle engine at the published widths: a layer is cast to float32 at a time
(0.21 GB), attention's scores are [H, S, S] for ONE layer (38 MB at 768),
and the scoring never holds [S, vocab] logits (49152 rows: three blocks).
"""

from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reference import _rmsnorm, _rope   # the same two formulas
from benchmark.reference_kanana import _vocab_blocks
from benchmark.reference_phi4flash import _held

#: passes after the first attend over the FIRST pass's keys and values (a
#: pool with one plane a layer); the final norm applied once, after the last
#: pass only (the stream between passes is not normed); the gate without
#: its bias
FAULTS = ("shared_kv", "norm_once", "no_gate_bias")


class Dims(NamedTuple):
    """What the forward needs beside the weights (hashable: a static
    argument)."""
    heads: int
    kv_heads: int
    head: int
    theta: float
    eps: float
    passes: int


def dims_of(cfg) -> Dims:
    """From the program's configuration (a LlamaConfig)."""
    if cfg.ut_steps < 2 or not cfg.post_norms:
        raise ValueError("this reference is the looped block's: passes > 1 "
                         "and a norm after each branch")
    return Dims(int(cfg.n_heads), int(cfg.n_kv_heads), int(cfg.head_dim),
                float(cfg.rope_theta), float(cfg.norm_eps),
                int(cfg.ut_steps))


def attention(z, lp, dims: Dims, kv=None):
    """z [S, d] (normed) -> (the operator's output [S, d] before the norm
    after the branch, the keys and values it attended over). ``kv``: keys
    and values to attend over in place of this call's own (the shared_kv
    fault)."""
    H, G, D = dims.heads, dims.kv_heads, dims.head
    S = z.shape[0]
    q = _rope((z @ lp["wq"]).reshape(S, H, D), dims.theta)
    if kv is None:
        kv = (_rope((z @ lp["wk"]).reshape(S, G, D), dims.theta),
              (z @ lp["wv"]).reshape(S, G, D))
    k, v = kv
    s = jnp.einsum("sgjd,tgd->gjst", q.reshape(S, G, H // G, D), k) \
        * D ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("gjst,tgd->sgjd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(S, H * D) @ lp["wo"], kv


def hidden(params, tokens, dims: Dims, hold=None, faults=()):
    """tokens [S] int32 -> (h^U [S, d] float32, the gates lambda [U, S]).
    ``hold``: the precision the weights and the values passed between
    operators are rounded to (None is the reference proper; "bf16";
    "fp8"); ``faults``: the module docstring's."""
    f32 = jnp.float32
    q = _held(hold)
    stacks = params["layers"]
    final = q(params["final_norm"].astype(f32))
    w_e = q(params["exit_w"].astype(f32))
    b_e = 0.0 if "no_gate_bias" in faults \
        else q(params["exit_b"].astype(f32))[0]

    def normed(x, w):
        return q(_rmsnorm(x, w, dims.eps))

    def layer(x, lp):
        a, m, kv = lp
        a, m = ({k: q(w.astype(f32)) for k, w in part.items()}
                for part in (a, m))
        y, kv = attention(normed(x, a["attn_norm"]), a, dims, kv)
        x = q(x + normed(y, a["attn_post_norm"]))
        z = normed(x, m["mlp_norm"])
        z = q(jax.nn.silu(z @ m["w_gate"]) * (z @ m["w_up"])) @ m["w_down"]
        return q(x + normed(z, m["mlp_post_norm"])), kv

    x = q(params["embed"][tokens].astype(f32))      # gather, then cast
    gates, first = [], None
    for u in range(dims.passes):
        x, kv = lax.scan(layer, x, (stacks["attn"], stacks["dense"],
                                    first if "shared_kv" in faults else None))
        first = kv if first is None else first
        h = normed(x, final)
        gates.append(jax.nn.sigmoid(h @ w_e + b_e))
        if "norm_once" not in faults:
            x = h
    return h, jnp.stack(gates)


def exit_distribution(gates):
    """gates lambda [U, ...] -> p [U, ...]: p_u = lambda_u prod_{j<u} (1 -
    lambda_j) for u < U, and what is left at the last pass."""
    stays = jnp.cumprod(1.0 - gates[:-1], axis=0)
    before = jnp.concatenate([jnp.ones_like(gates[:1]), stays[:-1]])
    return jnp.concatenate([gates[:-1] * before, stays[-1:]])


def _head(params):
    return params["lm_head"] if "lm_head" in params else params["embed"]


def forward(params, tokens, dims: Dims, hold=None, faults=()):
    """tokens [S] int32 -> (logits [S, vocab] float32, the gates [U, S]):
    the whole logits, for sizes at which they fit (the tests)."""
    h, gates = hidden(params, tokens, dims, hold, faults)
    return h @ _held(hold)(_head(params).astype(jnp.float32)).T, gates


def forward_logits(params, tokens, dims: Dims) -> jax.Array:
    with jax.default_matmul_precision("highest"):
        return forward(params, tokens, dims)[0]


@functools.partial(jax.jit, static_argnames=("dims", "hold", "precision",
                                             "faults"))
def token_scores(params, tokens, nxt, dims: Dims, hold=None,
                 precision="highest", faults=()):
    """Per position of tokens [S]: the reference's argmax for the next
    token, and how far under its top logit the token ``nxt`` [S] sits
    there. One program per padded length; reference_trinity.token_scores'
    walk of the head in blocks of vocabulary rows. ``precision`` is
    "highest" for the reference proper; hold_ouro.py asks for "bfloat16"
    beside a ``hold`` to read what computing in the stated precision
    costs."""
    with jax.default_matmul_precision(precision):
        x, _ = hidden(params, tokens, dims, hold, faults)
        head = _head(params)
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
