"""The plain reference for the Kanana-2 block (model_type "deepseek_v3":
latent attention, a shared expert beside routed experts, leading dense
layers): the decoder's forward pass in its PUBLISHED, expanded form, in
straightforward jax.numpy, float32, matmul precision "highest"; no kernel,
no page pool, no cache of any kind, no absorption of the up-projection, no
grouping of tokens by expert, nothing imported from ray_tpu.

For hidden states x [S, d] of ONE sequence, RMSNorm eps = rms_norm_eps
throughout, no bias anywhere:

    h  = x + attn(rmsnorm(x, w_attn))
    x' = h + ffn_i(rmsnorm(h, w_ffn))

  attn (q_lora_rank null: the query has no low-rank step), H heads:
    q = z W_q, per head q_h = [q_nope_h (nope), q_pe_h (rope)]
    a = z W_kva (rank + rope);  c = rmsnorm(a[:rank], w_kv);  k_pe = a[rank:]
    rotary on q_pe_h and on k_pe (ONE k_pe for all heads), over ADJACENT
      pairs (2j, 2j+1), pair j turning at theta^(-2j/rope) (rope_interleave)
    [k_nope_h (nope), v_h (v)] = split(c W_kvb)_h
                                         W_kvb [rank, H (nope + v)]
    score_h[t, s] = (q_nope_h[t] . k_nope_h[s] + q_pe_h[t] . k_pe[s])
                    / sqrt(nope + rope),  causal softmax
    attn = concat_h(sum_s p_h[t, s] v_h[s]) W_o       W_o [H v, d]
  ffn_i, i < first_k_dense_replace:  (silu(z W1) * (z W3)) W2
  ffn_i after:
    s = sigmoid(z W_r) float32;  chosen = top_k(s + b)   b enters the
    CHOICE only (topk_method noaux_tc; n_group = topk_group = 1: no limit)
    g = s[chosen];  g = g / (sum(g) + 1e-20)  (norm_topk_prob);
    g = g * routed_scaling_factor
    ffn = sum_j g_j expert_j(z) + shared(z)      each a SwiGLU; `shared`
    ONE of width n_shared_experts x moe_intermediate_size, no gate of its
    own, every token

After the last layer rmsnorm, then logits from an lm_head of its own.

Departures from, and readings of, the published description (each is in
the configuration file's `assumed` too): the 1e-20 in the renormalisation
is the family's code, not a key; the shared experts are one SwiGLU of the
summed width (the family's code builds them so); b is drawn from the seed,
not trained to balance load.

It reads the program's parameter tree because those ARE the weights under
test: {"embed", "lm_head", "final_norm", "layers": {"attn": {attn_norm, wq,
w_kva, kv_norm, w_uk [H, nope, rank], w_uv [H, rank, v], wo}, "dense":
{mlp_norm, w_gate, w_up, w_down}, "moe": {mlp_norm, router, router_bias,
w_gate, w_up, w_down [n, E, ...], w_shared_gate, w_shared_up,
w_shared_down}}}. The program holds the up-projection split per head in
the layout its absorbed products read; here it is UN-STACKED to the
published W_kvb [rank, H (nope + v)] and the cached latent is expanded to
per-head keys and values, which the program never does.

Computed in blocks so that it fits beside the served weights at the
published widths: a layer (an expert) is cast to float32 at a time,
attention runs one head at a time ([S, S] scores, not [H, S, S]), and the
scoring never holds [S, vocab] logits: the head is walked in blocks of
vocabulary rows keeping each position's top logit and its logit for the
token that followed.
"""

from __future__ import annotations

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reference import _rmsnorm            # the same formula
from benchmark.reference_lfm2 import _held, expert_layer


def dims_of(cfg) -> tuple:
    """Hashable: (heads, latent rank, nope, rope, v, rope theta, norm eps,
    experts per token, renormalise, leading dense layers, router score,
    renormalisation epsilon, routing scale)."""
    return (int(cfg.n_heads), int(cfg.kv_lora_rank),
            int(cfg.qk_nope_head_dim), int(cfg.qk_rope_head_dim),
            int(cfg.v_head_dim), float(cfg.rope_theta), float(cfg.norm_eps),
            int(cfg.experts_per_token), bool(cfg.norm_topk_prob),
            int(cfg.n_dense_layers), str(cfg.router_score),
            float(cfg.router_eps), float(cfg.router_scale))


def rope_pairs(x, theta: float):
    """x [S, ..., D]: adjacent pairs (2j, 2j+1) of position s turned by the
    angle s * theta^(-2j/D)."""
    S, D = x.shape[0], x.shape[-1]
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] \
        * theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)   # [S, D/2]
    ang = ang.reshape((S,) + (1,) * (x.ndim - 2) + (D // 2,))
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                     even * jnp.sin(ang) + odd * jnp.cos(ang)], axis=-1)
    return out.reshape(x.shape)


def published_kvb(w_uk, w_uv):
    """The program's per-head halves -> the published up-projection
    W_kvb [rank, H (nope + v)] (kv_b_proj, transposed to right-multiply)."""
    H, _, r = w_uk.shape
    both = jnp.concatenate([w_uk.transpose(2, 0, 1),       # [r, H, nope]
                            w_uv.transpose(1, 0, 2)], axis=-1)
    return both.reshape(r, -1)


def attention(z, lp, dims):
    """z [S, d] (normed) -> the operator's output [S, d], expanded form."""
    H, r, dn, dr, dv, theta, eps = dims[:7]
    S = z.shape[0]
    q = (z @ lp["wq"]).reshape(S, H, dn + dr)
    a = z @ lp["w_kva"]
    c = _rmsnorm(a[:, :r], lp["kv_norm"], eps)
    q_pe = rope_pairs(q[..., dn:], theta)                   # [S, H, rope]
    k_pe = rope_pairs(a[:, r:], theta)                      # [S, rope]
    kv = (c @ published_kvb(lp["w_uk"], lp["w_uv"])).reshape(S, H, dn + dv)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def head(_, qkv):                    # one head: scores [S, S]
        q_nope, q_rot, k_nope, v = qkv
        s = (q_nope @ k_nope.T + q_rot @ k_pe.T) * (dn + dr) ** -0.5
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return None, p @ v

    per_head = [t.transpose(1, 0, 2) for t in (
        q[..., :dn], q_pe, kv[..., :dn], kv[..., dn:])]
    _, o = lax.scan(head, None, per_head)                   # [H, S, v]
    return o.transpose(1, 0, 2).reshape(S, H * dv) @ lp["wo"]


def hidden(params, tokens, dims, hold=None):
    """tokens [S] int32 -> (the last norm's output [S, d] float32, the
    experts each expert layer chose [n_expert_layers, S, k]). ``hold``:
    reference_lfm2.forward's (a dtype the weights and the values passed
    between operators are rounded to; None is the reference proper)."""
    eps, top_k, renorm, n_dense, score, r_eps, r_scale = dims[6:]
    f32 = jnp.float32
    q = _held(hold)
    x = q(params["embed"][tokens].astype(f32))   # gather, then cast
    stacks = params["layers"]
    experts = ("w_gate", "w_up", "w_down")

    def entry(kind, i, skip=()):
        return {k: q(a[i].astype(f32)) for k, a in stacks[kind].items()
                if k not in skip}

    def normed(x, w):
        return q(_rmsnorm(x, w, eps))

    def swiglu(z, gate, up, down):
        return q(jax.nn.silu(z @ gate) * (z @ up)) @ down

    chosen = []
    for i in range(stacks["attn"]["wq"].shape[0]):
        lp = entry("attn", i)
        x = q(x + attention(normed(x, lp["attn_norm"]), lp, dims))
        if i < n_dense:
            lp = entry("dense", i)
            x = q(x + swiglu(normed(x, lp["mlp_norm"]), lp["w_gate"],
                             lp["w_up"], lp["w_down"]))
            continue
        m = i - n_dense
        # the routed experts' matrices are cast one expert at a time
        lp, moe = entry("moe", m, skip=experts), stacks["moe"]
        z = normed(x, lp["mlp_norm"])
        y, e = expert_layer(
            z, lp["router"], lp.get("router_bias"), moe["w_gate"],
            moe["w_up"], moe["w_down"], top_k, renorm, layer=m, hold=hold,
            score=score, eps=r_eps, scale=r_scale)
        if "w_shared_gate" in lp:           # every token, counted once
            y = y + swiglu(z, lp["w_shared_gate"], lp["w_shared_up"],
                           lp["w_shared_down"])
        x = q(x + y)
        chosen.append(e)
    out = normed(x, q(params["final_norm"].astype(f32)))
    return out, (jnp.stack(chosen) if chosen else None)


def forward(params, tokens, dims, hold=None):
    """tokens [S] int32 -> (logits [S, vocab] float32, chosen experts):
    the whole logits, for sizes at which they fit (the tests)."""
    x, chosen = hidden(params, tokens, dims, hold)
    return x @ _held(hold)(params["lm_head"].astype(jnp.float32)).T, chosen


def forward_logits(params, tokens, dims) -> jax.Array:
    return forward(params, tokens, dims)[0]


def _vocab_blocks(vocab: int, at_most: int = 20000) -> int:
    """How many equal blocks of vocabulary rows, each of at most
    ``at_most`` (1 where nothing up to 64 divides the vocabulary)."""
    return next((n for n in range(1, 65)
                 if vocab % n == 0 and vocab // n <= at_most), 1)


@functools.partial(jax.jit, static_argnames=("dims", "hold", "precision"))
def token_scores(params, tokens, nxt, dims, hold=None, precision="highest"):
    """Per position of tokens [S]: the reference's argmax for the next
    token, and how far under its top logit the token ``nxt`` [S] sits
    there. One program per padded length. The head is walked in blocks of
    vocabulary rows: [S, vocab] float32 logits (2.4 GB at 4608 x 128256)
    never exist. ``precision`` is "highest" for the reference proper;
    hold_kanana.py asks for "bfloat16" (every matmul's operands rounded to
    bf16, as a program that computes in bf16 rounds them) to read what
    computing in the stated precision costs, beside holding in it."""
    with jax.default_matmul_precision(precision):
        x, _ = hidden(params, tokens, dims, hold)
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
