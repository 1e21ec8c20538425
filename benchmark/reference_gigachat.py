"""The plain reference for the GigaChat3.5 block (model_type "gigachat3_5":
gated-delta-rule linear-attention layers with every fourth layer latent
attention, leading dense layers, then routed experts of which this chip
holds a share beside a shared one; every norm a zero-centred gated norm, a
norm after each branch as well as before it, every SwiGLU clamped): the
decoder's forward pass in straightforward jax.numpy, float32, matmul
precision "highest"; no kernel, no page pool, no cache of any kind, no chunk
form of the recurrence, no absorption of the latent's up-projection, no
grouping of tokens by expert, nothing imported from ray_tpu.

For hidden states x [S, d] of ONE sequence (token t, position t), no bias
anywhere. norm(x; w) = x / rms(x) * G sigmoid(w), G = layernorm_gating_weight
= 2 (ZeroCenteredGatedNorm: 1 at w = 0), eps rms_norm_eps. Every layer:

    x' = x  + norm(mixer(norm(x;  w_pre));  w_post)       layernorm_type
    x'' = x' + norm(ffn  (norm(x'; w_pre'));  w_post')     "pre_post"

  mixer, a DELTA layer (GigaChat35GatedDeltaNet: Hk = 32 key heads, Hv = 64
  value heads of 128, conv of K = 4 taps), h the normed input:
    u = h W_qkv (2 Hk 128 + Hv 128 channels: q, then k, then v)
    c[t] = silu(sum_j w[j] * u[t - (K-1) + j])    depthwise, causal, no bias
    q_j = c_q[j] / |c_q[j]| * 128^-1/2;  k_j = c_k[j] / |c_k[j]|   (the
          norm's square has 1e-6 added under the root);  v_h = c_v[h]
    beta_h = sigmoid(h W_b);  g_h = -exp(A_log_h) softplus(h W_a + dt_bias_h)
    per value head h, key head j = h // (Hv / Hk), S_h in R^{128 x 128},
    zeros at position 0:
        S_h <- e^{g_h} S_h;  S_h <- S_h + k_j (x) beta_h (v_h - S_h^T k_j)
        o_h = S_h^T q_j
    y_h = o_h / rms(o_h; linear_attn_o_norm_eps) * s sigmoid(w~)
          * s sigmoid(h W_z)_h        s = linear_sigmoid_gate_scale = 2
    mixer = concat_h(y_h) W_out
  mixer, a LATENT layer (64 heads of [128 nope, 64 rope], value 128):
    c_q = norm(h W_qa; w_qa) (1536);  q = c_q W_qb, per head [q_nope, q_pe]
    a = h W_kva (512 + 64);  c = norm(a[:512]; w_kv);  k_pe = a[512:]
    rotary on q_pe and on k_pe (ONE k_pe for all heads), over ADJACENT
      pairs (rope_interleave), at YaRN's frequencies (factor 8, original
      32768 positions, beta 32 / 1: `yarn_freqs`)
    [k_nope_h, v_h] = split(c W_kvb)_h
    score_h[t, s] = (q_nope_h[t] . k_nope_h[s] + q_pe_h[t] . k_pe[s])
                    * 192^-1/2 * m^2,  m = 0.1 mscale_all_dim ln 8 + 1
                    (use_mla_scaling_factor),  causal softmax
    o_h = sum_s p_h[t, s] v_h[s];  o_h <- o_h * sigmoid(h W_og)_h
                                                        (gated_attention)
    mixer = concat_h(o_h) W_o
  ffn: act(g, u) = silu(min(g, L)) * clip(u, -L, L), L = swiglu_limit = 10
    leading dense layers:  act(z W1, z W3) W2
    after:  r = sigmoid(z W_r) in R^256;  C = top-8 of (r + b)   b enters
      the CHOICE only;  w_e = 2.5 r_e / (sum_{c in C} r_c + 1e-20)
      ffn = sum_{e in C, e HELD here} w_e SwiGLU^e(z) + SwiGLU^shared(z)
      (no gate on the shared expert: use_shared_expert_sigmoid false)

After the last layer norm, then logits from an lm_head of its own, over the
rows of the vocabulary this chip holds.

Departures from, and readings of, the published description (each is in the
configuration file's `assumed` or `departures` too): both `2 sigmoid`
readings; the clamp one-sided on the gate and two-sided on the linear half;
g's A_log / dt_bias parametrisation and the L2 norm with its 1e-6 (the
delta-net family's modelling code; no key); sigmoid scoring with a
selection bias and no group limit (n_group 1); the 1e-20 in the
renormalisation; the output gate's shape [d, 64 x 128]; the SQUARED m on the
score scale; the experts this chip does not hold contribute nothing
(``held`` = (first, n): the program's weights hold those n only, and so does
this reference, renormalised over ALL 8 chosen); the vocabulary is the held
rows; the 2 multi-token-prediction layers are not served.

``state_hold`` rounds the recurrent state to a dtype after EVERY token (None:
float32, as the family carries it): the lower-precision control of the
comparison that decides `correct` (hold_gigachat.py). ``fault`` names ONE
part left out, for the same study: "no_read" (S += k (x) beta v: the delta
rule without its read, i.e. plain gated linear attention), "no_decay" (g =
0), "no_yarn" (plain frequencies and no m^2), "no_gate" (the latent layer's
output gate), "plain_norm" (every norm's weight taken as it is, not through
2 sigmoid).

It reads the program's parameter tree because those ARE the weights under
test: {"embed", "lm_head", "final_norm", "layers": {"delta": {delta_norm,
w_qkv, w_z, w_ba (b, then a), w_conv [n, K, ch], A_log, dt_bias, gate_norm,
w_out, attn_post_norm}, "attn": {attn_norm, wq_a, q_a_norm, wq, w_kva,
kv_norm, w_uk [H, nope, rank], w_uv [H, rank, v], w_og, wo, attn_post_norm},
"dense": {mlp_norm, w_gate, w_up, w_down, mlp_post_norm}, "moe": {mlp_norm,
router [n, d, E], router_bias [n, E], w_gate, w_up [n, held, d, f], w_down
[n, held, f, d], w_shared_gate, w_shared_up, w_shared_down,
mlp_post_norm}}}. The program holds the latent's up-projection split per
head; a head's k_nope and v are c w_uk[h]^T and c w_uv[h], the columns of
the published W_kvb that are that head's.

Computed in blocks so that 9 k tokens fit beside the served weights and a
state of 160 slots at the published widths: a delta layer runs a QUARTER of
its value heads at a time (their columns of W_qkv, W_z and W_ba, their rows
of W_out), the latent layer one head and one block of queries at a time, a
dense layer a quarter of its width, an expert at a time, and the scoring
never holds [S, vocab] logits.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reference_kanana import _vocab_blocks
from benchmark.reference_mimo import routing

FAULTS = ("no_read", "no_decay", "no_yarn", "no_gate", "plain_norm")
#: queries a block of the latent attention (a block's scores are [block, S])
Q_BLOCK = 1024
#: groups a delta layer's value heads are run in
HEAD_GROUPS = 4


def _held(dtype):
    """Rounding to the precision a value is HELD in (None: float32, no
    rounding): what ``hold`` applies to weights and to the values that pass
    from one operator to the next, and ``state_hold`` to the recurrent
    state. bfloat16 is rounded by ``lax.reduce_precision``: a cast to bf16
    and back inside one program is a pair of conversions the TPU compiler
    removes as excess precision (it did: held so, the state read the same
    tokens as not held, to the last of 9216; my chip run, PR 55), and the
    study's control would be the reading it is compared with."""
    if dtype is None:
        return lambda a: a
    if jnp.dtype(dtype) == jnp.bfloat16:
        return lambda a: lax.reduce_precision(a, exponent_bits=8,
                                              mantissa_bits=7)
    return lambda a: a.astype(dtype).astype(jnp.float32)


class Dims(NamedTuple):
    """What the forward needs beside the weights (hashable: a jit static)."""
    kinds: Tuple[bool, ...]          # per layer: True = latent attention
    n_dense: int
    heads: int
    rank: int
    nope: int
    rope: int
    v: int
    theta: float
    yarn: Tuple[float, ...]          # factor, original, fast, slow, m, m_all
    eps: float
    norm_gate: float
    delta: Tuple[int, int, int, int, int]   # Hk, Hv, dk, dv, conv taps
    delta_eps: float
    delta_gate: float
    clamp: float
    top_k: int
    renorm: bool
    score: str
    r_eps: float
    r_scale: float
    held: Tuple[int, int]


def dims_of(cfg) -> Dims:
    return Dims(
        tuple(t == "full_attention" for t in cfg.layer_types),
        int(cfg.n_dense_layers), int(cfg.n_heads), int(cfg.kv_lora_rank),
        int(cfg.qk_nope_head_dim), int(cfg.qk_rope_head_dim),
        int(cfg.v_head_dim), float(cfg.rope_theta),
        tuple(float(v) for v in cfg.rope_yarn), float(cfg.norm_eps),
        float(cfg.norm_gate),
        (int(cfg.delta_key_heads), int(cfg.delta_value_heads),
         int(cfg.delta_key_dim), int(cfg.delta_value_dim),
         int(cfg.delta_conv)), float(cfg.delta_norm_eps),
        float(cfg.delta_gate_scale), float(cfg.ffn_clamp),
        int(cfg.experts_per_token), bool(cfg.norm_topk_prob),
        str(cfg.router_score), float(cfg.router_eps),
        float(cfg.router_scale),
        tuple(cfg.experts_held) or (0, int(cfg.n_experts)))


def gated_norm(x, w, eps: float, gate: float):
    """x / rms(x) * gate * sigmoid(w) (gate 0: * w, the plain RMSNorm)."""
    w = gate * jax.nn.sigmoid(w) if gate else w
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_freqs(dim: int, theta: float, yarn) -> jax.Array:
    """The dim / 2 rotary frequencies: pair j at theta^(-2j/dim) where it
    turns more than beta_fast times over the original positions, at 1 /
    factor of that where fewer than beta_slow, a linear blend between."""
    j = jnp.arange(0, dim // 2, dtype=jnp.float32)
    plain = theta ** (-2.0 * j / dim)
    if not yarn:
        return plain
    factor, original, fast, slow = yarn[:4]

    def at(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low, high = max(math.floor(at(fast)), 0), min(math.ceil(at(slow)),
                                                  dim - 1)
    ramp = jnp.clip((j - low) / max(high - low, 1e-3), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def rotate_pairs(x, freqs, scale: float = 1.0):
    """x [S, ..., D]: adjacent pairs (2j, 2j+1) of position s turned by the
    angle s * freqs[j] (cos and sin times ``scale``)."""
    S, D = x.shape[0], x.shape[-1]
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs     # [S, D/2]
    ang = ang.reshape((S,) + (1,) * (x.ndim - 2) + (D // 2,))
    cos, sin = scale * jnp.cos(ang), scale * jnp.sin(ang)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def act(g, u, clamp: float):
    if clamp:
        g, u = jnp.minimum(g, clamp), jnp.clip(u, -clamp, clamp)
    return jax.nn.silu(g) * u


def _cols(w, start, n, q):
    """Columns start .. start + n - 1 of a raw weight, cast and held."""
    return q(lax.dynamic_slice_in_dim(w, start, n, axis=w.ndim - 1)
             .astype(jnp.float32))


def delta_inputs(z, raw, dims: Dims, q, gi=0, groups: int = 1, fault=None):
    """What the recurrence of group ``gi`` of ``groups`` groups of value
    heads takes, from z [S, d] (normed): (q, k [S, hk, dk], v [S, hv, dv],
    g, beta [S, hv]), q and k a KEY head each. ``raw(name)`` gives the
    layer's raw leaf."""
    Hk, Hv, dk, dv, K = dims.delta
    S = z.shape[0]
    hk, hv = Hk // groups, Hv // groups

    def conv(u, w):
        """silu of the causal depthwise conv: u [S, ch], w [K, ch]."""
        pad = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), u.dtype), u])
        return jax.nn.silu(sum(w[j] * pad[j:j + S] for j in range(K)))

    def unit(a):
        return a * lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)

    def part(first, n):
        return conv(z @ _cols(raw("w_qkv"), first, n, q),
                    _cols(raw("w_conv"), first, n, lambda a: a))

    qs = unit(part(gi * hk * dk, hk * dk).reshape(S, hk, dk)) * dk ** -0.5
    ks = unit(part(Hk * dk + gi * hk * dk, hk * dk).reshape(S, hk, dk))
    vs = part(2 * Hk * dk + gi * hv * dv, hv * dv).reshape(S, hv, dv)
    beta = jax.nn.sigmoid(z @ _cols(raw("w_ba"), gi * hv, hv, q))
    a = z @ _cols(raw("w_ba"), Hv + gi * hv, hv, q)
    A = jnp.exp(lax.dynamic_slice_in_dim(
        raw("A_log").astype(jnp.float32), gi * hv, hv))
    dt = lax.dynamic_slice_in_dim(
        raw("dt_bias").astype(jnp.float32), gi * hv, hv)
    g = -A * jax.nn.softplus(a + dt)
    if fault == "no_decay":
        g = jnp.zeros_like(g)
    return qs, ks, vs, g, beta


def recurrence(qs, ks, vs, g, beta, state_hold=None, fault=None):
    """The gated delta rule token after token from a state of zeros: qs, ks
    [S, hk, dk], vs [S, hv, dv], g, beta [S, hv] -> (o [S, hv, dv], the
    state after the last token [hv, dk, dv]). The state is float32 and its
    products are taken at the highest precision whatever surrounds them (the
    configuration states the recurrence float32); ``state_hold`` rounds it
    after every token."""
    hv, dk, dv = vs.shape[1], qs.shape[2], vs.shape[2]
    sq = _held(state_hold)
    qs, ks = (jnp.repeat(t, hv // t.shape[1], axis=1) for t in (qs, ks))
    read = functools.partial(jnp.einsum, "hkv,hk->hv",
                             precision=lax.Precision.HIGHEST)

    def token(s, inp):
        qt, kt, vt, gt, bt = inp
        s = jnp.exp(gt)[:, None, None] * s
        seen = read(s, kt)
        if fault == "no_read":
            seen = jnp.zeros_like(seen)
        s = sq(s + kt[:, :, None] * (bt[:, None] * (vt - seen))[:, None])
        return s, read(s, qt)

    s, o = lax.scan(token, jnp.zeros((hv, dk, dv), jnp.float32),
                    (qs, ks, vs, g, beta))
    return o, s


def delta_mixer(z, raw, dims: Dims, q, state_hold=None, fault=None):
    """z [S, d] (normed) -> the delta layer's mixer output [S, d], a group
    of value heads at a time. ``raw(name)`` gives the layer's raw leaf."""
    Hk, Hv, dk, dv, K = dims.delta
    S = z.shape[0]
    G = HEAD_GROUPS if Hk % HEAD_GROUPS == 0 else 1
    hv = Hv // G
    norm_w = q(raw("gate_norm").astype(jnp.float32))

    def group(acc, gi):
        o, _ = recurrence(*delta_inputs(z, raw, dims, q, gi, G, fault),
                          state_hold, fault)
        o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + dims.delta_eps)
        s = dims.delta_gate
        gate = s * jax.nn.sigmoid(
            z @ _cols(raw("w_z"), gi * hv * dv, hv * dv, q))
        y = q((o * (s * jax.nn.sigmoid(norm_w))).reshape(S, hv * dv) * gate)
        rows = q(lax.dynamic_slice_in_dim(raw("w_out"), gi * hv * dv,
                                          hv * dv, axis=0)
                 .astype(jnp.float32))
        return acc + y @ rows, None

    y, _ = lax.scan(group, jnp.zeros_like(z), jnp.arange(G))
    return y


def latent_mixer(z, lp, raw, dims: Dims, q, fault=None):
    """z [S, d] (normed) -> the latent layer's mixer output [S, d], in the
    PUBLISHED, expanded form: one head and one block of queries at a time.
    ``lp`` holds the small leaves cast; ``raw(name)`` the raw wide ones."""
    H, r, dn, dr, dv = dims.heads, dims.rank, dims.nope, dims.rope, dims.v
    S = z.shape[0]
    yarn = () if fault == "no_yarn" else dims.yarn
    freqs = yarn_freqs(dr, dims.theta, yarn)
    turn = yarn_mscale(yarn[0], yarn[4]) / yarn_mscale(yarn[0], yarn[5]) \
        if yarn else 1.0
    scale = (dn + dr) ** -0.5 \
        * (yarn_mscale(yarn[0], yarn[5]) ** 2 if yarn else 1.0)
    norm = functools.partial(
        gated_norm, eps=dims.eps,
        gate=0.0 if fault == "plain_norm" else dims.norm_gate)
    c_q = q(norm(z @ lp["wq_a"], lp["q_a_norm"])) if "wq_a" in lp else z
    a = z @ lp["w_kva"]
    c = q(norm(a[:, :r], lp["kv_norm"]))
    k_pe = rotate_pairs(a[:, r:], freqs, turn)             # [S, rope]
    nb = -(-S // Q_BLOCK)
    s_pos = jnp.arange(S)

    def head(acc, j):
        qj = c_q @ _cols(raw("wq"), j * (dn + dr), dn + dr, q)
        q_pe = rotate_pairs(qj[:, dn:], freqs, turn)
        # this head's columns of the published W_kvb
        k_nope = c @ q(raw("w_uk")[j].astype(jnp.float32)).T
        v = c @ q(raw("w_uv")[j].astype(jnp.float32))
        qq = jnp.pad(jnp.concatenate([qj[:, :dn], q_pe], axis=-1),
                     ((0, nb * Q_BLOCK - S), (0, 0)))
        kk = jnp.concatenate([k_nope, k_pe], axis=-1)

        def block(_, b):
            t_pos = b * Q_BLOCK + jnp.arange(Q_BLOCK)
            s = (lax.dynamic_slice_in_dim(qq, b * Q_BLOCK, Q_BLOCK)
                 @ kk.T) * scale
            s = jnp.where(s_pos[None, :] <= t_pos[:, None], s, -jnp.inf)
            return None, jax.nn.softmax(s, axis=-1) @ v

        _, o = lax.scan(block, None, jnp.arange(nb))
        o = o.reshape(nb * Q_BLOCK, dv)[:S]
        if gated:
            o = o * jax.nn.sigmoid(z @ _cols(raw("w_og"), j * dv, dv, q))
        rows = q(lax.dynamic_slice_in_dim(raw("wo"), j * dv, dv, axis=0)
                 .astype(jnp.float32))
        return acc + q(o) @ rows, None

    gated = raw("w_og") is not None and fault != "no_gate"
    y, _ = lax.scan(head, jnp.zeros_like(z), jnp.arange(H))
    return y


def dense_ffn(z, raw, q, clamp: float, blocks: int = 4):
    """act(z W1, z W3) W2, the width a quarter at a time."""
    f = raw("w_gate").shape[-1]
    n = f // blocks if f % blocks == 0 else f

    def part(acc, b):
        h = q(act(z @ _cols(raw("w_gate"), b * n, n, q),
                  z @ _cols(raw("w_up"), b * n, n, q), clamp))
        d = q(lax.dynamic_slice_in_dim(raw("w_down"), b * n, n, axis=0)
              .astype(z.dtype))
        return acc + h @ d, None

    y, _ = lax.scan(part, jnp.zeros_like(z), jnp.arange(f // n))
    return y


def held_experts(z, full, gate, up, down, layer: int, first: int, q,
                 clamp: float):
    """sum over the HELD experts e of full[:, first + e] SwiGLU^e(z): every
    held expert on every token (a token that did not choose it has weight
    0), one expert's matrices cast at a time."""
    def one(acc, e):
        g, u, d = (q(a[layer, e].astype(z.dtype)) for a in (gate, up, down))
        w = lax.dynamic_index_in_dim(full, first + e, axis=1)
        return acc + w * (q(act(z @ g, z @ u, clamp)) @ d), None

    y, _ = lax.scan(one, jnp.zeros_like(z), jnp.arange(gate.shape[1]))
    return y


def expert_ffn(z, lp, moe, m: int, dims: Dims, q):
    """(the expert layer's output for normed z [S, d]: the held experts'
    part of the routed sum, and the shared expert; the experts chosen)."""
    full, chosen = routing(z, lp["router"], lp.get("router_bias"),
                           dims.top_k, dims.renorm, dims.score, dims.r_eps,
                           dims.r_scale)
    y = held_experts(z, full, moe["w_gate"], moe["w_up"], moe["w_down"], m,
                     dims.held[0], q, dims.clamp)
    if "w_shared_gate" in moe:           # every token, counted once
        g, u, d = (q(moe[k][m].astype(jnp.float32)) for k in (
            "w_shared_gate", "w_shared_up", "w_shared_down"))
        y = y + q(act(z @ g, z @ u, dims.clamp)) @ d
    return y, chosen


def hidden(params, tokens, dims: Dims, hold=None, fault=None,
           state_hold=None):
    """tokens [S] int32 -> (the last norm's output [S, d] float32, the
    experts each expert layer chose [n_expert_layers, S, k]). ``hold``: a
    dtype the weights and the values passed between operators are rounded
    to (None is the reference proper); ``state_hold``, ``fault``: the
    module docstring's."""
    f32 = jnp.float32
    q = _held(hold)
    x = q(params["embed"][tokens].astype(f32))   # gather, then cast
    stacks = params["layers"]
    norm = functools.partial(
        gated_norm, eps=dims.eps,
        gate=0.0 if fault == "plain_norm" else dims.norm_gate)

    def leaf(kind, i, name):
        return q(stacks[kind][name][i].astype(f32))

    def normed(x, w):
        return q(norm(x, w))

    small = ("attn_norm", "wq_a", "q_a_norm", "w_kva", "kv_norm")
    chosen, seen = [], {"attn": 0, "delta": 0}
    for i, latent in enumerate(dims.kinds):
        kind = "attn" if latent else "delta"
        n = seen[kind]
        seen[kind] += 1

        def raw(name, kind=kind, n=n):
            return stacks[kind][name][n] if name in stacks[kind] else None

        if latent:
            lp = {k: leaf(kind, n, k) for k in small if k in stacks[kind]}
            y = latent_mixer(normed(x, lp["attn_norm"]), lp, raw, dims, q,
                             fault)
        else:
            y = delta_mixer(normed(x, leaf(kind, n, "delta_norm")), raw,
                            dims, q, state_hold, fault)
        if "attn_post_norm" in stacks[kind]:
            y = norm(q(y), leaf(kind, n, "attn_post_norm"))
        x = q(x + y)
        ffn = "dense" if i < dims.n_dense else "moe"
        m = i if i < dims.n_dense else i - dims.n_dense
        z = normed(x, leaf(ffn, m, "mlp_norm"))
        if ffn == "dense":
            y = dense_ffn(z, lambda k, m=m: stacks["dense"][k][m], q,
                          dims.clamp)
        else:
            lp = {k: leaf("moe", m, k) for k in ("router", "router_bias")
                  if k in stacks["moe"]}
            y, e = expert_ffn(z, lp, stacks["moe"], m, dims, q)
            chosen.append(e)
        if "mlp_post_norm" in stacks[ffn]:
            y = norm(q(y), leaf(ffn, m, "mlp_post_norm"))
        x = q(x + y)
    out = normed(x, q(params["final_norm"].astype(f32)))
    return out, (jnp.stack(chosen) if chosen else None)


def forward(params, tokens, dims: Dims, hold=None, fault=None,
            state_hold=None):
    """tokens [S] int32 -> (logits [S, vocab] float32, chosen experts):
    the whole logits, for sizes at which they fit (the tests)."""
    x, chosen = hidden(params, tokens, dims, hold, fault, state_hold)
    return x @ _held(hold)(params["lm_head"].astype(jnp.float32)).T, chosen


def forward_logits(params, tokens, dims: Dims) -> jax.Array:
    with jax.default_matmul_precision("highest"):
        return forward(params, tokens, dims)[0]


@functools.partial(jax.jit, static_argnames=("dims", "hold", "precision",
                                             "fault", "state_hold"))
def token_scores(params, tokens, nxt, dims: Dims, hold=None,
                 precision="highest", fault=None, state_hold=None):
    """Per position of tokens [S]: the reference's argmax for the next
    token, and how far under its top logit the token ``nxt`` [S] sits
    there. One program per padded length; reference_kanana.token_scores'
    walk of the head in blocks of vocabulary rows. ``precision`` is
    "highest" for the reference proper; hold_gigachat.py asks for
    "bfloat16" beside a ``hold`` and a ``state_hold`` to read what
    computing in a lower precision than the configuration states costs."""
    with jax.default_matmul_precision(precision):
        x, _ = hidden(params, tokens, dims, hold, fault, state_hold)
        head = params["lm_head"]
        V = head.shape[0]
        nb = _vocab_blocks(V, 4100)
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


@functools.partial(jax.jit, static_argnames=("dims", "state_hold"))
def first_layer_state(params, tokens, n, dims: Dims, state_hold=None):
    """The recurrence of the FIRST layer alone, which is a delta layer and
    whose input is the embedding, so that it needs no layer before it:
    tokens [S] of which the first ``n`` count -> ((q, k [S, Hk, dk], v [S,
    Hv, dv], g, beta [S, Hv]) as the recurrence takes them, the state after
    token n - 1 [Hv, dk, dv]). A token that does not count neither decays
    nor writes (g = 0, beta = 0). What `correct` compares the program's
    recurrence with ON THE SAME INPUTS (checks_gigachat.MAX_STATE_ERROR):
    against the whole forward the bf16 around the recurrence weighs more
    than the precision the state is held in."""
    if dims.kinds[0]:
        raise ValueError("the first layer is no delta layer")
    with jax.default_matmul_precision("highest"):
        layer = params["layers"]["delta"]
        z = gated_norm(params["embed"][tokens].astype(jnp.float32),
                       layer["delta_norm"][0].astype(jnp.float32),
                       dims.eps, dims.norm_gate)
        qs, ks, vs, g, beta = delta_inputs(
            z, lambda name: layer[name][0], dims, lambda a: a)
        counts = (jnp.arange(tokens.shape[0]) < n)[:, None]
        g, beta = jnp.where(counts, g, 0.0), jnp.where(counts, beta, 0.0)
        _, state = recurrence(qs, ks, vs, g, beta, state_hold)
    return (qs, ks, vs, g, beta), state


def state_error(state, want) -> float:
    """How far a recurrent state [Hv, dk, dv] lies from the reference's:
    the largest, over the value heads, of |state_h - want_h| / |want_h|
    (Frobenius). A state rounded to bf16 ONCE reads about 1.6e-3."""
    state, want = (np.asarray(a, np.float64) for a in (state, want))
    off = np.sqrt(((state - want) ** 2).sum(axis=(1, 2)))
    return float((off / np.sqrt((want ** 2).sum(axis=(1, 2)))).max())


def score_greedy(params, dims: Dims, prompt: List[int], generated: List[int],
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
