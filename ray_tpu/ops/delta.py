"""The gated delta rule (gated DeltaNet linear attention) of the serving step.

Per value head h, with a state S_h in R^{dk x dv} (key on the rows, value on
the columns), a log-decay g_h[t] <= 0 and a write strength beta_h[t] in
(0, 1); the key head of value head h is h // (Hv / Hk):

    S_h <- exp(g_h[t]) S_h
    S_h <- S_h + k[t] (x) beta_h[t] (v_h[t] - S_h^T k[t])
    o_h[t] = S_h^T q[t]

Where Mamba-2's and the retention's states decay and ADD (ops/ssm.py,
ops/retention.py), this one READS the state it is about to write: the write
is a rank-one CORRECTION towards v along k.

The state of every layer and batch slot is ONE leaf of the pool, [layers,
slots + 1, Hv, dk, dv] FLOAT32 (llm/cache.py: axis 1 is batch slots, the
last one scratch): the family's modelling code carries the recurrent state
in float32, and so does the pool; nothing here rounds it. Both entry points
take the whole leaf and a layer's index and return the leaf, updated in
place where a caller donates it; a row whose first token has position 0
(``fresh``) starts from zeros whatever its slot holds, so nothing ever
zeroes a slot.

``delta_decode_update``  one token a row. A Pallas kernel
    (``_delta_update_pallas``): a grid step moves ``hb`` heads of one slot's
    state through VMEM, in to out aliased; a head's [dk, dv] tile is read
    against k and q held as COLUMNS (a key value a sublane, the same on
    every lane), reduced over its sublanes, corrected and stored: 2 dk dv
    float32 values moved for ~7 dk dv operations, so HBM bounds it.
``delta_chunk_scan``  ragged rows of many tokens, in the chunk form: the
    flat token axis in blocks of ``chunk``. Inside a block the correction
    of token t depends on the corrections of the earlier tokens of ITS OWN
    ROW: with gamma the cumulative log-decay and A[t, s] = beta_t
    exp(gamma_t - gamma_s) (k_t . k_s) for s < t (the WY representation),

        U = (I + A)^-1 (beta V - beta exp(gamma) K S_0)
        O = exp(gamma) Q S_0 + (exp(gamma_t - gamma_s) (q_t . k_s))_{s<=t} U
        S_end = exp(gamma_end) S_0 + (exp(gamma_end - gamma) K)^T U

    and the rows' states ride from block to block, as ops/ssm.py's do. The
    triangular solve is block forward substitution written as matmuls
    (``_unit_lower_inverse``). All of it float32 at the highest matmul
    precision: the recurrence is the float32 part of this block.

Each has a plain sequential reference for the CPU path and the tests
(``delta_decode_reference``, ``delta_scan_reference``: the three lines
above, token after token), chosen as the paged attention's is (``impl``,
``interpret``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.paged_attention import _use_reference
from ray_tpu.ops.ssm import _rows_of, slot_rows, store_slot_rows

_F32 = jnp.float32
_HI = lax.Precision.HIGHEST


def _per_value_head(a, Hv: int):
    """q or k [T, Hk, dk] as [T, Hv, dk]: key head j serves value heads
    j Hv/Hk .. (j + 1) Hv/Hk - 1."""
    return a if a.shape[1] == Hv else jnp.repeat(a, Hv // a.shape[1], axis=1)


def _token(s, q, k, v, g, beta):
    """The three lines of the module docstring for one token, over any
    leading axes: s [.., H, dk, dv], q, k [.., H, dk], v [.., H, dv], g,
    beta [.., H]. Returns (o [.., H, dv], s)."""
    s = jnp.exp(g)[..., None, None] * s
    seen = jnp.einsum("...hkv,...hk->...hv", s, k, precision=_HI)
    s = s + k[..., :, None] * (beta[..., None] * (v - seen))[..., None, :]
    return jnp.einsum("...hkv,...hk->...hv", s, q, precision=_HI), s


# --------------------------------------------------------------------------
# one token a row
# --------------------------------------------------------------------------

def delta_decode_reference(state, q, k, v, g, beta, slots, fresh, layer):
    """The recurrence for one token of each row, vectorised: state [L, S +
    1, Hv, dk, dv] float32, q, k [R, Hk, dk] (normalised, q scaled), v [R,
    Hv, dv], g, beta [R, Hv], slots [R] (each row's slot; several rows may
    share the scratch slot, whose content is garbage), fresh [R] bool.
    Returns (o [R, Hv, dv] float32, state)."""
    Hv = v.shape[1]
    q, k, v, g, beta = (a.astype(_F32) for a in (q, k, v, g, beta))
    s = slot_rows(state, layer, slots).astype(_F32)
    s = jnp.where(fresh[:, None, None, None], 0.0, s)
    o, s = _token(s, _per_value_head(q, Hv), _per_value_head(k, Hv), v, g,
                  beta)
    return o, store_slot_rows(state, layer, slots, s.astype(state.dtype))


#: value heads of a slot's state a grid step of the update kernel moves: 64
#: tiles of [128, 128] float32 are 4 MB, in and out and twice over (the
#: pipeline) 16 MB of VMEM; their k and q columns side by side fill the 128
#: lanes of one operand (at 16 heads a step the operand's 32 lanes were
#: padded to 128 in HBM: 84 MB written and read a call for 10 MB of values)
_UPDATE_HEADS = 64


def _update_kernel(layer_ref, slots_ref, fresh_ref, kq_ref, rows_ref, s_ref,
                   o_ref, y_ref):
    """``hb`` heads of one row: ``s_ref`` / ``o_ref`` their states in and
    out [hb, dk, dv]; ``kq_ref`` [dk, 2 hb] the heads' k, then their q,
    TRANSPOSED: a key value a sublane and a head a lane, so that a head's
    k is one lane broadcast over the tile's lanes; ``rows_ref`` [3, hb, dv] float32:
    beta v, beta and the decay exp(g), the last two the same on every lane;
    ``y_ref`` [hb, dv] the read-out. A fresh row does not read its slot."""
    del layer_ref, slots_ref
    hb, dk, dv = s_ref.shape

    def run(read: bool):
        for i in range(hb):
            kc = jnp.broadcast_to(kq_ref[:, i:i + 1], (dk, dv))
            qc = jnp.broadcast_to(kq_ref[:, hb + i:hb + i + 1], (dk, dv))
            u = rows_ref[0, i:i + 1, :]                        # [1, dv]
            if read:
                d = rows_ref[2, i:i + 1, :]
                s = d * s_ref[i]
                u = u - rows_ref[1, i:i + 1, :] \
                    * jnp.sum(s * kc, axis=0, keepdims=True)
                s = s + kc * u
            else:
                s = kc * u
            o_ref[i] = s
            y_ref[i:i + 1, :] = jnp.sum(s * qc, axis=0, keepdims=True)

    is_fresh = fresh_ref[pl.program_id(0)] != 0
    pl.when(is_fresh)(lambda: run(False))
    pl.when(jnp.logical_not(is_fresh))(lambda: run(True))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _delta_update_pallas(state, q, k, v, g, beta, slots, fresh, layer,
                         interpret: bool = False):
    """(o [R, Hv, dv] float32, state) for one token of each row; q, k [R,
    Hv, dk] (a key head's already repeated), ``layer`` [1] int32. The state
    is aliased in to out: only the rows' slots move."""
    R, Hv, dv = v.shape
    dk = k.shape[-1]
    hb = min(_UPDATE_HEADS, Hv)
    if Hv % hb:
        raise ValueError(f"{Hv} value heads are no whole number of blocks "
                         f"of {hb}")
    nb = Hv // hb
    # [R, nb, dk, 2 hb]: a block's k, then its q, a head a lane
    kq = jnp.stack([k, q], axis=1).reshape(R, 2, nb, hb, dk) \
        .transpose(0, 2, 4, 1, 3).reshape(R, nb, dk, 2 * hb)
    wide = (R, Hv, dv)
    rows = jnp.stack([beta[:, :, None] * v,
                      jnp.broadcast_to(beta[:, :, None], wide),
                      jnp.broadcast_to(jnp.exp(g)[:, :, None], wide)],
                     axis=1)                                 # [R, 3, Hv, dv]
    block = pl.BlockSpec((None, None, hb, dk, dv),
                         lambda r, j, l, s, f: (l[0], s[r], j, 0, 0))
    block_bytes = hb * dk * dv * 4
    state, y = pl.pallas_call(
        _update_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(R, nb),
            in_specs=[pl.BlockSpec((None, None, dk, 2 * hb),
                                   lambda r, j, *_: (r, j, 0, 0)),
                      pl.BlockSpec((None, 3, hb, dv),
                                   lambda r, j, *_: (r, 0, j, 0)),
                      block],
            out_specs=[block,
                       pl.BlockSpec((None, hb, dv),
                                    lambda r, j, *_: (r, j, 0))],
        ),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((R, Hv, dv), _F32)],
        # operands count the scalar-prefetch arrays: the state is the 6th
        input_output_aliases={5: 0},
        cost_estimate=pl.CostEstimate(
            flops=7 * R * Hv * dk * dv, transcendentals=0,
            bytes_accessed=2 * R * nb * block_bytes),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # the block in and out, each twice (pipelined), and the tiles
            # of a head's k and q held as columns
            vmem_limit_bytes=max(6 * block_bytes, 16 << 20)),
        name="_delta_update_pallas",
        interpret=interpret,
    )(layer, slots.astype(jnp.int32), fresh.astype(jnp.int32), kq, rows,
      state)
    return y, state


def delta_decode_update(state, q, k, v, g, beta, slots, fresh, *, layer,
                        impl: Optional[str] = None,
                        interpret: Optional[bool] = None):
    """One token of each row through layer ``layer`` of the state leaf
    (``delta_decode_reference``'s arguments and result). Rows without a
    token name the scratch slot. The kernel on a TPU (``impl`` "kernel",
    or ``interpret``), the reference elsewhere."""
    if _use_reference(impl, interpret):
        return delta_decode_reference(state, q, k, v, g, beta, slots, fresh,
                                      layer)
    if state.dtype != _F32:
        raise ValueError(f"the update kernel moves a float32 state; the "
                         f"leaf is {state.dtype}")
    Hv = v.shape[1]
    q, k, v, g, beta = (a.astype(_F32) for a in (q, k, v, g, beta))
    return _delta_update_pallas(
        state, _per_value_head(q, Hv), _per_value_head(k, Hv), v, g, beta,
        slots, fresh, jnp.asarray(layer, jnp.int32).reshape(1),
        bool(interpret))


# --------------------------------------------------------------------------
# ragged rows of many tokens
# --------------------------------------------------------------------------

def delta_scan_reference(state, q, k, v, g, beta, pos, q_start, q_len,
                         row_slot, layer):
    """The recurrence over a flat ragged batch, token after token
    (``lax.scan``): q, k [T, Hk, dk], v [T, Hv, dv], g, beta [T, Hv], pos
    [T]; row r owns tokens q_start[r] .. + q_len[r] - 1 and slot
    row_slot[r] (the scratch slot where q_len is 0), starts from its slot's
    state, or from zeros where its first position is 0, and leaves its last
    state there. Tokens no row owns give zeros. Returns (o [T, Hv, dv]
    float32, state)."""
    T, Hv = v.shape[:2]
    q, k, v, g, beta = (a.astype(_F32) for a in (q, k, v, g, beta))
    q, k = _per_value_head(q, Hv), _per_value_head(k, Hv)
    own, fresh = _rows_of(T, pos, q_start, q_len)
    rows = slot_rows(state, layer, row_slot).astype(_F32)
    rows = jnp.where(fresh[:, None, None, None], 0.0, rows)
    row = jnp.argmax(own, axis=1)
    valid = own.any(axis=1)

    def step(rows, inp):
        qt, kt, vt, gt, bt, r, ok = inp
        o, new = _token(rows[r], qt, kt, vt, gt, bt)
        return rows.at[r].set(jnp.where(ok, new, rows[r])), \
            jnp.where(ok, o, 0.0)

    rows, o = lax.scan(step, rows, (q, k, v, g, beta, row, valid))
    return o, store_slot_rows(state, layer, row_slot,
                              rows.astype(state.dtype))


def _unit_lower_inverse(a):
    """(I + a)^-1 for a strictly lower-triangular a [.., Q, Q], Q a power
    of two: block forward substitution as matmuls. T starts as the inverse
    of the diagonal (I) and doubles the blocks it is exact on: with M_b the
    entries of a that join the two halves of a block of 2b (its lower left
    quarter), T <- T - T (a * M_b) T puts -T22 a21 T11 there and changes
    nothing else, T being block diagonal in blocks of b. log2(Q) turns, no
    division and nothing that grows: the powers of a never form."""
    Q = a.shape[-1]
    if Q & (Q - 1):
        raise ValueError(f"a block of {Q} tokens is no power of two")
    t = jnp.broadcast_to(jnp.eye(Q, dtype=a.dtype), a.shape)
    i = np.arange(Q)
    b = 1
    while b < Q:
        joins = (i[:, None] // (2 * b) == i[None, :] // (2 * b)) \
            & (i[:, None] // b % 2 == 1) & (i[None, :] // b % 2 == 0)
        t = t - jnp.matmul(jnp.matmul(t, a * joins, precision=_HI), t,
                           precision=_HI)
        b *= 2
    return t


def delta_chunk_scan(state, q, k, v, g, beta, pos, q_start, q_len, row_slot,
                     *, layer, chunk: int = 64, impl: Optional[str] = None,
                     interpret: Optional[bool] = None):
    """``delta_scan_reference``'s arguments and result in the chunk form
    (the module's docstring), equal to it in exact arithmetic however the
    rows fall into blocks. A row's tokens are consecutive, so between two
    tokens of one row lie only that row's tokens, and the decay between
    them is a difference of ONE cumulative sum over the block; two rows
    that share a block share nothing else (A, and the scores, are masked
    to pairs of one row, so the solve decouples)."""
    if _use_reference(impl, interpret):
        return delta_scan_reference(state, q, k, v, g, beta, pos, q_start,
                                    q_len, row_slot, layer)
    T, Hv = v.shape[:2]
    Q = min(chunk, 1 << (max(T, 1) - 1).bit_length())
    q, k, v, g, beta = (jnp.pad(a.astype(_F32),
                                ((0, -T % Q),) + ((0, 0),) * (a.ndim - 1))
                        for a in (q, k, v, g, beta))
    q, k = _per_value_head(q, Hv), _per_value_head(k, Hv)
    own, fresh = _rows_of(v.shape[0], pos, q_start, q_len)
    valid = own.any(axis=1)
    a = jnp.where(valid[:, None], g, 0.0)                  # [T, H], <= 0
    beta = jnp.where(valid[:, None], beta, 0.0)
    rows = slot_rows(state, layer, row_slot).astype(_F32)  # [R, H, dk, dv]
    rows = jnp.where(fresh[:, None, None, None], 0.0, rows)
    R = rows.shape[0]
    lower = jnp.tril(jnp.ones((Q, Q), bool))
    strict = jnp.tril(jnp.ones((Q, Q), bool), -1)
    mm = functools.partial(jnp.einsum, precision=_HI)

    def exp_where(keep, e):
        # masked inside too: an exponent that is not kept may overflow
        return jnp.where(keep, jnp.exp(jnp.where(keep, e, 0.0)), 0.0)

    def block(rows, inp):
        a, b, q, k, v, own = inp    # [Q, H] x2, [Q, H, dk] x2, [Q, H, dv]
        of = own.astype(_F32)                              # [Q, R]
        live = own.any(axis=1, keepdims=True)              # [Q, 1]
        cum = jnp.cumsum(a, axis=0)                        # inclusive
        # the sum before each row's first token of this block, and up to
        # its last
        before = mm("tr,th->rh", of, a)
        first = jnp.argmax(own, axis=0)                    # [R]
        start = cum[first] - a[first]                      # [R, H]
        end = start + before
        same = mm("tr,sr->ts", of, of) > 0                 # [Q, Q]
        # exp(gamma_t - gamma_s) for the pairs of one row, s <= t: [H, t, s]
        decay = exp_where((lower & same)[None],
                          cum.T[:, :, None] - cum.T[:, None, :])
        since = exp_where(live, cum - mm("tr,rh->th", of, start))
        left = exp_where(live, mm("tr,rh->th", of, end) - cum)
        inv = _unit_lower_inverse(
            jnp.where(strict[None], decay, 0.0) * b.T[:, :, None]
            * mm("thk,shk->hts", k, k))
        # U = inv (beta V - beta exp(gamma) K S_0), a row against its own
        # state
        u = mm("hts,shv->thv", inv, b[:, :, None] * v)
        w = mm("hts,shk->thk", inv, (b * since)[:, :, None] * k)
        qs = since[:, :, None] * q
        o = jnp.zeros_like(v)
        for r in range(R):
            mine = of[:, r][:, None, None]
            u = u - mine * mm("thk,hkv->thv", w, rows[r])
            o = o + mine * mm("thk,hkv->thv", qs, rows[r])
        o = o + mm("hts,shv->thv", decay * mm("thk,shk->hts", q, k), u)
        # the rows' states at the block's end
        kl = left[:, :, None] * k
        rows = jnp.exp(before)[:, :, None, None] * rows + jnp.stack(
            [mm("thk,thv->hkv", of[:, r][:, None, None] * kl, u)
             for r in range(R)])
        return rows, o

    def maybe(rows, inp):
        # the step's shape is static and most of its chunk tokens are
        # padding most of the time: a block no row owns a token of is
        # skipped (its o is zeros, as the full computation gives)
        return lax.cond(
            inp[-1].any(), block,
            lambda rows, inp: (rows, jnp.zeros(inp[4].shape, _F32)),
            rows, inp)

    def blocks(arr):
        return arr.reshape((-1, Q) + arr.shape[1:])

    # unrolled, as ops/ssm.py's scan is and for its reason (PERF.md, PR 37)
    rows, o = lax.scan(maybe, rows,
                       tuple(map(blocks, (a, beta, q, k, v, own))),
                       unroll=True)
    o = o.reshape((-1,) + v.shape[1:])[:T]
    return o, store_slot_rows(state, layer, row_slot,
                              rows.astype(state.dtype))
