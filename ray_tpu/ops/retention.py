"""Power retention of degree 2 (the gated, normalised linear attention of
the serving step).

Per key/value head g, with a log-gate a_g <= 0 a token, M = H / G query
heads reading each group's state, and phi: R^d -> R^D such that
phi(x) . phi(y) = (x . y)^2:

    S_g[t] = e^{a_g[t]} S_g[t-1] + phi(k_g[t]) v_g[t]^T        in R^{D x P}
    Z_g[t] = e^{a_g[t]} Z_g[t-1] + k_g[t] k_g[t]^T             in R^{d x d}
    o_j[t] = phi(q_j[t])^T S_g[t] / (q_j[t]^T Z_g[t] q_j[t] + eps)

which is, token for token, the quadratic form sum_s w(t, s) v[s] / (sum_s
w(t, s) + eps) with w(t, s) = e^{a[s+1] + .. + a[t]} (q[t] . k[s])^2. The
callers scale q and k (a scale s inside the power is sqrt(s) on each).

``phi`` (``expand``): x in 8-value blocks x_0 .. x_{n-1}; for each PAIR of
blocks a <= b the 64 products x_a[i] x_b[j], times sqrt(2) where a < b;
pairs in the order (0,0) (0,1) .. (0,n-1) (1,1) .. : D = 64 n (n + 1) / 2,
8704 = 68 x 128 at d = 128 (the least any expansion holds is d (d + 1) / 2 =
8256: a diagonal block keeps both of x[i] x[j] and x[j] x[i]). 64 rows of D
are then whole tiles of the held dtype, and what varies along them is ONE
value of x_a times an 8-value block of x_b: a sublane-broadcast row times
an 8-row tile, no lane moves.

The normaliser is the same sum under the expansion x (x) x, a symmetric
[d, d] matrix a group: phi(q) . z = q^T Z q, and the matrix costs two small
products where the expanded vector would cost an expansion of every query
head in HBM. It is held in float32.

State of every layer and batch slot: two leaves of the pool (llm/cache.py),
``state`` [layers, slots + 1, G, D, P] in the dtype it is held in (a
group's [D, P] block contiguous: D on the sublanes, the value's P on the
lanes) and ``norm`` [layers, slots + 1, G, d, d] float32; axis 1 is batch
slots, the last one scratch. Both entry points take the whole leaves and a
layer's index and return them, updated in place where a caller donates
them; a row whose first token has position 0 (``fresh``) starts from zeros
whatever its slot holds. Everything is computed in float32 and a row's
state is rounded to the held dtype ONCE, when the row's last token has gone
in; the read-out reads it before the rounding.

``retention_decode_update``  one token a row. A Pallas kernel
    (``_retention_update_pallas``): a grid step moves one slot's one
    group's [D, P] block through VMEM, in to out aliased, and the group's
    [d, d] normaliser with it; phi(k) and phi(q) exist only as tiles in
    VMEM. 2 D P values moved for (3 + 3 M) D P operations: HBM bounds it on
    paper, the vector unit is close behind.
``retention_chunk_scan``  ragged rows of many tokens: the flat token axis
    in blocks of ``chunk``; inside a block the masked quadratic form (no
    phi), the row's entering state read through phi(q) a group at a time
    and left updated. Plain jnp contractions (on a TPU at the default
    matmul precision: operands rounded to bf16, sums float32).

Each has a plain sequential reference for the CPU path and the tests
(``retention_decode_reference``, ``retention_scan_reference``), chosen as
ops/ssm.py's are (``impl``, ``interpret``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.paged_attention import _use_reference
from ray_tpu.ops.ssm import _rows_of, slot_rows, store_slot_rows

_F32 = jnp.float32
#: values a block of x: a float32 tile's sublanes
_BLOCK = 8
_SQRT2 = 2.0 ** 0.5
#: added to the normaliser
EPS = 1e-6


def expanded_dim(d: int) -> int:
    """D of ``expand`` for vectors of d values."""
    if d % _BLOCK:
        raise ValueError(f"the expansion takes whole blocks of {_BLOCK} "
                         f"values, got {d}")
    n = d // _BLOCK
    return _BLOCK * _BLOCK * n * (n + 1) // 2


def expand_pieces(x):
    """phi(x) over the last axis [..., d] as its n pieces, piece a the
    pairs (a, a) .. (a, n-1): [..., (n - a) * 64]. Whoever contracts over D
    can do it a piece at a time: the pieces are no whole number of lane
    tiles, and written side by side into one array they cost four times
    their bytes (PERF.md, PR 41)."""
    lead, n = x.shape[:-1], x.shape[-1] // _BLOCK
    expanded_dim(x.shape[-1])
    xb = x.reshape(lead + (n, _BLOCK))
    parts = []
    for a in range(n):
        c = jnp.where(jnp.arange(n - a) == 0, 1.0, _SQRT2).astype(x.dtype)
        pair = jnp.einsum("...i,...bj->...bij", xb[..., a, :], xb[..., a:, :])
        parts.append((pair * c[:, None, None]).reshape(lead + (-1,)))
    return parts


def expand(x):
    """phi(x) over the last axis [..., d] -> [..., D] (the module's
    docstring): phi(x) . phi(y) = (x . y)^2."""
    return jnp.concatenate(expand_pieces(x), axis=-1)


def _grouped(q, G: int):
    """[.., H, d] -> [.., G, M, d]: query head j reads group j // M."""
    return q.reshape(q.shape[:-2] + (G, q.shape[-2] // G, q.shape[-1]))


def _read_out(q, S, Z):
    """(phi(q)^T S, q^T Z q) for q [.., G, M, d], S [.., G, D, P], Z [..,
    G, d, d]."""
    num = jnp.einsum("...gmd,...gdp->...gmp", expand(q), S)
    den = jnp.einsum("...gmi,...gij,...gmj->...gm", q, Z, q)
    return num, den


# --------------------------------------------------------------------------
# one token a row
# --------------------------------------------------------------------------

def retention_decode_reference(state, norm, q, k, v, a, slots, fresh, layer):
    """The recurrence above for one token of each row, vectorised: q [R,
    H, d], k, v [R, G, d | P], a [R, G] (the log-gate), slots [R] (each
    row's slot; several rows may share the scratch slot, whose content is
    garbage), fresh [R] bool. Returns (o [R, H, P] float32, state, norm)."""
    q, k, v, a = (x.astype(_F32) for x in (q, k, v, a))
    R, H, _ = q.shape
    S = slot_rows(state, layer, slots).astype(_F32)       # [R, G, D, P]
    Z = slot_rows(norm, layer, slots)                     # [R, G, d, d]
    keep = jnp.where(fresh[:, None], 0.0, jnp.exp(a))[:, :, None, None]
    S = keep * S + expand(k)[..., None] * v[:, :, None, :]
    Z = keep * Z + k[..., None] * k[:, :, None, :]
    num, den = _read_out(_grouped(q, k.shape[1]), S, Z)
    o = (num / (den[..., None] + EPS)).reshape(R, H, -1)
    return o, store_slot_rows(state, layer, slots, S.astype(state.dtype)), \
        store_slot_rows(norm, layer, slots, Z)


def _update_kernel(layer_ref, slots_ref, fresh_ref, rows_ref, s_ref, z_ref,
                   so_ref, zo_ref, num_ref, den_ref, kcol_ref, kv_ref,
                   qcol_ref, *, M: int):
    """One row, one group. ``rows_ref`` [M + 3, d] float32: k, v, the M
    query heads, and the gate's e^a on every lane; ``s_ref`` / ``so_ref``
    the group's state in and out [D, P]; ``z_ref`` / ``zo_ref`` its
    normaliser [d, d]; ``num_ref`` [Mp, P]: row m phi(q_m)^T S; ``den_ref``
    [Mp, d]: row m the column sums of q_m q_m^T * Z (the caller adds the
    lanes up).

    k and each q are turned ONCE into [d, P] tiles whose every lane holds
    the column (``kcol``, ``qcol``), and k (x) v into ``kv``; the second
    copy of ``kv`` and ``qcol`` is times sqrt(2), for the pairs a < b. Then
    a pair of blocks (64 rows of D) at a time: row i of the pair's tile i
    is kv[8 a + i] (a sublane-broadcast row) times kcol's block b, added to
    the decayed state, rounded and stored, and multiplied by each query
    head's qcol[8 a + i] * qcol block b into that head's [8, P]
    accumulator, whose sublanes are summed at the end. Nothing moves across
    lanes inside the loop. A fresh row does not read its slot."""
    del layer_ref, slots_ref
    d, P = kcol_ref.shape
    n = d // _BLOCK
    x = rows_ref[...]
    k_row, v_row, keep = x[0:1], x[1:2], x[M + 2:M + 3]
    k_lanes = jnp.broadcast_to(k_row, (d, d))             # [j, i] = k[i]
    kcol = k_lanes.T                                      # [j, lane] = k[j]
    kcol_ref[...] = kcol
    kv_ref[0] = kcol * v_row
    kv_ref[1] = kcol * (v_row * _SQRT2)
    q_lanes = [jnp.broadcast_to(x[2 + m:3 + m], (d, d)) for m in range(M)]
    for m in range(M):
        qcol_ref[0, m] = q_lanes[m].T
        qcol_ref[1, m] = q_lanes[m].T * _SQRT2
    keep8 = jnp.broadcast_to(keep, (_BLOCK, P))
    pad = [jnp.zeros((num_ref.shape[0] - M, P), _F32)] \
        if num_ref.shape[0] > M else []

    def run(read: bool):
        z = k_lanes * kcol
        if read:
            z = z + keep * z_ref[...]
        zo_ref[...] = z
        den_ref[...] = jnp.concatenate(
            [jnp.sum(q_lanes[m] * qcol_ref[0, m] * z, axis=0, keepdims=True)
             for m in range(M)] + pad, axis=0)

        def pair(a, b, far: int, accs):
            rows = pl.ds(pl.multiple_of(
                (a * n - (a * (a - 1)) // 2 + (b - a)) * _BLOCK * _BLOCK,
                _BLOCK * _BLOCK), _BLOCK * _BLOCK)
            blk = pl.ds(pl.multiple_of(b * _BLOCK, _BLOCK), _BLOCK)
            s = s_ref[rows, :].astype(_F32) if read else None
            kblk = kcol_ref[blk, :]
            qblk = [qcol_ref[0, m, blk, :] for m in range(M)]
            accs, new = list(accs), []
            for i in range(_BLOCK):
                at = pl.ds(a * _BLOCK + i, 1)
                t = kv_ref[far, at, :] * kblk
                if read:
                    t = t + keep8 * s[i * _BLOCK:(i + 1) * _BLOCK]
                new.append(t)
                for m in range(M):
                    accs[m] = accs[m] + (qcol_ref[far, m, at, :]
                                         * qblk[m]) * t
            so_ref[rows, :] = jnp.concatenate(new, axis=0).astype(
                so_ref.dtype)
            return tuple(accs)

        def over_a(a, accs):
            return lax.fori_loop(
                a + 1, n, lambda b, accs: pair(a, b, 1, accs),
                pair(a, a, 0, accs))

        accs = lax.fori_loop(
            0, n, over_a, (jnp.zeros((_BLOCK, P), _F32),) * M)
        num_ref[...] = jnp.concatenate(
            [jnp.sum(acc, axis=0, keepdims=True) for acc in accs] + pad,
            axis=0)

    is_fresh = fresh_ref[pl.program_id(0)] != 0
    pl.when(is_fresh)(lambda: run(False))
    pl.when(jnp.logical_not(is_fresh))(lambda: run(True))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _retention_update_pallas(state, norm, q, k, v, a, slots, fresh, layer,
                             interpret: bool = False):
    """(phi(q)^T S [R, H, P], q^T Z q [R, H], state, norm) for one token of
    each row; ``layer`` [1] int32. Both leaves are aliased in to out: only
    the rows' slots move."""
    R, H, d = q.shape
    G, D, P = state.shape[2:]
    M = H // G
    if d != P or D != expanded_dim(d) or norm.shape[2:] != (G, d, d):
        raise ValueError(f"state {state.shape} and normaliser {norm.shape} "
                         f"are not those of {G} groups of {d}")
    q, k, v, a = (x.astype(_F32) for x in (q, k, v, a))
    rows = jnp.concatenate(
        [k[:, :, None], v[:, :, None], _grouped(q, G),
         jnp.broadcast_to(jnp.exp(a)[:, :, None, None], (R, G, 1, d))],
        axis=2)                                           # [R, G, M + 3, d]
    Mp = -(-M // _BLOCK) * _BLOCK
    s_block = pl.BlockSpec((None, None, None, D, P),
                           lambda r, g, l, s, f: (l[0], s[r], g, 0, 0))
    z_block = pl.BlockSpec((None, None, None, d, d),
                           lambda r, g, l, s, f: (l[0], s[r], g, 0, 0))

    def row_block(n_rows):
        return pl.BlockSpec((None, None, n_rows, d),
                            lambda r, g, *_: (r, g, 0, 0))

    block_bytes = D * P * state.dtype.itemsize
    state, norm, num, den = pl.pallas_call(
        functools.partial(_update_kernel, M=M),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(R, G),
            in_specs=[row_block(M + 3), s_block, z_block],
            out_specs=[s_block, z_block, row_block(Mp), row_block(Mp)],
            scratch_shapes=[pltpu.VMEM((d, P), _F32),
                            pltpu.VMEM((2, d, P), _F32),
                            pltpu.VMEM((2, M, d, P), _F32)],
        ),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct(norm.shape, norm.dtype),
                   jax.ShapeDtypeStruct((R, G, Mp, P), _F32),
                   jax.ShapeDtypeStruct((R, G, Mp, d), _F32)],
        # operands count the scalar-prefetch arrays: the state is the 5th
        input_output_aliases={4: 0, 5: 1},
        cost_estimate=pl.CostEstimate(
            flops=(3 + 3 * M) * R * G * D * P, transcendentals=0,
            bytes_accessed=2 * R * G * (block_bytes + 4 * d * d)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # the group's block in and out, each twice (pipelined)
            vmem_limit_bytes=max(6 * block_bytes, 16 << 20)),
        name="_retention_update_pallas",
        interpret=interpret,
    )(layer, slots.astype(jnp.int32), fresh.astype(jnp.int32), rows, state,
      norm)
    return num[:, :, :M].reshape(R, H, P), \
        den[:, :, :M].sum(axis=-1).reshape(R, H), state, norm


def retention_decode_update(state, norm, q, k, v, a, slots, fresh, *, layer,
                            impl: Optional[str] = None,
                            interpret: Optional[bool] = None):
    """One token of each row through layer ``layer`` of both leaves
    (``retention_decode_reference``'s arguments and result). Rows without a
    token name the scratch slot. The kernel on a TPU (``impl`` "kernel", or
    ``interpret``), the reference elsewhere."""
    if _use_reference(impl, interpret):
        return retention_decode_reference(state, norm, q, k, v, a, slots,
                                          fresh, layer)
    num, den, state, norm = _retention_update_pallas(
        state, norm, q, k, v, a, slots, fresh,
        jnp.asarray(layer, jnp.int32).reshape(1), bool(interpret))
    return num / (den[..., None] + EPS), state, norm


# --------------------------------------------------------------------------
# ragged rows of many tokens
# --------------------------------------------------------------------------

def retention_scan_reference(state, norm, q, k, v, a, pos, q_start, q_len,
                             row_slot, layer):
    """The recurrence over a flat ragged batch, token after token
    (``lax.scan``): q [T, H, d], k, v [T, G, d | P], a [T, G], pos [T]; row
    r owns tokens q_start[r] .. + q_len[r] - 1 and slot row_slot[r] (the
    scratch slot where q_len is 0), starts from its slot's state, or from
    zeros where its first position is 0, and leaves its last state there.
    Tokens no row owns give zeros. Returns (o [T, H, P] float32, state,
    norm)."""
    T, H, _ = q.shape
    G = k.shape[1]
    q, k, v, a = (x.astype(_F32) for x in (q, k, v, a))
    own, fresh = _rows_of(T, pos, q_start, q_len)
    zero = fresh[:, None, None, None]
    S = jnp.where(zero, 0.0, slot_rows(state, layer, row_slot).astype(_F32))
    Z = jnp.where(zero, 0.0, slot_rows(norm, layer, row_slot))
    row = jnp.argmax(own, axis=1)
    valid = own.any(axis=1)

    def step(carry, inp):
        S, Z = carry
        qt, kt, vt, at, r, ok = inp
        keep = jnp.exp(at)[:, None, None]
        s = keep * S[r] + expand(kt)[..., None] * vt[:, None, :]
        z = keep * Z[r] + kt[..., None] * kt[:, None, :]
        num, den = _read_out(_grouped(qt, G), s, z)
        o = jnp.where(ok, num / (den[..., None] + EPS), 0.0)
        return (S.at[r].set(jnp.where(ok, s, S[r])),
                Z.at[r].set(jnp.where(ok, z, Z[r]))), o.reshape(H, -1)

    (S, Z), o = lax.scan(step, (S, Z), (q, k, v, a, row, valid))
    return o, store_slot_rows(state, layer, row_slot, S.astype(state.dtype)), \
        store_slot_rows(norm, layer, row_slot, Z)


def retention_chunk_scan(state, norm, q, k, v, a, pos, q_start, q_len,
                         row_slot, *, layer, chunk: int = 256,
                         impl: Optional[str] = None,
                         interpret: Optional[bool] = None):
    """``retention_scan_reference``'s arguments and result in the chunked
    form (the module's docstring), equal to it in exact arithmetic however
    the rows fall into blocks. A row's tokens are consecutive, so between
    two tokens of one row lie only that row's tokens, and the decay between
    them is a difference of ONE cumulative sum over the block. phi(q) and
    phi(k) exist for one block and one group at a time."""
    if _use_reference(impl, interpret):
        return retention_scan_reference(state, norm, q, k, v, a, pos,
                                        q_start, q_len, row_slot, layer)
    T, H, _ = q.shape
    G, R = k.shape[1], row_slot.shape[0]
    Q = min(chunk, T)
    q, k, v, a = (jnp.pad(x.astype(_F32),
                          ((0, -T % Q),) + ((0, 0),) * (x.ndim - 1))
                  for x in (q, k, v, a))
    own, fresh = _rows_of(q.shape[0], pos, q_start, q_len)
    valid = own.any(axis=1)
    a = jnp.where(valid[:, None], a, 0.0)                 # [T, G], <= 0
    k, v = (jnp.where(valid[:, None, None], x, 0.0) for x in (k, v))
    zero = fresh[:, None, None, None]
    S = jnp.where(zero, 0.0, slot_rows(state, layer, row_slot).astype(_F32))
    Z = jnp.where(zero, 0.0, slot_rows(norm, layer, row_slot))
    causal = jnp.tril(jnp.ones((Q, Q), bool))

    def exp_where(keep, e):
        # masked inside too: an exponent that is not kept may overflow
        return jnp.where(keep, jnp.exp(jnp.where(keep, e, 0.0)), 0.0)

    held = state.dtype

    def block(carry, inp):
        # G-tuples of [R, D, P] and [R, d, d]; [R]: the row's state is not
        # known to be zeros (it started from its slot's, or owned a block)
        S, Z, full = carry
        a, q, k, v, own = inp         # [Q, G] [Q, G, M, d] [Q, G, d|P] [Q, R]
        of = own.astype(_F32)
        live = own.any(axis=1, keepdims=True)             # [Q, 1]
        cum = jnp.cumsum(a, axis=0)                       # inclusive
        # the sum before each row's first token of this block, and up to
        # its last
        before = jnp.einsum("tr,tg->rg", of, a)
        first = jnp.argmax(own, axis=0)                   # [R]
        start = cum[first] - a[first]                     # [R, G]
        end = start + before
        # a token against the earlier tokens of its own row, a group at a
        # time: [G, t, s]
        mask = (causal & (of @ of.T > 0))[None]
        decay = exp_where(mask, cum.T[:, :, None] - cum.T[:, None, :])
        w = jnp.einsum("tgmd,sgd->gmts", q, k) ** 2 * decay[:, None]
        num = jnp.einsum("gmts,sgp->tgmp", w, v)
        den = w.sum(axis=-1).transpose(2, 0, 1)           # [Q, G, M]

        def entering(S, Z):
            # ... against its row's state at the block's start, through
            # phi(q): a group and a piece of phi at a time, the operands of
            # the product in the dtype the state is held in (what the
            # matrix unit rounds them to in any case, at half the bytes)
            since = exp_where(live, cum - of @ start)     # [Q, G]
            nums, dens = [], []
            for g in range(len(S)):
                n_g, at = 0.0, 0
                for fq in expand_pieces(q[:, g]):         # [Q, M, a piece]
                    rows = slice(at, at + fq.shape[-1])
                    n_g = n_g + sum(of[:, r, None, None] * jnp.einsum(
                        "tmd,dp->tmp", fq.astype(held),
                        S[g][r, rows].astype(held),
                        preferred_element_type=_F32) for r in range(R))
                    at = rows.stop
                d_g = sum(of[:, r, None] * jnp.einsum(
                    "tmi,ij,tmj->tm", q[:, g], Z[g][r], q[:, g])
                    for r in range(R))
                nums.append(since[:, g, None, None] * n_g)
                dens.append(since[:, g, None] * d_g)
            return jnp.stack(nums, axis=1), jnp.stack(dens, axis=1)

        # skipped where no row of the block has a state to read: a prompt's
        # first block reads zeros, and phi(q) is the chunk form's largest
        # intermediate
        n_in, d_in = lax.cond(
            (own.any(axis=0) & full).any(), entering,
            lambda S, Z: (jnp.zeros_like(num), jnp.zeros_like(den)), S, Z)
        # ... which it leaves as at the block's end
        left = exp_where(live, of @ end - cum)
        grow = jnp.exp(before)                            # [R, G]
        S_out, Z_out = [], []
        for g in range(len(S)):
            fk = expand(k[:, g])                          # [Q, D]
            lk = (left[:, g, None] * of).T                # [R, Q]
            S_out.append(grow[:, g, None, None] * S[g] + jnp.stack([
                jnp.einsum("sd,sp->dp", (lk[r][:, None] * fk).astype(held),
                           v[:, g].astype(held),
                           preferred_element_type=_F32) for r in range(R)]))
            Z_out.append(grow[:, g, None, None] * Z[g] + jnp.einsum(
                "rs,si,sj->rij", lk, k[:, g], k[:, g]))
        o = (num + n_in) / ((den + d_in)[..., None] + EPS)
        return (tuple(S_out), tuple(Z_out), full | own.any(axis=0)), o

    def maybe(carry, inp):
        # the step's shape is static and most of its chunk tokens are
        # padding most of the time: a block no row owns a token of is
        # skipped (its o is zeros, as the full computation gives)
        return lax.cond(
            inp[-1].any(), block,
            lambda carry, inp: (carry, jnp.zeros(
                inp[1].shape[:-1] + (inp[3].shape[-1],), _F32)),
            carry, inp)

    def blocks(arr):
        return arr.reshape((-1, Q) + arr.shape[1:])

    def groups(x):
        return tuple(x[:, g] for g in range(G))

    # unrolled, as ops/ssm.py's scan is and for its reason
    (S, Z, _), o = lax.scan(
        maybe, (groups(S), groups(Z), jnp.logical_not(fresh)),
        tuple(map(blocks, (a, _grouped(q, G), k, v, own))), unroll=True)
    o = o.reshape((-1, H, o.shape[-1]))[:T]
    return o, store_slot_rows(state, layer, row_slot,
                              jnp.stack(S, axis=1).astype(state.dtype)), \
        store_slot_rows(norm, layer, row_slot, jnp.stack(Z, axis=1))
