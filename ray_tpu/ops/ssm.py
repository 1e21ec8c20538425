"""The selective state-space recurrence (Mamba-2) of the serving step.

Per head h, with a scalar A_h < 0 and a state S_h in R^{P x N}; B and C
[N] are shared by all heads (one group); dt is the softplus'd step:

    S_h[t] = exp(dt_h[t] A_h) S_h[t-1] + dt_h[t] x_h[t] B[t]^T
    y_h[t] = S_h[t] C[t] + D_h x_h[t]

The state of every layer and batch slot is ONE leaf of the pool,
[layers, slots + 1, N, H P] in the dtype it is held in (llm/cache.py:
axis 1 is batch slots, the last one scratch; a slot's matrix lies
TRANSPOSED, a state column n a row of H P values, so that the update
kernel finds what varies with (h, p) on the lanes and what varies with n
on the sublanes and broadcasts nothing across lanes). Both entry points take
the whole leaf and a layer's index and return the leaf, updated in place
where a caller donates it; a row whose first token has position 0 (``fresh``)
starts from zeros whatever its slot holds, so nothing ever zeroes a slot.
The recurrence is computed in float32 and a row's state is rounded to the
held dtype ONCE, when the row's last token has gone in.

``ssm_decode_update``  one token a row: read the slot's state, decay it,
    add the outer product, read out, write it back. A Pallas kernel
    (``_ssm_update_pallas``): a grid step moves one slot's [N, H P] block
    through VMEM, in to out aliased; 2 H P N values moved for ~5 H P N
    operations, so HBM bounds it.
``ssm_chunk_scan``  ragged rows of many tokens, in the chunked form: the
    flat token axis in blocks of ``chunk``; inside a block a token sees the
    earlier tokens of ITS OWN ROW through L[t, s] = exp(sum_{s<r<=t} dt_r
    A) and (C_t . B_s), and its row's state at the block's start through
    exp(sum_{r<=t} dt_r A); the rows' states ride from block to block.
    Plain jnp contractions (on a TPU at the default matmul precision: the
    operands of a product are rounded to bf16 and summed in float32, as
    the family's own kernels do; decays, cumulative sums and the carried
    state are float32).

Each has a plain sequential reference for the CPU path and the tests:
``ssm_decode_reference`` (the formula, vectorised over rows) and
``ssm_scan_reference`` (``lax.scan`` over the tokens), chosen as the paged
attention's is (``impl``, ``interpret``).
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

_F32 = jnp.float32


# --------------------------------------------------------------------------
# one token a row
# --------------------------------------------------------------------------

def slot_rows(state, layer, slots):
    """The slots' entries [R, ...] of one layer of a state leaf [layers,
    slots + 1, ...], as held, read slot after slot by dynamic_slice
    (``store_slot_rows`` says why no gather)."""
    return jnp.stack([lax.dynamic_slice(
        state, (layer, slots[r]) + (0,) * (state.ndim - 2),
        (1, 1) + state.shape[2:])[0, 0] for r in range(slots.shape[0])])


def store_slot_rows(state, layer, slots, rows):
    """``rows`` [R, ...] (in the leaf's dtype) back to the slots, slot
    after slot by dynamic_update_slice: XLA reads and writes those in
    place, where a gather or scatter of rows this wide it split in two and
    ran on sliced COPIES of half the leaf (2.4 GB at the published
    sizes). Unrolled: the rows are the step's few chunk rows (or a CPU
    test's), and inside a loop of its own each write cost 0.27 ms, a
    hundred times its bytes (PERF.md, PR 37)."""
    for r in range(rows.shape[0]):
        state = lax.dynamic_update_slice(
            state, rows[r][None, None],
            (layer, slots[r]) + (0,) * (state.ndim - 2))
    return state


def _rows_state(state, layer, slots, H: int):
    """The slots' states of one layer as float32 [R, H, P, N]."""
    s = slot_rows(state, layer, slots).astype(_F32)       # [R, N, H P]
    return s.reshape(s.shape[:2] + (H, -1)).transpose(0, 2, 3, 1)


def _store_rows(state, layer, slots, s):
    """[R, H, P, N] float32 back to the slots, as the leaf holds it."""
    s = s.transpose(0, 3, 1, 2).reshape((s.shape[0], s.shape[3], -1))
    return store_slot_rows(state, layer, slots, s.astype(state.dtype))


def ssm_decode_reference(state, x, dt, A, B, C, D, slots, fresh, layer):
    """The recurrence above for one token of each row, vectorised: state
    [L, S + 1, N, H P], x [R, H, P], dt [R, H] (softplus'd), A, D [H],
    B, C [R, N], slots [R] (each row's slot; several rows may share the
    scratch slot, whose content is garbage), fresh [R] bool. Returns
    (y [R, H, P] float32, state)."""
    x, dt, B, C = (a.astype(_F32) for a in (x, dt, B, C))
    s = _rows_state(state, layer, slots, x.shape[1])
    s = jnp.where(fresh[:, None, None, None], 0.0, s)
    s = jnp.exp(dt * A)[:, :, None, None] * s \
        + (dt[:, :, None] * x)[..., None] * B[:, None, None, :]
    y = jnp.einsum("rhpn,rn->rhp", s, C) + D[None, :, None] * x
    return y, _store_rows(state, layer, slots, s)


#: lanes of the state a turn of the update kernel's loop takes: one vector
#: register's width, so a [N, 128] float32 tile is N / 8 registers
_UPDATE_LANES = 128


def _update_kernel(layer_ref, slots_ref, fresh_ref, rows_ref, bc_ref, s_ref,
                   o_ref, y_ref):
    """One row: ``s_ref`` / ``o_ref`` the slot's state in and out [N, H P]
    (a state column n on a sublane, the heads' rows side by side on the
    lanes); ``rows_ref`` [2, H P] float32: dt_h x_h and the head's decay
    exp(dt_h A_h), each a value a lane; ``bc_ref`` [2, N] the rows B and
    C; ``y_ref`` [1, H P] the read-out S C. B and C are turned ONCE into
    [N, lanes] tiles whose every lane holds the column; then, 128 lanes at
    a time, the state is decayed by a sublane-broadcast row, takes the
    outer product as that tile times another such row, is rounded and
    stored, and is reduced over its sublanes against C: nothing moves
    across lanes inside the loop. A fresh row does not read its slot."""
    del layer_ref, slots_ref
    N, HP = s_ref.shape
    W = min(_UPDATE_LANES, HP)
    b_col, c_col = (jnp.broadcast_to(bc_ref[i:i + 1, :], (W, N)).T
                    for i in (0, 1))                          # [N, W]

    def run(read: bool):
        def tile(j, _):
            at = pl.ds(pl.multiple_of(j * W, W), W)
            s = rows_ref[0:1, at] * b_col
            if read:
                s = s + rows_ref[1:2, at] * s_ref[:, at].astype(_F32)
            o_ref[:, at] = s.astype(o_ref.dtype)
            y_ref[:, at] = jnp.sum(s * c_col, axis=0, keepdims=True)
            return 0
        lax.fori_loop(0, HP // W, tile, 0)

    is_fresh = fresh_ref[pl.program_id(0)] != 0
    pl.when(is_fresh)(lambda: run(False))
    pl.when(jnp.logical_not(is_fresh))(lambda: run(True))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssm_update_pallas(state, x, dt, A, B, C, slots, fresh, layer,
                       interpret: bool = False):
    """(S C [R, H, P] float32, state) for one token of each row; ``layer``
    [1] int32. The state is aliased in to out: only the rows' slots move."""
    R, H, P = x.shape
    N, HP = state.shape[2:]
    if HP % min(_UPDATE_LANES, HP):
        raise ValueError(f"heads x head_dim = {HP} is no whole number of "
                         f"{_UPDATE_LANES}-lane tiles")
    x, dt, B, C = (a.astype(_F32) for a in (x, dt, B, C))
    rows = jnp.stack(
        [(dt[:, :, None] * x).reshape(R, HP),
         jnp.broadcast_to(jnp.exp(dt * A)[:, :, None], (R, H, P)
                          ).reshape(R, HP)], axis=1)         # [R, 2, H P]
    bc = jnp.stack([B, C], axis=1)                           # [R, 2, N]
    block = pl.BlockSpec((None, None, N, HP),
                         lambda r, l, s, f: (l[0], s[r], 0, 0))
    slot_bytes = N * HP * state.dtype.itemsize
    state, y = pl.pallas_call(
        _update_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(R,),
            in_specs=[pl.BlockSpec((None, 2, HP), lambda r, *_: (r, 0, 0)),
                      pl.BlockSpec((None, 2, N), lambda r, *_: (r, 0, 0)),
                      block],
            out_specs=[block,
                       pl.BlockSpec((None, 1, HP), lambda r, *_: (r, 0, 0))],
        ),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((R, 1, HP), _F32)],
        # operands count the scalar-prefetch arrays: the state is the 6th
        input_output_aliases={5: 0},
        cost_estimate=pl.CostEstimate(
            flops=5 * R * N * HP, transcendentals=0,
            bytes_accessed=2 * R * slot_bytes),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # the slot's block in and out, each twice (pipelined)
            vmem_limit_bytes=max(6 * slot_bytes, 16 << 20)),
        name="_ssm_update_pallas",
        interpret=interpret,
    )(layer, slots.astype(jnp.int32), fresh.astype(jnp.int32), rows, bc,
      state)
    return y.reshape(R, H, P), state


def ssm_decode_update(state, x, dt, A, B, C, D, slots, fresh, *, layer,
                      impl: Optional[str] = None,
                      interpret: Optional[bool] = None):
    """One token of each row through layer ``layer`` of the state leaf
    (``ssm_decode_reference``'s arguments and result). Rows without a
    token name the scratch slot. The kernel on a TPU (``impl`` "kernel",
    or ``interpret``), the reference elsewhere."""
    if _use_reference(impl, interpret):
        return ssm_decode_reference(state, x, dt, A, B, C, D, slots, fresh,
                                    layer)
    y, state = _ssm_update_pallas(
        state, x, dt, A, B, C, slots, fresh,
        jnp.asarray(layer, jnp.int32).reshape(1), bool(interpret))
    return y + D[None, :, None] * x.astype(_F32), state


# --------------------------------------------------------------------------
# ragged rows of many tokens
# --------------------------------------------------------------------------

def _rows_of(T: int, pos, q_start, q_len):
    """(own [T, R] bool: token t of T is row r's; fresh [R]: the row's
    first token has position 0) of a flat ragged batch."""
    t = jnp.arange(T, dtype=jnp.int32)[:, None]
    own = (t >= q_start[None]) & (t < (q_start + q_len)[None])
    return own, pos[jnp.clip(q_start, 0, pos.shape[0] - 1)] == 0


def ssm_scan_reference(state, x, dt, A, B, C, D, pos, q_start, q_len,
                       row_slot, layer):
    """The recurrence over a flat ragged batch, token after token
    (``lax.scan``): x [T, H, P], dt [T, H], B, C [T, N], pos [T]; row r
    owns tokens q_start[r] .. + q_len[r] - 1 and slot row_slot[r] (the
    scratch slot where q_len is 0), starts from its slot's state, or from
    zeros where its first position is 0, and leaves its last state there.
    Tokens no row owns give D x. Returns (y [T, H, P] float32, state)."""
    T = x.shape[0]
    x, dt, B, C = (a.astype(_F32) for a in (x, dt, B, C))
    own, fresh = _rows_of(T, pos, q_start, q_len)
    rows = _rows_state(state, layer, row_slot, x.shape[1])
    rows = jnp.where(fresh[:, None, None, None], 0.0, rows)
    row = jnp.argmax(own, axis=1)
    valid = own.any(axis=1)

    def step(rows, inp):
        xt, dtt, bt, ct, r, ok = inp
        s = rows[r]
        new = jnp.exp(dtt * A)[:, None, None] * s \
            + (dtt[:, None] * xt)[..., None] * bt[None, None, :]
        y = jnp.einsum("hpn,n->hp", jnp.where(ok, new, 0.0), ct) \
            + D[:, None] * xt
        return rows.at[r].set(jnp.where(ok, new, s)), y

    rows, y = lax.scan(step, rows, (x, dt, B, C, row, valid))
    return y, _store_rows(state, layer, row_slot, rows)


def ssm_chunk_scan(state, x, dt, A, B, C, D, pos, q_start, q_len, row_slot,
                   *, layer, chunk: int = 256, impl: Optional[str] = None,
                   interpret: Optional[bool] = None):
    """``ssm_scan_reference``'s arguments and result in the chunked form
    (the module's docstring), equal to it in exact arithmetic however the
    rows fall into blocks. A row's tokens are consecutive, so between two
    tokens of one row lie only that row's tokens, and the decay between
    them is a difference of ONE cumulative sum over the block."""
    if _use_reference(impl, interpret):
        return ssm_scan_reference(state, x, dt, A, B, C, D, pos, q_start,
                                  q_len, row_slot, layer)
    T, H, P = x.shape
    Q = min(chunk, T)
    x, dt, B, C = (jnp.pad(a.astype(_F32),
                           ((0, -T % Q),) + ((0, 0),) * (a.ndim - 1))
                   for a in (x, dt, B, C))
    own, fresh = _rows_of(x.shape[0], pos, q_start, q_len)
    valid = own.any(axis=1)
    a = jnp.where(valid[:, None], dt * A, 0.0)            # [T, H], <= 0
    xdt = jnp.where(valid[:, None, None], dt[:, :, None] * x, 0.0)
    rows = _rows_state(state, layer, row_slot, H)         # [R, H, P, N]
    rows = jnp.where(fresh[:, None, None, None], 0.0, rows)
    causal = jnp.tril(jnp.ones((Q, Q), bool))

    def exp_where(keep, e):
        # masked inside too: an exponent that is not kept may overflow
        return jnp.where(keep, jnp.exp(jnp.where(keep, e, 0.0)), 0.0)

    def block(rows, inp):
        a, xdt, b, c, own = inp           # [Q, H] [Q, H, P] [Q, N] x2 [Q, R]
        of = own.astype(_F32)
        live = own.any(axis=1, keepdims=True)             # [Q, 1]
        cum = jnp.cumsum(a, axis=0)                       # inclusive
        # the sum before each row's first token of this block, and up to
        # its last
        before = jnp.einsum("tr,th->rh", of, a)
        first = jnp.argmax(own, axis=0)                   # [R]
        start = cum[first] - a[first]                     # [R, H]
        end = start + before
        # a token against the earlier tokens of its own row, a head at a
        # time: [H, t, s]
        mask = (causal & (of @ of.T > 0))[None]
        decay = exp_where(mask, cum.T[:, :, None] - cum.T[:, None, :])
        y = jnp.einsum("hts,shp->thp", decay * (c @ b.T)[None], xdt)
        # ... and against its row's state at the block's start
        since = exp_where(live, cum - of @ start)         # [Q, H]
        y = y + since[:, :, None] * jnp.einsum(
            "trn,rhpn->thp", of[:, :, None] * c[:, None, :], rows)
        # the rows' states at the block's end
        left = exp_where(live, of @ end - cum)
        rows = jnp.exp(before)[:, :, None, None] * rows + jnp.einsum(
            "shp,srn->rhpn", left[:, :, None] * xdt,
            of[:, :, None] * b[:, None, :])
        return rows, y

    def maybe(rows, inp):
        # the step's shape is static and most of its chunk tokens are
        # padding most of the time: a block no row owns a token of is
        # skipped (its y is zeros, as the full computation gives)
        return lax.cond(
            inp[-1].any(), block,
            lambda rows, inp: (rows, jnp.zeros(inp[1].shape, _F32)),
            rows, inp)

    def blocks(arr):
        return arr.reshape((-1, Q) + arr.shape[1:])

    # unrolled (a step's chunk rows are a few blocks): as a loop XLA laid the
    # stacked y out with the BLOCK index inside the tiles and each turn's
    # write of its 4 MB touched all of it, 0.13 ms a block and three
    # quarters of the scan's time (PERF.md, PR 37)
    rows, y = lax.scan(maybe, rows, tuple(map(blocks, (a, xdt, B, C, own))),
                       unroll=True)
    y = y.reshape((-1, H, P))[:T] + D[None, :, None] * x[:T]
    return y, _store_rows(state, layer, row_slot, rows)
