"""The selective scan (Mamba-1) of the serving step.

Every (channel c, state index n) pair of a layer's state S in R^{N x C}
decays on its own: A [N, C] < 0 is a MATRIX (ops/ssm.py's Mamba-2 has one
scalar a head, which is what lets its chunk form be matrix products; this
one has no such form), dt [C] the softplus'd step a channel, B and C [N]
shared by all channels:

    S[n, c] <- exp(dt[c] A[n, c]) S[n, c] + dt[c] x[c] B[n]
    y[c]     = sum_n S[n, c] C[n] + D[c] x[c]

The state of every layer and batch slot is ONE leaf of the pool, [layers,
slots + 1, N, C] float32 (llm/cache.py: axis 1 is batch slots, the last
one scratch; a slot's matrix lies with the state index on the sublanes and
the channels on the lanes, as ops/ssm.py's does, and A lies the same way,
so a tile of the state meets its tile of A and nothing is broadcast across
lanes). Both entry points take the whole leaf and a layer's index and
return the leaf, updated in place where a caller donates it; a row whose
first token has position 0 (``fresh``) starts from zeros whatever its slot
holds, so nothing ever zeroes a slot.

``selective_decode_update``  one token a row. A Pallas kernel
    (``_selective_update_pallas``): a grid step moves one slot's [N, C]
    block through VMEM, in to out aliased, and computes the decay TILE
    exp(dt A) where ops/ssm.py broadcasts a scalar: 2 N C values moved
    for N C exponentials and ~5 N C operations, so HBM bounds it.
``selective_chunk_scan``  ragged rows of many tokens. A Pallas kernel
    (``_selective_scan_pallas``) that walks the flat token axis in blocks
    with every row's [N, C] float32 state resident in VMEM: the first
    block fetches a row's state from its slot (or zeros), the last puts
    it back, and between them nothing of the state touches HBM. Bound by
    the vector and transcendental units (N C exponentials a token), not by
    the MXU: there is no [T, T] matrix a head to make.

Each has a plain sequential reference for the CPU path and the tests:
``selective_decode_reference`` (the formula, vectorised over rows) and
``selective_scan_reference`` (``lax.scan`` over the tokens), chosen as the
paged attention's is (``impl``, ``interpret``).
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
#: lanes of the state a turn of either kernel's loop takes: one vector
#: register's width, so a [N, 128] float32 tile is N / 8 registers
_LANES = 128
#: tokens a grid step of the chunk kernel holds in VMEM
_TOKEN_BLOCK = 64


def _lane_columns(a):
    """[..., N] -> [..., N, 128]: each value on every lane of its own
    sublane, so that a kernel reads B or C as a tile and broadcasts nothing
    across lanes (made once, by XLA: 8 KB a token)."""
    return jnp.broadcast_to(a[..., None], a.shape + (_LANES,))


# --------------------------------------------------------------------------
# one token a row
# --------------------------------------------------------------------------

def selective_decode_reference(state, x, dt, A, B, C, D, slots, fresh,
                               layer):
    """The recurrence above for one token of each row, vectorised: state
    [L, S + 1, N, Ch], x and dt [R, Ch] (dt softplus'd), A [N, Ch], B and C
    [R, N], D [Ch], slots [R] (each row's slot; several rows may share the
    scratch slot, whose content is garbage), fresh [R] bool. Returns (y
    [R, Ch] float32, state)."""
    x, dt, B, C = (a.astype(_F32) for a in (x, dt, B, C))
    s = slot_rows(state, layer, slots).astype(_F32)        # [R, N, Ch]
    s = jnp.where(fresh[:, None, None], 0.0, s)
    s = jnp.exp(dt[:, None, :] * A[None]) * s \
        + (dt * x)[:, None, :] * B[:, :, None]
    y = jnp.einsum("rnc,rn->rc", s, C) + D[None] * x
    return y, store_slot_rows(state, layer, slots, s.astype(state.dtype))


def _update_kernel(layer_ref, slots_ref, fresh_ref, rows_ref, bc_ref, a_ref,
                   s_ref, o_ref, y_ref):
    """One row: ``s_ref`` / ``o_ref`` the slot's state in and out [N, Ch];
    ``rows_ref`` [2, Ch] float32: dt x and dt, a value a lane; ``bc_ref``
    [2, N, 128]: B and C as lane columns; ``a_ref`` [N, Ch]: A, the same
    block every step. 128 lanes at a time the decay tile is made, the state
    decayed, the outer product added, stored, and reduced over its sublanes
    against C. A fresh row does not read its slot."""
    del layer_ref, slots_ref
    N, Ch = s_ref.shape
    b_col, c_col = bc_ref[0], bc_ref[1]                        # [N, 128]

    def run(read: bool):
        for j in range(Ch // _LANES):
            at = pl.ds(j * _LANES, _LANES)
            s = rows_ref[0:1, at] * b_col
            if read:
                s = s + jnp.exp(rows_ref[1:2, at] * a_ref[:, at]) \
                    * s_ref[:, at].astype(_F32)
            o_ref[:, at] = s.astype(o_ref.dtype)
            y_ref[:, at] = jnp.sum(s * c_col, axis=0, keepdims=True)

    is_fresh = fresh_ref[pl.program_id(0)] != 0
    pl.when(is_fresh)(lambda: run(False))
    pl.when(jnp.logical_not(is_fresh))(lambda: run(True))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _selective_update_pallas(state, x, dt, A, B, C, slots, fresh, layer,
                             interpret: bool = False):
    """(S C [R, Ch] float32, state) for one token of each row; ``layer``
    [1] int32. The state is aliased in to out: only the rows' slots move."""
    R, Ch = x.shape
    N = state.shape[2]
    if Ch % _LANES:
        raise ValueError(f"{Ch} channels are no whole number of "
                         f"{_LANES}-lane tiles")
    x, dt, B, C = (a.astype(_F32) for a in (x, dt, B, C))
    rows = jnp.stack([dt * x, dt], axis=1)                   # [R, 2, Ch]
    bc = _lane_columns(jnp.stack([B, C], axis=1))            # [R, 2, N, 128]
    block = pl.BlockSpec((None, None, N, Ch),
                         lambda r, l, s, f: (l[0], s[r], 0, 0))
    slot_bytes = N * Ch * state.dtype.itemsize
    state, y = pl.pallas_call(
        _update_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(R,),
            in_specs=[pl.BlockSpec((None, 2, Ch), lambda r, *_: (r, 0, 0)),
                      pl.BlockSpec((None, 2, N, _LANES),
                                   lambda r, *_: (r, 0, 0, 0)),
                      pl.BlockSpec((N, Ch), lambda r, *_: (0, 0)),
                      block],
            out_specs=[block,
                       pl.BlockSpec((None, 1, Ch), lambda r, *_: (r, 0, 0))],
        ),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((R, 1, Ch), _F32)],
        # operands count the scalar-prefetch arrays: the state is the 7th
        input_output_aliases={6: 0},
        cost_estimate=pl.CostEstimate(
            flops=5 * R * N * Ch, transcendentals=R * N * Ch,
            bytes_accessed=2 * R * slot_bytes),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # the slot's block in and out, each twice (pipelined), and A
            vmem_limit_bytes=max(8 * slot_bytes, 16 << 20)),
        name="_selective_update_pallas",
        interpret=interpret,
    )(layer, slots.astype(jnp.int32), fresh.astype(jnp.int32), rows, bc,
      A.astype(_F32), state)
    return y.reshape(R, Ch), state


def selective_decode_update(state, x, dt, A, B, C, D, slots, fresh, *, layer,
                            impl: Optional[str] = None,
                            interpret: Optional[bool] = None):
    """One token of each row through layer ``layer`` of the state leaf
    (``selective_decode_reference``'s arguments and result). Rows without
    a token name the scratch slot. The kernel on a TPU (``impl`` "kernel",
    or ``interpret``), the reference elsewhere."""
    if _use_reference(impl, interpret):
        return selective_decode_reference(state, x, dt, A, B, C, D, slots,
                                          fresh, layer)
    y, state = _selective_update_pallas(
        state, x, dt, A, B, C, slots, fresh,
        jnp.asarray(layer, jnp.int32).reshape(1), bool(interpret))
    return y + D[None] * x.astype(_F32), state


# --------------------------------------------------------------------------
# ragged rows of many tokens
# --------------------------------------------------------------------------

def selective_scan_reference(state, x, dt, A, B, C, D, pos, q_start, q_len,
                             row_slot, layer):
    """The recurrence over a flat ragged batch, token after token
    (``lax.scan``): x and dt [T, Ch], B and C [T, N], pos [T]; row r owns
    tokens q_start[r] .. + q_len[r] - 1 and slot row_slot[r] (the scratch
    slot where q_len is 0), starts from its slot's state, or from zeros
    where its first position is 0, and leaves its last state there. Tokens
    no row owns give D x. Returns (y [T, Ch] float32, state)."""
    T = x.shape[0]
    x, dt, B, C = (a.astype(_F32) for a in (x, dt, B, C))
    own, fresh = _rows_of(T, pos, q_start, q_len)
    rows = slot_rows(state, layer, row_slot).astype(_F32)   # [R, N, Ch]
    rows = jnp.where(fresh[:, None, None], 0.0, rows)
    row = jnp.argmax(own, axis=1)
    valid = own.any(axis=1)

    def step(rows, inp):
        xt, dtt, bt, ct, r, ok = inp
        s = rows[r]
        new = jnp.exp(dtt[None, :] * A) * s \
            + (dtt * xt)[None, :] * bt[:, None]
        y = jnp.where(ok, new, 0.0).T @ ct + D * xt
        return rows.at[r].set(jnp.where(ok, new, s)), y

    rows, y = lax.scan(step, rows, (x, dt, B, C, row, valid))
    return y, store_slot_rows(state, layer, row_slot,
                              rows.astype(state.dtype))


def _scan_kernel(layer_ref, start_ref, len_ref, slot_ref, fresh_ref,  # SMEM
                 u_ref, dt_ref, b_ref, c_ref, a_ref, state_in,
                 state_out, y_ref, s_ref, sem):
    """Grid step (j, r): block j of ``_TOKEN_BLOCK`` tokens of the flat
    chunk region, row r's tokens of it. ``start_ref`` / ``len_ref`` /
    ``slot_ref`` / ``fresh_ref`` [R]: the rows' spans, slots, and whether
    each starts from zeros. ``u_ref`` (dt x) and ``dt_ref`` [block, Ch],
    ``b_ref`` / ``c_ref`` [block, N, 128] (lane columns), ``a_ref`` [N,
    Ch]; the state leaf stays in HBM (``state_in`` / ``state_out``: one
    buffer). ``s_ref`` [R, N, Ch] float32 holds EVERY row's state for the
    whole call: row r's comes in at the first block and goes back at the
    last, and the blocks between touch VMEM only.

    Tokens go eight at a time (a sublane tile of dt and dt x is read
    whole; Mosaic loads no single row at a dynamic index), 128 lanes at a
    time, the state's tile in registers across the eight: a token that is
    not row r's leaves the tile as it is and the read-out where it is."""
    del state_in
    TB, Ch = u_ref.shape
    j, r = pl.program_id(0), pl.program_id(1)
    layer, slot = layer_ref[0], slot_ref[r]
    lo, n = start_ref[r], len_ref[r]
    base = j * TB

    @pl.when((j == 0) & (fresh_ref[r] != 0))
    def _():
        s_ref[r] = jnp.zeros(s_ref.shape[1:], _F32)

    @pl.when((j == 0) & (fresh_ref[r] == 0))
    def _():
        dma = pltpu.make_async_copy(state_out.at[layer, slot], s_ref.at[r],
                                    sem)
        dma.start()
        dma.wait()

    @pl.when(r == 0)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    sublane = lax.broadcasted_iota(jnp.int32, (8, _LANES), 0)

    def group(g, _):
        t8 = pl.multiple_of(g * 8, 8)
        first = base + t8

        @pl.when((first < lo + n) & (first + 8 > lo))
        def _():
            own = [(first + k >= lo) & (first + k < lo + n)
                   for k in range(8)]

            def tile(i, _):
                at = pl.ds(pl.multiple_of(i * _LANES, _LANES), _LANES)
                a = a_ref[:, at]
                s = s_ref[r, :, at]
                dt8, u8 = dt_ref[pl.ds(t8, 8), at], u_ref[pl.ds(t8, 8), at]
                y = y_ref[pl.ds(t8, 8), at]
                for k in range(8):
                    new = jnp.exp(dt8[k:k + 1] * a) * s \
                        + u8[k:k + 1] * b_ref[t8 + k]
                    s = jnp.where(own[k], new, s)
                    out = jnp.sum(s * c_ref[t8 + k], axis=0, keepdims=True)
                    y = jnp.where((sublane == k) & own[k], out, y)
                s_ref[r, :, at] = s
                y_ref[pl.ds(t8, 8), at] = y
                return 0
            lax.fori_loop(0, Ch // _LANES, tile, 0)
        return 0

    @pl.when((base < lo + n) & (base + TB > lo))
    def _():
        lax.fori_loop(0, TB // 8, group, 0)

    @pl.when((j == pl.num_programs(0) - 1) & (n > 0))
    def _():
        dma = pltpu.make_async_copy(s_ref.at[r], state_out.at[layer, slot],
                                    sem)
        dma.start()
        dma.wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def _selective_scan_pallas(state, x, dt, A, B, C, pos, q_start, q_len,
                           row_slot, layer, interpret: bool = False):
    """(S C [T, Ch] float32 with zeros where no row owns the token, state);
    ``layer`` [1] int32. The state leaf is aliased in to out and stays in
    HBM: a row's slot moves once each way."""
    T, Ch = x.shape
    N, R = state.shape[2], q_start.shape[0]
    if Ch % _LANES or state.dtype != _F32:
        raise ValueError(f"the scan kernel walks a float32 state of whole "
                         f"{_LANES}-lane tiles, got {Ch} channels in "
                         f"{state.dtype}")
    TB = _TOKEN_BLOCK
    pad = -T % TB
    x, dt, B, C = (jnp.pad(a.astype(_F32),
                           ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                   for a in (x, dt, B, C))
    Tp = T + pad
    fresh = pos[jnp.clip(q_start, 0, T - 1)] == 0
    tokens = pl.BlockSpec((TB, Ch), lambda j, r, *_: (j, 0))
    columns = pl.BlockSpec((TB, N, _LANES), lambda j, r, *_: (j, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    state, y = pl.pallas_call(
        _scan_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(Tp // TB, R),
            in_specs=[tokens, tokens, columns, columns,
                      pl.BlockSpec((N, Ch), lambda j, r, *_: (0, 0)), hbm],
            out_specs=[hbm, tokens],
            scratch_shapes=[pltpu.VMEM((R, N, Ch), _F32),
                            pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((Tp, Ch), _F32)],
        # operands count the scalar-prefetch arrays: the state is the 11th
        input_output_aliases={10: 0},
        cost_estimate=pl.CostEstimate(
            flops=5 * Tp * N * Ch, transcendentals=Tp * N * Ch,
            bytes_accessed=Tp * (3 * Ch + 2 * N * _LANES) * 4),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=32 << 20),
        name="_selective_scan_pallas",
        interpret=interpret,
    )(layer, q_start.astype(jnp.int32), q_len.astype(jnp.int32),
      row_slot.astype(jnp.int32), fresh.astype(jnp.int32), dt * x, dt,
      _lane_columns(B), _lane_columns(C), A.astype(_F32), state)
    return y[:T], state


def selective_chunk_scan(state, x, dt, A, B, C, D, pos, q_start, q_len,
                         row_slot, *, layer, impl: Optional[str] = None,
                         interpret: Optional[bool] = None):
    """``selective_scan_reference``'s arguments and result by the kernel
    that walks the tokens with a row's state resident (the module's
    docstring) on a TPU (``impl`` "kernel", or ``interpret``), the
    reference elsewhere. Rows must not share a slot but the scratch
    slot."""
    if _use_reference(impl, interpret):
        return selective_scan_reference(state, x, dt, A, B, C, D, pos,
                                        q_start, q_len, row_slot, layer)
    y, state = _selective_scan_pallas(
        state, x, dt, A, B, C, pos, q_start, q_len, row_slot,
        jnp.asarray(layer, jnp.int32).reshape(1), bool(interpret))
    return y + D[None] * x.astype(_F32), state
