"""Flash attention: Pallas forward AND backward kernels.

No reference implementation exists in-tree (the reference delegates to
vLLM/CUDA — SURVEY.md §5 long-context); built from the public flash/
blockwise-attention recipes (PAPERS.md) on the Pallas TPU pattern:
stream KV blocks through VMEM with online-softmax accumulators in scratch,
never materializing the [L, L] score matrix in HBM — in either pass.

  flash_attention(q, k, v)  [B, L, H, D] → [B, L, H, D]
    fwd:  grid (B·H, query blocks, resident key blocks); a grid step
          walks its resident keys in an inner loop; saves per-row
          logsumexp.
    bwd:  two kernels — dq (same walk) and dk/dv over (B·H, key blocks,
          resident query blocks) — recompute p = exp(s − lse) blockwise
          from the saved lse.
    flash_tiling picks each kernel's blocks from the static shape; under
    the causal mask no kernel visits or fetches a block above the
    diagonal, and only the blocks the diagonal crosses are masked.
    The forward kernel's two outputs carry the checkpoint names
    FLASH_SAVE_NAMES: a remat policy that saves them (models/llama.py:
    remat_policy_fn) hands the backward its o and lse, and the forward
    kernel does not run a second time under the remat boundary.

`blockwise_attention` is the pure-JAX (lax.scan) equivalent: same online
softmax, differentiable by autodiff, used as the numerics reference and as
a portable fallback.
"""

from __future__ import annotations

import functools
from collections import Counter
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float("-inf")


# --------------------------------------------------------------------------
# Blockwise attention in pure JAX (reference numerics + portable fallback)
# --------------------------------------------------------------------------

def blockwise_attention(q, k, v, *, causal: bool = True,
                        sm_scale: Optional[float] = None,
                        block_k: int = 256) -> jax.Array:
    """Online-softmax attention, scanning KV blocks; [B, L, H, D] layout."""
    B, Lq, H, D = q.shape
    Lk = k.shape[1]
    if sm_scale is None:
        sm_scale = D ** -0.5
    blk = min(block_k, Lk)
    if Lk % blk:
        raise ValueError(f"seq len {Lk} not divisible by block_k {blk}")
    nk = Lk // blk
    kb = k.reshape(B, nk, blk, H, D)
    vb = v.reshape(B, nk, blk, H, D)
    qpos = jnp.arange(Lq)
    qs = q * q.dtype.type(sm_scale)

    o0 = jnp.zeros((B, Lq, H, D), jnp.float32)
    m0 = jnp.full((B, H, Lq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Lq), jnp.float32)

    def step(carry, blk_idx):
        o, m, l = carry
        kt, vt = kb[:, blk_idx], vb[:, blk_idx]
        s = jnp.einsum("bqhd,bkhd->bhqk", qs, kt,
                       preferred_element_type=jnp.float32)
        if causal:
            kpos = blk_idx * blk + jnp.arange(blk)
            s = jnp.where((qpos[:, None] >= kpos[None, :])[None, None],
                          s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.where(jnp.isneginf(s), 0.0, jnp.exp(s - m_new[..., None]))
        corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_new))
        l = l * corr + p.sum(axis=-1)
        o = o * corr.transpose(0, 2, 1)[..., None] + jnp.einsum(
            "bhqk,bkhd->bqhd", p.astype(vt.dtype), vt,
            preferred_element_type=jnp.float32)
        return (o, m_new, l), None

    (o, m, l), _ = lax.scan(step, (o0, m0, l0), jnp.arange(nk))
    o = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return o.astype(q.dtype)


# --------------------------------------------------------------------------
# Tiling: a function of the static shape
# --------------------------------------------------------------------------

class KernelTiling(NamedTuple):
    """How one kernel tiles a head. ``block`` rows of the operand a grid
    step owns (queries in the forward and dq, keys in dk/dv) meet the
    other operand ``step`` rows an inner-loop turn, out of ``resident``
    rows of it held in VMEM a grid step."""
    block: int
    step: int
    resident: int


class FlashTiling(NamedTuple):
    fwd: KernelTiling
    dq: KernelTiling
    dkv: KernelTiling


#: rows wanted for a kernel's block and for its step, cut to the lengths
#: by _fit. Timed on a v5e at [64, 4096, 128] bf16 causal, each kernel
#: alone (PERF.md §5, PR 29, the table of candidates): all three are at
#: their best or within 1 % of it at 512 x 512; 256 pays the fixed cost
#: of a turn twice as often, 1024 wastes more above the diagonal of the
#: blocks it crosses and takes 2-4 times as long to lower.
_BLOCK = 512
#: the resident operand pair (K and V, or Q and dO), double-buffered
_RESIDENT_BYTES = 8 << 20


def _fit(L: int, want: int) -> Optional[int]:
    """A block of at most ``want`` rows for a length of L: the whole
    length when that is short enough (a multiple of the 8-row sublane
    tile), else the largest of want, want/2, ... 128 that divides it.
    None: no block the TPU lowering takes tiles this length."""
    if L <= want:
        return L if L % 8 == 0 else None
    b = want
    while b >= 128:
        if L % b == 0:
            return b
        b //= 2
    return None


def _resident(L: int, step: int, head_dim: int, itemsize: int) -> int:
    """Rows of the streamed operand pair to keep in VMEM a grid step:
    the whole length where two double-buffered [L, D] operands fit
    _RESIDENT_BYTES, else the largest multiple of ``step`` that divides
    L and fits. A step that is not a multiple of 128 rows is its own
    resident block (an inner loop would slice inside a packed tile)."""
    if step % 128 and step != L:
        return step
    cap = max(step, _RESIDENT_BYTES // (4 * head_dim * itemsize))
    n = L // step
    return step * max(d for d in range(1, n + 1)
                      if n % d == 0 and d * step <= cap)


def _kernel_tiling(L_own: int, L_other: int, head_dim: int, itemsize: int,
                   block: Optional[int] = None, step: Optional[int] = None
                   ) -> Optional[KernelTiling]:
    """One kernel's tiling: blocks of ``L_own`` rows against steps of
    ``L_other`` rows (given, or _BLOCK cut to the length)."""
    block = _fit(L_own, _BLOCK) if block is None else block
    step = _fit(L_other, _BLOCK) if step is None else step
    if block is None or step is None:
        return None
    return KernelTiling(block, step,
                        _resident(L_other, step, head_dim, itemsize))


def flash_tiling(Lq: int, Lk: int, head_dim: int,
                 dtype) -> Optional[FlashTiling]:
    """The three kernels' tilings for this static shape, or None when a
    length cannot be tiled for the TPU lowering (callers fall back to the
    einsum/blockwise path). A length under one large block is its own
    block. The causal flag does not enter: a causal call walks only the
    blocks under its diagonal, a full one has none to skip or mask."""
    itemsize = jnp.dtype(dtype).itemsize
    qk = _kernel_tiling(Lq, Lk, head_dim, itemsize)
    kq = _kernel_tiling(Lk, Lq, head_dim, itemsize)
    return None if qk is None or kq is None else FlashTiling(qk, qk, kq)


def _resolve_tiling(Lq, Lk, head_dim, dtype, blk_q, blk_k,
                    interpret) -> FlashTiling:
    """flash_tiling's choice, or an explicit blk_q / blk_k (tests, the
    autotuner) as the query / key rows of all three kernels. Interpret
    mode has no Mosaic tiling: there a length no block divides is one
    block."""
    t = flash_tiling(Lq, Lk, head_dim, dtype)
    if blk_q is None and blk_k is None and t is not None:
        return t
    if t is None and not interpret and (blk_q is None or blk_k is None):
        raise ValueError(
            f"flash attention: lengths ({Lq}, {Lk}) divide into no block "
            f"the TPU lowering takes (multiples of 128, or one whole "
            f"length that is a multiple of 8)")
    bq = min(blk_q, Lq) if blk_q else t.fwd.block if t else Lq
    bk = min(blk_k, Lk) if blk_k else t.dkv.block if t else Lk
    if Lq % bq or Lk % bk:
        raise ValueError(f"L ({Lq},{Lk}) must divide blocks ({bq},{bk})")
    itemsize = jnp.dtype(dtype).itemsize
    qk = _kernel_tiling(Lq, Lk, head_dim, itemsize, bq, bk)
    return FlashTiling(qk, qk, _kernel_tiling(Lk, Lq, head_dim, itemsize,
                                              bk, bq))


# --------------------------------------------------------------------------
# Pallas kernels ([BH, L, D] layout inside)
#
# A grid step owns one block of rows (queries; keys in dk/dv) and walks
# the other operand, resident in VMEM, in an inner lax.fori_loop. Under
# the causal mask the loop runs only over the steps its block can see:
# first the steps wholly under the diagonal, with no mask at all, then
# the few the diagonal crosses, masked. Steps above the diagonal are
# never visited, and where a head's keys are split over several resident
# blocks the index maps name the last live block again, so a dead grid
# step fetches nothing.
#
# No -inf guard: every row sees key 0 (the mask is aligned top-left, and
# there is no other mask), key 0 is in the first step the loop takes, so
# from there on a row's running max is finite and exp(-inf - m) is 0.
# --------------------------------------------------------------------------

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b


def _dot(a, b, dims):
    # operands in their own dtype (bf16 on the MXU), f32 accumulation
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _lanes(x, n: int):
    """x [rows, 128] with every lane of a row the same -> [rows, n]. Row
    statistics are held lane-replicated: a [rows, 1] column costs as many
    vector registers and a lane broadcast at every use (forward at steps
    of 512 keys: 4.35 ms a call with columns, 2.83 so; PERF.md, PR 29)."""
    rows, w = x.shape
    if n % w:
        return jnp.broadcast_to(x[:, :1], (rows, n))
    return x if n == w else jnp.concatenate([x] * (n // w), axis=1)


def _turn_rows(t, step, nsteps):
    """Rows of the resident operand that turn ``t`` takes. A resident
    block of one step is taken whole: its length need not be a multiple
    of the 128 lanes a dynamic slice of the row vectors must start on."""
    if nsteps == 1:
        return slice(None)
    return pl.ds(pl.multiple_of(t * step, step), step)


def _walk_to_diagonal(turn, carry, causal, first_row, rows, col0, step,
                      nsteps):
    """Run ``turn(t, carry, masked)`` over those of the ``nsteps`` steps
    of ``step`` columns from column ``col0`` that the ``rows`` rows from
    ``first_row`` can see: unmasked over the steps every row sees whole,
    masked over the rest that any row sees; all of them, unmasked, when
    not causal."""
    full = live = nsteps
    if causal:
        full = jnp.clip((first_row + 1 - col0) // step, 0, nsteps)
        live = jnp.clip((first_row + rows - col0 + step - 1) // step, 0,
                        nsteps)
    carry = lax.fori_loop(0, full, functools.partial(turn, masked=False),
                          carry)
    if causal:
        carry = lax.fori_loop(full, live,
                              functools.partial(turn, masked=True), carry)
    return carry


def _rows_ahead(causal, shape):
    """[row, column] -> row - column of a score tile: the part of the
    causal test that no loop turn changes, built once a grid step. A
    turn compares it with one scalar, how far its columns start past
    the tile's rows."""
    if not causal:
        return None
    return (lax.broadcasted_iota(jnp.int32, shape, 0)
            - lax.broadcasted_iota(jnp.int32, shape, 1))


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, causal, sm_scale, step):
    blk_q, res_k = q_ref.shape[1], k_ref.shape[1]
    qi, ri = pl.program_id(1), pl.program_id(2)
    nsteps = res_k // step

    @pl.when(ri == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0]
    ahead = _rows_ahead(causal, (blk_q, step))

    def turn(t, ml, masked):
        m_prev, l_prev = ml                         # [blk_q, 128] each
        at = _turn_rows(t, step, nsteps)
        s = _dot(q, k_ref[0, at, :], _NT) * sm_scale
        if masked:      # query position >= key position
            s = jnp.where(ahead >= ri * res_k + t * step - qi * blk_q,
                          s, _NEG_INF)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, step))
        corr = jnp.exp(m_prev - m_new)
        acc_ref[...] = acc_ref[...] * _lanes(corr, acc_ref.shape[1]) + _dot(
            p.astype(v_ref.dtype), v_ref[0, at, :], _NN)
        return m_new, l_prev * corr + p.sum(axis=-1, keepdims=True)

    m_ref[...], l_ref[...] = _walk_to_diagonal(
        turn, (m_ref[...], l_ref[...]), causal, qi * blk_q, blk_q,
        ri * res_k, step, nsteps)

    @pl.when(ri == pl.num_programs(2) - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse = m_ref[:, :1] + jnp.log(l)
        # lse is materialized [8, blk_q] (sublane-replicated) to satisfy
        # the TPU (8, 128) tiling floor for output blocks.
        lse_ref[0] = jnp.broadcast_to(lse[:, 0][None, :], lse_ref.shape[1:])


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc, *, causal, sm_scale, step):
    blk_q, res_k = q_ref.shape[1], k_ref.shape[1]
    qi, ri = pl.program_id(1), pl.program_id(2)
    nsteps = res_k // step

    @pl.when(ri == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q, do = q_ref[0], do_ref[0]
    lse, delta = lse_ref[0, 0][:, None], delta_ref[0, 0][:, None]
    ahead = _rows_ahead(causal, (blk_q, step))

    def turn(t, carry, masked):
        at = _turn_rows(t, step, nsteps)
        k = k_ref[0, at, :]
        s = _dot(q, k, _NT) * sm_scale
        if masked:
            s = jnp.where(ahead >= ri * res_k + t * step - qi * blk_q,
                          s, _NEG_INF)
        p = jnp.exp(s - lse)                        # masked → exp(-inf) = 0
        ds = p * (_dot(do, v_ref[0, at, :], _NT) - delta)
        dq_acc[...] += _dot(ds.astype(k.dtype), k, _NN)
        return carry

    _walk_to_diagonal(turn, 0, causal, qi * blk_q, blk_q, ri * res_k, step,
                      nsteps)

    @pl.when(ri == pl.num_programs(2) - 1)
    def _finish():
        dq_ref[0] = (dq_acc[...] * sm_scale).astype(dq_ref.dtype)


def _dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, causal, sm_scale, step):
    """Scores are held transposed, [keys, queries]: lse and delta then
    broadcast along sublanes as the rows they are stored as, and both
    accumulating products are plain a @ b (p.T @ do would transpose a
    whole score tile a turn)."""
    blk_k, res_q = k_ref.shape[1], q_ref.shape[1]
    ki, ri = pl.program_id(1), pl.program_id(2)
    nsteps = res_q // step

    @pl.when(ri == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    k, v = k_ref[0], v_ref[0]
    ahead = _rows_ahead(causal, (blk_k, step))

    def turn(t, carry, masked):
        at = _turn_rows(t, step, nsteps)
        q, do = q_ref[0, at, :], do_ref[0, at, :]
        s = _dot(k, q, _NT) * sm_scale              # [blk_k, step]
        if masked:      # rows are keys here: key position <= query's
            s = jnp.where(ahead <= ri * res_q + t * step - ki * blk_k,
                          s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0, :1, at])
        dv_acc[...] += _dot(p.astype(do.dtype), do, _NN)
        ds = p * (_dot(v, do, _NT) - delta_ref[0, :1, at])
        dk_acc[...] += _dot(ds.astype(q.dtype), q, _NN)
        return carry

    if causal:
        # query steps before the key block's first key see none of it;
        # from its last key on they see all of it
        q0, k0 = ri * res_q, ki * blk_k
        lo = jnp.clip((k0 - q0) // step, 0, nsteps)
        whole = jnp.clip((k0 + blk_k - 1 - q0 + step - 1) // step, lo,
                         nsteps)
        lax.fori_loop(lo, whole, functools.partial(turn, masked=True), 0)
        lax.fori_loop(whole, nsteps, functools.partial(turn, masked=False),
                      0)
    else:
        lax.fori_loop(0, nsteps, functools.partial(turn, masked=False), 0)

    @pl.when(ri == pl.num_programs(2) - 1)
    def _finish():
        dk_ref[0] = (dk_acc[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


# --------------------------------------------------------------------------
# pallas_call wrappers
# --------------------------------------------------------------------------

def _params(vmem_bytes: int):
    # the head and the block axis are independent; the resident axis
    # accumulates into the scratch
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=min(max(2 * vmem_bytes, 32 << 20), 100 << 20))


def _vmem_bytes(t: KernelTiling, D: int, itemsize: int, n_own: int,
                n_acc: int) -> int:
    """Rough VMEM a grid step holds: ``n_own`` double-buffered operand
    blocks of the kernel's own rows, the double-buffered resident pair,
    ``n_acc`` f32 accumulators, and the f32 / operand-dtype score tiles
    of one turn (scores, probabilities, mask, their casts)."""
    return (2 * n_own * t.block * D * itemsize
            + 4 * t.resident * D * itemsize
            + n_acc * t.block * max(D, 128) * 4
            + 5 * t.block * t.step * 4)


def _live_map(causal, own, resident, up):
    """Index map of the resident operand: (head, resident block, 0).
    Causal grid steps whose resident block lies wholly beyond the
    diagonal name the nearest live block instead, which is the one
    already in VMEM: nothing is fetched for them. ``up``: the live
    blocks are those up to the diagonal (keys for a block of queries);
    else those from it on (queries for a block of keys)."""
    if not causal:
        return lambda b, i, j: (b, j, 0)
    if up:
        return lambda b, i, j: (
            b, jnp.minimum(j, (i * own + own - 1) // resident), 0)
    return lambda b, i, j: (b, jnp.maximum(j, (i * own) // resident), 0)


def _pairs(BH, Lq, Lk, causal):
    """(query, key) pairs the kernels score: a product costs 2 * D flops
    a pair, the softmax one exp."""
    return BH * Lq * Lk // (2 if causal else 1)


def _fwd_call(q, k, v, causal, sm_scale, blk_q, blk_k, interpret):
    BH, Lq, D = q.shape
    Lk = k.shape[1]
    t = _resolve_tiling(Lq, Lk, D, q.dtype, blk_q, blk_k, interpret).fwd
    isz = q.dtype.itemsize
    own = pl.BlockSpec((1, t.block, D), lambda b, i, j: (b, i, 0))
    res = pl.BlockSpec((1, t.resident, D),
                       _live_map(causal, t.block, t.resident, up=True))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, sm_scale=sm_scale,
                          step=t.step),
        grid=(BH, Lq // t.block, Lk // t.resident),
        in_specs=[own, res, res],
        out_specs=[own,
                   pl.BlockSpec((1, 8, t.block), lambda b, i, j: (b, 0, i))],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Lq, D), q.dtype),
            jax.ShapeDtypeStruct((BH, 8, Lq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((t.block, D), jnp.float32),
            pltpu.VMEM((t.block, 128), jnp.float32),
            pltpu.VMEM((t.block, 128), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=2 * 2 * D * _pairs(BH, Lq, Lk, causal),
            transcendentals=_pairs(BH, Lq, Lk, causal),
            bytes_accessed=(2 * q.size + k.size + v.size) * isz
            + BH * 8 * Lq * 4),
        compiler_params=_params(_vmem_bytes(t, D, isz, n_own=2, n_acc=3)),
        interpret=interpret,
    )(q, k, v)


def _bwd_call(q, k, v, o, lse, do, causal, sm_scale, blk_q, blk_k,
              interpret, dlse=None):
    BH, Lq, D = q.shape
    Lk = k.shape[1]
    tiling = _resolve_tiling(Lq, Lk, D, q.dtype, blk_q, blk_k, interpret)
    isz = q.dtype.itemsize
    delta = jnp.einsum("bld,bld->bl", do.astype(jnp.float32),
                       o.astype(jnp.float32))
    if dlse is not None:
        # lse cotangent folds into delta: ds = p∘(dP − delta + dlse)
        # because d lse/d s = p — so the kernels run unchanged with
        # delta' = delta − dlse (the flash_attention_block merge path)
        delta = delta - dlse.astype(jnp.float32)
    delta = jnp.broadcast_to(delta[:, None, :], (BH, 8, Lq))
    pairs = _pairs(BH, Lq, Lk, causal)
    rows = 2 * BH * 8 * Lq * 4                      # lse and delta

    t = tiling.dq
    own = pl.BlockSpec((1, t.block, D), lambda b, i, j: (b, i, 0))
    own_row = pl.BlockSpec((1, 8, t.block), lambda b, i, j: (b, 0, i))
    res = pl.BlockSpec((1, t.resident, D),
                       _live_map(causal, t.block, t.resident, up=True))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, sm_scale=sm_scale,
                          step=t.step),
        grid=(BH, Lq // t.block, Lk // t.resident),
        in_specs=[own, res, res, own, own_row, own_row],
        out_specs=own,
        out_shape=jax.ShapeDtypeStruct((BH, Lq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((t.block, D), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=3 * 2 * D * pairs, transcendentals=pairs,
            bytes_accessed=(3 * q.size + k.size + v.size) * isz + rows),
        compiler_params=_params(_vmem_bytes(t, D, isz, n_own=3, n_acc=1)),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    t = tiling.dkv
    own = pl.BlockSpec((1, t.block, D), lambda b, i, j: (b, i, 0))
    live = _live_map(causal, t.block, t.resident, up=False)
    res = pl.BlockSpec((1, t.resident, D), live)
    res_row = pl.BlockSpec((1, 8, t.resident),
                           lambda b, i, j: (b, 0, live(b, i, j)[1]))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, sm_scale=sm_scale,
                          step=t.step),
        grid=(BH, Lk // t.block, Lq // t.resident),
        in_specs=[own, own, res, res, res_row, res_row],
        out_specs=[own, own],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Lk, D), k.dtype),
            jax.ShapeDtypeStruct((BH, Lk, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((t.block, D), jnp.float32),
            pltpu.VMEM((t.block, D), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=4 * 2 * D * pairs, transcendentals=pairs,
            bytes_accessed=(2 * q.size + 2 * k.size + 2 * v.size) * isz
            + rows),
        compiler_params=_params(_vmem_bytes(t, D, isz, n_own=4, n_acc=2)),
        interpret=interpret,
    )(k, v, q, do, lse, delta)
    return dq, dk, dv


# --------------------------------------------------------------------------
# Public API with custom VJP
# --------------------------------------------------------------------------

def _bhl(x):
    B, L, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, L, D)


def _blhd(x, B, H):
    BH, L, D = x.shape
    return x.reshape(B, H, L, D).transpose(0, 2, 1, 3)


def flash_attention(q, k, v, causal: bool = True,
                    sm_scale: Optional[float] = None,
                    blk_q: Optional[int] = 256, blk_k: Optional[int] = 256,
                    interpret: bool = False) -> jax.Array:
    """[B, L, H, D] flash attention; Pallas fwd+bwd, O(L·blk) memory.

    blk_q/blk_k None → the autotuned pair for this (L, head_dim, dtype,
    platform) when one is cached (see autotune_blocks), else what
    flash_tiling picks for the shape, per kernel. Thin facade over
    flash_attention_block (which also exposes lse for the ring-attention
    merge); the discarded lse output contributes a zero cotangent that
    the shared backward folds away."""
    if blk_q is None or blk_k is None:
        tuned = get_tuned_blocks(q.shape[1], k.shape[1], q.shape[-1],
                                 q.dtype)
        if tuned is not None:
            blk_q = tuned[0] if blk_q is None else blk_q
            blk_k = tuned[1] if blk_k is None else blk_k
    return flash_attention_block(q, k, v, causal, sm_scale, blk_q, blk_k,
                                 interpret)[0]


# --------------------------------------------------------------------------
# Block-size autotuning: sweep + cache per (Lq, Lk, head_dim, dtype,
# platform) of ONE (blk_q, blk_k) pair for all three kernels, which then
# stands in for flash_tiling's per-kernel choice at that shape. Nothing
# in the tree calls it (ROADMAP D14); CPU hosts (tests) never measure —
# the heuristic ranking alone picks the block.
# --------------------------------------------------------------------------

_BLOCK_CACHE: dict = {}
_BLOCK_SIZES = (512, 256, 128, 64, 32, 16, 8)
_VMEM_BUDGET = 12 * 1024 * 1024  # conservative per-core VMEM budget


def clear_block_cache() -> None:
    _BLOCK_CACHE.clear()


def _platform() -> str:
    try:
        return jax.devices()[0].platform
    except Exception:  # pragma: no cover — no backend at all
        return "cpu"


def _block_cache_key(Lq, Lk, head_dim, dtype):
    return (int(Lq), int(Lk), int(head_dim), jnp.dtype(dtype).name,
            _platform())


def get_tuned_blocks(Lq, Lk, head_dim, dtype) -> Optional[tuple]:
    """Cache-only lookup of a tuned (blk_q, blk_k) — safe at trace time
    (no sweep). None when this shape was never autotuned."""
    return _BLOCK_CACHE.get(_block_cache_key(Lq, Lk, head_dim, dtype))


def _est_vmem_bytes(blk_q: int, blk_k: int, D: int, itemsize: int) -> int:
    """Rough resident-VMEM model of the fwd/bwd kernels: operand blocks in
    their dtype + f32 accumulators/score tiles."""
    operand = itemsize * (2 * blk_q * D + 2 * blk_k * D)
    accum = 4 * (3 * blk_q * D + 2 * blk_q * 128 + 2 * blk_q * blk_k)
    return operand + accum


def block_candidates(Lq: int, Lk: int, head_dim: int,
                     dtype=jnp.bfloat16) -> list:
    """(blk_q, blk_k) pairs that divide the sequence lengths, respect the
    Mosaic tiling, and fit the VMEM model — heuristic-best first
    (closest to the classic 256x256 flash block).

    Tiling: blk_k sits on a sublane dimension (>= 8 floor). blk_q also
    sits on the LANE dimension of the lse/delta blocks ``(1, 8, blk_q)``,
    which the TPU lowering only accepts in multiples of 128 (or the whole
    length) — every blk_q in 8..64 is refused for L=2048 (compiled for a
    described v5e, tests/test_tpu_compile.py)."""
    itemsize = jnp.dtype(dtype).itemsize
    qs = [b for b in _BLOCK_SIZES if b <= Lq and Lq % b == 0
          and (b % 128 == 0 or b == Lq)]
    ks = [b for b in _BLOCK_SIZES if b <= Lk and Lk % b == 0]
    pairs = [(bq, bk) for bq in qs for bk in ks
             if _est_vmem_bytes(bq, bk, head_dim, itemsize) <= _VMEM_BUDGET]
    return sorted(pairs, key=lambda p: (abs(p[0] - 256) + abs(p[1] - 256),
                                        -(p[0] * p[1])))


def _time_blocks(Lq, Lk, D, dtype, blk_q, blk_k, *, bh: int = 8,
                 reps: int = 3) -> float:
    """Wall-time one candidate: fwd kernel + both bwd kernels, jitted,
    median-of-reps. Raises what the compiler raises for a candidate it
    refuses."""
    import time as _time
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (bh, Lq, D), dtype)
    k = jax.random.normal(ks[1], (bh, Lk, D), dtype)
    v = jax.random.normal(ks[2], (bh, Lk, D), dtype)
    do = jax.random.normal(ks[3], (bh, Lq, D), dtype)
    scale = D ** -0.5
    fwd = jax.jit(lambda q, k, v: _fwd_call(
        q, k, v, True, scale, blk_q, blk_k, False))
    bwd = jax.jit(lambda q, k, v, o, lse, do: _bwd_call(
        q, k, v, o, lse, do, True, scale, blk_q, blk_k, False))
    o, lse = fwd(q, k, v)
    jax.block_until_ready(bwd(q, k, v, o, lse, do))  # warm both
    times = []
    for _ in range(reps):
        t0 = _time.perf_counter()
        o, lse = fwd(q, k, v)
        jax.block_until_ready(bwd(q, k, v, o, lse, do))
        times.append(_time.perf_counter() - t0)
    return sorted(times)[len(times) // 2]


def autotune_blocks(Lq: int, Lk: Optional[int] = None, head_dim: int = 64,
                    dtype=jnp.bfloat16, *,
                    measure: Optional[bool] = None) -> Optional[tuple]:
    """Pick (blk_q, blk_k) for the flash kernels at this shape and cache
    it per (Lq, Lk, head_dim, dtype, platform).

    measure=None → sweep-and-time only where the Mosaic kernels actually
    lower (real TPU; CPU hosts rank heuristically — timing interpret mode
    would measure the emulator, not the kernel). A candidate the compiler
    refuses just loses the sweep; if EVERY candidate is refused the
    kernels do not work here and that is an error, not a pick. Returns
    None when no block >= the Mosaic floor divides the lengths (callers
    fall back to the einsum/blockwise path). Call this EAGERLY (e.g.
    bench warm-up) so jit traces hit the cache via get_tuned_blocks."""
    Lk = Lq if Lk is None else Lk
    key = _block_cache_key(Lq, Lk, head_dim, dtype)
    if key in _BLOCK_CACHE:
        return _BLOCK_CACHE[key]
    cands = block_candidates(Lq, Lk, head_dim, dtype)
    if not cands:
        return None
    if measure is None:
        measure = kernels_supported()
    best = cands[0]
    if measure and len(cands) > 1:
        timed, last_err = {}, None
        for bk in cands:
            try:
                timed[bk] = _time_blocks(Lq, Lk, head_dim, dtype, *bk)
            except Exception as e:  # noqa: BLE001 — this candidate loses
                last_err = e
        if not timed:
            raise RuntimeError(
                f"flash autotune: all {len(cands)} block candidates for "
                f"Lq={Lq} Lk={Lk} head_dim={head_dim} failed to compile "
                f"or run") from last_err
        best = min(timed, key=timed.get)
    _BLOCK_CACHE[key] = best
    return best


# --------------------------------------------------------------------------
# Block API: (o, lse) with differentiable lse — the ring-attention inner
# kernel (per-rotation fused block whose results merge by log-sum-exp)
# --------------------------------------------------------------------------

#: checkpoint_name tags on the forward kernel's two outputs, o [B·H, L, D]
#: and lse [B·H, 8, L] float32: the only residuals of the backward that
#: nothing but the kernel can rebuild. models/llama.py:remat_policy_fn
#: reads them ("full" and "selective" save these names); nothing else does
FLASH_SAVE_NAMES = ("flash_o", "flash_lse")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention_block(q, k, v, causal: bool = True,
                          sm_scale: Optional[float] = None,
                          blk_q: Optional[int] = 256,
                          blk_k: Optional[int] = 256,
                          interpret: bool = False):
    """Fused attention of q against ONE KV block: returns (o [B,L,H,D],
    lse [B,H,Lq]). blk_q / blk_k None: flash_tiling's choice for the
    shape. lse is differentiable — its cotangent (nonzero when
    block results are merged across ring rotations) folds into the
    backward kernels' delta term, so the same Pallas kernels serve both
    the standalone and the ring-merged case."""
    out, _ = _block_vjp_fwd(q, k, v, causal, sm_scale, blk_q, blk_k,
                            interpret)
    return out


def _block_vjp_fwd(q, k, v, causal, sm_scale, blk_q, blk_k, interpret):
    """Forward rule: the kernel's o and lse are tagged FLASH_SAVE_NAMES
    before anything is derived from them, so both the returned values and
    the residuals come from the tagged arrays: under a jax.checkpoint whose
    policy saves the names the backward is handed them, and only q, k and v
    are rebuilt. Outside a checkpoint the tags do nothing."""
    B, Lq, H, D = q.shape
    scale = sm_scale if sm_scale is not None else D ** -0.5
    o, lse = _fwd_call(_bhl(q), _bhl(k), _bhl(v), causal, scale,
                       blk_q, blk_k, interpret)
    o = checkpoint_name(o, FLASH_SAVE_NAMES[0])
    lse = checkpoint_name(lse, FLASH_SAVE_NAMES[1])
    lse_bhl = lse[:, 0, :].reshape(B, H, Lq)
    return (_blhd(o, B, H), lse_bhl), (q, k, v, o, lse)


def _block_vjp_bwd(causal, sm_scale, blk_q, blk_k, interpret, res, g):
    do, dlse = g
    q, k, v, o, lse = res
    B, Lq, H, D = q.shape
    scale = sm_scale if sm_scale is not None else D ** -0.5
    dq, dk, dv = _bwd_call(_bhl(q), _bhl(k), _bhl(v), o, lse, _bhl(do),
                           causal, scale, blk_q, blk_k, interpret,
                           dlse=dlse.reshape(B * H, Lq))
    return _blhd(dq, B, H), _blhd(dk, B, H), _blhd(dv, B, H)


flash_attention_block.defvjp(_block_vjp_fwd, _block_vjp_bwd)


def kernel_calls(jaxpr) -> Counter:
    """pallas_calls in a jaxpr by the kernel function's name, sub-jaxprs
    included (a scan body counts once, however many turns it takes):
    {"_fwd_kernel": 1, "_dq_kernel": 1, "_dkv_kernel": 1} is one layer's
    value-and-grad with the forward's outputs kept."""
    counts: Counter = Counter()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            counts[eqn.params["jaxpr"].debug_info.func_name] += 1
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            counts += kernel_calls(sub)
    return counts


def kernels_supported() -> bool:
    """True when the Mosaic TPU kernels can actually lower here."""
    return jax.devices()[0].platform == "tpu"


def flash_attention_sharded(q, k, v, mesh, *, causal: bool = True,
                            head_axis: str = "tp",
                            batch_axes=("dp", "fsdp")) -> jax.Array:
    """shard_map wrapper: pallas_call is a Mosaic custom call that GSPMD
    cannot auto-partition, so run the kernel per-shard (batch over dp/fsdp,
    heads over tp; seq must NOT be sharded — use ring attention for sp)."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.mesh import shard_map_compat

    if mesh.shape.get("sp", 1) > 1:
        raise ValueError("flash_attention_sharded cannot shard the sequence "
                         "axis; use attention='ring' when sp > 1")
    spec = P(batch_axes, None, head_axis, None)
    fn = shard_map_compat(
        functools.partial(flash_attention, causal=causal,
                          blk_q=None, blk_k=None),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)
