"""Flash attention: Pallas forward AND backward kernels.

No reference implementation exists in-tree (the reference delegates to
vLLM/CUDA — SURVEY.md §5 long-context); built from the public flash/
blockwise-attention recipes (PAPERS.md) on the Pallas TPU pattern:
stream KV blocks through VMEM with online-softmax accumulators in scratch,
never materializing the [L, L] score matrix in HBM — in either pass.

  flash_attention(q, k, v)  [B, L, H, D] → [B, L, H, D]
    fwd:  grid (B·H, Lq/blkq, Lk/blkk); saves per-row logsumexp.
    bwd:  two kernels — dq over (B·H, nq, nk) and dk/dv over (B·H, nk, nq)
          — recompute p = exp(s − lse) blockwise from the saved lse.
    causal blocks above the diagonal are skipped in all three kernels.

`blockwise_attention` is the pure-JAX (lax.scan) equivalent: same online
softmax, differentiable by autodiff, used as the numerics reference and as
a portable fallback.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

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
# Pallas kernels ([BH, L, D] layout inside)
# --------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, causal, sm_scale, blk_q, blk_k):
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    run = True
    if causal:
        run = ki * blk_k <= qi * blk_q + blk_q - 1

    @pl.when(run)
    def _block():
        q = q_ref[0]
        s = lax.dot_general(  # bf16×bf16 → f32 accumulate on the MXU
            q, k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if causal:
            qpos = qi * blk_q + lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 0)
            kpos = ki * blk_k + lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 1)
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        m_prev, l_prev = m_ref[:, :1], l_ref[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.where(jnp.isneginf(s), 0.0, jnp.exp(s - m_new))
        corr = jnp.where(jnp.isneginf(m_prev), 0.0, jnp.exp(m_prev - m_new))
        l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        lse = m_ref[:, 0] + jnp.log(l[:, 0])
        # lse is materialized [8, blk_q] (sublane-replicated) to satisfy
        # the TPU (8, 128) tiling floor for output blocks.
        lse_ref[0] = jnp.broadcast_to(lse[None, :], lse_ref.shape[1:])


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc, *, causal, sm_scale, blk_q, blk_k):
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = True
    if causal:
        run = ki * blk_k <= qi * blk_q + blk_q - 1

    @pl.when(run)
    def _block():
        s = lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
        if causal:
            qpos = qi * blk_q + lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 0)
            kpos = ki * blk_k + lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 1)
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0][:, None])     # masked rows → exp(-inf)=0
        dp = lax.dot_general(do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0][:, None])
        dq_acc[:] += lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *,
                causal, sm_scale, blk_q, blk_k):
    ki, qi = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = True
    if causal:
        run = qi * blk_q + blk_q - 1 >= ki * blk_k

    @pl.when(run)
    def _block():
        s = lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
        if causal:
            qpos = qi * blk_q + lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 0)
            kpos = ki * blk_k + lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 1)
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0][:, None])
        dv_acc[:] += lax.dot_general(
            p.astype(do_ref.dtype), do_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = lax.dot_general(do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0][:, None])
        dk_acc[:] += lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


# --------------------------------------------------------------------------
# pallas_call wrappers
# --------------------------------------------------------------------------

def _fwd_call(q, k, v, causal, sm_scale, blk_q, blk_k, interpret):
    BH, Lq, D = q.shape
    Lk = k.shape[1]
    blk_q, blk_k = min(blk_q, Lq), min(blk_k, Lk)
    if Lq % blk_q or Lk % blk_k:
        raise ValueError(f"L ({Lq},{Lk}) must divide blocks ({blk_q},{blk_k})")
    kernel = functools.partial(_fwd_kernel, causal=causal, sm_scale=sm_scale,
                               blk_q=blk_q, blk_k=blk_k)
    return pl.pallas_call(
        kernel,
        grid=(BH, Lq // blk_q, Lk // blk_k),
        in_specs=[
            pl.BlockSpec((1, blk_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, blk_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, blk_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 8, blk_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Lq, D), q.dtype),
            jax.ShapeDtypeStruct((BH, 8, Lq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_q, D), jnp.float32),
            pltpu.VMEM((blk_q, 128), jnp.float32),
            pltpu.VMEM((blk_q, 128), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


def _bwd_call(q, k, v, o, lse, do, causal, sm_scale, blk_q, blk_k,
              interpret, dlse=None):
    BH, Lq, D = q.shape
    Lk = k.shape[1]
    blk_q, blk_k = min(blk_q, Lq), min(blk_k, Lk)
    delta = jnp.einsum("bld,bld->bl", do.astype(jnp.float32),
                       o.astype(jnp.float32))
    if dlse is not None:
        # lse cotangent folds into delta: ds = p∘(dP − delta + dlse)
        # because d lse/d s = p — so the kernels run unchanged with
        # delta' = delta − dlse (the flash_attention_block merge path)
        delta = delta - dlse.astype(jnp.float32)
    delta = jnp.broadcast_to(delta[:, None, :], (BH, 8, Lq))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, sm_scale=sm_scale,
                          blk_q=blk_q, blk_k=blk_k),
        grid=(BH, Lq // blk_q, Lk // blk_k),
        in_specs=[
            pl.BlockSpec((1, blk_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, blk_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, blk_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 8, blk_q), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, 8, blk_q), lambda b, i, j: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, blk_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Lq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((blk_q, D), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, sm_scale=sm_scale,
                          blk_q=blk_q, blk_k=blk_k),
        grid=(BH, Lk // blk_k, Lq // blk_q),
        in_specs=[
            pl.BlockSpec((1, blk_k, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_k, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_q, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, blk_q, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, 8, blk_q), lambda b, i, j: (b, 0, j)),
            pl.BlockSpec((1, 8, blk_q), lambda b, i, j: (b, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, blk_k, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_k, D), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Lk, D), k.dtype),
            jax.ShapeDtypeStruct((BH, Lk, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_k, D), jnp.float32),
            pltpu.VMEM((blk_k, D), jnp.float32),
        ],
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

    blk_q/blk_k None → use the autotuned block for this (L, head_dim,
    dtype, platform) when one is cached (see autotune_blocks), else the
    classic 256. Thin facade over flash_attention_block (which also
    exposes lse for the ring-attention merge); the discarded lse output
    contributes a zero cotangent that the shared backward folds away."""
    if blk_q is None or blk_k is None:
        tuned = get_tuned_blocks(q.shape[1], k.shape[1], q.shape[-1],
                                 q.dtype) or (256, 256)
        blk_q = tuned[0] if blk_q is None else blk_q
        blk_k = tuned[1] if blk_k is None else blk_k
    return flash_attention_block(q, k, v, causal, sm_scale, blk_q, blk_k,
                                 interpret)[0]


# --------------------------------------------------------------------------
# Block API: (o, lse) with differentiable lse — the ring-attention inner
# kernel (per-rotation fused block whose results merge by log-sum-exp)
# --------------------------------------------------------------------------

def pick_block(L: int, preferred: int = 256, min_block: int = 8
               ) -> Optional[int]:
    """Largest kernel block size <= preferred that divides L (Pallas grid
    constraint); None when no divisor >= min_block exists. The default
    floor of 8 matches the Mosaic sublane tiling — COMPILED kernels must
    never run below it (callers fall back to the einsum/blockwise path
    instead); only interpret-mode callers, where no Mosaic tiling exists,
    may pass min_block=1 for tiny shards."""
    for b in (preferred, 128, 64, 32, 16, 8, 4, 2, 1):
        if min_block <= b <= preferred and L % b == 0:
            return min(b, L)
    return None


# --------------------------------------------------------------------------
# Block-size autotuning: sweep + cache per (Lq, Lk, head_dim, dtype,
# platform). The fixed 256 default is tuned for long sequences; at bench
# shapes (L=2048, head_dim 128) the best (blk_q, blk_k) depends on VMEM
# pressure and MXU occupancy, so measure instead of guessing. CPU hosts
# (tests) never measure — the heuristic ranking alone picks the block.
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention_block(q, k, v, causal: bool = True,
                          sm_scale: Optional[float] = None,
                          blk_q: int = 256, blk_k: int = 256,
                          interpret: bool = False):
    """Fused attention of q against ONE KV block: returns (o [B,L,H,D],
    lse [B,H,Lq]). lse is differentiable — its cotangent (nonzero when
    block results are merged across ring rotations) folds into the
    backward kernels' delta term, so the same Pallas kernels serve both
    the standalone and the ring-merged case."""
    out, _ = _block_vjp_fwd(q, k, v, causal, sm_scale, blk_q, blk_k,
                            interpret)
    return out


def _block_vjp_fwd(q, k, v, causal, sm_scale, blk_q, blk_k, interpret):
    B, Lq, H, D = q.shape
    scale = sm_scale if sm_scale is not None else D ** -0.5
    o, lse = _fwd_call(_bhl(q), _bhl(k), _bhl(v), causal, scale,
                       blk_q, blk_k, interpret)
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
