"""Paged-KV attention — Pallas TPU kernels + JAX references.

No equivalent exists in the reference tree (serving delegates to vLLM's
CUDA PagedAttention — reference: python/ray/llm/_internal/serve/
deployments/llm/vllm/vllm_engine.py); built from the paged/ragged
attention recipe (PAPERS.md "Ragged Paged Attention") on the Pallas
scalar-prefetch pattern:

  - the KV cache lives in HBM as fixed-size pages
    ``[total_pages, kv_heads, page_size, head_dim]``; a sequence's cache
    is the pages named by its row of ``page_table`` — no per-sequence
    contiguous allocation, so fragmentation-free continuous batching;
  - ``paged_attention``: one decode token per sequence
    ``[B, q_heads, head_dim]``, grid (B, max_pages) — the original
    decode-only kernel, kept as the single-token oracle;
  - ``ragged_paged_attention``: a RAGGED token batch ``[T, Hq, D]`` —
    concatenated query tokens from R sequences described by
    ``(q_start, q_len, kv_len)`` rows, where q_len is a prefill chunk
    for some rows and 1 for decode rows. Grid (T, max_pages): the
    scalar-prefetched page table (plus per-token row/visibility vectors
    derived from the descriptors in-program) drives the BlockSpec
    index_map, each grid step DMAs exactly one page, causal masking is
    a per-token visible-length compare, and online-softmax scratch
    carries across the page axis. One dispatch serves mixed
    prefill+decode — the engine's whole step program;
  - int8 KV pages: both ragged paths take optional per-(page, head,
    slot) scale arrays ``[P, Hkv, ps]`` and dequantize in-kernel
    (k_f32 = k_int8 * scale), halving KV HBM per token;
  - GQA: q is grouped [kv_heads, q_per_kv, head_dim] and the score matmul
    batches over kv_heads on the MXU.

The ``*_reference`` functions are the pure-JAX gather equivalents — the
numerics oracles and the portable fallbacks on CPU test meshes.
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
# Pure-JAX reference (portable fallback + numerics oracle)
# --------------------------------------------------------------------------

def paged_attention_reference(q, k_pages, v_pages, page_table, seq_lens, *,
                              sm_scale: Optional[float] = None) -> jax.Array:
    """Gather-based paged attention.

    q:          [B, Hq, D]       one decode token per sequence
    k/v_pages:  [P, Hkv, ps, D]  the shared page pool
    page_table: [B, max_pages]   page ids per sequence (unused tail: any)
    seq_lens:   [B]              valid KV tokens (incl. the current one)
    returns     [B, Hq, D]
    """
    B, Hq, D = q.shape
    P_, Hkv, ps, _ = k_pages.shape
    max_pages = page_table.shape[1]
    if sm_scale is None:
        sm_scale = D ** -0.5
    # gather pages -> [B, Hkv, max_pages*ps, D]
    k = k_pages[page_table]  # [B, max_pages, Hkv, ps, D]
    v = v_pages[page_table]
    k = k.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, max_pages * ps, D)
    v = v.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, max_pages * ps, D)
    qg = q.reshape(B, Hkv, Hq // Hkv, D).astype(jnp.float32)
    s = jnp.einsum("bgqd,bgtd->bgqt", qg, k.astype(jnp.float32)) * sm_scale
    pos = jnp.arange(max_pages * ps)[None, None, None, :]
    s = jnp.where(pos < seq_lens[:, None, None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgqt,bgtd->bgqd", p, v.astype(jnp.float32))
    return o.reshape(B, Hq, D).astype(q.dtype)


# --------------------------------------------------------------------------
# Pallas kernel
# --------------------------------------------------------------------------

def _decode_kernel(page_table_ref, seq_lens_ref,  # scalar prefetch
                   q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, sm_scale, page_size,
                   q_per_kv):
    b, pi = pl.program_id(0), pl.program_id(1)
    n_pages = pl.num_programs(1)
    seq_len = seq_lens_ref[b]

    @pl.when(pi == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # tokens this page holds for this sequence: (0, page_size]
    page_start = pi * page_size
    valid = seq_len - page_start

    @pl.when(valid > 0)
    def _page():
        q = q_ref[0].astype(jnp.float32)         # [Hq, D]
        k = k_ref[0]                              # [Hkv, ps, D]
        v = v_ref[0]
        Hq = q.shape[0]
        Hkv = k.shape[0]
        qg = q.reshape(Hkv, q_per_kv, q.shape[-1])
        # batched over kv heads on the MXU: [Hkv, qpk, ps]
        s = lax.dot_general(
            qg, k.astype(jnp.float32),
            (((2,), (2,)), ((0,), (0,)))) * sm_scale
        col = lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(col < valid, s, _NEG_INF)
        m_prev = m_ref[:, :1]                     # [Hq, 1]
        l_prev = l_ref[:, :1]
        s2 = s.reshape(Hq, page_size)
        m_new = jnp.maximum(m_prev, s2.max(axis=-1, keepdims=True))
        p = jnp.where(jnp.isneginf(s2), 0.0, jnp.exp(s2 - m_new))
        corr = jnp.where(jnp.isneginf(m_prev), 0.0, jnp.exp(m_prev - m_new))
        l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
        pv = lax.dot_general(                      # [Hkv, qpk, D]
            p.reshape(Hkv, q_per_kv, page_size).astype(v.dtype), v,
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        acc_ref[:] = acc_ref[:] * corr + pv.reshape(Hq, -1)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(pi == n_pages - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def _paged_attention_pallas(q, k_pages, v_pages, page_table, seq_lens,
                            sm_scale: float, interpret: bool = False):
    B, Hq, D = q.shape
    P_, Hkv, ps, _ = k_pages.shape
    max_pages = page_table.shape[1]
    q_per_kv = Hq // Hkv

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, max_pages),
        in_specs=[
            pl.BlockSpec((1, Hq, D), lambda b, p, pt, sl: (b, 0, 0)),
            pl.BlockSpec((1, Hkv, ps, D),
                         lambda b, p, pt, sl: (pt[b, p], 0, 0, 0)),
            pl.BlockSpec((1, Hkv, ps, D),
                         lambda b, p, pt, sl: (pt[b, p], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hq, D), lambda b, p, pt, sl: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hq, D), jnp.float32),
            pltpu.VMEM((Hq, 128), jnp.float32),
            pltpu.VMEM((Hq, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(_decode_kernel, sm_scale=sm_scale,
                               page_size=ps, q_per_kv=q_per_kv)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        interpret=interpret,
    )(page_table, seq_lens, q, k_pages, v_pages)


def kernels_supported(device: Optional[jax.Device] = None) -> bool:
    dev = device if device is not None else jax.devices()[0]
    return dev.platform == "tpu"


def paged_attention(q, k_pages, v_pages, page_table, seq_lens, *,
                    sm_scale: Optional[float] = None,
                    interpret: Optional[bool] = None,
                    impl: Optional[str] = None) -> jax.Array:
    """Dispatch: Pallas kernel on TPU, gather reference elsewhere.

    ``interpret=True`` forces the kernel through the Pallas interpreter
    (CPU) — used by tests to validate the kernel itself off-TPU.
    ``impl`` pins the implementation outright ("kernel" | "reference"):
    code that compiles for a SPECIFIC mesh (the tp serving engine) must
    choose by the mesh's platform, because the process's default backend
    (what the interpret=None autodetect sees) can be a different
    accelerator than the mesh the program runs on.
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if q.shape[1] % k_pages.shape[1]:
        raise ValueError(
            f"q heads {q.shape[1]} not a multiple of kv heads "
            f"{k_pages.shape[1]}")
    if impl == "reference":
        return paged_attention_reference(
            q, k_pages, v_pages, page_table, seq_lens, sm_scale=sm_scale)
    if impl is not None and impl != "kernel":
        raise ValueError(f"impl must be 'kernel' or 'reference', "
                         f"got {impl!r}")
    if interpret is None:
        if impl is None and not kernels_supported():
            return paged_attention_reference(
                q, k_pages, v_pages, page_table, seq_lens,
                sm_scale=sm_scale)
        interpret = False
    return _paged_attention_pallas(
        q, k_pages, v_pages, page_table,
        seq_lens.astype(jnp.int32), sm_scale, interpret)


# --------------------------------------------------------------------------
# Ragged paged attention: mixed prefill chunks + decode rows, one dispatch
# --------------------------------------------------------------------------
#
# Ragged batch layout (the engine's step program):
#   q [T, Hq, D] holds R sequences' query tokens concatenated; row r owns
#   tokens q_start[r] .. q_start[r]+q_len[r]-1 (disjoint spans; q_len 0 =
#   inactive row; tokens owned by no row are padding and produce zeros).
#   Token j of row r sits at absolute position kv_len[r]-q_len[r]+j and
#   causally sees kv positions <= that, i.e. the first
#   kv_len[r]-q_len[r]+j+1 slots of the row's pages (the row's OWN chunk
#   K/V included — the caller scatters the chunk into the pages before
#   attending, exactly like the decode step writes-then-attends).


def _token_descriptors(q_start, q_len, kv_len, T: int):
    """Per-token (owning row, visible kv length) from per-row descriptors.

    O(R*T) int compare — noise next to attention; runs inside the jitted
    wrapper so the host never materializes per-token metadata.
    """
    tvec = jnp.arange(T, dtype=jnp.int32)
    in_row = (tvec[None, :] >= q_start[:, None]) & \
             (tvec[None, :] < (q_start + q_len)[:, None])       # [R, T]
    token_row = jnp.argmax(in_row, axis=0).astype(jnp.int32)
    owned = jnp.any(in_row, axis=0)
    vis = kv_len[token_row] - q_len[token_row] \
        + (tvec - q_start[token_row]) + 1
    token_vis = jnp.where(owned, vis, 0).astype(jnp.int32)
    return token_row, token_vis


def ragged_paged_attention_reference(q, k_pages, v_pages, page_table,
                                     q_start, q_len, kv_len, *,
                                     k_scale=None, v_scale=None,
                                     sm_scale: Optional[float] = None,
                                     max_q_len: Optional[int] = None,
                                     decode_rows: int = 0) -> jax.Array:
    """Gather-based ragged paged attention (oracle + CPU fallback).

    q: [T, Hq, D]; k/v_pages: [P, Hkv, ps, D] (int8 when scales given);
    k/v_scale: [P, Hkv, ps] per-(page, head, slot) dequant scales or
    None; page_table: [R, max_pages]; q_start/q_len/kv_len: [R].

    ``decode_rows``/``max_q_len`` are STATIC cost hints, not semantics:
    the first ``decode_rows`` rows must have q_len <= 1 and are computed
    decode-style (one gathered score row each); the rest are prefill
    rows computed on ``max_q_len``-sized blocks (default T). Wrong hints
    that still satisfy the q_len bounds only cost time, never accuracy.
    """
    T, Hq, D = q.shape
    R, max_pages = page_table.shape
    _, Hkv, ps, _ = k_pages.shape
    if sm_scale is None:
        sm_scale = D ** -0.5
    max_kv = max_pages * ps
    qpk = Hq // Hkv

    # one page gather per row -> [R, Hkv, max_kv, D] fp32 (dequantized)
    kr = k_pages[page_table]                     # [R, mp, Hkv, ps, D]
    vr = v_pages[page_table]
    kr = kr.astype(jnp.float32)
    vr = vr.astype(jnp.float32)
    if k_scale is not None:
        kr = kr * k_scale[page_table].astype(jnp.float32)[..., None]
        vr = vr * v_scale[page_table].astype(jnp.float32)[..., None]
    kr = kr.transpose(0, 2, 1, 3, 4).reshape(R, Hkv, max_kv, D)
    vr = vr.transpose(0, 2, 1, 3, 4).reshape(R, Hkv, max_kv, D)

    out = jnp.zeros((T, Hq, D), jnp.float32)
    tkv = jnp.arange(max_kv)

    def _safe_softmax(s):
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.where(jnp.isneginf(s), 0.0,
                      jnp.exp(s - jnp.where(jnp.isneginf(m), 0.0, m)))
        return p / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)

    Rd = decode_rows
    if Rd:
        idx = jnp.clip(q_start[:Rd], 0, T - 1)
        qd = q[idx].reshape(Rd, Hkv, qpk, D).astype(jnp.float32)
        s = jnp.einsum("rgqd,rgtd->rgqt", qd, kr[:Rd]) * sm_scale
        vis = jnp.where(q_len[:Rd] > 0, kv_len[:Rd], 0)
        s = jnp.where(tkv[None, None, None, :] < vis[:, None, None, None],
                      s, _NEG_INF)
        od = jnp.einsum("rgqt,rgtd->rgqd", _safe_softmax(s), vr[:Rd])
        od = od.reshape(Rd, Hq, D)
        od = jnp.where((q_len[:Rd] > 0)[:, None, None], od, 0.0)
        out = out.at[idx].add(od)

    if R - Rd:
        C = min(max_q_len if max_q_len is not None else T, T)
        qpad = jnp.pad(q.astype(jnp.float32), ((0, C), (0, 0), (0, 0)))
        starts = jnp.clip(q_start[Rd:], 0, T)

        qc = jax.vmap(lambda s0: lax.dynamic_slice(
            qpad, (s0, 0, 0), (C, Hq, D)))(starts)   # [Rp, C, Hq, D]
        qc = qc.reshape(-1, C, Hkv, qpk, D)
        s = jnp.einsum("rcgqd,rgtd->rcgqt", qc, kr[Rd:]) * sm_scale
        cvec = jnp.arange(C)
        vis = kv_len[Rd:, None] - q_len[Rd:, None] + cvec[None, :] + 1
        vis = jnp.where(cvec[None, :] < q_len[Rd:, None], vis, 0)
        s = jnp.where(tkv[None, None, None, None, :]
                      < vis[:, :, None, None, None], s, _NEG_INF)
        oc = jnp.einsum("rcgqt,rgtd->rcgqd", _safe_softmax(s), vr[Rd:])
        oc = oc.reshape(-1, C, Hq, D)
        oc = jnp.where((cvec[None, :] < q_len[Rd:, None])[:, :, None, None],
                       oc, 0.0)
        dest = starts[:, None] + cvec[None, :]        # [Rp, C] < T + C
        out = out + jnp.zeros((T + C, Hq, D),
                              jnp.float32).at[dest].add(oc)[:T]
    return out.astype(q.dtype)


def _ragged_kernel(tr_ref, vis_ref, pt_ref,          # scalar prefetch
                   q_ref, k_ref, v_ref, *rest, sm_scale, page_size,
                   q_per_kv, has_scales):
    if has_scales:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    t, pi = pl.program_id(0), pl.program_id(1)
    n_pages = pl.num_programs(1)
    vis = vis_ref[t]          # visible kv length of THIS token (0 = pad)

    @pl.when(pi == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    page_start = pi * page_size
    valid = vis - page_start

    @pl.when(valid > 0)
    def _page():
        q = q_ref[0].astype(jnp.float32)          # [Hq, D]
        k = k_ref[0].astype(jnp.float32)          # [Hkv, ps, D]
        v = v_ref[0].astype(jnp.float32)
        if has_scales:
            k = k * ks_ref[0].astype(jnp.float32)[..., None]
            v = v * vs_ref[0].astype(jnp.float32)[..., None]
        Hq = q.shape[0]
        Hkv = k.shape[0]
        qg = q.reshape(Hkv, q_per_kv, q.shape[-1])
        s = lax.dot_general(
            qg, k, (((2,), (2,)), ((0,), (0,)))) * sm_scale
        col = lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(col < valid, s, _NEG_INF)
        m_prev = m_ref[:, :1]                     # [Hq, 1]
        l_prev = l_ref[:, :1]
        s2 = s.reshape(Hq, page_size)
        m_new = jnp.maximum(m_prev, s2.max(axis=-1, keepdims=True))
        p = jnp.where(jnp.isneginf(s2), 0.0, jnp.exp(s2 - m_new))
        corr = jnp.where(jnp.isneginf(m_prev), 0.0, jnp.exp(m_prev - m_new))
        l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
        pv = lax.dot_general(                      # [Hkv, qpk, D]
            p.reshape(Hkv, q_per_kv, page_size), v,
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        acc_ref[:] = acc_ref[:] * corr + pv.reshape(Hq, -1)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(pi == n_pages - 1)
    def _finish():
        # padding tokens never accumulate: l stays 0 -> output 0
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def _ragged_attention_pallas(q, k_pages, v_pages, page_table,
                             q_start, q_len, kv_len, k_scale, v_scale,
                             sm_scale: float, interpret: bool = False):
    T, Hq, D = q.shape
    _, Hkv, ps, _ = k_pages.shape
    max_pages = page_table.shape[1]
    q_per_kv = Hq // Hkv
    token_row, token_vis = _token_descriptors(
        q_start.astype(jnp.int32), q_len.astype(jnp.int32),
        kv_len.astype(jnp.int32), T)

    has_scales = k_scale is not None
    kv_spec = pl.BlockSpec(
        (1, Hkv, ps, D), lambda t, p, tr, vis, pt: (pt[tr[t], p], 0, 0, 0))
    in_specs = [
        pl.BlockSpec((1, Hq, D), lambda t, p, tr, vis, pt: (t, 0, 0)),
        kv_spec, kv_spec,
    ]
    operands = [q, k_pages, v_pages]
    if has_scales:
        sc_spec = pl.BlockSpec(
            (1, Hkv, ps), lambda t, p, tr, vis, pt: (pt[tr[t], p], 0, 0))
        in_specs += [sc_spec, sc_spec]
        operands += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(T, max_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hq, D),
                               lambda t, p, tr, vis, pt: (t, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hq, D), jnp.float32),
            pltpu.VMEM((Hq, 128), jnp.float32),
            pltpu.VMEM((Hq, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(_ragged_kernel, sm_scale=sm_scale,
                               page_size=ps, q_per_kv=q_per_kv,
                               has_scales=has_scales)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, Hq, D), q.dtype),
        interpret=interpret,
    )(token_row, token_vis, page_table, *operands)


def ragged_paged_attention(q, k_pages, v_pages, page_table, q_start,
                           q_len, kv_len, *, k_scale=None, v_scale=None,
                           sm_scale: Optional[float] = None,
                           max_q_len: Optional[int] = None,
                           decode_rows: int = 0,
                           interpret: Optional[bool] = None,
                           impl: Optional[str] = None) -> jax.Array:
    """Mixed prefill+decode attention over a ragged token batch in ONE
    dispatch. Dispatch rules identical to ``paged_attention``: Pallas
    kernel on TPU, gather reference elsewhere; ``impl`` pins the choice
    for mesh-specific programs, ``interpret=True`` runs the kernel
    through the Pallas interpreter on CPU (the tier-1 kernel tests).
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if q.shape[1] % k_pages.shape[1]:
        raise ValueError(
            f"q heads {q.shape[1]} not a multiple of kv heads "
            f"{k_pages.shape[1]}")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be given together")
    if impl == "reference":
        return ragged_paged_attention_reference(
            q, k_pages, v_pages, page_table, q_start, q_len, kv_len,
            k_scale=k_scale, v_scale=v_scale, sm_scale=sm_scale,
            max_q_len=max_q_len, decode_rows=decode_rows)
    if impl is not None and impl != "kernel":
        raise ValueError(f"impl must be 'kernel' or 'reference', "
                         f"got {impl!r}")
    if interpret is None:
        if impl is None and not kernels_supported():
            return ragged_paged_attention_reference(
                q, k_pages, v_pages, page_table, q_start, q_len, kv_len,
                k_scale=k_scale, v_scale=v_scale, sm_scale=sm_scale,
                max_q_len=max_q_len, decode_rows=decode_rows)
        interpret = False
    return _ragged_attention_pallas(
        q, k_pages, v_pages, page_table, q_start.astype(jnp.int32),
        q_len.astype(jnp.int32), kv_len.astype(jnp.int32),
        k_scale, v_scale, sm_scale, interpret)


# --------------------------------------------------------------------------
# Page-cache update helper (the ragged step's one scatter per layer)
# --------------------------------------------------------------------------

def write_ragged_kv(k_pages, v_pages, k_t, v_t, token_page, token_slot,
                    k_scale=None, v_scale=None):
    """Scatter a ragged batch's per-token K/V into the page pool.

    k_t/v_t: [T, Hkv, D] this layer's roped K/V for every ragged token
    (decode rows and prefill chunks alike); token_page/token_slot: [T]
    destination page id and in-page slot — padding tokens point at page
    0 (the scratch page, garbage by contract). When the pool is int8
    (``k_scale``/``v_scale`` [P, Hkv, ps] given), rows quantize with
    per-token/per-head scales (ops.int8.quantize_kv) and the scales
    scatter alongside — every write stays local, nothing requantizes.
    Returns (k_pages, v_pages, k_scale, v_scale); scales pass through as
    None on fp pools.
    """
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be given together")
    if k_scale is not None:
        from ray_tpu.ops.int8 import quantize_kv
        kq, ks = quantize_kv(k_t)                 # [T, Hkv, D], [T, Hkv]
        vq, vs = quantize_kv(v_t)
        k_pages = k_pages.at[token_page, :, token_slot, :].set(kq)
        v_pages = v_pages.at[token_page, :, token_slot, :].set(vq)
        k_scale = k_scale.at[token_page, :, token_slot].set(
            ks.astype(k_scale.dtype))
        v_scale = v_scale.at[token_page, :, token_slot].set(
            vs.astype(v_scale.dtype))
    else:
        # advanced indices at axes 0 and 2 are separated by a basic
        # slice, so the indexed result is [T, Hkv, D]
        k_pages = k_pages.at[token_page, :, token_slot, :].set(
            k_t.astype(k_pages.dtype))
        v_pages = v_pages.at[token_page, :, token_slot, :].set(
            v_t.astype(v_pages.dtype))
    return k_pages, v_pages, k_scale, v_scale
