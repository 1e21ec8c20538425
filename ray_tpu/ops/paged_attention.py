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
  - ``ragged_paged_attention``: a RAGGED token batch ``[T, Hq, D]`` —
    concatenated query tokens from R sequences described by
    ``(q_start, q_len, kv_len)`` rows, where q_len is a prefill chunk
    for some rows and 1 for decode rows. One dispatch serves mixed
    prefill+decode — the engine's whole step program. The kernel is
    BLOCKED: a grid step is a tile of ONE row's query tokens (one token
    for the first ``decode_rows`` rows, up to 128 for the others,
    ceil(max_q_len / 128) tiles a row) and loops over blocks of several
    of that row's pages. The pool stays in HBM; a block's pages come by
    one async copy each into a double buffer, started a block ahead (the
    next tile's first block behind the current tile's last), so K/V is
    fetched once per tile, not once per token. The loop runs
    ceil(visible / block) turns from the scalar-prefetched lengths:
    pages past a tile's last visible position are neither copied nor
    multiplied, empty rows and tiles past q_len cost one empty grid
    step, and only blocks that straddle the tile's own positions are
    causally masked. Operands go to the MXU in the pool's dtype (all
    ``q_per_kv`` query heads of a KV head in one [tokens * q_per_kv, D]
    operand) with fp32 accumulation; running max, sum and rescale are
    fp32. XLA gathers q into tile order and the outputs back (rows may
    start anywhere in q);
  - int8 KV pages: the ragged paths take optional per-(page, head,
    slot) scale arrays ``[P, Hkv, ps]``. The kernel feeds the int8
    values to the MXU as bf16 (exact) and applies the scales to the
    score columns and to the probabilities, so no dequantized K/V copy
    exists; int8 halves KV HBM per token;
  - GQA: the query heads of one KV head share each K/V fetch;
  - the ragged paths take one layer's pool or the whole STACKED pool
    ``[L, P, Hkv, ps, D]`` with a layer index (scalar prefetch): the
    step programs carry the pool whole and never slice a layer out for
    a custom call (a copy of it), and ``write_ragged_kv`` updates it in
    place (``_kv_write_pallas``, pool aliased in to out);
  - a LATENT pool (``v_pages=None`` with a ``v_width``): the absorbed
    form of latent attention (MLA) is multi-query attention over ONE kv
    head whose row is the token's normed latent followed by its shared
    rotary key part; the score runs over the whole row and the value is
    the row's leading ``v_width`` values. There is no V leaf: the kernel
    fetches a K block once, into one buffer, and takes the value as a
    lane slice of it; the write kernel moves one leaf. With K and V pools
    both kernels lower as they did before the form existed.

Three STATIC options of the ragged paths, for a block whose attention
layers differ (MiMo-V2-Flash is the first): a program that sets none lowers
as it did before they existed.

  - a WINDOW (``window=W``): a token at position t sees t - W < s <= t. A
    tile's page walk starts at the first page that holds a visible
    position (it already stopped at the last), the mask gets its lower
    edge, and a tile is ONE block of ceil((W - 1 + bq) / ps) + 1 pages
    rounded up to whole lanes, laid from that first page on (a chunk
    tile's window longer than _WINDOW_BLOCK slots: in blocks of that
    many). The rows'
    page table may then be COMPACT (``page_base`` [R]: the logical page a
    row's entry 0 stands for; the kernel subtracts it), so neither the
    table nor the pool grows with the context (llm/cache.py: the group
    that frees behind the window). The window form lowers under a kernel
    name of its own (``ragged_window_kernel``);
  - a SINK (``sink`` [Hq] float32): one logit a query head that joins the
    running maximum and the denominator and carries no value. The kernel
    folds it in where a tile's accumulation STARTS (m = sink, l = 1, acc =
    0: a first key of score ``sink`` and value zero), which the running
    rescale then treats as any other;
  - K and V rows of different width: the two leaves' own last axes (q is
    as wide as K, the result as wide as V).

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


def kernels_supported(device: Optional[jax.Device] = None) -> bool:
    dev = device if device is not None else jax.devices()[0]
    return dev.platform == "tpu"


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
#
# Static hints (``decode_rows``, ``max_q_len``): the first decode_rows
#   rows hold at most ONE token each, every other row at most max_q_len
#   (default T). They are the tiling, for the kernel and the reference
#   alike: decode rows are one-token tiles, the others ceil(max_q_len /
#   bq) tiles of bq tokens (_ragged_tiling). A hint looser than the
#   batch only costs time (empty tiles); a row LONGER than its hint is
#   the caller's error.


def ragged_paged_attention_reference(q, k_pages, v_pages, page_table,
                                     q_start, q_len, kv_len, *,
                                     k_scale=None, v_scale=None,
                                     sm_scale: Optional[float] = None,
                                     max_q_len: Optional[int] = None,
                                     decode_rows: int = 0,
                                     layer=None,
                                     v_width: Optional[int] = None,
                                     window: Optional[int] = None,
                                     sink=None, page_base=None
                                     ) -> jax.Array:
    """Gather-based ragged paged attention (oracle + CPU fallback).

    q: [T, Hq, D]; k/v_pages: [P, Hkv, ps, D] (int8 when scales given);
    k/v_scale: [P, Hkv, ps] per-(page, head, slot) dequant scales or
    None; page_table: [R, max_pages]; q_start/q_len/kv_len: [R]. With
    ``layer`` the pool and scales are the stacked ones ([L, P, ...]) and
    the rows' pages are gathered from that layer.

    ``decode_rows``/``max_q_len`` are the STATIC tiling hints (see
    "Ragged batch layout"): the first ``decode_rows`` rows must have
    q_len <= 1 and are computed decode-style (one gathered score row
    each); the rest are prefill rows computed on ``max_q_len``-sized
    blocks (default T). Wrong hints that still satisfy the q_len bounds
    only cost time, never accuracy.

    A latent pool: ``v_pages=None`` and ``v_width``; the value is the
    leading ``v_width`` values of the K row and the result [T, Hq,
    v_width].

    ``window``, ``sink`` [Hq], ``page_base`` [R] (module docstring): a
    token sees the last ``window`` positions only; the sink joins every
    row's softmax as one more logit with no value; entry j of row r's page
    table is the row's logical page page_base[r] + j.
    """
    T, Hq, D = q.shape
    R, max_pages = page_table.shape
    Hkv, ps, _ = k_pages.shape[-3:]
    if sm_scale is None:
        sm_scale = D ** -0.5
    max_kv = max_pages * ps
    qpk = Hq // Hkv
    at = page_table if layer is None else (layer, page_table)

    # one page gather per row -> [R, Hkv, max_kv, D] fp32 (dequantized)
    kr = k_pages[at].astype(jnp.float32)         # [R, mp, Hkv, ps, D]
    if v_pages is not None:
        vr = v_pages[at].astype(jnp.float32)
    if k_scale is not None:
        kr = kr * k_scale[at].astype(jnp.float32)[..., None]
        vr = vr * v_scale[at].astype(jnp.float32)[..., None]
    kr = kr.transpose(0, 2, 1, 3, 4).reshape(R, Hkv, max_kv, D)
    if v_pages is not None:
        vr = vr.transpose(0, 2, 1, 3, 4).reshape(
            R, Hkv, max_kv, vr.shape[-1])
    else:
        vr = kr[..., :v_width]
    Dv = vr.shape[-1]                            # the result's width

    out = jnp.zeros((T, Hq, Dv), jnp.float32)
    tkv = jnp.arange(max_kv)
    if sink is not None:
        sink = sink.astype(jnp.float32).reshape(Hkv, qpk, 1)

    def _safe_softmax(s):
        m = jnp.max(s, axis=-1, keepdims=True)
        if sink is not None:
            # one more logit a head: mass in the denominator, no value
            m = jnp.maximum(m, sink)
            p = jnp.where(jnp.isneginf(s), 0.0, jnp.exp(s - m))
            return p / (p.sum(axis=-1, keepdims=True) + jnp.exp(sink - m))
        p = jnp.where(jnp.isneginf(s), 0.0,
                      jnp.exp(s - jnp.where(jnp.isneginf(m), 0.0, m)))
        return p / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)

    def seen(vis, base):
        """[..., max_kv] bool: the slots a token that sees ``vis`` [...]
        positions reads, of a row whose table starts at logical page
        ``base`` (broadcast against vis)."""
        at = tkv if base is None else tkv + base[..., None] * ps
        ok = at < vis[..., None]
        if window is not None:
            ok = ok & (at >= vis[..., None] - window)
        return ok

    Rd = decode_rows
    if Rd:
        idx = jnp.clip(q_start[:Rd], 0, T - 1)
        qd = q[idx].reshape(Rd, Hkv, qpk, D).astype(jnp.float32)
        s = jnp.einsum("rgqd,rgtd->rgqt", qd, kr[:Rd]) * sm_scale
        vis = jnp.where(q_len[:Rd] > 0, kv_len[:Rd], 0)
        if window is None and page_base is None:
            ok = tkv[None, None, None, :] < vis[:, None, None, None]
        else:
            ok = seen(vis, None if page_base is None else page_base[:Rd]
                      )[:, None, None, :]
        s = jnp.where(ok, s, _NEG_INF)
        od = jnp.einsum("rgqt,rgtd->rgqd", _safe_softmax(s), vr[:Rd])
        od = od.reshape(Rd, Hq, Dv)
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
        if window is None and page_base is None:
            ok = tkv[None, None, None, None, :] < vis[:, :, None, None, None]
        else:
            ok = seen(vis, None if page_base is None
                      else page_base[Rd:, None])[:, :, None, None, :]
        s = jnp.where(ok, s, _NEG_INF)
        oc = jnp.einsum("rcgqt,rgtd->rcgqd", _safe_softmax(s), vr[Rd:])
        oc = oc.reshape(-1, C, Hq, Dv)
        oc = jnp.where((cvec[None, :] < q_len[Rd:, None])[:, :, None, None],
                       oc, 0.0)
        dest = starts[:, None] + cvec[None, :]        # [Rp, C] < T + C
        out = out + jnp.zeros((T + C, Hq, Dv),
                              jnp.float32).at[dest].add(oc)[:T]
    return out.astype(q.dtype)


#: score of a kv slot a token may not see; finite, so no inf - inf anywhere
_MASK = -0.7 * float(jnp.finfo(jnp.float32).max)


#: the latent one-token tile: bytes of a KV block, and page copies started
#: (or waited for) a turn of the page walk's loop (PERF.md, PR 34)
_LATENT_BLOCK_BYTES = 2048 * 1280
_LATENT_WALK_UNROLL = 16


#: the chunk tile of a table that reaches far (``max_pages * page_size``
#: slots or more): its KV block doubles until a slot row of the block meets
#: this many values of K and V (a latent tile's 256 slots of 640 + 512), as
#: long as the block's float32 scores fit this much VMEM (PERF.md, PR 48)
_LONG_REACH = 8192
#: slots of a window tile's block at most (the long table's chunk block),
#: and the double-buffered K and V pages up to which a ONE-TOKEN tile still
#: takes its whole window as one block (W = 2048 at 4 heads of 128 + 128
#: values: 8.9 MB). Measured at that shape (PERF.md, PR 49; _ragged_tiling)
_WINDOW_BLOCK = 1024
_WINDOW_ROW_BYTES = 16 << 20
_CHUNK_BLOCK_VALUES = 256 * (640 + 512)
_SCORES_VMEM = 16 << 20


def _ragged_tiling(n_tokens: int, q_per_kv: int, page_size: int,
                   max_pages: int, latent_row_bytes: Optional[int] = None,
                   window: Optional[int] = None, kv_heads: int = 1,
                   kv_width: Optional[int] = None):
    """Static tiling of rows that hold at most ``n_tokens`` query tokens.

    Returns (bq, nq, mrows, bkp): a row is ``nq`` tiles of ``bq`` tokens;
    a tile's queries of one KV head are one ``[mrows, D]`` matmul operand
    (``bq * q_per_kv`` rows, head-major, padded to the bf16 sublane
    packing); a KV block is ``bkp`` pages. Chunk rows take MXU-sized
    tiles of 128 tokens against blocks of 256 kv slots (more under a long
    table, below); one-token rows
    take blocks of 512 and fewer loop turns. Measured on a v5e at
    Mistral-7B head shapes (PERF.md, PR 25): 128- and 512-slot blocks for
    chunks and 256- and 1024-slot blocks for one-token rows were within a
    fifth of these, 256-token tiles a third slower. At those shapes a
    block of 512 slots is 2-4 MB and a turn costs ~1 us beyond its read
    (70-84 % of the read rate; ledger, PR 33).

    ``latent_row_bytes`` (a latent pool: ONE kv head, one leaf, a slot
    that many bytes): 512 slots of 1280 B are 0.66 MB, read in 0.8 us,
    and a one-token turn took 2.0 us: 1.3 us the page walk (32 copies of
    20 KB started and waited for one by one) and then 0.8 us the two
    products and the softmax, one after the other (my chip runs, PR 34:
    the kernel alone at Kanana-2's shape, ~466 turns a call: copies only
    0.61 ms, compute only 0.36, both 0.93, against a read of 0.35). So its one-token tile takes blocks of _LATENT_BLOCK_BYTES
    (2048 slots of 1280 B: the compute's fixed cost a turn falls by a
    third) and _ragged_kernel walks their pages _LATENT_WALK_UNROLL to a
    loop turn: 0.53 ms a call; 1024- and 4096-slot blocks 0.56 and 0.55,
    walks of 8 and 32 pages 0.54 and 0.52. The chunk tile is compute-bound
    and keeps its blocks.

    ``kv_heads``, ``kv_width`` (the values of K and of V a slot and head
    brings to the two products) size the CHUNK tile's block where the table
    reaches _LONG_REACH slots or more. A loop turn's fixed cost there is the
    float32 accumulator's rescale and the two running statistics kept
    across lanes, ``kv_heads * mrows`` rows of each, whatever the block;
    the turn's products grow with ``bk * kv_width``. MiMo-V2-Flash's full
    layers (4 KV heads x 1024 operand rows, 256 + 128 values, prefixes of
    thousands) ran blocks of 256 slots at 29-31 % of the MXU's peak, the
    same time with the page copies taken out (my chip runs, PR 48: the tile
    alone at 2 rows x 512 tokens on prefixes of 2300 + 3400 / 0 + 9000 /
    12000 + 512 slots: 2.75 / 3.99 / 5.30 ms a call). Blocks of 512: 2.12 /
    3.00 / 3.95; **1024: 1.73 / 2.16 / 2.62 (kept: 46-64 % of the peak)**;
    2048 ([4, 1024, 2048] scores, 32 MB): 1.96 / 2.56 / 3.05; the heads
    taken in turn inside a block of 1024: 1.81 / 2.36 / 2.84. So a block
    doubles until a slot row of it holds _CHUNK_BLOCK_VALUES, while its
    scores fit _SCORES_VMEM. The latent tile (one head, 640 + 512 values)
    holds that many at 256 slots and keeps them: 1024 bought its call 5 %
    (2.76 -> 2.63 ms). A short table keeps 256 whatever its rows: a tile
    multiplies whole blocks, and the contexts of a few hundred slots that
    PR 25 measured fill one or two. The ONE-TOKEN tile of that shape keeps
    512 slots walked a page a turn: 1.554 ms a call for 325 k cached slots
    of 3072 B (79 % of the read rate; the copies alone 1.518, the products
    alone 0.739); blocks of 1024 / 2048 / 4096: 1.584 / 1.588 / 1.635; the
    walk 4 / 8 / 16 pages a turn at 512 / 1024 / 2048 slots: 1.544 / 1.577
    / 1.571: the page copies of 128 + 64 KB are bound by HBM, not by the
    scalar work that starts them, and the products hide under them.

    ``window``: a tile's tokens see W - 1 + bq slots between them, so a
    tile is ONE block, laid from the tile's first visible page on:
    ceil((W - 1 + bq) / ps) + 1 pages (the span may start anywhere in its
    first page), rounded up to whole 128-lane score columns (W = 128, ps =
    16: 16 pages, 256 columns, for a one-token tile and for a chunk tile,
    which holds 64 tokens). A window whose one block would be longer than
    _WINDOW_BLOCK slots (W = 2048 at pages of 64: 34 pages, 2176 columns:
    17.8 MB of float32 scores a chunk tile of 64 tokens x 8 heads on 4
    key/value heads, three times over in VMEM) is walked in blocks of that
    many, laid from the same first page: the kernel's loop, mask and copies
    are the full form's with both edges. Measured at that shape, the
    kernel alone, one layer's call (my chip run, PR 49: 128 one-token rows
    of which an eighth lie inside the window, reading 505 MB; and 2 chunk
    rows of 1024 tokens after prefixes of 8192 and 0), blocks of 256 / 512
    / 1024 / ONE of 2176 slots: the one-token rows 0.950 / 0.837 / 0.855 /
    **0.742** ms (65 / 74 / 72 / 83 % of the HBM peak: a turn's fixed cost
    is paid once a row), the chunk rows 1.049 / 0.930 / **0.774** / 0.764
    (a last block partly masked costs less than the turns it saves; ONE
    block would be 53 MB of scores for 1 % more). So a chunk tile takes
    blocks of _WINDOW_BLOCK = 1024, and a one-token tile ONE block while
    its double-buffered pages stay under _WINDOW_ROW_BYTES (8.9 MB here).
    """
    bq = min(128, pl.cdiv(n_tokens, 8) * 8) if n_tokens > 1 else 1
    if bq * q_per_kv > 1024:
        # many query heads on one KV head (the latent form: 32 on 1): a
        # tile of 128 tokens would be a 4096-row operand and ~50 MB of
        # VMEM; 1024 rows keep it where 8 heads x 128 tokens are
        bq = max(8, 1024 // q_per_kv // 8 * 8)
    if window is not None and bq > 64:
        # the block is the window plus the tile: tiles of 64 tokens score
        # 256 columns a token where tiles of 128 would score 384, in a
        # third of the VMEM
        bq = 64
    nq = pl.cdiv(n_tokens, bq)
    mrows = pl.cdiv(bq * q_per_kv, 16) * 16
    bk = 256 if bq > 1 else 512
    if bq == 1 and latent_row_bytes:
        bk = max(bk, _LATENT_BLOCK_BYTES // latent_row_bytes // bk * bk)
    if bq > 1 and kv_width and max_pages * page_size >= _LONG_REACH:
        # [kv_heads, mrows, bk] float32 scores of the block twice as long
        while bk * kv_width < _CHUNK_BLOCK_VALUES \
                and kv_heads * mrows * (2 * bk) * 4 <= _SCORES_VMEM:
            bk *= 2
    if window is not None:
        lanes = pl.cdiv((pl.cdiv(window - 1 + bq, page_size) + 1)
                        * page_size, 128) * 128
        # a long window is walked in blocks, as a full table is; a
        # one-token tile's scores are nothing, so it keeps ONE block while
        # its pages (bf16, twice over) fit _WINDOW_ROW_BYTES
        if bq > 1 or 4 * kv_heads * lanes * (kv_width or 0) \
                > _WINDOW_ROW_BYTES:
            lanes = min(lanes, max(_WINDOW_BLOCK, page_size))
        return bq, nq, mrows, pl.cdiv(lanes, page_size)
    bkp = max(1, min(max_pages, bk // page_size))
    return bq, nq, mrows, bkp


def _ragged_kernel(layer_ref, q_len_ref, kv_len_ref, pt_ref,   # prefetch
                   *rest, sm_scale, row0, bq, nq, has_scales, v_width=None,
                   window=None, has_sink=False):
    """One grid step = one tile: ``bq`` query tokens of ONE row against
    that row's pages, a block of ``bkp`` pages a loop turn.

    q_ref/o_ref: [1, Hkv, mrows, D] the tile in head-major order (matmul
    row = q_head_in_group * bq + token); k_hbm/v_hbm: the whole STACKED
    pool [L, P, Hkv, ps, D], left in HBM and read at ``[layer, page]``
    (``layer_ref``: a layer sliced out for a custom call would be a copy
    of it); kbuf/vbuf: [2, Hkv, bkp, ps, D] double-buffered blocks,
    one async copy a page, started a block ahead; a tile's last block
    starts the NEXT tile's first (``ahead_ref`` carries "started" and
    the buffer it went to across grid steps), so one-token tiles, which
    walk one or two blocks, do not wait out each first copy. The loop
    runs ceil(visible / bk) turns, so pages past the tile's last visible
    position are neither copied nor multiplied, and only the blocks that
    straddle the tile's own positions pay for a causal mask. Every
    vector op is batched over the KV heads: one traced op, unrolled by
    the compiler, which keeps all heads' matmuls and softmaxes in flight
    and the program small to trace and lower at start-up.

    A latent pool (``v_width``): no v_hbm and no vbuf; the value is the
    leading ``v_width`` lanes of the K block, read from the same buffer,
    and o_ref / acc_ref are that wide. Its one-token tile is bound by
    neither the copies nor the MXU but by their sum: a turn's scalar work
    (start the next block's page copies, wait for this block's) and its
    vector work run one after the other, so that tile walks its pages
    ``walk`` to a loop turn over the larger blocks _ragged_tiling gives
    it (the numbers are in that docstring). A per-head pool's one-token
    tile is bound by the copies themselves where a page is long (MiMo's
    full layers: 128 KB of K and 64 KB of V a page, 80 % of the HBM rate
    with no compute at all, 79 % with it) and keeps a page a turn: walks
    of 4 to 32 pages over blocks of 512 to 4096 slots came within 2 % of
    it either way (PERF.md, PR 48).

    ``window`` (static): one more scalar-prefetch operand, base_ref [R], the
    logical page a row's table starts at. A tile's walk and its blocks are
    laid from ``first``, the page that holds the first position its first
    token sees; the mask has both edges in every block. ``has_sink``: one
    more operand after q, sink_ref [Hkv, mrows, 128] float32 (each matmul
    row's own head's logit over the lanes), which the running maximum and
    the denominator START from. K and V blocks are as wide as their own
    leaves.
    """
    if window is not None:
        base_ref, *rest = rest
    q_ref, *rest = rest
    if has_sink:
        sink_ref, *rest = rest
    k_hbm, *rest = rest
    latent = v_width is not None
    if not latent:
        v_hbm, *rest = rest
    if has_scales:
        ks_ref, vs_ref, *rest = rest
    if latent:
        o_ref, kbuf, sem, ahead_ref, acc_ref, m_ref, l_ref = rest
        pools = ((k_hbm, kbuf, 0),)
    else:
        o_ref, kbuf, vbuf, sem, ahead_ref, acc_ref, m_ref, l_ref = rest
        pools = ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1))
    Hkv, mrows, Dv = acc_ref.shape
    _, _, bkp, ps, D = kbuf.shape
    bk = bkp * ps
    max_pages = pt_ref.shape[1]
    cdt = q_ref.dtype                       # MXU operand dtype
    layer = layer_ref[0]

    def tile(t):
        """(row, position of the tile's first token, pages and blocks it
        walks, how many of those blocks every token sees whole)"""
        row = row0 + t // nq
        off = (t % nq) * bq                 # tile's first token in its row
        q_len, kv_len = q_len_ref[row], kv_len_ref[row]
        n_valid = jnp.clip(q_len - off, 0, bq)
        pos0 = kv_len - q_len + off
        vis = jnp.where(n_valid > 0, pos0 + n_valid, 0)   # slots the last sees
        if window is not None:
            # the walk starts at the page of the first slot the FIRST
            # token sees, and the blocks are laid from there
            first = jnp.maximum(pos0 - (window - 1), 0) // ps
            n_blocks = pl.cdiv(jnp.maximum(vis - first * ps, 0), bk)
            return (row, pos0, (first, pl.cdiv(vis, ps), base_ref[row]),
                    n_blocks, 0)
        n_blocks = pl.cdiv(vis, bk)
        return (row, pos0, jnp.minimum(pl.cdiv(vis, ps), max_pages), n_blocks,
                jnp.clip((pos0 + 1) // bk, 0, n_blocks))

    t, n_tiles = pl.program_id(0), pl.num_programs(0)
    row, pos0, n_pages, n_blocks, n_full = tile(t)
    nxt = jnp.minimum(t + 1, n_tiles - 1)
    nxt_row, _, nxt_pages, nxt_blocks, _ = tile(nxt)
    nxt_live = jnp.logical_and(t + 1 < n_tiles, nxt_blocks > 0)
    # the first slot of block 0 (a window's blocks start at its first page)
    org = n_pages[0] * ps if window is not None else 0

    # the latent one-token tile: a page is one head's 16 rows, its copy
    # as short as the scalar work that starts it, so the walk is unrolled
    walk = min(_LATENT_WALK_UNROLL, bkp) if latent and bq == 1 else 1

    def copy_pages(row, n_pages, b, slot, wait=False):
        """Start (or wait for) the copies of block b's live pages."""
        if window is not None:
            # logical pages first .. end - 1, found in the row's compact
            # table at their distance from its base
            first, end, base = n_pages
            n_pages, at = end - first, first - base

        def entry(i):
            return b * bkp + i if window is None else at + b * bkp + i

        def page(i, _):
            src = 0 if wait else pt_ref[row, entry(i)]
            for hbm, buf, s in pools:
                cp = pltpu.make_async_copy(
                    hbm.at[layer, src], buf.at[slot, :, i], sem.at[s, slot])
                cp.wait() if wait else cp.start()
            return 0
        n = jnp.clip(n_pages - b * bkp, 0, bkp)
        if walk == 1:
            lax.fori_loop(0, n, page, 0)
            return

        def pages(g, _):
            for j in range(walk):
                page(g * walk + j, 0)
            return 0
        lax.fori_loop(0, n // walk, pages, 0)
        lax.fori_loop(n // walk * walk, n, page, 0)

    @pl.when(t == 0)
    def _first():
        # a masked slot's p is 0, and 0 * (VMEM nobody wrote) may be NaN
        for _, buf, _ in pools:
            buf[...] = jnp.zeros_like(buf)
        ahead_ref[0] = 0                    # nobody started my first block
        ahead_ref[1] = 0                    # ... which goes to buffer 0

    @pl.when(n_blocks == 0)
    def _dead():                            # empty row, or a tile past q_len
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n_blocks > 0)
    def _live():
        slot0 = ahead_ref[1]

        @pl.when(ahead_ref[0] == 0)
        def _():
            copy_pages(row, n_pages, 0, slot0)

        acc_ref[...] = jnp.zeros_like(acc_ref)
        if has_sink:
            # a first key of score sink and value zero
            m_ref[...] = sink_ref[...]
            l_ref[...] = jnp.ones_like(l_ref)
        else:
            m_ref[...] = jnp.full_like(m_ref, _MASK)
            l_ref[...] = jnp.zeros_like(l_ref)
        # kv slot - token, relative to the block's first slot and pos0
        rel = lax.broadcasted_iota(jnp.int32, (mrows, bk), 1) \
            - lax.broadcasted_iota(jnp.int32, (mrows, bk), 0) % bq

        def block(b, masked):
            slot = (slot0 + b) % 2

            @pl.when(b + 1 < n_blocks)
            def _():
                copy_pages(row, n_pages, b + 1, 1 - slot)

            @pl.when(jnp.logical_and(b + 1 == n_blocks, nxt_live))
            def _():                        # the next tile's first block
                copy_pages(nxt_row, nxt_pages, 0, 1 - slot)

            copy_pages(row, n_pages, b, slot, wait=True)
            # every op below is batched over the KV heads
            if latent:
                k = kbuf[slot].reshape(Hkv, bk, D).astype(cdt)
                v = kbuf[slot, :, :, :, :v_width].reshape(
                    Hkv, bk, v_width).astype(cdt)
            else:
                k, v = kbuf[slot], vbuf[slot]       # [Hkv, bkp, ps, D]
                if has_scales:          # int8 values are exact in bf16
                    k, v = k.astype(jnp.float32), v.astype(jnp.float32)
                k = k.reshape(Hkv, bk, D).astype(cdt)
                v = v.reshape(Hkv, bk, Dv).astype(cdt)
            s = lax.dot_general(
                q_ref[0], k, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32) * sm_scale
            if has_scales:              # dequantize the score columns
                s = s * ks_ref[0, b]
            if window is not None:
                edge = pos0 - org - b * bk
                s = jnp.where((rel <= edge) & (rel > edge - window), s, _MASK)
            elif masked:
                s = jnp.where(rel <= pos0 - b * bk, s, _MASK)
            m_prev, l_prev = m_ref[:, :, :1], l_ref[:, :, :1]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
            if has_scales:              # ... and the probabilities
                p = p * vs_ref[0, b]
            pv = lax.dot_general(
                p.astype(cdt), v, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            acc_ref[...] = acc_ref[...] * corr + pv
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
            l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
            return b + 1

        b = lax.fori_loop(0, n_full, lambda _, b: block(b, False), 0)
        lax.fori_loop(n_full, n_blocks, lambda _, b: block(b, True), b)
        ahead_ref[0] = nxt_live.astype(jnp.int32)
        ahead_ref[1] = (slot0 + n_blocks) % 2
        # slot 0 (a window: the token itself) is visible to every token of
        # a live tile: l >= 1
        o_ref[0] = (acc_ref[...] / l_ref[:, :, :1]).astype(o_ref.dtype)


def _scale_blocks(scale, layer, page_table, bk: int):
    """Per-row dequant scales in kv order, cut into the kernel's blocks:
    [L, P, Hkv, ps] at ``layer`` -> [R, n_blocks, Hkv, 1, bk] fp32 (a
    lane-dense row a block and head; 1/D of the K/V bytes, gathered by
    XLA)."""
    R, max_pages = page_table.shape
    _, _, Hkv, ps = scale.shape
    s = scale[layer, page_table].astype(jnp.float32)  # [R, max_pages, Hkv, ps]
    s = s.transpose(0, 2, 1, 3).reshape(R, Hkv, max_pages * ps)
    nb = pl.cdiv(max_pages * ps, bk)
    s = jnp.pad(s, ((0, 0), (0, 0), (0, nb * bk - max_pages * ps)))
    return s.reshape(R, Hkv, nb, 1, bk).transpose(0, 2, 1, 3, 4)


def _ragged_rows_pallas(q, k_pages, v_pages, layer, page_table, q_start,
                        q_len, kv_len, k_scale, v_scale, *, row0: int,
                        n_rows: int, n_tokens: int, sm_scale: float,
                        interpret: bool, v_width: Optional[int] = None,
                        window: Optional[int] = None, sink=None,
                        page_base=None):
    """Attention of rows ``row0 : row0 + n_rows`` (each at most
    ``n_tokens`` query tokens) over layer ``layer`` ([1] int32) of the
    stacked pool -> [n_rows * nq * bq, Hq, Dv], row-major by (row, token);
    slots past a row's q_len hold garbage or zeros. A latent pool
    (``v_pages`` None): the result is [..., v_width]. ``window``, ``sink``
    [Hq] float32 and ``page_base`` [R] int32 (with a window, always): the
    module docstring's."""
    T, Hq, D = q.shape
    latent = v_pages is None
    Dv = v_width if latent else v_pages.shape[-1]
    _, _, Hkv, ps, _ = k_pages.shape
    max_pages = page_table.shape[1]
    qpk = Hq // Hkv
    bq, nq, mrows, bkp = _ragged_tiling(
        n_tokens, qpk, ps, max_pages,
        D * k_pages.dtype.itemsize if latent else None, window, Hkv, D + Dv)
    n_tiles, bk = n_rows * nq, bkp * ps

    # tile order: [tile, kv head, q head in group * bq + token, D]
    tok = q_start[row0:row0 + n_rows, None] \
        + jnp.arange(nq * bq, dtype=jnp.int32)
    qt = q[jnp.clip(tok, 0, T - 1)].reshape(n_tiles, bq, Hkv, qpk, D)
    qt = qt.transpose(0, 2, 3, 1, 4).reshape(n_tiles, Hkv, qpk * bq, D)
    qt = jnp.pad(qt, ((0, 0), (0, 0), (0, mrows - qpk * bq), (0, 0)))

    def tile_map(t, *_):
        return (t, 0, 0, 0)

    def row_map(t, *_):
        return (row0 + t // nq, 0, 0, 0, 0)

    tile_spec = pl.BlockSpec((1, Hkv, mrows, D), tile_map)
    pools = [k_pages] if latent else [k_pages, v_pages]
    in_specs, operands = [tile_spec], [qt]
    if sink is not None:
        # each matmul row's own head's logit, over the lanes of the
        # running maximum it starts: [Hkv, mrows, 128], one block for all
        # tiles (fetched once)
        rows = jnp.repeat(sink.astype(jnp.float32).reshape(Hkv, qpk), bq,
                          axis=1)
        rows = jnp.pad(rows, ((0, 0), (0, mrows - qpk * bq)))
        in_specs.append(pl.BlockSpec((Hkv, mrows, 128),
                                     lambda t, *_: (0, 0, 0)))
        operands.append(jnp.broadcast_to(rows[..., None], (Hkv, mrows, 128)))
    in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * len(pools)
    operands += pools
    has_scales = k_scale is not None
    if has_scales:
        ks = _scale_blocks(k_scale, layer[0], page_table, bk)
        vs = _scale_blocks(v_scale, layer[0], page_table, bk)
        in_specs += [pl.BlockSpec((1,) + ks.shape[1:], row_map)] * 2
        operands += [ks, vs]

    kv_bufs = [pltpu.VMEM((2, Hkv, bkp, ps, a.shape[-1]), a.dtype)
               for a in pools]
    stat = pltpu.VMEM((Hkv, mrows, 128), jnp.float32)
    # q and o tiles twice (pipelined), the page buffers, fp32 statistics,
    # and a block's scores and probabilities for all heads. A quarter of
    # headroom and no more: what the kernel is granted XLA takes from
    # its own use of VMEM around it (the next layer's weight prefetch)
    need = 4 * Hkv * mrows * D * q.dtype.itemsize \
        + 2 * len(pools) * Hkv * bk * D * k_pages.dtype.itemsize \
        + Hkv * mrows * (2 * 128 + Dv) * 4 \
        + 3 * Hkv * mrows * max(bk, 128) * 4
    options, prefetch = {}, [layer, q_len, kv_len, page_table]
    if window is not None:
        # the window form lowers under a name of its own: the readers find
        # a kernel in the trace by it (and the other form's must not move)
        options.update(window=window, has_sink=sink is not None)
        prefetch.append(page_base.astype(jnp.int32))
        if sink is not None:
            need += 2 * Hkv * mrows * 128 * 4
    elif sink is not None:
        raise ValueError("a sink is built for the window form only")
    kernel = functools.partial(_ragged_kernel, sm_scale=sm_scale, row0=row0,
                               bq=bq, nq=nq, has_scales=has_scales,
                               v_width=v_width, **options)
    out = pl.pallas_call(
        kernel,
        **(dict(name="ragged_window_kernel") if window is not None else {}),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(n_tiles,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, Hkv, mrows, Dv), tile_map),
            scratch_shapes=kv_bufs + [
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((2,), jnp.int32),
                pltpu.VMEM((Hkv, mrows, Dv), jnp.float32), stat, stat],
        ),
        out_shape=jax.ShapeDtypeStruct(qt.shape[:-1] + (Dv,), q.dtype),
        # half of every tile against a full page table: XLA's scheduler
        # places the next layer's weight prefetches by this and by the
        # VMEM limit (with no estimate, or 48 MB of VMEM, the mixed step
        # compiled to 59 MB more temporaries: PERF.md, PR 25)
        cost_estimate=pl.CostEstimate(
            flops=2 * n_tiles * Hkv * mrows * D * max_pages * ps,
            transcendentals=n_tiles * Hkv * mrows * max_pages * ps // 2,
            bytes_accessed=2 * qt.size * q.dtype.itemsize
            + n_tiles * Hkv * max_pages * ps * D * k_pages.dtype.itemsize),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(need * 5 // 4, 16 << 20)),
        interpret=interpret,
    )(*prefetch, *operands)
    out = out[:, :, :qpk * bq].reshape(n_tiles, Hkv, qpk, bq, Dv)
    return out.transpose(0, 3, 1, 2, 4).reshape(n_tiles * bq, Hq, Dv)


def _check_layer(k_pages, layer) -> None:
    if (layer is None) != (k_pages.ndim == 4):
        raise ValueError("a layer index goes with the stacked pool "
                         "[L, P, Hkv, ps, D], and only with it")


def _stacked(k_pages, v_pages, k_scale, v_scale, layer):
    """The pool as the kernels take it: stacked [L, P, Hkv, ps, D] with
    ``layer`` as a [1] int32 array. One layer's arrays (rank 4) become a
    stack of one (adding the axis moves nothing)."""
    _check_layer(k_pages, layer)
    if layer is None:
        k_pages = k_pages[None]
        v_pages = None if v_pages is None else v_pages[None]
        if k_scale is not None:
            k_scale, v_scale = k_scale[None], v_scale[None]
        layer = 0
    return (k_pages, v_pages, k_scale, v_scale,
            jnp.asarray(layer, jnp.int32).reshape(1))


def _token_rows(q_start, q_len, T: int):
    """Per token: (does a row own it, that row, its index in the row's
    span). O(R*T) int compare, inside the jitted wrapper, so the host
    never builds per-token data."""
    tvec = jnp.arange(T, dtype=jnp.int32)
    in_row = (tvec[None, :] >= q_start[:, None]) & \
             (tvec[None, :] < (q_start + q_len)[:, None])       # [R, T]
    row = jnp.argmax(in_row, axis=0).astype(jnp.int32)
    return jnp.any(in_row, axis=0), row, tvec - q_start[row]


@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "max_q_len", "decode_rows", "interpret", "v_width",
    "window"))
def _ragged_attention_pallas(q, k_pages, v_pages, page_table,
                             q_start, q_len, kv_len, k_scale, v_scale,
                             sm_scale: float,
                             max_q_len: Optional[int] = None,
                             decode_rows: int = 0,
                             interpret: bool = False, layer=None,
                             v_width: Optional[int] = None,
                             window: Optional[int] = None, sink=None,
                             page_base=None):
    """The blocked kernel over the static tiling the hints give: the
    first ``decode_rows`` rows as one-token tiles, the others as
    ceil(max_q_len / bq) tiles of bq tokens. XLA gathers q into tile
    order and the outputs back into token order (rows may start anywhere
    in q); tokens no row owns come back zero. The pool (and its scales)
    is the stacked one, read at ``layer``; one layer's arrays
    [P, Hkv, ps, D] are taken as a stack of one."""
    k_pages, v_pages, k_scale, v_scale, layer = _stacked(
        k_pages, v_pages, k_scale, v_scale, layer)
    T = q.shape[0]
    R = page_table.shape[0]
    q_start, q_len, kv_len = (a.astype(jnp.int32)
                              for a in (q_start, q_len, kv_len))
    Rd = min(decode_rows, R)
    C = min(max_q_len if max_q_len is not None else T, T)
    call = functools.partial(
        _ragged_rows_pallas, q, k_pages, v_pages, layer, page_table,
        q_start, q_len, kv_len, k_scale, v_scale, sm_scale=sm_scale,
        interpret=interpret, v_width=v_width)
    if window is not None:
        if page_base is None:       # a table that starts at page 0
            page_base = jnp.zeros(R, jnp.int32)
        call = functools.partial(call, window=window, sink=sink,
                                 page_base=page_base)
    owned, row, j = _token_rows(q_start, q_len, T)
    # a latent pool's result is [T, Hq, v_width]; with V rows narrower
    # than K's, [T, Hq, the V leaf's width]
    out = jnp.zeros(q.shape[:-1] + (v_width or v_pages.shape[-1],), q.dtype)
    if R - Rd:
        o = call(row0=Rd, n_rows=R - Rd, n_tokens=C)
        slots = o.shape[0] // (R - Rd)             # a row's tiles, in tokens
        out = o[jnp.maximum(row - Rd, 0) * slots + jnp.clip(j, 0, slots - 1)]
    if Rd:
        o = call(row0=0, n_rows=Rd, n_tokens=1)
        out = jnp.where((row < Rd)[:, None, None],
                        o[jnp.minimum(row, Rd - 1)], out)
    return jnp.where(owned[:, None, None], out, 0)


def _use_reference(impl: Optional[str], interpret: Optional[bool]) -> bool:
    """The ragged paths' dispatch rule: ``impl`` pins the choice, an
    explicit ``interpret`` means the kernel, and otherwise the kernel
    runs where the default backend is a TPU."""
    if impl not in (None, "kernel", "reference"):
        raise ValueError(f"impl must be 'kernel' or 'reference', "
                         f"got {impl!r}")
    if impl is not None:
        return impl == "reference"
    return interpret is None and not kernels_supported()


def ragged_paged_attention(q, k_pages, v_pages, page_table, q_start,
                           q_len, kv_len, *, k_scale=None, v_scale=None,
                           sm_scale: Optional[float] = None,
                           max_q_len: Optional[int] = None,
                           decode_rows: int = 0,
                           interpret: Optional[bool] = None,
                           impl: Optional[str] = None,
                           layer=None,
                           v_width: Optional[int] = None,
                           window: Optional[int] = None, sink=None,
                           page_base=None) -> jax.Array:
    """Mixed prefill+decode attention over a ragged token batch in ONE
    dispatch. Dispatch rule (``_use_reference``): Pallas kernel on
    TPU, gather reference elsewhere; ``impl`` pins the choice
    for mesh-specific programs, ``interpret=True`` runs the kernel
    through the Pallas interpreter on CPU (the tier-1 kernel tests).

    k/v_pages (and the scales) are one layer's [P, Hkv, ps, D], or the
    whole stacked pool [L, P, Hkv, ps, D] with ``layer`` the index to
    read: the step programs pass the pool whole, because a layer sliced
    out for a custom call is a copy of it.

    A LATENT pool (the absorbed form of latent attention): ``v_pages``
    None and ``v_width`` the value's width. k_pages has ONE kv head, a
    token's row is scored whole against q [T, Hq, D] and its leading
    ``v_width`` values are the value: the result is [T, Hq, v_width].

    ``window`` (static), ``sink`` [Hq] float32, ``page_base`` [R] int32:
    the module docstring's three options; a sink and a page base come with
    a window. q is as wide as the K leaf's rows and the result as wide as
    the V leaf's.
    """
    if window is None and (sink is not None or page_base is not None):
        raise ValueError("a sink and a compact page table (page_base) are "
                         "built for window attention: they need a window")
    if window is not None and (k_scale is not None or v_pages is None):
        raise ValueError("window attention is built over fp K and V pools: "
                         "no int8 scales, no latent pool")
    if (v_pages is None) != (v_width is not None):
        raise ValueError("a pool with no v leaf goes with a v_width (the "
                         "value is that many leading values of the K "
                         "row), and only with it")
    if v_pages is None and k_scale is not None:
        raise ValueError("a latent pool has no int8 form")
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if q.shape[1] % k_pages.shape[-3]:
        raise ValueError(
            f"q heads {q.shape[1]} not a multiple of kv heads "
            f"{k_pages.shape[-3]}")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be given together")
    _check_layer(k_pages, layer)
    if _use_reference(impl, interpret):
        return ragged_paged_attention_reference(
            q, k_pages, v_pages, page_table, q_start, q_len, kv_len,
            k_scale=k_scale, v_scale=v_scale, sm_scale=sm_scale,
            max_q_len=max_q_len, decode_rows=decode_rows, layer=layer,
            v_width=v_width, **({} if window is None else dict(
                window=window, sink=sink, page_base=page_base)))
    return _ragged_attention_pallas(
        q, k_pages, v_pages, page_table, q_start, q_len, kv_len,
        k_scale, v_scale, sm_scale, max_q_len, decode_rows,
        bool(interpret), layer, v_width, **({} if window is None else dict(
            window=window, sink=sink, page_base=page_base)))


# --------------------------------------------------------------------------
# Page-cache update: the ragged step's one in-place write per layer
# --------------------------------------------------------------------------
#
# The pool is ONE buffer in ONE layout, updated in place: inside a step
# program every toucher of it is a custom call on the row-major stacked
# array, so XLA has no layout to choose and nothing to copy. A token's
# K/V [Hkv, D] is one ROW of each head's [ps, D] tile of its page, and in
# a 16- or 8-bit pool rows share 32-bit words, so no DMA can place a
# single token. The write therefore works on whole pages ("units": one
# page touched by one row): XLA lays the new tokens out as page images
# [U, Hkv, ps, D] (a gather of the step's K/V, a few MB at most), and the
# kernel reads a unit's page, replaces the slots lo..hi-1 with the
# image's and writes the page back, by page-sized DMAs. Units are static
# in number: one for each of the first ``decode_rows`` rows, and
# ceil((max_q_len + ps - 1) / ps) for each other row (a chunk may start
# mid-page). A row's units touch distinct pages and live rows own
# distinct pages; only the scratch page is written by several (padding,
# garbage by contract).

_WRITE_GROUP = 16        # units a grid step reads, merges and writes back


def _kv_write_kernel(layer_ref, page_ref, lo_ref, hi_ref,   # scalar prefetch
                     *refs):
    """One grid step = ``G`` units: read their pages from layer
    ``layer_ref[0]`` of the pool, take slots lo..hi-1 from the images,
    write the pages back. ``refs``: for each leaf of the pool (K and V,
    or the one leaf of a latent pool) its images, then the leaves in,
    the leaves out and a page buffer each, then the semaphores. The
    leaves in are the leaves out (aliased): the pool is read and written
    through the output refs. Units with hi <= lo are dead and move
    nothing."""
    n = (len(refs) - 1) // 4
    imgs, outs, bufs, sem = refs[:n], refs[2 * n:3 * n], refs[3 * n:-1], \
        refs[-1]
    G = bufs[0].shape[0]
    layer, u0 = layer_ref[0], pl.program_id(0) * G
    pairs = tuple((outs[i], bufs[i], imgs[i], i) for i in range(n))

    def each_live(fn):
        def unit(g, _):
            @pl.when(hi_ref[u0 + g] > lo_ref[u0 + g])
            def _():
                fn(g, u0 + g)
            return 0
        lax.fori_loop(0, G, unit, 0)

    def copy(g, u, to_pool: bool, wait: bool):
        for hbm, buf, _, s in pairs:
            page = hbm.at[layer, 0 if wait else page_ref[u]]
            src, dst = (buf.at[g], page) if to_pool else (page, buf.at[g])
            cp = pltpu.make_async_copy(src, dst, sem.at[s])
            cp.wait() if wait else cp.start()

    each_live(functools.partial(copy, to_pool=False, wait=False))
    each_live(functools.partial(copy, to_pool=False, wait=True))

    def merge(g, u):
        news = {}                   # one mask a page shape (K's and V's)
        for _, buf, img, _ in pairs:
            shape = buf.shape[1:]                       # (Hkv, ps, D)
            if shape not in news:
                slot = lax.broadcasted_iota(jnp.int32, shape, 1)
                news[shape] = jnp.logical_and(slot >= lo_ref[u],
                                              slot < hi_ref[u])
            buf[g] = jnp.where(news[shape], img[g], buf[g])

    each_live(merge)
    each_live(functools.partial(copy, to_pool=True, wait=False))
    each_live(functools.partial(copy, to_pool=True, wait=True))


def _write_units(token_page, token_slot, q_start, q_len, *, T: int, ps: int,
                 decode_rows: int, max_q_len: int):
    """The write's units from the ragged descriptors, padded to whole
    groups: (page [U], lo [U], hi [U], tok [U, ps]) where ``tok[u, s]``
    is the token whose K/V slot ``s`` of unit ``u`` takes if lo <= s <
    hi. Row r's tokens q_start[r] + j go to consecutive slots from
    token_slot[q_start[r]] on, page after page (token_page of the first
    token in each)."""
    R = q_start.shape[0]
    Rd = min(decode_rows, R)
    first = jnp.clip(q_start, 0, T - 1)
    s0 = token_slot[first]                               # [R]

    def units(rows, n_pages):
        k = jnp.arange(n_pages, dtype=jnp.int32)[None, :]
        start, s, n = (a[rows, None] for a in (first, s0, q_len))
        j_lo = jnp.maximum(k * ps - s, 0)                # first token, in row
        j_hi = jnp.minimum((k + 1) * ps - s, n)
        lo = s + j_lo - k * ps
        hi = lo + jnp.maximum(j_hi - j_lo, 0)
        t0 = jnp.clip(start + j_lo, 0, T - 1)
        return [a.reshape(-1) for a in (token_page[t0], lo, hi, t0 - lo)]

    parts = []
    if Rd:
        parts.append(units(slice(0, Rd), 1))
    if R - Rd:
        parts.append(units(slice(Rd, R), (max_q_len + 2 * ps - 2) // ps))
    page, lo, hi, base = (jnp.concatenate(a) for a in zip(*parts))
    pad = -page.shape[0] % min(_WRITE_GROUP, page.shape[0])
    page, lo, hi, base = (jnp.pad(a, (0, pad)) for a in (page, lo, hi, base))
    tok = jnp.clip(base[:, None] + jnp.arange(ps, dtype=jnp.int32), 0, T - 1)
    return page, lo, hi, tok


@functools.partial(jax.jit, static_argnames=(
    "max_q_len", "decode_rows", "interpret"))
def _kv_write_pallas(k_pages, v_pages, k_t, v_t, layer, token_page,
                     token_slot, q_start, q_len,
                     max_q_len: Optional[int] = None, decode_rows: int = 0,
                     interpret: bool = False):
    """``k_t``/``v_t`` [T, Hkv, D] (already in the pool's dtype; each as
    wide as its own leaf's rows) into layer ``layer`` ([1] int32) of the
    stacked pool, in place; a tuple of the leaves written. A latent pool:
    ``v_pages`` and ``v_t`` None."""
    T, Hkv, D = k_t.shape
    pools = [k_pages] if v_pages is None else [k_pages, v_pages]
    n = len(pools)
    ps = k_pages.shape[3]
    C = min(max_q_len if max_q_len is not None else T, T)
    page, lo, hi, tok = _write_units(
        token_page.astype(jnp.int32), token_slot.astype(jnp.int32),
        q_start.astype(jnp.int32), q_len.astype(jnp.int32), T=T, ps=ps,
        decode_rows=decode_rows, max_q_len=C)
    U = page.shape[0]
    G = min(_WRITE_GROUP, U)
    # page images, head-major like the pool: [U, Hkv, ps, D]
    imgs = [a[tok].transpose(0, 2, 1, 3) for a in (k_t, v_t)[:n]]

    widths = [a.shape[-1] for a in pools]
    img_specs = [pl.BlockSpec((G, Hkv, ps, w), lambda i, *_: (i, 0, 0, 0))
                 for w in widths]
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    bufs = [pltpu.VMEM((G, Hkv, ps, w), k_pages.dtype) for w in widths]
    group_bytes = G * Hkv * ps * D * k_pages.dtype.itemsize
    return pl.pallas_call(
        _kv_write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(U // G,),
            in_specs=img_specs + [pool_spec] * n,
            out_specs=[pool_spec] * n,
            scratch_shapes=bufs + [pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in pools],
        # operands count the scalar-prefetch arrays: with K and V the
        # pools are 6 and 7
        input_output_aliases={4 + n + i: i for i in range(n)},
        cost_estimate=pl.CostEstimate(
            flops=0, transcendentals=0,
            bytes_accessed=3 * n * U * Hkv * ps * D
            * k_pages.dtype.itemsize),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # both images twice (pipelined), both page buffers, and the
            # merge's temporaries
            vmem_limit_bytes=max(6 * n * group_bytes, 16 << 20)),
        interpret=interpret,
    )(layer, page, lo, hi, *imgs, *pools)


def write_ragged_kv(k_pages, v_pages, k_t, v_t, token_page, token_slot,
                    k_scale=None, v_scale=None, *, layer=None,
                    q_start=None, q_len=None,
                    max_q_len: Optional[int] = None, decode_rows: int = 0,
                    interpret: Optional[bool] = None,
                    impl: Optional[str] = None):
    """Write a ragged batch's per-token K/V into the page pool.

    k_t/v_t: [T, Hkv, D] this layer's roped K/V for every ragged token
    (decode rows and prefill chunks alike); token_page/token_slot: [T]
    destination page id and in-page slot — padding tokens point at page
    0 (the scratch page, garbage by contract). The pool is one layer's
    [P, Hkv, ps, D], or the whole stacked [L, P, Hkv, ps, D] with
    ``layer`` the index to write (the step programs: the pool is their
    scan carry, updated in place). When the pool is int8
    (``k_scale``/``v_scale`` given, shaped like the pool less D), rows
    quantize with per-token/per-head scales (ops.int8.quantize_kv) and
    the scales scatter alongside — every write stays local, nothing
    requantizes.

    Two implementations, chosen as the attention's is (``impl``,
    ``interpret``): the reference is ``.at[page, :, slot].set``; the
    kernel (``_kv_write_pallas``) writes whole pages by DMA, in place,
    and needs the rows' ``q_start``/``q_len`` and the static hints the
    attention takes — tokens no row owns are not written at all. The
    scale leaves are read by XLA only and always take the scatter.
    Returns (k_pages, v_pages, k_scale, v_scale); scales pass through as
    None on fp pools.

    A latent pool (one leaf): ``v_pages`` and ``v_t`` None, k_t [T, 1, W]
    the tokens' rows; v_pages comes back None.
    """
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be given together")
    if (v_pages is None) != (v_t is None) or (
            v_pages is None and k_scale is not None):
        raise ValueError("a latent pool is one leaf: no v_pages, no v_t, "
                         "no scales")
    _check_layer(k_pages, layer)
    at = (token_page, slice(None), token_slot)
    if layer is not None:
        at = (layer,) + at
    if k_scale is not None:
        from ray_tpu.ops.int8 import quantize_kv
        k_t, ks = quantize_kv(k_t)                # [T, Hkv, D], [T, Hkv]
        v_t, vs = quantize_kv(v_t)
        k_scale = k_scale.at[at].set(ks.astype(k_scale.dtype))
        v_scale = v_scale.at[at].set(vs.astype(v_scale.dtype))
    k_t = k_t.astype(k_pages.dtype)
    if v_t is not None:
        v_t = v_t.astype(v_pages.dtype)
    if _use_reference(impl, interpret):
        # advanced indices separated by a basic slice: the indexed
        # result is [T, Hkv, D]
        k_pages = k_pages.at[at].set(k_t)
        if v_t is not None:
            v_pages = v_pages.at[at].set(v_t)
        return k_pages, v_pages, k_scale, v_scale
    if q_start is None or q_len is None:
        raise ValueError("the write kernel needs the rows' q_start/q_len")
    one_layer = layer is None
    k_pages, v_pages, _, _, layer = _stacked(k_pages, v_pages, None, None,
                                             layer)
    k_pages, *v_pages = _kv_write_pallas(
        k_pages, v_pages, k_t, v_t, layer, token_page, token_slot, q_start,
        q_len, max_q_len, decode_rows, bool(interpret))
    v_pages = v_pages[0] if v_pages else None
    if one_layer:
        k_pages = k_pages[0]
        v_pages = None if v_pages is None else v_pages[0]
    return k_pages, v_pages, k_scale, v_scale
