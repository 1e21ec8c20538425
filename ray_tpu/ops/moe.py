"""Dropless routed-expert feed-forward for the serving step.

The training-side experts (parallel/moe.py) route into static capacity
buckets and drop what overflows; a server may not. Here every valid token
is multiplied by exactly its ``top_k`` experts, whatever else is in the
batch:

  - route: softmax over the experts (or a sigmoid of each, with a
    per-expert bias that enters the choice and not the weights) in
    float32, top-k (weights kept as they are, or renormalised); padding
    slots get weight 0 and an expert id past the last expert, so they
    join no group;
  - group: the (token, expert) pairs are laid out expert by expert, each
    expert's group padded up to whole ROW TILES of ``tm`` rows, so a tile
    belongs to one expert. The layout is a cumulative sum over a one-hot
    [pairs, experts] matrix — no sort. Shapes are static (the worst case
    is pairs + experts * (tm - 1) rows); the number of tiles in use is
    data, and tiles past it cost an empty grid step: no copy, no matmul.
    Work grows with routed pairs, not with tokens * experts;
  - experts: ONE Pallas kernel (``_moe_experts_pallas``) computes, for a
    tile, (silu(x G_e) * (x U_e)) D_e with the expert's weights streamed
    from HBM in blocks of the expert width and float32 accumulation. The
    weights are indexed in the kernel as [layer, expert, ...]: the stacked
    tree is passed whole with the layer as a scalar, because a per-layer
    slice handed to a custom call is a copy of every expert's weights;
  - combine: each token gathers its k rows and sums them under the
    routing weights in float32.

``tm`` and the width block ``fb`` follow from the static shapes
(``_tiling``); nothing is configured. The counters a step reports
(``moe_pairs``, ``moe_hits``, ``moe_hot``) are reduced here, on the
device, over valid tokens only.

A chip's SHARE of the layer (``held=(first, n)``): the weights hold experts
first .. first + n - 1 only. The router keeps every expert's output and its
k a token, the weights are renormalised over all k chosen, and a pair whose
expert is held elsewhere goes nowhere (the expert id past the last that
padding has): grouping and the kernel run over the n held. Nothing stands
in for the absent chips or their traffic; the step counts the pairs it did
not serve (``moe_absent``) beside the ones it did.

On a backend without the kernel (CPU test meshes) the tiles go through an
einsum over gathered expert weights: the same layout, the same numerics
contract, the portable fallback and the oracle of the kernel's tests.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.paged_attention import kernels_supported

#: what a step program reports about its routing, in this order
COUNTERS = ("moe_pairs", "moe_hits", "moe_hot")
#: ... and, after them, where the layer holds a share of its experts: the
#: pairs routed to experts it does not hold
COUNTER_ABSENT = "moe_absent"


#: the router's jax.named_scope: it reaches the device trace in each op's
#: name path, where the benchmark's readers match it
SCOPE_ROUTER = "moe_router"


@jax.named_scope(SCOPE_ROUTER)
def route(m, valid, router, top_k: int, renorm: bool, *,
          score: str = "softmax", bias=None, eps: float = 0.0,
          scale: float = 1.0):
    """(weights [T, k] float32, experts [T, k] int32). The router runs in
    float32 at the highest matmul precision: T x d x E is nothing next to
    the experts, and a bf16 rounding of a logit flips a near-tie between
    the k-th and the (k+1)-th expert, which costs a whole expert's output.
    Padding tokens: weight 0, expert id E (one past the last).

    ``score``: "softmax" over the experts, or "sigmoid" of each logit.
    ``bias`` [E] float32 is added to the scores for the CHOICE only: the
    weights are the chosen experts' scores without it. ``eps`` joins the
    renormalising sum and ``scale`` multiplies the weights. The defaults
    are OLMoE's routing, operation for operation."""
    logits = jnp.dot(m.astype(jnp.float32), router.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    if score == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    elif score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"score must be 'softmax' or 'sigmoid', "
                         f"got {score!r}")
    if bias is None:
        w, e = lax.top_k(scores, top_k)
    else:
        _, e = lax.top_k(scores + bias.astype(jnp.float32), top_k)
        w = jnp.take_along_axis(scores, e, axis=-1)
    if renorm:
        total = jnp.sum(w, axis=-1, keepdims=True)
        w = w / (total + eps if eps else total)
    if scale != 1.0:
        w = w * scale
    keep = valid[:, None]
    return jnp.where(keep, w, 0.0), \
        jnp.where(keep, e.astype(jnp.int32), router.shape[-1])


#: elements of one weight block [d, fb] the kernel holds at most: 1024
#: columns at d = 2048 (4 MB in bf16; three matrices, double-buffered: 24 MB
#: of VMEM), the shapes PERF.md's table (PR 26) was measured at
_BLOCK_ELEMS = 2048 * 1024


def _tiling(n_pairs: int, n_experts: int, f: int, d: int) -> Tuple[int, int]:
    """(tm, fb) from the static shapes. A row tile is the power of two
    nearest above the mean group, held to [16, 128]: 16 rows is one bf16
    sublane tile (the decode loop's groups hold ~4 pairs: a pure weight
    stream, 90 % of its roofline at 16 x 1024), and 128 is within a tenth
    of the best tile both for a mixed step full of real tokens (256 wins)
    and for one that is a quarter real (64 wins). The width block is the
    whole expert width up to 1024 columns and up to _BLOCK_ELEMS elements
    of a [d, fb] block (model width d = 2048: the same 1024; d = 4096: 512,
    where 1024 would be 8 MB a matrix and 48 MB of VMEM for the three,
    double-buffered): contiguous in HBM, one grid step a tile where the
    expert is no wider. The table, measured at d = 2048, is in PERF.md
    (PR 26)."""
    tm = 16
    while tm < 128 and tm * n_experts < n_pairs:
        tm *= 2
    fb = f
    while (fb > 1024 or d * fb > _BLOCK_ELEMS) and fb % 2 == 0:
        fb //= 2
    return tm, fb


def group(experts, n_experts: int, tm: int):
    """Lay the pairs out by expert in whole tiles of tm rows.

    experts [T, k] int32 (n_experts = routed nowhere) ->
      dest [T, k]   each pair's row, n_rows for a pair routed nowhere
      tile_expert [n_tiles], n_used []   the expert of each tile in use
      counts [E]    pairs per expert
    """
    T, k = experts.shape
    P = T * k
    n_tiles = (P + n_experts * (tm - 1)) // tm
    pe = experts.reshape(P)
    onehot = (pe[:, None] == jnp.arange(n_experts, dtype=jnp.int32)[None]
              ).astype(jnp.int32)                              # [P, E]
    counts = onehot.sum(axis=0)
    rank = ((jnp.cumsum(onehot, axis=0) - onehot) * onehot).sum(axis=1)
    tiles = (counts + tm - 1) // tm
    tile_end = jnp.cumsum(tiles)
    row0 = (tile_end - tiles) * tm                             # [E]
    dest = (onehot * row0[None]).sum(axis=1) + rank
    dest = jnp.where(pe < n_experts, dest, n_tiles * tm)
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(n_tiles, dtype=jnp.int32),
                         side="right"), n_experts - 1).astype(jnp.int32)
    return dest.reshape(T, k), tile_expert, tile_end[-1], counts


def gate_half(g, clamp: float = 0.0):
    """silu(g), the gate half of a SwiGLU; with ``clamp`` = c (a published
    swiglu_limit) silu(min(g, c)): held from above."""
    return jax.nn.silu(jnp.minimum(g, clamp) if clamp else g)


def up_half(u, clamp: float = 0.0):
    """The linear half of a SwiGLU; with ``clamp`` = c clip(u, -c, c)."""
    return jnp.clip(u, -clamp, clamp) if clamp else u


def _experts_kernel(te_ref, meta_ref,                 # scalar prefetch
                    x_ref, g_ref, u_ref, d_ref, o_ref, acc_ref, *, nf,
                    clamp=0.0):
    t, j = pl.program_id(0), pl.program_id(1)

    @pl.when(t < meta_ref[0])
    def _live():
        @pl.when(j == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        x = x_ref[...]                                       # [tm, d]
        g = jnp.dot(x, g_ref[...], preferred_element_type=jnp.float32)
        u = jnp.dot(x, u_ref[...], preferred_element_type=jnp.float32)
        h = (gate_half(g, clamp) * up_half(u, clamp)).astype(x.dtype)
        acc_ref[...] += jnp.dot(h, d_ref[...],
                                preferred_element_type=jnp.float32)

        @pl.when(j == nf - 1)
        def _():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "fb", "interpret",
                                             "clamp"))
def _moe_experts_pallas(x_rows, tile_expert, n_used, layer, gate, up, down,
                        tm: int, fb: int, interpret: bool = False,
                        clamp: float = 0.0):
    """x_rows [n_tiles * tm, d] in tile order; gate / up [L, E, d, f],
    down [L, E, f, d]; returns the experts' outputs, row for row. Tiles
    from n_used on are pinned to the last live tile's blocks, so they
    move nothing and compute nothing; their rows come back undefined."""
    n_rows, d = x_rows.shape
    n_tiles = n_rows // tm
    f = gate.shape[-1]
    nf = f // fb
    isz = x_rows.dtype.itemsize
    meta = jnp.stack([n_used, layer]).astype(jnp.int32)

    def pin(t, j, meta):
        live = t < meta[0]
        return (jnp.where(live, t, jnp.maximum(meta[0] - 1, 0)),
                jnp.where(live, j, nf - 1))

    def row_map(t, j, te, meta):
        return pin(t, j, meta)[0], 0

    def in_map(t, j, te, meta):                  # gate, up: [L, E, d, f]
        tt, jj = pin(t, j, meta)
        return meta[1], te[tt], 0, jj

    def out_map(t, j, te, meta):                 # down: [L, E, f, d]
        tt, jj = pin(t, j, meta)
        return meta[1], te[tt], jj, 0

    # double-buffered weight blocks and row tiles, the accumulator, and
    # the float32 [tm, fb] intermediates
    need = 2 * 3 * d * fb * gate.dtype.itemsize + 4 * tm * d * isz \
        + tm * d * 4 + 3 * tm * fb * 4
    return pl.pallas_call(
        functools.partial(_experts_kernel, nf=nf, clamp=clamp),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_tiles, nf),
            in_specs=[pl.BlockSpec((tm, d), row_map),
                      pl.BlockSpec((None, None, d, fb), in_map),
                      pl.BlockSpec((None, None, d, fb), in_map),
                      pl.BlockSpec((None, None, fb, d), out_map)],
            out_specs=pl.BlockSpec((tm, d), row_map),
            scratch_shapes=[pltpu.VMEM((tm, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(x_rows.shape, x_rows.dtype),
        # every tile against one expert's three matrices: with no
        # estimate or a loose VMEM limit XLA's scheduler keeps more of
        # the next layer's weights in flight (PERF.md, PR 25)
        cost_estimate=pl.CostEstimate(
            flops=6 * n_rows * d * f,
            transcendentals=n_rows * f,
            bytes_accessed=n_tiles * 3 * d * f * gate.dtype.itemsize
            + 2 * n_rows * d * isz),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(need * 5 // 4, 16 << 20)),
        interpret=interpret,
    )(tile_expert, meta, x_rows, gate, up, down)


def _experts_reference(x_rows, tile_expert, layer, gate, up, down, tm: int,
                       clamp: float = 0.0):
    """The tiles through an einsum over gathered expert weights."""
    n_rows, d = x_rows.shape
    cd = x_rows.dtype
    xt = x_rows.reshape(n_rows // tm, tm, d)
    g, u, dn = (w[layer][tile_expert].astype(cd) for w in (gate, up, down))
    h = gate_half(jnp.einsum("ntd,ndf->ntf", xt, g), clamp) \
        * up_half(jnp.einsum("ntd,ndf->ntf", xt, u), clamp)
    return jnp.einsum("ntf,nfd->ntd", h, dn).reshape(n_rows, d)


def moe_ffn(m, valid, router, gate, up, down, top_k: int, renorm: bool, *,
            layer=None, impl: Optional[str] = None,
            interpret: bool = False, held: Optional[Tuple[int, int]] = None,
            clamp: float = 0.0, **routing):
    """m [T, d] (normed hidden states), valid [T] bool -> (y [T, d] in m's
    dtype, counters [3] int32 in COUNTERS' order).

    router [d, E]; gate / up [E, d, f] and down [E, f, d], or the stacked
    [L, E, ...] trees with ``layer`` the index to use (what the serving
    step passes: see the module docstring). y is 0 for padding tokens.
    ``impl``: "kernel" | "reference", None = the kernel on a TPU.
    ``routing``: route's score, bias, eps and scale. ``clamp``:
    ``gate_half``'s and ``up_half``'s (0: none).

    ``held`` = (first, n): the weights are [.., n, ..], experts first ..
    first + n - 1 of the router's E (module docstring); y is then this
    chip's part of the layer's output, and the counters are [4]: COUNTERS
    over the held experts, then COUNTER_ABSENT.
    """
    T, d = m.shape
    if layer is None:
        gate, up, down, layer = gate[None], up[None], down[None], 0
    layer = jnp.asarray(layer, jnp.int32)
    E, f = gate.shape[1], gate.shape[-1]
    w, e = route(m, valid, router, top_k, renorm, **routing)
    if held is None:
        tm, fb = _tiling(T * top_k, E, f, d)
    else:
        first, n = held
        if n != E:
            raise ValueError(f"held={held} names {n} experts; the weights "
                             f"hold {E}")
        n_routed = router.shape[-1]
        chosen = (e < n_routed).sum()          # the valid tokens' pairs
        e = e - first
        e = jnp.where((e >= 0) & (e < n), e, n)
        # the mean group is the share's: pairs * n / the router's experts
        tm, fb = _tiling(-(-T * top_k * n // n_routed), E, f, d)
    dest, tile_expert, n_used, counts = group(e, E, tm)
    n_rows = tile_expert.shape[0] * tm
    # each row's token (row 0's for the padding inside a tile: finite)
    row_token = jnp.zeros(n_rows, jnp.int32).at[dest.reshape(-1)].set(
        jnp.arange(T * top_k, dtype=jnp.int32) // top_k, mode="drop",
        unique_indices=True)
    x_rows = m[row_token]
    if impl is None:
        impl = "kernel" if kernels_supported() or interpret else "reference"
    if impl == "kernel":
        y_rows = _moe_experts_pallas(x_rows, tile_expert, n_used, layer,
                                     gate, up, down, tm, fb, interpret,
                                     clamp)
    elif impl == "reference":
        y_rows = _experts_reference(x_rows, tile_expert, layer, gate, up,
                                    down, tm, clamp)
    else:
        raise ValueError(f"impl must be 'kernel' or 'reference', "
                         f"got {impl!r}")
    # rows of dead tiles are undefined: select, do not multiply by 0
    picked = y_rows[jnp.minimum(dest, n_rows - 1)]             # [T, k, d]
    picked = jnp.where((dest < n_rows)[..., None],
                       picked.astype(jnp.float32), 0.0)
    y = (w[..., None] * picked).sum(axis=1).astype(m.dtype)
    counters = [counts.sum(), (counts > 0).sum(), counts.max()]
    if held is not None:
        counters.append(chosen - counts.sum())
    return y, jnp.stack(counters).astype(jnp.int32)
