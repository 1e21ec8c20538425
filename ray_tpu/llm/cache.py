"""Paged KV-cache bookkeeping (host side).

Role-equivalent to vLLM's block manager (the reference delegates paging to
vLLM — reference: llm/_internal/serve/deployments/llm/vllm/): a refcounted
free-list page allocator over the device-resident page pool, plus a
hash-indexed prefix cache over full KV pages (vLLM automatic prefix
caching, rebuilt for the TPU paged pool). Page 0 is reserved as the
scratch target for inactive batch slots, so the fixed-shape decode step
can always write *somewhere* without corrupting live sequences.

Prefix cache design:
  - a prompt's FULL token blocks (page_size tokens each) are keyed by a
    chain hash (block i's key folds in block i-1's key), so a lookup
    walks the chain and stops at the first miss — only page-aligned
    prefixes are shared, exactly vLLM's block-granular policy;
  - a cached page referenced by a live sequence is read-only by
    refcount: sequences never write into positions < their prompt
    length except through copy-on-write (engine copies the page first);
  - pages whose ONLY reference is the cache's are evictable, LRU order;
    the engine evicts under allocator pressure, so the cache is free
    HBM turned into hit-rate rather than reserved memory;
  - a page holds KV and nothing else. A configuration with conv,
    state-space or retention layers (recurrent state per batch slot,
    ``make_kv_cache``) gets NO prefix cache: a hit would restore the KV
    of the matched pages and not the recurrent state at that position
    (``prefix_cache_supported``; saving the state at page boundaries is
    open, ROADMAP Queue 2).

Two kinds of device state live in the one pool pytree: pages, for the
attention layers only, allocated and shared by the page; and the
recurrent layers' state (``STATE_LEAVES``), one fixed-size entry a batch
slot, owned by whoever holds the slot and never allocated or freed: a
conv layer's last inputs (kilobytes a slot), a state-space layer's matrix
state and its own conv's last inputs, a retention layer's matrix state and
normaliser, a gated-delta-rule layer's FLOAT32 matrix state and its conv's
last inputs (megabytes a slot and layer: there ``max_batch`` is a memory
decision as ``total_pages`` is). Both kinds live in one pool where a
configuration has both, a LATENT page leaf beside slot-state leaves too
(GigaChat3.5: one {"k"} leaf, no "v", and the delta layers' two leaves);
ALL THREE kinds of cache at once in Phi-4-mini-flash's pool: a Mamba-1
layer's float32 state and conv inputs a slot (``SLOT_STATE[MAMBA1]``), the
window page group, and a full group of ONE layer whose pages the cross
layers read and do not write (a differential pair of 64-wide heads held as
one 128-lane head: ``page_heads``).
A configuration with NO attention layer
has page leaves with no layer in them: the host's page accounting runs as
ever over pages that hold nothing and cost nothing (a deployment sizes
``total_pages`` so that they never bind), and what admits a sequence is a
free slot.

A SECOND PAGE GROUP, where some layers see a window only (``WINDOW``
layers; MiMo-V2-Flash is the first such block): their pages live in leaves
of their own (``WINDOW_LEAVES``: "k_win" / "v_win", a second LEAF of the
one pool pytree, not a second manager), counted by a ``PageAllocator`` of
their own, and a sequence owns pages of each group (``SequenceState.pages``
and ``.win_pages``). The full group holds a sequence's whole context; the
window group returns every page that lies wholly behind the window once a
step is booked (``window_first_page``), so it holds a constant a sequence
however long the context, and its page table is COMPACT: a base (the
logical page entry 0 stands for) and ``window_table_width`` entries a row,
so that neither the table nor the descriptor grows with the context for the
group that does not. The group is sized from the engine's own geometry
(``window_group_pages``: what every batch slot and every chunk row can hold
at once), so it never binds. No prefix cache with such a group
(``prefix_cache_supported``): a hit at a page boundary would need the
window's tokens before it, which are gone (keeping a cached boundary's last
window of pages is open, ROADMAP Queue 2).

PAGE PLANES, where the layers run several times (``cfg.ut_steps`` passes
over the same weights; Ouro-2.6B is the first such block): each pass keeps
keys and values of its own, so the page leaves' leading axis counts PLANES,
passes x attention layers (``page_planes``), pass u's layer l at plane
u * layers + l. A page is a page in every plane: ONE page table, one
allocator, one copy on write and one prefix hash serve them all, and a
prefix hit restores every plane (pages stay the only state). A token costs
``page_planes`` times a layer's bytes, which is what sets the batch.
"""

from __future__ import annotations

import collections
import logging
import os
from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp

from ray_tpu.models.llama import (ATTENTION, CONV, CROSS, DELTA, GMU, MAMBA,
                                  MAMBA1, RETENTION, WINDOW, LlamaConfig)
from ray_tpu.ops.retention import expanded_dim

logger = logging.getLogger(__name__)

SCRATCH_PAGE = 0
LANES = 128     # of a TPU vector register: the minor tile of HBM layouts


class DoubleFreeError(RuntimeError):
    """A page was freed more times than it was referenced."""


class PageAllocator:
    """Refcounted page allocator.

    ``alloc`` hands out pages at refcount 1; ``incref`` adds sharers
    (prefix-cache hits map the same physical page into several
    sequences); ``free`` DECREMENTS and only returns the page to the
    free list when the count hits zero. Freeing an unreferenced page is
    a double free: it would re-append the page and double-grant it,
    silently cross-wiring two sequences' KV — raise under pytest,
    log-and-skip in production (``strict_free`` overrides the default).
    """

    def __init__(self, total_pages: int,
                 strict_free: Optional[bool] = None):
        if total_pages < 2:
            raise ValueError("need at least 2 pages (one is scratch)")
        self._free: List[int] = list(range(1, total_pages))
        self._ref: Dict[int, int] = {}
        self.total_pages = total_pages
        if strict_free is None:
            strict_free = bool(os.environ.get("PYTEST_CURRENT_TEST"))
        self.strict_free = strict_free

    @property
    def num_free(self) -> int:
        return len(self._free)

    def refcount(self, page: int) -> int:
        return self._ref.get(page, 0)

    def alloc(self, n: int) -> Optional[List[int]]:
        if n > len(self._free):
            return None
        out, self._free = self._free[:n], self._free[n:]
        for p in out:
            self._ref[p] = 1
        return out

    def incref(self, pages: List[int]) -> None:
        for p in pages:
            if p == SCRATCH_PAGE:
                continue
            if self._ref.get(p, 0) <= 0:
                raise ValueError(f"incref of unallocated page {p}")
            self._ref[p] += 1

    def free(self, pages: List[int]) -> None:
        for p in pages:
            if p == SCRATCH_PAGE:
                continue
            ref = self._ref.get(p, 0)
            if ref <= 0:
                if self.strict_free:
                    raise DoubleFreeError(f"double free of page {p}")
                logger.warning("double free of page %d ignored", p)
                continue
            if ref == 1:
                del self._ref[p]
                self._free.append(p)
            else:
                self._ref[p] = ref - 1


def hash_token_blocks(prompt: List[int], page_size: int,
                      kv_tag: str = "") -> List[int]:
    """Chain hashes of the prompt's FULL token blocks: block i's hash
    folds in block i-1's, so equal hashes mean equal page-aligned
    prefixes (vLLM's block hash chain).

    ``kv_tag`` seeds the chain with the KV page dtype/quantization
    scheme (e.g. "bfloat16" vs "int8"): a page's BYTES depend on how
    the pool stores KV, so pages written under one scheme must never
    hash-match a lookup under another — same tokens, different
    (incompatible) cache contents.
    """
    out: List[int] = []
    h = hash((0x9E3779B9, kv_tag))
    for i in range(len(prompt) // page_size):
        block = tuple(prompt[i * page_size:(i + 1) * page_size])
        h = hash((h, block))
        out.append(h)
    return out


class PrefixCache:
    """Hash-indexed table of full KV pages, with LRU eviction of pages
    no live sequence references.

    The cache holds ONE allocator reference per published page; a page
    whose refcount drops to exactly that one (sequence finished) becomes
    evictable. ``match`` increfs hit pages on behalf of the caller —
    releasing them goes back through ``allocator.free`` +
    ``note_release`` like any other sequence page.
    """

    def __init__(self, allocator: PageAllocator, page_size: int,
                 kv_tag: str = ""):
        self.allocator = allocator
        self.page_size = page_size
        self.kv_tag = kv_tag        # KV dtype/quant scheme, in the hash
        self._pages: Dict[int, int] = {}          # block hash -> page id
        self._hash_of: Dict[int, int] = {}        # page id -> block hash
        # evictable pages (cache holds the only reference), LRU order
        self._lru: "collections.OrderedDict[int, None]" = \
            collections.OrderedDict()
        self.lookups = 0
        self.hits = 0
        self.hit_tokens = 0
        self.evictions = 0

    @property
    def num_cached(self) -> int:
        return len(self._pages)

    @property
    def num_evictable(self) -> int:
        return len(self._lru)

    def match(self, prompt: List[int]) -> Tuple[List[int], int, bool]:
        """Longest cached page-aligned prefix of ``prompt``.

        Returns ``(pages, matched_tokens, cow_needed)``; the matched
        pages are INCREF'd for the caller. ``matched_tokens`` is capped
        at ``len(prompt) - 1`` — the tail must compute at least the last
        position's logits to sample the first token. When that cap cuts
        into the last matched page (prompt length an exact page multiple
        with every block cached), ``cow_needed`` is True: the tail
        token's KV lands INSIDE that shared page, so the caller must
        copy it before writing (copy-on-write).
        """
        self.lookups += 1
        pages: List[int] = []
        for h in hash_token_blocks(prompt, self.page_size, self.kv_tag):
            p = self._pages.get(h)
            if p is None:
                break
            pages.append(p)
        if not pages:
            return [], 0, False
        matched = len(pages) * self.page_size
        cow = False
        if matched >= len(prompt):
            matched = len(prompt) - 1
            cow = True
        self.hits += 1
        self.hit_tokens += matched
        for p in pages:
            self._lru.pop(p, None)   # referenced again: not evictable
        self.allocator.incref(pages)
        return pages, matched, cow

    def register(self, prompt: List[int], pages: List[int]) -> None:
        """Publish a fully-prefilled prompt's full pages under their
        chain hashes (one cache reference per newly published page).
        Already-published hashes (the pages this prompt itself hit) are
        left as-is."""
        for i, h in enumerate(hash_token_blocks(prompt, self.page_size,
                                                self.kv_tag)):
            if i >= len(pages):
                break
            if h in self._pages:
                continue
            p = pages[i]
            if p in self._hash_of:
                continue
            self._pages[h] = p
            self._hash_of[p] = h
            self.allocator.incref([p])

    def note_release(self, pages: List[int]) -> None:
        """Call after ``allocator.free`` on a sequence's pages: cached
        pages whose only remaining reference is the cache's become
        LRU-evictable (most recently released = last evicted)."""
        for p in pages:
            if p in self._hash_of and self.allocator.refcount(p) == 1:
                self._lru[p] = None
                self._lru.move_to_end(p)

    def evict(self, n: int) -> int:
        """Drop up to ``n`` least-recently-used unreferenced cached
        pages back to the allocator free list; returns how many freed."""
        freed = 0
        while freed < n and self._lru:
            p, _ = self._lru.popitem(last=False)
            h = self._hash_of.pop(p)
            self._pages.pop(h, None)
            self.allocator.free([p])
            freed += 1
            self.evictions += 1
        return freed


#: the pool's leaves that are not pages: a conv layer's last inputs; a
#: state-space layer's matrix state and the last inputs of its conv; a
#: retention layer's matrix state and its normaliser; a gated-delta-rule
#: layer's matrix state and the last inputs of its conv
STATE_LEAF, SSM_LEAF, SSM_CONV_LEAF = "conv", "ssm", "ssm_conv"
RET_LEAF, RET_NORM_LEAF = "retention", "retention_norm"
DELTA_LEAF, DELTA_CONV_LEAF = "delta", "delta_conv"
SSM1_LEAF, SSM1_CONV_LEAF = "ssm1", "ssm1_conv"

#: what a layer of each kind keeps per BATCH SLOT: kind -> {leaf: cfg ->
#: (its shape after [layers of the kind, max_batch + 1], its dtype)}. The
#: ONE place that says so: ``make_kv_cache`` builds the leaves from it, the
#: page copy and the engine's accounting tell them from pages by it
#: (``STATE_LEAVES``), and whether a configuration keeps any decides the
#: prefix cache and the mixed step's descriptor (``keeps_slot_state``). The
#: body that reads a leaf is llm/model.py's, by the same kind.
SLOT_STATE = {
    ATTENTION: {},          # pages, and nothing a slot
    WINDOW: {},             # pages of the second group, nothing a slot
    CONV: {
        # the last inputs of the depthwise conv, oldest first
        STATE_LEAF: lambda c: ((c.conv_kernel - 1, c.dim), c.dtype)},
    MAMBA: {
        # a head's [P, N] matrix lies transposed, its N columns as rows
        # over all heads' values: the layout the update kernel reads
        # (ops/ssm.py). The largest thing a slot owns by three orders:
        # H P N values a layer
        SSM_LEAF: lambda c: (
            (c.ssm_state, c.ssm_heads * c.ssm_head_dim), c.dtype),
        # the last inputs of its conv (x, B and C together, before the
        # bias and the SiLU), oldest first
        SSM_CONV_LEAF: lambda c: (
            (c.ssm_conv - 1, c.ssm_channels), c.dtype)},
    RETENTION: {
        # a key/value head's matrix state over the expanded key (D =
        # expanded_dim(head_dim), 8704 at 128): D on the sublanes, the
        # value on the lanes, a head's block contiguous: what the update
        # kernel moves in one DMA (ops/retention.py)
        RET_LEAF: lambda c: (
            (c.n_kv_heads, expanded_dim(c.head_dim), c.head_dim), c.dtype),
        # its normaliser as a symmetric matrix
        RET_NORM_LEAF: lambda c: (
            (c.n_kv_heads, c.head_dim, c.head_dim), jnp.float32)},
    DELTA: {
        # a VALUE head's matrix state, the key on the sublanes and the
        # value on the lanes, FLOAT32 whatever the model's dtype: the
        # family carries its recurrent state in float32 (4 MB a slot and
        # layer at 64 heads of 128 x 128), and the update kernel moves a
        # slot's heads as they lie (ops/delta.py)
        DELTA_LEAF: lambda c: (
            (c.delta_value_heads, c.delta_key_dim, c.delta_value_dim),
            jnp.float32),
        # the last inputs of its conv (q, k and v together, before the
        # SiLU), oldest first
        DELTA_CONV_LEAF: lambda c: (
            (c.delta_conv - 1, delta_channels(c)), c.dtype)},
    MAMBA1: {
        # a Mamba-1 layer's state, one value a (state index, channel) pair,
        # the state index on the sublanes and the channels on the lanes
        # (the state-space leaf's layout: what both kernels of
        # ops/selective_scan.py read), FLOAT32 whatever the model's dtype:
        # a pair that decays by 0.9999 a token takes increments a
        # thousandth of what it holds, which a bfloat16 state drops
        SSM1_LEAF: lambda c: ((c.ssm1_state, c.ssm1_channels), jnp.float32),
        # the last inputs of its conv (before the bias and the SiLU),
        # oldest first
        SSM1_CONV_LEAF: lambda c: (
            (c.ssm1_conv - 1, c.ssm1_channels), c.dtype)},
    GMU: {},                # reads the newest mamba1 layer's output: nothing
    CROSS: {},              # reads the newest full layer's pages: nothing
}
STATE_LEAVES = tuple(leaf for leaves in SLOT_STATE.values()
                     for leaf in leaves)
#: the kinds whose layers hold PAGES: the full group's and the second
#: group's (``make_kv_cache`` stacks each group's leaves over them)
PAGE_KINDS = (ATTENTION, WINDOW)


def keeps_nothing(kind: str) -> bool:
    """Whether a layer of ``kind`` leaves nothing in the pool: no page and
    no state a slot. What it computes for a token only that token's later
    layers read (llm/model.py: tail_start)."""
    return kind not in PAGE_KINDS and not SLOT_STATE[kind]


def delta_channels(cfg: LlamaConfig) -> int:
    """Channels of a gated-delta-rule layer's conv: q, then k, then v."""
    return 2 * cfg.delta_key_heads * cfg.delta_key_dim \
        + cfg.delta_value_heads * cfg.delta_value_dim


#: the window layers' page leaves: the second page group's
WINDOW_LEAVES = ("k_win", "v_win")


def window_first_page(pos: int, window: int, page_size: int) -> int:
    """The first logical page a token at position ``pos`` still sees with
    a window of ``window`` positions (the token itself counted): every
    page before it lies wholly behind the window of ``pos`` and of every
    later position."""
    return max(0, pos - (window - 1)) // page_size


def window_table_width(window: int, n_tokens: int, page_size: int) -> int:
    """Entries of a row's COMPACT window page table: a row of at most
    ``n_tokens`` consecutive tokens reads and writes window - 1 + n_tokens
    positions, which may start anywhere in their first page."""
    return -(-(window - 1 + n_tokens) // page_size) + 1


def window_group_pages(cfg: LlamaConfig, page_size: int, max_batch: int,
                       decode_chunk: int, prefill_chunk: int,
                       prefill_rows: int) -> int:
    """Pages of the window group (0: the configuration has no window
    layer): what every batch slot holds across a decode block and every
    chunk row across its chunk, and the scratch page. A sequence between
    two steps holds less than a decode block's, so the group never binds:
    what admits a sequence is a slot and pages of the FULL group."""
    if not cfg.layers_of(WINDOW):
        return 0
    w = cfg.sliding_window
    return max_batch * window_table_width(w, decode_chunk, page_size) \
        + prefill_rows * window_table_width(w, prefill_chunk, page_size) + 1


def page_heads(cfg: LlamaConfig, window: bool = False) -> Tuple[int, int,
                                                                 int]:
    """(key/value heads, K row, V row) of a page group as the pool holds
    them (before any lane padding). Differential attention holds a PAIR of
    adjacent heads as one head twice as wide, [k1 | k2] and [v1 | v2]: at a
    published head of 64 that is a whole 128-lane row and no byte of it
    padding, and the kernels score a pair's two queries against it as
    [q1 | 0] and [0 | q2] (llm/model.py: _diff_attention)."""
    heads = cfg.window_kv_heads if window else cfg.n_kv_heads
    pair = 2 if cfg.diff_attention else 1
    return heads // pair, cfg.qk_head_dim * pair, cfg.v_dim * pair


def page_planes(cfg: LlamaConfig) -> int:
    """Entries of the full page group's leading axis: one a pass of the
    walk and attention layer (pass u, layer l at u * layers + l); the
    attention layers themselves where they run once."""
    return cfg.ut_steps * len(cfg.layers_of(ATTENTION))


def slot_state_kinds(cfg: LlamaConfig) -> Tuple[str, ...]:
    """The kinds of ``cfg``'s layers that keep state per batch slot."""
    return tuple(kind for kind, leaves in SLOT_STATE.items()
                 if leaves and cfg.layers_of(kind))


def keeps_slot_state(cfg: LlamaConfig) -> bool:
    """Whether some layer keeps state per batch slot (``SLOT_STATE``):
    the pool then has leaves beside its pages, and a token of the mixed
    step names its slot."""
    return bool(slot_state_kinds(cfg))


def make_kv_cache(cfg: LlamaConfig, total_pages: int, page_size: int,
                  dtype=None, kv_dtype: Optional[str] = None,
                  max_batch: int = 0, lane_pad: bool = False,
                  window_pages: int = 0):
    """Device-resident paged KV pool as a dict pytree.

    {"k", "v"}: [n_attn, total_pages, Hkv, page_size, Dk | Dv], one entry
    of the leading axis for each ATTENTION layer (every layer, unless the
    configuration names others: those have no pages) and, where the layers
    run several times, each pass (``page_planes``). Dk and Dv are the
    score head's and the value head's width (``cfg.qk_head_dim``,
    ``cfg.v_dim``: both head_dim unless the configuration says otherwise;
    192 and 128 give K rows of 256 lanes and V rows of 128 under
    ``lane_pad``). With WINDOW layers a SECOND PAGE GROUP rides the same
    dict, {"k_win", "v_win"}: [n_window, window_pages, window_kv_heads,
    page_size, Dk | Dv] (``WINDOW_LEAVES``; the module docstring says how
    it is counted and freed; ``window_group_pages`` sizes it). With
    ``kv_dtype="int8"`` the pools are int8 and {"k_scale", "v_scale"}
    [n_layers, total_pages, Hkv, page_size] bf16 per-(page, head, slot)
    dequant scales ride alongside — one pytree, so jit donation,
    shard_map specs and COW copies treat pages + scales as one unit.
    ``kv_dtype`` in {None/"model" (cfg dtype), "int8"}. Where no layer is
    an attention layer ``k`` and ``v`` are [0, total_pages, ...]: leaves
    with no layer and no bytes.

    The discipline (llm/model.py): one buffer in this one row-major
    layout for every program. The step programs take it donated, carry
    it whole through their scans and update it in place — a layer is
    written (``_kv_write_pallas``, whole pages by DMA) and read (the
    attention kernel) by its index into the stack, never sliced out of
    it; a token is one row of each head's [page_size, D] tile.

    ``lane_pad`` (the pool the KERNELS take, on a TPU): D is head_dim
    rounded up to the 128 lanes of a vector register. A narrower row is
    padded to that in HBM whatever its shape says, and the kernels' page
    DMAs cannot slice inside it (Mosaic refuses head_dim 64); the step
    zero-pads q, k and v to the pool's D. At head_dim 128 it changes
    nothing.

    Latent attention (cfg.kv_lora_rank): ONE leaf, {"k"}: [n_attn,
    total_pages, 1, page_size, W]. A token's row is its normed latent
    (kv_lora_rank values) followed by its rotated shared key part
    (qk_rope_head_dim), for all heads; there is no "v" leaf at all: the
    value is the leading kv_lora_rank values of the same row, which the
    kernels take as a lane slice of the K block they already hold. W is
    ``latent_row_width``: the row itself, or with ``lane_pad`` whole
    lanes (576 -> 640: four and a half 128-lane tiles cannot be moved by
    a page DMA, and HBM tiles the row to 640 whatever its shape says, so
    the padding costs no memory and a ninth more bytes read). Pages stay
    the only state: the prefix cache, the copy on write and
    recompute-preemption work on this leaf as on K and V.

    State per batch slot (``SLOT_STATE``: which kinds, which leaves, in
    what shape): each leaf is [layers of its kind, max_batch + 1, ...].
    Axis 1 is BATCH SLOTS, not pages (the page copy leaves it alone), and
    the last is a scratch slot that padding tokens write. The leaves ride
    the same dict, so they are donated, carried and updated in place with
    the pages. Nothing ever zeroes a slot: a row whose first token has
    position 0 reads zeros instead of its slot. The step reads and writes
    the state-space leaves by dynamic slices only (llm/model.py
    ``_SsmConvState``, ops/ssm.py): around a gather or a scatter of rows
    XLA re-laid the whole conv leaf twice a layer and copied half the
    state leaf (PERF.md, PR 37).
    """
    if kv_dtype not in (None, "model", "int8"):
        raise ValueError(f"kv_dtype must be 'model' or 'int8', "
                         f"got {kv_dtype!r}")
    kinds = slot_state_kinds(cfg)
    if kinds and max_batch < 1:
        raise ValueError(f"{', '.join(kinds)} layers keep state per batch "
                         f"slot: make_kv_cache needs max_batch")
    if cfg.kv_lora_rank:
        if kv_dtype == "int8":
            raise ValueError(
                "kv_dtype 'int8' is not built for a latent pool "
                "(kv_lora_rank): one scale a token would cover the normed "
                "latent and the rotary key part, two ranges in one row")
        kv = {"k": jnp.zeros(
            (page_planes(cfg), total_pages, 1, page_size,
             latent_row_width(cfg, lane_pad)), dtype or cfg.dtype)}
    else:
        def rows(width):
            return -(-width // LANES) * LANES if lane_pad else width

        hkv, dk, dv = page_heads(cfg)
        shape = (page_planes(cfg), total_pages, hkv, page_size)
        dk, dv = rows(dk), rows(dv)
        windowed = bool(cfg.layers_of(WINDOW))
        if kv_dtype == "int8":
            if windowed or dk != dv:
                raise ValueError(
                    "kv_dtype 'int8' is not built for a pool of two page "
                    "groups or of K and V rows of different width")
            from ray_tpu.ops.int8 import KV_SCALE_DTYPE
            kv = {"k": jnp.zeros(shape + (dk,), jnp.int8),
                  "v": jnp.zeros(shape + (dv,), jnp.int8),
                  "k_scale": jnp.zeros(shape, KV_SCALE_DTYPE),
                  "v_scale": jnp.zeros(shape, KV_SCALE_DTYPE)}
        else:
            dtype = dtype or cfg.dtype
            kv = {"k": jnp.zeros(shape + (dk,), dtype),
                  "v": jnp.zeros(shape + (dv,), dtype)}
        if windowed:
            if window_pages < 2:
                raise ValueError(
                    "sliding_attention layers keep their pages in a second "
                    "group: make_kv_cache needs window_pages "
                    "(window_group_pages)")
            shape = (len(cfg.layers_of(WINDOW)), window_pages,
                     page_heads(cfg, window=True)[0], page_size)
            kv.update(k_win=jnp.zeros(shape + (dk,), dtype),
                      v_win=jnp.zeros(shape + (dv,), dtype))
    for kind in kinds:
        slots = (len(cfg.layers_of(kind)), max_batch + 1)
        for leaf, of in SLOT_STATE[kind].items():
            shape, leaf_dtype = of(cfg)
            kv[leaf] = jnp.zeros(slots + shape, leaf_dtype)
    return kv


def latent_row_width(cfg: LlamaConfig, lane_pad: bool = False) -> int:
    """Values a token's row of a latent pool holds: the latent and the
    shared rotary key part, rounded up to whole lanes with ``lane_pad``."""
    w = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    return -(-w // LANES) * LANES if lane_pad else w


def prefix_cache_supported(cfg: LlamaConfig) -> bool:
    """Whether a page-aligned prefix hit restores ALL of a sequence's
    state at that position: true where pages are the only state and no
    page group frees behind a window (the window's tokens before a cached
    boundary are gone)."""
    return not keeps_slot_state(cfg) and not cfg.layers_of(WINDOW)


def kv_cache_tag(cfg: LlamaConfig, kv_dtype: Optional[str]) -> str:
    """The PrefixCache hash seed for a pool config: pages written under
    one KV storage scheme must never match a lookup under another (a
    latent pool's pages hold other values than a K/V pool's; K and V rows
    of different width, and a second page group behind a window, name
    themselves too, though a configuration with such a group has no prefix
    cache to seed; so does a pool of a plane a pass)."""
    if kv_dtype == "int8":
        return "int8"
    name = str(jnp.dtype(cfg.dtype).name)
    if cfg.kv_lora_rank:
        return f"{name}-latent{cfg.kv_lora_rank}+{cfg.qk_rope_head_dim}"
    if cfg.qk_head_dim != cfg.v_dim:
        name += f"-k{cfg.qk_head_dim}v{cfg.v_dim}"
    if cfg.layers_of(WINDOW):
        # two groups: the full layers' pages, and the window layers' that
        # free behind the window
        name += f"-window{cfg.sliding_window}x{cfg.window_kv_heads}"
    if cfg.ut_steps > 1:
        name += f"-passes{cfg.ut_steps}"
    return name


class SequenceState:
    """Per-request paging state."""

    def __init__(self, request_id: str, prompt: List[int],
                 max_new_tokens: int, enqueue_ts: float = 0.0):
        self.request_id = request_id
        self.prompt = list(prompt)
        self.max_new_tokens = max_new_tokens
        self.generated: List[int] = []
        self.pages: List[int] = []
        # the window group's (a configuration with window layers): the
        # pages of logical pages win_base .. win_base + len(win_pages) - 1;
        # those before win_base were freed behind the window
        self.win_pages: List[int] = []
        self.win_base = 0
        self.slot: Optional[int] = None     # decode batch slot
        self.done = False
        self.enqueue_ts = enqueue_ts        # admission age (HOL fairness)
        # chunked-prefill progress: prompt tokens whose KV is in pages
        # (prefix-cache hits + chunks computed so far); prefilling=True
        # keeps the sequence out of the decode batch until the tail is
        # fully computed
        self.num_computed = 0
        self.cached_tokens = 0              # served from the prefix cache
        self.prefilling = False
        # recompute-preemption state: a preempted sequence folds its
        # generated tokens into the prompt, re-prefills, then restores
        # the split in _postfill_book (n_prompt marks the original
        # boundary; restore_generated stashes the folded tokens)
        self.n_prompt = len(self.prompt)
        self.preempt_count = 0
        self.restore_generated: List[int] = []
        self.record = None                  # flight-recorder RequestRecord
        # what launched programs the engine has not booked yet hold of it
        # (llm/engine.py runs one program ahead): how many of them it is a
        # row of, the tokens they give it unless one of them is EOS, and
        # whether the last token max_new_tokens allows is among those (it
        # is then a row of no later program)
        self.flights = 0
        self.unbooked = 0
        self.ended = False

    @property
    def num_tokens(self) -> int:
        return len(self.prompt) + len(self.generated)

    @property
    def num_launched(self) -> int:
        """num_tokens once every launched program is booked."""
        return self.num_tokens + self.unbooked

    @property
    def tokens_left(self) -> int:
        """Tokens max_new_tokens still allows past the launched ones (a
        preempted sequence's generated tokens lie folded in its prompt
        until its re-prefill is booked: restore_generated)."""
        return self.max_new_tokens - len(self.generated) \
            - len(self.restore_generated) - self.unbooked

    def pages_needed(self, page_size: int, headroom: int = 0) -> int:
        return -(-(self.num_launched + headroom) // page_size)
