"""Ragged paged-attention tests: the single-dispatch mixed
prefill+decode kernel (ops/paged_attention.py ragged_* APIs) against a
dense per-token oracle, across GQA configs, page-boundary-straddling
chunks, degenerate single-row batches, and int8-quantized KV pages.

The Pallas kernel runs in interpret mode (pallas_interpret marker) so
the kernel logic — the static tiling from ``decode_rows`` / ``max_q_len``,
page copies driven by the scalar-prefetched table, the per-tile block
count and causal mask, online softmax across a tile's KV blocks — is
exercised in tier-1 on CPU.

A test file is what a worker of the suite is handed, so the interpreter's
long cases stand in files of their own: what blocking can break in
tests/test_ragged_blocked.py, the latent one-token tile in
tests/test_ragged_latent.py, the window block's full layers in
tests/test_ragged_full_layer.py. Here: the reference against the dense
oracle (tests/_ragged.py), the kernel on the small batch, int8 pages, the
in-place write, the step programs through the kernels, rows of one
sequence.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _ragged import dense_oracle, mixed_batch, unowned
from ray_tpu.ops.int8 import dequantize_kv, quantize_kv
from ray_tpu.ops.paged_attention import (_ragged_attention_pallas,
                                         ragged_paged_attention,
                                         ragged_paged_attention_reference,
                                         write_ragged_kv)


@pytest.mark.parametrize("Hq,Hkv", [(8, 8), (8, 4), (8, 1)])
def test_ragged_reference_matches_dense_gqa(Hq, Hkv):
    args = mixed_batch(jax.random.PRNGKey(Hq * 10 + Hkv), Hq, Hkv, 32)
    want = dense_oracle(*args)
    got = ragged_paged_attention_reference(*args, max_q_len=6,
                                           decode_rows=2)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)
    # cost hints must be cost-only: no hints, same numbers
    got2 = ragged_paged_attention_reference(*args)
    np.testing.assert_allclose(np.asarray(got2), want, atol=1e-5)
    # padding tokens (owned by no row) must come back exactly zero
    assert np.all(np.asarray(got)[unowned(args)] == 0.0)


@pytest.mark.pallas_interpret
@pytest.mark.parametrize("Hq,Hkv", [(8, 8), (8, 4), (8, 1)])
def test_ragged_pallas_interpret_matches_reference(Hq, Hkv, pallas_interpret):
    D = 128   # lane-width head_dim, the TPU-shaped case
    args = mixed_batch(jax.random.PRNGKey(Hq + Hkv), Hq, Hkv, D, ps=16)
    ref = ragged_paged_attention_reference(*args)
    out = _ragged_attention_pallas(*args, None, None, D ** -0.5,
                                   interpret=pallas_interpret)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-2)


@pytest.mark.pallas_interpret
def test_ragged_pallas_int8_pages(pallas_interpret):
    Hq, Hkv, D = 8, 4, 128
    q, kp, vp, pt, q_start, q_len, kv_len = mixed_batch(
        jax.random.PRNGKey(11), Hq, Hkv, D, ps=16)
    kq, ksc = quantize_kv(kp)
    vq, vsc = quantize_kv(vp)
    ref = ragged_paged_attention_reference(q, kq, vq, pt, q_start, q_len,
                                           kv_len, k_scale=ksc,
                                           v_scale=vsc)
    out = _ragged_attention_pallas(q, kq, vq, pt, q_start, q_len, kv_len,
                                   ksc, vsc, D ** -0.5,
                                   interpret=pallas_interpret)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-2)
    # and the int8 path stays close to unquantized attention
    fp = ragged_paged_attention_reference(q, kp, vp, pt, q_start, q_len,
                                          kv_len)
    assert float(jnp.max(jnp.abs(ref - fp))) < 0.05


def test_ragged_single_row_degenerate():
    """R=1 batches — one decode row, then one prefill row — must work
    (the scheduler emits these when the engine idles down)."""
    key = jax.random.PRNGKey(5)
    Hq, Hkv, D, ps = 4, 2, 32, 8
    ks = jax.random.split(key, 3)
    kp = jax.random.normal(ks[1], (6, Hkv, ps, D), jnp.float32)
    vp = jax.random.normal(ks[2], (6, Hkv, ps, D), jnp.float32)
    pt = jnp.array([[1, 2, 3]], jnp.int32)
    q1 = jax.random.normal(ks[0], (1, Hq, D), jnp.float32)
    dec = ragged_paged_attention_reference(
        q1, kp, vp, pt, jnp.array([0]), jnp.array([1]), jnp.array([17]))
    want = dense_oracle(q1, kp, vp, pt, [0], [1], [17])
    np.testing.assert_allclose(np.asarray(dec), want, atol=1e-5)
    q5 = jax.random.normal(ks[0], (5, Hq, D), jnp.float32)
    pf = ragged_paged_attention_reference(
        q5, kp, vp, pt, jnp.array([0]), jnp.array([5]), jnp.array([13]))
    want = dense_oracle(q5, kp, vp, pt, [0], [5], [13])
    np.testing.assert_allclose(np.asarray(pf), want, atol=1e-5)


def test_ragged_all_decode_matches_decode_reference():
    """An all-decode ragged batch — one token a row against paged K/V,
    GQA, lengths that end mid-page — against the dense oracle."""
    key = jax.random.PRNGKey(9)
    B, Hq, Hkv, D, ps = 4, 8, 4, 64, 8
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, Hq, D), jnp.float32)
    kp = jax.random.normal(ks[1], (10, Hkv, ps, D), jnp.float32)
    vp = jax.random.normal(ks[2], (10, Hkv, ps, D), jnp.float32)
    pt = jnp.array([[1, 2, 3], [4, 5, 6], [7, 8, 9], [1, 4, 7]],
                   jnp.int32)
    sl = jnp.array([11, 24, 5, 17], jnp.int32)
    rag = ragged_paged_attention_reference(
        q, kp, vp, pt, jnp.arange(B, dtype=jnp.int32),
        jnp.ones(B, jnp.int32), sl, decode_rows=B, max_q_len=1)
    want = dense_oracle(q, kp, vp, pt, range(B), [1] * B, sl)
    np.testing.assert_allclose(np.asarray(rag), want, atol=1e-5)


def test_ragged_dispatcher_interpret_path():
    """The public entry point routes to the kernel (interpret=True on
    CPU) and matches the reference on a mixed batch."""
    args = mixed_batch(jax.random.PRNGKey(2), 8, 4, 128, ps=16)
    ref = ragged_paged_attention_reference(*args)
    out = ragged_paged_attention(*args, impl="kernel", interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-2)


# ---------------------------------------------------------------- int8 KV


def test_int8_kv_roundtrip_error_bound():
    """Per-(token, head) int8 KV quantization: round-trip error within
    the 1/127 step bound for unit-scale rows, including bf16 scales."""
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 4, 64), jnp.float32)
    q, s = quantize_kv(x)
    assert q.dtype == jnp.int8 and s.shape == (64, 4)
    back = dequantize_kv(q, s)
    err = float(jnp.max(jnp.abs(back - x)))
    # step/2 = amax/254 plus bf16 scale rounding (2^-8 relative)
    amax = float(jnp.max(jnp.abs(x)))
    assert err < amax * (1 / 254 + 2 ** -8) * 1.5, err


def test_write_ragged_kv_fp_and_int8():
    key = jax.random.PRNGKey(4)
    Hkv, ps, D, P, T = 2, 8, 16, 5, 10
    ks = jax.random.split(key, 2)
    k_t = jax.random.normal(ks[0], (T, Hkv, D), jnp.float32)
    v_t = jax.random.normal(ks[1], (T, Hkv, D), jnp.float32)
    page = jnp.array([1, 1, 1, 2, 2, 3, 3, 3, 4, 0], jnp.int32)
    slot = jnp.array([0, 1, 2, 5, 6, 0, 1, 7, 3, 0], jnp.int32)
    # fp path
    kp = jnp.zeros((P, Hkv, ps, D), jnp.float32)
    vp = jnp.zeros_like(kp)
    kp2, vp2, ksc, vsc = write_ragged_kv(kp, vp, k_t, v_t, page, slot)
    assert ksc is None and vsc is None
    for t in range(T):
        np.testing.assert_allclose(
            np.asarray(kp2[page[t], :, slot[t]]), np.asarray(k_t[t]))
        np.testing.assert_allclose(
            np.asarray(vp2[page[t], :, slot[t]]), np.asarray(v_t[t]))
    # int8 path: scatter quantized rows + scales, round-trip bounded
    kq = jnp.zeros((P, Hkv, ps, D), jnp.int8)
    vq = jnp.zeros_like(kq)
    from ray_tpu.ops.int8 import KV_SCALE_DTYPE
    ks8 = jnp.zeros((P, Hkv, ps), KV_SCALE_DTYPE)
    vs8 = jnp.zeros_like(ks8)
    kq2, vq2, ks2, vs2 = write_ragged_kv(kq, vq, k_t, v_t, page, slot,
                                         ks8, vs8)
    assert kq2.dtype == jnp.int8 and ks2.dtype == KV_SCALE_DTYPE
    for t in range(T - 1):   # last token aliases scratch page 0
        got = dequantize_kv(kq2[page[t], :, slot[t]],
                            ks2[page[t], :, slot[t]])
        np.testing.assert_allclose(np.asarray(got), np.asarray(k_t[t]),
                                   atol=2e-2)
        got = dequantize_kv(vq2[page[t], :, slot[t]],
                            vs2[page[t], :, slot[t]])
        np.testing.assert_allclose(np.asarray(got), np.asarray(v_t[t]),
                                   atol=2e-2)


# --------------------------------------------------------------------------
# The in-place write: _kv_write_pallas (interpreter) against .at[].set
# --------------------------------------------------------------------------

def _write_batch(ps, batch, rng):
    """A ragged batch's write descriptors over rows that own their pages.
    ``decode``: 4 one-token rows, one of them inactive. ``mixed``: the
    same and two chunk rows, one that starts mid-page on a cached prefix
    and crosses two page boundaries, one page-aligned that fills whole
    pages. Tokens no row owns point at the scratch page. Returns (T,
    token_page, token_slot, q_start, q_len, max_q_len, decode_rows)."""
    Rd, pad = 4, 3
    q_len = [1, 0, 1, 1]
    first = [ps + 3, 0, 2 * ps - 1, 0]          # position of the row's token
    if batch == "mixed":
        q_len += [2 * ps + 5, 2 * ps]
        first += [ps + ps // 2 + 1, 0]          # mid-page on 1.5 pages cached
    R = len(q_len)
    max_q_len = max(q_len)
    q_start = np.concatenate([np.arange(Rd), Rd + np.cumsum(
        [0] + q_len[Rd:-1])]).astype(np.int32)[:R]
    T = int(q_start[-1] + q_len[-1]) + pad
    pages = iter(rng.permutation(np.arange(1, 40)))
    token_page = np.zeros(T, np.int32)           # scratch unless owned
    token_slot = rng.integers(0, ps, T).astype(np.int32)
    for r in range(R):
        pos = first[r] + np.arange(q_len[r])
        table = np.array([next(pages) for _ in range(6)])
        token_page[q_start[r]:q_start[r] + q_len[r]] = table[pos // ps]
        token_slot[q_start[r]:q_start[r] + q_len[r]] = pos % ps
    return (T, token_page, token_slot, q_start, np.asarray(q_len, np.int32),
            max_q_len, Rd)


@pytest.mark.pallas_interpret
@pytest.mark.parametrize("heads", ["all", "tp2-shard"])
@pytest.mark.parametrize("batch", ["decode", "mixed"])
@pytest.mark.parametrize("ps", [16, 32])
@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_kv_write_in_place_matches_scatter(kv, ps, batch, heads):
    """The step programs' write (whole pages by DMA, aliased in to out)
    puts the same values into the same slots as ``.at[layer, page, :,
    slot].set``: both pool dtypes, both page sizes, one-token rows alone
    and with chunks (one starting mid-page), an inactive row, padding
    tokens on the scratch page, a layer index other than 0 with every
    other layer left bit-identical, and one tp=2 shard's half of the
    heads (inside shard_map the kernel sees the local heads only)."""
    rng = np.random.default_rng(ps + len(batch))
    L, P, Hkv, D, layer = 3, 40, 4, 128, 1
    T, token_page, token_slot, q_start, q_len, max_q_len, Rd = \
        _write_batch(ps, batch, rng)
    dt = jnp.int8 if kv == "int8" else jnp.bfloat16
    shape = (L, P, Hkv, ps, D)
    kp, vp = (jnp.asarray(rng.integers(-100, 100, shape), dt)
              for _ in range(2))
    scales = [None, None] if kv == "bf16" else \
        [jnp.asarray(rng.uniform(0.5, 1.0, shape[:-1]), jnp.bfloat16)
         for _ in range(2)]
    k_t, v_t = (jnp.asarray(rng.normal(size=(T, Hkv, D)), jnp.bfloat16)
                for _ in range(2))
    if heads == "tp2-shard":                     # the second shard's heads
        kp, vp, k_t, v_t = (a[..., Hkv // 2:, :, :] if a.ndim == 5
                            else a[:, Hkv // 2:] for a in (kp, vp, k_t, v_t))
        scales = [s if s is None else s[:, :, Hkv // 2:] for s in scales]
    args = (kp, vp, k_t, v_t, jnp.asarray(token_page),
            jnp.asarray(token_slot), *scales)
    want = write_ragged_kv(*args, layer=layer, impl="reference")
    got = write_ragged_kv(
        *args, layer=layer, q_start=jnp.asarray(q_start),
        q_len=jnp.asarray(q_len), max_q_len=max_q_len, decode_rows=Rd,
        impl="kernel", interpret=True)
    assert (got[2] is None) == (kv == "bf16")
    for name, g, w, before in zip("k v k_scale v_scale".split(), got, want,
                                  (kp, vp, *scales)):
        if g is None:
            continue
        g, w, before = (np.asarray(a.astype(jnp.float32))
                        for a in (g, w, before))
        # the scratch page is garbage by contract; everything else equal
        np.testing.assert_array_equal(g[:, 1:], w[:, 1:], err_msg=name)
        others = [i for i in range(L) if i != layer]
        np.testing.assert_array_equal(g[others], before[others],
                                      err_msg=f"{name}: other layers")
        assert not np.array_equal(g[layer, 1:], before[layer, 1:]), name


@pytest.mark.pallas_interpret
@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_step_programs_through_the_kernels_match_reference(monkeypatch,
                                                           kv_dtype):
    """Both step programs with the kernels (write and attention, through
    the interpreter) under the layer scan that carries the stacked pool,
    against the reference branch of the same programs: the same tokens,
    and the same pool in every layer off the scratch page."""
    from jax.experimental import pallas as pl
    from _chunk_rows import as_descriptor
    from ray_tpu.llm import model as M
    from ray_tpu.llm.cache import make_kv_cache
    from ray_tpu.models.llama import LlamaConfig, init_params
    real = pl.pallas_call
    monkeypatch.setattr(pl, "pallas_call", lambda *a, **kw: real(
        *a, **{**kw, "interpret": True}))
    cfg = LlamaConfig(vocab_size=64, dim=64, n_layers=3, n_heads=4,
                      n_kv_heads=2, ffn_dim=96, dtype=jnp.float32,
                      param_dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    ps, B, C = 8, 2, 12
    rng = np.random.default_rng(3)

    def pool():
        kv = make_kv_cache(cfg, 12, ps, kv_dtype=kv_dtype)
        return {n: jnp.asarray(rng.integers(-3, 4, a.shape), a.dtype)
                if n in ("k", "v") else jnp.ones_like(a)
                for n, a in kv.items()}

    # two decode rows at positions 9 and 3, one chunk of 12 tokens from
    # position 5 (mid-page) of a third sequence; two padding tokens
    T = B + C + 2
    table = np.array([[1, 2, 0], [3, 0, 0], [4, 5, 6]], np.int32)
    pos = np.concatenate([[9, 3], 5 + np.arange(C), [0, 0]]).astype(np.int32)
    row_of = np.concatenate([[0, 1], np.full(C, 2), [0, 0]])
    page = table[row_of, pos // ps]
    page[-2:] = 0
    mixed, step_layout = as_descriptor(
        tokens=rng.integers(0, 64, T), token_pos=pos, token_page=page,
        token_slot=pos % ps, page_table=table, q_start=[0, 1, B],
        q_len=[1, 1, C], kv_len=[10, 4, 5 + C])
    rng_state = rng.bit_generator.state
    outs = {}
    for impl in ("reference", "kernel"):
        rng.bit_generator.state = rng_state         # the same pool twice
        nxt, kv, _ = M.ragged_step(params, mixed, pool(),
                                layouts=(step_layout,), cfg=cfg,
                                paged_impl=impl, max_q_len=C, decode_rows=B)
        decode, loop_layout = as_descriptor(
            tokens=nxt[:B], positions=[10, 4], seq_lens=[11, 5],
            page_table=table[:B])
        toks, kv, _, _, _ = M.ragged_decode_loop(
            params, decode, kv, layouts=(loop_layout,), num_steps=3,
            cfg=cfg, paged_impl=impl)
        outs[impl] = (np.asarray(nxt), np.asarray(toks),
                      {n: np.asarray(a.astype(jnp.float32))
                       for n, a in kv.items()})
    (nxt_r, toks_r, kv_r), (nxt_k, toks_k, kv_k) = \
        outs["reference"], outs["kernel"]
    np.testing.assert_array_equal(nxt_k, nxt_r)
    np.testing.assert_array_equal(toks_k, toks_r)
    for n in kv_r:
        np.testing.assert_allclose(kv_k[n][:, 1:], kv_r[n][:, 1:],
                                   rtol=1e-5, atol=1e-5, err_msg=n)


# --------------------------------------------------------------------------
# Two chunk rows of ONE sequence in one call (llm/engine.py:_deal_chunk_rows)
# --------------------------------------------------------------------------

@pytest.mark.pallas_interpret
@pytest.mark.parametrize("pool", ["per_head", "latent"])
def test_two_rows_of_one_sequence_in_one_call_equal_two_calls(pool):
    """A sequence with one page cached computes its next two chunks as two
    rows of ONE call (write, then attention, both kernels through the
    interpreter, on the stacked pool), the rows' boundary on a page's
    edge and the same page table in both: the second row reads the first
    row's tokens from the pool. Equal to the two chunks a call apart, in
    every written page and in every token's result, beside a decode row
    of another sequence."""
    ps, C, L, P, layer = 16, 32, 2, 12, 1
    Hq, Hkv, D, vw = (4, 2, 128, None) if pool == "per_head" \
        else (4, 1, 256, 128)
    rng = np.random.default_rng(len(pool))
    T = 1 + 2 * C
    table = np.zeros((3, 6), np.int32)
    table[0, 0] = 9                              # the decode row's sequence
    table[1:] = [3, 7, 1, 5, 8, 2]               # one sequence, both rows
    pos = np.concatenate([[5], ps + np.arange(2 * C)])
    row_of = np.concatenate([[0], np.full(C, 1), np.full(C, 2)])
    page, slot = table[row_of, pos // ps], pos % ps
    q_start = jnp.asarray([0, 1, 1 + C], jnp.int32)
    kv_len = np.array([6, ps + C, ps + 2 * C], np.int32)
    q = jnp.asarray(rng.normal(size=(T, Hq, D)), jnp.float32)
    k_t = jnp.asarray(rng.normal(size=(T, Hkv, D)), jnp.float32)
    v_t = None if vw else jnp.asarray(rng.normal(size=(T, Hkv, D)),
                                      jnp.float32)
    kp = jnp.asarray(rng.normal(size=(L, P, Hkv, ps, D)), jnp.float32)
    vp = None if vw else jnp.asarray(rng.normal(size=kp.shape), jnp.float32)

    def call(kp, vp, q_len):
        """One step's write and attention for the rows ``q_len`` leaves
        alive; the tokens of the others go to the scratch page."""
        live = np.asarray(q_len)[row_of] > 0
        hints = dict(layer=jnp.asarray([layer], jnp.int32), max_q_len=C,
                     decode_rows=1, impl="kernel", interpret=True)
        q_len = jnp.asarray(q_len, jnp.int32)
        kp, vp, _, _ = write_ragged_kv(
            kp, vp, k_t, v_t, jnp.asarray(np.where(live, page, 0)),
            jnp.asarray(slot), q_start=q_start, q_len=q_len, **hints)
        out = ragged_paged_attention(
            q, kp, vp, jnp.asarray(table), q_start, q_len,
            jnp.asarray(np.where(np.asarray(q_len) > 0, kv_len, 0)),
            v_width=vw, **hints)
        return kp, vp, np.asarray(out), live

    kp1, vp1, out1, _ = call(kp, vp, [1, C, C])
    kp2, vp2, first, a = call(kp, vp, [1, C, 0])
    kp2, vp2, second, b = call(kp2, vp2, [0, 0, C])
    np.testing.assert_array_equal(np.asarray(kp1)[:, 1:],
                                  np.asarray(kp2)[:, 1:])
    if vp is not None:
        np.testing.assert_array_equal(np.asarray(vp1)[:, 1:],
                                      np.asarray(vp2)[:, 1:])
    assert a.sum() == 1 + C and b.sum() == C and not (a & b).any()
    np.testing.assert_allclose(out1[a], first[a], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(out1[b], second[b], rtol=1e-6, atol=1e-6)
    # and the second row did read the first: without the first row's
    # tokens in the pool its results are others
    _, _, blind, _ = call(kp, vp, [0, 0, C])
    assert np.abs(blind[b] - out1[b]).max() > 1e-2


@pytest.mark.parametrize("state", [False, True])
def test_the_deal_never_joins_two_rows_inside_a_page(state):
    """llm/engine.py:_deal_chunk_rows over random queues, starts, budgets
    and row counts: the first rows are the one-row-a-sequence deal
    unchanged (FIFO, so nobody waits longer for a row than before); a
    further row of a sequence starts where its last one ended and only on
    a page's edge (the write kernel's units: two on one page in one call
    would race); rows, chunk and budget are kept; with recurrent state
    (``state``) nothing is joined at all."""
    from ray_tpu.llm import InferenceEngine
    from ray_tpu.llm.cache import SequenceState
    from ray_tpu.models.llama import LlamaConfig
    eng = InferenceEngine(LlamaConfig.tiny(n_layers=1), page_size=16,
                          total_pages=8, max_batch=2, max_seq_len=64)
    eng._has_state = state
    rng = np.random.default_rng(int(state))
    joined = 0
    for _ in range(400):
        ps = eng.page_size = int(rng.choice([4, 16]))
        eng.prefill_chunk = ps * int(rng.integers(1, 5)) \
            if rng.random() < 0.8 else int(rng.integers(1, 70))
        eng.prefill_rows = int(rng.integers(1, 6))
        eng.step_token_budget = int(rng.choice(
            [0, rng.integers(1, 40), ps * rng.integers(1, 12)]))
        eng._chunking = []
        for i in range(int(rng.integers(0, 5))):
            seq = SequenceState(f"r{i}", [0] * int(rng.integers(1, 200)), 4)
            # where earlier steps left it: mostly a page's edge (a prefix
            # hit, whole chunks), sometimes anywhere (a cut by the budget)
            seq.num_computed = int(rng.integers(0, len(seq.prompt)))
            if rng.random() < 0.7:
                seq.num_computed -= seq.num_computed % ps
            eng._chunking.append(seq)
        rows = eng._deal_chunk_rows()
        budget = eng.step_token_budget or 1 << 30
        first, left = [], budget                 # the deal as it was
        for seq in eng._chunking[:eng.prefill_rows]:
            n = min(eng.prefill_chunk, len(seq.prompt) - seq.num_computed,
                    left)
            if n <= 0:
                break
            first.append((seq, seq.num_computed, n))
            left -= n
        assert rows[:len(first)] == first
        assert len(rows) <= eng.prefill_rows
        assert sum(n for _, _, n in rows) <= budget
        ends = {id(seq): start + n for seq, start, n in first}
        for seq, start, n in rows[len(first):]:
            assert not state
            assert start == ends[id(seq)] and start % ps == 0
            assert 0 < n <= eng.prefill_chunk
            assert start + n <= len(seq.prompt)
            ends[id(seq)] = start + n
            joined += 1
        if not state and len(rows) < eng.prefill_rows \
                and sum(n for _, _, n in rows) < budget:
            # rows and budget to spare: every sequence with a row is at
            # its prompt's end or inside a page
            assert all(end == len(seq.prompt) or end % ps
                       for seq in eng._chunking[:len(first)]
                       for end in [ends[id(seq)]])
    assert (joined == 0) == state
