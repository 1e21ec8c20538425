"""The ragged paged-attention kernel (ops/paged_attention.py) at the window
block's FULL layers (MiMo-V2-Flash's shapes), in interpret mode against
the reference: the one-token tile and the chunk tile at lengths around
their blocks' edges. (tests/test_ragged.py has the per-head kernel.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.paged_attention import (ragged_paged_attention,
                                         ragged_paged_attention_reference)


# heads, K rows of 256 lanes and V rows of 128, pages of 64, a table that
# reaches 8192 slots; small pool
_FULL_HKV, _FULL_QPK, _FULL_DK, _FULL_DV, _FULL_PS, _FULL_MP = \
    4, 16, 256, 128, 64, 128


def _full_tiling(n_tokens):
    from ray_tpu.ops.paged_attention import _ragged_tiling
    return _ragged_tiling(n_tokens, _FULL_QPK, _FULL_PS, _FULL_MP,
                          kv_heads=_FULL_HKV, kv_width=_FULL_DK + _FULL_DV)


def _full_layer_batch(lens, q_lens, dtype):
    """Rows of ``lens`` cached slots (their last ``q_lens`` the query
    tokens; an empty row still owns one slot of q) over a stacked pool of
    two layers whose pages are dealt at random; table entries past a row's
    length name a page of NaNs. -> (q, pool and descriptors for
    ragged_paged_attention, the same with the NaNs zeroed for the gather
    path)."""
    lens, q_lens = np.asarray(lens), np.asarray(q_lens)
    ps, mp = _FULL_PS, _FULL_MP
    need = -(-lens // ps)
    P = int(need.sum()) + 2
    pt = np.full((len(lens), mp), P - 1, np.int32)      # the page of NaNs
    perm = 1 + np.random.default_rng(int(lens.sum())).permutation(P - 2)
    at = 0
    for r, n in enumerate(need):
        pt[r, :n] = perm[at:at + n]
        at += n
    ks = jax.random.split(jax.random.PRNGKey(int(lens.sum())), 3)
    k = jax.random.normal(ks[0], (2, P, _FULL_HKV, ps, _FULL_DK), jnp.float32)
    v = jax.random.normal(ks[1], (2, P, _FULL_HKV, ps, _FULL_DV), jnp.float32)
    k, v = (a.at[:, P - 1].set(jnp.nan).astype(dtype) for a in (k, v))
    spans = np.maximum(q_lens, 1)
    q = jax.random.normal(ks[2], (int(spans.sum()), _FULL_HKV * _FULL_QPK,
                                  _FULL_DK), jnp.float32).astype(dtype)
    q_start = np.cumsum(spans) - spans
    rows = (jnp.asarray(pt), jnp.asarray(q_start, jnp.int32),
            jnp.asarray(q_lens, jnp.int32), jnp.asarray(lens, jnp.int32))
    zeroed = tuple(jnp.nan_to_num(a.astype(jnp.float32)) for a in (k, v))
    return q, (k, v) + rows, zeroed + rows


# a one-token case's length = blocks * (the tile's block) + slots
_FULL_ONE_TOKEN = {
    "1": (0, 1), "63": (0, 63), "64": (0, 64), "65": (0, 65),
    "block-1": (1, -1), "block": (1, 0), "block+1": (1, 1),
    "2*block+5": (2, 5), "bf16:block+1": (1, 1), "bf16:3*block-70": (3, -70)}


@pytest.mark.pallas_interpret
@pytest.mark.parametrize("case", list(_FULL_ONE_TOKEN))
def test_full_layer_one_token_tile_matches_reference(case):
    """Three decode rows (the case's length, an EMPTY row, a row whose last
    page is partial) through the per-head one-token tile at the window
    block's full-layer shape, in interpret mode against the gather path:
    lengths around a page of 64 and around the tile's block, whatever
    _ragged_tiling gives it."""
    dtype = jnp.bfloat16 if case.startswith("bf16:") else jnp.float32
    blocks, slots = _FULL_ONE_TOKEN[case]
    bk = _FULL_PS * _full_tiling(1)[3]
    lens = np.array([blocks * bk + slots, 0, 150])
    q, args, zeroed = _full_layer_batch(lens, lens > 0, dtype)
    kw = dict(sm_scale=192 ** -0.5, decode_rows=3, layer=1)
    want = ragged_paged_attention_reference(q.astype(jnp.float32), *zeroed,
                                            **kw)
    got = ragged_paged_attention(q, *args, interpret=True, **kw)
    assert got.shape == (3, 64, _FULL_DV) and got.dtype == dtype
    got = np.asarray(got, np.float32)
    assert np.all(got[1] == 0.0)                    # the empty row
    np.testing.assert_allclose(got, np.asarray(want),
                               atol=1e-4 if dtype == jnp.float32 else 3e-2)


# a chunk case: (blocks, slots) of the prefix the chunk's 70 tokens follow
_FULL_CHUNK = {"0": (0, 0), "block-70": (1, -70), "block-30": (1, -30),
               "block+1": (1, 1), "bf16:2*block-5": (2, -5)}


@pytest.mark.pallas_interpret
@pytest.mark.parametrize("case", list(_FULL_CHUNK))
def test_full_layer_chunk_tile_matches_reference(case):
    """Two chunk rows of 70 and 9 tokens (two tiles of 64 a row: a whole
    one and a partial one, and a tile past the second row's length) behind
    one decode row, at the window block's full-layer shape, whose table
    reaches far enough for the long chunk block: prefixes that end a tile
    short of the block's edge, inside it and past it, so a block boundary
    falls before, inside and after a tile's own (masked) positions."""
    dtype = jnp.bfloat16 if case.startswith("bf16:") else jnp.float32
    bq, _, mrows, bkp = _full_tiling(70)
    assert (bq, mrows, bkp * _FULL_PS) == (64, 1024, 1024)
    blocks, slots = _FULL_CHUNK[case]
    prefix = blocks * bkp * _FULL_PS + slots
    q_lens = np.array([1, 70, 9])
    lens = np.array([200, prefix + 70, 130 + 9])
    q, args, zeroed = _full_layer_batch(lens, q_lens, dtype)
    kw = dict(sm_scale=192 ** -0.5, decode_rows=1, max_q_len=70, layer=0)
    want = ragged_paged_attention_reference(q.astype(jnp.float32), *zeroed,
                                            **kw)
    got = ragged_paged_attention(q, *args, interpret=True, **kw)
    assert got.shape == (80, 64, _FULL_DV) and got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               atol=1e-4 if dtype == jnp.float32 else 3e-2)
