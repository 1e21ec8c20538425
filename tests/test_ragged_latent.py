"""The latent one-token tile of the ragged paged-attention kernel
(ops/paged_attention.py) at Kanana-2's head shape, in interpret mode
against the reference, at lengths around every edge of its blocks and its
walk. (tests/test_ragged.py has the per-head kernel.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.paged_attention import (ragged_paged_attention,
                                         ragged_paged_attention_reference)




# the latent one-token tile at Kanana-2's head shape (32 query heads on
# ONE kv head, rows of 640 lanes, value the leading 512), small pool
_LATENT_W, _LATENT_VW, _LATENT_HQ, _LATENT_PS = 640, 512, 32, 16


def _latent_block(dtype) -> int:
    """Slots of the one-token tile's KV block for a pool of ``dtype``."""
    from ray_tpu.ops.paged_attention import _ragged_tiling
    return _LATENT_PS * _ragged_tiling(
        1, _LATENT_HQ, _LATENT_PS, 1 << 20,
        _LATENT_W * jnp.dtype(dtype).itemsize)[3]


# a case's length = blocks * (the tile's block) + walks * (16 pages' slots
# times the walk's unroll) + slots
_LATENT_LENGTHS = {
    "1": (0, 0, 1), "15": (0, 0, 15), "16": (0, 0, 16), "17": (0, 0, 17),
    "block-1": (1, 0, -1), "block": (1, 0, 0), "block+1": (1, 0, 1),
    "2*block+5": (2, 0, 5), "walk-1": (0, 1, -1), "walk+1": (0, 1, 1),
    "bf16:block+1": (1, 0, 1), "bf16:2*block+5": (2, 0, 5)}


@pytest.mark.pallas_interpret
@pytest.mark.parametrize("case", list(_LATENT_LENGTHS))
def test_latent_one_token_tile_matches_reference(case):
    """Three decode rows (the case's length, an EMPTY row, a row whose
    last page is partial) through the kernel's latent one-token tile in
    interpret mode against the gather path: lengths around a page, around
    the tile's block (a wrong block boundary or a wrong count of the
    unrolled page walk shows here, not in the benchmark's ``correct``),
    around a whole turn of the walk's unrolled loop. The table's entries
    past a row's length name a page of NaNs."""
    from ray_tpu.ops.paged_attention import _LATENT_WALK_UNROLL
    dtype = jnp.bfloat16 if case.startswith("bf16:") else jnp.float32
    blocks, walks, slots = _LATENT_LENGTHS[case]
    kv_len = blocks * _latent_block(dtype) \
        + walks * _LATENT_WALK_UNROLL * _LATENT_PS + slots
    ps, W = _LATENT_PS, _LATENT_W
    lens = np.array([kv_len, 0, 21])
    need = -(-lens // ps)
    mp = max(int(need.max()), 2) + 1
    P = int(need.sum()) + 2
    pt = np.full((3, mp), P - 1, np.int32)          # the page of NaNs
    perm = 1 + np.random.default_rng(kv_len).permutation(P - 2)
    pt[0, :need[0]], pt[2, :need[2]] = perm[:need[0]], perm[need[0]:]
    ks = jax.random.split(jax.random.PRNGKey(kv_len), 2)
    pool = jax.random.normal(ks[0], (2, P, 1, ps, W), jnp.float32)
    pool = pool.at[:, P - 1].set(jnp.nan).astype(dtype)
    q = jax.random.normal(ks[1], (3, _LATENT_HQ, W), jnp.float32)
    args = (pool, None, jnp.asarray(pt), jnp.arange(3, dtype=jnp.int32),
            jnp.asarray(lens > 0, jnp.int32), jnp.asarray(lens, jnp.int32))
    kw = dict(sm_scale=192 ** -0.5, decode_rows=3, layer=1,
              v_width=_LATENT_VW)
    # the reference reads the whole table: give it zeros where the NaNs are
    want = ragged_paged_attention_reference(
        q, jnp.nan_to_num(pool.astype(jnp.float32)), *args[1:], **kw)
    got = ragged_paged_attention(q.astype(dtype), *args, interpret=True, **kw)
    assert got.shape == (3, _LATENT_HQ, _LATENT_VW) and got.dtype == dtype
    got = np.asarray(got, np.float32)
    assert np.all(got[1] == 0.0)                    # the empty row
    # fp32 throughout; bf16 operands and probabilities as the chip runs it
    np.testing.assert_allclose(got, np.asarray(want),
                               atol=1e-4 if dtype == jnp.float32 else 3e-2)
