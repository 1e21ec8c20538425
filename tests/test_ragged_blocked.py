"""What blocking can break in the ragged paged-attention kernel
(ops/paged_attention.py), in interpret mode against the reference and the
dense oracle of tests/_ragged.py, and the tilings the benchmark's shapes
take. (tests/test_ragged.py has the kernel on a small batch.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _ragged import dense_oracle, unowned
from ray_tpu.ops.int8 import quantize_kv
from ray_tpu.ops.paged_attention import (ragged_paged_attention,
                                         ragged_paged_attention_reference)


# ------------------------------------------- what blocking can break
#
# name -> (Hq, Hkv, T, decode_rows, max_q_len, [(q_start, q_len, kv_len)]).
# page_size 16, so prefill tiles are min(128, max_q_len rounded up to 8)
# tokens against blocks of 256 kv slots and decode tiles are one token
# against blocks of 384 (the whole 24-page table; 512 with a wider one).
_SMALL_ROWS = [(0, 1, 50), (1, 1, 7), (2, 0, 0), (3, 1, 130), (4, 12, 44),
               (16, 5, 5)]
_BLOCKED_CASES = {
    # a chunk that starts mid-tile in q and is no multiple of the tile
    "chunk_mid_tile_ragged_len": (
        8, 2, 176, 2, 160, [(0, 1, 40), (1, 1, 300), (5, 150, 150)]),
    # cached prefixes that end mid-page and mid-block (70, 200 tokens)
    "prefix_mid_page_mid_block": (
        8, 2, 192, 0, 152, [(2, 150, 220), (152, 30, 230)]),
    # kv_len exactly at a block edge, and one past it
    "kv_len_at_block_edge": (
        8, 2, 296, 4, 136,
        [(0, 1, 256), (1, 1, 257), (2, 1, 128), (3, 1, 129),
         (4, 128, 128), (132, 129, 129), (261, 28, 128)]),
    "empty_rows_between_live": (
        8, 2, 64, 4, 24,
        [(0, 1, 33), (0, 0, 0), (1, 1, 18), (0, 0, 0),
         (0, 0, 0), (4, 20, 20), (0, 0, 0), (24, 9, 50)]),
    # gaps between rows and a long tail that no row owns
    "padding_tokens_exact_zeros": (
        8, 2, 96, 1, 16, [(3, 1, 20), (10, 7, 7), (30, 16, 40)]),
    # the decode loop's shape, with free batch slots
    "decode_rows_with_empty_slots": (
        8, 2, 8, 8, 1,
        [(0, 1, 17), (1, 0, 0), (2, 1, 256), (3, 0, 0), (4, 1, 1),
         (5, 1, 300), (6, 0, 0), (7, 1, 96)]),
    # Mistral-7B's head shapes and its tp=4 shard's, at a small T
    "mistral_heads_32_8": (32, 8, 24, 4, 12, _SMALL_ROWS),
    "tp4_shard_heads_8_2": (8, 2, 24, 4, 12, _SMALL_ROWS),
}


def _blocked_batch(name, kv, poison_unused_pages=False):
    """bf16 q and a bf16 or int8 pool for one of _BLOCKED_CASES; every
    row gets its own pages. ``poison_unused_pages`` points the table
    entries past a row's length at a page of NaNs."""
    Hq, Hkv, T, decode_rows, max_q_len, rows = _BLOCKED_CASES[name]
    D, ps, max_pages, P = 128, 16, 24, 8 * 24 + 2
    ks = jax.random.split(jax.random.PRNGKey(len(name)), 3)
    q = jax.random.normal(ks[0], (T, Hq, D), jnp.float32)
    kp = jax.random.normal(ks[1], (P, Hkv, ps, D), jnp.float32)
    vp = jax.random.normal(ks[2], (P, Hkv, ps, D), jnp.float32)
    pt = 1 + np.random.default_rng(len(name)).permutation(
        len(rows) * max_pages).reshape(len(rows), max_pages)
    if poison_unused_pages:
        kp, vp = kp.at[P - 1].set(jnp.nan), vp.at[P - 1].set(jnp.nan)
        for r, (_, _, kv_len) in enumerate(rows):
            pt[r, -(-kv_len // ps):] = P - 1
    q_start, q_len, kv_len = (jnp.array(c, jnp.int32) for c in zip(*rows))
    q = q.astype(jnp.bfloat16)
    if kv == "int8":
        (kp, ksc), (vp, vsc) = quantize_kv(kp), quantize_kv(vp)
    else:
        kp, vp, ksc, vsc = (kp.astype(jnp.bfloat16),
                            vp.astype(jnp.bfloat16), None, None)
    args = (q, kp, vp, jnp.asarray(pt, jnp.int32), q_start, q_len, kv_len)
    return args, dict(k_scale=ksc, v_scale=vsc, max_q_len=max_q_len,
                      decode_rows=decode_rows)


@pytest.mark.pallas_interpret
@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("name", list(_BLOCKED_CASES))
def test_ragged_blocked_kernel_matches_reference(name, kv):
    args, kw = _blocked_batch(name, kv)
    ref = ragged_paged_attention_reference(
        args[0].astype(jnp.float32), *args[1:], **kw)
    out = ragged_paged_attention(*args, **kw, impl="kernel", interpret=True)
    assert out.dtype == args[0].dtype and out.shape == args[0].shape
    # bf16 operands and bf16 probabilities against an fp32 reference
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), atol=3e-2)
    assert np.all(np.asarray(out, np.float32)[unowned(args)] == 0.0)


@pytest.mark.pallas_interpret
@pytest.mark.parametrize("name", ["chunk_mid_tile_ragged_len",
                                  "decode_rows_with_empty_slots"])
def test_ragged_blocked_kernel_skips_pages_past_length(name):
    """Table entries past a row's length may name anything: here a page
    of NaNs, which one copied page (p = 0 times NaN) would leak."""
    args, kw = _blocked_batch(name, "bf16", poison_unused_pages=True)
    want = dense_oracle(*args)
    out = ragged_paged_attention(*args, **kw, impl="kernel", interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32), want,
                               atol=3e-2)


def test_ragged_tiling_clamps_to_small_shapes():
    """Tile sizes follow the shapes: MXU-sized for the engine's chunks,
    clamped for the tiny chunks and page tables tier-1 runs."""
    from ray_tpu.ops.paged_attention import _ragged_tiling
    # (n_tokens, q_per_kv, page_size, max_pages) -> (bq, nq, mrows, bkp)
    assert _ragged_tiling(512, 4, 16, 144) == (128, 4, 512, 16)
    assert _ragged_tiling(1, 4, 16, 144) == (1, 1, 16, 32)
    assert _ragged_tiling(4, 1, 8, 4) == (8, 1, 16, 4)
    assert _ragged_tiling(130, 2, 32, 3) == (128, 2, 256, 3)


# ((n_tokens, q_per_kv, page_size, max_pages), the pool's other numbers) at
# the shapes the benchmark's serve cells run: what _ragged_tiling gave them
# before the latent one-token tile had a rule of its own (PR 33's tree),
# that tile's value, and since PR 48 the chunk tile of a table that reaches
# 8192 slots or more over slot rows narrower than the latent pool's (the
# window block's full layers')
_BENCHMARK_TILINGS = {
    "mistral_one_token": (((1, 4, 16, 144), {}), (1, 1, 16, 32)),
    "mistral_chunk": (((512, 4, 16, 144), {}), (128, 4, 512, 16)),
    "olmoe_one_token": (((1, 1, 16, 96), {}), (1, 1, 16, 32)),
    "olmoe_chunk": (((512, 1, 16, 96), {}), (128, 4, 128, 16)),
    "lfm2_one_token": (((1, 4, 16, 192), {}), (1, 1, 16, 32)),
    "lfm2_chunk": (((512, 4, 16, 192), {}), (128, 4, 512, 16)),
    # a slot row of 640 + 512 values: 256 slots hold a block's worth (1024
    # bought 5 %, PR 48: not kept), though the table reaches 9728 slots
    "latent_chunk": (((512, 32, 16, 608),
                      dict(latent_row_bytes=1280, kv_width=1152)),
                     (32, 16, 1024, 16)),
    # 2048 slots of 1280 B a block (was 512: (1, 1, 32, 32))
    "latent_one_token": (((1, 32, 16, 608), dict(latent_row_bytes=1280)),
                         (1, 1, 32, 128)),
    # the same bytes a block where a slot is wider, and never more pages
    # than the table has
    "latent_one_token_fp32": (((1, 32, 16, 608),
                               dict(latent_row_bytes=2560)), (1, 1, 32, 64)),
    "latent_one_token_short_table": (((1, 32, 16, 40),
                                      dict(latent_row_bytes=1280)),
                                     (1, 1, 32, 40)),
    # the window block (MiMo-V2-Flash), pages of 64. Full layers: 4 KV
    # heads of 16 query heads, a table of 19456 slots: the one-token tile
    # keeps its 512 slots (PR 48's study: no block, walk or order of the
    # heads' chains beat it), the chunk tile takes 1024 (was 256:
    # (64, 8, 1024, 4))
    "mimo_full_one_token": (((1, 16, 64, 304),
                             dict(kv_heads=4, kv_width=384)), (1, 1, 16, 8)),
    "mimo_full_chunk": (((512, 16, 64, 304), dict(kv_heads=4, kv_width=384)),
                        (64, 8, 1024, 16)),
    # window layers: 8 KV heads of 8, ONE block of 256 slots a tile
    "mimo_window_one_token": (((1, 8, 64, 8),
                               dict(kv_heads=8, kv_width=384, window=128)),
                              (1, 1, 16, 4)),
    "mimo_window_chunk": (((512, 8, 64, 8),
                           dict(kv_heads=8, kv_width=384, window=128)),
                          (64, 8, 512, 4)),
    # a long table alone does not grow a block whose scores would not fit:
    # 16 KV heads of 8 query heads keep 256 slots
    "long_table_many_heads_chunk": (((512, 8, 16, 1024),
                                     dict(kv_heads=16, kv_width=256)),
                                    (128, 4, 1024, 16)),
    # ... and Mistral's heads under a long table take 1024 (2048 slots of
    # 256 values would meet the latent block's, their scores would not fit)
    "long_table_mistral_chunk": (((512, 4, 16, 1024),
                                  dict(kv_heads=8, kv_width=256)),
                                 (128, 4, 512, 64)),
}


@pytest.mark.parametrize("name", list(_BENCHMARK_TILINGS))
def test_ragged_tiling_at_the_benchmarks_shapes(name):
    """(bq, nq, mrows, bkp) pinned: the per-head forms of the short-context
    cells keep their tiling whatever the latent one-token tile and the
    long tables' chunk tiles take."""
    from ray_tpu.ops.paged_attention import _ragged_tiling
    (args, kw), want = _BENCHMARK_TILINGS[name]
    assert _ragged_tiling(*args, **kw) == want
    if "latent_row_bytes" not in kw:
        return
