"""Whole step programs compiled for a described TPU v5e (what and why:
tests/test_tpu_compile.py) at the widths and pools of two configurations
that keep state a slot beside their pages: lfm2 (a conv's inputs) and
brumby (a retention state and no pages). granite, the third:
tests/test_tpu_compile_granite.py.
"""

import jax
import pytest

from _tpu_compile import (bytes_of, chip, compile_step_program,  # noqa: F401
                          one_row_mixed_step_cases)


def _lfm2_cfg(n_layers=6):
    """lfm2-24b-a2b-serve-1chip's widths; 6 layers = the two leading dense
    conv layers and ONE period (attn conv conv conv) of its ten."""
    from ray_tpu.models.llama import LlamaConfig
    pattern = ["conv", "conv"] + ["full_attention", "conv", "conv",
                                  "conv"] * ((n_layers - 2) // 4)
    return LlamaConfig(vocab_size=65536, dim=2048, n_layers=n_layers,
                       n_heads=32, n_kv_heads=8, ffn_dim=1536,
                       dense_ffn_dim=11776, n_dense_layers=2, n_experts=64,
                       experts_per_token=4, norm_topk_prob=True,
                       layer_types=pattern, qk_norm_per_head=True,
                       router_score="sigmoid", router_bias=True,
                       router_eps=1e-6, rope_theta=1e6,
                       param_dtype="bfloat16")


_LFM2_SIZES = dict(max_batch=128, pages=10752, max_seq=3072)


@pytest.mark.parametrize("program", ["mixed", "decode"])
def test_lfm2_step_programs_compile_at_benchmark_shapes(chip, program):
    """lfm2-24b-a2b-serve-1chip's two step programs at its published
    widths (6 of its 10 layers): the paged kernels at head_dim 64 in a
    pool of 128-lane rows (Mosaic refuses a 64-wide page DMA), 32 q / 8 kv
    heads; the expert kernel at width 1536 (two width blocks of 768) once
    for each expert layer of the period; the conv state carried beside
    the pool, both aliased from argument to result. 128 decode rows, 2
    chunks of 512, 10752 pages of 16."""
    compiled, kv, rows = compile_step_program(chip, _lfm2_cfg(), program,
                                              **_LFM2_SIZES)
    assert kv["k"].shape == (1, 10752, 8, 16, 128)
    assert kv["conv"].shape == (5, 129, 2, 2048)
    text = compiled.as_text()
    # the write, the attention (chunk and one-token tiles | one-token),
    # and the experts of the period's four expert layers
    assert text.count("tpu_custom_call") == (7 if program == "mixed" else 6)
    assert "_moe_experts_pallas" in text
    assert jax.tree.leaves(compiled.out_info)[0].shape == (rows + 3,)
    mem = compiled.memory_analysis()
    held = sum(bytes_of(f"bf16[{','.join(map(str, a.shape))}]")
               for a in kv.values())
    assert mem.alias_size_in_bytes >= held
    assert mem.temp_size_in_bytes < 2**28


def _brumby_cfg(n_layers=2):
    """brumby-14b-serve-1chip's widths; the scan's body is one layer."""
    from ray_tpu.models.llama import LlamaConfig
    return LlamaConfig(vocab_size=151936, dim=5120, n_layers=n_layers,
                       n_heads=40, n_kv_heads=8, ffn_dim=17408,
                       rope_theta=1e6, norm_eps=1e-6,
                       layer_types=["retention"] * n_layers,
                       qk_norm_per_head=True, tie_embeddings=False,
                       retention_chunk=256, param_dtype="bfloat16")


_BRUMBY_SIZES = dict(max_batch=32, pages=19457, max_seq=9728, rows=1,
                     chunk=1024)


@pytest.mark.parametrize("program", ["mixed", "decode"])
def test_brumby_step_programs_compile_at_benchmark_shapes(chip, program):
    """brumby-14b-serve-1chip's two step programs at its published widths
    (two layers of the eight): the in-place update (Mosaic takes a key/value
    head's [8704, 128] bf16 block, the dynamic one-row reads that build phi
    from sublane-broadcast rows, and the transposes that turn k and the five
    q into columns) ONCE, in the layer scan's body, and no paged write or
    attention at all: the page leaves have no layer. Both state leaves
    aliased from argument to result, and no second copy of the state among
    the temporaries (1.46 GB at this depth). 32 decode rows, 1 chunk of
    1024, 19457 pages of 16 that hold nothing."""
    compiled, kv, rows = compile_step_program(chip, _brumby_cfg(), program,
                                              **_BRUMBY_SIZES)
    assert kv["retention"].shape == (2, 33, 8, 8704, 128)
    assert kv["retention_norm"].shape == (2, 33, 8, 128, 128)
    assert kv["k"].shape == kv["v"].shape == (0, 19457, 8, 16, 128)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "_retention_update_pallas" in text
    assert jax.tree.leaves(compiled.out_info)[0].shape == (
        (rows,) if program == "mixed" else (8, 32))
    mem = compiled.memory_analysis()
    held = kv["retention"].size * 2 + kv["retention_norm"].size * 4
    assert mem.alias_size_in_bytes >= held
    assert mem.temp_size_in_bytes < 2**30 < kv["retention"].size * 2


test_one_row_mixed_step_compiles_at_benchmark_shapes = \
    one_row_mixed_step_cases(lfm2=(_lfm2_cfg, _LFM2_SIZES),
                             brumby=(_brumby_cfg, _BRUMBY_SIZES))
