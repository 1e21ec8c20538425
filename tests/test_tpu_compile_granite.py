"""granite4-h-micro-serve-1chip's whole step programs compiled for a described
TPU v5e (what and why: tests/test_tpu_compile.py): a state-space layer's
matrix state a slot beside the pages.
"""

import jax
import pytest

from _tpu_compile import (bytes_of, chip, compile_step_program,  # noqa: F401
                          one_row_mixed_step_cases)


def _granite_cfg(n_layers=10):
    """granite4-h-micro-serve-1chip's widths; 10 layers = ONE period of its
    four (5 mamba, attention, 4 mamba)."""
    from ray_tpu.models.llama import LlamaConfig
    period = ["mamba"] * 5 + ["full_attention"] + ["mamba"] * 4
    return LlamaConfig(vocab_size=100352, dim=2048, n_layers=n_layers,
                       n_heads=32, n_kv_heads=8, ffn_dim=8192,
                       layer_types=period * (n_layers // 10), ssm_state=128,
                       ssm_heads=64, ssm_head_dim=64, ssm_conv=4,
                       ssm_chunk=256, rope=False, attn_scale=0.015625,
                       embed_scale=12.0, residual_scale=0.22,
                       logits_divisor=8.0, param_dtype="bfloat16")


_GRANITE_SIZES = dict(max_batch=128, pages=10752, max_seq=3072)


@pytest.mark.parametrize("program", ["mixed", "decode"])
def test_granite_step_programs_compile_at_benchmark_shapes(chip, program):
    """granite4-h-micro-serve-1chip's two step programs at its published
    widths (one period of ten layers, the scan's body): the in-place state
    update (Mosaic takes a slot's [128, 4096] bf16 block and the
    transposes that turn B and C into columns) once for each of the period's 9 mamba
    layers, beside the paged write and attention at head_dim 64 in
    128-lane rows; both state leaves and the pages aliased from argument
    to result, and NO second copy of a state leaf among the temporaries
    (1.26 GB at this depth): the compiled peak is arguments + well under
    1 GB. 128 decode rows, 2 chunks of 512, 10752 pages of 16."""
    compiled, kv, rows = compile_step_program(chip, _granite_cfg(), program,
                                              **_GRANITE_SIZES)
    assert kv["ssm"].shape == (9, 129, 128, 4096)
    assert kv["ssm_conv"].shape == (9, 129, 3, 4352)
    assert kv["k"].shape == (1, 10752, 8, 16, 128)
    text = compiled.as_text()
    # the write, the attention (chunk and one-token tiles | one-token),
    # and the nine updates
    assert text.count("tpu_custom_call") == (12 if program == "mixed"
                                             else 11)
    assert "_ssm_update_pallas" in text
    assert jax.tree.leaves(compiled.out_info)[0].shape == (
        (rows,) if program == "mixed" else (8, 128))
    mem = compiled.memory_analysis()
    held = sum(bytes_of(f"bf16[{','.join(map(str, a.shape))}]")
               for a in kv.values())
    assert mem.alias_size_in_bytes >= held
    assert mem.temp_size_in_bytes < 2**29 < kv["ssm"].size * 2


test_one_row_mixed_step_compiles_at_benchmark_shapes = \
    one_row_mixed_step_cases(granite=(_granite_cfg, _GRANITE_SIZES))
