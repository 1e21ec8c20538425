"""mimo-v2-flash-serve-1chip's whole step programs compiled for a described
TPU v5e (what and why: tests/test_tpu_compile.py) at its widths, pool and
whole cut: a second page group that frees behind a window, 16 held
experts. Its configuration file's `compiled_peak` quotes what these cases
print.
"""

import jax
import pytest

from _tpu_compile import (bytes_of, chip, compile_step_program,  # noqa: F401
                          one_row_mixed_step_cases)


def _mimo_cfg(n_layers=7):
    """mimo-v2-flash-serve-1chip's widths from its own file: the dense
    full-attention layer and one whole period (five window layers and a
    full one), 16 of 256 experts held, an eighth of the vocabulary."""
    import json
    import os

    from benchmark.runners import serve_mimo
    from ray_tpu.models.llama import LlamaConfig
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "mimo-v2-flash-serve-1chip.json")) as f:
        config = json.load(f)
    return LlamaConfig.tiny(**serve_mimo.model_fields(
        {**config, "num_hidden_layers": n_layers}))


_MIMO_SIZES = dict(max_batch=96, pages=12800, max_seq=19456, ps=64,
                   pool_rows=2)


@pytest.mark.parametrize("program", ["mixed", "decode"])
def test_mimo_step_programs_compile_at_benchmark_shapes(chip, program):
    """mimo-v2-flash-serve-1chip's two step programs at its published
    widths and its whole cut (7 layers): Mosaic takes the ragged kernel at
    16 query heads a key/value head over K rows of 256 lanes and V rows of
    128, the window form (ONE block a tile from the tile's first visible
    page on, a compact table read at its distance from the row's base, the
    sink's block fetched once) under a name of its own, the write of two
    leaves of different width, and the expert kernel over the 16 held
    experts at d = 4096 in width blocks of 512. Both page groups aliased
    from argument to result, and both programs' peak (arguments +
    temporaries; the configuration file keeps the numbers) fits the chip
    beside the reference's scoring. 96 decode rows, 2 chunks of 512, 12800
    pages of 64 and the window group's 407."""
    compiled, kv, rows = compile_step_program(chip, _mimo_cfg(), program,
                                               **_MIMO_SIZES)
    assert kv["k"].shape == (2, 12800, 4, 64, 256)
    assert kv["v"].shape == (2, 12800, 4, 64, 128)
    assert kv["k_win"].shape == (5, 407, 8, 64, 256)
    assert kv["v_win"].shape == (5, 407, 8, 64, 128)
    text = compiled.as_text()
    assert "ragged_window_kernel" in text and "_moe_experts_pallas" in text
    # 4 counters: the routing's three and the pairs held elsewhere
    assert jax.tree.leaves(compiled.out_info)[0].shape == (rows + 4,)
    mem = compiled.memory_analysis()
    held = sum(bytes_of(f"bf16[{','.join(map(str, a.shape))}]")
               for a in kv.values())
    assert mem.alias_size_in_bytes >= held
    peak = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    print(f"mimo {program}: arguments {mem.argument_size_in_bytes / 1e9:.3f}"
          f" GB, temporaries {mem.temp_size_in_bytes / 1e9:.3f} GB")
    assert peak < 13.6e9      # + the reference's 1.93 GB: under 15.5


test_one_row_mixed_step_compiles_at_benchmark_shapes = \
    one_row_mixed_step_cases(mimo=(_mimo_cfg, _MIMO_SIZES))
