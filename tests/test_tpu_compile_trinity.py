"""trinity-mini-serve-1chip's whole step programs compiled for a described
TPU v5e (what and why: tests/test_tpu_compile.py) at its widths, pool and
whole cut: a second page group that frees behind a window of 2048, every
expert, the 200192-row head. Its configuration file's `compiled_peak`
quotes what these cases print.
"""

import jax
import pytest

from _tpu_compile import (bytes_of, chip, compile_step_program,  # noqa: F401
                          one_row_mixed_step_cases)


def _trinity_cfg(n_layers=5):
    """trinity-mini-serve-1chip's widths from its own file: the dense
    window layer and one whole period (three window layers and a full
    one), all 128 experts, the whole vocabulary."""
    import json
    import os

    from benchmark.runners import serve_trinity
    from ray_tpu.models.llama import LlamaConfig
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "trinity-mini-serve-1chip.json")) as f:
        config = json.load(f)
    assert config["num_hidden_layers"] == n_layers
    return LlamaConfig.tiny(**serve_trinity.model_fields(config))


_TRINITY_SIZES = dict(max_batch=128, pages=19200, max_seq=34816, ps=64,
                      chunk=1024, pool_rows=2)


@pytest.mark.parametrize("program", ["mixed", "decode"])
def test_trinity_step_programs_compile_at_benchmark_shapes(chip, program):
    """trinity-mini-serve-1chip's two step programs at its published
    widths and its whole cut (5 layers): Mosaic takes the window form at a
    window of 2048 over pages of 64 (a one-token tile ONE block of 2176
    slots, 8.9 MB of double-buffered pages; a chunk tile blocks of 1024
    slots from its first visible page, where ONE block would be 17.8 MB of
    scores: ops/paged_attention.py:_ragged_tiling), at 8 query heads a
    key/value head and K = V = 128 lanes, a compact table of 34 / 49
    entries a row; the ragged kernel over the one
    full layer's 544-page table; the expert kernel over 128 experts at d =
    2048, f = 1024; and the head's [rows, 200192] float32 logits. Both page
    groups aliased from argument to result, and both programs' peak
    (arguments + temporaries; the configuration file keeps the numbers)
    fits the chip beside the reference's scoring. 128 decode rows, 2 chunks
    of 1024, 19200 pages of 64 and the window group's 4451."""
    compiled, kv, rows = compile_step_program(chip, _trinity_cfg(), program,
                                               **_TRINITY_SIZES)
    assert kv["k"].shape == kv["v"].shape == (1, 19200, 4, 64, 128)
    assert kv["k_win"].shape == kv["v_win"].shape == (4, 4451, 4, 64, 128)
    text = compiled.as_text()
    assert "ragged_window_kernel" in text and "_moe_experts_pallas" in text
    # the write and the tiles of 5 attention layers, 4 expert layers
    assert text.count("tpu_custom_call") == (19 if program == "mixed"
                                             else 14)
    # the routing's three counters behind the tokens
    assert jax.tree.leaves(compiled.out_info)[0].shape == (
        (rows + 3,) if program == "mixed" else (8 * 128 + 3,))
    mem = compiled.memory_analysis()
    held = sum(bytes_of(f"bf16[{','.join(map(str, a.shape))}]")
               for a in kv.values())
    assert mem.alias_size_in_bytes >= held
    peak = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    print(f"trinity {program}: arguments "
          f"{mem.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.3f} GB")
    assert peak < 13.8e9      # + the reference's 0.73 GB: under 15.5


test_one_row_mixed_step_compiles_at_benchmark_shapes = \
    one_row_mixed_step_cases(trinity=(_trinity_cfg, _TRINITY_SIZES))
