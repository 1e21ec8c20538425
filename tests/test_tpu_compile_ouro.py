"""ouro-2.6b-serve-1chip's whole step programs compiled for a described
TPU v5e (what and why: tests/test_tpu_compile.py) at its widths, ALL 48
layers, four passes and its pool of a page plane a pass and layer. Its
configuration file's `compiled_peak` quotes what these cases print: the
peak that sizes the pool.
"""

import json
import os

import jax
import pytest

from _tpu_compile import (bytes_of, chip, compile_step_program,  # noqa: F401
                          one_row_mixed_step_cases)


def _config():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "ouro-2.6b-serve-1chip.json")) as f:
        return json.load(f)


def _ouro_cfg():
    """ouro-2.6b-serve-1chip's widths from its own file: nothing cut."""
    from benchmark.runners import serve_ouro
    from ray_tpu.models.llama import LlamaConfig
    config = _config()
    assert config["num_hidden_layers"] == 48 and config["reduced"] == []
    return LlamaConfig.tiny(**serve_ouro.model_fields(config))


def _sizes():
    engine = _config()["engine"]
    return dict(max_batch=engine["max_batch"], pages=engine["total_pages"],
                max_seq=engine["max_seq_len"], ps=engine["page_size"],
                chunk=engine["prefill_chunk"], rows=engine["prefill_rows"])


@pytest.mark.parametrize("program", ["mixed", "decode"])
def test_ouro_step_programs_compile_at_benchmark_shapes(chip, program):
    """Both step programs at the WHOLE model and the cell's engine
    settings: ONE scan over the four passes around the one scan over the 48
    layers (the write, the chunk tiles and the one-token tiles once each in
    the text, not 192 times), the paged kernels at 16 key/value heads of
    128 with one query head each over a pool of 192 planes, aliased from
    argument to result; the four exit counters behind the tokens; and the
    peak (arguments + temporaries: XLA keeps a re-laid copy of the wq, wk
    and wv stacks, 1.21 GB, with one pass as with four) fits the chip
    beside the reference's scoring at 768 positions (2 MB of HBM
    temporaries compiled for the same chip: its activations live in fast
    memory), under the 15.5 GB the sizing rule allows."""
    engine = _config()["engine"]
    compiled, kv, rows = compile_step_program(chip, _ouro_cfg(), program,
                                               **_sizes())
    assert kv["k"].shape == kv["v"].shape \
        == (192, engine["total_pages"], 16, 16, 128)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == (3 if program == "mixed" else 2)
    assert jax.tree.leaves(compiled.out_info)[0].shape == (
        (rows + 4,) if program == "mixed"
        else (8 * engine["max_batch"] + 4,))
    mem = compiled.memory_analysis()
    held = sum(bytes_of(f"bf16[{','.join(map(str, a.shape))}]")
               for a in kv.values())
    assert held == engine["total_pages"] * 16 * 1_572_864
    assert mem.alias_size_in_bytes >= held
    peak = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    print(f"ouro {program}: arguments "
          f"{mem.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.3f} GB, pool {held / 1e9:.3f} GB")
    assert peak < 15.4e9      # + the reference's temporaries: under 15.5


test_one_row_mixed_step_compiles_at_benchmark_shapes = \
    one_row_mixed_step_cases(ouro=(_ouro_cfg, _sizes()))
