"""Rehearsal compiles for a DESCRIBED TPU v5e (no chip attached).

The TPU compiler is installed wherever jax[tpu] is, and compiles for a
topology that is only described (`jax.experimental.topologies`). That
refuses what interpret mode lets through — a slice not aligned to the
tiling, a kernel over its fast-memory budget, a program that does not
fit HBM — at no chip time. Each case asserts the Mosaic kernel is IN the
compiled program (`tpu_custom_call`): nothing here may pass by taking a
reference branch.

Nothing runs, so these say nothing about results or speed. The persistent
compile cache is off around them (an entry written for a described device
cannot be read back without the chip, and warns).

A whole step program takes the compiler 10 to 40 s and a test file is what
a worker of the suite is handed, so the compiles are several files of a
few configurations each, over tests/_tpu_compile.py (the described chip,
`compile_step_program`, the one-row case):

  test_tpu_compile_kernels.py  the kernels alone, at the cells' shapes
  test_tpu_compile_serve.py    mistral, olmoe, kanana: per-head and latent
                               pools, the pool updated in place
  test_tpu_compile_state.py    lfm2, brumby: state a slot
  test_tpu_compile_granite.py  granite: a state-space layer's state a slot
  test_tpu_compile_mimo.py     mimo: a second page group, held experts
  test_tpu_compile_trinity.py  trinity: a second page group, the 200k head
  test_tpu_compile.py (here)   gigachat, phi4flash: whole cuts whose
                               programs hold every kind of kernel

The `compiled_peak` of benchmark/configs/*.json quotes the arguments and
temporaries the whole-cut cases print (mimo's and trinity's in their files).
"""

import jax
import jax.numpy as jnp
import pytest

from _tpu_compile import chip, compile_step_program  # noqa: F401


def _gigachat_cfg(n_layers=5):
    """gigachat35-432b-a28b-serve-1chip's widths from its own file: the
    dense delta layer and one whole period (three delta layers and the
    latent one), 16 of 256 experts held, an eighth of the vocabulary."""
    import json
    import os

    from benchmark.runners import serve_gigachat
    from ray_tpu.models.llama import LlamaConfig
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "gigachat35-432b-a28b-serve-1chip.json")) as f:
        config = json.load(f)
    assert config["num_hidden_layers"] == n_layers
    return LlamaConfig.tiny(**serve_gigachat.model_fields(config))


_GIGACHAT_SIZES = dict(max_batch=176, pages=9216, max_seq=19456, ps=64)


def test_gigachat_mixed_step_compiles_at_benchmark_shapes(chip):
    """gigachat35-432b-a28b-serve-1chip's mixed step (the program that holds
    every kernel and both forms of the recurrence; the decode loop's
    numbers are in the configuration file, compiled the same way) at its
    published widths and its whole cut (5 layers): the delta update kernel
    over a float32 state leaf of 177 slots, the latent kernels at 64 query
    heads on the one row a token (640 lanes) over a 304-page table, the
    expert kernel over the 16 HELD experts at d = 7168, f = 2048 with the
    clamp, and the chunk form's solve as matmuls; ONE pool whose latent
    leaf and both state leaves are aliased from argument to result, and the
    program's peak (arguments + temporaries; the configuration file keeps
    the numbers) fits the chip beside the reference's scoring. 176 decode
    rows, 2 chunks of 512, 9216 pages of 64."""
    compiled, kv, rows = compile_step_program(
        chip, _gigachat_cfg(), "mixed", **_GIGACHAT_SIZES)
    assert {k: (a.shape, a.dtype.name) for k, a in kv.items()} == {
        "k": ((1, 9216, 1, 64, 640), "bfloat16"),
        "delta": ((4, 177, 64, 128, 128), "float32"),
        "delta_conv": ((4, 177, 3, 16384), "bfloat16")}
    text = compiled.as_text()
    assert "_delta_update_pallas" in text and "_moe_experts_pallas" in text
    # 4 updates, the latent write and its tiles, 4 expert layers
    assert text.count("tpu_custom_call") == 11
    # the routing's three counters and the absent pairs behind the tokens
    assert jax.tree.leaves(compiled.out_info)[0].shape == (rows + 4,)
    mem = compiled.memory_analysis()
    held = sum(a.size * a.dtype.itemsize for a in kv.values())
    assert mem.alias_size_in_bytes >= held
    peak = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    print(f"gigachat mixed: arguments "
          f"{mem.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.3f} GB")
    assert peak < 13.9e9      # + the reference's 1.58 GB: under 15.5


def _phi4flash_cfg():
    """phi4-mini-flash-serve-1chip's widths from its own file: all 32
    layers, every width and the whole vocabulary."""
    import json
    import os

    from benchmark.runners import serve_phi4flash
    from ray_tpu.models.llama import LlamaConfig
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "phi4-mini-flash-serve-1chip.json")) as f:
        config = json.load(f)
    return LlamaConfig.tiny(**serve_phi4flash.model_fields(config)), \
        config["engine"]


@pytest.mark.parametrize("program", ["mixed", "decode"])
def test_phi4flash_step_programs_compile_at_benchmark_shapes(chip, program):
    """phi4-mini-flash-serve-1chip's two step programs at its published
    widths and FULL depth (32 layers in three scans: six layer bodies):
    the selective-scan kernels, the window form at a window of 512 over
    pages of 64 and the ragged kernel at a differential pair as one
    128-lane head (10 key/value heads, 4 queries a head), the cross
    layers' reading of the one full layer's pages with no write, and the
    head's [rows, 200064] float32 logits. Every pool leaf aliased from
    argument to result, and both programs' peak (arguments + temporaries;
    the configuration file keeps the numbers) fits the chip beside the
    reference's scoring."""
    cfg, engine = _phi4flash_cfg()
    compiled, kv, rows = compile_step_program(
        chip, cfg, program, max_batch=engine["max_batch"],
        pages=engine["total_pages"], max_seq=engine["max_seq_len"],
        ps=engine["page_size"], chunk=engine["prefill_chunk"],
        rows=engine["prefill_rows"])
    slots = engine["max_batch"] + 1
    assert kv["k"].shape == kv["v"].shape \
        == (1, engine["total_pages"], 10, 64, 128)
    assert kv["k_win"].shape[0] == 8 and kv["k_win"].shape[2:] == (10, 64, 128)
    assert kv["ssm1"].shape == (9, slots, 16, 5120) \
        and kv["ssm1"].dtype == jnp.float32
    assert kv["ssm1_conv"].shape == (9, slots, 3, 5120)
    text = compiled.as_text()
    assert "ragged_window_kernel" in text \
        and "_selective_update_pallas" in text
    assert ("_selective_scan_pallas" in text) == (program == "mixed")
    # a scan's body holds each kernel once: the update (x 2 scans) and, in
    # the mixed step, the chunk scan beside it; a write and a read in the
    # window and the full layer (the mixed step's read two calls: the
    # chunk rows' tiles and the one-token tiles); a read and NO write in
    # the cross layer, ONE call in the mixed step too: behind the cut
    # (llm/model.py: tail_start) every row is one token
    assert text.count("tpu_custom_call") == (11 if program == "mixed" else 7)
    mem = compiled.memory_analysis()
    held = sum(a.size * a.dtype.itemsize for a in kv.values())
    assert mem.alias_size_in_bytes >= held
    peak = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    print(f"phi4flash {program}: arguments "
          f"{mem.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.3f} GB")
    assert peak < 14.7e9      # + the reference's ~0.7 GB: under 15.5
    if program == "mixed":
        # the tail's [T, 10240] temporaries are [R, 10240] (0.095 GB with
        # every token walked to the end: compiled for v5e, PR 63)
        assert mem.temp_size_in_bytes <= 0.095e9
