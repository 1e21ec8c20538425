"""Whole step programs compiled for a described TPU v5e (what and why:
tests/test_tpu_compile.py) at the widths and pools of the configurations
whose pages are their only state: mistral and olmoe (a per-head pool,
updated in place), kanana (a latent pool).
"""

import re

import jax
import pytest

from _tpu_compile import (bytes_of, chip, compile_step_program,  # noqa: F401
                          one_row_mixed_step_cases)


def _olmoe_cfg(n_layers=2):
    from ray_tpu.models.llama import LlamaConfig
    return LlamaConfig(vocab_size=50304, dim=2048, n_layers=n_layers,
                       n_heads=16, n_kv_heads=16, ffn_dim=1024,
                       rope_theta=10000.0, param_dtype="bfloat16",
                       n_experts=64, experts_per_token=8, qk_norm=True,
                       tie_embeddings=False)


def _mistral_cfg(n_layers=2):
    from ray_tpu.models.llama import LlamaConfig
    return LlamaConfig(vocab_size=32768, dim=4096, n_layers=n_layers,
                       n_heads=32, n_kv_heads=8, ffn_dim=14336,
                       rope_theta=1e6, param_dtype="bfloat16")


#: the two serve configurations' widths and pools (benchmark/configs)
_SERVE = {"mistral": (_mistral_cfg, dict(max_batch=16, pages=640,
                                         max_seq=2304)),
          "olmoe": (_olmoe_cfg, dict(max_batch=32, pages=1280,
                                     max_seq=1536))}


def test_whole_ragged_step_program_compiles(chip):
    """One whole engine step at Llama-3-8B widths (2 layers): embed,
    per-layer projections, the in-place KV write into the page pool, the
    ragged kernel, logits, argmax."""
    from ray_tpu.models.llama import LlamaConfig
    cfg = LlamaConfig.llama3_8b(n_layers=2, param_dtype="bfloat16")
    compiled, _, _ = compile_step_program(
        chip, cfg, "mixed", max_batch=8, pages=640, max_seq=1024)
    # the write, chunk tiles, one-token tiles
    assert compiled.as_text().count("tpu_custom_call") == 3
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16 * 2**30


@pytest.mark.parametrize("program", ["mixed", "decode"])
def test_olmoe_step_programs_compile_at_benchmark_shapes(chip, program):
    """olmoe-1b7b-serve-1chip's two step programs at its published widths
    (2 of its 12 layers): 64 experts top-8 of width 1024 through the
    dropless expert kernel, q/k norm, the untied head, and the blocked
    paged kernel at 16 KV heads with one query head each. 32 decode rows,
    2 chunks of 512, 1280 pages of 16."""
    make_cfg, sizes = _SERVE["olmoe"]
    compiled, _, rows = compile_step_program(chip, make_cfg(), program,
                                              **sizes)
    text = compiled.as_text()
    # the write, the attention (chunk and one-token tiles | one-token),
    # the experts
    assert text.count("tpu_custom_call") == (4 if program == "mixed" else 3)
    assert "_moe_experts_pallas" in text
    assert jax.tree.leaves(compiled.out_info)[0].shape == (rows + 3,)


_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = (?P<result>.*?) (?P<op>[\w\-]+)\(")
#: what may yield a pool- or layer-shaped result: the program's own
#: plumbing and the in-place write
_POOL_PLUMBING = {"parameter", "get-tuple-element", "tuple", "while",
                  "bitcast", "custom-call"}
#: layers that make a pool leaf 168 MB at each configuration's widths and
#: pages: more than the chip's 128 MiB of VMEM, as at the cells' depths.
#: A smaller leaf the compiler prefetches there, whole or by halves
#: (copy-start / slice-start), which reads as a copy and is none
_POOL_LAYERS = {"mistral": 8, "olmoe": 2}


@pytest.mark.parametrize("widths", sorted(_SERVE))
@pytest.mark.parametrize("program", ["mixed", "decode"])
def test_step_programs_update_the_pool_in_place(chip, program, widths):
    """The KV pool is one buffer in one layout, updated in place: in the
    compiled step programs at both serve configurations' widths and pool
    shapes (``_POOL_LAYERS`` layers) nothing but the write kernel yields an array of
    the pool's or of one layer's shape — no layout copy, no slice of a
    layer out of the stack, no re-stack, no copy from one scan's output
    to the other's carry — the pool is aliased from argument to result,
    and the temporaries hold less than one pool. (Threaded through the
    layer scan as xs/ys the pool moved about four times a step and was
    held three times: PERF.md, PR 27.) The decode loop also hoists
    transposed copies of stacked attention weights out of its step scan,
    as it did before; they are weights, not pool, and are taken off."""
    make_cfg, sizes = _SERVE[widths]
    cfg = make_cfg(_POOL_LAYERS[widths])
    compiled, kv, _ = compile_step_program(chip, cfg, program, **sizes)
    text = compiled.as_text()
    pool = ",".join(map(str, kv["k"].shape))
    one_layer = ",".join(map(str, kv["k"].shape[1:]))
    shaped = re.compile(r"bf16\[(%s|%s)\]" % (pool, one_layer))
    touched, weight_copies = [], 0
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        if shaped.search(m["result"]) and m["op"] not in _POOL_PLUMBING:
            touched.append(line.strip()[:160])
        if m["op"] == "copy" and re.match(
                r"bf16\[%d,\d+,\d+\]" % cfg.n_layers, m["result"]):
            weight_copies += bytes_of(m["result"])
    assert not touched, touched
    assert "_kv_write_pallas" in text
    kernels = 1 + (2 if program == "mixed" else 1) + bool(cfg.n_experts)
    assert text.count("tpu_custom_call") == kernels
    pool_bytes = 2 * bytes_of(f"bf16[{pool}]")
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes - weight_copies < pool_bytes // 2


def _kanana_cfg(n_layers=2):
    """kanana2-30b-a3b-serve-1chip's widths from its own file; 2 layers =
    the leading dense layer and ONE expert layer (the scan's body)."""
    import json
    import os

    from benchmark.runners import serve_kanana
    from ray_tpu.models.llama import LlamaConfig
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "kanana2-30b-a3b-serve-1chip.json")) as f:
        fields = serve_kanana.model_fields(json.load(f))
    return LlamaConfig.tiny(**{**fields, "n_layers": n_layers})


test_one_row_mixed_step_compiles_at_benchmark_shapes = \
    one_row_mixed_step_cases(
        mistral=_SERVE["mistral"], olmoe=_SERVE["olmoe"],
        kanana=(_kanana_cfg, dict(max_batch=48, pages=21600, max_seq=9728)))
