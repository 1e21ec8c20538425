"""What the rehearsal compiles for a DESCRIBED TPU v5e share
(tests/test_tpu_compile*.py): the described chip, shapes on it, one of the
engine's step programs compiled from shapes, and the one-row case every
serve configuration's mixed step goes through.

Not a test file. A test FILE is what a worker of the suite is handed
(`--dist loadfile`) and a whole step program takes the TPU compiler 10 to
40 s, so the compiles are cut into files of a few configurations each
(test_tpu_compile.py and test_tpu_compile_<group>.py; the kernels alone
in test_tpu_compile_kernels.py). A configuration's step programs and its
one-row case stand in ONE file: the full mixed shape is compiled once for
both (``compile_step_program`` keeps what a process compiled).
"""

import functools
import os
import re

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

HQ, HKV, D = 32, 8, 128          # Llama-3-8B attention head shapes


@pytest.fixture(scope="module")
def chip():
    """Sharding on one chip of a described v5e 2x2 host."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e topology here: {e!r}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def sds(chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def kernel_calls(lowered) -> int:
    return lowered.compile().as_text().count("tpu_custom_call")


def abstract(chip, fn):
    return jax.tree.map(lambda a: sds(chip, a.shape, a.dtype),
                        jax.eval_shape(fn))


_COMPILED = {}


def compile_step_program(chip, cfg, program, **sizes):
    """_compile_once, kept: a shape compiled for one test is not compiled
    again for another (the one-row cases compare with the full shape the
    configuration's own test compiled, in the same file)."""
    key = (cfg, program, tuple(sorted(sizes.items())))
    if key not in _COMPILED:
        _COMPILED[key] = _compile_once(chip, cfg, program, **sizes)
    return _COMPILED[key]


def _compile_once(chip, cfg, program, *, max_batch, pages, max_seq, rows=2,
                  chunk=512, ps=16, pool_rows=None):
    """One of the engine's step programs, compiled from shapes
    (jax.eval_shape: no weights exist): the mixed step over max_batch
    decode rows + ``rows`` chunks of ``chunk`` (one of its shapes,
    llm/model.py:chunk_row_shapes), or the 8-step decode loop.
    ``pool_rows``: the chunk rows the engine's pool is sized for where
    that is not ``rows`` (a window group holds what every shape's rows can:
    the smaller shapes run over the full shape's pool). Every program
    takes the slots' newest tokens as its fourth operand and returns them
    as its last result (the engine launches a program before it has read
    the one before: llm/engine.py), in no more programs than before.
    Returns (compiled, the pool's abstract pytree, rows of the result)."""
    from ray_tpu.llm import model as M
    from ray_tpu.llm.cache import make_kv_cache, window_group_pages
    from ray_tpu.models.llama import init_params
    params = abstract(chip, functools.partial(init_params, cfg,
                                              jax.random.PRNGKey(0)))
    # the pool the kernels take (StepPrograms.init_kv on a TPU); a second
    # page group where the configuration has window layers, at the
    # engine's own size
    kv = abstract(chip, functools.partial(
        make_kv_cache, cfg, pages, ps, max_batch=max_batch, lane_pad=True,
        window_pages=window_group_pages(cfg, ps, max_batch, 8, chunk,
                                        pool_rows or rows)))
    # the engine's own seam over these sizes: its layouts and statics
    fns = M.StepPrograms(cfg, decode_chunk=8, max_q_len=chunk,
                         decode_rows=max_batch, max_pages=max_seq // ps,
                         kv_quantized=False, prefill_rows=rows, page_size=ps)
    name, layout, n_out = {
        "mixed": ("ragged_step", fns.step_layouts[rows], max_batch + rows),
        "decode": ("decode_loop", fns.decode_layout, 8 * max_batch)}[program]
    jit, statics = fns.jits[name]
    desc = sds(chip, (M.layout_size(layout),), jnp.int32)
    last = sds(chip, (max_batch,), jnp.int32)
    compiled = jit.lower(params, desc, kv, last, **{
        **statics, "paged_impl": "kernel"}).compile()
    newest = jax.tree.leaves(compiled.out_info)[-1]
    assert (newest.shape, newest.dtype) == ((max_batch,), jnp.int32)
    assert len(jax.tree.leaves(compiled.args_info)) \
        == len(jax.tree.leaves((params, kv))) + 2
    assert fns.program_budget == 2 + len(fns.row_shapes) <= 4
    return compiled, kv, n_out


def bytes_of(shape: str) -> int:
    dims = re.match(r"\(?(bf16|s8|f32|s32)\[([\d,]*)\]", shape)
    size = {"bf16": 2, "s8": 1, "f32": 4, "s32": 4}[dims.group(1)]
    for d in filter(None, dims.group(2).split(",")):
        size *= int(d)
    return size




def one_row_mixed_step_cases(**configurations):
    """test_one_row_mixed_step_compiles_at_benchmark_shapes over the
    serve ``configurations`` a file compiles, name -> (its widths, the sizes
    of its full mixed-step shape: benchmark/configs/*.json's engine
    settings). The case is written here once; a file takes it for the
    configurations whose full shape it compiles anyway."""

    @pytest.mark.parametrize("widths", sorted(configurations))
    def test_one_row_mixed_step_compiles_at_benchmark_shapes(chip, widths):
        """The SMALLEST shape of each serve configuration's mixed step
        (max_batch decode rows + ONE chunk row; llm/engine.py runs it when a
        step is dealt one row): it compiles for the described v5e with the
        kernels the full shape has (_ragged_tiling and the state kernels read
        their sizes from the operands), returns max_batch + 1 rows, aliases
        the pool as the full shape does, and needs no more memory to speak
        of: the same arguments, and temporaries smaller or, in mistral's
        case, 38 MB larger (its two-row program packs them into 2 MB, its
        one-row program takes 40: compiled for v5e, PR 42); the room is 64
        MiB. brumby's full shape IS one row."""
        make_cfg, sizes = configurations[widths]
        cfg = make_cfg()
        full, kv, full_rows = compile_step_program(chip, cfg, "mixed",
                                                   **sizes)
        one, _, rows = compile_step_program(chip, cfg, "mixed",
                                            **{**sizes, "rows": 1})
        assert rows == sizes["max_batch"] + 1 <= full_rows
        assert (rows == full_rows) == (widths == "brumby")
        from ray_tpu.llm.model import step_counters
        counters = len(step_counters(cfg))
        assert jax.tree.leaves(one.out_info)[0].shape == (rows + counters,)
        assert one.as_text().count("tpu_custom_call") \
            == full.as_text().count("tpu_custom_call") > 0
        m1, m2 = one.memory_analysis(), full.memory_analysis()
        assert m1.alias_size_in_bytes == m2.alias_size_in_bytes > 0
        assert m1.argument_size_in_bytes <= m2.argument_size_in_bytes
        assert m1.temp_size_in_bytes <= m2.temp_size_in_bytes + 2**26

    return test_one_row_mixed_step_compiles_at_benchmark_shapes
