"""Rehearsal compiles for a DESCRIBED TPU v5e (no chip attached).

The TPU compiler is installed wherever jax[tpu] is, and compiles for a
topology that is only described (`jax.experimental.topologies`). That
refuses what interpret mode lets through — a slice not aligned to the
tiling, a kernel over its fast-memory budget, a program that does not
fit HBM — at no chip time. Kept here: the main path's kernels at
Llama-3-8B head shapes (32 q / 8 kv heads x 128) and one whole serving
step. Each asserts the Mosaic kernel is IN the compiled program
(`tpu_custom_call`): nothing here may pass by taking a reference branch.

Nothing runs, so these say nothing about results or speed. The persistent
compile cache is off around them (an entry written for a described device
cannot be read back without the chip, and warns).
"""

import functools
import importlib
import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

fa = importlib.import_module("ray_tpu.ops.flash_attention")
pa = importlib.import_module("ray_tpu.ops.paged_attention")

HQ, HKV, D = 32, 8, 128          # Llama-3-8B attention head shapes
FLASH_L = 2048


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


def _sds(chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _kernel_calls(lowered) -> int:
    return lowered.compile().as_text().count("tpu_custom_call")


def _pool(chip, kv, pages, ps, hkv=HKV):
    """(k_pages, v_pages, k_scale, v_scale) shapes of one layer's pool."""
    dt = jnp.int8 if kv == "int8" else jnp.bfloat16
    page = _sds(chip, (pages, hkv, ps, D), dt)
    scale = _sds(chip, (pages, hkv, ps), jnp.bfloat16) \
        if kv == "int8" else None
    return page, page, scale, scale


def _ragged_kernel_calls(chip, kv, *, T, R, max_q_len, decode_rows, ps,
                         pages, max_pages, hq=HQ, hkv=HKV) -> int:
    """Pallas calls in the compiled ragged attention of one layer."""
    k, v, ks, vs = _pool(chip, kv, pages, ps, hkv)
    row = _sds(chip, (R,), jnp.int32)
    return _kernel_calls(pa._ragged_attention_pallas.lower(
        _sds(chip, (T, hq, D), jnp.bfloat16), k, v,
        _sds(chip, (R, max_pages), jnp.int32), row, row, row, ks, vs,
        sm_scale=D ** -0.5, max_q_len=max_q_len, decode_rows=decode_rows))


@pytest.mark.parametrize("ps", [16, 32])
@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_ragged_paged_attention_compiles(chip, kv, ps):
    """The engine's mixed prefill+decode attention: 8 decode rows + 2
    prefill chunks of 128 tokens over a 1k-token page table."""
    assert _ragged_kernel_calls(
        chip, kv, T=8 + 2 * 128, R=10, max_q_len=128, decode_rows=8, ps=ps,
        pages=256, max_pages=1024 // ps) == 2   # one-token and chunk tiles


@pytest.mark.parametrize("program,hq,hkv,kv", [
    ("mixed", 32, 8, "bf16"), ("decode", 32, 8, "bf16"),
    ("mixed", 8, 2, "bf16"),              # a tp=4 shard's local heads
    ("mixed", 32, 8, "int8"), ("decode", 32, 8, "int8")])
def test_ragged_kernel_compiles_at_benchmark_shapes(chip, program, hq, hkv,
                                                    kv):
    """The blocked kernel at mistral7b-serve-1chip's shapes (benchmark/
    configs): the mixed step packs 16 decode rows + 2 chunks of 512 into
    1040 slots, the decode loop is 16 one-token rows; 640 pages of 16,
    a 144-page table a row."""
    max_batch, rows, chunk = 16, 2, 512
    T, R, max_q_len = (max_batch + rows * chunk, max_batch + rows, chunk) \
        if program == "mixed" else (max_batch, max_batch, 1)
    assert _ragged_kernel_calls(
        chip, kv, T=T, R=R, max_q_len=max_q_len, decode_rows=max_batch,
        ps=16, pages=640, max_pages=144, hq=hq, hkv=hkv) \
        == (2 if program == "mixed" else 1)


def test_decode_paged_attention_compiles(chip):
    B, ps = 8, 16
    k, v, _, _ = _pool(chip, "bf16", 256, ps)
    lowered = pa._paged_attention_pallas.lower(
        _sds(chip, (B, HQ, D), jnp.bfloat16), k, v,
        _sds(chip, (B, 1024 // ps), jnp.int32), _sds(chip, (B,), jnp.int32),
        sm_scale=D ** -0.5)
    assert _kernel_calls(lowered) == 1


@pytest.mark.parametrize(
    "blk_q,blk_k", fa.block_candidates(FLASH_L, FLASH_L, D, jnp.bfloat16))
def test_flash_fwd_bwd_compiles_at_every_candidate_block(chip, blk_q, blk_k):
    """Every (blk_q, blk_k) the autotuner may pick for L=2048, head_dim
    128 must compile, forward and backward — a pick the compiler refuses
    is found here, not on the chip."""
    x = _sds(chip, (1, FLASH_L, HQ // 8, D), jnp.bfloat16)   # 4 heads

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, blk_q=blk_q,
                                  blk_k=blk_k).astype(jnp.float32).sum()

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(x, x, x)
    assert _kernel_calls(lowered) == 3          # fwd, dq, dk/dv


def test_whole_ragged_step_program_compiles(chip):
    """One whole engine step at Llama-3-8B widths (2 layers; shapes from
    jax.eval_shape, so no weights exist): embed, per-layer projections,
    KV scatter into the page pool, the ragged kernel, logits, argmax."""
    from ray_tpu.llm import model as M
    from ray_tpu.llm.cache import make_kv_cache
    from ray_tpu.models.llama import LlamaConfig, init_params
    cfg = LlamaConfig.llama3_8b(n_layers=2, param_dtype="bfloat16")
    max_batch, rows, chunk, ps, pages, max_seq = 8, 2, 512, 16, 640, 1024
    T, R = max_batch + rows * chunk, max_batch + rows

    def abstract(fn):
        return jax.tree.map(lambda a: _sds(chip, a.shape, a.dtype),
                            jax.eval_shape(fn))

    params = abstract(functools.partial(init_params, cfg,
                                        jax.random.PRNGKey(0)))
    kv = abstract(functools.partial(make_kv_cache, cfg, pages, ps))
    tok, row = _sds(chip, (T,), jnp.int32), _sds(chip, (R,), jnp.int32)
    compiled = M.ragged_step.lower(
        params, tok, tok, tok, tok,
        _sds(chip, (R, max_seq // ps), jnp.int32), row, row, row, kv,
        cfg=cfg, paged_impl="kernel", max_q_len=chunk,
        decode_rows=max_batch).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16 * 2**30


@pytest.mark.parametrize("program", ["mixed", "decode"])
def test_olmoe_step_programs_compile_at_benchmark_shapes(chip, program):
    """olmoe-1b7b-serve-1chip's two step programs at its published widths
    (2 of its 12 layers; shapes from jax.eval_shape): 64 experts top-8 of
    width 1024 through the dropless expert kernel, q/k norm, the untied
    head, and the blocked paged kernel at 16 KV heads with one query head
    each. 32 decode rows, 2 chunks of 512, 1280 pages of 16."""
    from ray_tpu.llm import model as M
    from ray_tpu.llm.cache import make_kv_cache
    from ray_tpu.models.llama import LlamaConfig, init_params
    cfg = LlamaConfig(vocab_size=50304, dim=2048, n_layers=2, n_heads=16,
                      n_kv_heads=16, ffn_dim=1024, rope_theta=10000.0,
                      param_dtype="bfloat16", n_experts=64,
                      experts_per_token=8, qk_norm=True,
                      tie_embeddings=False)
    max_batch, rows, chunk, ps, pages, max_seq = 32, 2, 512, 16, 1280, 1536

    def abstract(fn):
        return jax.tree.map(lambda a: _sds(chip, a.shape, a.dtype),
                            jax.eval_shape(fn))

    params = abstract(functools.partial(init_params, cfg,
                                        jax.random.PRNGKey(0)))
    kv = abstract(functools.partial(make_kv_cache, cfg, pages, ps))
    table = functools.partial(_sds, chip, dtype=jnp.int32)
    if program == "mixed":
        T, R = max_batch + rows * chunk, max_batch + rows
        tok, row = table((T,)), table((R,))
        compiled = M.ragged_step.lower(
            params, tok, tok, tok, tok, table((R, max_seq // ps)), row, row,
            row, kv, cfg=cfg, paged_impl="kernel", max_q_len=chunk,
            decode_rows=max_batch).compile()
        kernels, out = 3, (R + 3,)      # chunk tiles, one-token tiles, experts
    else:
        row = table((max_batch,))
        compiled = M.ragged_decode_loop.lower(
            params, row, row, kv, table((max_batch, max_seq // ps)), row,
            num_steps=8, cfg=cfg, paged_impl="kernel").compile()
        kernels, out = 2, (8 * max_batch + 3,)
    assert compiled.as_text().count("tpu_custom_call") == kernels
    assert "_moe_experts_pallas" in compiled.as_text()
    assert jax.tree.leaves(compiled.out_info)[0].shape == out
