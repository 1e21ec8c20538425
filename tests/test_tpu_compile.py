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
import re

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


def _flash_grad_kernel_calls(chip, Lq, Lk, causal, heads=4, blocks=None):
    """Pallas calls in jit(grad) of one flash_attention_block call, with
    a cotangent on lse too (the ring's merge sends one)."""
    ra = importlib.import_module("ray_tpu.parallel.ring_attention")
    q = _sds(chip, (1, Lq, heads, D), jnp.bfloat16)
    k = _sds(chip, (1, Lk, heads, D), jnp.bfloat16)
    blk_q, blk_k = blocks or ra._resolve_fused_blocks(
        Lq, Lk, D, jnp.bfloat16, interpret=False)

    def loss(q, k, v):
        o, lse = fa.flash_attention_block(q, k, v, causal, None, blk_q,
                                          blk_k)
        return o.astype(jnp.float32).sum() + lse.sum()

    return _kernel_calls(
        jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, k, k))


def test_flash_compiles_at_the_train_cells_shape(chip):
    """mistral7b-train-1chip: 2 rows x 32 heads of 4096 x 128 bf16, causal,
    the tiling flash_tiling picks. Exactly one custom call of each kind
    (forward, dq, dk/dv): benchmark/metrics/flash_attn_*.json count them."""
    assert _flash_grad_kernel_calls(chip, 4096, 4096, True, heads=64,
                                    blocks=(None, None)) == 3


@pytest.fixture(scope="module")
def train_layers(chip):
    """Three layers of mistral7b-train-1chip under its remat ("full"), value
    and gradient through _scan_layers, on the one-device mesh the trainer
    hands loss_fn, compiled once for the tests below: (text, memory)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ray_tpu.models import llama
    from ray_tpu.parallel import MeshSpec, build_mesh
    cfg = llama.LlamaConfig(
        vocab_size=32768, dim=4096, n_layers=3, n_heads=32, n_kv_heads=8,
        ffn_dim=14336, rope_theta=1e6, attention="flash")
    assert cfg.remat and cfg.remat_policy == "full"
    mesh = build_mesh(MeshSpec(), devices=list(chip.device_set))
    layers = jax.eval_shape(functools.partial(llama.init_params, cfg),
                            jax.random.PRNGKey(0))["layers"]
    layers = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=NamedSharding(mesh, P())), layers)
    x = jax.ShapeDtypeStruct((2, 4096, cfg.dim), cfg.dtype,
                             sharding=NamedSharding(mesh, P()))

    def loss(layers, x):
        return llama._scan_layers(
            layers, x, cfg, jnp.arange(4096),
            llama._make_attn_fn(cfg, mesh)).astype(jnp.float32).sum()

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(fa, "kernels_supported", lambda: True)
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            layers, x).compile()
    return compiled.as_text(), compiled.memory_analysis()


def _instructions(text):
    """(name, result type, line) of every instruction of a compiled
    text that stands in a loop's body or the entry: what is written to a
    buffer of its own. An instruction inside a fused computation is a value
    in flight and is left out."""
    fused, out = False, []
    for ln in text.splitlines():
        if re.match(r"^(ENTRY )?%?[\w.\-]+ \(.*\) -> .* \{$", ln):
            fused = ln.lstrip("%").startswith("fused_computation")
            continue
        m = re.match(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.+?) [a-z][\w\-]*\(", ln)
        if m and not fused:
            out.append((*m.groups(), ln.strip().removeprefix("ROOT ")
                        .lstrip("%")))
    return out


def test_train_layer_keeps_the_forward_kernels_outputs(train_layers):
    """The compiled program holds THREE flash kernels, not four (the forward
    is not run again under the remat boundary), and each is still the
    instruction benchmark/metrics/flash_attn_roofline.json looks for (the
    region a kernel is traced in decides its name's prefix). No
    compute-dtype copy of a whole stacked weight is made (_in_its_turn;
    without it XLA holds one of each through both loops)."""
    import json
    text, _ = train_layers
    calls = [ln for _, _, ln in _instructions(text)
             if "tpu_custom_call" in ln]
    assert len(calls) == 3, [c[:60] for c in calls]
    with open(os.path.join(os.path.dirname(__file__), os.pardir, "benchmark",
                           "metrics", "flash_attn_roofline.json")) as f:
        kernels = json.load(f)["args"]["kernels"]
    for kind, patterns in kernels.items():
        found = [c[:40] for c in calls
                 if any(re.search(p, c) for p in patterns)]
        assert len(found) == 1, (kind, found)
    assert not any(c.startswith("rematted_computation") for c in calls)
    assert "f32[3,4096,14336]" in text and "bf16[3,4096,14336]" not in text


def test_train_layers_backward_reads_the_stacks_in_place(train_layers):
    """The remat boundary of a scan's body fences nothing it is handed
    (llama.remat_scan_body, prevent_cse=False): the backward turn's casts
    read the masters' float32 stacks in place, as the forward's do, and no
    float32 slice of a master is written out first. With jax.checkpoint's
    default every operand of the rematted turn is a buffer of its own:
    seven dynamic-slice_bitcast_fusion instructions with these result
    types, and two more for the kept x and the kept o (PERF.md, PR 53; the
    kept o is still sliced out, under another name: a kernel takes whole
    buffers). The products, the kernels and what is recomputed stay what
    they were but for the ONE MLP product "full" keeps where the shapes
    pay for it (PR 56). Fails the day jax's default, or XLA's fusion of a
    slice into the cast that reads it, changes."""
    text, memory = train_layers
    inst = _instructions(text)
    masters = ("f32[4096,14336]", "f32[14336,4096]", "f32[4096,4096]",
               "f32[4096,1024]")
    assert [(n, r) for n, r, _ in inst if r.startswith(masters)] == []
    # the one slice left is the kept log-sum-exp's (the dq kernel's operand)
    assert [r.split("{")[0] for n, r, _ in inst
            if n.startswith("dynamic-slice_bitcast_fusion")] \
        == ["f32[64,8,4096]"]
    # 9 forward + 8 recomputed products, 9 gradients: "full" keeps mlp_up
    # at these shapes (llama.full_remat_keeps: 3 x 310 MB against 1.31 GB
    # of bf16 weights) and the backward turn does not run its product
    # again. ONE stack holds it, written by the product's own fusion and
    # read where it lies by the backward's fusions: no instruction of its
    # own slices a layer's [2, 4096, 14336] out of it first
    assert text.count(" convolution(") == 26
    stack = "bf16[3,2,4096,14336]"
    made = [ln for _, r, ln in inst if stack in r and not re.search(
        r" (parameter|tuple|get-tuple-element|while)\(", ln)]
    assert sorted(("dynamic-update-slice" in ln and " fusion(" in ln,
                   'custom_call_target="AllocateBuffer"' in ln)
                  for ln in made) == [(False, True), (True, False)], made
    assert not [n for n, r, ln in inst if "dynamic-slice" in n
                and (stack in ln or r.startswith(("bf16[2,4096,14336]",
                                                  "bf16[1,2,4096,14336]")))]
    assert not any(".remat" in n for n, _, _ in inst)
    # 2.366 GB without the kept stack (0.70 GB): this compile reads 3.477
    assert memory.temp_size_in_bytes < 3.55e9, memory.temp_size_in_bytes


@pytest.mark.parametrize("Lq,Lk,causal", [
    (192, 192, True),      # the old divisor pick gave blk_q = 64: refused
    (1024, 2048, False),   # an off-diagonal rotation with longer keys
    (2304, 2304, True),    # 128 x 18: blocks of 256
    (128, 128, True)])
def test_ring_shard_blocks_compile(chip, Lq, Lk, causal):
    """What _resolve_fused_blocks hands the fused ring path compiles,
    forward and backward: a query block is a multiple of 128 lanes or
    the whole shard (ROADMAP S2, fourth bullet)."""
    assert _flash_grad_kernel_calls(chip, Lq, Lk, causal) == 3


@pytest.mark.parametrize("shape,expect", [
    # (Lq, Lk, head_dim, dtype): fwd, dq, dkv as (block, step, resident)
    ((4096, 4096, 128, jnp.bfloat16),       # the train cell
     ((512, 512, 4096), (512, 512, 4096), (512, 512, 4096))),
    ((2048, 2048, 128, jnp.bfloat16),
     ((512, 512, 2048), (512, 512, 2048), (512, 512, 2048))),
    ((1024, 16384, 128, jnp.bfloat16),      # a ring shard, keys past VMEM
     ((512, 512, 8192), (512, 512, 8192), (512, 512, 1024))),
    ((128, 128, 128, jnp.bfloat16),         # one block: the whole length
     ((128, 128, 128), (128, 128, 128), (128, 128, 128))),
    ((192, 192, 128, jnp.bfloat16),
     ((192, 192, 192), (192, 192, 192), (192, 192, 192))),
    ((2304, 2304, 64, jnp.float32),         # 128 x 18
     ((256, 256, 2304), (256, 256, 2304), (256, 256, 2304))),
    ((20, 20, 128, jnp.bfloat16), None),    # no block the lowering takes
    ((1000, 1000, 128, jnp.bfloat16), None)])
def test_flash_tiling_table(shape, expect):
    """The tiling is a function of the static shape alone; a change to
    the choice shows here (PERF.md §5 has the timings behind it)."""
    got = fa.flash_tiling(*shape)
    assert (got if got is None else tuple(map(tuple, got))) == expect


def _abstract(chip, fn):
    return jax.tree.map(lambda a: _sds(chip, a.shape, a.dtype),
                        jax.eval_shape(fn))


_COMPILED = {}


def _compile_step_program(chip, cfg, program, **sizes):
    """_compile_step_program_once, kept: a shape compiled for one test is
    not compiled again for another (the one-row tests compare with the
    full shape the configuration's own test compiled)."""
    key = (cfg, program, tuple(sorted(sizes.items())))
    if key not in _COMPILED:
        _COMPILED[key] = _compile_step_program_once(chip, cfg, program,
                                                    **sizes)
    return _COMPILED[key]


def _compile_step_program_once(chip, cfg, program, *, max_batch, pages,
                               max_seq, rows=2, chunk=512, ps=16,
                               pool_rows=None):
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
    params = _abstract(chip, functools.partial(init_params, cfg,
                                               jax.random.PRNGKey(0)))
    # the pool the kernels take (StepPrograms.init_kv on a TPU); a second
    # page group where the configuration has window layers, at the
    # engine's own size
    kv = _abstract(chip, functools.partial(
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
    desc = _sds(chip, (M.layout_size(layout),), jnp.int32)
    last = _sds(chip, (max_batch,), jnp.int32)
    compiled = jit.lower(params, desc, kv, last, **{
        **statics, "paged_impl": "kernel"}).compile()
    newest = jax.tree.leaves(compiled.out_info)[-1]
    assert (newest.shape, newest.dtype) == ((max_batch,), jnp.int32)
    assert len(jax.tree.leaves(compiled.args_info)) \
        == len(jax.tree.leaves((params, kv))) + 2
    assert fns.program_budget == 2 + len(fns.row_shapes) <= 4
    return compiled, kv, n_out


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
    compiled, _, _ = _compile_step_program(
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
    compiled, _, rows = _compile_step_program(chip, make_cfg(), program,
                                              **sizes)
    text = compiled.as_text()
    # the write, the attention (chunk and one-token tiles | one-token),
    # the experts
    assert text.count("tpu_custom_call") == (4 if program == "mixed" else 3)
    assert "_moe_experts_pallas" in text
    assert jax.tree.leaves(compiled.out_info)[0].shape == (rows + 3,)


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


@pytest.mark.parametrize("program", ["mixed", "decode"])
def test_lfm2_step_programs_compile_at_benchmark_shapes(chip, program):
    """lfm2-24b-a2b-serve-1chip's two step programs at its published
    widths (6 of its 10 layers): the paged kernels at head_dim 64 in a
    pool of 128-lane rows (Mosaic refuses a 64-wide page DMA), 32 q / 8 kv
    heads; the expert kernel at width 1536 (two width blocks of 768) once
    for each expert layer of the period; the conv state carried beside
    the pool, both aliased from argument to result. 128 decode rows, 2
    chunks of 512, 10752 pages of 16."""
    compiled, kv, rows = _compile_step_program(
        chip, _lfm2_cfg(), program, max_batch=128, pages=10752, max_seq=3072)
    assert kv["k"].shape == (1, 10752, 8, 16, 128)
    assert kv["conv"].shape == (5, 129, 2, 2048)
    text = compiled.as_text()
    # the write, the attention (chunk and one-token tiles | one-token),
    # and the experts of the period's four expert layers
    assert text.count("tpu_custom_call") == (7 if program == "mixed" else 6)
    assert "_moe_experts_pallas" in text
    assert jax.tree.leaves(compiled.out_info)[0].shape == (rows + 3,)
    mem = compiled.memory_analysis()
    held = sum(_bytes_of(f"bf16[{','.join(map(str, a.shape))}]")
               for a in kv.values())
    assert mem.alias_size_in_bytes >= held
    assert mem.temp_size_in_bytes < 2**28


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


def _bytes_of(shape: str) -> int:
    dims = re.match(r"\(?(bf16|s8|f32|s32)\[([\d,]*)\]", shape)
    size = {"bf16": 2, "s8": 1, "f32": 4, "s32": 4}[dims.group(1)]
    for d in filter(None, dims.group(2).split(",")):
        size *= int(d)
    return size


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
    compiled, kv, _ = _compile_step_program(chip, cfg, program, **sizes)
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
            weight_copies += _bytes_of(m["result"])
    assert not touched, touched
    assert "_kv_write_pallas" in text
    kernels = 1 + (2 if program == "mixed" else 1) + bool(cfg.n_experts)
    assert text.count("tpu_custom_call") == kernels
    pool_bytes = 2 * _bytes_of(f"bf16[{pool}]")
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes - weight_copies < pool_bytes // 2


def test_latent_kernels_compile_at_kanana2_shapes(chip):
    """The kernel form of latent attention (MLA, absorbed): ONE kv head, 32
    query heads, rows of 640 lanes (576 held in whole lanes) whose leading
    512 are the value, no V leaf: the mixed step's shape (48 decode rows +
    2 chunk rows of 512), the decode loop's (48 one-token rows) and the
    one-leaf write, for a v5e. A row of 576
    is refused by Mosaic ("must be aligned to tiling (128)"), which is why
    the pool pads it."""
    L, P, ps, W, vw, hq = 2, 512, 16, 640, 512, 32
    T, R, mp = 48 + 2 * 512, 50, 608
    pool = _sds(chip, (L, P, 1, ps, W), jnp.bfloat16)
    row = _sds(chip, (R,), jnp.int32)
    layer = _sds(chip, (), jnp.int32)
    attn = pa._ragged_attention_pallas.lower(
        _sds(chip, (T, hq, W), jnp.bfloat16), pool, None,
        _sds(chip, (R, mp), jnp.int32), row, row, row, None, None,
        sm_scale=192 ** -0.5, max_q_len=512, decode_rows=48, layer=layer,
        v_width=vw)
    assert _kernel_calls(attn) == 2          # chunk tiles, one-token tiles
    # the decode loop's call at the cell's size: 48 one-token rows over a
    # pool of 21600 pages, in the blocks _ragged_tiling gives that tile
    # (a block too large for VMEM is refused here, before any chip run)
    rows = _sds(chip, (48,), jnp.int32)
    decode = pa._ragged_attention_pallas.lower(
        _sds(chip, (48, hq, W), jnp.bfloat16),
        _sds(chip, (8, 21600, 1, ps, W), jnp.bfloat16), None,
        _sds(chip, (48, mp), jnp.int32), rows, rows, rows, None, None,
        sm_scale=192 ** -0.5, decode_rows=48, layer=layer, v_width=vw)
    assert _kernel_calls(decode) == 1
    tok = _sds(chip, (T,), jnp.int32)
    write = pa._kv_write_pallas.lower(
        pool, None, _sds(chip, (T, 1, W), jnp.bfloat16), None,
        _sds(chip, (1,), jnp.int32), tok, tok, row, row, max_q_len=512,
        decode_rows=48)
    assert _kernel_calls(write) == 1
    narrow = _sds(chip, (L, P, 1, ps, 576), jnp.bfloat16)
    with pytest.raises(Exception, match="aligned to tiling"):
        pa._kv_write_pallas.lower(
            narrow, None, _sds(chip, (T, 1, 576), jnp.bfloat16), None,
            _sds(chip, (1,), jnp.int32), tok, tok, row, row, max_q_len=512,
            decode_rows=48).compile()


def test_full_layer_kernels_compile_at_mimo_shapes(chip):
    """The blocked kernel at the window block's FULL layers, the
    benchmark's sizes (mimo-v2-flash-serve-1chip): 4 KV heads of 16 query
    heads, K rows of 256 lanes and V rows of 128, 12800 pages of 64, a
    304-page table a row. The mixed step's call (96 one-token rows + 2
    chunks of 512: the chunk tile is 64 tokens x 16 heads = 1024 operand
    rows against blocks of 1024 slots, [4, 1024, 1024] float32 scores and
    ~100 MB of VMEM granted: a block too large is refused here, before any
    chip run) and the decode loop's (96 one-token rows, blocks of 512)."""
    L, P, ps, mp, hq, hkv = 2, 12800, 64, 304, 64, 4
    shape = dict(kv_heads=hkv, kv_width=256 + 128)
    assert pa._ragged_tiling(512, hq // hkv, ps, mp, **shape) \
        == (64, 8, 1024, 16)
    assert pa._ragged_tiling(1, hq // hkv, ps, mp, **shape) == (1, 1, 16, 8)
    k = _sds(chip, (L, P, hkv, ps, 256), jnp.bfloat16)
    v = _sds(chip, (L, P, hkv, ps, 128), jnp.bfloat16)
    layer = _sds(chip, (), jnp.int32)
    for T, R, max_q_len, calls in ((96 + 2 * 512, 98, 512, 2), (96, 96, 1, 1)):
        row = _sds(chip, (R,), jnp.int32)
        lowered = pa._ragged_attention_pallas.lower(
            _sds(chip, (T, hq, 256), jnp.bfloat16), k, v,
            _sds(chip, (R, mp), jnp.int32), row, row, row, None, None,
            sm_scale=192 ** -0.5, max_q_len=max_q_len, decode_rows=96,
            layer=layer)
        assert _kernel_calls(lowered) == calls


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
    compiled, kv, rows = _compile_step_program(
        chip, _granite_cfg(), program, max_batch=128, pages=10752,
        max_seq=3072)
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
    held = sum(_bytes_of(f"bf16[{','.join(map(str, a.shape))}]")
               for a in kv.values())
    assert mem.alias_size_in_bytes >= held
    assert mem.temp_size_in_bytes < 2**29 < kv["ssm"].size * 2


def _brumby_cfg(n_layers=2):
    """brumby-14b-serve-1chip's widths; the scan's body is one layer."""
    from ray_tpu.models.llama import LlamaConfig
    return LlamaConfig(vocab_size=151936, dim=5120, n_layers=n_layers,
                       n_heads=40, n_kv_heads=8, ffn_dim=17408,
                       rope_theta=1e6, norm_eps=1e-6,
                       layer_types=["retention"] * n_layers,
                       qk_norm_per_head=True, tie_embeddings=False,
                       retention_chunk=256, param_dtype="bfloat16")


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
    compiled, kv, rows = _compile_step_program(
        chip, _brumby_cfg(), program, max_batch=32, pages=19457,
        max_seq=9728, rows=1, chunk=1024)
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
    compiled, kv, rows = _compile_step_program(chip, _mimo_cfg(), program,
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
    held = sum(_bytes_of(f"bf16[{','.join(map(str, a.shape))}]")
               for a in kv.values())
    assert mem.alias_size_in_bytes >= held
    peak = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    print(f"mimo {program}: arguments {mem.argument_size_in_bytes / 1e9:.3f}"
          f" GB, temporaries {mem.temp_size_in_bytes / 1e9:.3f} GB")
    assert peak < 13.6e9      # + the reference's 1.93 GB: under 15.5


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
    compiled, kv, rows = _compile_step_program(chip, _trinity_cfg(), program,
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
    held = sum(_bytes_of(f"bf16[{','.join(map(str, a.shape))}]")
               for a in kv.values())
    assert mem.alias_size_in_bytes >= held
    peak = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    print(f"trinity {program}: arguments "
          f"{mem.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.3f} GB")
    assert peak < 13.8e9      # + the reference's 0.73 GB: under 15.5


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


def test_delta_update_kernel_compiles_at_published_shapes(chip):
    """ops/delta.py's one-token update at 176 rows of 64 value heads of 128
    x 128 float32 over a leaf of 4 layers and 177 slots: Mosaic takes a
    slot's 4 MB block in and out, a head's k and q as ONE lane broadcast
    over the tile each (no transpose); that the leaf is aliased from the
    program's argument to its result is held on the whole step programs
    below, which donate it."""
    from ray_tpu.ops import delta
    R, Hv, dk, dv, L, S = 176, 64, 128, 128, 4, 177
    f32 = jnp.float32
    compiled = delta._delta_update_pallas.lower(
        _sds(chip, (L, S, Hv, dk, dv), f32), _sds(chip, (R, Hv, dk), f32),
        _sds(chip, (R, Hv, dk), f32), _sds(chip, (R, Hv, dv), f32),
        _sds(chip, (R, Hv), f32), _sds(chip, (R, Hv), f32),
        _sds(chip, (R,), jnp.int32), _sds(chip, (R,), jnp.bool_),
        _sds(chip, (1,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "_delta_update_pallas" in text


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
    compiled, kv, rows = _compile_step_program(
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


#: configuration -> (its widths, the sizes of its full mixed-step shape):
#: benchmark/configs/*.json's engine settings
_MIXED = {
    "mistral": (_mistral_cfg, _SERVE["mistral"][1]),
    "olmoe": (_olmoe_cfg, _SERVE["olmoe"][1]),
    "lfm2": (_lfm2_cfg, dict(max_batch=128, pages=10752, max_seq=3072)),
    "kanana": (_kanana_cfg, dict(max_batch=48, pages=21600, max_seq=9728)),
    "granite": (_granite_cfg, dict(max_batch=128, pages=10752,
                                   max_seq=3072)),
    "brumby": (_brumby_cfg, dict(max_batch=32, pages=19457, max_seq=9728,
                                 rows=1, chunk=1024)),
    "mimo": (_mimo_cfg, _MIMO_SIZES),
    "trinity": (_trinity_cfg, _TRINITY_SIZES)}


@pytest.mark.parametrize("widths", sorted(_MIXED))
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
    make_cfg, sizes = _MIXED[widths]
    cfg = make_cfg()
    full, kv, full_rows = _compile_step_program(chip, cfg, "mixed", **sizes)
    one, _, rows = _compile_step_program(chip, cfg, "mixed",
                                         **{**sizes, "rows": 1})
    assert rows == sizes["max_batch"] + 1 <= full_rows
    assert (rows == full_rows) == (widths == "brumby")
    counters = (3 + bool(cfg.experts_held)) if cfg.n_experts else 0
    assert jax.tree.leaves(one.out_info)[0].shape == (rows + counters,)
    assert one.as_text().count("tpu_custom_call") \
        == full.as_text().count("tpu_custom_call") > 0
    m1, m2 = one.memory_analysis(), full.memory_analysis()
    assert m1.alias_size_in_bytes == m2.alias_size_in_bytes > 0
    assert m1.argument_size_in_bytes <= m2.argument_size_in_bytes
    assert m1.temp_size_in_bytes <= m2.temp_size_in_bytes + 2**26


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


def test_selective_scan_kernels_compile_at_published_shapes(chip):
    """ops/selective_scan.py's two kernels at the published sizes (a
    float32 state [16, 5120] a slot, 9 layers, 160 slots): Mosaic takes the
    one-token update (a decay TILE made in the kernel) and the chunk rows'
    scan (every row's state resident in VMEM, tokens eight at a time: it
    loads no single row at a dynamic index)."""
    from ray_tpu.ops import selective_scan as ss
    f32, i32 = jnp.float32, jnp.int32
    state = _sds(chip, (9, 161, 16, 5120), f32)
    A = _sds(chip, (16, 5120), f32)

    def operands(T):
        return (_sds(chip, (T, 5120), f32), _sds(chip, (T, 5120), f32), A,
                _sds(chip, (T, 16), f32), _sds(chip, (T, 16), f32))

    row = _sds(chip, (160,), i32)
    assert _kernel_calls(ss._selective_update_pallas.lower(
        state, *operands(160), row, _sds(chip, (160,), jnp.bool_),
        _sds(chip, (1,), i32))) == 1
    two = _sds(chip, (2,), i32)
    assert _kernel_calls(ss._selective_scan_pallas.lower(
        state, *operands(1024), _sds(chip, (1024,), i32), two, two, two,
        _sds(chip, (1,), i32))) == 1


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
    compiled, kv, rows = _compile_step_program(
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
