"""The kernels alone, compiled for a described TPU v5e (what and why:
tests/test_tpu_compile.py): the main path's at Llama-3-8B head shapes (32
q / 8 kv heads x 128) and the train cell's three layers, and each block's
own at its cell's published shapes.
"""

import functools
import importlib
import os
import re

import jax
import jax.numpy as jnp
import pytest

from _tpu_compile import D, HKV, HQ, chip, kernel_calls, sds  # noqa: F401

fa = importlib.import_module("ray_tpu.ops.flash_attention")
pa = importlib.import_module("ray_tpu.ops.paged_attention")

FLASH_L = 2048


def _pool(chip, kv, pages, ps, hkv=HKV):
    """(k_pages, v_pages, k_scale, v_scale) shapes of one layer's pool."""
    dt = jnp.int8 if kv == "int8" else jnp.bfloat16
    page = sds(chip, (pages, hkv, ps, D), dt)
    scale = sds(chip, (pages, hkv, ps), jnp.bfloat16) \
        if kv == "int8" else None
    return page, page, scale, scale


def _ragged_kernel_calls(chip, kv, *, T, R, max_q_len, decode_rows, ps,
                         pages, max_pages, hq=HQ, hkv=HKV) -> int:
    """Pallas calls in the compiled ragged attention of one layer."""
    k, v, ks, vs = _pool(chip, kv, pages, ps, hkv)
    row = sds(chip, (R,), jnp.int32)
    return kernel_calls(pa._ragged_attention_pallas.lower(
        sds(chip, (T, hq, D), jnp.bfloat16), k, v,
        sds(chip, (R, max_pages), jnp.int32), row, row, row, ks, vs,
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
    x = sds(chip, (1, FLASH_L, HQ // 8, D), jnp.bfloat16)   # 4 heads

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, blk_q=blk_q,
                                  blk_k=blk_k).astype(jnp.float32).sum()

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(x, x, x)
    assert kernel_calls(lowered) == 3          # fwd, dq, dk/dv


def _flash_grad_kernel_calls(chip, Lq, Lk, causal, heads=4, blocks=None):
    """Pallas calls in jit(grad) of one flash_attention_block call, with
    a cotangent on lse too (the ring's merge sends one)."""
    ra = importlib.import_module("ray_tpu.parallel.ring_attention")
    q = sds(chip, (1, Lq, heads, D), jnp.bfloat16)
    k = sds(chip, (1, Lk, heads, D), jnp.bfloat16)
    blk_q, blk_k = blocks or ra._resolve_fused_blocks(
        Lq, Lk, D, jnp.bfloat16, interpret=False)

    def loss(q, k, v):
        o, lse = fa.flash_attention_block(q, k, v, causal, None, blk_q,
                                          blk_k)
        return o.astype(jnp.float32).sum() + lse.sum()

    return kernel_calls(
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
    pool = sds(chip, (L, P, 1, ps, W), jnp.bfloat16)
    row = sds(chip, (R,), jnp.int32)
    layer = sds(chip, (), jnp.int32)
    attn = pa._ragged_attention_pallas.lower(
        sds(chip, (T, hq, W), jnp.bfloat16), pool, None,
        sds(chip, (R, mp), jnp.int32), row, row, row, None, None,
        sm_scale=192 ** -0.5, max_q_len=512, decode_rows=48, layer=layer,
        v_width=vw)
    assert kernel_calls(attn) == 2          # chunk tiles, one-token tiles
    # the decode loop's call at the cell's size: 48 one-token rows over a
    # pool of 21600 pages, in the blocks _ragged_tiling gives that tile
    # (a block too large for VMEM is refused here, before any chip run)
    rows = sds(chip, (48,), jnp.int32)
    decode = pa._ragged_attention_pallas.lower(
        sds(chip, (48, hq, W), jnp.bfloat16),
        sds(chip, (8, 21600, 1, ps, W), jnp.bfloat16), None,
        sds(chip, (48, mp), jnp.int32), rows, rows, rows, None, None,
        sm_scale=192 ** -0.5, decode_rows=48, layer=layer, v_width=vw)
    assert kernel_calls(decode) == 1
    tok = sds(chip, (T,), jnp.int32)
    write = pa._kv_write_pallas.lower(
        pool, None, sds(chip, (T, 1, W), jnp.bfloat16), None,
        sds(chip, (1,), jnp.int32), tok, tok, row, row, max_q_len=512,
        decode_rows=48)
    assert kernel_calls(write) == 1
    narrow = sds(chip, (L, P, 1, ps, 576), jnp.bfloat16)
    with pytest.raises(Exception, match="aligned to tiling"):
        pa._kv_write_pallas.lower(
            narrow, None, sds(chip, (T, 1, 576), jnp.bfloat16), None,
            sds(chip, (1,), jnp.int32), tok, tok, row, row, max_q_len=512,
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
    k = sds(chip, (L, P, hkv, ps, 256), jnp.bfloat16)
    v = sds(chip, (L, P, hkv, ps, 128), jnp.bfloat16)
    layer = sds(chip, (), jnp.int32)
    for T, R, max_q_len, calls in ((96 + 2 * 512, 98, 512, 2), (96, 96, 1, 1)):
        row = sds(chip, (R,), jnp.int32)
        lowered = pa._ragged_attention_pallas.lower(
            sds(chip, (T, hq, 256), jnp.bfloat16), k, v,
            sds(chip, (R, mp), jnp.int32), row, row, row, None, None,
            sm_scale=192 ** -0.5, max_q_len=max_q_len, decode_rows=96,
            layer=layer)
        assert kernel_calls(lowered) == calls


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
        sds(chip, (L, S, Hv, dk, dv), f32), sds(chip, (R, Hv, dk), f32),
        sds(chip, (R, Hv, dk), f32), sds(chip, (R, Hv, dv), f32),
        sds(chip, (R, Hv), f32), sds(chip, (R, Hv), f32),
        sds(chip, (R,), jnp.int32), sds(chip, (R,), jnp.bool_),
        sds(chip, (1,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "_delta_update_pallas" in text


def test_selective_scan_kernels_compile_at_published_shapes(chip):
    """ops/selective_scan.py's two kernels at the published sizes (a
    float32 state [16, 5120] a slot, 9 layers, 160 slots): Mosaic takes the
    one-token update (a decay TILE made in the kernel) and the chunk rows'
    scan (every row's state resident in VMEM, tokens eight at a time: it
    loads no single row at a dynamic index)."""
    from ray_tpu.ops import selective_scan as ss
    f32, i32 = jnp.float32, jnp.int32
    state = sds(chip, (9, 161, 16, 5120), f32)
    A = sds(chip, (16, 5120), f32)

    def operands(T):
        return (sds(chip, (T, 5120), f32), sds(chip, (T, 5120), f32), A,
                sds(chip, (T, 16), f32), sds(chip, (T, 16), f32))

    row = sds(chip, (160,), i32)
    assert kernel_calls(ss._selective_update_pallas.lower(
        state, *operands(160), row, sds(chip, (160,), jnp.bool_),
        sds(chip, (1,), i32))) == 1
    two = sds(chip, (2,), i32)
    assert kernel_calls(ss._selective_scan_pallas.lower(
        state, *operands(1024), sds(chip, (1024,), i32), two, two, two,
        sds(chip, (1,), i32))) == 1
