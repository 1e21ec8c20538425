"""Pallas kernel tests — run in interpret mode on the CPU mesh
(the kernels themselves run on a real TPU in benchmark/run.py)."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import blockwise_attention, flash_attention
from ray_tpu.parallel.attention import causal_attention


def make_qkv(B=2, L=256, H=4, D=32, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (B, L, H, D), dtype) for k in ks)


class TestBlockwiseAttention:
    def test_matches_naive(self):
        q, k, v = make_qkv()
        ref = causal_attention(q, k, v).astype(jnp.float32)
        got = blockwise_attention(q, k, v, block_k=64).astype(jnp.float32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_grads_match_naive(self):
        q, k, v = make_qkv(L=128)

        def loss_ref(q, k, v):
            return (causal_attention(q, k, v) ** 2).sum()

        def loss_blk(q, k, v):
            return (blockwise_attention(q, k, v, block_k=32)
                    .astype(jnp.float32) ** 2).sum()

        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        gb = jax.grad(loss_blk, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gr, gb):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       rtol=1e-3, atol=1e-3)


class TestFlashAttention:
    """interpret=True executes the actual kernel logic on CPU."""

    def test_fwd_matches_naive(self):
        q, k, v = make_qkv(L=256)
        ref = causal_attention(q, k, v).astype(jnp.float32)
        got = flash_attention(q, k, v, True, None, 128, 128, True)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref), rtol=2e-4, atol=2e-4)

    def test_bwd_matches_naive(self):
        q, k, v = make_qkv(L=128, H=2)

        def loss_ref(q, k, v):
            return (causal_attention(q, k, v) ** 2).sum()

        def loss_fl(q, k, v):
            return (flash_attention(q, k, v, True, None, 64, 64, True)
                    .astype(jnp.float32) ** 2).sum()

        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        gf = jax.grad(loss_fl, argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", gr, gf):
            np.testing.assert_allclose(
                np.asarray(b), np.asarray(a), rtol=2e-3, atol=2e-3,
                err_msg=f"d{name} mismatch")

    def test_noncausal(self):
        q, k, v = make_qkv(L=128)
        s = jnp.einsum("bqhd,bkhd->bhqk", q * q.shape[-1] ** -0.5, k)
        p = jax.nn.softmax(s, axis=-1)
        ref = jnp.einsum("bhqk,bkhd->bqhd", p, v)
        got = flash_attention(q, k, v, False, None, 64, 64, True)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=2e-4, atol=2e-4)

    def test_llama_flash_matches_full(self):
        from ray_tpu.models import llama
        cfg_full = llama.LlamaConfig.tiny(dtype=jnp.float32, n_layers=2)
        params = llama.init_params(cfg_full, jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                    cfg_full.vocab_size)
        full = llama.forward(params, tokens, cfg_full)
        # route through the blockwise fallback semantics via flash interpret
        import ray_tpu.ops as ops
        orig = ops.flash_attention
        try:
            def interp_flash(q, k, v, *a, **kw):
                return orig(q, k, v, True, None, 16, 16, True)
            ops.flash_attention = interp_flash
            cfg_fl = llama.LlamaConfig.tiny(dtype=jnp.float32, n_layers=2,
                                            attention="flash")
            fl = llama.forward(params, tokens, cfg_fl)
        finally:
            ops.flash_attention = orig
        np.testing.assert_allclose(np.asarray(fl), np.asarray(full),
                                   rtol=2e-3, atol=2e-3)


def _naive_block(q, k, v, causal):
    """(o, lse) of plain softmax attention in float32; the causal mask is
    aligned top-left (query i sees keys <= i), as the kernels' is."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    if causal:
        vis = jnp.arange(q.shape[1])[:, None] >= jnp.arange(k.shape[1])
        s = jnp.where(vis, s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", jnp.exp(s - lse[..., None]), v), lse


#: (Lq, Lk, blk_q, blk_k, resident bytes or None): what tiles occur
_FLASH_CASES = {
    # one block: the whole length
    "one-block": (128, 128, None, None, None),
    # flash_tiling's own blocks: under the diagonal, crossed, and steps
    # the inner loops never reach
    "picked": (2048, 2048, None, None, None),
    # the ring's rotations: Lq != Lk
    "long-keys": (256, 512, None, None, None),
    "long-queries": (512, 256, 128, 128, None),
    # a step that is its own resident block: dead GRID steps, which the
    # index maps point back at the last live block
    "small-pair": (256, 256, 64, 32, None),
    # resident blocks of two steps each: inner loops AND dead grid steps
    "split-resident": (1024, 1024, 128, 128, 2 * 4 * 32 * 4 * 128),
}


class TestFlashTiles:
    """Forward, dq, dk, dv and the dlse path of flash_attention_block
    against the naive product, at every kind of tile the kernels walk."""

    @pytest.mark.parametrize("causal", [True, False],
                             ids=["causal", "full"])
    @pytest.mark.parametrize("case", list(_FLASH_CASES))
    def test_block_matches_naive(self, monkeypatch, case, causal):
        import importlib
        fa = importlib.import_module("ray_tpu.ops.flash_attention")
        Lq, Lk, blk_q, blk_k, resident = _FLASH_CASES[case]
        if resident is not None:
            monkeypatch.setattr(fa, "_RESIDENT_BYTES", resident)
        B, H, D = 1, 2, 32
        ks = jax.random.split(jax.random.PRNGKey(7), 5)
        q = jax.random.normal(ks[0], (B, Lq, H, D))
        k = jax.random.normal(ks[1], (B, Lk, H, D))
        v = jax.random.normal(ks[2], (B, Lk, H, D))
        w = jax.random.normal(ks[3], (B, Lq, H, D))
        u = jax.random.normal(ks[4], (B, H, Lq))   # a nonzero dlse

        def loss(fn, q, k, v):
            o, lse = fn(q, k, v)
            return (o * w).sum() + (lse * u).sum(), (o, lse)

        def flash(q, k, v):
            return fa.flash_attention_block(q, k, v, causal, None, blk_q,
                                            blk_k, True)

        grad = functools.partial(jax.value_and_grad, argnums=(1, 2, 3),
                                 has_aux=True)
        (_, (o, lse)), g = grad(loss)(flash, q, k, v)
        (_, (o_ref, lse_ref)), g_ref = grad(loss)(
            functools.partial(_naive_block, causal=causal), q, k, v)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref),
                                   rtol=2e-4, atol=2e-4)
        for name, a, b in zip("qkv", g_ref, g):
            np.testing.assert_allclose(
                np.asarray(b), np.asarray(a), rtol=2e-3, atol=2e-3,
                err_msg=f"d{name} mismatch")


class TestFlashSaveNames:
    """The forward kernel's outputs carry FLASH_SAVE_NAMES: a checkpoint
    that saves the names runs the forward once, and the merged (dlse != 0)
    backward still matches the naive product."""

    @pytest.mark.parametrize("saved,forward_calls", [(True, 1), (False, 2)],
                             ids=["names_saved", "nothing_saved"])
    def test_dlse_path_under_a_checkpoint(self, saved, forward_calls):
        import importlib
        fa = importlib.import_module("ray_tpu.ops.flash_attention")
        B, L, H, D = 1, 128, 2, 32
        ks = jax.random.split(jax.random.PRNGKey(11), 5)
        q, k, v, w = (jax.random.normal(kk, (B, L, H, D)) for kk in ks[:4])
        u = jax.random.normal(ks[4], (B, H, L))    # a nonzero dlse
        policy = jax.checkpoint_policies.save_only_these_names(
            *(fa.FLASH_SAVE_NAMES if saved else ()))

        def loss(fn, q, k, v):
            o, lse = fn(q, k, v)
            return (o * w).sum() + (lse * u).sum()

        flash = jax.checkpoint(
            lambda q, k, v: fa.flash_attention_block(q, k, v, True, None,
                                                     64, 64, True),
            policy=policy)
        grad = functools.partial(jax.grad, argnums=(1, 2, 3))
        g = grad(loss)(flash, q, k, v)
        g_ref = grad(loss)(functools.partial(_naive_block, causal=True),
                           q, k, v)
        for name, a, b in zip("qkv", g_ref, g):
            np.testing.assert_allclose(
                np.asarray(b), np.asarray(a), rtol=2e-3, atol=2e-3,
                err_msg=f"d{name} mismatch")
        jaxpr = jax.make_jaxpr(jax.grad(functools.partial(loss, flash),
                                        argnums=(0, 1, 2)))(q, k, v)
        assert fa.kernel_calls(jaxpr.jaxpr) == {
            "_fwd_kernel": forward_calls, "_dq_kernel": 1, "_dkv_kernel": 1}

    def test_names_do_nothing_outside_a_checkpoint(self):
        import importlib
        fa = importlib.import_module("ray_tpu.ops.flash_attention")
        q, k, v = make_qkv(B=1, L=128, H=2, D=32)
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda q: fa.flash_attention(q, k, v, interpret=True,
                                         blk_q=64, blk_k=64).sum()))(q)
        assert fa.kernel_calls(jaxpr.jaxpr) == {
            "_fwd_kernel": 1, "_dq_kernel": 1, "_dkv_kernel": 1}


class TestBlockAutotune:
    @pytest.fixture(autouse=True)
    def _clean_cache(self):
        import importlib
        fa = importlib.import_module("ray_tpu.ops.flash_attention")
        fa.clear_block_cache()
        yield
        fa.clear_block_cache()

    def test_candidates_respect_floor_and_divisibility(self):
        import importlib
        block_candidates = importlib.import_module(
            "ray_tpu.ops.flash_attention").block_candidates
        cands = block_candidates(2048, 2048, 64)
        assert cands, "L=2048 must have candidates"
        assert cands[0] == (256, 256)        # heuristic-best first
        for bq, bk in cands:
            assert bq >= 8 and bk >= 8
            assert 2048 % bq == 0 and 2048 % bk == 0
            # blk_q is a lane dimension of the lse/delta blocks: 128s only
            assert bq % 128 == 0
        # ... unless it is the whole (short) length
        assert {bq for bq, _ in block_candidates(64, 64, 64)} == {64}

    def test_autotune_measures_and_caches(self, monkeypatch):
        import importlib
        fa = importlib.import_module("ray_tpu.ops.flash_attention")
        calls = []

        def fake_timer(Lq, Lk, D, dtype, bq, bk, **kw):
            calls.append((bq, bk))
            return abs(bq - 128) + abs(bk - 32)   # makes (128, 32) win

        monkeypatch.setattr(fa, "_time_blocks", fake_timer)
        best = fa.autotune_blocks(256, 64, 32, jnp.float32, measure=True)
        assert best == (128, 32)
        assert calls, "measure=True must actually time candidates"
        assert fa.get_tuned_blocks(256, 64, 32, jnp.float32) == (128, 32)
        # second call is a pure cache hit: no further timing
        n = len(calls)
        assert fa.autotune_blocks(256, 64, 32, jnp.float32,
                                  measure=True) == (128, 32)
        assert len(calls) == n

    @pytest.mark.parametrize("refused,outcome", [
        ("all", RuntimeError),        # the kernels do not work here
        ("best", (256, 128)),         # one refused candidate just loses
    ])
    def test_autotune_refused_candidates(self, monkeypatch, refused,
                                         outcome):
        """A candidate the compiler refuses loses the sweep; when EVERY
        candidate is refused there is nothing to pick — an error, never
        the heuristic's first guess."""
        import importlib
        fa = importlib.import_module("ray_tpu.ops.flash_attention")

        def timer(Lq, Lk, D, dtype, bq, bk, **kw):
            if refused == "all" or (bq, bk) == (256, 256):
                raise ValueError("Mosaic refused this block")
            return abs(bq - 256) + abs(bk - 128)

        monkeypatch.setattr(fa, "_time_blocks", timer)
        if outcome is RuntimeError:
            with pytest.raises(RuntimeError, match="all .* candidates"):
                fa.autotune_blocks(2048, 2048, 128, measure=True)
            assert fa.get_tuned_blocks(2048, 2048, 128,
                                       jnp.bfloat16) is None
        else:
            assert fa.autotune_blocks(2048, 2048, 128,
                                      measure=True) == outcome

    def test_autotune_heuristic_without_measure(self):
        import importlib
        fa = importlib.import_module("ray_tpu.ops.flash_attention")
        assert fa.autotune_blocks(2048, 2048, 64, jnp.bfloat16,
                                  measure=False) == (256, 256)

    def test_autotune_indivisible_returns_none(self):
        import importlib
        fa = importlib.import_module("ray_tpu.ops.flash_attention")
        assert fa.autotune_blocks(20, 20, 64, jnp.float32,
                                  measure=False) is None

    def test_flash_attention_uses_tuned_blocks(self, monkeypatch):
        """blk_q/blk_k=None routes through the tuned cache (the sharded
        wrappers pass None so every trace picks the autotuned block)."""
        import importlib
        fa = importlib.import_module("ray_tpu.ops.flash_attention")
        q, k, v = make_qkv(B=1, L=64, H=2, D=32)
        fa._BLOCK_CACHE[fa._block_cache_key(64, 64, 32, q.dtype)] = (32, 32)
        seen = {}
        real = fa._fwd_call

        def spy(q_, k_, v_, causal, scale, blk_q, blk_k, interpret):
            seen["blocks"] = (blk_q, blk_k)
            return real(q_, k_, v_, causal, scale, blk_q, blk_k, interpret)

        monkeypatch.setattr(fa, "_fwd_call", spy)
        fa.flash_attention(q, k, v, blk_q=None, blk_k=None, interpret=True)
        assert seen["blocks"] == (32, 32)


class TestInt8Matmul:
    def test_forward_close_to_fp(self):
        from ray_tpu.ops import int8_matmul
        x = jax.random.normal(jax.random.PRNGKey(0), (64, 128))
        w = jax.random.normal(jax.random.PRNGKey(1), (128, 32))
        got = np.asarray(int8_matmul(x, w))
        ref = np.asarray(x @ w)
        rel = np.abs(got - ref).max() / np.abs(ref).max()
        assert rel < 0.02, rel    # dynamic W8A8: ~1% at these shapes

    def test_grads_are_exact_fp_transpose(self):
        """The straight-through backward uses fp transposes of the ORIGINAL
        operands, so grads equal the fp matmul's grads exactly."""
        from ray_tpu.ops import int8_matmul
        x = jax.random.normal(jax.random.PRNGKey(2), (16, 32))
        w = jax.random.normal(jax.random.PRNGKey(3), (32, 8))
        g = jax.random.normal(jax.random.PRNGKey(4), (16, 8))
        loss8 = lambda x, w: (int8_matmul(x, w) * g).sum()
        lossfp = lambda x, w: ((x @ w) * g).sum()
        gx8, gw8 = jax.grad(loss8, argnums=(0, 1))(x, w)
        gxf, gwf = jax.grad(lossfp, argnums=(0, 1))(x, w)
        np.testing.assert_allclose(np.asarray(gx8), np.asarray(gxf),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(gw8), np.asarray(gwf),
                                   rtol=1e-6, atol=1e-6)

    def test_jit_and_finite(self):
        from ray_tpu.ops import int8_matmul
        x = jax.random.normal(jax.random.PRNGKey(5), (8, 16))
        w = jax.random.normal(jax.random.PRNGKey(6), (16, 4))
        out = jax.jit(int8_matmul)(x, w)
        assert out.shape == (8, 4)
        assert np.isfinite(np.asarray(out)).all()
