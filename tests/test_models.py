"""Model-family tests on the virtual 8-device CPU mesh."""

import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.models import llama, mixtral
from ray_tpu.models.mlp import MLPConfig, mlp_init, mlp_apply, mlp_loss
from ray_tpu.parallel import MeshSpec, build_mesh


def make_inputs(cfg, B=2, L=32, seed=0):
    return jax.random.randint(jax.random.PRNGKey(seed), (B, L), 0,
                              cfg.vocab_size)


class TestLlamaSingleDevice:
    def test_forward_shape_and_finite(self):
        cfg = llama.LlamaConfig.tiny(dtype=jnp.float32)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        tokens = make_inputs(cfg)
        logits = jax.jit(functools.partial(llama.forward, cfg=cfg))(
            params, tokens)
        assert logits.shape == (2, 32, cfg.vocab_size)
        assert np.isfinite(np.asarray(logits)).all()

    def test_loss_decreases_with_sgd(self):
        cfg = llama.LlamaConfig.tiny(n_layers=2, dtype=jnp.float32)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        tokens = make_inputs(cfg, B=4, L=16)
        loss_grad = jax.jit(jax.value_and_grad(
            functools.partial(llama.loss_fn, cfg=cfg)))
        l0, g = loss_grad(params, tokens)
        params2 = jax.tree.map(lambda p, gi: p - 0.5 * gi, params, g)
        l1, _ = loss_grad(params2, tokens)
        assert float(l1) < float(l0)

    def test_param_specs_align(self):
        cfg = llama.LlamaConfig.tiny()
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        specs = llama.param_specs(cfg)
        jax.tree.map(lambda p, s: None, params, specs)  # same structure
        # every leaf rank matches its spec length
        flat_p = jax.tree.leaves(params)
        flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        for p, s in zip(flat_p, flat_s):
            assert len(s) <= p.ndim


class TestLlamaSharded:
    @pytest.fixture(scope="class")
    def mesh(self):
        return build_mesh(MeshSpec(dp=2, fsdp=2, tp=2))

    def _sharded_forward(self, cfg, mesh, B=4, L=32):
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        specs = llama.param_specs(cfg)
        params = jax.device_put(
            params, jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                                 is_leaf=lambda x: isinstance(x, P)))
        tokens = jax.device_put(
            make_inputs(cfg, B, L),
            NamedSharding(mesh, P(("dp", "fsdp"), None)))
        out = jax.jit(functools.partial(llama.forward, cfg=cfg, mesh=mesh))(
            params, tokens)
        return params, tokens, out

    def test_fsdp_tp_forward_matches_single(self, mesh):
        cfg = llama.LlamaConfig.tiny(dtype=jnp.float32)
        params, tokens, out = self._sharded_forward(cfg, mesh)
        expect = jax.jit(functools.partial(llama.forward, cfg=cfg))(
            jax.device_put(jax.tree.map(np.asarray, params)),
            np.asarray(tokens))
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=1e-4, atol=1e-4)

    def test_ring_attention_matches_full(self):
        mesh = build_mesh(MeshSpec(sp=4, tp=2))
        cfg_full = llama.LlamaConfig.tiny(dtype=jnp.float32)
        cfg_ring = llama.LlamaConfig.tiny(dtype=jnp.float32,
                                          attention="ring")
        params = llama.init_params(cfg_full, jax.random.PRNGKey(0))
        tokens = make_inputs(cfg_full, B=2, L=32)
        full = jax.jit(functools.partial(llama.forward, cfg=cfg_full))(
            params, tokens)
        ring = jax.jit(functools.partial(llama.forward, cfg=cfg_ring,
                                         mesh=mesh))(params, tokens)
        np.testing.assert_allclose(np.asarray(ring), np.asarray(full),
                                   rtol=2e-3, atol=2e-3)

    def test_ulysses_attention_matches_full(self):
        mesh = build_mesh(MeshSpec(sp=4, tp=2))
        cfg_full = llama.LlamaConfig.tiny(dtype=jnp.float32)
        cfg_uly = llama.LlamaConfig.tiny(dtype=jnp.float32,
                                         attention="ulysses")
        params = llama.init_params(cfg_full, jax.random.PRNGKey(0))
        tokens = make_inputs(cfg_full, B=2, L=32)
        full = jax.jit(functools.partial(llama.forward, cfg=cfg_full))(
            params, tokens)
        uly = jax.jit(functools.partial(llama.forward, cfg=cfg_uly,
                                        mesh=mesh))(params, tokens)
        np.testing.assert_allclose(np.asarray(uly), np.asarray(full),
                                   rtol=2e-3, atol=2e-3)

    def test_flash_falls_back_on_cpu_mesh(self):
        # attention='flash' on a CPU mesh routes to the blockwise fallback
        # (Mosaic kernels only lower on real TPU) and matches full attention.
        mesh = build_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
        cfg_full = llama.LlamaConfig.tiny(dtype=jnp.float32, n_layers=2)
        cfg_fl = llama.LlamaConfig.tiny(dtype=jnp.float32, n_layers=2,
                                        attention="flash")
        params = llama.init_params(cfg_full, jax.random.PRNGKey(0))
        tokens = make_inputs(cfg_full, B=4, L=32)
        full = jax.jit(functools.partial(llama.forward, cfg=cfg_full))(
            params, tokens)
        fl = jax.jit(functools.partial(llama.forward, cfg=cfg_fl,
                                       mesh=mesh))(params, tokens)
        np.testing.assert_allclose(np.asarray(fl), np.asarray(full),
                                   rtol=2e-3, atol=2e-3)

    def test_ring_loss_with_pow2_seq(self):
        # loss_fn must keep the full (sp-divisible) seq through forward.
        mesh = build_mesh(MeshSpec(sp=4, tp=2))
        cfg = llama.LlamaConfig.tiny(dtype=jnp.float32, n_layers=2,
                                     attention="ring")
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        tokens = make_inputs(cfg, B=2, L=32)
        loss = jax.jit(functools.partial(llama.loss_fn, cfg=cfg,
                                         mesh=mesh))(params, tokens)
        assert np.isfinite(float(loss))

    def test_pipeline_forward_matches_single(self):
        mesh = build_mesh(MeshSpec(pp=2, dp=2, tp=2))
        cfg = llama.LlamaConfig.tiny(dtype=jnp.float32, pp_microbatches=2)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        specs = llama.param_specs(cfg)
        sharded = jax.device_put(
            params, jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                                 is_leaf=lambda x: isinstance(x, P)))
        tokens = make_inputs(cfg, B=4, L=16)
        expect = jax.jit(functools.partial(llama.forward, cfg=cfg))(
            params, tokens)
        got = jax.jit(functools.partial(llama.forward, cfg=cfg, mesh=mesh))(
            sharded, tokens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                                   rtol=1e-4, atol=1e-4)

    def test_pipeline_grads(self):
        """Pipelined grads must MATCH the single-program reference, not just
        be finite — catches shard_map transpose bugs that scale grads by the
        axis size (check_rep is disabled in shard_map_compat)."""
        mesh = build_mesh(MeshSpec(pp=2, fsdp=2, tp=2))
        cfg = llama.LlamaConfig.tiny(dtype=jnp.float32, n_layers=2,
                                     pp_microbatches=2)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        tokens = make_inputs(cfg, B=4, L=16)
        g = jax.jit(jax.grad(functools.partial(
            llama.loss_fn, cfg=cfg, mesh=mesh)))(params, tokens)
        g_ref = jax.jit(jax.grad(functools.partial(
            llama.loss_fn, cfg=cfg, mesh=None)))(params, tokens)
        for got, ref in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=1e-4, atol=1e-5)


class TestMLP:
    def test_train_step_decreases_loss(self):
        cfg = MLPConfig(in_dim=16, hidden=32, out_dim=4)
        params = mlp_init(cfg, jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (64, 16))
        y = jax.random.randint(jax.random.PRNGKey(2), (64,), 0, 4)
        lg = jax.jit(jax.value_and_grad(mlp_loss))
        l0, g = lg(params, (x, y))
        params = jax.tree.map(lambda p, gi: p - 0.1 * gi, params, g)
        l1, _ = lg(params, (x, y))
        assert float(l1) < float(l0)


def _use_interpreted_flash_kernel(monkeypatch):
    """attention="flash" resolves to the Pallas kernels in interpret mode
    (off the TPU the program takes the blockwise branch by itself)."""
    import importlib
    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    monkeypatch.setattr(
        llama, "_make_attn_fn",
        lambda cfg, mesh: functools.partial(fa.flash_attention, blk_q=None,
                                            blk_k=None, interpret=True))
    return fa


class TestRematPolicies:
    """remat_policy must be a pure speed/memory lever: every policy
    computes identical losses AND gradients (ISSUE 7 parity guard)."""

    def _loss_and_grads(self, attention, L, **remat):
        cfg = llama.LlamaConfig.tiny(n_layers=2, dtype=jnp.float32,
                                     attention=attention, **remat)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        tokens = make_inputs(cfg, B=2, L=L)
        loss, grads = jax.jit(jax.value_and_grad(
            functools.partial(llama.loss_fn, cfg=cfg)))(params, tokens)
        return float(loss), grads

    @pytest.mark.parametrize("attention,L", [("full", 16), ("flash", 128)],
                             ids=["full", "flash_kernel"])
    @pytest.mark.parametrize("other", [{"remat_policy": "dots"},
                                       {"remat_policy": "selective"},
                                       {"remat": False}],
                             ids=["dots", "selective", "no_remat"])
    def test_policies_identical_loss_and_grads(self, monkeypatch, attention,
                                               L, other):
        if attention == "flash":
            _use_interpreted_flash_kernel(monkeypatch)
        ref_loss, ref_grads = self._loss_and_grads(attention, L,
                                                   remat_policy="full")
        loss, grads = self._loss_and_grads(attention, L, **other)
        assert loss == pytest.approx(ref_loss, abs=1e-6), other
        for got, ref in zip(jax.tree.leaves(grads),
                            jax.tree.leaves(ref_grads)):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(ref),
                rtol=1e-5, atol=1e-6, err_msg=str(other))

    def test_unknown_policy_raises(self):
        with pytest.raises(ValueError, match="remat_policy"):
            llama.remat_policy_fn("nope")

    @pytest.mark.parametrize("remat,forward_calls", [
        ({"remat_policy": "full"}, 1), ({"remat_policy": "selective"}, 1),
        ({"remat": False}, 1),
        # no dot, so not kept: the backward runs the forward kernel again
        ({"remat_policy": "dots"}, 2), ({"remat_policy": "dots_no_batch"}, 2),
    ], ids=["full", "selective", "no_remat", "dots", "dots_no_batch"])
    def test_forward_kernel_calls_a_layer(self, monkeypatch, remat,
                                          forward_calls):
        """What the remat boundary keeps decides how often the flash
        forward runs: counted in the traced program, not read off a flag."""
        fa = _use_interpreted_flash_kernel(monkeypatch)
        cfg = llama.LlamaConfig.tiny(n_layers=2, dtype=jnp.float32,
                                     attention="flash", **remat)
        assert llama.flash_forward_calls(cfg, 128) == forward_calls
        params = jax.eval_shape(functools.partial(llama.init_params, cfg),
                                jax.random.PRNGKey(0))
        jaxpr = jax.make_jaxpr(jax.grad(functools.partial(
            llama.loss_fn, cfg=cfg)))(
                params, jax.ShapeDtypeStruct((2, 128), jnp.int32))
        # one scan body forward, one backward: three kernels a layer where
        # the forward's outputs are kept, four where they are not
        assert fa.kernel_calls(jaxpr.jaxpr) == {
            "_fwd_kernel": forward_calls, "_dq_kernel": 1, "_dkv_kernel": 1}

    def test_no_kernel_no_forward_calls(self):
        # off the TPU attention="flash" is the blockwise scan: no kernel
        cfg = llama.LlamaConfig.tiny(n_layers=2, attention="flash")
        assert llama.flash_impl() == "blockwise"
        assert llama.flash_forward_calls(cfg, 64) == 0
        assert llama.flash_forward_calls(
            dataclasses.replace(cfg, attention="full"), 64) == 0

    def _saved(self, capsys, cfg, L, policy):
        """Lines of print_saved_residuals for one checkpointed layer:
        behind jax.checkpoint with the policy of that name alone, or
        (policy "scan") behind the boundary _scan_layers builds, which
        is handed the stacked layers and reads the shapes."""
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        lp = jax.tree.map(lambda a: a[0], params["layers"])
        layer = functools.partial(llama._layer, cfg=cfg,
                                  positions=jnp.arange(L),
                                  attn_fn=llama._make_attn_fn(cfg, None))
        if policy == "scan":
            assert cfg.remat and cfg.remat_policy == "full"
            body = llama.remat_scan_body(layer, cfg, params["layers"])
        else:
            body = jax.checkpoint(layer,
                                  policy=llama.remat_policy_fn(policy))
        capsys.readouterr()
        jax.ad_checkpoint.print_saved_residuals(
            body, lp, jnp.zeros((2, L, cfg.dim), cfg.dtype))
        return [ln for ln in capsys.readouterr().out.splitlines()
                if " from the argument " not in ln
                and not ln.endswith("from a constant")]

    # "selective": six projections beside them (nothing in the backward
    # reads mlp_down's output, so the seventh name is never a residual).
    # "scan": the boundary _scan_layers builds under "full", at a shape
    # full_remat_keeps lets keep the one projection (2 x 128 tokens: 262
    # kB a layer with the kernel's two against 591 kB of weights at dim
    # 256) and at one it does not (2 x 1024)
    @pytest.mark.parametrize("policy,dim,L,others", [
        ("full", 64, 128, 0), ("selective", 64, 128, 6),
        ("scan", 256, 128, 1), ("scan", 256, 1024, 0)],
        ids=["full", "selective", "scan_keeps", "scan_over_budget"])
    def test_kernel_outputs_are_saved_by_name(self, monkeypatch, capsys,
                                              policy, dim, L, others):
        _use_interpreted_flash_kernel(monkeypatch)
        cfg = llama.LlamaConfig.tiny(n_layers=2, attention="flash", dim=dim)
        B, BH = 2, 2 * cfg.n_heads
        kept = self._saved(capsys, cfg, L, policy)
        assert len(kept) == 2 + others, kept
        # lse by its name; o by its shape (jax puts a reduce_precision on
        # a kept bf16 value, and the line names that and not the tag)
        assert any(ln.startswith(f"f32[{BH},8,{L}] named 'flash_lse'")
                   for ln in kept), kept
        assert any(ln.startswith(f"bf16[{BH},{L},{cfg.head_dim}] ")
                   for ln in kept), kept
        if policy == "scan":
            assert sum(ln.startswith(f"bf16[{B},{L},{cfg.ffn_dim}] ")
                       for ln in kept) == others, kept

    @pytest.mark.parametrize("policy,L,kept", [
        ("full", 32, 0), ("scan", 32, 1), ("scan", 1024, 0)],
        ids=["by_name", "scan_keeps", "scan_over_budget"])
    @pytest.mark.parametrize("attention", ["full", "flash"])
    def test_full_keeps_only_the_layer_input_without_the_kernel(
            self, capsys, attention, policy, L, kept):
        # attention="flash" off the TPU is the blockwise scan: no value
        # carries the kernel's names, and "full" by its name alone keeps
        # what it always kept; the scan's boundary keeps the ONE named
        # projection where the shapes pay for it, and nothing where not
        cfg = llama.LlamaConfig.tiny(n_layers=2, attention=attention)
        saved = self._saved(capsys, cfg, L, policy)
        assert len(saved) == kept, saved
        assert all(ln.startswith(f"bf16[2,{L},{cfg.ffn_dim}] ")
                   for ln in saved), saved

    #: 6 layers of mistral7b-train-1chip's widths in bf16: the budget
    CELL_BUDGET = 6 * 218_112_000 * 2
    UP, O_LSE = 4096 * 14336 * 2, 32 * 4096 * (128 * 2 + 8 * 4)  # a row

    @pytest.mark.parametrize("kernel,B,L,names,held", [
        (True, 2, 4096, ("flash_o", "flash_lse", "mlp_up"),
         6 * 2 * (UP + O_LSE)),                       # 1.86 GB of 2.62
        (True, 3, 4096, ("flash_o", "flash_lse"), 6 * 3 * O_LSE),
        (True, 2, 8192, ("flash_o", "flash_lse"), 6 * 2 * 2 * O_LSE),
        (False, 2, 4096, ("mlp_up",), 6 * 2 * UP),
        (False, 4, 4096, (), 0)],
        ids=["cell", "3x4096", "2x8192", "cell_no_kernel", "4x4096_none"])
    def test_full_remat_keeps_follows_the_shapes(self, monkeypatch, kernel,
                                                 B, L, names, held):
        """The rule alone, on shapes (nothing is computed): at the train
        cell's shapes the ONE projection is kept beside the kernel's two,
        with a row more or rows twice as long it is not, and "full" is
        what it was; the kernel's outputs count where the body has them."""
        if kernel:
            _use_interpreted_flash_kernel(monkeypatch)
        cfg = llama.LlamaConfig(
            vocab_size=32768, dim=4096, n_layers=6, n_heads=32,
            n_kv_heads=8, ffn_dim=14336, rope_theta=1e6, attention="flash")
        stacked = jax.eval_shape(functools.partial(llama.init_params, cfg),
                                 jax.random.PRNGKey(0))["layers"]
        lp = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), stacked)
        body = functools.partial(llama._layer, cfg=cfg,
                                 positions=jnp.arange(L),
                                 attn_fn=llama._make_attn_fn(cfg, None))
        x = jax.ShapeDtypeStruct((B, L, cfg.dim), cfg.dtype)
        assert llama.full_remat_keeps(body, (lp, x), stacked, cfg.dtype) \
            == {"names": names, "bytes": held,
                "budget_bytes": self.CELL_BUDGET}

    def test_full_remat_keeps_nothing_a_body_does_not_name(self):
        # models/mixtral.py's layer has no mlp_up: whatever the shapes
        stacked = {"w": jax.ShapeDtypeStruct((4, 64, 64), jnp.float32)}
        lp = {"w": jax.ShapeDtypeStruct((64, 64), jnp.float32)}
        x = jax.ShapeDtypeStruct((2, 8, 64), jnp.bfloat16)
        assert llama.full_remat_keeps(
            lambda lp, x: x @ lp["w"].astype(x.dtype), (lp, x), stacked,
            jnp.bfloat16) == {"names": (), "bytes": 0,
                              "budget_bytes": 4 * 64 * 64 * 2}

    def test_cast_in_its_turn_changes_no_value(self):
        """_scan_layers casts each layer's weights in that layer's turn,
        after adding a zero that depends on the scan's counter, and holds
        them (_in_its_turn, so that no compute-dtype copy of the whole
        stack is made): loss and every gradient equal, bit for bit, those
        of a plain scan over the masters, and the gradients come back in
        the masters' dtype."""
        cfg = llama.LlamaConfig.tiny(n_layers=2)
        assert cfg.dtype == jnp.bfloat16 and cfg.param_dtype == jnp.float32
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        tokens = make_inputs(cfg, B=2, L=16)

        def over_the_masters(params, tokens):
            body = jax.checkpoint(
                functools.partial(llama._layer, cfg=cfg,
                                  positions=jnp.arange(tokens.shape[1]),
                                  attn_fn=llama._full_attention),
                policy=llama.remat_policy_fn(cfg.remat_policy))
            x, _ = jax.lax.scan(lambda x, lp: (body(lp, x), None),
                                params["embed"].astype(cfg.dtype)[tokens],
                                params["layers"])
            x = llama._rmsnorm(x, params["final_norm"], cfg.norm_eps)
            logits = jnp.einsum("bld,vd->blv", x,
                                params["embed"].astype(cfg.dtype),
                                preferred_element_type=jnp.float32)
            return llama._nll_mean(logits, tokens)

        loss, grads = jax.jit(jax.value_and_grad(functools.partial(
            llama.loss_fn, cfg=cfg)))(params, tokens)
        ref_loss, ref = jax.jit(jax.value_and_grad(over_the_masters))(
            params, tokens)
        assert float(loss) == float(ref_loss)
        for got, want, p in zip(jax.tree.leaves(grads), jax.tree.leaves(ref),
                                jax.tree.leaves(params)):
            assert got.dtype == p.dtype == jnp.float32
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_in_its_turn_casts_and_changes_no_value(self):
        lp = {"w": jnp.arange(16, dtype=jnp.bfloat16).reshape(4, 4),
              "m": jnp.array([-0.0, 1.0000001, -3.14159, 1e-30], jnp.float32)}
        out = llama._in_its_turn(lp, jnp.int32(3), jnp.bfloat16)
        for k, w in lp.items():
            assert out[k].dtype == jnp.bfloat16
            np.testing.assert_array_equal(np.asarray(out[k]),
                                          np.asarray(w.astype(jnp.bfloat16)))
        # the cotangent of a held weight comes back as it is, in the
        # master's dtype
        g = jax.grad(lambda m: llama._in_its_turn(
            {"m": m}, jnp.int32(0), jnp.bfloat16)["m"].astype(
                jnp.float32).sum())(lp["m"])
        assert g.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(g), np.ones(4, np.float32))


def _llama_scan_case(L=16):
    """L 16: "full" keeps mlp_up (full_remat_keeps: 3 x 16 kB of it
    against 3 x 148 kB of float32 weights); L 256: over, nothing kept."""
    cfg = llama.LlamaConfig.tiny(n_layers=3, dtype=jnp.float32)
    assert cfg.remat and cfg.remat_policy == "full"
    assert cfg.n_kv_heads < cfg.n_heads               # GQA

    def plain(params, tokens):
        # no checkpoint, no held copy: _layer over the masters
        x, _ = jax.lax.scan(
            lambda x, lp: (llama._layer(
                lp, x, cfg, jnp.arange(tokens.shape[1]),
                llama._full_attention), None),
            params["embed"][tokens], params["layers"])
        x = llama._rmsnorm(x, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum("bld,vd->blv", x, params["embed"],
                            preferred_element_type=jnp.float32)
        return llama._nll_mean(logits, tokens)

    # _held's barrier, once in the forward turn and once in the recomputed
    return (functools.partial(llama.loss_fn, cfg=cfg), plain,
            llama.init_params(cfg, jax.random.PRNGKey(0)),
            make_inputs(cfg, B=2, L=L), 2)


def _mixtral_scan_case():
    cfg = mixtral.MixtralConfig.tiny(n_layers=3, dtype=jnp.float32)
    assert cfg.remat
    return (functools.partial(mixtral.loss_fn, cfg=cfg),
            functools.partial(mixtral.loss_fn,
                              cfg=dataclasses.replace(cfg, remat=False)),
            mixtral.init_params(cfg, jax.random.PRNGKey(0)),
            make_inputs(cfg, B=2, L=16), 0)


def _overlap_case(mod, config):
    """mod's fsdp_overlap loss (the prefetch-scheduled scan) on a mesh
    that shards fsdp."""
    mesh = build_mesh(MeshSpec(dp=2, fsdp=4))
    cfg = config.tiny(n_layers=3, dtype=jnp.float32, fsdp_overlap=True)
    # mixtral's specs name ep; this mesh does not
    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, P(*[
            ax if ax in mesh.shape else None for ax in s])),
        mod.param_specs(cfg), is_leaf=lambda x: isinstance(x, P))
    return (functools.partial(mod.loss_fn, cfg=cfg, mesh=mesh),
            functools.partial(mod.loss_fn, mesh=mesh,
                              cfg=dataclasses.replace(cfg, remat=False)),
            jax.device_put(mod.init_params(cfg, jax.random.PRNGKey(0)),
                           shardings),
            jax.device_put(make_inputs(cfg, B=8, L=16),
                           NamedSharding(mesh, P(("dp", "fsdp"), None))), 0)


#: every caller of llama.remat_scan_body that runs on the CPU -> (loss
#: behind cfg's remat boundary, the same loss with no boundary, params,
#: tokens, optimization_barriers the program itself places)
_BOUNDARY_CASES = {
    "llama_scan": _llama_scan_case,
    "llama_scan_over_budget": functools.partial(_llama_scan_case, 256),
    "llama_overlap": functools.partial(_overlap_case, llama,
                                       llama.LlamaConfig),
    "mixtral_scan": _mixtral_scan_case,
    "mixtral_overlap": functools.partial(_overlap_case, mixtral,
                                         mixtral.MixtralConfig),
}


@pytest.mark.parametrize("case", _BOUNDARY_CASES.values(),
                         ids=_BOUNDARY_CASES.keys())
def test_a_scan_bodys_remat_boundary_fences_nothing(case):
    """llama.remat_scan_body builds every layer scan's remat boundary with
    prevent_cse=False: the lowered gradient holds the optimization_barriers
    the program places itself (_held's) and none from jax.checkpoint, whose
    default fences every operand of the rematted turn (one more barrier,
    and on the chip a copy of each operand out of its stack: PERF.md,
    PR 53). The boundary changes no value: float32 on the CPU, loss and
    every gradient are those of the same scan with no boundary at all."""
    with_boundary, without, params, tokens, own = case()
    vag = jax.jit(jax.value_and_grad(with_boundary))
    traced = vag.trace(params, tokens)

    def remats(jaxpr):
        for eqn in jaxpr.eqns:
            if "prevent_cse" in eqn.params:
                yield eqn.params["prevent_cse"]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from remats(sub)

    found = list(remats(traced.jaxpr.jaxpr))
    assert found and not any(found), found
    assert traced.lower().as_text().count("optimization_barrier") == own
    loss, grads = vag(params, tokens)
    ref_loss, ref = jax.jit(jax.value_and_grad(without))(params, tokens)
    assert float(loss) == pytest.approx(float(ref_loss), abs=1e-6)
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(ref)):
        assert got.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("remat,L,same", [
    ({}, 16, False), ({}, 256, True),
    ({"remat_policy": "selective"}, 16, True),
    ({"remat_policy": "dots"}, 16, True), ({"remat": False}, 16, True)],
    ids=["full_keeps", "full_over_budget", "selective", "dots", "no_remat"])
def test_only_a_full_boundary_with_room_is_another_program(monkeypatch,
                                                           remat, L, same):
    """What the shapes decide is the whole of the change: "full" over its
    budget, and every other policy, lower to the text of a boundary built
    from the policy's name alone (remat_scan_body without the stacked
    layers: what every caller got before full_remat_keeps)."""
    cfg = llama.LlamaConfig.tiny(n_layers=3, **remat)
    params = jax.eval_shape(functools.partial(llama.init_params, cfg),
                            jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, L), jnp.int32)

    def text():
        return jax.jit(jax.grad(functools.partial(
            llama.loss_fn, cfg=cfg))).lower(params, tokens).as_text()

    shaped, by_shape = text(), llama.remat_scan_body
    monkeypatch.setattr(llama, "remat_scan_body",
                        lambda body, cfg, stacked=None: by_shape(body, cfg))
    assert (text() == shaped) == same


class TestFsdpOverlap:
    """Explicit prefetch-scheduled fsdp step vs the GSPMD-auto step:
    same loss, same grads — the overlap schedule only moves collectives,
    never the math (ISSUE 7 numeric-parity acceptance)."""

    @pytest.fixture(scope="class")
    def mesh(self):
        return build_mesh(MeshSpec(dp=2, fsdp=4))

    def _place(self, cfg, mesh, B=8, L=16):
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        specs = llama.param_specs(cfg)
        params = jax.device_put(
            params, jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                                 is_leaf=lambda x: isinstance(x, P)))
        tokens = jax.device_put(
            make_inputs(cfg, B, L),
            NamedSharding(mesh, P(("dp", "fsdp"), None)))
        return params, tokens

    def test_overlap_loss_and_grads_match_gspmd(self, mesh):
        cfg = llama.LlamaConfig.tiny(n_layers=2, dtype=jnp.float32)
        params, tokens = self._place(cfg, mesh)
        cfg_ov = dataclasses.replace(cfg, fsdp_overlap=True)
        vag = lambda c: jax.jit(jax.value_and_grad(functools.partial(
            llama.loss_fn, cfg=c, mesh=mesh)))
        l_ref, g_ref = vag(cfg)(params, tokens)
        l_ov, g_ov = vag(cfg_ov)(params, tokens)
        assert float(l_ov) == pytest.approx(float(l_ref), abs=1e-5)
        for got, ref in zip(jax.tree.leaves(g_ov), jax.tree.leaves(g_ref)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=1e-4, atol=1e-5)

    def test_overlap_composes_with_selective_remat(self, mesh):
        cfg = llama.LlamaConfig.tiny(n_layers=2, dtype=jnp.float32,
                                     remat_policy="selective")
        params, tokens = self._place(cfg, mesh)
        cfg_ov = dataclasses.replace(cfg, fsdp_overlap=True)
        l_ref = jax.jit(functools.partial(
            llama.loss_fn, cfg=cfg, mesh=mesh))(params, tokens)
        l_ov = jax.jit(functools.partial(
            llama.loss_fn, cfg=cfg_ov, mesh=mesh))(params, tokens)
        assert float(l_ov) == pytest.approx(float(l_ref), abs=1e-5)

    def test_overlap_rejects_tp_sharding(self):
        mesh = build_mesh(MeshSpec(fsdp=2, tp=2, dp=2))
        cfg = llama.LlamaConfig.tiny(n_layers=2, dtype=jnp.float32,
                                     fsdp_overlap=True)
        params, tokens = self._place(cfg, mesh, B=4)
        with pytest.raises(ValueError, match="fsdp_overlap"):
            jax.jit(functools.partial(
                llama.loss_fn, cfg=cfg, mesh=mesh))(params, tokens)

    def test_overlap_noop_when_fsdp_unsharded(self):
        # fsdp=1 mesh: the flag must route to the normal GSPMD path
        mesh = build_mesh(MeshSpec(dp=8))
        cfg = llama.LlamaConfig.tiny(n_layers=2, dtype=jnp.float32,
                                     fsdp_overlap=True)
        params, tokens = self._place(cfg, mesh)
        loss = jax.jit(functools.partial(
            llama.loss_fn, cfg=cfg, mesh=mesh))(params, tokens)
        assert np.isfinite(float(loss))


class TestInt8MLP:
    def test_int8_flag_changes_path_but_stays_finite(self):
        cfg = llama.LlamaConfig.tiny(n_layers=2, dtype=jnp.float32)
        cfg8 = dataclasses.replace(cfg, int8_mlp=True)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        tokens = make_inputs(cfg, B=2, L=16)
        l_fp, g_fp = jax.jit(jax.value_and_grad(functools.partial(
            llama.loss_fn, cfg=cfg)))(params, tokens)
        l_8, g_8 = jax.jit(jax.value_and_grad(functools.partial(
            llama.loss_fn, cfg=cfg8)))(params, tokens)
        assert np.isfinite(float(l_8))
        assert all(np.isfinite(np.asarray(g)).all()
                   for g in jax.tree.leaves(g_8))
        # quantized path is close to fp (W8A8 dynamic quant, tiny model)
        assert float(l_8) == pytest.approx(float(l_fp), rel=0.05)
