"""Multi-host JaxTrainer: 2 worker processes x 4 virtual CPU devices form
ONE 8-device global mesh via jax.distributed, train tiny-Llama FSDP, and
match the single-process loss (VERDICT round-1 item 5 done-criterion;
reference analog: torch process-group rendezvous, train/torch/config.py:66).
"""

import time

import pytest

import ray_tpu as rt
from ray_tpu import train
from ray_tpu.parallel.mesh import MeshSpec


def _make_loop():
    """Defined inside a function so cloudpickle ships it BY VALUE (worker
    processes cannot import the test module)."""
    def loop(cfg):
        import jax
        import jax.numpy as jnp
        import numpy as np
        import optax

        from ray_tpu.models import llama
        from ray_tpu.train.train_step import (make_train_step, shard_batch,
                                              shard_params)

        ctx = train.get_context()
        assert jax.process_count() == cfg["expect_processes"]
        assert len(jax.devices()) == 8, jax.devices()
        mesh = ctx.global_mesh()
        assert mesh.shape["fsdp"] == 8

        mcfg = llama.LlamaConfig.tiny(n_layers=2)
        params = llama.init_params(mcfg, jax.random.PRNGKey(11))
        with mesh:
            params = shard_params(params, mesh, llama.param_specs(mcfg))
            opt = optax.sgd(1e-2)
            init_fn, step_fn = make_train_step(
                lambda p, b: llama.loss_fn(p, b, mcfg), opt)
            opt_state = init_fn(params)
            rng = np.random.default_rng(11)
            for _ in range(3):
                batch = rng.integers(
                    0, mcfg.vocab_size, (8, 32)).astype(np.int32)
                batch = shard_batch(jnp.asarray(batch), mesh)
                params, opt_state, metrics = step_fn(
                    params, opt_state, batch)
            train.report({"loss": float(metrics["loss"])})
    return loop


@pytest.fixture(scope="module")
def cluster_rt():
    rt.init(num_cpus=4, _system_config={
        "object_store_memory_bytes": 128 * 1024 * 1024,
    })
    yield rt
    rt.shutdown()


def _fit(num_workers, local_devices, name):
    trainer = train.JaxTrainer(
        _make_loop(),
        train_loop_config={"expect_processes": num_workers},
        scaling_config=train.ScalingConfig(
            num_workers=num_workers,
            mesh=MeshSpec(fsdp=-1),
            jax_distributed=True,
            jax_platform="cpu",
            local_device_count=local_devices),
        run_config=train.RunConfig(name=name))
    return trainer.fit()


def test_two_process_global_mesh_matches_single(cluster_rt, tmp_path):
    multi = _fit(2, 4, "mh2")
    single = _fit(1, 8, "mh1")
    assert multi.metrics["loss"] == pytest.approx(
        single.metrics["loss"], rel=2e-4), \
        (multi.metrics, single.metrics)


# ---------------------------------------------------------------- elastic

def _make_elastic_loop():
    """Worker loop for the elastic test: fixed batch (loss strictly
    decreases), checkpoint every step, rank 1 kills itself once."""
    def loop(cfg):
        import os
        import time

        import jax
        import jax.numpy as jnp
        import numpy as np
        import optax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ray_tpu.models import llama
        from ray_tpu.train.train_step import make_train_step, shard_batch

        ctx = train.get_context()
        mesh = ctx.global_mesh()
        n_dp = mesh.shape["dp"]

        # dp is THE elastic axis: params replicate (re-shard onto any world
        # size), the global batch is one fixed row tiled to dp — so the
        # mean loss is directly comparable across world sizes and strictly
        # decreasing under SGD (continuity check below).
        mcfg = llama.LlamaConfig.tiny(n_layers=2)
        params = llama.init_params(mcfg, jax.random.PRNGKey(7))
        opt = optax.sgd(5e-2)
        init_fn, step_fn = make_train_step(
            lambda p, b: llama.loss_fn(p, b, mcfg), opt)
        opt_state = init_fn(params)
        restored = ctx.get_checkpoint() is not None
        if restored:
            # restore re-shards host-numpy leaves onto the NEW (smaller)
            # mesh — the elastic re-mesh path under test
            state = ctx.get_checkpoint().load(
                target={"params": params, "opt": opt_state})
            params, opt_state = state["params"], state["opt"]
        with mesh:
            replicated = jax.tree.map(
                lambda x: jax.device_put(x, NamedSharding(mesh, P())),
                params)
            params = replicated
            opt_state = jax.tree.map(
                lambda x: jax.device_put(x, NamedSharding(mesh, P())),
                opt_state)
            rng = np.random.default_rng(3)
            row = rng.integers(0, mcfg.vocab_size, (1, 32)).astype(np.int32)
            fixed = np.tile(row, (n_dp, 1))
            while ctx.step < cfg["total_steps"]:
                if (ctx.get_rank() == 1 and ctx.step == cfg["kill_at"]
                        and not os.path.exists(cfg["marker"])):
                    open(cfg["marker"], "w").close()
                    # die only once the step's checkpoint is COMMITTED:
                    # report() returns when this rank's shard is down,
                    # rank 0 writes the manifest after every rank's, and
                    # a group torn down in between restores one step back
                    deadline = time.monotonic() + 120
                    while not os.path.exists(cfg["committed"]) \
                            and time.monotonic() < deadline:
                        time.sleep(0.05)
                    os._exit(1)
                batch = shard_batch(jnp.asarray(fixed), mesh, spec=P("dp"))
                params, opt_state, metrics = step_fn(
                    params, opt_state, batch)
                train.report(
                    {"loss": float(metrics["loss"]),
                     "world_size": ctx.get_world_size(),
                     "n_devices": len(jax.devices()),
                     "restored": restored},
                    checkpoint_tree={"params": params, "opt": opt_state})
    return loop


def test_elastic_shrink_on_worker_loss(cluster_rt, tmp_path):
    """Kill 1 of 4 workers mid-run: the ScalingPolicy restarts the group
    at 3 workers, the mesh re-resolves over 6 devices, training restores
    from the last checkpoint and the loss keeps decreasing (VERDICT #2
    done-criterion; reference: train/v2 scaling_policy.py:29)."""
    marker = str(tmp_path / "killed-once")
    kill_at = 3
    committed = str(tmp_path / "elastic1" / f"checkpoint_{kill_at:08d}"
                    / "MANIFEST.json")
    # capacity-driven initial sizing is part of the policy under test:
    # wait until the previous tests' actors have released their CPUs so
    # the run deterministically starts at the full 4 workers
    deadline = time.monotonic() + 120
    while rt.available_resources().get("CPU", 0) < 4 and \
            time.monotonic() < deadline:
        time.sleep(0.2)
    trainer = train.JaxTrainer(
        _make_elastic_loop(),
        train_loop_config={"total_steps": 6, "kill_at": kill_at,
                           "marker": marker, "committed": committed},
        scaling_config=train.ScalingConfig(
            num_workers=4,
            min_workers=2,
            # aggressive poll ON PURPOSE: the killed worker's freed CPU
            # reads as capacity gain immediately, and only the
            # grow_cooldown_s hysteresis (VERDICT r4 #8) keeps the
            # shrunken group from bouncing straight back to 4 — this
            # test now also covers kill+immediate-capacity-return
            # restarting AT MOST once (world_size stays 3 to the end)
            grow_poll_s=0.5,
            grow_cooldown_s=120.0,
            mesh=MeshSpec(dp=-1),
            jax_distributed=True,
            jax_platform="cpu",
            local_device_count=2),
        run_config=train.RunConfig(
            name="elastic1",
            storage_path=str(tmp_path),  # fresh per invocation: a stale
            # results dir would restore past total_steps and no-op the run
            failure_config=train.FailureConfig(max_failures=2)))
    result = trainer.fit()
    assert result.error is None, result.error
    history = result.metrics_history
    # the surviving run resumed at kill_at+1 on a 3-worker, 6-device mesh
    assert history[0]["_step"] == kill_at + 1, history[0]
    assert history[0]["restored"] is True
    assert result.metrics["world_size"] == 3
    assert result.metrics["n_devices"] == 6
    assert history[-1]["_step"] == 6
    # loss continuity: fixed batch + SGD decreases monotonically, so the
    # restored step must be BELOW the loss recorded at the kill-step
    # checkpoint (a re-initialized model would jump back to ~log(vocab))
    from ray_tpu.train.checkpoint import CheckpointManager
    killed_ckpt_metrics = __import__("json").load(open(
        CheckpointManager(result.path).dir_for(kill_at) + "/metrics.json"))
    assert history[0]["loss"] < killed_ckpt_metrics["loss"], \
        (history[0], killed_ckpt_metrics)


def test_elastic_requires_fill_axis(cluster_rt):
    trainer = train.JaxTrainer(
        _make_elastic_loop(),
        train_loop_config={},
        scaling_config=train.ScalingConfig(
            num_workers=2, min_workers=1, mesh=MeshSpec(fsdp=2)),
        run_config=train.RunConfig(name="elastic-bad"))
    with pytest.raises(ValueError, match="fill"):
        trainer.fit()


def test_elastic_policy_sizing():
    from ray_tpu.train.scaling_policy import ElasticScalingPolicy
    pol = ElasticScalingPolicy(2, 8, {"CPU": 2.0})
    assert pol.initial_size(lambda: {"CPU": 16.0}) == 8
    assert pol.initial_size(lambda: {"CPU": 9.0}) == 4
    assert pol.initial_size(lambda: {"CPU": 1.0}) == 2   # floor
    assert pol.after_failure(5, None) == 4
    assert pol.after_failure(2, None) == 2               # never below min


def test_elastic_grow_on_capacity_gain(cluster_rt, tmp_path):
    """Start capacity-constrained at 2 workers; free capacity mid-run and
    the grow monitor interrupts + restarts the group at 4, restored from
    the latest checkpoint (VERDICT #2 'on capacity gain, N+k')."""
    started_flag = str(tmp_path / "started")

    @rt.remote(num_cpus=2)
    class Hog:
        def ping(self):
            return True

    hog = Hog.remote()
    rt.get(hog.ping.remote())  # 2 of 4 CPUs held -> initial fit = 2
    # wait until the head's accounting reflects the hog, or initial_size
    # would optimistically start at 4 with two actors pending
    deadline = time.monotonic() + 30
    while rt.available_resources().get("CPU", 4) > 2 and \
            time.monotonic() < deadline:
        time.sleep(0.2)
    assert rt.available_resources().get("CPU", 0) <= 2

    def loop(cfg):
        import os
        import time as _t

        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        ctx = train.get_context()
        mesh = ctx.global_mesh()
        n = mesh.shape["dp"]
        arr = jax.device_put(jnp.arange(float(n)), NamedSharding(mesh, P("dp")))
        while ctx.step < cfg["steps"]:
            if ctx.get_rank() == 0 and ctx.step >= 1:
                open(cfg["started_flag"], "w").close()
            _t.sleep(0.25)
            # sharded tree -> checkpoint gather is a collective (lockstep)
            train.report({"world_size": ctx.get_world_size()},
                         checkpoint_tree={"x": arr, "step": ctx.step})

    trainer = train.JaxTrainer(
        loop,
        train_loop_config={"steps": 40, "started_flag": started_flag},
        scaling_config=train.ScalingConfig(
            num_workers=4,
            min_workers=1,
            grow_poll_s=0.5,
            mesh=MeshSpec(dp=-1),
            jax_distributed=True,
            jax_platform="cpu",
            local_device_count=2),
        run_config=train.RunConfig(
            name="elastic-grow", storage_path=str(tmp_path),
            failure_config=train.FailureConfig(max_failures=1)))

    # free the hog's 2 CPUs once the constrained group is actually training
    def _free_hog():
        deadline = time.monotonic() + 120
        import os
        while not os.path.exists(started_flag) and \
                time.monotonic() < deadline:
            time.sleep(0.2)
        rt.kill(hog)

    import threading
    threading.Thread(target=_free_hog, daemon=True).start()
    result = trainer.fit()
    assert result.error is None, result.error
    assert result.metrics["world_size"] == 4, result.metrics
    # restored continuation, not a from-scratch restart
    assert result.metrics_history[0]["_step"] > 1, result.metrics_history[0]
    assert result.metrics_history[-1]["_step"] == 40


def test_two_slice_hybrid_mesh_across_processes(cluster_rt):
    """2 worker processes x 4 devices = 2 'slices': dp spans slices (DCN)
    while fsdp stays inside each process's devices (ICI) — the multi-slice
    hybrid mesh trained through the real multi-process path
    (MeshSpec.dcn_dp; slice grouping falls out of process_index)."""
    def loop(cfg):
        import jax
        import jax.numpy as jnp
        import numpy as np
        import optax

        from ray_tpu.models import llama
        from ray_tpu.train.train_step import (make_train_step, shard_batch,
                                              shard_params)

        ctx = train.get_context()
        mesh = ctx.global_mesh()
        assert dict(mesh.shape) == {"pp": 1, "dp": 2, "fsdp": 4,
                                    "sp": 1, "tp": 1}, mesh.shape
        # slice locality: each dp block's devices live on ONE process
        for b in range(2):
            procs = {d.process_index for d in mesh.devices[:, b].flatten()}
            assert len(procs) == 1, (b, procs)

        mcfg = llama.LlamaConfig.tiny(n_layers=2)
        params = llama.init_params(mcfg, jax.random.PRNGKey(11))
        with mesh:
            params = shard_params(params, mesh, llama.param_specs(mcfg))
            init_fn, step_fn = make_train_step(
                lambda p, b: llama.loss_fn(p, b, mcfg), optax.sgd(1e-2))
            opt_state = init_fn(params)
            rng = np.random.default_rng(11)
            batch = rng.integers(0, mcfg.vocab_size, (8, 32)).astype(np.int32)
            batch = shard_batch(jnp.asarray(batch), mesh)
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            loss = float(metrics["loss"])
            assert np.isfinite(loss)
            train.report({"loss": loss})

    result = train.JaxTrainer(
        loop,
        scaling_config=train.ScalingConfig(
            num_workers=2,
            mesh=MeshSpec(dcn_dp=2, fsdp=-1),
            jax_distributed=True,
            jax_platform="cpu",
            local_device_count=4),
        run_config=train.RunConfig(name="hybrid2")).fit()
    assert result.error is None, result.error
