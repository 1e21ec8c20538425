"""Drive a training job: JaxTrainer.fit() with a loop that runs in the
worker the runtime leased the chip(s) to.

The loop is the user's part of a pre-training job as chip_smoke.py wrote
it (weights born sharded from the seed, make_train_step over llama.loss_fn,
adafactor, train.report every step), with the model and the batch from the
cell's files. Order inside the worker:
  set-up   weights and optimizer state -> the plain reference's loss on
           batch 0 and these weights -> warm-up steps (the step compiles
           twice today: PERF.md, Open questions)
  window   steps, each ending in a blocked read of its loss, until
           --seconds have passed; the window closes with the step that
           crosses the line, so every counted step is whole
"""

from __future__ import annotations

import os
import shutil
import time
from typing import Dict

from benchmark import checks, cluster


def train_loop(cfg: Dict) -> None:
    """train_loop_per_worker (runs where the chips are)."""
    import functools

    import jax
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark import loadgen, reference, trace_reduce
    from ray_tpu import train
    from ray_tpu.models import llama
    from ray_tpu.train.train_step import make_train_step, shard_batch
    from ray_tpu.util import compile_tracker

    devices = jax.devices()
    mix, seed = cfg["traffic"], cfg["seed"]
    lcfg = llama.LlamaConfig(**cfg["model"])
    mesh = train.get_context().global_mesh()
    tracker = compile_tracker.get_global()

    def counts():
        return dict(tracker.stats()["counts"]) if tracker else None

    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), llama.param_specs(lcfg),
        is_leaf=lambda x: isinstance(x, P))
    params = jax.jit(functools.partial(llama.init_params, lcfg),
                     out_shardings=shardings)(
                         jax.random.PRNGKey(seed % (2 ** 31)))
    batches = loadgen.train_batches(mix, seed, lcfg.vocab_size)
    init_fn, step_fn = make_train_step(
        functools.partial(llama.loss_fn, cfg=lcfg, mesh=mesh),
        optax.adafactor(cfg["trainer"]["learning_rate"]))
    opt_state = init_fn(params)
    ref_loss = float(reference.mean_nll(
        params, shard_batch(batches[0], mesh), reference.dims_of(lcfg)))

    def one_step(i):
        nonlocal params, opt_state
        batch = shard_batch(batches[i % len(batches)], mesh)
        params, opt_state, m = step_fn(params, opt_state, batch)
        return float(m["loss"])               # blocks until the step is done

    warm_losses = [one_step(i) for i in range(mix["warmup_steps"])]

    tracing, trace = False, {}
    counts_open, t_open = counts(), time.time()
    mono_open = time.monotonic()
    losses, ends, step_s = [], [], []
    i = mix["warmup_steps"]
    while True:
        if cfg["trace"] and not trace and len(ends) == mix["trace_after_steps"]:
            trace_reduce.start_trace(cfg["trace_dir"])
            tracing, trace["start"] = True, time.monotonic()
            trace["first_step"] = len(ends)
        t_step = time.monotonic()
        losses.append(one_step(i))
        ends.append(time.monotonic() - mono_open)
        step_s.append(time.monotonic() - t_step)
        i += 1
        if tracing and len(ends) - trace["first_step"] >= mix["trace_steps"]:
            trace["stop"] = time.monotonic()   # writing it takes seconds
            jax.profiler.stop_trace()
            tracing = False
            trace["steps"] = len(ends) - trace["first_step"]
        train.report({"step": i, "loss": losses[-1]})
        if ends[-1] >= cfg["seconds"]:
            break
    if tracing:
        trace.update(stop=time.monotonic(),
                     steps=len(ends) - trace["first_step"])
        jax.profiler.stop_trace()
    counts_close = counts()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    train.report({"summary": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "flash_impl": llama.flash_impl(),
        "pid": os.getpid(),
        "memory_peak_bytes": max([p for p in peaks if p is not None],
                                 default=None),
        "bytes_limit": (devices[0].memory_stats() or {}).get("bytes_limit"),
        "reference_loss": ref_loss, "warm_losses": warm_losses,
        "losses": losses, "step_ends": ends, "step_s": step_s,
        "t_open_wall": t_open,
        "counts_open": counts_open, "counts_close": counts_close,
        "trace": {k: v for k, v in trace.items()},
        "tokens_per_step": int(np.prod(batches.shape[1:])),
        "compile_cache_dir": jax.config.jax_compilation_cache_dir}})


def run(ctx: Dict) -> Dict:
    from ray_tpu import train
    from ray_tpu.parallel.mesh import MeshSpec
    config, mix = ctx["config"], ctx["traffic"]
    storage = os.path.join(ctx["scratch"], "train_storage")
    trace_dir = os.path.join(ctx["scratch"], "trace")
    for d in (storage, trace_dir):
        shutil.rmtree(d, ignore_errors=True)
    os.makedirs(storage, exist_ok=True)
    loop_cfg = {"model": cluster.llama_fields(config),
                "trainer": config["trainer"], "traffic": mix,
                "seed": ctx["seed"], "seconds": ctx["seconds"],
                "trace": bool(ctx["trace"]), "trace_dir": trace_dir}
    timing: Dict[str, float] = {}
    with cluster.Cluster(ctx["chips"]):
        timing["cluster_up_s"] = time.time() - ctx["t_start"]
        result = train.JaxTrainer(
            train_loop, train_loop_config=loop_cfg,
            scaling_config=train.ScalingConfig(
                num_workers=1, use_tpu=True, chips_per_worker=ctx["chips"],
                mesh=MeshSpec(**config["trainer"].get("mesh", {}))),
            run_config=train.RunConfig(name="bench", storage_path=storage)
        ).fit()
    s = result.metrics_history[-1]["summary"]
    if not ctx["rehearse"] and (s["platform"] != "tpu"
                                or s["count"] != ctx["chips"]
                                or s["flash_impl"] != "kernel"):
        raise cluster.NoAccelerator(
            f"the trainer ran on {s['platform']} x {s['count']} with "
            f"attention {s['flash_impl']!r}; the cell needs "
            f"{ctx['chips']} TPU chip(s) and the kernels")
    timing["setup_s"] = s["t_open_wall"] - ctx["t_start"]
    faults = checks.training(s["losses"], s["warm_losses"][0],
                             s["reference_loss"])
    reported = [h for h in result.metrics_history if "summary" not in h]
    if len(reported) != len(s["losses"]):
        faults.append(f"{len(reported)} steps were train.report()ed, "
                      f"{len(s['losses'])} ran")
    if s["counts_open"] != s["counts_close"]:
        faults.append(f"something compiled inside the window: "
                      f"{s['counts_open']} -> {s['counts_close']}")
    tr = s["trace"]
    trace = {"dir": trace_dir, "start": {"monotonic": tr["start"]},
             "stop": {"monotonic": tr["stop"]},
             "steps": tr["steps"]} if tr.get("stop") else {}
    return {
        "kind": "train_job", "config": config, "traffic": mix,
        "timing": timing, "device": s, "train": s,
        "window_s": s["step_ends"][-1], "trace": trace,
        "attempted": len(s["losses"]), "failed": 0 if not faults else sum(
            1 for f in faults if f.startswith("losses not finite")),
        "faults": faults,
        "offered": {"tokens_per_step": s["tokens_per_step"]},
        "notes": {"reference_loss": s["reference_loss"],
                  "step0_loss": s["warm_losses"][0],
                  "first_loss": s["losses"][0], "last_loss": s["losses"][-1],
                  "compile_counts_at_close": s["counts_close"],
                  # where a far-off run lost its time: in a step, or between
                  "median_step_s": sorted(s["step_s"])[len(s["step_s"]) // 2],
                  "slowest_step_s": max(s["step_s"]),
                  "between_steps_s": s["step_ends"][-1] - sum(s["step_s"])},
    }
