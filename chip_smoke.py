"""chip_smoke.py — the quickest proof that the system still starts on a chip.

Drives the two main paths once, through the entry points a user would call,
at Llama-3-8B's published widths (depth cut to what one chip's HBM holds,
weights random from --seed):

  serve  rt.init() -> serve.run(build_llm_app(...)) -> HTTP /v1/completions
         (8 concurrent prompts of 128-512 tokens, 32 new tokens each, one of
         them streamed) -> the replica's own report and plain-path check
  train  JaxTrainer(loop, ScalingConfig(use_tpu=True, chips_per_worker=1))
         .fit(): 3 optimizer steps of the flash-attention model, each
         train.report()ed, one checkpoint saved

This process NEVER initialises a jax backend. Everything that needs the chip
runs in a worker the cluster runtime leased the chip to, one chip-owning
process alive at a time; every phase ends in a full rt.shutdown() so the
chip is free for the next. There is no CPU mode: a phase that did not run on
a TPU, or took a reference/blockwise branch instead of a kernel, fails.

Output: one JSON object per line. The last line of a run that passed is
exactly {"ok": true, "device": {"platform", "kind", "count"}} as the
chip-holding workers reported it; a failed run names its failures in the
phase's own line and on stderr, and exits 1 without that line (with no
accelerator it prints nothing on stdout at all).

--chips 4 runs ONLY the cross-chip paths and what they are compared with
(one process driving four chips): the same train loop on a fsdp=2 x tp=2
mesh against a one-device run of the same seed, and a tp=4 serving replica
against a tp=1 replica on the same prompts.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import signal
import sys
import tempfile
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

# Llama-3-8B's published widths (models/llama.py LlamaConfig.llama3_8b).
# LLMServer builds its config from LlamaConfig.tiny(**model_config), so every
# width and rope_theta is passed explicitly. Only depth is ever cut.
WIDTHS = dict(vocab_size=128256, dim=4096, n_heads=32, n_kv_heads=8,
              ffn_dim=14336, rope_theta=500000.0)
PUBLISHED_LAYERS = 32

# Serving holds bf16 weights, as a deployment would (fp32 masters are a
# training concern). Per layer 218.1M params = 0.41 GiB; the tied embedding
# 0.98 GiB; the page pool (640 pages x 16 tokens: 8 sequences of 1024 tokens,
# the scratch page, and prefix-cache slack) 0.04 GiB a layer, held three times
# over while the decode loop runs (the pool and two scan copies of it).
# 24 layers: 11.7 GiB resident, 13.7 GiB at the decode loop's peak (compiled
# for v5e: llm/model.py ragged_decode_loop) of the 15.75 GiB the chip offers;
# 26 layers would leave 0.7 GiB, 32 need 17.7.
SERVE = {
    "model": {**WIDTHS, "n_layers": 24, "param_dtype": "bfloat16"},
    "engine": {"page_size": 16, "total_pages": 640, "max_batch": 8,
               "max_seq_len": 1024},
    "prompt_lens": [128, 192, 256, 320, 384, 448, 512, 160],
    "max_tokens": 32,
    "because": "bf16 weights (0.41 GiB a layer + 0.98 GiB embedding) plus a "
               "640-page KV pool held 3x at the decode loop's peak: 24 "
               "layers peak at 13.7 of 15.75 GiB HBM, 32 need 17.7",
}

# Training keeps fp32 master weights and adafactor (what the benchmark's
# training cell trains with): weights + grads alone are 1.75 GiB a layer and 4.2 GiB for the
# 128k-row embedding, and the [B, L, vocab] fp32 logits 2 GiB at 2x2048
# tokens. 4 layers at 2x2048 compile to 14.0 GiB; 4 layers at 4x2048, or 6
# at 2x2048, are refused by the TPU compiler for HBM.
TRAIN = {
    "model": {**WIDTHS, "n_layers": 4, "attention": "flash"},
    "batch": 2, "seq_len": 2048, "steps": 3, "lr": 1e-3,
    "because": "fp32 masters + grads (1.75 GiB a layer, 4.2 GiB embedding) "
               "and 2x2048x128256 fp32 logits: 4 layers compile to 14.0 of "
               "15.75 GiB HBM; 6 layers, or 4 at 4x2048 tokens, are refused",
}

#: a greedy token may differ from the plain path's where bf16 rounding flips
#: a near-tie; the plain path's logit for it must then be within this of its
#: own top logit (random-init logits have unit variance — a wrong page,
#: mask or position costs whole logits)
LOGIT_TOL = 0.25
#: ... and such near-ties are rare: on the chip 242-245 of 256 tokens equal
#: the plain path's (PR 21 runs), so well over this share must
MIN_EQUAL = 0.85
#: flash vs full attention, sharded vs one device: same math in bf16
LOSS_RTOL = 2e-2

DEADLINE_S = 1100          # the contract allows 1200 s, compilation included


def emit(obj: dict, file=None) -> None:
    print(json.dumps(obj, default=str), file=file or sys.stdout, flush=True)


def _keep_stdout_for_results() -> None:
    """The daemons and workers this process starts inherit its stdout and
    print their own lines there (RTPU_HEAD_READY ...). Results are one JSON
    object per line, so: keep the real stdout for emit() alone and hand
    every child stderr as its fd 1."""
    sys.stdout.flush()
    sys.stdout = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)


class PhaseFailed(RuntimeError):
    pass


# ---------------------------------------------------------------------------
# cluster session helpers (parent side — no jax)
# ---------------------------------------------------------------------------

class _Cluster:
    """rt.init() ... rt.shutdown() around one phase; on failure the chip
    workers' stderr tails go to this process's stderr (the machine a chip
    run happened on is gone by the time anyone reads the result)."""

    def __init__(self, chips: int):
        self.chips = chips

    def __enter__(self):
        import ray_tpu as rt
        rt.init()
        self.session = os.environ.get("RTPU_SESSION", "")
        have = rt.cluster_resources().get("TPU", 0)
        if have < self.chips:
            rt.shutdown()
            raise PhaseFailed(
                f"the cluster advertises {have:g} TPU chips, need "
                f"{self.chips}: no accelerator here")
        return rt

    def __exit__(self, exc_type, exc, tb):
        import ray_tpu as rt
        from ray_tpu import serve
        try:
            if exc_type is not None:
                _dump_worker_logs(self.session)
            serve.shutdown()
        finally:
            rt.shutdown()
        return False


def _dump_worker_logs(session: str, tail_lines: int = 40) -> None:
    from ray_tpu.util import log_plane
    log_dir = log_plane.session_log_dir(session)
    if not os.path.isdir(log_dir):
        return
    out_dir = os.path.join("chiprun_out", "smoke_logs", session)
    os.makedirs(out_dir, exist_ok=True)
    for name in sorted(os.listdir(log_dir)):
        path = os.path.join(log_dir, name)
        try:
            shutil.copy(path, out_dir)
            if not name.endswith(".err"):
                continue
            with open(path, errors="replace") as f:
                lines = f.read().splitlines()[-tail_lines:]
        except OSError:
            continue
        if lines:
            sys.stderr.write(f"--- {name} ---\n" + "\n".join(lines) + "\n")


def _cache_state(counts: dict) -> str:
    """warm = programs were loaded from the persistent compile cache; cold =
    none was (every cacheable one was compiled and written); uncached = no
    program took long enough to be cached at all. A warm run can still show
    a miss or two: jax only caches what took a second or more to compile, so
    a program near that line is written by one run and not by the next."""
    if counts.get("cache_hit", 0):
        return "warm"
    return "cold" if counts.get("cache_miss", 0) else "uncached"


def _record_times(phase: str, state: str, times: dict) -> dict:
    """Remember this phase's wall/compile seconds by cache state next to the
    compile cache itself, and return what is known for both states — a cold
    and a warm run of the same checkout read side by side."""
    from ray_tpu.util import compile_cache
    cache_dir = os.environ.get(compile_cache.ENV_VAR) \
        or compile_cache.DEFAULT_DIR
    path = os.path.join(cache_dir, "chip_smoke_times.json")
    try:
        with open(path) as f:
            known = json.load(f)
    except (OSError, ValueError):
        known = {}
    known.setdefault(phase, {})[state] = times
    try:
        os.makedirs(cache_dir, exist_ok=True)
        with open(path, "w") as f:
            json.dump(known, f)
    except OSError:
        pass
    return known[phase]


# ---------------------------------------------------------------------------
# phase 0: which device do leased workers see
# ---------------------------------------------------------------------------

def _device_probe():
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices()), "pid": os.getpid()}


def run_device(chips: int) -> dict:
    """A task leased `chips` TPU chips reports what jax sees there."""
    t0 = time.time()
    with _Cluster(chips) as rt:
        probe = rt.remote(num_cpus=1, resources={"TPU": float(chips)})(
            _device_probe)
        device = rt.get(probe.remote(), timeout=300)
    return {"phase": "device", "device": device,
            "driver_pid": os.getpid(), "wall_s": round(time.time() - t0, 1)}


def check_device(rep: dict, chips: int) -> list:
    d = rep["device"]
    bad = []
    if d["platform"] != "tpu":
        bad.append(f"device: leased worker sees platform {d['platform']!r}, "
                   f"not 'tpu'")
    if d["count"] != chips:
        bad.append(f"device: leased worker sees {d['count']} devices, "
                   f"leased {chips}")
    if d["pid"] == rep["driver_pid"]:
        bad.append("device: probe ran in the driver process")
    return bad


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def make_prompts(lens, vocab: int, seed: int) -> list:
    """Deterministic token prompts, distinct from the first token on (no
    shared prefix: nothing rides the prefix cache)."""
    return [[(seed * 7919 + 104729 * (j + 1) + 31 * i * (j + 3)) % vocab
             for i in range(n)] for j, n in enumerate(lens)]


def _complete(port: int, name: str, prompt: list, max_tokens: int,
              stream: bool) -> dict:
    """One real /v1/completions request; streamed ones are read as SSE."""
    body = {"model": name, "prompt": prompt, "max_tokens": max_tokens,
            "timeout_s": 600, "stream": stream}
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/completions",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    t0 = time.time()
    with urllib.request.urlopen(req, timeout=660) as resp:
        if not stream:
            out = json.loads(resp.read())
            if "choices" not in out:
                raise PhaseFailed(f"completion failed: {out}")
            return {"token_ids": out["choices"][0]["token_ids"],
                    "latency_s": round(time.time() - t0, 3),
                    "streamed": False}
        tokens, chunks, first = [], 0, None
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data: ") or line == "data: [DONE]":
                continue
            item = json.loads(line[len("data: "):])
            if "error" in item:
                raise PhaseFailed(f"stream failed: {item}")
            delta = item["choices"][0]["token_ids"]
            if delta:
                first = first or round(time.time() - t0, 3)
                tokens += delta
                chunks += 1
        return {"token_ids": tokens, "chunks": chunks, "ttft_s": first,
                "latency_s": round(time.time() - t0, 3), "streamed": True}


def _request_all(port: int, name: str, prompts: list, max_tokens: int) -> list:
    """All prompts at once (one thread each); the last one streamed."""
    def one(i):
        try:
            return _complete(port, name, prompts[i], max_tokens,
                             stream=(i == len(prompts) - 1))
        except Exception as e:  # noqa: BLE001 — reported per request
            return {"error": repr(e)}

    with ThreadPoolExecutor(len(prompts)) as pool:
        return list(pool.map(one, range(len(prompts))))


def _deploy_and_query(spec: dict, name: str, tp: int, seed: int,
                      prompts: list) -> dict:
    """build_llm_app -> serve.run -> /v1/completions for every prompt."""
    from ray_tpu import serve
    from ray_tpu.llm import build_llm_app
    engine = {**spec["engine"], "seed": seed, "tp": tp}
    t0 = time.time()
    handle = serve.run(build_llm_app(spec["model"], engine, name=name),
                       timeout_s=420)
    ready_s = round(time.time() - t0, 1)
    port = serve.start_http_proxy()
    t1 = time.time()
    results = _request_all(port, name, prompts, spec["max_tokens"])
    return {"name": name, "tp": tp, "handle": handle, "ready_s": ready_s,
            "requests_s": round(time.time() - t1, 1), "results": results}


def _report(dep: dict) -> None:
    """The replica's own account of where it ran and what it compiled —
    read last, so the HBM peak covers everything the replica did."""
    dep["report"] = dep.pop("handle").engine_report.remote() \
        .result(timeout=120)


def _plain_checks(handle, prompts: list, results: list) -> list:
    """Every answered prompt scored by the replica's plain path."""
    out = []
    for p, r in zip(prompts, results):
        if not r or "error" in r:
            out.append(None)
            continue
        chk = handle.plain_check.remote(
            {"prompt_ids": p, "token_ids": r["token_ids"]}).result(
                timeout=600)
        out.append({"equal": chk["plain_tokens"] == r["token_ids"],
                    "n_equal": sum(a == b for a, b in zip(
                        chk["plain_tokens"], r["token_ids"])),
                    "max_gap": round(max(chk["gap"]), 4)})
    return out


def _compile_wall_s(compile_seconds) -> float:
    """Seconds of the calls that compiled, over every callable of the
    replica's report (engine_report(): one dict a name)."""
    return round(sum(c["wall_s"] for c in (compile_seconds or {}).values()),
                 1)


def _serve_summary(dep: dict, spec: dict) -> dict:
    """One deployment's line: the replica's report plus what the client
    side saw (token ids themselves are left out)."""
    rep = dict(dep["report"], compile_seconds=dep["report"].get(
        "compile_seconds"), compile_counts=dep["report"].get(
            "compile_counts"))
    rep["worker_pid"] = rep.pop("pid")
    return {**{k: dep[k] for k in ("name", "ready_s", "requests_s")}, **rep,
            "requests": [{k: v for k, v in (r or {}).items()
                          if k != "token_ids"} for r in dep["results"]],
            "tokens_out": sum(len((r or {}).get("token_ids", ()))
                              for r in dep["results"]),
            "prompt_tokens": sum(spec["prompt_lens"])}


def _what_ran(spec: dict) -> dict:
    """The published widths, and the depth this run was cut to and why."""
    out = {"widths": WIDTHS,
           "reduced": {"n_layers": spec["model"]["n_layers"],
                       "published_n_layers": PUBLISHED_LAYERS,
                       "because": spec["because"]}}
    if "engine" in spec:
        out.update(param_dtype=spec["model"]["param_dtype"],
                   engine=spec["engine"])
    return out


def run_serve(spec: dict, seed: int) -> dict:
    """One-chip serving phase."""
    t0 = time.time()
    prompts = make_prompts(spec["prompt_lens"], spec["model"]["vocab_size"],
                           seed)
    with _Cluster(1):
        dep = _deploy_and_query(spec, "llm", 1, seed, prompts)
        checks = _plain_checks(dep["handle"], prompts, dep["results"])
        _report(dep)
    out = {"phase": "serve", "driver_pid": os.getpid(), **_what_ran(spec),
           **_serve_summary(dep, spec), "plain_check": checks,
           "prompts_equal_to_plain_path": sum(
               bool(c and c["equal"]) for c in checks),
           "wall_s": round(time.time() - t0, 1)}
    out["compile_s"] = _compile_wall_s(out["compile_seconds"])
    out["cache"] = _cache_state(out["compile_counts"] or {})
    return out


def _check_replica(tag: str, s: dict, spec: dict, driver_pid: int) -> list:
    bad = []
    if s["platform"] != "tpu":
        bad.append(f"{tag}: replica ran on platform {s['platform']!r}, "
                   f"not 'tpu'")
    if s["paged_impl"] != "kernel":
        bad.append(f"{tag}: paged attention impl {s['paged_impl']!r}, "
                   f"not 'kernel'")
    if not 1 <= s["compiled_step_programs"] <= s["step_program_budget"]:
        bad.append(f"{tag}: {s['compiled_step_programs']} compiled step "
                   f"programs, want 1..{s['step_program_budget']}")
    if s["worker_pid"] == driver_pid:
        bad.append(f"{tag}: the engine ran in the driver process")
    want = spec["max_tokens"]
    if len(s["requests"]) != len(spec["prompt_lens"]):
        bad.append(f"{tag}: {len(s['requests'])} of "
                   f"{len(spec['prompt_lens'])} requests were sent")
    for i, r in enumerate(s["requests"]):
        if "error" in r:
            bad.append(f"{tag}: request {i} failed: {r['error']}")
    if s["tokens_out"] != want * len(spec["prompt_lens"]):
        bad.append(f"{tag}: {s['tokens_out']} tokens came back, want "
                   f"{want * len(spec['prompt_lens'])}")
    streamed = [r for r in s["requests"] if r.get("streamed")]
    if not streamed or not all(r.get("chunks", 0) >= 2 for r in streamed):
        bad.append(f"{tag}: the streamed request did not arrive in >= 2 "
                   f"chunks")
    return bad


def _check_plain(tag: str, checks: list, n_tokens: int) -> list:
    """The engine's greedy tokens ARE the plain path's, up to bf16: nearly
    every position equal, and the rest near-ties. Which positions those are
    is not reproducible — concurrent requests interleave differently run to
    run, so a token is computed by the mixed step in one run and by the
    decode loop in the next — hence no demand on any single prompt; how
    many came out identical token for token is reported next to this."""
    bad = []
    if not checks or any(c is None for c in checks):
        return [f"{tag}: plain-path check did not cover every prompt"]
    worst = max(c["max_gap"] for c in checks)
    if not worst <= LOGIT_TOL:
        bad.append(f"{tag}: a greedy token sits {worst} logits under the "
                   f"plain path's choice (tolerance {LOGIT_TOL})")
    equal = sum(c["n_equal"] for c in checks)
    if equal < MIN_EQUAL * n_tokens * len(checks):
        bad.append(f"{tag}: only {equal} of {n_tokens * len(checks)} greedy "
                   f"tokens equal the plain path's (want {MIN_EQUAL:.0%})")
    return bad


def check_serve(out: dict, spec: dict) -> list:
    return _check_replica("serve", out, spec, out["driver_pid"]) \
        + _check_plain("serve", out["plain_check"], spec["max_tokens"])


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def train_loop(config: dict) -> None:
    """train_loop_per_worker: runs in the worker that holds the chip(s)."""
    import dataclasses
    import functools
    import gc

    import jax
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu import train
    from ray_tpu.models import llama
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh
    from ray_tpu.train.train_step import make_train_step, shard_batch
    from ray_tpu.util import compile_cache, compile_tracker

    devices = jax.devices()
    cfg = llama.LlamaConfig(**config["model"])
    B, L, steps = config["batch"], config["seq_len"], config["steps"]
    tracker = compile_tracker.get_global()

    def one_run(tag: str, mesh, save: bool) -> dict:
        shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), llama.param_specs(cfg),
            is_leaf=lambda x: isinstance(x, P))
        # born sharded: no device stages the whole model
        params = jax.jit(functools.partial(llama.init_params, cfg),
                         out_shardings=shardings)(
                             jax.random.PRNGKey(config["seed"]))
        tokens = shard_batch(jax.random.randint(
            jax.random.PRNGKey(config["seed"] + 1), (B, L), 0,
            cfg.vocab_size), mesh)
        held = {}
        for leaf in jax.tree.leaves(params):
            for sh in leaf.addressable_shards:
                held[sh.device.id] = held.get(sh.device.id, 0) \
                    + sh.data.nbytes
        init_fn, step_fn = make_train_step(
            functools.partial(llama.loss_fn, cfg=cfg, mesh=mesh),
            optax.adafactor(config["lr"]))
        opt_state = init_fn(params)
        # the plain path: same weights, same batch, attention="full"
        full_cfg = dataclasses.replace(cfg, attention="full")
        full_loss = float(jax.jit(functools.partial(
            llama.loss_fn, cfg=full_cfg, mesh=mesh))(params, tokens))
        kernel_calls = step_fn.lower(params, opt_state, tokens) \
            .as_text().count("tpu_custom_call")
        losses, step_s, compiled = [], [], []
        for i in range(steps):
            t0, programs = time.time(), step_fn._cache_size()
            params, opt_state, m = step_fn(params, opt_state, tokens)
            losses.append(float(m["loss"]))
            step_s.append(round(time.time() - t0, 3))
            compiled.append(step_fn._cache_size() > programs)
            last = i == steps - 1
            train.report(
                {"run": tag, "step": i, "loss": losses[-1],
                 "grad_norm": float(m["grad_norm"]), "step_s": step_s[-1],
                 "compiled": compiled[-1]},
                checkpoint_tree={"params": params} if save and last
                else None)
        out = {"mesh": {k: v for k, v in mesh.shape.items() if v > 1},
               "losses": losses, "full_attention_loss": full_loss,
               "kernel_calls": kernel_calls, "step_s": step_s,
               "step_compiled": compiled,
               "param_bytes": sum(x.nbytes
                                  for x in jax.tree.leaves(params)),
               "param_bytes_per_device": held}
        del params, opt_state, tokens, m
        gc.collect()
        return out

    ctx = train.get_context()
    runs = {"mesh": one_run("mesh", ctx.global_mesh(),
                            save=config["checkpoint"])}
    hbm = [{"id": d.id, **{k: (d.memory_stats() or {}).get(k) for k in (
        "bytes_in_use", "peak_bytes_in_use", "bytes_limit")}}
        for d in devices]
    if config["compare_one_device"]:
        runs["one_device"] = one_run(
            "one_device", build_mesh(MeshSpec(), devices[:1]), save=False)
    train.report({"summary": {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices), "flash_impl": llama.flash_impl(),
        "worker_pid": os.getpid(), "runs": runs, "hbm_after_mesh_run": hbm,
        "compile_counts": tracker.stats()["counts"] if tracker else None,
        "compile_cache_dir": os.environ.get(compile_cache.ENV_VAR)}})


def run_train(spec: dict, seed: int, chips: int = 1, mesh=None) -> dict:
    """JaxTrainer.fit() on `chips` chips (mesh: fsdp/tp degrees; with more
    than one chip the loop also makes the one-device comparison run)."""
    from ray_tpu import train
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.train.checkpoint import MANIFEST_FILE
    t0 = time.time()
    storage = tempfile.mkdtemp(prefix="chip_smoke_train_")
    config = {**{k: spec[k] for k in ("model", "batch", "seq_len", "steps",
                                      "lr")},
              "seed": seed, "checkpoint": chips == 1,
              "compare_one_device": chips > 1}
    try:
        with _Cluster(chips):
            result = train.JaxTrainer(
                train_loop, train_loop_config=config,
                scaling_config=train.ScalingConfig(
                    num_workers=1, use_tpu=True, chips_per_worker=chips,
                    mesh=MeshSpec(**(mesh or {}))),
                run_config=train.RunConfig(
                    name="chip_smoke", storage_path=storage)).fit()
        history = result.metrics_history
        summary = history[-1]["summary"]
        ckpt_bytes = None
        if result.checkpoint is not None:
            with open(os.path.join(result.checkpoint.path,
                                   MANIFEST_FILE)) as f:
                ckpt_bytes = sum(s["bytes"]
                                 for s in json.load(f)["shards"])
    finally:
        shutil.rmtree(storage, ignore_errors=True)
    out = {"phase": "train", "driver_pid": os.getpid(), **_what_ran(spec),
           "batch_tokens": [spec["batch"], spec["seq_len"]],
           "reported_steps": [h for h in history if "summary" not in h],
           "checkpoint_bytes": ckpt_bytes, **summary,
           "wall_s": round(time.time() - t0, 1)}
    # seconds the steps that compiled took over a step that did not
    run = out["runs"]["mesh"]
    steady = min([t for t, c in zip(run["step_s"], run["step_compiled"])
                  if not c], default=0.0)
    out["compile_s"] = round(sum(
        t - steady for t, c in zip(run["step_s"], run["step_compiled"])
        if c), 1)
    out["cache"] = _cache_state(out["compile_counts"] or {})
    return out


def _close(a: float, b: float) -> bool:
    return abs(a - b) <= LOSS_RTOL * max(abs(a), abs(b))


def check_train(out: dict, spec: dict, chips: int = 1) -> list:
    bad = []
    if out["platform"] != "tpu":
        bad.append(f"train: worker ran on platform {out['platform']!r}, "
                   f"not 'tpu'")
    if out["device_count"] != chips:
        bad.append(f"train: worker saw {out['device_count']} devices, "
                   f"leased {chips}")
    if out["flash_impl"] != "kernel":
        bad.append(f"train: attention='flash' resolved to "
                   f"{out['flash_impl']!r}, not 'kernel'")
    if out["worker_pid"] == out["driver_pid"]:
        bad.append("train: the loop ran in the driver process")
    run = out["runs"]["mesh"]
    losses = run["losses"]
    if run["kernel_calls"] < 3:
        bad.append(f"train: the lowered step holds {run['kernel_calls']} "
                   f"tpu_custom_calls, want the flash fwd + 2 bwd kernels")
    if len(losses) != spec["steps"] \
            or not all(math.isfinite(x) for x in losses):
        bad.append(f"train: losses not finite over {spec['steps']} steps: "
                   f"{losses}")
    elif not losses[-1] < losses[0]:
        bad.append(f"train: loss did not fall: {losses}")
    elif not _close(losses[0], run["full_attention_loss"]):
        bad.append(f"train: step-0 loss {losses[0]} vs attention='full' "
                   f"{run['full_attention_loss']} (rtol {LOSS_RTOL})")
    n_reported = len(out["reported_steps"])
    want = spec["steps"] * (2 if chips > 1 else 1)
    if n_reported != want:
        bad.append(f"train: {n_reported} steps were train.report()ed, "
                   f"want {want}")
    if chips == 1:
        if not out["checkpoint_bytes"] \
                or out["checkpoint_bytes"] < run["param_bytes"]:
            bad.append(f"train: checkpoint holds {out['checkpoint_bytes']} "
                       f"bytes, the params are {run['param_bytes']}")
        return bad
    # cross-chip: the sharded curve against the one-device curve, and the
    # weights really spread over the chips
    one = out["runs"]["one_device"]["losses"]
    if len(one) != len(losses) \
            or not all(_close(a, b) for a, b in zip(losses, one)):
        bad.append(f"train: {run['mesh']} losses {losses} vs one device "
                   f"{one} (rtol {LOSS_RTOL})")
    per_dev = run["param_bytes_per_device"]
    if len(per_dev) != chips or max(per_dev.values()) \
            > 0.6 * run["param_bytes"]:
        bad.append(f"train: params not spread over {chips} chips: "
                   f"{per_dev} of {run['param_bytes']}")
    for d in out["hbm_after_mesh_run"]:
        if d["peak_bytes_in_use"] is None:
            bad.append("train: a device reports no memory_stats()")
        elif d["peak_bytes_in_use"] < 0.05 * run["param_bytes"]:
            bad.append(f"train: device {d['id']} peaked at "
                       f"{d['peak_bytes_in_use']} bytes — it held nothing")
    return bad


# ---------------------------------------------------------------------------
# --chips 4: tp=4 serving against tp=1
# ---------------------------------------------------------------------------

def run_serve_tp(spec: dict, seed: int, tp: int = 4) -> dict:
    """The same prompts through a tp=`tp` replica (one process, `tp`
    chips) and then a tp=1 replica; the tp=1 replica's plain path scores
    both token streams."""
    from ray_tpu import serve
    t0 = time.time()
    prompts = make_prompts(spec["prompt_lens"], spec["model"]["vocab_size"],
                           seed)
    with _Cluster(tp):
        sharded = _deploy_and_query(spec, f"llm-tp{tp}", tp, seed, prompts)
        _report(sharded)
        serve.delete(sharded["name"])      # its worker dies: chips free
        single = _deploy_and_query(spec, "llm-tp1", 1, seed, prompts)
        checks = {
            "tp1": _plain_checks(single["handle"], prompts,
                                 single["results"]),
            f"tp{tp}": _plain_checks(single["handle"], prompts,
                                     sharded["results"])}
        _report(single)
    same = [(a or {}).get("token_ids") == (b or {}).get("token_ids")
            and "token_ids" in (a or {})
            for a, b in zip(sharded["results"], single["results"])]
    out = {"phase": f"serve_tp{tp}", "driver_pid": os.getpid(),
           **_what_ran(spec),
           "sharded": _serve_summary(sharded, spec),
           "single": _serve_summary(single, spec),
           "prompts_with_identical_tokens": sum(same),
           "plain_check": checks, "wall_s": round(time.time() - t0, 1)}
    out["compile_s"] = _compile_wall_s(out["sharded"]["compile_seconds"])
    out["cache"] = _cache_state(out["sharded"]["compile_counts"] or {})
    return out


def check_serve_tp(out: dict, spec: dict, tp: int = 4) -> list:
    pid = out["driver_pid"]
    sh = out["sharded"]
    bad = _check_replica(f"serve_tp{tp}", sh, spec, pid) \
        + _check_replica("serve_tp1", out["single"], spec, pid)
    # tp sums four bf16 partial products where tp=1 rounds once, so the two
    # replicas part ways at near-ties too: the tp=1 replica's plain path
    # must accept the sharded replica's tokens as it accepts its own
    for side in ("tp1", f"tp{tp}"):
        bad += _check_plain(f"serve_{side}", out["plain_check"][side],
                            spec["max_tokens"])
    # really spread: no device holds (or ever held) the whole model
    whole = sh["param_bytes"] + sh["kv_bytes"]
    if len(sh["devices"]) != tp:
        bad.append(f"serve_tp{tp}: engine state sits on "
                   f"{len(sh['devices'])} devices, want {tp}")
    for d in sh["devices"]:
        if d["engine_bytes"] > 0.6 * whole:
            bad.append(f"serve_tp{tp}: device {d['id']} holds "
                       f"{d['engine_bytes']} of {whole} engine bytes")
        if d["peak_bytes_in_use"] is None:
            bad.append(f"serve_tp{tp}: device {d['id']} reports no "
                       f"memory_stats()")
        elif d["peak_bytes_in_use"] > 0.8 * whole:
            bad.append(f"serve_tp{tp}: device {d['id']} peaked at "
                       f"{d['peak_bytes_in_use']} bytes — the whole model "
                       f"({whole}) was staged on it")
    return bad


# ---------------------------------------------------------------------------

def _phases(chips: int, seed: int) -> list:
    """(name, run, check) per phase, in order."""
    device = ("device", lambda: run_device(chips),
              lambda out: check_device(out, chips))
    if chips == 1:
        return [device,
                ("serve", lambda: run_serve(SERVE, seed),
                 lambda out: check_serve(out, SERVE)),
                ("train", lambda: run_train(TRAIN, seed),
                 lambda out: check_train(out, TRAIN))]
    return [device,
            ("train", lambda: run_train(TRAIN, seed, chips=chips,
                                        mesh={"fsdp": 2, "tp": 2}),
             lambda out: check_train(out, TRAIN, chips=chips)),
            (f"serve_tp{chips}", lambda: run_serve_tp(SERVE, seed, chips),
             lambda out: check_serve_tp(out, SERVE, chips))]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the cross-chip paths and what they are "
                         "compared with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    _keep_stdout_for_results()

    def on_deadline(*_):
        raise PhaseFailed(f"not done after {DEADLINE_S}s")
    signal.signal(signal.SIGALRM, on_deadline)
    signal.alarm(DEADLINE_S)

    failures, device = [], None
    for name, run, check in _phases(args.chips, args.seed):
        try:
            out = run()
            bad = check(out)
        except Exception as e:  # noqa: BLE001 — a failed phase fails the
            import traceback    # run; later phases still report
            traceback.print_exc()
            out, bad = {"phase": name}, [f"{name}: {e!r}"]
        if "cache" in out:
            out["seconds_by_cache_state"] = _record_times(
                name, out["cache"], {"wall_s": out["wall_s"],
                                     "compile_s": out["compile_s"]})
        out["failures"] = bad
        failures += bad
        if name == "device":
            if bad:
                emit(out, file=sys.stderr)   # no accelerator: no result
                break
            device = out["device"]
        emit(out)
    signal.alarm(0)
    if failures:
        for f in failures:
            sys.stderr.write(f"chip_smoke FAILED: {f}\n")
        return 1
    emit({"ok": True, "device": {"platform": device["platform"],
                                 "kind": device["kind"],
                                 "count": device["count"]}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
