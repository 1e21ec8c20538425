"""Cluster bring-up for a run (parent side: this process never initialises
a jax backend — the workers the runtime leases the chips to own them).

Copied from chip_smoke.py's recipe (rt.init -> work -> serve.shutdown ->
rt.shutdown, worker stderr tails on failure) rather than imported: the
benchmark takes from the program only the system under test.
"""

from __future__ import annotations

import os
import shutil
import sys
import time
from typing import Dict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: what a run leaves behind (session logs, the trace, trainer storage)
SCRATCH = os.path.join(ROOT, ".bench_scratch")


def prepare_environment(config: Dict, rehearse_devices: int = 0) -> None:
    """What every process of the run inherits: the configuration's own
    deployment variables, the session directory inside the checkout, and
    the compile cache. The compile cache sits at a
    fixed path inside the checkout unless the machine placed it; every
    program is cached, however fast it compiled (jax's default keeps only
    what took a second or more, which made programs near that line cached
    by one run and not by the next)."""
    for key, value in config.get("deployment_settings", {}).get(
            "env", {}).items():
        os.environ[key] = value
    os.environ["RTPU_session_dir"] = os.path.join(SCRATCH, "session")
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # worker output stays in the session's log files (dumped on failure)
    os.environ.setdefault("RTPU_log_to_driver", "0")
    if rehearse_devices:
        # selftest only: chips this host does not have, CPU devices for them
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["TPU_ACCELERATOR_TYPE"] = "v5litepod-4"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={rehearse_devices}")


def keep_stdout_for_the_result() -> None:
    """Daemons and workers inherit this process's stdout and print there;
    the result is the LAST line of stdout, so children get stderr."""
    sys.stdout.flush()
    sys.stdout = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)


class NoAccelerator(RuntimeError):
    pass


class Cluster:
    """rt.init() ... rt.shutdown() around one run."""

    def __init__(self, chips: int):
        self.chips = chips
        self.session = ""

    def __enter__(self):
        import ray_tpu as rt
        rt.init()
        self.session = os.environ.get("RTPU_SESSION", "")
        have = rt.cluster_resources().get("TPU", 0)
        if have < self.chips:
            rt.shutdown()
            raise NoAccelerator(f"the cluster advertises {have:g} TPU "
                                f"chips, the cell needs {self.chips}")
        return self

    def __exit__(self, exc_type, exc, tb):
        import ray_tpu as rt
        from ray_tpu import serve
        try:
            if exc_type is not None:
                self.dump_worker_logs()
            serve.shutdown()
        finally:
            rt.shutdown()
        return False

    def dump_worker_logs(self, tail_lines: int = 60) -> None:
        from ray_tpu.util import log_plane
        log_dir = log_plane.session_log_dir(self.session)
        if not os.path.isdir(log_dir):
            return
        out_dir = os.path.join(ROOT, "chiprun_out", "bench_logs",
                               self.session)
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
                sys.stderr.write(f"--- {name} ---\n" + "\n".join(lines)
                                 + "\n")


def deploy_llm(config: Dict, seed: int, name: str = "llm"):
    """serve.run(build_llm_app(...)) with the file's model and engine
    settings; returns (handle, http_port, seconds until healthy). The app
    is the program's: build_llm_app decides the replica's resources and
    arguments, and only the class it binds is swapped for the probed
    subclass (benchmark/replica.py)."""
    from ray_tpu import serve
    from ray_tpu.llm import build_llm_app

    from benchmark.replica import ProbedLLMServer
    engine = {**config["engine"], "seed": int(seed) % (2 ** 31)}
    dep = config.get("deployment_settings", {})
    t0 = time.monotonic()
    app = build_llm_app(llama_fields(config), engine, name=name,
                        max_ongoing_requests=dep.get(
                            "max_ongoing_requests", 16))
    app = serve.Application(
        serve.Deployment(ProbedLLMServer, app.deployment._config),
        app.args, app.kwargs)
    handle = serve.run(app, timeout_s=900)
    ready_s = time.monotonic() - t0
    port = serve.start_http_proxy()
    return handle, port, ready_s


#: the published config.json's keys -> the program's LlamaConfig fields
_HF_TO_LLAMA = {"vocab_size": "vocab_size", "hidden_size": "dim",
                "num_hidden_layers": "n_layers",
                "num_attention_heads": "n_heads",
                "num_key_value_heads": "n_kv_heads",
                "intermediate_size": "ffn_dim", "rope_theta": "rope_theta",
                "rms_norm_eps": "norm_eps"}


def llama_fields(config: Dict) -> Dict:
    """LlamaConfig keyword arguments for a configuration file: its
    published keys renamed, then the file's own `program_fields` (dtype of
    the held weights, attention implementation, remat)."""
    if config["hidden_size"] != config["num_attention_heads"] \
            * config["head_dim"]:
        raise ValueError("the program derives head_dim as hidden_size / "
                         "heads; this configuration's differs")
    out = {dst: config[src] for src, dst in _HF_TO_LLAMA.items()}
    out.update(config.get("program_fields", {}))
    return out
