"""Drive a served sparse-expert model: runners/serve.py's method, step for
step (deploy through the program's own path, warm the cell's programs,
offer the mix, gather what the readers need), with the one thing a block
that is not the Llama/Mistral block needs of its own: the session deploys
the replica whose reference is that block's (replica_moe.py), built from a
configuration file whose published keys include the expert ones.

The program is asked first whether it has those fields. One that has not
(a commit before the block was served) fails here, in seconds and before
any cluster is up, with an exit code of its own.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict

from benchmark import cluster
from benchmark.runners import serve

#: the published config.json's expert keys -> the program's fields
_HF_TO_FIELDS = {"num_experts": "n_experts",
                 "num_experts_per_tok": "experts_per_token",
                 "norm_topk_prob": "norm_topk_prob",
                 "tie_word_embeddings": "tie_embeddings"}


def model_fields(config: Dict) -> Dict:
    """The program's model_config for a configuration file: the dense
    keys as cluster.llama_fields maps them, the expert keys above, then
    the file's own `program_fields` (qk_norm is implied by model_type,
    not a published key: the file states it there and under `assumed`)."""
    out = cluster.llama_fields(config)
    out.update({dst: config[src] for src, dst in _HF_TO_FIELDS.items()})
    out.update(config.get("program_fields", {}))
    return out


def require_program_support(fields: Dict) -> None:
    from ray_tpu.models.llama import LlamaConfig
    have = {f.name for f in dataclasses.fields(LlamaConfig)}
    missing = sorted(set(fields) - have)
    if missing:
        raise SystemExit(
            f"benchmark: this program cannot build the configuration: its "
            f"LlamaConfig has no field(s) {missing}")


class Session(serve.Session):
    """serve.Session, deploying replica_moe.ProbedMoEServer with the
    fields above (cluster.deploy_llm's steps; only the class that
    build_llm_app binds is swapped)."""

    def __init__(self, config: Dict, seed: int):
        from ray_tpu import serve as rt_serve
        from ray_tpu.llm import build_llm_app

        from benchmark.replica_moe import ProbedMoEServer
        self.config = config
        engine = {**config["engine"], "seed": int(seed) % (2 ** 31)}
        dep = config.get("deployment_settings", {})
        t0 = time.monotonic()
        app = build_llm_app(model_fields(config), engine, name=serve.MODEL,
                            max_ongoing_requests=dep.get(
                                "max_ongoing_requests", 16))
        app = rt_serve.Application(
            rt_serve.Deployment(ProbedMoEServer, app.deployment._config),
            app.args, app.kwargs)
        self.handle = rt_serve.run(app, timeout_s=900)
        self.ready_s = time.monotonic() - t0
        self.port = rt_serve.start_http_proxy()


def run(ctx: Dict) -> Dict:
    """serve.run with this module's Session in Session's place."""
    require_program_support(model_fields(ctx["config"]))
    base, serve.Session = serve.Session, Session
    try:
        return serve.run(ctx)
    finally:
        serve.Session = base
