"""Drive a served Brumby model (model_type "brumby": every layer a
power-retention layer, no attention layer at all): runners/serve.py's
method, step for step, as runners/serve_granite.py does it for granite, with
what this block needs of its own: the session deploys the replica whose
reference is that block's (replica_brumby.py), built from a configuration
file whose published keys are the Qwen3 block's it was retrained from, and
the cell's closed loop dealt as context-latent's is (runners/
serve_kanana.py:closed_loop, imported: every seed offers every window the
same work).

The program is asked first whether it has the fields. One that has not (a
commit before the block was served) fails here, in seconds and before any
cluster is up, with an exit code of its own.
"""

from __future__ import annotations

import time
from typing import Dict

from benchmark import checks_brumby, loadgen
from benchmark.checks_lfm2 import gap_summary
from benchmark.runners import serve
from benchmark.runners.serve_kanana import closed_loop
from benchmark.runners.serve_moe import require_program_support

#: the published config.json's keys -> the program's LlamaConfig fields
_HF_TO_FIELDS = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "intermediate_size": "ffn_dim",
    "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings"}
#: published keys whose one value the program builds
_MUST_BE = {"model_type": "brumby", "attention_bias": False,
            "hidden_act": "silu", "rope_scaling": None,
            "sliding_window": None, "use_sliding_window": False}

def model_fields(config: Dict) -> Dict:
    """The program's model_config for a configuration file: the published
    keys renamed, every layer a retention layer with the Qwen3 block's q/k
    norm per head, then the file's own `program_fields`. A key the program
    does not build (another `model_type`, a window, a bias in q, k, v) is
    refused by name."""
    for key, want in _MUST_BE.items():
        if config[key] != want:
            raise ValueError(f"the program builds {key} = {want!r} only; "
                             f"this configuration has {config[key]!r}")
    if config["hidden_size"] != config["num_attention_heads"] \
            * config["head_dim"]:
        raise ValueError("the program derives head_dim as hidden_size / "
                         "heads; this configuration's differs")
    out = {dst: config[src] for src, dst in _HF_TO_FIELDS.items()}
    out["layer_types"] = ["retention"] * config["num_hidden_layers"]
    out["qk_norm_per_head"] = True
    out.update(config.get("program_fields", {}))
    return out


def cut_for_rehearsal(config: Dict) -> None:
    """The selftest overrides the keys every configuration has (hidden
    size, depth, heads, head_dim) with tiny ones; this block's own are cut
    to match, in place: a retention block of 8 tokens, so that the tiny
    prompts cross block and chunk boundaries."""
    config.setdefault("program_fields", {})["retention_chunk"] = 8


class Session(serve.Session):
    """serve.Session, deploying replica_brumby.ProbedBrumbyServer with the
    fields above (cluster.deploy_llm's steps; only the class that
    build_llm_app binds is swapped)."""

    def __init__(self, config: Dict, seed: int):
        from ray_tpu import serve as rt_serve
        from ray_tpu.llm import build_llm_app

        from benchmark.replica_brumby import ProbedBrumbyServer
        self.config = config
        engine = {**config["engine"], "seed": int(seed) % (2 ** 31)}
        dep = config.get("deployment_settings", {})
        t0 = time.monotonic()
        app = build_llm_app(model_fields(config), engine, name=serve.MODEL,
                            max_ongoing_requests=dep.get(
                                "max_ongoing_requests", 16))
        app = rt_serve.Application(
            rt_serve.Deployment(ProbedBrumbyServer, app.deployment._config),
            app.args, app.kwargs)
        self.handle = rt_serve.run(app, timeout_s=900)
        self.ready_s = time.monotonic() - t0
        self.port = rt_serve.start_http_proxy()

    def warm_and_score(self, mix: Dict, seed: int) -> Dict:
        """serve.Session's, and the checks that no page did any work (the
        engine took no prefix hit and copied no page: with state per batch
        slot a hit that restores pages alone is a wrong answer, so the
        warm-up's repeated prompt must have been computed whole) and that
        the engine holds state per batch slot."""
        out = super().warm_and_score(mix, seed)
        stats = self.probe()["stats"]
        if stats["cached_tokens"] or stats["cow_copies"]:
            raise RuntimeError(
                f"the engine took a prefix hit with retention layers "
                f"present: {stats['cached_tokens']} cached tokens")
        if not stats.get("state_bytes_per_slot"):
            raise RuntimeError("the engine reports no state per batch slot")
        return out


def run(ctx: Dict) -> Dict:
    """serve.run with this module's Session in Session's place, the closed
    loop dealt by serve_kanana.closed_loop, and the scored tokens held to
    checks_brumby.py's limits (a CPU rehearsal computes in float32 and keeps
    checks.py's)."""
    if ctx["rehearse"]:
        cut_for_rehearsal(ctx["config"])
    require_program_support(model_fields(ctx["config"]))
    scored = {}

    def served_tokens(groups):
        scored.update(scored_gaps=gap_summary(groups))
        return checks_brumby.served_tokens(groups)

    base, serve.Session = serve.Session, Session
    held, deal = serve.checks.served_tokens, loadgen.closed_loop
    loadgen.closed_loop = closed_loop
    if not ctx["rehearse"]:
        serve.checks.served_tokens = served_tokens
    try:
        data = serve.run(ctx)
    finally:
        serve.Session, serve.checks.served_tokens = base, held
        loadgen.closed_loop = deal
    data["notes"].update(scored)
    for key in ("state_bytes", "state_bytes_per_slot", "state_resets",
                "chunk_rows", "ragged_dispatches"):
        data["notes"][key] = data["stats_close"].get(key)
    return data
