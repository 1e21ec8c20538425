"""Drive a served Ouro model (model_type "ouro", a looped language model:
ONE stack of layers walked `total_ut_steps` times over the same weights,
each pass on page planes of its own, the final norm after every pass and an
exit gate on each pass's normed stream): runners/serve.py's method, step
for step, as runners/serve_phi4flash.py does it for its block, with what
this block needs of its own: the session deploys the replica whose
reference is that block's (replica_ouro.py), built from a configuration
file whose published keys are this family's (`total_ut_steps`,
`early_exit_threshold`, `layer_types`, `use_sliding_window`, ...) and whose
every other reading is a `program_fields` entry beside its line of
`assumed`.

The program is asked first whether it has those fields. One that has not
(a commit before the block was served) fails here, in seconds and before
any cluster is up, with an exit code of its own.
"""

from __future__ import annotations

import time
from typing import Dict

from benchmark import checks_ouro
from benchmark.runners import serve
from benchmark.runners.serve_moe import require_program_support

#: the published config.json's keys -> the program's LlamaConfig fields
_HF_TO_FIELDS = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "intermediate_size": "ffn_dim",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings", "total_ut_steps": "ut_steps"}
#: published keys whose one value the program builds. early_exit_threshold
#: 1: every token runs every pass (a threshold below 1 makes depth differ
#: by token, a scheduler the program has not got)
_MUST_BE = {"model_type": "ouro", "hidden_act": "silu", "rope_scaling": None,
            "sliding_window": None, "use_sliding_window": False,
            "early_exit_threshold": 1}
FULL = "full_attention"


def model_fields(config: Dict) -> Dict:
    """The program's model_config for a configuration file: the published
    keys renamed (the passes among them), every layer full attention, then
    the file's own `program_fields` (every reading no key carries: the
    norm after each branch). A key the program does not build is refused by
    name."""
    for key, want in _MUST_BE.items():
        if config[key] != want:
            raise ValueError(f"the program builds {key} = {want!r} only; "
                             f"this configuration has {config[key]!r}")
    if config["hidden_size"] != config["num_attention_heads"] \
            * config["head_dim"]:
        raise ValueError("the program derives head_dim as hidden_size / "
                         "heads; this configuration's differs")
    if list(config["layer_types"]) != [FULL] * config["num_hidden_layers"]:
        raise ValueError("the program builds layer_types of full_attention "
                         "only in a looped stack, one a layer")
    out = {dst: config[src] for src, dst in _HF_TO_FIELDS.items()}
    out.update(config.get("program_fields", {}))
    return out


def cut_for_rehearsal(config: Dict) -> None:
    """The selftest overrides the keys every configuration has (hidden
    size, depth, heads, head_dim 8) with tiny ones; this block's own are
    cut to match, in place: a kind a layer of the two that are left (the
    four passes stay)."""
    config["layer_types"] = [FULL] * config["num_hidden_layers"]


class Session(serve.Session):
    """serve.Session, deploying replica_ouro.ProbedOuroServer with the
    fields above (cluster.deploy_llm's steps; only the class that
    build_llm_app binds is swapped)."""

    def __init__(self, config: Dict, seed: int):
        from ray_tpu import serve as rt_serve
        from ray_tpu.llm import build_llm_app

        from benchmark.replica_ouro import ProbedOuroServer
        self.config = config
        engine = {**config["engine"], "seed": int(seed) % (2 ** 31)}
        dep = config.get("deployment_settings", {})
        t0 = time.monotonic()
        app = build_llm_app(model_fields(config), engine, name=serve.MODEL,
                            max_ongoing_requests=dep.get(
                                "max_ongoing_requests", 16))
        app = rt_serve.Application(
            rt_serve.Deployment(ProbedOuroServer, app.deployment._config),
            app.args, app.kwargs)
        self.handle = rt_serve.run(app, timeout_s=900)
        self.ready_s = time.monotonic() - t0
        self.port = rt_serve.start_http_proxy()

    def warm_and_score(self, mix: Dict, seed: int) -> Dict:
        """serve.Session's, and the checks that the pool holds a plane a
        pass and layer, and that the warm-up's repeated prompt took its
        prefix hit (pages are the only state: one page table, one hash and
        one copy on write serve every plane)."""
        out = super().warm_and_score(mix, seed)
        stats = self.probe()["stats"]
        planes = self.config["total_ut_steps"] \
            * self.config["num_hidden_layers"]
        if stats.get("kv_planes") != planes:
            raise RuntimeError(
                f"the pool holds {stats.get('kv_planes')} page planes; "
                f"{self.config['total_ut_steps']} passes over "
                f"{self.config['num_hidden_layers']} layers keep {planes}")
        if not stats["cached_tokens"] or not stats["cow_copies"]:
            raise RuntimeError("the warm-up's repeated prompt took no "
                               "prefix hit on the looped pool")
        return out


def run(ctx: Dict) -> Dict:
    """serve.run with this module's Session in Session's place, and the
    scored tokens held to checks_ouro.py's limits (a CPU rehearsal computes
    in float32 and keeps checks.py's)."""
    if ctx["rehearse"]:
        cut_for_rehearsal(ctx["config"])
    require_program_support(model_fields(ctx["config"]))
    scored = {}

    def served_tokens(groups):
        scored.update(scored_gaps=checks_ouro.gap_summary(groups),
                      scored_requests=checks_ouro.request_shares(groups))
        return checks_ouro.served_tokens(groups)

    base, serve.Session = serve.Session, Session
    held = serve.checks.served_tokens
    if not ctx["rehearse"]:
        serve.checks.served_tokens = served_tokens
    try:
        data = serve.run(ctx)
    finally:
        serve.Session, serve.checks.served_tokens = base, held
    data["notes"].update(scored)
    exits = sorted(k for k in data["stats_close"]
                   if k.startswith("ut_exit_at_"))
    for key in ["decode_tokens", "decode_steps", "chunk_rows",
                "chunk_rows_joined", "ragged_dispatches",
                "decode_dispatches", "cached_tokens", "cow_copies"] + exits:
        a, b = data["stats_open"].get(key), data["stats_close"].get(key)
        data["notes"][key] = None if a is None or b is None else b - a
    for key in ("kv_token_layer_bytes", "kv_planes", "kv_token_bytes"):
        data["notes"][key] = data["stats_close"].get(key)
    return data
