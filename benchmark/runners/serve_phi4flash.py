"""Drive a served Phi-4-mini-flash model (model_type "phi4flash": a
decoder-hybrid-decoder of Mamba-1 layers beside window-512 attention, ONE
full-attention layer whose pages the cross-decoder's attention layers read,
gated memory units on one Mamba-1 layer's output, differential attention
throughout, LayerNorm, no positions): runners/serve.py's method, step for
step, as runners/serve_granite.py does it for granite-4.0-h, with what this
block needs of its own: the session deploys the replica whose reference is
that block's (replica_phi4flash.py), built from a configuration file whose
published keys are this family's (`mb_per_layer`, `sliding_window`,
`layer_norm_eps`, `mlp_bias`, `lm_head_bias`, ...) and whose every other
reading is a `program_fields` entry beside its line of `assumed`.

The program is asked first whether it has those fields. One that has not
(a commit before the block was served) fails here, in seconds and before
any cluster is up, with an exit code of its own.
"""

from __future__ import annotations

import time
from typing import Dict, List

from benchmark import checks_phi4flash
from benchmark.runners import serve
from benchmark.runners.serve_moe import require_program_support

#: the published config.json's keys -> the program's LlamaConfig fields
_HF_TO_FIELDS = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "intermediate_size": "ffn_dim",
    "layer_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
    "sliding_window": "sliding_window"}
#: published keys whose one value the program builds
_MUST_BE = {"model_type": "phi4flash", "hidden_act": "silu",
            "mb_per_layer": 2, "mlp_bias": False, "lm_head_bias": False,
            "embd_pdrop": 0, "resid_pdrop": 0}
MAMBA1, GMU, FULL, WINDOW, CROSS = ("mamba1", "gmu", "full_attention",
                                    "sliding_attention", "cross_attention")


def layer_types(n_layers: int) -> List[str]:
    """The kinds of a decoder-hybrid-decoder's layers (`mb_per_layer` 2:
    every even layer is state-space), the program's names: the first half
    Mamba-1 beside window attention; layer n/2 Mamba-1 (its scan output is
    the memory) and n/2 + 1 the one full-attention layer; then gated
    memory units beside cross attention."""
    half = n_layers // 2
    return [(MAMBA1 if i <= half else GMU) if i % 2 == 0
            else WINDOW if i < half else FULL if i == half + 1 else CROSS
            for i in range(n_layers)]


def model_fields(config: Dict) -> Dict:
    """The program's model_config for a configuration file: the published
    keys renamed, the layers' kinds from the depth, the window layers on
    the published key/value heads, then the file's own `program_fields`
    (every reading no key carries). A key the program does not build is
    refused by name."""
    for key, want in _MUST_BE.items():
        if config[key] != want:
            raise ValueError(f"the program builds {key} = {want!r} only; "
                             f"this configuration has {config[key]!r}")
    if config["hidden_size"] != config["num_attention_heads"] \
            * config["head_dim"]:
        raise ValueError("the program derives head_dim as hidden_size / "
                         "heads; this configuration's differs")
    if config["num_hidden_layers"] % 4:
        raise ValueError("a decoder-hybrid-decoder's two halves are whole "
                         "pairs of layers: the depth must divide by 4")
    out = {dst: config[src] for src, dst in _HF_TO_FIELDS.items()}
    out["layer_types"] = layer_types(config["num_hidden_layers"])
    out["window_kv_heads"] = config["num_key_value_heads"]
    # unused (no positions); a window layer's configuration wants one
    out["window_rope_theta"] = 10000.0
    out.update(config.get("program_fields", {}))
    return out


def cut_for_rehearsal(config: Dict) -> None:
    """The selftest overrides the keys every configuration has (hidden
    size, depth, heads, head_dim 8) with tiny ones; this block's own are
    cut to match, in place: eight layers (two window layers beside Mamba-1,
    the Mamba-1 and full pair, one gated memory unit and one cross layer),
    a window of 16 tokens (two pages of 8), dt of rank 4."""
    config.update(num_hidden_layers=8, sliding_window=16)
    config["program_fields"] = {**config["program_fields"],
                                "ssm1_dt_rank": 4}


class Session(serve.Session):
    """serve.Session, deploying replica_phi4flash.ProbedPhi4FlashServer
    with the fields above (cluster.deploy_llm's steps; only the class that
    build_llm_app binds is swapped)."""

    def __init__(self, config: Dict, seed: int):
        from ray_tpu import serve as rt_serve
        from ray_tpu.llm import build_llm_app

        from benchmark.replica_phi4flash import ProbedPhi4FlashServer
        self.config = config
        engine = {**config["engine"], "seed": int(seed) % (2 ** 31)}
        dep = config.get("deployment_settings", {})
        t0 = time.monotonic()
        app = build_llm_app(model_fields(config), engine, name=serve.MODEL,
                            max_ongoing_requests=dep.get(
                                "max_ongoing_requests", 16))
        app = rt_serve.Application(
            rt_serve.Deployment(ProbedPhi4FlashServer,
                                app.deployment._config),
            app.args, app.kwargs)
        self.handle = rt_serve.run(app, timeout_s=900)
        self.ready_s = time.monotonic() - t0
        self.port = rt_serve.start_http_proxy()

    def warm_and_score(self, mix: Dict, seed: int) -> Dict:
        """serve.Session's, and the checks that the engine took no prefix
        hit (with state per batch slot and a window group a hit that
        restores KV alone is a wrong answer: the warm-up's repeated prompt
        must have been computed whole), that the window group did free
        pages, and that ONE full layer's pages are read by every cross
        layer and itself."""
        out = super().warm_and_score(mix, seed)
        stats = self.probe()["stats"]
        if stats["cached_tokens"] or stats["cow_copies"]:
            raise RuntimeError(
                f"the engine took a prefix hit with state-space and window "
                f"layers present: {stats['cached_tokens']} cached tokens")
        if not stats.get("window_pages_freed"):
            raise RuntimeError("the window group freed no page over the "
                               "warm-up's prompts")
        kinds = layer_types(self.config["num_hidden_layers"])
        if stats.get("shared_kv_readers") != 1 + kinds.count(CROSS):
            raise RuntimeError(
                f"{stats.get('shared_kv_readers')} layers read the full "
                f"layer's pages; the configuration has 1 + "
                f"{kinds.count(CROSS)}")
        return out


def run(ctx: Dict) -> Dict:
    """serve.run with this module's Session in Session's place, and the
    scored tokens held to checks_phi4flash.py's limits (a CPU rehearsal
    computes in float32 and keeps checks.py's)."""
    if ctx["rehearse"]:
        cut_for_rehearsal(ctx["config"])
    require_program_support(model_fields(ctx["config"]))
    scored = {}

    def served_tokens(groups):
        scored.update(scored_gaps=checks_phi4flash.gap_summary(groups),
                      scored_requests=checks_phi4flash.request_shares(groups))
        return checks_phi4flash.served_tokens(groups)

    base, serve.Session = serve.Session, Session
    held = serve.checks.served_tokens
    if not ctx["rehearse"]:
        serve.checks.served_tokens = served_tokens
    try:
        data = serve.run(ctx)
    finally:
        serve.Session, serve.checks.served_tokens = base, held
    data["notes"].update(scored)
    for key in ("window_pages_freed", "page_steps_full", "page_steps_window",
                "rows_inside_window", "decode_tokens", "state_resets",
                "chunk_rows", "chunk_rows_joined", "ragged_dispatches",
                "decode_dispatches"):
        a, b = data["stats_open"].get(key), data["stats_close"].get(key)
        data["notes"][key] = None if a is None or b is None else b - a
    for key in ("state_bytes_per_slot", "kv_token_layer_bytes",
                "shared_kv_readers"):
        data["notes"][key] = data["stats_close"].get(key)
    return data
