"""Drive a served Kanana-2 model (model_type "deepseek_v3": latent
attention, a shared expert beside routed experts, a leading dense layer):
runners/serve.py's method, step for step, as runners/serve_lfm2.py does it
for LFM2, with what this block needs of its own: the session deploys the
replica whose reference is that block's (replica_kanana.py), built from a
configuration file whose published keys are this family's (`kv_lora_rank`,
`qk_nope_head_dim`, `qk_rope_head_dim`, `v_head_dim`, `rope_interleave`,
`first_k_dense_replace`, `n_routed_experts`, `n_shared_experts`, ...),
and the cell's closed loop dealt so that every seed offers every window
the same work (`closed_loop`).

The program is asked first whether it has those fields. One that has not
(a commit before the block was served) fails here, in seconds and before
any cluster is up, with an exit code of its own.
"""

from __future__ import annotations

import random
import time
from typing import Dict

import numpy as np

from benchmark import checks_kanana, loadgen
from benchmark.runners import serve
from benchmark.runners.serve_moe import require_program_support

#: the published config.json's keys -> the program's LlamaConfig fields
_HF_TO_FIELDS = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "moe_intermediate_size": "ffn_dim",        # one routed expert's width
    "intermediate_size": "dense_ffn_dim",      # the leading dense layer's
    "first_k_dense_replace": "n_dense_layers",
    "n_routed_experts": "n_experts",
    "num_experts_per_tok": "experts_per_token",
    "norm_topk_prob": "norm_topk_prob", "scoring_func": "router_score",
    "routed_scaling_factor": "router_scale", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
    "tie_word_embeddings": "tie_embeddings"}

#: published keys the program has no variation point for: it builds the
#: block only where they say what it builds
_MUST_BE = {"q_lora_rank": None, "rope_scaling": None,
            "rope_interleave": True,   # adjacent pairs: llama._rope_pairs
            "attention_bias": False, "hidden_act": "silu", "n_group": 1,
            "topk_group": 1, "moe_layer_freq": 1, "topk_method": "noaux_tc"}


def model_fields(config: Dict) -> Dict:
    """The program's model_config for a configuration file: the published
    keys renamed, the two the family's code derives (the shared expert is
    ONE SwiGLU of n_shared_experts x moe_intermediate_size; topk_method
    noaux_tc means a per-expert selection bias), then the file's own
    `program_fields` (what that code implies and the published file does
    not state: see `assumed`)."""
    for key, want in _MUST_BE.items():
        if config[key] != want:
            raise ValueError(f"the program builds this block with {key} = "
                             f"{want!r} only; the configuration says "
                             f"{config[key]!r}")
    if config["qk_head_dim"] != config["qk_nope_head_dim"] \
            + config["qk_rope_head_dim"]:
        raise ValueError("qk_head_dim is not qk_nope_head_dim + "
                         "qk_rope_head_dim")
    out = {dst: config[src] for src, dst in _HF_TO_FIELDS.items()}
    out["shared_ffn_dim"] = config["n_shared_experts"] \
        * config["moe_intermediate_size"]
    out["router_bias"] = True
    out.update(config.get("program_fields", {}))
    return out


def cut_for_rehearsal(config: Dict) -> None:
    """The selftest overrides the keys every configuration has (hidden
    size, depth, heads) with tiny ones; this block's own keys are cut to
    match, in place: the dense layer and an expert layer in however few
    layers are left, the shared expert, few experts, and a latent two lanes
    wide (256) whose row is not a whole number of them (256 + 16), as the
    published 512 + 64 is not."""
    n = config["num_hidden_layers"]
    config["first_k_dense_replace"] = min(config["first_k_dense_replace"],
                                          n - 1)
    config.update(kv_lora_rank=256, qk_nope_head_dim=16, qk_rope_head_dim=16,
                  qk_head_dim=32, v_head_dim=16, n_routed_experts=8,
                  num_experts_per_tok=3,
                  moe_intermediate_size=max(8, config["intermediate_size"]
                                            // 4))


#: the ONE deal of the mix's lengths into queues that every seed offers
_DEAL = 0


def closed_loop(mix: Dict, seed: int, clients: int, vocab: int) -> Dict:
    """loadgen.closed_loop's plan (the same multiset of per_client x
    clients requests, the same openers), dealt so that every seed offers
    every window the same work. A window of this cell holds ~90 turn-overs
    of 1-8 k prompt tokens each, under two requests a client, and three
    fifths of the device's time is their prefill: loadgen's deal, which
    draws from the seed which prompt meets which output in which client's
    which turn, moved `out_tok_per_s` by 5-8 % between seeds and by far
    less between two runs of one seed (the driver's first check of PR 33).
    Here the lengths are dealt ONCE (`_DEAL`, not the seed): turn r of all
    the queues together holds a stratified sample of both distributions
    (of every `per_client` consecutive quantiles one, so every turn has the
    mix's mean and spread), paired and placed by one fixed shuffle, each
    queue behind one of loadgen's openers. The seed deals the queues to the
    clients (whose opener is admitted first and sets the phases) and
    draws the token ids (and, in the program, the weights)."""
    per = int(mix["per_client"])
    deal = random.Random(_DEAL)

    def dealt(lens):
        lens = list(lens)
        deal.shuffle(lens)
        return lens

    def turns(spec):
        q = loadgen.lognormal_quantiles(per * clients, spec)
        return [dealt(q[i * per + (r + i) % per] for i in range(clients))
                for r in range(per)]

    median = mix["output"]["median"]
    lens = [list(zip(
        dealt(loadgen.lognormal_quantiles(clients, mix["prompt"])),
        dealt(max(1, int(round(median * (j + 0.5) / clients)))
              for j in range(clients))))]
    lens += [list(zip(p, o)) for p, o in zip(turns(mix["prompt"]),
                                             turns(mix["output"]))]
    order = list(range(clients))
    random.Random(seed * 1000003 + 3).shuffle(order)
    rng = np.random.default_rng([seed, 3])
    queues = [[{"prompt": loadgen._tokens(rng, turn[q][0], vocab),
                "max_tokens": turn[q][1]} for turn in lens] for q in order]
    return {"kind": "closed_loop", "queues": queues,
            "lead_in_s": float(mix["lead_in_s"])}


class Session(serve.Session):
    """serve.Session, deploying replica_kanana.ProbedKananaServer with the
    fields above (cluster.deploy_llm's steps; only the class that
    build_llm_app binds is swapped)."""

    def __init__(self, config: Dict, seed: int):
        from ray_tpu import serve as rt_serve
        from ray_tpu.llm import build_llm_app

        from benchmark.replica_kanana import ProbedKananaServer
        self.config = config
        engine = {**config["engine"], "seed": int(seed) % (2 ** 31)}
        dep = config.get("deployment_settings", {})
        t0 = time.monotonic()
        app = build_llm_app(model_fields(config), engine, name=serve.MODEL,
                            max_ongoing_requests=dep.get(
                                "max_ongoing_requests", 16))
        app = rt_serve.Application(
            rt_serve.Deployment(ProbedKananaServer, app.deployment._config),
            app.args, app.kwargs)
        self.handle = rt_serve.run(app, timeout_s=900)
        self.ready_s = time.monotonic() - t0
        self.port = rt_serve.start_http_proxy()

    def warm_and_score(self, mix: Dict, seed: int) -> Dict:
        """serve.Session's, and the check that the warm-up's repeated
        prompt DID take its prefix hit and its copy on write on the latent
        leaf (pages are this block's only state, so the prefix cache is
        on), and that the pool is the one leaf the configuration states."""
        out = super().warm_and_score(mix, seed)
        stats = self.probe()["stats"]
        if not (stats["cached_tokens"] and stats["cow_copies"]):
            raise RuntimeError(
                f"the warm-up's repeated prompt took no prefix hit or no "
                f"copy on write on the latent pool: {stats}")
        width = stats.get("kv_row_width")
        if width is None or width > 640:
            raise RuntimeError(
                f"the pool is not a latent pool of at most 640 values a "
                f"token a layer (kv_row_width {width})")
        return out


def run(ctx: Dict) -> Dict:
    """serve.run with this module's Session in Session's place, and the
    scored tokens held to this block's limits (checks_kanana.py says why
    checks.py's cannot hold for it; a CPU rehearsal computes in float32
    and keeps checks.py's)."""
    if ctx["rehearse"]:
        cut_for_rehearsal(ctx["config"])
    require_program_support(model_fields(ctx["config"]))
    scored = {}

    def served_tokens(groups):
        scored.update(scored_gaps=checks_kanana.gap_summary(groups),
                      scored_requests=checks_kanana.request_shares(groups))
        return checks_kanana.served_tokens(groups)

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
    for key in ("kv_row_width", "kv_token_layer_bytes"):
        data["notes"][key] = data["stats_close"].get(key)
    return data
