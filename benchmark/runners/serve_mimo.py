"""Drive a served MiMo-V2-Flash model (model_type "mimo_v2_flash": full and
window attention layers on different key/value heads, a score head of 192
beside a value head of 128, a partial rotary embedding, a value scale, a
sink in the window layers' softmax, a leading dense layer and routed experts
of which this chip holds a share): runners/serve.py's method, step for step,
as runners/serve_brumby.py does it for Brumby, with what this block needs of
its own: the session deploys the replica whose reference is that block's
(replica_mimo.py), built from a configuration file whose published keys are
this family's (`hybrid_layer_pattern`, `swa_*`, `partial_rotary_factor`,
`attention_value_scale`, `add_swa_attention_sink_bias`, `moe_layer_freq`,
...), and the cell's closed loop dealt as context-latent's is (runners/
serve_kanana.py:closed_loop, imported: every seed offers every window the
same work).

The program is asked first whether it has the fields. One that has not (a
commit before the block was served) fails here, in seconds and before any
cluster is up, with an exit code of its own.
"""

from __future__ import annotations

import time
from typing import Dict, List

from benchmark import checks_mimo, loadgen
from benchmark.runners import serve
from benchmark.runners.serve_kanana import closed_loop
from benchmark.runners.serve_moe import require_program_support

#: the published config.json's keys -> the program's LlamaConfig fields
_HF_TO_FIELDS = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "swa_num_key_value_heads": "window_kv_heads",
    "moe_intermediate_size": "ffn_dim",        # one routed expert's width
    "intermediate_size": "dense_ffn_dim",      # the leading dense layer's
    "num_experts_per_tok": "experts_per_token",
    "norm_topk_prob": "norm_topk_prob", "scoring_func": "router_score",
    "rope_theta": "rope_theta", "swa_rope_theta": "window_rope_theta",
    "layernorm_epsilon": "norm_eps", "head_dim": "score_head_dim",
    "v_head_dim": "value_head_dim", "sliding_window": "sliding_window",
    "attention_value_scale": "value_scale",
    "add_swa_attention_sink_bias": "attn_sink",
    "tie_word_embeddings": "tie_embeddings"}
#: published keys whose one value the program builds
_MUST_BE = {"model_type": "mimo_v2_flash", "attention_bias": False,
            "hidden_act": "silu", "add_full_attention_sink_bias": False,
            "n_shared_experts": None, "routed_scaling_factor": None,
            "n_group": 1, "topk_group": 1, "topk_method": "noaux_tc"}
FULL, WINDOW = "full_attention", "sliding_attention"


def served_pattern(config: Dict) -> List[int]:
    """The published `hybrid_layer_pattern` (1 = window) of the layers this
    configuration serves: all of them where the depth is the published
    one; in a cut, the leading layers that have no experts and then whole
    periods of the pattern's settled part (its LAST period: the published
    list begins with a shorter run of window layers before the period of
    six settles, which a cut of one period does not keep)."""
    pattern, n = list(config["hybrid_layer_pattern"]), \
        config["num_hidden_layers"]
    if n >= len(pattern):
        return pattern
    lead = config["moe_layer_freq"].index(1)
    period = len(pattern) - 1 - max(
        i for i, w in enumerate(pattern[:-1]) if not w)
    if (n - lead) % period:
        raise ValueError(f"{n} layers are no {lead} leading layers and "
                         f"whole periods of {period}")
    return pattern[:lead] + pattern[-period:] * ((n - lead) // period)


def model_fields(config: Dict) -> Dict:
    """The program's model_config for a configuration file: the published
    keys renamed, the layers' kinds from the published pattern, the rotary
    values from the published factor, the chip's share of the experts, then
    the file's own `program_fields`. A key the program does not build is
    refused by name."""
    for key, want in _MUST_BE.items():
        if config[key] != want:
            raise ValueError(f"the program builds {key} = {want!r} only; "
                             f"this configuration has {config[key]!r}")
    for mine, full in (("swa_num_attention_heads", "num_attention_heads"),
                       ("swa_head_dim", "head_dim"),
                       ("swa_v_head_dim", "v_head_dim"),
                       ("sliding_window_size", "sliding_window"),
                       ("attention_chunk_size", "sliding_window")):
        if config[mine] != config[full]:
            raise ValueError(f"the program builds {mine} = {full} only")
    out = {dst: config[src] for src, dst in _HF_TO_FIELDS.items()}
    out["layer_types"] = [WINDOW if w else FULL
                          for w in served_pattern(config)]
    out["n_dense_layers"] = config["moe_layer_freq"].index(1)
    out["rotary_dim"] = int(config["head_dim"]
                            * config["partial_rotary_factor"])
    # the router's width is the PUBLISHED count; n_routed_experts counts
    # the experts held here
    first, n = config["experts_held"]
    if n != config["n_routed_experts"]:
        raise ValueError("n_routed_experts counts the experts HELD here: "
                         "it is not experts_held's")
    out["n_experts"] = config.get("published", {}).get(
        "n_routed_experts", n)
    out["experts_held"] = [first, n]
    out["router_bias"] = True
    out.update(config.get("program_fields", {}))
    return out


def cut_for_rehearsal(config: Dict) -> None:
    """The selftest overrides the keys every configuration has (hidden
    size, depth, heads, head_dim 8) with tiny ones; this block's own are cut
    to match, in place: a leading dense layer and one period of (window,
    full) in the three layers, a window of 16 tokens (two pages of 8), few
    experts of which half are held, a value head narrower than the score
    head."""
    n = config["num_hidden_layers"] = 3
    config.update(
        hybrid_layer_pattern=[0, 1, 0], moe_layer_freq=[0] + [1] * (n - 1),
        num_key_value_heads=2, swa_num_key_value_heads=4,
        swa_num_attention_heads=config["num_attention_heads"],
        head_dim=16, swa_head_dim=16, v_head_dim=8, swa_v_head_dim=8,
        partial_rotary_factor=0.5, sliding_window=16, sliding_window_size=16,
        attention_chunk_size=16, n_routed_experts=4, num_experts_per_tok=3,
        experts_held=[2, 4], published={"n_routed_experts": 8},
        moe_intermediate_size=max(8, config["intermediate_size"] // 4))


class Session(serve.Session):
    """serve.Session, deploying replica_mimo.ProbedMimoServer with the
    fields above (cluster.deploy_llm's steps; only the class that
    build_llm_app binds is swapped)."""

    def __init__(self, config: Dict, seed: int):
        from ray_tpu import serve as rt_serve
        from ray_tpu.llm import build_llm_app

        from benchmark.replica_mimo import ProbedMimoServer
        self.config = config
        engine = {**config["engine"], "seed": int(seed) % (2 ** 31)}
        dep = config.get("deployment_settings", {})
        t0 = time.monotonic()
        app = build_llm_app(model_fields(config), engine, name=serve.MODEL,
                            max_ongoing_requests=dep.get(
                                "max_ongoing_requests", 16))
        app = rt_serve.Application(
            rt_serve.Deployment(ProbedMimoServer, app.deployment._config),
            app.args, app.kwargs)
        self.handle = rt_serve.run(app, timeout_s=900)
        self.ready_s = time.monotonic() - t0
        self.port = rt_serve.start_http_proxy()

    def warm_and_score(self, mix: Dict, seed: int) -> Dict:
        """serve.Session's, and the checks that the engine took no prefix
        hit and copied no page (a group that frees behind a window cannot
        restore a hit's window, so the warm-up's repeated prompt must have
        been computed whole) and that the window group did free pages."""
        out = super().warm_and_score(mix, seed)
        stats = self.probe()["stats"]
        if stats["cached_tokens"] or stats["cow_copies"]:
            raise RuntimeError(
                f"the engine took a prefix hit with a window group "
                f"present: {stats['cached_tokens']} cached tokens")
        if not stats.get("window_pages_freed"):
            raise RuntimeError("the window group freed no page over the "
                               "warm-up's prompts")
        return out


def steady_state(records, wall_open: float, window_s: float, slots: int,
                 slice_s: float = 10.0) -> Dict:
    """What the loop did in each ``slice_s`` seconds of the window, from
    the request log (a record's chunk entries carry their mixed step's
    index, its decode entries the tokens a dispatch gave it, one for a
    mixed step and up to decode_chunk for a decode block; the rows of one
    dispatch share its booking time): mixed steps, decode blocks, the share
    of mixed steps among the dispatches, the decode blocks' occupancy (rows
    a block / batch slots) and the tokens booked. `first` and `last` are
    the window's first and last whole slice: the lead-in is long enough
    when the two agree (PERF.md, PR 45)."""
    slices = []
    for i in range(int(window_s // slice_s)):
        lo = wall_open + i * slice_s
        hi = lo + slice_s
        mixed, blocks, rows, chunk_tokens, tokens = set(), set(), 0, 0, 0
        for rec in records:
            t0 = rec["t0_wall"]
            for ts, n, step in rec.get("chunks", ()):
                if lo <= t0 + ts < hi:
                    mixed.add(step)
                    chunk_tokens += n
            if rec.get("ttft") is None:
                continue
            t = t0 + rec["ttft"]
            tokens += lo <= t < hi                  # the first token
            for dt, n in rec.get("decode", ()):
                t += dt
                if lo <= t < hi:
                    tokens += n
                    if n > 1:
                        blocks.add(round(t, 4))
                        rows += 1
        n = len(mixed) + len(blocks)
        slices.append({
            "mixed_steps": len(mixed), "decode_blocks": len(blocks),
            "chunk_tokens": chunk_tokens, "tokens": tokens,
            "mixed_share_pct": 100.0 * len(mixed) / n if n else None,
            "block_occupancy_pct": 100.0 * rows / (len(blocks) * slots)
            if blocks else None})
    return {"first": slices[0], "last": slices[-1], "slices": slices} \
        if slices else {}


def run(ctx: Dict) -> Dict:
    """serve.run with this module's Session in Session's place, the closed
    loop dealt by serve_kanana.closed_loop, and the scored tokens held to
    checks_mimo.py's limits (a CPU rehearsal computes in float32 and keeps
    checks.py's)."""
    if ctx["rehearse"]:
        cut_for_rehearsal(ctx["config"])
    require_program_support(model_fields(ctx["config"]))
    scored = {}

    def served_tokens(groups):
        scored.update(scored_gaps=checks_mimo.gap_summary(groups),
                      scored_requests=checks_mimo.request_shares(groups))
        return checks_mimo.served_tokens(groups)

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
    for key in ("window_pages_freed", "page_steps_full", "page_steps_window",
                "moe_pairs", "moe_absent", "chunk_rows", "chunk_rows_joined",
                "ragged_dispatches", "decode_dispatches"):
        a, b = data["stats_open"].get(key), data["stats_close"].get(key)
        data["notes"][key] = None if a is None or b is None else b - a
    # the window's edges on the wall clock the request log keeps
    wall_open = time.time() - (time.monotonic() - data["t_open"])
    data["notes"]["steady_state"] = steady_state(
        data["request_log"], wall_open, data["window_s"],
        ctx["config"]["engine"]["max_batch"])
    return data
