"""benchmark/kernel_cost_trinity.py's arithmetic on hand-computed cases: the
block's shape numbers from the published keys, the bytes the configuration
file and ISSUE 49 state, the work of the two attention kernels at a window
of 2048 and of the expert kernel; the runner's mapping of the published
keys to the program's fields and its refusals; the rehearsal cut; the
cell's traffic file holding the parameters ISSUE 49 names, the same queues
under every seed; the configuration file against the catalog's row; the
readers' silence where there is nothing to read; and the limits that decide
`correct` for the block (checks_trinity.py) beside checks.py's, with the
method of the readings they lie between (hold_trinity.py) at tiny widths."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from _readings import entry  # noqa: E402
from benchmark import checks, checks_trinity, hold_trinity  # noqa: E402
from benchmark import kernel_cost, kernel_cost_trinity as kc  # noqa: E402
from benchmark import loadgen  # noqa: E402
from benchmark.readers import engine_clocks, trinity_roofline  # noqa: E402
from benchmark.runners import serve_kanana, serve_trinity  # noqa: E402
from ray_tpu.llm import model as M  # noqa: E402

CELL = "mixed-trinity-1chip"


def _load(*path):
    with open(os.path.join(ROOT, "benchmark", *path)) as f:
        return json.load(f)


def _config():
    return _load("configs", "trinity-mini-serve-1chip.json")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_model_dims_and_the_bytes_the_file_states():
    config = _config()
    dims = kc.model_dims(config)
    assert (dims["full_layers"], dims["window_layers"], dims["dense_layers"],
            dims["expert_layers"]) == (1, 4, 1, 4)
    assert (dims["heads"], dims["kv_heads"], dims["window_kv_heads"],
            dims["score_head"], dims["value_head"], dims["window"]) \
        == (32, 4, 4, 128, 128, 2048)
    assert (dims["experts"], dims["shared_experts"], dims["top_k"],
            dims["expert_ffn"], dims["hidden"], dims["vocab"]) \
        == (128, 1, 8, 1024, 2048, 200192)
    # the pools, as held: K and V rows of 128 lanes, bf16
    full_token, win_token = 1 * 4 * 256 * 2, 4 * 4 * 256 * 2
    assert (full_token, win_token) == (2048, 8192)
    engine = config["engine"]
    assert f"{full_token} B a token" in config["pools"] \
        and f"{win_token} B a token" in config["pools"]
    assert engine["total_pages"] * 64 * full_token == pytest.approx(
        2.52e9, rel=2e-3)
    from ray_tpu.llm.cache import window_group_pages, window_table_width
    from ray_tpu.models.llama import LlamaConfig
    cfg = LlamaConfig.tiny(**serve_trinity.model_fields(config))
    assert window_table_width(2048, 8, 64) == 34
    assert engine["prefill_chunk"] == 1024
    assert window_table_width(2048, 1024, 64) == 49
    group = window_group_pages(cfg, 64, 128, 8, 1024, 2)
    assert group == 128 * 34 + 2 * 49 + 1 == 4451
    assert "4451 pages" in config["pools"]
    assert group * 64 * win_token == pytest.approx(2.33e9, rel=3e-3)
    assert engine["max_seq_len"] == 32768 + 2048 == 544 * 64
    # weights: an attention operator (the gate as wide as wq), an expert,
    # the dense layer, the vocabulary twice
    op = 2 * 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048
    expert = 3 * 2048 * 1024
    assert (op, expert) == (27262976, 6291456)
    layer = op + 129 * expert + 2048 * 128
    assert layer == pytest.approx(839.1e6, rel=1e-3)
    total = 4 * layer + op + 3 * 2048 * 6144 + 2 * 200192 * 2048
    assert total == pytest.approx(4241e6, rel=1e-3)
    assert "4241 M parameters, 8.48 GB" in config["weights"]
    # the program's tree, counted leaf by leaf, says the same (+ norms)
    import jax
    from ray_tpu.models.llama import init_params
    tree = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    n = sum(a.size for a in jax.tree.leaves(tree))
    assert 0 < n - total < 1e5
    # memory_peak_bytes' floor is a quarter of the chip: far under this
    assert (total * 2 + 2.52e9 + 2.33e9) / 16.9e9 > 0.75


def _decode_step_bytes(dims, rows, window_tokens,
                       full_tokens):
    """What a decode step of ``rows`` sequences has to read, by part, in
    bytes (bf16): the experts a layer that at least one of rows x top_k
    pairs hits (all of them, as good as, at 1024 pairs over 128), the
    window layers' pages at ``window_tokens`` cached tokens a row, the full
    layers' at ``full_tokens``, the head, and the other weights (attention
    operators, the dense layer, the shared experts, the routers). ISSUE
    49's arithmetic."""
    d, f, h, hd = dims["hidden"], dims["expert_ffn"], dims["heads"], \
        dims["score_head"]
    token = dims["kv_heads"] * (dims["score_head"] + dims["value_head"]) * 2
    attn = (2 * d * h * hd + 2 * d * dims["kv_heads"] * hd + h * hd * d) * 2
    layers = dims["full_layers"] + dims["window_layers"]
    return {
        "experts": dims["expert_layers"] * dims["experts"] * 3.0 * d * f * 2,
        "window_pages": dims["window_layers"] * rows * window_tokens * token,
        "full_pages": dims["full_layers"] * rows * full_tokens * token,
        "head": dims["vocab"] * d * 2.0,
        "other_weights": layers * attn
        + dims["dense_layers"] * 3.0 * d * dims["dense_ffn"] * 2
        + dims["expert_layers"] * (dims["shared_experts"] * 3.0 * d * f
                                   + d * dims["experts"]) * 2}


def test_a_decode_steps_bytes_are_what_the_issue_says():
    """ISSUE 49's arithmetic of a decode step at a full batch: 6.44 GB of
    experts, 2.14 of window pages (mean 2039 tokens a row x 4 layers), 1.72
    of full pages (128 rows at mid-life, 6545 tokens), 0.82 of head, 0.40
    of the other weights: 11.5 GB, 14 ms at 819 GB/s."""
    got = _decode_step_bytes(kc.model_dims(_config()), 128, 2039, 6545)
    want = {"experts": 6.44e9, "window_pages": 2.14e9, "full_pages": 1.72e9,
            "head": 0.82e9, "other_weights": 0.40e9}
    for part, nbytes in want.items():
        assert got[part] == pytest.approx(nbytes, rel=0.02), part
    total = sum(got.values())
    assert total == pytest.approx(11.5e9, rel=0.01)
    assert total / kernel_cost.peaks("TPU v5 lite")["hbm_bytes_per_s"] \
        == pytest.approx(14.0e-3, rel=0.02)
    assert got["experts"] / total == pytest.approx(0.56, abs=0.01)
    assert got["head"] / total == pytest.approx(0.07, abs=0.005)


def _log():
    """One request: a 3000-token prompt in chunks of 1000, then two decode
    dispatches of 1 and 2 tokens."""
    return [{"t0_wall": 100.0, "prompt_tokens": 3000, "admits": [[0.0, 0]],
             "chunks": [[0.1, 1000, 1], [0.2, 1000, 2], [0.3, 1000, 3]],
             "ttft": 0.3, "decode": [[0.1, 1], [0.1, 2]]}]


def test_attention_work_on_the_hand_computed_walk():
    from benchmark import kernel_cost_mimo
    dims = kc.model_dims(_config())
    ctx, reads, q = kernel_cost_mimo.attention_sums(_log(), 0.0, 1e9)
    assert (ctx, reads, q) == (
        3000 * 3001 // 2 + 3001 + 3002 + 3003,
        1000 + 2000 + 3000 + 3001 + 3002 + 3003, 3003)
    f, b = kc.full_attention_work(_log(), 0.0, 1e9, dims)
    assert f == 32 * 2 * 256 * ctx * 1
    assert b == (4 * 256 * reads + 32 * 256 * q) * 2 * 1
    ctx, reads, q = kernel_cost_mimo.attention_sums(_log(), 0.0, 1e9, 2048)
    # contexts 1 .. 2048, then 952 + 3 tokens at 2048; a row of n tokens
    # reads min(context, 2047 + n)
    assert ctx == 2048 * 2049 // 2 + 955 * 2048
    assert reads == 1000 + 2000 + 3000 + 3 * 2048 and q == 3003
    f, b = kc.window_attention_work(_log(), 0.0, 1e9, dims)
    assert f == 32 * 2 * 256 * ctx * 4
    assert b == (4 * 256 * reads + 32 * 256 * q) * 2 * 4
    # 128 decode rows of ONE window layer at a full window: 0.54 GB
    rows = 128 * (4 * 256 * 2048 + 32 * 256) * 2
    assert rows == pytest.approx(0.539e9, rel=0.01)


def test_expert_work_is_all_128_experts():
    dims = kc.model_dims(_config())
    f, b = kc.moe_experts_work(1024, 128, dims)
    assert f == 6 * 2048 * 1024 * 1024
    assert b == (3 * 2048 * 1024 * 128 + 2 * 2048 * 1024) * 2
    assert 3 * 2048 * 1024 * 128 * 2 == pytest.approx(1.61e9, rel=1e-2)


#: what the accepted metrics read that this block is read by as the block
#: they were written for is (the same reader, the same arguments; words of
#: tests/_readings.py): the cell joins their lists, since `per_layer` holds
#: 128 entries at most
SHARED_BY_SPEC = [
    "decode_step_ms", "mixed_step_ms", "mixed_step_time_pct",
    "device_idle_pct", "engine_host_gap_ms", "idle_prep_pct",
    "paged_attn_time_pct", "window_attn_time_pct",
    "attn_window_proj_time_pct", "moe_ffn_time_pct",
    "window_pages_held_pct", "moe_shared_time_pct"]
#: the cell's own: the reader's `cost` argument of each
OWN = ["full_attention", "window_attention", "moe_experts"]


def test_the_cell_and_its_metrics_are_in_the_benchmark():
    """The cell and its configuration, found by name, and its metrics by
    what an entry reads (reader, arguments, `moves`, the cell in its
    `workloads`: tests/_readings.py), never by an entry's name or place
    in a list: a later PR appends its own cell, configuration and entries
    after them, and a `benchmark` PR may rename and fold entries."""
    bench = _bench()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "trinity-mini-serve-1chip", "mixed-window", 1)
    assert len(cell["why"]) <= 200
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert config["reduced"] == _config()["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "layer_types"]
    assert len(bench["per_layer"]) <= 128
    for cost in OWN:
        m, _, reader = entry("trinity_roofline", CELL, cost=cost)
        assert reader is trinity_roofline
        assert (m["moves"], m["unit"]) == ("out_tok_per_s", "%")
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "out_tok_per_s")["workloads"]
    for reading in ["replica_ready_s", "batch_occupancy_pct",
                    "engine_host_ms", "chunk_rows_joined_pct"]:
        entry(reading, CELL)
    for reading in SHARED_BY_SPEC:
        assert entry(reading, CELL)[0]["moves"] == "out_tok_per_s", reading
    assert not any(w["chips"] == 4 for w in bench["workloads"])


def test_the_readers_say_nothing_where_there_is_nothing_to_read():
    config = _config()
    for cost in OWN:
        spec = {"args": entry("trinity_roofline", CELL, cost=cost)[1]}
        assert trinity_roofline.read({"config": config},
                                     spec["args"]) is None

        class NoKernel:
            def op_time(self, patterns):
                return 0.0
        data = {"trace_summary": NoKernel(), "config": config,
                "trace": {"start": {"wall": 0.0}, "stop": {"wall": 1.0}},
                "request_log": [], "device": {"kind": "TPU v5 lite"}}
        assert trinity_roofline.read(data, spec["args"]) is None
    # the scopes the shared data files name are the program's, and so are
    # the two that no metric reads yet (PERF.md section 7)
    for scope in (M.SCOPE_SHARED, M.SCOPE_WINDOW_PROJ):
        assert entry("trace_scope", CELL, scope=scope)[1] == {"scope": scope}
    assert (M.SCOPE_GATE, M.SCOPE_HEAD) == ("attn_gate", "lm_head")


def test_the_page_counters_share_on_hand_counts():
    """window_pages_held_pct.mimo's data file over this block's counters;
    rows_inside_window over decode_tokens by the same reader (the data file
    a later `benchmark` PR owes: PERF.md section 7)."""
    a = dict.fromkeys(("decode_tokens", "rows_inside_window",
                       "page_steps_full", "page_steps_window"), 0)
    b = {"decode_tokens": 1200, "rows_inside_window": 150,
         "page_steps_full": 40000, "page_steps_window": 12000}
    data = {"config": _config(), "stats_open": a, "stats_close": b}
    held = _load("metrics", "window_pages_held_pct.mimo.json")
    assert held["reader"] == "engine_clocks"
    assert engine_clocks.read(dict(data), held["args"]) == 30.0
    inside = {"num": ["rows_inside_window"], "den": ["decode_tokens"],
              "scale": 100}
    assert engine_clocks.read(dict(data), inside) == 12.5
    # a program from before the block has no rows_inside_window
    old = {"stats_open": {"decode_tokens": 1}, "stats_close":
           {"decode_tokens": 5}}
    assert engine_clocks.read(old, inside) is None


def test_published_keys_map_to_the_programs_fields():
    from ray_tpu.llm.model import _pattern
    from ray_tpu.models.llama import LlamaConfig
    config = _config()
    fields = serve_trinity.model_fields(config)
    serve_trinity.require_program_support(fields)
    cfg = LlamaConfig.tiny(**fields)
    assert cfg.n_layers == 5 and cfg.n_dense_layers == 1
    assert cfg.layers_of("sliding_attention") == (0, 1, 2, 3)
    assert cfg.layers_of("full_attention") == (4,)
    W, F = "sliding_attention", "full_attention"
    assert _pattern(cfg) == ([(W, "dense")],
                             [(W, "moe")] * 3 + [(F, "moe")], 1)
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.window_kv_heads,
            cfg.qk_head_dim, cfg.v_dim, cfg.head_dim, cfg.ffn_dim,
            cfg.dense_ffn_dim, cfg.shared_ffn_dim, cfg.vocab_size) \
        == (2048, 32, 4, 4, 128, 128, 64, 1024, 6144, 1024, 200192)
    assert (cfg.rope_theta, cfg.window_rope_theta, cfg.norm_eps,
            cfg.sliding_window, cfg.rotary_dim) == (1e4, 1e4, 1e-5, 2048, 0)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.experts_held,
            cfg.router_score, cfg.norm_topk_prob, cfg.router_bias,
            cfg.router_scale, cfg.router_eps) \
        == (128, 8, (), "sigmoid", True, True, 2.826, 1e-20)
    assert cfg.embed_scale == pytest.approx(45.2548, abs=1e-4)
    # the four things no key says: fields of the file
    assert (cfg.attn_gate, cfg.qk_norm_per_head, cfg.full_rope,
            cfg.post_norms) == (True, True, False, True)
    assert {"attn_gate", "qk_norm_per_head", "full_rope", "post_norms"} \
        <= set(config["program_fields"])
    assert not cfg.tie_embeddings and not cfg.attn_sink
    assert config["published"]["num_hidden_layers"] == 32
    assert config["published"]["num_dense_layers"] == 2
    assert len(config["assumed"]) >= 10
    assert "4 : 1" in config["departures"]["layer_types"]
    assert "pipeline stages" in config["deployment"]
    assert "STAGE" in config["deployment"]
    assert "GB" in config["departures"]["compiled_peak"]
    # a correction is one line of the data file
    other = serve_trinity.model_fields(
        {**config, "program_fields": {**config["program_fields"],
                                      "full_rope": True}})
    assert LlamaConfig.tiny(**other).full_rope


def test_the_file_holds_every_number_of_the_catalogs_row():
    """Every key of the catalog's `config` under the same key and with the
    same value, but the three the file lists as reduced."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside the guides here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Trinity-Mini")
    config = _config()
    assert config["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items()
                    if config.get(k, "absent") != v)
    assert differ == sorted(config["reduced"])
    assert row["config"]["num_hidden_layers"] \
        == config["published"]["num_hidden_layers"]
    assert row["config"]["num_dense_layers"] \
        == config["published"]["num_dense_layers"]
    kinds = row["config"]["layer_types"]
    assert kinds == (["sliding_attention"] * 3 + ["full_attention"]) * 8
    # served: the published layer 0 and ONE whole period (layers 4-7)
    assert config["layer_types"] == kinds[:1] + kinds[4:8]


def test_a_program_without_the_block_is_refused_before_any_cluster(
        monkeypatch):
    """What the parent commit does with the new cell: its LlamaConfig has
    no attn_gate, and the runner exits at once."""
    import dataclasses

    from ray_tpu.models import llama
    fields = [f for f in dataclasses.fields(llama.LlamaConfig)
              if f.name not in ("attn_gate", "post_norms", "full_rope")]
    monkeypatch.setattr(dataclasses, "fields", lambda cls: fields)
    with pytest.raises(SystemExit, match="attn_gate"):
        serve_trinity.require_program_support(
            serve_trinity.model_fields(_config()))


@pytest.mark.parametrize("key,value", [
    ("model_type", "deepseek_v3"), ("hidden_act", "gelu"), ("n_group", 2),
    ("topk_group", 2), ("rope_scaling", {"type": "yarn"}),
    ("mup_enabled", False)])
def test_the_runner_refuses_what_the_program_does_not_build(key, value):
    with pytest.raises(ValueError, match=key):
        serve_trinity.model_fields({**_config(), key: value})


def test_rehearsal_cut_keeps_both_kinds_of_layer():
    from ray_tpu.models.llama import LlamaConfig
    config = {**_config(), "hidden_size": 64, "num_hidden_layers": 2,
              "num_attention_heads": 8, "num_key_value_heads": 4,
              "head_dim": 8, "intermediate_size": 128, "vocab_size": 256}
    serve_trinity.cut_for_rehearsal(config)
    fields = serve_trinity.model_fields(config)
    assert fields["layer_types"] == ["sliding_attention"] * 2 \
        + ["full_attention"]
    cfg = LlamaConfig.tiny(**fields)
    assert (cfg.qk_head_dim, cfg.head_dim, cfg.sliding_window,
            cfg.n_experts, cfg.shared_ffn_dim) == (16, 8, 16, 8, 32)
    assert cfg.gated_block and cfg.embed_scale == 8.0
    assert _config()["num_experts"] == 128                  # a copy


def test_traffic_file_holds_what_the_issue_names():
    mix = _load("traffic", "mixed-window.json")
    assert (mix["kind"], mix["runner"], mix["clients_from"]) == (
        "closed_loop", "serve_trinity", "engine.max_batch")
    assert (mix["per_client"], mix["score_in_window"],
            mix["reference_pad_to"], mix["trace_after_s"],
            mix["trace_seconds"]) == (6, 4, 12288, 12, 8)
    assert mix["lead_in_s"] == 60
    assert mix["prompt"] == {"median": 4096, "sigma": 0.9, "min": 256,
                             "max": 32768}
    assert mix["output"] == {"median": 1024, "sigma": 0.4, "min": 512,
                             "max": 2048}
    # far beyond the window, beyond it, crossing it while decoding, inside
    assert [(s["prompt"], s["max_tokens"]) for s in mix["sample"]] \
        == [(12000, 128), (3000, 200), (1900, 200), (100, 200)]
    assert all(s["prompt"] + s["max_tokens"] <= mix["reference_pad_to"]
               for s in mix["sample"])
    engine = _config()["engine"]
    assert (engine["max_batch"], engine["page_size"], engine["prefill_rows"],
            engine["decode_chunk"], engine["tp"], engine["prefix_cache"]) \
        == (128, 64, 2, 8, 1, False)
    assert engine["prefill_chunk"] == 1024      # 512 was tried: engine_why
    assert "4120.5" in _config()["engine_why"]
    assert serve_trinity.closed_loop is serve_kanana.closed_loop
    clients = engine["max_batch"]
    plans = [serve_trinity.closed_loop(mix, seed, clients, 200192)
             for seed in (0, 2 ** 31 + 11)]
    offered = [loadgen.offered_work(p) for p in plans]
    assert offered[0] == offered[1] and offered[0]["n"] == 7 * clients == 896
    lens = [sorted(tuple((len(r["prompt"]), r["max_tokens"]) for r in q)
                   for q in p["queues"]) for p in plans]
    assert lens[0] == lens[1]
    # ISSUE 49's arithmetic from the quantiles
    turns = [n for q in plans[0]["queues"] for r in q[1:]
             for n in [len(r["prompt"])]]
    outs = [r["max_tokens"] for q in plans[0]["queues"] for r in q[1:]]
    assert sum(turns) / len(turns) == pytest.approx(5997, abs=1)
    assert sum(outs) / len(outs) == pytest.approx(1096, abs=1)
    assert sum(n < 2048 for n in turns) / len(turns) \
        == pytest.approx(0.22, abs=0.005)
    assert sum(n > 16384 for n in turns) / len(turns) \
        == pytest.approx(0.06, abs=0.005)
    assert sum(len(q[0]["prompt"]) for q in plans[0]["queues"]) \
        == pytest.approx(770e3, rel=0.01)
    assert max(max(r["prompt"]) for q in plans[1]["queues"] for r in q) \
        < 200192
    assert max(turns) + max(outs) <= engine["max_seq_len"]


def _scored(n=100, equal=100, over=0, over1=0):
    """One warm-up request of n tokens: ``equal`` of them the reference's,
    ``over`` of them 0.2 logits under its choice and ``over1`` 2.0."""
    gaps = [2.0] * over1 + [0.2] * over + [0.0] * (n - over - over1)
    return {"warm-up": [{"served": [1] * n, "max_tokens": n,
                         "reference_tokens": [1] * equal + [2] * (n - equal),
                         "gap": gaps}]}


def test_checks_method_is_kept_beside_the_blocks_limits():
    assert not checks_trinity.served_tokens(_scored())
    assert any("no window request" in line for line in
               checks_trinity.served_tokens({**_scored(), "window": []}))
    short = _scored()
    short["warm-up"][0]["max_tokens"] = 160
    assert any("160 asked" in line
               for line in checks_trinity.served_tokens(short))
    inf = _scored()
    inf["warm-up"][0]["gap"][3] = float("inf")
    assert any("gap" in line for line in checks_trinity.served_tokens(inf))
    # a request wholly wrong fails by itself, beside its group and the pool
    wrong = _scored(equal=0, over=0, over1=100)
    assert len(checks_trinity.served_tokens(wrong)) == 1 + 2 + 2


def test_the_runner_holds_a_run_to_the_blocks_limits(monkeypatch):
    """serve_trinity.run puts its own served_tokens (checks_trinity's, and
    the gaps' summary into the notes) and serve_kanana's deal in place for
    the length of serve.run and puts both back, whatever the run does."""
    from benchmark.runners import serve
    seen = {}

    def fake_run(ctx):
        seen["checks"] = serve.checks.served_tokens
        seen["deal"] = loadgen.closed_loop
        seen["session"] = serve.Session
        seen["faults"] = serve.checks.served_tokens(
            _scored(equal=10, over1=80))
        raise RuntimeError("stop here")

    monkeypatch.setattr(serve, "run", fake_run)
    monkeypatch.setattr(serve_trinity, "require_program_support",
                        lambda fields: None)
    before, deal = serve.checks.served_tokens, loadgen.closed_loop
    with pytest.raises(RuntimeError, match="stop here"):
        serve_trinity.run({"rehearse": False, "config": _config()})
    assert seen["checks"] is not before and seen["faults"]
    assert seen["deal"] is serve_kanana.closed_loop
    assert seen["session"] is serve_trinity.Session
    assert serve.checks.served_tokens is before
    assert loadgen.closed_loop is deal
    assert serve.Session is not serve_trinity.Session


def test_hold_readings_method_at_tiny_widths():
    """hold_trinity.readings end to end on the CPU at the rehearsal's
    widths: two holds and two faults of the reference against its float32
    self, cut into the cell's scored requests and put through the block's
    limits. The numbers mean nothing here (the limits are set at the
    published widths); the bf16 hold is no further from the float32 choice
    than the fp8 hold."""
    from benchmark import reference_trinity as ref
    from ray_tpu.models.llama import LlamaConfig
    config = {**_config(), "hidden_size": 64, "num_hidden_layers": 2,
              "num_attention_heads": 8, "num_key_value_heads": 2,
              "head_dim": 8, "vocab_size": 512, "intermediate_size": 128}
    serve_trinity.cut_for_rehearsal(config)
    cfg = LlamaConfig.tiny(**serve_trinity.model_fields(config))
    mix = {"sample": [{"prompt": 40, "max_tokens": 24}],
           "score_in_window": 2, "prompt": {"min": 32},
           "output": {"median": 40}}
    holds = {k: hold_trinity.HOLDS[k]
             for k in ("bf16", "fp8", "no_gate", "no_post_norm")}
    row = hold_trinity.readings(5, cfg, ref.dims_of(cfg), mix, 128, holds)
    assert set(holds) <= set(row)
    assert row["bf16"]["equal"] >= row["fp8"]["equal"]
    assert row["bf16"]["equal"] > row["no_gate"]["equal"]
    assert row["bf16"]["equal"] > row["no_post_norm"]["equal"]
    assert [s["tokens"] for s in row["fp8"]["groups"].values()] == [24, 80]
    assert row["bf16"]["correct"] == (not row["bf16"]["faults"])
    assert set(hold_trinity.MUST_PASS) == set(hold_trinity.HOLDS)
    assert [k for k, v in hold_trinity.MUST_PASS.items() if v] \
        == ["bf16", "bf16_matmul"]
    assert [k for k, v in hold_trinity.MUST_PASS.items() if v is None] \
        == ["window_off_by_one"]
    assert set(ref.FAULTS) < set(hold_trinity.HOLDS)
    assert len(ref.FAULTS) == 10


def test_checks_py_limits_are_not_this_blocks():
    passing = _scored(equal=85, over=8, over1=2)
    assert not checks_trinity.served_tokens(passing)
    assert checks.served_tokens(passing)


@pytest.mark.parametrize("case,faults", [
    # the served path's and the bf16 holds' readings pass, the lowest too
    (dict(equal=89, over=5), 0), (dict(n=200, equal=157, over=29), 0),
    # fp8's readings fail, each share by its own limit (a request's equal
    # share with its group's and the pool's)
    (dict(equal=45, over=5), 3), (dict(equal=89, over=46), 2),
    (dict(equal=42, over=46), 5),
    # a full layer rotated: the window group's and the pool's equal share
    (dict(equal=73, over=12), 2),
    # a window off by one reads inside the rounding's range
    (dict(equal=84, over=8), 0)])
def test_each_limit_lies_between_its_two_readings(case, faults):
    """Over 10 served runs and one seed of the hold study (my chip runs,
    PR 49, hold_trinity.py at the published widths): a served group keeps
    85.3-90.9 % of its tokens and 3.5-8.1 % sit more than 0.12 under the
    reference's choice; held in fp8 41.1-41.7 % and 46.3-50.1 %; a request
    78.5-95.3 against 35.9-45.5 %. One line a fault."""
    assert len(checks_trinity.served_tokens(_scored(**case))) == faults
    assert 0.417 + 0.1 < checks_trinity.MIN_EQUAL < 0.853 - 0.1
    assert 0.081 + 0.1 < checks_trinity.MAX_OVER < 0.463 - 0.1
    assert 0.455 + 0.08 < checks_trinity.MIN_EQUAL_REQUEST < 0.785 - 0.08
