"""benchmark/kernel_cost_mimo.py's arithmetic on hand-computed cases: the
block's shape numbers from the published keys, the request log's walk with
and without the window, the work of the two attention kernels and of the
expert kernel over the held experts; the runner's mapping of the published
keys to the program's fields, the served pattern it derives and its
refusals; the rehearsal cut; the cell's traffic file holding the parameters
ISSUE 45 names, the same queues under every seed; the configuration file
against the catalog's row; the readers' silence where there is nothing to
read; and the limits that decide `correct` for the block (checks_mimo.py)
beside checks.py's, with the method of the readings they lie between
(hold_mimo.py) at tiny widths."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from _readings import entry  # noqa: E402
from benchmark import checks, checks_mimo, hold_mimo  # noqa: E402
from benchmark import kernel_cost, kernel_cost_mimo as kc  # noqa: E402
from benchmark import loadgen  # noqa: E402
from benchmark.readers import (engine_clocks, mimo_counters,  # noqa: E402
                               mimo_roofline)
from benchmark.runners import serve_kanana, serve_mimo  # noqa: E402

CELL = "context-mimo-1chip"


def _load(*path):
    with open(os.path.join(ROOT, "benchmark", *path)) as f:
        return json.load(f)


def _config():
    return _load("configs", "mimo-v2-flash-serve-1chip.json")


def test_model_dims_and_the_bytes_the_file_states():
    config = _config()
    dims = kc.model_dims(config)
    assert (dims["full_layers"], dims["window_layers"], dims["dense_layers"],
            dims["expert_layers"]) == (2, 5, 1, 6)
    assert (dims["heads"], dims["kv_heads"], dims["window_kv_heads"],
            dims["score_head"], dims["value_head"], dims["window"]) \
        == (64, 4, 8, 192, 128, 128)
    assert (dims["experts_held"], dims["experts_routed"], dims["top_k"],
            dims["expert_ffn"], dims["hidden"]) == (16, 256, 8, 2048, 4096)
    # the pools, as held: K rows of 256 lanes, V rows of 128, bf16
    full_token = 2 * 4 * (256 + 128) * 2
    win_token = 5 * 8 * (256 + 128) * 2
    assert (full_token, win_token) == (6144, 30720)
    engine = config["engine"]
    assert str(full_token) in config["pools"] \
        and str(win_token) in config["pools"]
    assert engine["total_pages"] * 64 * full_token == pytest.approx(
        5.03e9, rel=2e-3)
    from ray_tpu.llm.cache import window_group_pages
    from ray_tpu.models.llama import LlamaConfig
    cfg = LlamaConfig.tiny(**serve_mimo.model_fields(config))
    group = window_group_pages(cfg, 64, 96, 8, 512, 2)
    assert group == 96 * 4 + 2 * 11 + 1 == 407
    assert "407 pages" in config["pools"]
    assert group * 64 * win_token == pytest.approx(0.80e9, rel=1e-2)
    # at pages of 16 (the other cells'): 1043 pages, 0.51 GB
    assert window_group_pages(cfg, 16, 96, 8, 512, 2) \
        == 96 * 10 + 2 * 41 + 1 == 1043
    # weights: the operators, the dense layer, the held experts, the router
    win_op = 4096 * 64 * 192 + 4096 * 8 * (192 + 128) + 64 * 128 * 4096
    full_op = 4096 * 64 * 192 + 4096 * 4 * (192 + 128) + 64 * 128 * 4096
    expert = 3 * 4096 * 2048
    assert (win_op, full_op, expert) == (94371840, 89128960, 25165824)
    total = 5 * win_op + 2 * full_op + 3 * 4096 * 16384 \
        + 6 * (16 * expert + 4096 * 256) + 2 * 19072 * 4096
    assert total * 2 == pytest.approx(6.86e9, rel=2e-3)


def _log():
    """One request: a 300-token prompt in chunks of 200 and 100, then two
    decode dispatches of 1 and 2 tokens."""
    return [{"t0_wall": 100.0, "prompt_tokens": 300, "admits": [[0.0, 0]],
             "chunks": [[0.1, 200, 1], [0.2, 100, 2]], "ttft": 0.2,
             "decode": [[0.1, 1], [0.1, 2]]}]


def test_the_walk_with_and_without_the_window():
    ctx, reads, q = kc.attention_sums(_log(), 0.0, 1e9)
    # chunks: sum 1..200, sum 201..300; decode tokens 2, 3, 4 attend over
    # 301, 302, 303
    want = 200 * 201 // 2 + sum(range(201, 301)) + 301 + 302 + 303
    assert (ctx, reads, q) == (want, 200 + 300 + 301 + 302 + 303, 303)
    ctx, reads, q = kc.attention_sums(_log(), 0.0, 1e9, window=128)
    # chunk 1: 1..128 then 72 tokens at 128; chunk 2: every token at 128; a
    # row of n tokens reads min(context, 127 + n); a decode token 128
    assert ctx == 128 * 129 // 2 + 72 * 128 + 100 * 128 + 3 * 128
    assert reads == 200 + (127 + 100) + 3 * 128 and q == 303
    # the first chunk alone
    assert kc.attention_sums(_log(), 100.05, 100.15, 128)[1] == 200


def test_attention_work_on_the_hand_computed_walk():
    dims = kc.model_dims(_config())
    ctx, reads, q = kc.attention_sums(_log(), 0.0, 1e9)
    f, b = kc.full_attention_work(_log(), 0.0, 1e9, dims)
    assert f == 64 * 2 * 320 * ctx * 2
    assert b == (4 * 320 * reads + 64 * 320 * q) * 2 * 2
    ctx, reads, q = kc.attention_sums(_log(), 0.0, 1e9, 128)
    f, b = kc.window_attention_work(_log(), 0.0, 1e9, dims)
    assert f == 64 * 2 * 320 * ctx * 5
    assert b == (8 * 320 * reads + 64 * 320 * q) * 2 * 5
    # 96 decode rows of a window layer: 63 MB, 77 us at the HBM peak
    rows = 96 * (8 * 320 * 128 + 64 * 320) * 2
    assert rows == pytest.approx(63e6, rel=0.08)
    assert rows / kernel_cost.peaks("TPU v5 lite")["hbm_bytes_per_s"] \
        == pytest.approx(81e-6, rel=0.05)
    # the held K row reads at best 83 % of what the pool moves
    assert (192 + 128) / (256 + 128) == pytest.approx(0.833, abs=1e-3)


def test_expert_work_is_the_held_experts():
    dims = kc.model_dims(_config())
    f, b = kc.moe_experts_work(48, 16, dims)
    assert f == 6 * 4096 * 2048 * 48
    assert b == (3 * 4096 * 2048 * 16 + 2 * 4096 * 48) * 2
    # a decode step streams 0.805 GB of held experts a layer
    assert 3 * 4096 * 2048 * 16 * 2 == pytest.approx(0.805e9, rel=1e-3)


def test_the_readers_say_nothing_where_there_is_nothing_to_read():
    config = _config()
    for name in ("paged_attn_roofline.mimo", "window_attn_roofline.mimo",
                 "moe_ffn_roofline.mimo"):
        args = _load("metrics", name + ".json")["args"]
        assert mimo_roofline.read({"config": config}, args) is None

        class NoKernel:
            def op_time(self, patterns):
                return 0.0
        data = {"trace_summary": NoKernel(), "config": config,
                "trace": {"start": {"wall": 0.0}, "stop": {"wall": 1.0}},
                "request_log": [], "device": {"kind": "TPU v5 lite"}}
        assert mimo_roofline.read(data, args) is None
    for name in ("moe_experts_hit_pct.mimo", "moe_load_skew.mimo"):
        args = _load("metrics", name + ".json")["args"]
        assert mimo_counters.read({"config": config}, args) is None
        assert mimo_counters.read({"config": config, "stats_open": {"a": 1},
                                   "stats_close": {"a": 2}}, args) is None
    # a program from before the block has none of the three counters
    old = {"stats_open": {"moe_pairs": 1}, "stats_close": {"moe_pairs": 5}}
    for reading in ("moe_absent_pct", "window_pages_held_pct"):
        _, args, reader = entry(reading, CELL)
        assert reader is engine_clocks
        assert engine_clocks.read(dict(old), args) is None


def test_counter_metrics_on_hand_counts():
    config = _config()
    a = {"moe_pairs": 0, "moe_hits": 0, "moe_hot": 0, "decode_steps": 0,
         "moe_absent": 0, "page_steps_full": 0, "page_steps_window": 0}
    b = {"moe_pairs": 480, "moe_hits": 720, "moe_hot": 90,
         "decode_steps": 10, "moe_absent": 7200, "page_steps_full": 40000,
         "page_steps_window": 1000}
    data = {"config": config, "stats_open": a, "stats_close": b}

    def read(name, reader):
        return reader.read(dict(data),
                           _load("metrics", name + ".json")["args"])
    assert read("moe_experts_hit_pct.mimo", mimo_counters) \
        == 100 * 720 / (10 * 6 * 16)
    assert read("moe_load_skew.mimo", mimo_counters) == 90 * 16 / 480
    # ... and the two the cell shares, found by what they read
    assert engine_clocks.read(
        dict(data), entry("moe_absent_pct", CELL)[1]) == 93.75
    assert engine_clocks.read(
        dict(data), entry("window_pages_held_pct", CELL)[1]) == 2.5


def test_published_keys_map_to_the_programs_fields():
    from ray_tpu.llm.model import _pattern
    from ray_tpu.models.llama import LlamaConfig
    config = _config()
    fields = serve_mimo.model_fields(config)
    serve_mimo.require_program_support(fields)
    cfg = LlamaConfig.tiny(**fields)
    assert cfg.n_layers == 7
    assert cfg.layers_of("full_attention") == (0, 6)
    assert cfg.layers_of("sliding_attention") == (1, 2, 3, 4, 5)
    assert _pattern(cfg)[2] == 1 and len(_pattern(cfg)[1]) == 6
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.window_kv_heads,
            cfg.qk_head_dim, cfg.v_dim, cfg.rotary_dim, cfg.ffn_dim,
            cfg.dense_ffn_dim, cfg.vocab_size) \
        == (4096, 64, 4, 8, 192, 128, 64, 2048, 16384, 19072)
    assert (cfg.rope_theta, cfg.window_rope_theta, cfg.norm_eps,
            cfg.value_scale, cfg.sliding_window) \
        == (5e6, 1e4, 1e-5, 0.707, 128)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.experts_held,
            cfg.router_score, cfg.norm_topk_prob, cfg.router_bias,
            cfg.router_scale) == (256, 8, (0, 16), "sigmoid", True, True, 1.0)
    assert cfg.attn_sink and not cfg.tie_embeddings and not cfg.shared_ffn_dim
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 48,
                                   "n_routed_experts": 256,
                                   "vocab_size": 152576}
    assert len(config["assumed"]) >= 10 and "16 chips" in config["deployment"]
    assert "multi-token-prediction" in config["departures"]["not_served"]
    # the published depth serves the published pattern as it is
    whole = {**config, "num_hidden_layers": 48}
    assert serve_mimo.served_pattern(whole) == config["hybrid_layer_pattern"]
    # two periods behind the dense layer
    assert serve_mimo.served_pattern({**config, "num_hidden_layers": 13}) \
        == [0] + [1, 1, 1, 1, 1, 0] * 2
    with pytest.raises(ValueError, match="whole periods of 6"):
        serve_mimo.served_pattern({**config, "num_hidden_layers": 8})


def test_the_file_holds_every_number_of_the_catalogs_row():
    """Every key of the catalog's `config` under the same key and with the
    same value, but the three the file lists as reduced; the two published
    lists whole."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside the guides here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "MiMo-V2-Flash")
    config = _config()
    assert config["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items()
                    if config.get(k, "absent") != v)
    assert differ == sorted(config["reduced"])
    assert {k: row["config"][k] for k in differ} == config["published"]


def test_a_program_without_the_block_is_refused_before_any_cluster(
        monkeypatch):
    """What the parent commit does with the new cell: its LlamaConfig has
    no sliding_window, and the runner exits at once."""
    import dataclasses

    from ray_tpu.models import llama
    fields = [f for f in dataclasses.fields(llama.LlamaConfig)
              if f.name != "sliding_window"]
    monkeypatch.setattr(dataclasses, "fields", lambda cls: fields)
    with pytest.raises(SystemExit, match="sliding_window"):
        serve_mimo.require_program_support(
            serve_mimo.model_fields(_config()))


@pytest.mark.parametrize("key,value", [
    ("model_type", "deepseek_v3"), ("attention_bias", True),
    ("add_full_attention_sink_bias", True), ("n_shared_experts", 1),
    ("routed_scaling_factor", 2.5), ("topk_method", "greedy"),
    ("swa_head_dim", 128), ("attention_chunk_size", 256),
    ("n_routed_experts", 32)])
def test_the_runner_refuses_what_the_program_does_not_build(key, value):
    with pytest.raises(ValueError, match="HELD" if key == "n_routed_experts"
                       else key):
        serve_mimo.model_fields({**_config(), key: value})


def test_rehearsal_cut_keeps_both_kinds_of_layer_and_a_share():
    from ray_tpu.models.llama import LlamaConfig
    config = {**_config(), "hidden_size": 64, "num_hidden_layers": 2,
              "num_attention_heads": 8, "num_key_value_heads": 4,
              "head_dim": 8, "intermediate_size": 128, "vocab_size": 256}
    serve_mimo.cut_for_rehearsal(config)
    fields = serve_mimo.model_fields(config)
    assert fields["layer_types"] == ["full_attention", "sliding_attention",
                                     "full_attention"]
    assert (fields["n_experts"], fields["experts_held"]) == (8, [2, 4])
    cfg = LlamaConfig.tiny(**fields)
    assert (cfg.qk_head_dim, cfg.v_dim, cfg.rotary_dim,
            cfg.sliding_window) == (16, 8, 8, 16)
    assert _config()["experts_held"] == [0, 16]             # a copy


def test_traffic_file_holds_what_the_issue_names():
    mix = _load("traffic", "context-window.json")
    assert (mix["kind"], mix["runner"], mix["clients_from"]) == (
        "closed_loop", "serve_mimo", "engine.max_batch")
    assert (mix["per_client"], mix["lead_in_s"], mix["score_in_window"],
            mix["reference_pad_to"], mix["trace_after_s"],
            mix["trace_seconds"]) == (6, 30, 4, 9216, 12, 8)
    assert mix["prompt"] == {"median": 4096, "sigma": 0.7, "min": 1024,
                             "max": 16384}
    assert mix["output"] == {"median": 1536, "sigma": 0.4, "min": 768,
                             "max": 3072}
    assert [(s["prompt"], s["max_tokens"]) for s in mix["sample"]] \
        == [(9000, 96), (2500, 128), (700, 128), (100, 128)]
    assert all(s["prompt"] + s["max_tokens"] <= mix["reference_pad_to"]
               for s in mix["sample"])
    engine = _config()["engine"]
    assert engine["max_seq_len"] == 16384 + 3072
    assert (engine["max_batch"], engine["page_size"], engine["prefill_chunk"],
            engine["prefill_rows"], engine["decode_chunk"], engine["tp"]) \
        == (96, 64, 512, 2, 8, 1)
    assert serve_mimo.closed_loop is serve_kanana.closed_loop
    clients = engine["max_batch"]
    plans = [serve_mimo.closed_loop(mix, seed, clients, 19072)
             for seed in (0, 2 ** 31 + 11)]
    offered = [loadgen.offered_work(p) for p in plans]
    assert offered[0] == offered[1] and offered[0]["n"] == 7 * clients == 672
    lens = [sorted(tuple((len(r["prompt"]), r["max_tokens"]) for r in q)
                   for q in p["queues"]) for p in plans]
    assert lens[0] == lens[1]
    assert max(max(r["prompt"]) for q in plans[1]["queues"] for r in q) \
        < 19072


def _scored(n=100, equal=100, over=0, over1=0):
    """One warm-up request of n tokens: ``equal`` of them the reference's,
    ``over`` of them 0.2 logits under its choice and ``over1`` 2.0."""
    gaps = [2.0] * over1 + [0.2] * over + [0.0] * (n - over - over1)
    return {"warm-up": [{"served": [1] * n, "max_tokens": n,
                         "reference_tokens": [1] * equal + [2] * (n - equal),
                         "gap": gaps}]}


def test_checks_method_is_kept_beside_the_blocks_limits():
    assert not checks_mimo.served_tokens(_scored())
    assert any("no window request" in line for line in
               checks_mimo.served_tokens({**_scored(), "window": []}))
    short = _scored()
    short["warm-up"][0]["max_tokens"] = 160
    assert any("160 asked" in line
               for line in checks_mimo.served_tokens(short))
    inf = _scored()
    inf["warm-up"][0]["gap"][3] = float("inf")
    assert any("gap" in line for line in checks_mimo.served_tokens(inf))
    # a request wholly wrong fails by itself, beside its group and the pool
    wrong = _scored(equal=0, over=0, over1=100)
    assert len(checks_mimo.served_tokens(wrong)) == 1 + 2 + 2
    assert not hasattr(checks_mimo, "MAX_OVER_1")


def test_the_runner_holds_a_run_to_the_blocks_limits(monkeypatch):
    """serve_mimo.run puts its own served_tokens (checks_mimo's, and the
    gaps' summary into the notes) and serve_kanana's deal in place for the
    length of serve.run and puts both back, whatever the run does."""
    from benchmark.runners import serve
    seen = {}

    def fake_run(ctx):
        seen["checks"] = serve.checks.served_tokens
        seen["deal"] = loadgen.closed_loop
        seen["faults"] = serve.checks.served_tokens(
            _scored(equal=10, over1=80))
        raise RuntimeError("stop here")

    monkeypatch.setattr(serve, "run", fake_run)
    monkeypatch.setattr(serve_mimo, "require_program_support",
                        lambda fields: None)
    before, deal = serve.checks.served_tokens, loadgen.closed_loop
    with pytest.raises(RuntimeError, match="stop here"):
        serve_mimo.run({"rehearse": False, "config": _config()})
    assert seen["checks"] is not before and seen["faults"]
    assert seen["deal"] is serve_kanana.closed_loop
    assert serve.checks.served_tokens is before
    assert loadgen.closed_loop is deal
    assert serve.Session is not serve_mimo.Session


def test_hold_readings_method_at_tiny_widths():
    """hold_mimo.readings end to end on the CPU at the rehearsal's widths:
    two holds and one fault of the reference against its float32 self, cut
    into the cell's scored requests and put through the block's limits; the
    sink's share of mass read on the seeded weights. The numbers mean
    nothing here (the limits are set at the published widths); the bf16
    hold is no further from the float32 choice than the fp8 hold."""
    from benchmark import reference_mimo as ref
    from ray_tpu.models.llama import LlamaConfig
    config = {**_config(), "hidden_size": 64, "num_hidden_layers": 2,
              "num_attention_heads": 8, "num_key_value_heads": 2,
              "head_dim": 8, "vocab_size": 512, "intermediate_size": 128}
    serve_mimo.cut_for_rehearsal(config)
    cfg = LlamaConfig.tiny(**serve_mimo.model_fields(config))
    mix = {"sample": [{"prompt": 40, "max_tokens": 24}],
           "score_in_window": 2, "prompt": {"min": 32},
           "output": {"median": 40}}
    holds = {k: hold_mimo.HOLDS[k] for k in ("bf16", "fp8", "no_sink")}
    row = hold_mimo.readings(5, cfg, ref.dims_of(cfg), mix, 128, holds)
    assert set(holds) <= set(row)
    assert row["bf16"]["equal"] >= row["fp8"]["equal"]
    assert [s["tokens"] for s in row["fp8"]["groups"].values()] == [24, 80]
    assert row["bf16"]["correct"] == (not row["bf16"]["faults"])
    lo, mean, hi = row["sink_mass"]["full_window"]
    assert 0 < lo <= mean <= hi < 1
    # in a sequence's first tokens the sink takes nearly all of the mass
    assert row["sink_mass"]["first_16_tokens"][1] > mean
    assert set(hold_mimo.MUST_PASS) == set(hold_mimo.HOLDS)
    assert [k for k, v in hold_mimo.MUST_PASS.items() if v] \
        == ["bf16", "bf16_matmul"]
    assert [k for k, v in hold_mimo.MUST_PASS.items() if v is None] \
        == ["window_off_by_one"]
    assert set(ref.FAULTS) < set(hold_mimo.HOLDS)


@pytest.mark.parametrize("case,faults", [
    # the served path's and the bf16 holds' readings pass
    (dict(equal=96, over=1), 0), (dict(equal=93, over=1), 0),
    # fp8's least readings fail, each share by its own limit (a request's
    # equal share with its group's and the pool's)
    (dict(equal=68, over=1), 3), (dict(equal=96, over=25), 2),
    (dict(equal=58, over=28), 5),
    # a window off by one reads inside the rounding's range
    (dict(equal=92, over=1), 0)])
def test_each_limit_lies_between_its_two_readings(case, faults):
    """Over 2 seeds x 2 groups (my chip runs, PR 45, hold_mimo.py at the
    published widths): held in bf16 with bf16 matmuls the reference keeps
    96.5-97.3 % of a group's tokens and 0.2-0.5 % sit more than 0.12 under
    its choice; held in fp8 57.7-63.5 % and 24.6-28.8 %; a request 93.8-99.0
    against 49.0-68.0 %. One line a fault."""
    assert len(checks_mimo.served_tokens(_scored(**case))) == faults
    assert 0.635 + 0.1 < checks_mimo.MIN_EQUAL < 0.96 - 0.1
    assert 0.008 * 4 < checks_mimo.MAX_OVER < 0.246 / 2
    assert 0.680 + 0.1 < checks_mimo.MIN_EQUAL_REQUEST < 0.927 - 0.1


def test_checks_py_limits_are_not_this_blocks():
    passing = _scored(equal=85, over=8, over1=2)
    assert not checks_mimo.served_tokens(passing)
    assert checks.served_tokens(passing)
