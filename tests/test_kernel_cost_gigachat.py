"""benchmark/kernel_cost_gigachat.py's arithmetic on hand-computed cases: the
block's shape numbers by kind of layer from the published keys, the
parameters, bytes a slot and bytes a token that the configuration file and
ISSUE 55 state, the work of the delta rule's two forms, of the latent kernel
at 64 heads and of the expert kernel over the held share; the runner's
mapping of the published keys to the program's fields and its refusals; the
rehearsal cut; the cell's traffic file holding the parameters ISSUE 55
names, the same queues under every seed; the configuration file against the
catalog's row; the readers' silence where there is nothing to read; the
cell, its configuration and its metrics in BENCHMARK.json, found by name and
by what an entry reads; and the limits that decide `correct` for the block
(checks_gigachat.py), with the method of the readings they lie between
(hold_gigachat.py) at tiny widths."""

import json
import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from _readings import entry  # noqa: E402
from benchmark import checks, checks_gigachat, hold_gigachat  # noqa: E402
from benchmark import kernel_cost_gigachat as kc  # noqa: E402
from benchmark import kernel_cost_kanana, loadgen  # noqa: E402
from benchmark.readers import (engine_clocks, gigachat_counters,  # noqa: E402
                               gigachat_roofline)
from benchmark.runners import serve_gigachat, serve_kanana  # noqa: E402
from ray_tpu.llm import model as M  # noqa: E402
from ray_tpu.models.llama import mechanisms_beyond  # noqa: E402

CELL = "reason-gigachat-1chip"
CONFIG = "gigachat35-432b-a28b-serve-1chip"
OWN = {"delta_update_time_pct.gigachat": ("trace_share", None),
       "delta_update_roofline.gigachat": ("gigachat_roofline",
                                          "delta_update"),
       "delta_chunk_time_pct.gigachat": ("trace_scope", None),
       "delta_chunk_roofline.gigachat": ("gigachat_roofline",
                                         "delta_chunk"),
       "delta_proj_time_pct.gigachat": ("trace_scope", None),
       "mla_attn_roofline.gigachat": ("gigachat_roofline",
                                      "latent_attention"),
       "moe_ffn_roofline.gigachat": ("gigachat_roofline", "moe_experts"),
       "moe_experts_hit_pct.gigachat": ("gigachat_counters", None),
       "moe_load_skew.gigachat": ("gigachat_counters", None)}
#: accepted entries whose readers' arguments hold for this block too: the
#: cell joins their lists and spends no entry
SHARED = ["moe_absent_pct", "mla_proj_time_pct",
          "moe_shared_time_pct", "attn_gate_time_pct",
          "lm_head_time_pct", "decode_step_ms", "mixed_step_ms",
          "mixed_step_time_pct", "device_idle_pct", "engine_host_gap_ms",
          "idle_prep_pct", "paged_attn_time_pct", "moe_ffn_time_pct",
          "batch_occupancy_pct", "replica_ready_s", "engine_host_ms",
          "chunk_rows_joined_pct", "mixed_small_shape_pct", "engine_h2d_ms"]


def _load(*path):
    with open(os.path.join(ROOT, "benchmark", *path)) as f:
        return json.load(f)


def _config():
    return _load("configs", CONFIG + ".json")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_model_dims_by_kind_of_layer():
    dims = kc.model_dims(_config())
    assert (dims["delta_layers"], dims["attn_layers"], dims["dense_layers"],
            dims["expert_layers"]) == (4, 1, 1, 4)
    assert (dims["experts_held"], dims["experts_routed"], dims["top_k"],
            dims["expert_ffn"], dims["shared_ffn"], dims["dense_ffn"]) \
        == (16, 256, 8, 2048, 2048, 18432)
    assert (dims["hidden"], dims["heads"], dims["latent"], dims["rope"],
            dims["nope"], dims["v"], dims["q_rank"], dims["vocab"]) \
        == (7168, 64, 512, 64, 128, 128, 1536, 16032)
    assert (dims["delta_key_heads"], dims["delta_value_heads"],
            dims["delta_key_dim"], dims["delta_value_dim"],
            dims["delta_conv"], dims["delta_chunk"], dims["state_bytes"]) \
        == (32, 64, 128, 128, 4, 64, 4)
    assert kc.conv_channels(dims) == 16384


def test_the_files_arithmetic():
    """ISSUE 55's numbers: 4731.5 M parameters, 16.78 MB of matrix state
    (+ 0.39 MB of conv inputs) a slot, 1280 B a token."""
    config = _config()
    dims = kc.model_dims(config)
    p = kc.parameters(dims)
    assert p["delta_mixer"] == 7168 * 16384 + 2 * 7168 * 8192 \
        + 2 * 7168 * 64 + 4 * 16384 == 235_864_064
    assert p["latent_mixer"] == 7168 * 1536 + 1536 * 12288 + 7168 * 576 \
        + 512 * 16384 + 8192 * 7168 + 7168 * 8192 == 159_842_304
    assert p["expert"] == p["shared"] == 3 * 7168 * 2048 == 44_040_192
    assert p["dense_ffn"] == 3 * 7168 * 18432
    assert p["router"] == 7168 * 256 and p["vocab"] == 2 * 16032 * 7168
    # one WHOLE expert layer's routed experts: no chip holds one
    assert 256 * p["expert"] * 2 == pytest.approx(22.5e9, rel=0.01)
    layer0 = p["delta_mixer"] + p["dense_ffn"]
    ffn = 16 * p["expert"] + p["shared"] + p["router"]
    assert layer0 == pytest.approx(632.2e6, rel=1e-3)
    assert p["delta_mixer"] + ffn == pytest.approx(986.4e6, rel=1e-3)
    assert p["latent_mixer"] + ffn == pytest.approx(910.3e6, rel=1e-3)
    total = kc.total_parameters(dims)
    assert total == layer0 + 3 * (p["delta_mixer"] + ffn) \
        + p["latent_mixer"] + ffn + p["vocab"]
    assert total == pytest.approx(4731.5e6, abs=0.1e6)
    assert "4731.5 M" in config["weights"] and "9.46 GB" in config["weights"]
    matrix = 4 * 64 * 128 * 128 * 4
    assert matrix == pytest.approx(16.78e6, abs=0.01e6)
    assert kc.state_bytes_per_slot(dims) == matrix + 4 * 3 * 16384 * 2
    assert kc.token_row_bytes(dims) == 1280       # 576 values in 640 lanes
    engine = config["engine"]
    pool = engine["total_pages"] * engine["page_size"] * 1280
    state = (engine["max_batch"] + 1) * kc.state_bytes_per_slot(dims)
    # what the replica holds: over a quarter of the chip by the weights alone
    assert 2 * total / 16.91e9 > 0.5
    assert 12.5e9 < 2 * total + pool + state < 14.0e9
    assert "16.78 MB" in config["pools"] and "1280 B" in config["pools"]


def test_the_two_forms_work_by_hand():
    dims = kc.model_dims(_config())
    state = 64 * 128 * 128
    operands = (2 * 32 * 128 + 2 * 64 * 128 + 2 * 64) * 4
    f, b = kc.delta_update_work(10, dims)
    assert f == 7 * state * 10 * 4
    assert b == (2 * state * 4 + operands) * 10 * 4
    # memory-bound on a v5e: 197 TFLOP/s, 819 GB/s
    assert f / 197e12 < b / 819e9
    f, b = kc.delta_chunk_work(1000, 3, dims)
    assert f == 64 * (6 * 128 * 128 + 64 * (3 * 128 + 2 * 128)) * 1000 * 4
    assert b == (operands * 1000 + 2 * state * 4 * 3) * 4
    assert kc.delta_chunk_work(0, 0, dims) == (0.0, 0.0)
    # a request: 700 prompt tokens in chunks of 512 + 188, then decode
    rec = {"t0_wall": 100.0, "prompt_tokens": 700, "admits": [[0.0, 0]],
           "chunks": [[0.1, 512, 1], [0.2, 188, 2]], "ttft": 0.2,
           "decode": [[0.1, 1], [0.1, 8]]}
    assert kc.chunk_tokens([rec], 0.0, 1e9) == (700, 2)
    assert kc.chunk_tokens([rec], 100.15, 1e9) == (188, 1)
    assert kc.decode_tokens([rec], 0.0, 1e9) == (9, 0)
    # a record that folded 40 tokens after its kept entries (the log's cap
    # on entries): they lie between the last kept entry (100.4) and the
    # last token (ttft + tpot x 48 = 100.2 + 2.2), a span takes its share
    # of that stretch, and nothing goes missing
    folded = {**rec, "decode_overflow_tokens": 40, "n_generated": 49,
              "tpot": 2.2 / 48}
    assert kc.decode_tokens([folded], 0.0, 1e9) == (49, 40)
    got, est = kc.decode_tokens([folded], 100.35, 101.4)
    assert est == pytest.approx(20) and got == pytest.approx(8 + 20)
    assert kc.decode_tokens([folded], 0.0, 100.4) == (9, 0)
    # the latent kernel at 64 heads over ONE layer, the expert kernel at
    # the expert's width: the shared functions over this block's dims
    assert kc.latent_attention_work([rec], 0.0, 1e9, dims) \
        == kernel_cost_kanana.latent_attention_work(
            [rec], 0.0, 1e9, {"heads": 64, "latent": 512, "rope": 64,
                              "attn_layers": 1})
    f, b = kc.moe_experts_work(80, 16, dims)
    assert f == 6 * 7168 * 2048 * 80
    assert b == (3 * 7168 * 2048 * 16 + 2 * 7168 * 80) * 2


def test_published_keys_map_to_the_programs_fields():
    from ray_tpu.llm.cache import make_kv_cache
    from ray_tpu.llm.model import _pattern
    from ray_tpu.models.llama import LlamaConfig
    import jax
    config = _config()
    fields = serve_gigachat.model_fields(config)
    serve_gigachat.require_program_support(fields)
    cfg = LlamaConfig.tiny(**fields)
    D, F = "linear_attention", "full_attention"
    assert cfg.n_layers == 5 and cfg.n_dense_layers == 1
    assert cfg.layers_of(D) == (0, 1, 2, 3) and cfg.layers_of(F) == (4,)
    assert _pattern(cfg) == ([(D, "dense")],
                             [(D, "moe")] * 3 + [(F, "moe")], 1)
    assert (cfg.dim, cfg.n_heads, cfg.ffn_dim, cfg.dense_ffn_dim,
            cfg.shared_ffn_dim, cfg.vocab_size) \
        == (7168, 64, 2048, 18432, 2048, 16032)
    assert (cfg.kv_lora_rank, cfg.q_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim) == (512, 1536, 128, 64,
                                                      128)
    assert (cfg.delta_key_heads, cfg.delta_value_heads, cfg.delta_key_dim,
            cfg.delta_value_dim, cfg.delta_conv, cfg.delta_chunk,
            cfg.delta_norm_eps) == (32, 64, 128, 128, 4, 64, 1e-6)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.experts_held,
            cfg.router_score, cfg.norm_topk_prob, cfg.router_bias,
            cfg.router_scale, cfg.router_eps) \
        == (256, 8, (0, 16), "sigmoid", True, True, 2.5, 1e-20)
    m = 0.1 * math.log(8) + 1
    assert cfg.rope_yarn == (8.0, 32768.0, 32.0, 1.0, 1.0, 1.0)
    assert cfg.attn_scale == pytest.approx(192 ** -0.5 * m * m)
    assert (cfg.rope_theta, cfg.norm_eps) == (1e5, 1e-6)
    # the readings no key spells out: one line of the data file each
    assert (cfg.norm_gate, cfg.delta_gate_scale, cfg.ffn_clamp,
            cfg.attn_gate, cfg.post_norms) == (2.0, 2.0, 10.0, True, True)
    assert set(config["program_fields"]) == {
        "param_dtype", "router_bias", "router_eps", "delta_chunk"}
    assert not cfg.tie_embeddings and cfg.gated_block
    assert "linear_attention" in mechanisms_beyond(cfg)
    other = serve_gigachat.model_fields(
        {**config, "program_fields": {**config["program_fields"],
                                      "norm_gate": 0.0}})
    assert LlamaConfig.tiny(**other).norm_gate == 0.0
    # ONE pool: the latent leaf in 640 lanes, the state float32
    engine = config["engine"]
    kv = jax.eval_shape(lambda: make_kv_cache(
        cfg, engine["total_pages"], engine["page_size"],
        max_batch=engine["max_batch"], lane_pad=True))
    slots = engine["max_batch"] + 1
    assert {k: (a.shape, a.dtype.name) for k, a in kv.items()} == {
        "k": ((1, engine["total_pages"], 1, 64, 640), "bfloat16"),
        "delta": ((4, slots, 64, 128, 128), "float32"),
        "delta_conv": ((4, slots, 3, 16384), "bfloat16")}
    assert config["published"] == {
        "num_hidden_layers": 40, "first_k_dense_replace": 3,
        "full_attention_layers": [3, 7, 11, 15, 19, 23, 27, 31, 35, 39],
        "n_routed_experts": 256, "vocab_size": 128256}
    assert len(config["assumed"]) >= 12
    assert "4 : 1" in config["departures"]["depth"]
    assert "multi-token-prediction" in config["departures"]["not_served"]
    assert "one of 16 chips" in config["deployment"]
    assert "pipeline stages" in config["deployment"]
    assert "GB" in config["departures"]["compiled_peak"]
    assert "memory_peak_bytes" in config["departures"]["compiled_peak"]


def test_the_file_holds_every_number_of_the_catalogs_row():
    """Every key of the catalog's `config` under the same key and with the
    same value, but the five the file lists as reduced."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside the guides here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "GigaChat3.5-432B-A28B")
    config = _config()
    assert config["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items()
                    if config.get(k, "absent") != v)
    assert differ == sorted(config["reduced"]) == sorted([
        "num_hidden_layers", "first_k_dense_replace",
        "full_attention_layers", "n_routed_experts", "vocab_size"])
    for key in config["reduced"]:
        assert config["published"][key] == row["config"][key]
    # the floors: a whole period and four layers after the dense one, 8 or
    # more experts held, an eighth of the vocabulary or more
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    assert config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 >= row["config"]["vocab_size"]
    # served: the period's kinds are the published layers 4..7's
    latent = set(row["config"]["full_attention_layers"])
    assert [i in latent for i in range(4, 8)] \
        == [i in config["full_attention_layers"] for i in range(1, 5)]


def test_a_program_without_the_block_is_refused_before_any_cluster(
        monkeypatch):
    """What the parent commit does with the new cell: its LlamaConfig has
    no delta fields, and the runner exits at once."""
    import dataclasses

    from ray_tpu.models import llama
    fields = [f for f in dataclasses.fields(llama.LlamaConfig)
              if not f.name.startswith("delta_")
              and f.name not in ("q_lora_rank", "rope_yarn", "norm_gate",
                                 "ffn_clamp")]
    monkeypatch.setattr(dataclasses, "fields", lambda cls: fields)
    with pytest.raises(SystemExit, match="delta_key_heads"):
        serve_gigachat.require_program_support(
            serve_gigachat.model_fields(_config()))


@pytest.mark.parametrize("key,value", [
    ("model_type", "deepseek_v3"), ("hidden_act", "gelu"), ("n_group", 2),
    ("rope_interleave", False), ("gated_attention", False),
    ("use_shared_expert_sigmoid", True), ("use_mla_scaling_factor", False),
    ("layernorm_type", "pre"), ("linear_attention_type", "GatedDeltaNet")])
def test_the_runner_refuses_what_the_program_does_not_build(key, value):
    with pytest.raises(ValueError, match=key):
        serve_gigachat.model_fields({**_config(), key: value})


def test_rehearsal_cut_keeps_both_kinds_of_layer():
    from ray_tpu.models.llama import LlamaConfig
    config = {**_config(), "hidden_size": 64, "num_hidden_layers": 2,
              "num_attention_heads": 8, "num_key_value_heads": 4,
              "head_dim": 8, "intermediate_size": 128, "vocab_size": 256}
    serve_gigachat.cut_for_rehearsal(config)
    fields = serve_gigachat.model_fields(config)
    assert fields["layer_types"] == ["linear_attention"] * 2 \
        + ["full_attention"]
    cfg = LlamaConfig.tiny(**fields)
    assert (cfg.kv_lora_rank, cfg.q_lora_rank, cfg.n_experts,
            cfg.experts_held, cfg.shared_ffn_dim, cfg.delta_chunk) \
        == (256, 24, 8, (2, 4), 32, 8)
    assert "linear_attention" in mechanisms_beyond(cfg) \
        and cfg.dtype == "float32"
    assert _config()["n_routed_experts"] == 16              # a copy


def test_traffic_file_holds_what_the_issue_names():
    mix = _load("traffic", "reason-delta.json")
    assert (mix["kind"], mix["runner"], mix["clients_from"]) == (
        "closed_loop", "serve_gigachat", "engine.max_batch")
    assert (mix["per_client"], mix["score_in_window"],
            mix["reference_pad_to"], mix["trace_after_s"],
            mix["trace_seconds"]) == (6, 4, 9216, 12, 8)
    assert mix["lead_in_s"] == 30
    assert mix["prompt"] == {"median": 1024, "sigma": 1.0, "min": 128,
                             "max": 16384}
    assert mix["output"] == {"median": 1536, "sigma": 0.4, "min": 768,
                             "max": 3072}
    # 17 chunk boundaries from a slot's state; shorter than a chunk
    assert [(s["prompt"], s["max_tokens"]) for s in mix["sample"]] \
        == [(9000, 96), (2500, 128), (700, 128), (100, 128)]
    assert all(s["prompt"] + s["max_tokens"] <= mix["reference_pad_to"]
               and s["max_tokens"] >= 96 for s in mix["sample"])
    engine = _config()["engine"]
    assert 9000 // engine["prefill_chunk"] == 17
    assert (engine["page_size"], engine["prefill_chunk"],
            engine["prefill_rows"], engine["decode_chunk"], engine["tp"]) \
        == (64, 512, 2, 8, 1)
    assert engine["max_batch"] >= 160 and engine["max_batch"] % 16 == 0
    assert engine["max_seq_len"] == 16384 + 3072 == 19456
    # pages first: 160 sequences at mid-life x 1.5
    assert engine["total_pages"] * engine["page_size"] \
        == pytest.approx(160 * 2460 * 1.5, rel=0.01)
    assert serve_gigachat.closed_loop is serve_kanana.closed_loop
    clients = engine["max_batch"]
    plans = [serve_gigachat.closed_loop(mix, seed, clients, 16032)
             for seed in (0, 2 ** 31 + 11)]
    offered = [loadgen.offered_work(p) for p in plans]
    assert offered[0] == offered[1] and offered[0]["n"] == 7 * clients
    lens = [sorted(tuple((len(r["prompt"]), r["max_tokens"]) for r in q)
                   for q in p["queues"]) for p in plans]
    assert lens[0] == lens[1]
    assert max(t for q in plans[0]["queues"] for r in q
               for t in r["prompt"]) < 16032
    # ISSUE 55's arithmetic from the quantiles
    turns = [len(r["prompt"]) for q in plans[0]["queues"] for r in q[1:]]
    assert sum(turns) / len(turns) == pytest.approx(1700, abs=60)
    assert sum(n > 4096 for n in turns) / len(turns) \
        == pytest.approx(0.08, abs=0.01)
    assert sum(n > 8192 for n in turns) / len(turns) \
        == pytest.approx(0.02, abs=0.005)


def test_the_cell_and_its_metrics_are_in_the_benchmark():
    """The cell and its configuration by name, its metrics by what an
    entry reads (tests/_readings.py), never by an entry's name or place: a
    later PR appends after them, a `benchmark` PR renames and folds."""
    bench = _bench()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "reason-delta", 1)
    assert len(cell["why"]) <= 200
    config = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == _config()["reduced"]
    assert config["file"] == f"benchmark/configs/{CONFIG}.json"
    assert len(bench["per_layer"]) <= 128 and len(OWN) <= 12
    for name, (reader, cost) in OWN.items():
        # the data file is the cell's own; the entry is the one that reads
        # what it reads
        spec = _load("metrics", name + ".json")
        m, args, _ = entry(reader, CELL, **spec["args"])
        assert (spec["reader"], args.get("cost")) == (reader, cost)
        assert m["moves"] == "out_tok_per_s"
        if reader == "gigachat_roofline":
            assert m["unit"] == "%" and m["better"] == "higher"
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "out_tok_per_s")["workloads"]
    # one entry a reading for the cell (entry() fails on two)
    for reading in SHARED:
        entry(reading, CELL)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 0


def test_the_readers_say_nothing_where_there_is_nothing_to_read():
    config = _config()

    class NoKernel:
        busy_s = 1.0

        def op_time(self, patterns):
            return 0.0
    for name, (reader, cost) in OWN.items():
        spec = _load("metrics", name + ".json")
        assert spec["name"] == name and spec["reader"] == reader
        if reader != "gigachat_roofline":
            continue
        assert gigachat_roofline.read({"config": config},
                                      spec["args"]) is None
        data = {"trace_summary": NoKernel(), "config": config,
                "trace": {"start": {"wall": 0.0}, "stop": {"wall": 1.0}},
                "request_log": [], "device": {"kind": "TPU v5 lite"}}
        assert gigachat_roofline.read(data, spec["args"]) is None
    for quantity in ("hit_pct", "load_skew"):
        assert gigachat_counters.read({"config": config},
                                      {"quantity": quantity}) is None
    # the scopes and the kernel's name the data files read are the program's
    from ray_tpu.ops import delta
    assert _load("metrics", "delta_chunk_time_pct.gigachat.json")["args"] \
        == {"scope": M.SCOPE_DELTA_CHUNK}
    assert _load("metrics", "delta_proj_time_pct.gigachat.json")["args"] \
        == {"scope": M.SCOPE_DELTA_PROJ}
    assert _load("metrics", "delta_update_time_pct.gigachat.json")["args"] \
        == {"patterns": ["^" + delta._delta_update_pallas.__name__]}
    assert M.SCOPE_DELTA_UPDATE == "delta_update"


def test_the_counters_ratios_on_hand_counts():
    """16 experts held in 4 expert layers; 93.75 % of the pairs absent under
    even routing (the accepted moe_absent_pct.mimo's data file)."""
    keys = ("moe_pairs", "moe_hits", "moe_hot", "moe_absent",
            "decode_steps")
    data = {"config": _config(), "stats_open": dict.fromkeys(keys, 0),
            "stats_close": {"moe_pairs": 3520, "moe_hits": 576,
                            "moe_hot": 660, "moe_absent": 52800,
                            "decode_steps": 10}}
    assert gigachat_counters.read(data, {"quantity": "hit_pct"}) \
        == 100 * 576 / (10 * 4 * 16) == 90.0
    assert gigachat_counters.read(data, {"quantity": "load_skew"}) \
        == 660 * 16 / 3520 == 3.0
    absent = _load("metrics", "moe_absent_pct.mimo.json")
    assert engine_clocks.read(dict(data), absent["args"]) == 93.75


def _sample(n, wrong=0, gap=0.5, state_error=2e-6):
    toks = list(range(n))
    return {"served": toks, "max_tokens": n, "state_error": state_error,
            "reference_tokens": [t + (i < wrong) for i, t in enumerate(toks)],
            "gap": [gap if i < wrong else 0.0 for i in range(n)]}


def test_the_limits_of_correct():
    lim = checks_gigachat
    assert checks.MIN_EQUAL > lim.MIN_EQUAL > lim.MIN_EQUAL_REQUEST > 0.5
    assert 0 < lim.MAX_OVER < 0.13 and not hasattr(lim, "MAX_OVER_1")
    good = {"warm-up": [_sample(128, 2), _sample(96)],
            "window": [_sample(1536, 30)]}
    assert lim.served_tokens(good) == []
    # a group wholly wrong cannot hide in the pool
    assert any("warm-up" in f for f in lim.served_tokens(
        {"warm-up": [_sample(96, 96, gap=2.0)],
         "window": [_sample(1536)] * 4}))
    # ... nor one request of eight
    bad = lim.served_tokens(
        {"warm-up": [_sample(128)] * 4,
         "window": [_sample(1536)] * 3 + [_sample(1536, 1500, gap=0.05)]})
    assert any("sample 3" in f for f in bad)
    assert any("no window request" in f
               for f in lim.served_tokens({"warm-up": [_sample(96)],
                                           "window": []}))
    # the recurrent state of EVERY scored request, against the reference's
    # on the same inputs, between the chip's readings (the program 1.04e-4
    # at most, the state held in bf16 1.0e-2 at least); a state rounded to
    # bf16 ONCE reads 1.6e-3
    assert 5 * 1.04e-4 < lim.MAX_STATE_ERROR < 1.0e-2 / 5
    for err in (1.6e-3, float("nan"), None):
        off = _sample(1536, state_error=err)
        assert any("window sample 1: the recurrent state" in f
                   for f in lim.served_tokens(
                       {"warm-up": [_sample(96)],
                        "window": [_sample(1536), off]}))
    short = _sample(128)
    short["served"] = short["served"][:100]
    assert any("100 tokens served" in f for f in lim.served_tokens(
        {"warm-up": [short], "window": [_sample(64)]}))


def test_the_hold_studys_method_at_tiny_widths():
    """hold_gigachat.readings on the CPU: the float32 reference agrees with
    itself, a fault moves tokens, a state held in bf16 comes out NOT correct
    by the state's limit alone and the program's recurrence passes it. The
    method, not the numbers."""
    import jax.numpy as jnp

    from benchmark import reference_gigachat as ref
    from ray_tpu.models.llama import LlamaConfig
    config = {**_config(), "hidden_size": 64, "num_hidden_layers": 2,
              "num_attention_heads": 8, "num_key_value_heads": 4,
              "head_dim": 8, "intermediate_size": 128, "vocab_size": 256}
    serve_gigachat.cut_for_rehearsal(config)
    cfg = LlamaConfig.tiny(**serve_gigachat.model_fields(config))
    mix = {"sample": [{"prompt": 40, "max_tokens": 16}],
           "score_in_window": 2, "output": {"median": 12},
           "prompt": {"min": 4}}
    assert set(hold_gigachat.HOLDS) == {
        "bf16", "bf16_matmul", "state_bf16", "fp8", *ref.FAULTS}
    assert [k for k, v in hold_gigachat.MUST_PASS.items() if v] \
        == ["bf16", "bf16_matmul"]
    assert [k for k, v in hold_gigachat.MUST_PASS.items() if v is None] \
        == ["no_yarn"]
    assert hold_gigachat.MUST_PASS["state_bf16"] is False
    holds = {"float32": (None, "highest", None, None),
             "state_bf16": (jnp.float32, "highest", jnp.bfloat16, None),
             "no_read": (None, "highest", None, "no_read")}
    row = hold_gigachat.readings(3, cfg, ref.dims_of(cfg), mix, 64, holds,
                                 chunk_row=16)
    assert row["float32"]["equal"] == 1.0 and row["float32"]["worst"] == 0.0
    assert row["float32"]["correct"]
    assert row["no_read"]["equal"] < 1.0
    assert row["state_bf16"]["worst"] >= 0.0
    assert not row["state_bf16"]["correct"] and all(
        "the recurrent state" in f for f in row["state_bf16"]["faults"])
    held = [e for g in row["state_bf16"]["state_error"].values() for e in g]
    assert len(held) == 3 and min(held) > checks_gigachat.MAX_STATE_ERROR
    assert row["served_state"]["correct"] and all(
        e < checks_gigachat.MAX_STATE_ERROR / 10
        for g in row["served_state"]["state_error"].values() for e in g)
    assert set(row["float32"]["groups"]) == {"warm-up", "window"}
