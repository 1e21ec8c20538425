"""benchmark/kernel_cost_ouro.py's arithmetic on hand-computed cases: the
block's shape numbers from the published keys (192 attention readings a
token: 48 layers x 4 passes), what a token costs over every page plane
(1,572,864 B), the decode step's 25.2 GB at the cell's sizes, the shared
paged-attention work over this block's dims; the runner's mapping of the
published keys to the program's fields; the cell's traffic file holding the
parameters ISSUE 66 names, the same queues under every seed; the new
`per_layer` entries found by what they READ (tests/_readings.py), never by
name or place; and the limits that decide `correct` for the block
(checks_ouro.py), with the method of the readings they lie between
(hold_ouro.py) at tiny widths."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from _readings import entry  # noqa: E402
from benchmark import checks_ouro, hold_ouro, kernel_cost  # noqa: E402
from benchmark import kernel_cost_ouro as kc  # noqa: E402
from benchmark import loadgen  # noqa: E402
from benchmark.readers import ouro_counters, ouro_roofline  # noqa: E402
from benchmark.runners import serve_ouro  # noqa: E402

CELL = "reason-ouro-1chip"
REC = {"t0_wall": 100.0, "prompt_tokens": 3, "admits": [[0.0, 0]],
       "chunks": [[0.1, 3, 1]], "ttft": 0.1, "decode": [[0.1, 1], [0.1, 2]]}


def _load(*path):
    with open(os.path.join(ROOT, "benchmark", *path)) as f:
        return json.load(f)


def _config():
    return _load("configs", "ouro-2.6b-serve-1chip.json")


def _published():
    """The catalog row's `config` (model-configs guide,
    architectures.jsonl), where the catalog is installed."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(path) as f:
        return next(row for row in map(json.loads, f)
                    if row["name"] == "Ouro-2.6B")


def test_the_file_holds_every_published_key_and_cuts_nothing():
    config, row = _config(), _published()
    assert {k: config[k] for k in row["config"]} == row["config"]
    assert config["source"] == row["source_url"]
    assert config["reduced"] == [] and len(config["assumed"]) >= 8
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    listed = next(c for c in bench["configs"] if c["name"] == config["name"])
    assert listed["reduced"] == [] and listed["source"] == config["source"]
    assert listed["file"] == "benchmark/configs/ouro-2.6b-serve-1chip.json"
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        config["name"], "reason-loop", 1)
    # every reading no key carries is a program field beside its line
    assert config["program_fields"] == {"param_dtype": "bfloat16",
                                        "post_norms": True}


def test_model_dims_count_an_attention_reading_a_pass_and_layer():
    dims = kc.model_dims(_config())
    assert (dims["passes"], dims["stack_layers"], dims["layers"]) \
        == (4, 48, 192)
    assert (dims["heads"], dims["kv_heads"], dims["head_dim"]) \
        == (16, 16, 128)
    assert kc.kv_token_bytes(dims) == 1_572_864 == 192 * 8192
    # q, k, v, o of 2048 x 2048, a SwiGLU of 3 x 2048 x 5632, four norms
    assert kc.layer_params(dims) == 4 * 2048 ** 2 + 3 * 2048 * 5632 \
        + 4 * 2048 == 51_388_416
    # the shared dims would count a quarter of the readings
    assert kernel_cost.model_dims(_config())["layers"] == 48


def test_the_decode_step_moves_25_gb_at_the_cells_sizes():
    """4 x 4.93 GB of layers + 0.20 GB of head + 3.4 k tokens x 1.57 MB of
    pages: 25.2 GB, 30.8 ms at 819 GB/s; eight rows are memory-bound by
    two orders of magnitude."""
    dims = kc.model_dims(_config())
    flops, stream = kc.stream_work(8, dims)
    assert stream == (4 * 48 * 51_388_416 + 49152 * 2048) * 2
    assert 19.9e9 < stream < 20.0e9
    step = kc.decode_step_bytes(3400, dims)
    assert step == stream + 3400 * 1_572_864 and 25.2e9 < step < 25.3e9
    assert 30.7 < 1e3 * step / 819e9 < 30.9
    assert flops == 2 * 8 * (4 * 48 * 51_388_416 + 49152 * 2048)
    pct, bound = kernel_cost.roofline_pct(flops, stream, 2 * stream / 819e9,
                                          "TPU v5 lite")
    assert bound == "memory" and 49.9 < pct < 50.1


def test_paged_work_is_the_shared_function_over_192_readings():
    dims = kc.model_dims(_config())
    f, b = kernel_cost.paged_attention_work([REC], 0.0, 1e9, dims)
    ctx = (1 + 2 + 3) + (3 + 1) + (3 + 2) + (3 + 3)      # per query token
    assert f == 4 * 16 * 128 * ctx * 192
    reads = 3 + (4 + 5 + 6)
    assert b == (2 * 16 * 128 * reads + 2 * 16 * 128 * 6) * 2 * 192
    shared = kernel_cost.paged_attention_work(
        [REC], 0.0, 1e9, kernel_cost.model_dims(_config()))
    assert (f, b) == (4 * shared[0], 4 * shared[1])


def test_published_keys_map_to_the_programs_fields():
    from ray_tpu.llm.cache import page_planes
    from ray_tpu.models.llama import LlamaConfig
    fields = serve_ouro.model_fields(_config())
    serve_ouro.require_program_support(fields)
    cfg = LlamaConfig(**fields)
    assert (cfg.n_layers, cfg.ut_steps, cfg.head_dim) == (48, 4, 128)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.ffn_dim) == (16, 16, 5632)
    assert cfg.post_norms and not cfg.tie_embeddings and not cfg.attn_gate
    assert (cfg.rope_theta, cfg.norm_eps, cfg.vocab_size) \
        == (1e6, 1e-6, 49152)
    assert page_planes(cfg) == 192
    engine = _config()["engine"]
    assert engine["max_batch"] == 7 and engine["page_size"] == 16
    assert engine["max_seq_len"] >= 512 + 1024 + engine["decode_chunk"]
    assert engine["max_seq_len"] % engine["page_size"] == 0
    # the pool holds the batch at mid-life with its phase spread (and
    # would, by that rule, hold 8: sized down for the preemptions it saw)
    assert engine["total_pages"] * 16 >= 8 * 420 * 1.5


@pytest.mark.parametrize("key,value", [
    ("early_exit_threshold", 0.5), ("model_type", "llama"),
    ("use_sliding_window", True), ("sliding_window", 4096),
    ("layer_types", ["full_attention"] * 47 + ["sliding_attention"])])
def test_the_runner_refuses_what_the_program_does_not_build(key, value):
    with pytest.raises(ValueError, match=key):
        serve_ouro.model_fields({**_config(), key: value})


def test_a_program_without_the_field_fails_before_any_cluster(monkeypatch):
    """A commit from before the block was served: the runner exits with a
    message that names the missing field, at once."""
    import dataclasses

    from ray_tpu.models import llama
    real = dataclasses.fields
    monkeypatch.setattr(
        dataclasses, "fields",
        lambda c: [f for f in real(c) if not (c is llama.LlamaConfig
                                              and f.name == "ut_steps")])
    with pytest.raises(SystemExit, match="ut_steps"):
        serve_ouro.run({"rehearse": False, "config": _config()})


def test_rehearsal_cut_keeps_the_passes():
    config = {**_config(), "hidden_size": 64, "num_hidden_layers": 2,
              "num_attention_heads": 8, "num_key_value_heads": 4,
              "head_dim": 8, "intermediate_size": 128}
    serve_ouro.cut_for_rehearsal(config)
    fields = serve_ouro.model_fields(config)
    assert fields["n_layers"] == 2 and fields["ut_steps"] == 4


def test_traffic_file_holds_what_the_issue_names():
    mix, reason = _load("traffic", "reason-loop.json"), \
        _load("traffic", "reason.json")
    assert (mix["kind"], mix["runner"], mix["clients_from"]) == (
        "closed_loop", "serve_ouro", "engine.max_batch")
    assert (mix["per_client"], mix["lead_in_s"], mix["score_in_window"],
            mix["reference_pad_to"], mix["trace_after_s"],
            mix["trace_seconds"]) == (8, 12, 4, 768, 12, 8)
    assert mix["prompt"] == {"median": 128, "sigma": 0.5, "min": 32,
                             "max": 512}
    assert mix["output"] == {"median": 512, "sigma": 0.4, "min": 256,
                             "max": 1024}
    # the lengths of `reason`, number for number
    for key in ("prompt", "output", "sample", "per_client", "lead_in_s",
                "reference_pad_to", "score_in_window", "trace_after_s",
                "trace_seconds"):
        assert mix[key] == reason[key], key
    assert [(s["prompt"], s["max_tokens"]) for s in mix["sample"]] == [
        (600, 32), (200, 24), (96, 32), (40, 16)]
    # one scored prompt crosses a chunk boundary
    engine = _config()["engine"]
    assert any(s["prompt"] > engine["prefill_chunk"] for s in mix["sample"])
    plans = [loadgen.closed_loop(mix, seed, engine["max_batch"], 49152)
             for seed in (0, 2 ** 31 + 11)]
    offered = [loadgen.offered_work(p) for p in plans]
    assert offered[0] == offered[1] \
        and offered[0]["n"] == 9 * engine["max_batch"]


# ------------------------------------------------------- the entries, read

def test_the_cells_own_entries_are_found_by_what_they_read():
    roof, args, reader = entry("ouro_roofline", CELL, cost="paged_attention")
    assert reader is ouro_roofline and roof["moves"] == "out_tok_per_s"
    assert args["patterns"] == ["^_ragged_attention_pallas"]
    assert roof["unit"] == "%" and roof["name"].endswith("_roofline.ouro")
    stream, args, _ = entry("ouro_roofline", CELL, cost="layers_stream")
    assert args["steps"] == "engine.decode_chunk" \
        and args["rows"] == "engine.max_batch"
    assert stream["unit"] == "%" and "_roofline" in stream["name"]
    for told in (dict(key="kv_token_bytes"),
                 dict(quantity="exit_pass_mean")):
        m, _, reader = entry("ouro_counters", CELL, **told)
        assert reader is ouro_counters and m["workloads"] == [CELL]
    scope, args, _ = entry("trace_scope", CELL, scope="ut_exit")
    assert scope["workloads"] == [CELL] and scope["unit"] == "%"
    # ... and the accepted entries the cell joined, by what THEY read
    for reading in ("decode_step_ms", "mixed_step_ms", "mixed_step_time_pct",
                    "device_idle_pct", "paged_attn_time_pct",
                    "lm_head_time_pct", "batch_occupancy_pct",
                    "engine_host_gap_ms", "idle_prep_pct", "engine_host_ms",
                    "replica_ready_s", "chunk_rows_joined_pct",
                    "mixed_small_shape_pct", "engine_h2d_ms",
                    "h2d_arrays_a_dispatch"):
        m, _, _ = entry(reading, CELL)
        assert m["moves"] in ("out_tok_per_s", "setup_s"), reading
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert len(bench["per_layer"]) <= 128
    assert len(bench["workloads"]) == 13 and len(bench["configs"]) == 12


def test_the_counters_reader_reads_the_stats_and_the_exit_counts():
    data = {"device": {"stats": {"kv_token_bytes": 1572864,
                                 "kv_planes": 192}},
            "stats_open": {"ut_exit_at_1": 10, "ut_exit_at_2": 0,
                           "ut_exit_at_3": 0, "ut_exit_at_4": 5},
            "stats_close": {"ut_exit_at_1": 40, "ut_exit_at_2": 20,
                            "ut_exit_at_3": 10, "ut_exit_at_4": 15}}
    assert ouro_counters.read(data, {"key": "kv_token_bytes"}) == 1572864
    assert ouro_counters.read(data, {"key": "kv_planes"}) == 192
    # 30 rows at 1, 20 at 2, 10 at 3, 10 at 4
    assert ouro_counters.read(data, {"quantity": "exit_pass_mean"}) \
        == (30 + 40 + 30 + 40) / 70
    # a program from before the block: nothing to read, nothing raised
    older = {"device": {"stats": {}}, "stats_open": {"steps": 1},
             "stats_close": {"steps": 9}}
    assert ouro_counters.read(older, {"key": "kv_token_bytes"}) is None
    assert ouro_counters.read(older, {"quantity": "exit_pass_mean"}) is None
    assert ouro_counters.read({}, {"quantity": "exit_pass_mean"}) is None
    assert ouro_roofline.read({"config": _config()},
                              {"cost": "paged_attention",
                               "patterns": ["^x"]}) is None


def test_the_stream_share_takes_the_loops_time_outside_the_kernels(
        monkeypatch):
    """Two executions of the decode loop of 10 ms each on one chip, a
    paged kernel of 2 ms inside each and one inside the mixed step (which
    does not count): 16 ms for 2 x 8 steps of 19.9 GB at 819 GB/s."""
    from benchmark import trace_reduce
    ms = 1_000_000
    planes = {"/device:TPU:0": {
        "XLA Modules": [("jit__ragged_decode_loop(1)", 0, 10 * ms),
                        ("jit__ragged_step_body(2)", 10 * ms, 5 * ms),
                        ("jit__ragged_decode_loop(1)", 20 * ms, 10 * ms)],
        "XLA Ops": [("%fusion.1 = f32[] fusion()", 0, 8 * ms),
                    ("%_ragged_attention_pallas.3 = bf16[] custom-call()",
                     8 * ms, 2 * ms),
                    ("%_ragged_attention_pallas.9 = bf16[] custom-call()",
                     11 * ms, 3 * ms),
                    ("%fusion.1 = f32[] fusion()", 20 * ms, 8 * ms),
                    ("%_ragged_attention_pallas.3 = bf16[] custom-call()",
                     28 * ms, 2 * ms)]}}
    assert trace_reduce.DEVICE_PLANE.match("/device:TPU:0")
    assert trace_reduce.MODULE_LINE.match("XLA Modules") \
        and trace_reduce.OP_LINE.match("XLA Ops")
    monkeypatch.setattr(trace_reduce, "read_planes", lambda path: planes)
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: "x.pb")
    _, args, _ = entry("ouro_roofline", CELL, cost="layers_stream")
    data = {"config": _config(), "trace_summary": object(),
            "trace": {"dir": "d", "start": {"wall": 0}, "stop": {"wall": 1}},
            "device": {"kind": "TPU v5 lite"}}
    pct = ouro_roofline.read(data, args)
    _, stream = kc.stream_work(8, kc.model_dims(_config()))
    assert pct == pytest.approx(100 * (16 * stream / 819e9) / 0.016)
    assert data["notes"]["ouro_layers_stream_bound"] == "memory"


# -------------------------------------------------------------- the limits

def _scored(worst=0.0, n=50, equal=50, over=0, far=0):
    gaps = [0.0] * (n - 1 - over - far) + [0.2] * over + [1.2] * far \
        + [worst]
    return {"warm-up": [{"served": [1] * n, "max_tokens": n,
                         "reference_tokens": [1] * equal + [2] * (n - equal),
                         "gap": gaps}]}


def test_the_blocks_limits_are_held_on_every_group_and_request():
    """Rounding costs this block tenths of a logit on half its tokens and a
    whole logit on none: the share more than 1.0 under is what is held, on
    all tokens, on each group and on each request."""
    assert checks_ouro.served_tokens(_scored()) == []
    # half the tokens differ and sit 0.2 under: this block's rounding
    assert checks_ouro.served_tokens(_scored(equal=20, over=30)) == []
    far = checks_ouro.served_tokens(_scored(
        far=int(50 * checks_ouro.MAX_OVER_1_REQUEST) + 1))
    assert any("of a request" in line for line in far)
    assert any("more than 1.0 logits" in line for line in far)
    low = checks_ouro.served_tokens(_scored(
        equal=int(50 * checks_ouro.MIN_EQUAL) - 1))
    assert any("equal the reference's" in line for line in low)
    over = checks_ouro.served_tokens(_scored(
        over=int(50 * checks_ouro.MAX_OVER) + 2))
    assert any("sit more than 0.12" in line for line in over)
    worst = checks_ouro.served_tokens(_scored(
        worst=checks_ouro.MAX_GAP * 1.5))
    assert any("logits under" in line for line in worst)
    assert any("no window request" in line for line in
               checks_ouro.served_tokens({**_scored(), "window": []}))
    short = _scored()
    short["warm-up"][0]["max_tokens"] = 60
    assert any("60 asked" in line
               for line in checks_ouro.served_tokens(short))


def test_the_runner_holds_a_run_to_the_blocks_limits(monkeypatch):
    """serve_ouro.run puts its own served_tokens in checks.served_tokens'
    place for the length of serve.run and puts checks.py's back, whatever
    the run does."""
    from benchmark.runners import serve
    seen = {}

    def fake_run(ctx):
        seen["during"] = serve.checks.served_tokens
        raise RuntimeError("stop here")

    monkeypatch.setattr(serve, "run", fake_run)
    before = serve.checks.served_tokens
    with pytest.raises(RuntimeError, match="stop here"):
        serve_ouro.run({"rehearse": False, "config": _config()})
    assert seen["during"] is not before
    assert serve.checks.served_tokens is before
    assert serve.Session is not serve_ouro.Session


def test_hold_readings_method_at_tiny_widths():
    """hold_ouro.readings end to end on the CPU at the rehearsal's widths:
    the reference held in bf16, the fp8 control and the two faults of the
    loop, each against its float32 self, cut into the cell's scored
    requests and put through the block's limits. The numbers mean nothing
    here (the limits are set at the published widths); every control is
    further from the float32 choice than the bf16 hold."""
    from benchmark import reference_ouro as ref
    from ray_tpu.models.llama import LlamaConfig
    config = {**_config(), "hidden_size": 64, "num_hidden_layers": 2,
              "num_attention_heads": 8, "num_key_value_heads": 4,
              "head_dim": 8, "vocab_size": 512, "intermediate_size": 128}
    serve_ouro.cut_for_rehearsal(config)
    cfg = LlamaConfig.tiny(**serve_ouro.model_fields(config))
    mix = {"sample": [{"prompt": 40, "max_tokens": 24}],
           "score_in_window": 2, "prompt": {"min": 32},
           "output": {"median": 40}}
    names = ("bf16", "fp8", "shared_kv", "norm_once")
    row = hold_ouro.readings(5, cfg, ref.dims_of(cfg), mix, 128,
                             {k: hold_ouro.HOLDS[k] for k in names})
    assert set(names) <= set(row)
    assert set(hold_ouro.HOLDS) == {"bf16", "bf16_matmul", "fp8",
                                    *ref.FAULTS[:2]}
    assert hold_ouro.MUST_PASS == {
        "bf16": True, "bf16_matmul": True, "fp8": False,
        "shared_kv": False, "norm_once": False}
    for control in names[1:]:
        assert row["bf16"]["equal"] >= row[control]["equal"], control
        assert row["bf16"]["worst"] <= row[control]["worst"], control
    assert [s["tokens"] for s in row["fp8"]["groups"].values()] == [24, 80]
    assert row["bf16"]["correct"] == (not row["bf16"]["faults"])
