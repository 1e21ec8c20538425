"""benchmark/kernel_cost_phi4flash.py's arithmetic on hand-computed cases:
the block's shape numbers from the published keys (9 / 8 / 1 / 7 / 7 layers
by kind), what a batch slot owns of recurrent state, the work of the
one-token state update and of paged attention counted once for every layer
that READS the one full layer's pages; the runner's mapping of the
published keys to the program's fields; the cell's traffic file holding
the parameters ISSUE 63 names, the same queues under every seed; and the
limits that decide `correct` for the block (checks_phi4flash.py), with the
method of the readings they lie between (hold_phi4flash.py) at tiny
widths."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import checks_phi4flash, hold_phi4flash  # noqa: E402
from benchmark import kernel_cost, kernel_cost_mimo  # noqa: E402
from benchmark import kernel_cost_phi4flash as kc  # noqa: E402
from benchmark import loadgen  # noqa: E402
from benchmark.runners import serve_phi4flash  # noqa: E402

#: the catalog row's `config` (model-configs guide, architectures.jsonl)
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}
REC = {"t0_wall": 100.0, "prompt_tokens": 3, "admits": [[0.0, 0]],
       "chunks": [[0.1, 3, 1]], "ttft": 0.1, "decode": [[0.1, 1], [0.1, 2]]}


def _load(*path):
    with open(os.path.join(ROOT, "benchmark", *path)) as f:
        return json.load(f)


def _config():
    return _load("configs", "phi4-mini-flash-serve-1chip.json")


def test_the_file_holds_every_published_key_and_cuts_nothing():
    config = _config()
    assert {k: config[k] for k in PUBLISHED} == PUBLISHED
    assert config["reduced"] == [] and len(config["assumed"]) >= 12
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == config["name"])
    assert entry["reduced"] == [] and entry["source"] == config["source"]
    # every reading no key carries is a program field beside its line
    assert set(config["program_fields"]) == {
        "param_dtype", "ssm1_state", "ssm1_expand", "ssm1_conv",
        "ssm1_dt_rank", "diff_attention", "layer_norm", "attn_bias", "rope"}


def test_model_dims_count_the_layers_by_kind():
    dims = kc.model_dims(_config())
    assert [dims[k + "_layers"] for k in (
        "mamba", "window", "full", "gmu", "cross")] == [9, 8, 1, 7, 7]
    assert (dims["heads"], dims["kv_heads"], dims["head_dim"],
            dims["window"]) == (40, 20, 64, 512)
    assert (dims["ssm_state"], dims["ssm_channels"], dims["ssm_conv"]) \
        == (16, 5120, 4)
    # 9 x (16 x 5120 float32 + 3 x 5120 bf16): 3.2 MB a slot
    assert kc.state_bytes_per_slot(dims) == 9 * (327680 + 30720) \
        == 3225600
    # ONE layer's K and V: 20 heads x (64 + 64) bf16
    assert kc.kv_token_layer_bytes(dims) == 5120


def test_update_work_is_the_state_moved_once_each_way():
    assert kc.decode_tokens([REC], 0.0, 1e9) == 3
    dims = {"ssm_state": 4, "ssm_channels": 8, "mamba_layers": 3,
            "state_bytes": 4}
    flops, nbytes = kc.selective_update_work(3, dims)
    assert flops == 6 * 32 * 3 * 3
    # the state in and out at its held width, plus x, dt and y (8 each) and
    # B and C (4 each) in float32
    assert nbytes == (2 * 32 * 4 + (3 * 8 + 2 * 4) * 4) * 3 * 3


def test_update_is_memory_bound_at_the_published_sizes():
    """160 slots x 9 layers: 1.03 GB moved a decode step (0.94 of it the
    float32 states, each way once) for 0.7 GFLOP."""
    flops, nbytes = kc.selective_update_work(160, kc.model_dims(_config()))
    assert 1.02e9 < nbytes < 1.04e9 and flops < 1e9
    pct, bound = kernel_cost.roofline_pct(flops, nbytes, 2 * nbytes / 819e9,
                                          "TPU v5 lite")
    assert bound == "memory" and 49.0 < pct < 51.0


def test_every_reader_of_the_full_layers_pages_is_counted():
    """The full layer and each of the seven cross layers read the ONE full
    layer's pages for their own queries: eight times one layer's work; the
    eight window layers their own, cut to the window."""
    dims = kc.model_dims(_config())
    sums = kernel_cost_mimo.attention_sums([REC], 0.0, 1e9)
    f, b = kc.shared_attention_work([REC], 0.0, 1e9, dims)
    ctx, reads, q = sums
    assert f == 8 * 40 * 2 * (64 + 128) * ctx
    assert b == 8 * (5120 * reads + 40 * (64 + 128) * 2 * q)
    fw, bw = kc.window_attention_work([REC], 0.0, 1e9, dims)
    assert (fw, bw) == (f, b)           # 6 tokens of context: inside 512
    long = {**REC, "prompt_tokens": 3000, "chunks": [[0.1, 3000, 1]]}
    fw, _ = kc.window_attention_work([long], 0.0, 1e9, dims)
    fs, _ = kc.shared_attention_work([long], 0.0, 1e9, dims)
    assert fw < fs / 2
    assert kc.paged_attention_work([long], 0.0, 1e9, dims)[0] == fw + fs


def test_published_keys_map_to_the_programs_fields():
    from ray_tpu.models.llama import LlamaConfig
    fields = serve_phi4flash.model_fields(_config())
    serve_phi4flash.require_program_support(fields)
    cfg = LlamaConfig(**fields)
    assert cfg.n_layers == 32 and cfg.head_dim == 64
    assert cfg.layers_of("mamba1") == tuple(range(0, 17, 2))
    assert cfg.layers_of("sliding_attention") == tuple(range(1, 16, 2))
    assert cfg.layers_of("full_attention") == (17,)
    assert cfg.layers_of("gmu") == tuple(range(18, 32, 2))
    assert cfg.layers_of("cross_attention") == tuple(range(19, 32, 2))
    assert (cfg.ssm1_state, cfg.ssm1_channels, cfg.ssm1_conv,
            cfg.ssm1_dt_rank) == (16, 5120, 4, 160)
    assert cfg.diff_attention and cfg.layer_norm and cfg.attn_bias
    assert not cfg.rope and cfg.tie_embeddings and not cfg.n_experts
    assert (cfg.sliding_window, cfg.window_kv_heads) == (512, 20)


@pytest.mark.parametrize("key,value", [
    ("mb_per_layer", 4), ("mlp_bias", True), ("model_type", "phi3"),
    ("lm_head_bias", True)])
def test_the_runner_refuses_what_the_program_does_not_build(key, value):
    with pytest.raises(ValueError, match=key):
        serve_phi4flash.model_fields({**_config(), key: value})


def test_rehearsal_cut_keeps_every_kind_of_layer():
    config = {**_config(), "hidden_size": 64, "num_hidden_layers": 2,
              "num_attention_heads": 8, "num_key_value_heads": 4,
              "head_dim": 8, "intermediate_size": 128}
    serve_phi4flash.cut_for_rehearsal(config)
    fields = serve_phi4flash.model_fields(config)
    assert fields["layer_types"] == [
        "mamba1", "sliding_attention", "mamba1", "sliding_attention",
        "mamba1", "full_attention", "gmu", "cross_attention"]
    assert fields["sliding_window"] == 16 and fields["ssm1_dt_rank"] == 4


def test_traffic_file_holds_what_the_issue_names():
    mix = _load("traffic", "reason-shared-kv.json")
    assert (mix["kind"], mix["runner"], mix["clients_from"]) == (
        "closed_loop", "serve_phi4flash", "engine.max_batch")
    assert (mix["per_client"], mix["lead_in_s"], mix["score_in_window"]) \
        == (6, 12, 4)
    assert mix["prompt"] == {"median": 512, "sigma": 0.6, "min": 128,
                             "max": 2048}
    assert mix["output"] == {"median": 1024, "sigma": 0.4, "min": 512,
                             "max": 2048}
    assert [(s["prompt"], s["max_tokens"]) for s in mix["sample"]] == [
        (1100, 32), (600, 24), (190, 32), (130, 16), (470, 96)]
    # one scored request crosses the window's edge while DECODING
    assert any(s["prompt"] < 512 < s["prompt"] + s["max_tokens"]
               for s in mix["sample"])
    assert all(s["prompt"] + s["max_tokens"] <= mix["reference_pad_to"]
               for s in mix["sample"])
    engine = _config()["engine"]
    assert engine["max_seq_len"] >= 2048 + 2048
    plans = [loadgen.closed_loop(mix, seed, engine["max_batch"], 1000)
             for seed in (0, 2 ** 31 + 11)]
    offered = [loadgen.offered_work(p) for p in plans]
    assert offered[0] == offered[1] \
        and offered[0]["n"] == 7 * engine["max_batch"]


def _scored(worst=0.0, n=50, equal=50, over=0):
    gaps = [0.0] * (n - 1 - over) + [0.2] * over + [worst]
    return {"warm-up": [{"served": [1] * n, "max_tokens": n,
                         "reference_tokens": [1] * equal + [2] * (n - equal),
                         "gap": gaps}]}


def test_the_blocks_limits_are_held_on_every_group_and_request():
    ok = checks_phi4flash.served_tokens(_scored())
    assert ok == []
    low = checks_phi4flash.served_tokens(_scored(
        equal=int(50 * checks_phi4flash.MIN_EQUAL_REQUEST) - 1))
    assert any("of a request" in line for line in low)
    assert any("equal the reference's" in line for line in low)
    over = checks_phi4flash.served_tokens(_scored(
        over=int(50 * checks_phi4flash.MAX_OVER) + 2))
    assert any("sit more than" in line for line in over)
    far = checks_phi4flash.served_tokens(_scored(
        worst=checks_phi4flash.MAX_GAP * 1.5))
    assert any("logits under" in line for line in far)
    assert any("no window request" in line for line in
               checks_phi4flash.served_tokens({**_scored(), "window": []}))
    short = _scored()
    short["warm-up"][0]["max_tokens"] = 60
    assert any("60 asked" in line
               for line in checks_phi4flash.served_tokens(short))


def test_the_runner_holds_a_run_to_the_blocks_limits(monkeypatch):
    """serve_phi4flash.run puts its own served_tokens in
    checks.served_tokens' place for the length of serve.run and puts
    checks.py's back, whatever the run does."""
    from benchmark.runners import serve
    seen = {}

    def fake_run(ctx):
        seen["during"] = serve.checks.served_tokens
        raise RuntimeError("stop here")

    monkeypatch.setattr(serve, "run", fake_run)
    monkeypatch.setattr(serve_phi4flash, "require_program_support",
                        lambda fields: None)
    before = serve.checks.served_tokens
    with pytest.raises(RuntimeError, match="stop here"):
        serve_phi4flash.run({"rehearse": False, "config": _config()})
    assert seen["during"] is not before
    assert serve.checks.served_tokens is before
    assert serve.Session is not serve_phi4flash.Session


def test_hold_readings_method_at_tiny_widths():
    """hold_phi4flash.readings end to end on the CPU at the rehearsal's
    widths, three of its readings: the reference held in bf16, the
    lower-precision control (the recurrence's decay and carry in bf16,
    lambda left at lambda_init) and the fp8 hold, each against its float32
    self, cut into the cell's scored requests and put through the block's
    limits. The numbers mean nothing here (the limits are set at the
    published widths); the control is further from the float32 choice than
    the bf16 hold."""
    from benchmark import reference_phi4flash as ref
    from ray_tpu.models.llama import LlamaConfig
    config = {**_config(), "hidden_size": 64, "num_hidden_layers": 2,
              "num_attention_heads": 8, "num_key_value_heads": 4,
              "head_dim": 8, "vocab_size": 512, "intermediate_size": 128}
    serve_phi4flash.cut_for_rehearsal(config)
    cfg = LlamaConfig.tiny(**serve_phi4flash.model_fields(config))
    mix = {"sample": [{"prompt": 40, "max_tokens": 24}],
           "score_in_window": 2, "prompt": {"min": 32},
           "output": {"median": 40}}
    names = ("bf16", "control", "fp8")
    row = hold_phi4flash.readings(
        5, cfg, ref.dims_of(cfg), mix, 128,
        {k: hold_phi4flash.HOLDS[k] for k in names})
    assert set(names) <= set(row)
    assert set(hold_phi4flash.HOLDS) == {
        "bf16", "bf16_matmul", "control", "fp8", *ref.FAULTS}
    assert hold_phi4flash.MUST_PASS["control"] is False \
        and hold_phi4flash.MUST_PASS["bf16_matmul"] is True
    assert row["bf16"]["equal"] >= row["control"]["equal"]
    assert row["bf16"]["worst"] <= row["fp8"]["worst"]
    assert [s["tokens"] for s in row["fp8"]["groups"].values()] == [24, 80]
    assert row["bf16"]["correct"] == (not row["bf16"]["faults"])


# ----------------------- the tokens that leave the walk before its tail

def _served_stats(block: str) -> dict:
    """The engine's counters after three prompts of one to three chunks of
    16 beside decode rows, at the block's tiny widths."""
    import numpy as np

    from _blocks import ENGINE, config, run
    from ray_tpu.llm import InferenceEngine
    cfg = config(block)
    eng = InferenceEngine(cfg, **ENGINE)
    rng = np.random.default_rng(1)
    for n, new in ((7, 9), (40, 3), (33, 3)):
        eng.add_request(rng.integers(1, cfg.vocab_size, n).tolist(), new)
    run(eng)
    return eng.stats


@pytest.mark.parametrize("block,tail", [("mistral", False), ("granite", False),
                                        ("phi4flash", True)])
def test_the_engine_counts_the_tokens_that_left_before_the_tail(block, tail):
    """`walk_tokens`: every valid token of a mixed step; `walk_tokens_left`:
    those of them that are not their row's last, where the block has a
    tail (a decoder-hybrid-decoder's cross-decoder), and 0 where it has
    none: the Llama block's walk, and a state-space block's, is cut
    nowhere."""
    stats = _served_stats(block)
    assert stats["walk_tokens"] == stats["ragged_real_tokens"] > 0
    assert stats["prefill_tokens"] == 80 and stats["chunk_rows"] >= 6
    assert stats["walk_tokens_left"] == (
        stats["prefill_tokens"] - stats["chunk_rows"] if tail else 0)


def test_tail_skipped_pct_is_the_two_counters_ratio_and_silent_without():
    """The metric file ISSUE 64 names, as data only: engine_clocks.py's
    ratio of the two counters over the window, in the one cell whose block
    has a tail; a program without the counters (the parent's) reads
    nothing and does not raise."""
    from benchmark.readers import engine_clocks
    spec = _load("metrics", "tail_skipped_pct.phi4flash.json")
    assert (spec["name"], spec["reader"]) == ("tail_skipped_pct.phi4flash",
                                              "engine_clocks")
    assert spec["args"] == {"num": ["walk_tokens_left"],
                            "den": ["walk_tokens"], "scale": 100}
    # found by what it READS (tests/_readings.py), not by its place: later
    # cells append their own entries behind it
    from _readings import entry
    listed, args, _ = entry("engine_clocks", "reason-phi4flash-1chip",
                            num=["walk_tokens_left"])
    assert args == spec["args"] and listed == {
        "name": "tail_skipped_pct.phi4flash", "unit": "%",
        "better": "higher", "source": "program_counter",
        "layer": "step programs (llm/model.py, llm/tp.py)",
        "moves": "out_tok_per_s", "workloads": ["reason-phi4flash-1chip"]}
    opened = {"walk_tokens": 1000, "walk_tokens_left": 700}
    closed = {"walk_tokens": 1000 + 670 + 1182,
              "walk_tokens_left": 700 + 511 + 1022}
    got = engine_clocks.read({"stats_open": opened, "stats_close": closed},
                             spec["args"])
    assert got == pytest.approx(100 * 1533 / 1852)
    assert engine_clocks.read(
        {"stats_open": {"chunk_rows": 1}, "stats_close": {"chunk_rows": 9}},
        spec["args"]) is None
