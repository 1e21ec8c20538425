"""benchmark/kernel_cost_granite.py's arithmetic on hand-computed cases: the
block's shape numbers from the published keys, what a batch slot owns of
recurrent state, the decode tokens of a window from the request log, the
work of the one-token state update and of paged attention over the
ATTENTION layers only; the runner's mapping of the published keys to the
program's fields; the cell's traffic file holding the parameters ISSUE 37
names, the same queues under every seed; and the tolerance that decides
`correct` for the block (checks_granite.py) beside checks.py's, with the
method of the readings it lies between (hold_granite.py) at tiny widths."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import checks, checks_granite, hold_granite  # noqa: E402
from benchmark import kernel_cost, kernel_cost_granite as kc  # noqa: E402
from benchmark import loadgen  # noqa: E402
from benchmark.runners import serve_granite  # noqa: E402


def _load(*path):
    with open(os.path.join(ROOT, "benchmark", *path)) as f:
        return json.load(f)


def _config():
    return _load("configs", "granite4-h-micro-serve-1chip.json")


def test_model_dims_count_this_blocks_own_layers_and_widths():
    dims = kc.model_dims(_config())
    assert (dims["attn_layers"], dims["ssm_layers"]) == (4, 36)
    assert (dims["ssm_heads"], dims["ssm_head_dim"], dims["ssm_state"]) \
        == (64, 64, 128)
    assert dims["conv_channels"] == 4352 and dims["state_bytes"] == 2
    assert (dims["heads"], dims["kv_heads"], dims["head_dim"]) == (32, 8, 64)
    # 36 x (64 x 64 x 128 + 3 x 4352) bf16 values: 38.7 MB a slot
    assert kc.state_bytes_per_slot(dims) == 36 * (524288 + 13056) * 2 \
        == 38688768


def test_decode_tokens_and_update_work_on_a_hand_computed_request():
    """One request: a 3-token prompt in one chunk (its last token gives the
    first output token: no update), then 2 decode dispatches of 1 and 2
    tokens: selftest.check_cost's case."""
    rec = {"t0_wall": 100.0, "prompt_tokens": 3, "admits": [[0.0, 0]],
           "chunks": [[0.1, 3, 1]], "ttft": 0.1,
           "decode": [[0.1, 1], [0.1, 2]]}
    unfinished = {"t0_wall": 100.0, "prompt_tokens": 3, "ttft": None}
    assert kc.decode_tokens([rec, unfinished], 0.0, 1e9) == 3
    assert kc.decode_tokens([rec], 100.15, 100.25) == 1   # the first only
    assert kc.decode_tokens([rec], 100.25, 100.35) == 2
    dims = {"ssm_heads": 4, "ssm_head_dim": 8, "ssm_state": 16,
            "ssm_layers": 3, "state_bytes": 2}
    flops, nbytes = kc.ssm_update_work(3, dims)
    state = 4 * 8 * 16
    assert flops == 5 * state * 3 * 3
    # the state read once and written once at its held width, plus x and y
    # (4 x 8 each), B and C (16 each) and dt (4) in float32
    assert nbytes == (2 * state * 2 + (2 * 32 + 2 * 16 + 4) * 4) * 3 * 3


def test_update_is_memory_bound_at_the_published_sizes():
    """128 slots x 36 layers: 9.7 GB moved a decode step for 12 GFLOP, so
    the bound is the HBM peak: 11.8 ms at 819 GB/s."""
    dims = kc.model_dims(_config())
    flops, nbytes = kc.ssm_update_work(128, dims)
    assert 9.6e9 < nbytes < 9.9e9 and flops < 1.3e10
    pct, bound = kernel_cost.roofline_pct(flops, nbytes, 0.0236,
                                          "TPU v5 lite")
    assert bound == "memory" and 49.0 < pct < 51.0


def test_paged_attention_counts_the_attention_layers_only():
    rec = {"t0_wall": 100.0, "prompt_tokens": 3, "admits": [[0.0, 0]],
           "chunks": [[0.1, 3, 1]], "ttft": 0.1,
           "decode": [[0.1, 1], [0.1, 2]]}
    dims = kc.model_dims(_config())
    f, b = kc.paged_attention_work([rec], 0.0, 1e9, dims)
    f1, b1 = kernel_cost.paged_attention_work(
        [rec], 0.0, 1e9, {**dims, "layers": 1})
    assert (f, b) == (4 * f1, 4 * b1)


def test_published_keys_map_to_the_programs_fields():
    from ray_tpu.models.llama import LlamaConfig
    fields = serve_granite.model_fields(_config())
    serve_granite.require_program_support(fields)
    cfg = LlamaConfig(**fields)
    assert cfg.n_layers == 40 and len(cfg.layers_of("mamba")) == 36
    assert cfg.layers_of("full_attention") == (5, 15, 25, 35)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_conv,
            cfg.ssm_chunk) == (64, 64, 128, 4, 256)
    assert cfg.ssm_channels == 4352 and cfg.head_dim == 64
    assert (cfg.embed_scale, cfg.residual_scale, cfg.attn_scale,
            cfg.logits_divisor) == (12, 0.22, 0.015625, 8)
    assert not cfg.rope and cfg.tie_embeddings and not cfg.n_experts
    assert _config()["reduced"] == []


@pytest.mark.parametrize("key,value", [
    ("mamba_n_groups", 8), ("position_embedding_type", "rope"),
    ("mamba_conv_bias", False), ("num_local_experts", 4)])
def test_the_runner_refuses_what_the_program_does_not_build(key, value):
    with pytest.raises(ValueError, match=key):
        serve_granite.model_fields({**_config(), key: value})


def test_rehearsal_cut_keeps_both_kinds_of_operator():
    config = {**_config(), "hidden_size": 64, "num_hidden_layers": 2,
              "num_attention_heads": 8, "head_dim": 8,
              "intermediate_size": 128}
    serve_granite.cut_for_rehearsal(config)
    fields = serve_granite.model_fields(config)
    assert fields["layer_types"] == ["mamba", "full_attention"]
    assert fields["ssm_heads"] * fields["ssm_head_dim"] == 2 * 64


def test_traffic_file_holds_what_the_issue_names():
    mix = _load("traffic", "reason-ssm.json")
    hybrid = _load("traffic", "reason-hybrid.json")
    assert (mix["kind"], mix["runner"], mix["clients_from"]) == (
        "closed_loop", "serve_granite", "engine.max_batch")
    assert (mix["per_client"], mix["lead_in_s"], mix["score_in_window"],
            mix["trace_after_s"], mix["trace_seconds"]) == (12, 12, 4, 12, 8)
    assert mix["prompt"] == {"median": 512, "sigma": 0.6, "min": 128,
                             "max": 2048}
    assert mix["output"] == {"median": 512, "sigma": 0.4, "min": 256,
                             "max": 1024}
    # reason-hybrid's lengths on purpose: the cells differ by the block
    assert (mix["prompt"], mix["output"]) == (hybrid["prompt"],
                                              hybrid["output"])
    prompts = [s["prompt"] for s in mix["sample"]]
    assert len(prompts) == 4 and max(prompts) > 1024 and min(prompts) < 200
    assert all(s["prompt"] + s["max_tokens"] <= mix["reference_pad_to"]
               for s in mix["sample"])
    clients = _config()["engine"]["max_batch"]
    plans = [loadgen.closed_loop(mix, seed, clients, 1000)
             for seed in (0, 2 ** 31 + 11)]
    offered = [loadgen.offered_work(p) for p in plans]
    assert offered[0] == offered[1] and offered[0]["n"] == 1664


def _scored(worst, n=50, equal=50):
    return {"warm-up": [{"served": [1] * n, "max_tokens": n,
                         "reference_tokens": [1] * equal + [2] * (n - equal),
                         "gap": [0.0] * (n - 1) + [worst]}]}


@pytest.mark.parametrize("worst,faults", [
    (0.0, 0), (0.0092, 0), (0.05, 0), (0.08, 1), (0.111, 1), (0.241, 1)])
def test_the_blocks_tolerance_lies_between_its_two_readings(worst, faults):
    """Held in bf16 the reference read 0.0 on every token and the served
    path 0.009 at the most; held in fp8 the worst token of a seed read
    0.111 to 0.241 (my chip runs, PR 37, eight seeds): 0.06 passes the
    first and fails the second, where checks.py's 0.12 lies inside the
    fp8 readings. One line a fault."""
    assert len(checks_granite.served_tokens(_scored(worst))) == faults
    assert checks.LOGIT_TOL > checks_granite.LOGIT_TOL > 0.015


def test_checks_own_faults_are_kept_beside_the_blocks():
    assert any("equal" in line for line in
               checks_granite.served_tokens(_scored(0.0, equal=40)))
    assert any("no window request" in line for line in
               checks_granite.served_tokens({**_scored(0.0), "window": []}))
    short = _scored(0.0)
    short["warm-up"][0]["max_tokens"] = 60
    assert any("60 asked" in line
               for line in checks_granite.served_tokens(short))


def test_the_runner_holds_a_run_to_the_blocks_tolerance(monkeypatch):
    """serve_granite.run puts checks_granite.served_tokens in
    checks.served_tokens' place for the length of serve.run and puts
    checks.py's back, whatever the run does."""
    from benchmark.runners import serve
    seen = {}

    def fake_run(ctx):
        seen["during"] = serve.checks.served_tokens
        raise RuntimeError("stop here")

    monkeypatch.setattr(serve, "run", fake_run)
    monkeypatch.setattr(serve_granite, "require_program_support",
                        lambda fields: None)
    before = serve.checks.served_tokens
    with pytest.raises(RuntimeError, match="stop here"):
        serve_granite.run({"rehearse": False, "config": _config()})
    assert seen["during"] is checks_granite.served_tokens
    assert serve.checks.served_tokens is before
    assert serve.Session is not serve_granite.Session


def test_hold_readings_method_at_tiny_widths():
    """hold_granite.readings end to end on the CPU at the rehearsal's
    widths: both holds of the reference against its float32 self, cut into
    the cell's scored requests and put through the block's limits. The
    numbers mean nothing here (the tolerance is set at the published
    widths); the bf16 hold is no further from the float32 choice than the
    fp8 hold."""
    from benchmark import reference_granite as ref
    from ray_tpu.models.llama import LlamaConfig
    config = {**_config(), "hidden_size": 64, "num_hidden_layers": 2,
              "num_attention_heads": 8, "num_key_value_heads": 2,
              "head_dim": 8, "vocab_size": 512, "intermediate_size": 128}
    serve_granite.cut_for_rehearsal(config)
    cfg = LlamaConfig.tiny(**serve_granite.model_fields(config))
    mix = {"sample": [{"prompt": 40, "max_tokens": 24}],
           "score_in_window": 2, "prompt": {"min": 32},
           "output": {"median": 40}}
    row = hold_granite.readings(5, cfg, ref.dims_of(cfg), mix, 128)
    assert set(hold_granite.HOLDS) <= set(row)
    assert row["bf16"]["equal"] >= row["fp8"]["equal"]
    assert row["bf16"]["worst"] <= row["fp8"]["worst"]
    assert [s["tokens"] for s in row["fp8"]["groups"].values()] == [24, 80]
    assert row["bf16"]["correct"] == (not row["bf16"]["faults"])
    assert row["logits_std"] > 0 and row["top2_margin"] > 0
