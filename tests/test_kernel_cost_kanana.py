"""benchmark/kernel_cost_kanana.py's arithmetic on hand-computed cases: the
block's shape numbers from the published keys, the work of latent paged
attention (ONE row a cached token for all heads) and the expert kernel's
work at a routed expert's width; and the cell's traffic file holding the
parameters ISSUE 33 names, the same queues under every seed; and the
limits that decide `correct` for the block (checks_kanana.py) held on all
tokens, on each group and on each request."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import checks_kanana, hold_kanana  # noqa: E402
from benchmark import kernel_cost, kernel_cost_kanana as kc  # noqa: E402
from benchmark import loadgen  # noqa: E402
from benchmark.runners import serve_kanana  # noqa: E402


def _config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kanana2-30b-a3b-serve-1chip.json")) as f:
        return json.load(f)


def test_model_dims_count_this_blocks_own_layers_and_widths():
    dims = kc.model_dims(_config())
    assert (dims["attn_layers"], dims["dense_layers"],
            dims["expert_layers"]) == (8, 1, 7)
    assert (dims["expert_ffn"], dims["shared_ffn"], dims["dense_ffn"]) \
        == (768, 1536, 6144)
    assert (dims["experts"], dims["top_k"], dims["heads"]) == (128, 6, 32)
    assert (dims["latent"], dims["rope"], dims["nope"], dims["v"]) \
        == (512, 64, 128, 128)


def test_latent_attention_work_on_a_hand_computed_request():
    """One request: a 3-token prompt in one chunk, then 2 decode
    dispatches (1 and 2 tokens): selftest.check_cost's case. Per query
    token heads x (2 x 576 + 2 x 512) operations a visible token; a row
    reads its visible rows ONCE, 576 values each, whatever the heads."""
    dims = {"heads": 32, "latent": 512, "rope": 64, "attn_layers": 2}
    rec = {"t0_wall": 100.0, "prompt_tokens": 3, "admits": [[0.0, 0]],
           "chunks": [[0.1, 3, 1]], "ttft": 0.1,
           "decode": [[0.1, 1], [0.1, 2]]}
    ctx = (1 + 2 + 3) + (3 + 1) + (3 + 2) + (3 + 3)     # per query token
    kv_reads, q_tokens = 3 + (4 + 5 + 6), 6
    assert kc.attention_sums([rec], 0.0, 1e9) == (ctx, kv_reads, q_tokens)
    f, b = kc.latent_attention_work([rec], 0.0, 1e9, dims)
    assert f == 32 * (576 + 512) * 2 * ctx * 2
    assert b == (576 * kv_reads + 32 * (576 + 512) * q_tokens) * 2 * 2
    # per-head K and V of the same block would read 32 x (192 + 128)
    # values a cached token: the latent reads 576
    per_head = 32 * (192 + 128) * kv_reads * 2 * 2
    assert per_head / (576 * kv_reads * 2 * 2) == pytest.approx(17.78, 0.01)
    f1, _ = kc.latent_attention_work([rec], 100.15, 100.25, dims)
    assert f1 == 32 * (576 + 512) * 2 * 4 * 2           # the first decode
    # memory-bound at decode on a v5e: ~60 operations a byte against 240
    fd, bd = kc.latent_attention_work(
        [{**rec, "prompt_tokens": 4000, "chunks": [], "admits": [[0.0, 4000]],
          "decode": [[0.1, 8]]}], 0.0, 1e9, dims)
    assert 55 < fd / bd < 61
    assert kernel_cost.roofline_pct(fd, bd, 1.0, "TPU v5 lite")[1] == "memory"


def test_expert_work_is_counted_at_a_routed_experts_width():
    dims = kc.model_dims(_config())
    f, b = kc.moe_experts_work(288.0, 114.0, dims)
    assert f == 6 * 2048 * 768 * 288
    assert b == (3 * 2048 * 768 * 114 + 2 * 2048 * 288) * 2


def test_published_keys_become_the_programs_fields():
    from ray_tpu.models.llama import LlamaConfig
    fields = serve_kanana.model_fields(_config())
    cfg = LlamaConfig.tiny(**fields)
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (512, 128, 64, 128)
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.n_experts,
            cfg.experts_per_token, cfg.ffn_dim, cfg.dense_ffn_dim,
            cfg.shared_ffn_dim) == (8, 1, 128, 6, 768, 6144, 1536)
    assert cfg.router_bias and not cfg.tie_embeddings
    assert (cfg.router_score, cfg.router_scale, cfg.router_eps) \
        == ("sigmoid", 2.448, 1e-20)
    with pytest.raises(ValueError, match="q_lora_rank"):
        serve_kanana.model_fields({**_config(), "q_lora_rank": 1536})
    # the one rotary pairing the latent block builds is the adjacent pairs
    with pytest.raises(ValueError, match="rope_interleave"):
        serve_kanana.model_fields({**_config(), "rope_interleave": False})
    tiny = {**_config(), "num_hidden_layers": 2, "intermediate_size": 128}
    serve_kanana.cut_for_rehearsal(tiny)
    assert tiny["first_k_dense_replace"] == 1 and tiny["kv_lora_rank"] >= 256
    assert serve_kanana.model_fields(tiny)["shared_ffn_dim"] == 64


def test_traffic_file_holds_the_named_parameters_and_a_fixed_multiset():
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "context-latent.json")) as f:
        mix = json.load(f)
    assert mix["kind"] == "closed_loop" and mix["runner"] == "serve_kanana"
    assert (mix["clients_from"], mix["per_client"], mix["lead_in_s"]) \
        == ("engine.max_batch", 8, 12)
    assert mix["prompt"] == {"median": 4096, "sigma": 0.5, "min": 1024,
                             "max": 8192}
    assert mix["output"] == {"median": 768, "sigma": 0.4, "min": 384,
                             "max": 1536}
    assert (mix["score_in_window"], mix["trace_after_s"],
            mix["trace_seconds"]) == (4, 12, 8)
    sample = mix["sample"]
    assert len(sample) == 4
    assert max(s["prompt"] for s in sample) > 4096
    assert min(s["prompt"] for s in sample) < 300
    assert max(s["prompt"] + s["max_tokens"] for s in sample) \
        <= mix["reference_pad_to"]
    # every scored request is long enough for a limit of its own
    assert min(s["max_tokens"] for s in sample) >= 96
    clients = _config()["engine"]["max_batch"]
    plans = [serve_kanana.closed_loop(mix, s, clients, 1000)
             for s in (1, 2 ** 31 + 11)]
    offered = [loadgen.offered_work(p) for p in plans]
    # loadgen's multiset, openers included, under every seed
    assert offered[0] == offered[1] == loadgen.offered_work(
        loadgen.closed_loop(mix, 1, clients, 1000))
    assert offered[0]["n"] == 432
    assert [len(q) for q in plans[0]["queues"]] == [9] * 48
    lens = [[[(len(r["prompt"]), r["max_tokens"]) for r in q]
             for q in p["queues"]] for p in plans]
    # every seed offers the SAME queues (the same work in every window);
    # the seed deals them to the clients and draws the token ids
    assert sorted(lens[0]) == sorted(lens[1]) and lens[0] != lens[1]
    assert plans[0]["queues"][0][0]["prompt"] \
        != plans[1]["queues"][0][0]["prompt"]
    # each turn holds a stratified sample: the mix's mean to half a percent
    for name, at in (("prompt_lens", 0), ("output_lens", 1)):
        mean = (sum(offered[0][name])
                - sum(q[0][at] for q in lens[0])) / 384
        for turn in range(1, 9):
            got = sum(q[turn][at] for q in lens[0]) / 48
            assert abs(got / mean - 1) < 0.005, (name, turn, got, mean)
    # the longest request fits the engine's max_seq_len
    assert max(offered[0]["prompt_lens"]) + max(offered[0]["output_lens"]) \
        <= _config()["engine"]["max_seq_len"]


def _request(n: int, equal: float, over: float = 0.3, over_1: float = 0.09):
    """A scored request of n tokens with these shares equal, more than
    0.12 and more than 1.0 under the reference's choice."""
    wrong = n - round(equal * n)
    gaps = [1.5] * round(over_1 * n)
    gaps += [0.5] * (round(over * n) - len(gaps))
    gaps += [0.0] * (n - len(gaps))
    return {"served": [1] * n, "max_tokens": n, "gap": gaps,
            "reference_tokens": [0] * wrong + [1] * (n - wrong)}


def _sound():
    return {"warm-up": [_request(n, 0.62) for n in (96, 128, 128, 128)],
            "window": [_request(n, 0.62) for n in (520, 560, 600, 540)]}


@pytest.mark.parametrize("group,fault,said", [
    (None, None, None),
    # one of the window's four requests wholly wrong: the pool still reads
    # 47 % equal, the request reads 3 %
    ("window", [_request(560, 0.03, 0.95, 0.75)], "window sample 0"),
    # the warm-up group wrong (the prefix hit, the copy on write, the
    # longest prompt): a sixth of the tokens, nothing in the pool
    ("warm-up", [_request(n, 0.03, 0.95, 0.75)
                 for n in (96, 128, 128, 128)], "the warm-up tokens"),
    # every request a little wrong: no request under its floor, the pool is
    ("*", 0.45, "all served tokens"),
    ("*", (0.62, 0.55, 0.09), "0.12 logits under"),
    ("*", (0.62, 0.3, 0.25), "1.0 logits under")])
def test_correct_is_held_on_all_tokens_each_group_and_each_request(
        group, fault, said):
    groups = _sound()
    if group == "*":
        how = fault if isinstance(fault, tuple) else (fault,)
        groups = {g: [_request(s["max_tokens"], *how) for s in samples]
                  for g, samples in groups.items()}
    elif group:
        groups[group][:len(fault)] = fault
    bad = checks_kanana.served_tokens(groups)
    if said is None:
        assert bad == []
    else:
        assert any(said in line for line in bad), bad
    if group == "window":
        pooled = checks_kanana.shares(
            [s for g in groups.values() for s in g])
        assert pooled["equal"] > 0.45      # the pool alone would not see it
    # a client's opener of a few tokens has no limit of its own
    tiny = _sound()
    tiny["window"].append(_request(8, 0.125))
    assert checks_kanana.served_tokens(tiny) == []
    short = {**_sound(), "window": []}
    assert any("no window request" in line
               for line in checks_kanana.served_tokens(short))


def test_hold_readings_are_cut_into_the_cells_scored_requests():
    """hold_kanana.py lays the requests the cell scores on one sequence of
    the reference's length: the traffic file's four warm-up samples where
    a served request's tokens sit (after its prompt), and four window
    requests of the median output inside it."""
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "context-latent.json")) as f:
        mix = json.load(f)
    length = mix["reference_pad_to"]
    cuts = hold_kanana.requests_of(mix, length)
    assert cuts["warm-up"] == [(4499, 96), (1499, 128), (699, 128),
                               (259, 128)]
    assert len(cuts["window"]) == 4
    assert all(n == 768 and a >= 1023 and a + n <= length
               for a, n in cuts["window"])
    groups = hold_kanana.as_groups(
        cuts, *(np.arange(length) for _ in range(3)))
    assert [len(s["served"]) for s in groups["warm-up"]] \
        == [96, 128, 128, 128]
    assert groups["warm-up"][0]["gap"][0] == 4499


def test_hold_readings_method_at_tiny_widths():
    """hold_kanana.readings end to end on the CPU at the rehearsal's
    widths: three holds of the reference against its float32 self, cut
    into requests and put through the cell's limits. The numbers mean
    nothing here (the limits are set at the published widths); the bf16
    hold is nearer the float32 choice than the fp8 hold."""
    from benchmark import reference_kanana as ref
    from ray_tpu.models.llama import LlamaConfig
    config = {**_config(), "hidden_size": 64, "num_hidden_layers": 2,
              "num_attention_heads": 4, "num_key_value_heads": 4,
              "vocab_size": 512, "intermediate_size": 128}
    serve_kanana.cut_for_rehearsal(config)
    cfg = LlamaConfig.tiny(**serve_kanana.model_fields(config))
    mix = {"sample": [{"prompt": 40, "max_tokens": 24}],
           "score_in_window": 2, "prompt": {"min": 32},
           "output": {"median": 40}}
    row = hold_kanana.readings(5, cfg, ref.dims_of(cfg), mix, 128)
    assert set(hold_kanana.HOLDS) <= set(row)
    assert row["bf16"]["equal"] > row["fp8"]["equal"]
    assert [q["tokens"] for q in row["fp8"]["requests"]] == [24, 40, 40]
    assert row["bf16"]["correct"] == (not row["bf16"]["faults"])
