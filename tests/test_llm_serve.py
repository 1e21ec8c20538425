"""LLM serving through ray_tpu.serve: a replica-hosted engine doing
continuous batching across concurrent requests (reference capability:
ray.serve.llm LLMDeployment over vLLM)."""

import threading
import time

import jax.numpy as jnp
import pytest

import ray_tpu as rt
from ray_tpu import serve
from ray_tpu.llm import InferenceEngine
from ray_tpu.llm.serve_llm import LLMServer
from ray_tpu.models.llama import LlamaConfig


@pytest.fixture(scope="module")
def serve_rt():
    # 8 TPU resources let the tp>1 deployment's derived {"TPU": tp} gang
    # reservation schedule on the test cluster; the fast telemetry period
    # lets the flight-recorder head-aggregation test poll quickly
    rt.init(num_cpus=4, resources={"TPU": 8}, _system_config={
        "object_store_memory_bytes": 128 * 1024 * 1024,
        "metrics_export_period_s": 1.0,
    })
    yield rt
    serve.shutdown()
    rt.shutdown()


def test_llm_deployment_concurrent_requests(serve_rt):
    from ray_tpu.llm import LLMServer

    dep = serve.deployment(name="llm", max_ongoing_requests=8)(LLMServer)
    h = serve.run(dep.bind(
        {"n_layers": 2},
        {"page_size": 8, "total_pages": 64, "max_batch": 4,
         "max_seq_len": 128, "seed": 7},
    ), timeout_s=240)

    prompts = [[5, 17, 42], [5, 17, 42], [9, 9, 1, 2]]
    resps = [h.remote({"prompt_ids": p, "max_tokens": 6}) for p in prompts]
    outs = [r.result(timeout=300) for r in resps]
    assert all(len(o["token_ids"]) == 6 for o in outs)
    # same prompt -> same greedy tokens (engine must be deterministic)
    assert outs[0]["token_ids"] == outs[1]["token_ids"]
    stats = h.stats.remote().result(timeout=60)
    # continuous batching + chunking: 18 tokens in a handful of dispatches
    assert stats["decode_dispatches"] < 9, stats


def test_llm_request_record_links_router_trace(serve_rt):
    """Acceptance: the trace_id the serve router stamps on the wire is
    the one in the engine's flight-recorder record, and the record ships
    to the head (requests_dump) over the telemetry plane."""
    from ray_tpu.core.worker import global_worker
    from ray_tpu.llm import LLMServer
    from ray_tpu.util import trace_context

    dep = serve.deployment(name="llm-obs", max_ongoing_requests=8)(
        LLMServer)
    h = serve.run(dep.bind(
        {"n_layers": 2},
        {"page_size": 8, "total_pages": 64, "max_batch": 4,
         "max_seq_len": 128, "seed": 7},
    ), timeout_s=240)

    tid = trace_context.new_trace_id()
    tok = trace_context.activate(tid, trace_context.new_span_id())
    try:
        out = h.remote({"prompt_ids": [5, 17, 42, 9],
                        "max_tokens": 4}).result(timeout=300)
    finally:
        trace_context.deactivate(tok)
    rid = out["request_id"]

    # replica-local view: the record carries the ROUTER's trace_id
    recs = h.request_records.remote().result(timeout=60)
    rec = {r["rid"]: r for r in recs}[rid]
    assert rec["trace_id"] == tid
    assert rec["done"] and rec["finish_reason"] == "length"
    assert rec["n_generated"] == 4 and rec["ttft"] > 0

    # head-side view: telemetry_push ships the finished record
    head = global_worker.backend.head
    deadline = time.monotonic() + 60
    got = []
    while time.monotonic() < deadline:
        got = head.call("requests_dump", {"request": rid}, timeout=10)
        if got and got[0].get("done"):
            break
        time.sleep(0.5)
    assert got, "record never reached the head"
    assert got[0]["rid"] == rid and got[0]["trace_id"] == tid
    assert got[0]["worker"] and got[0]["node"]
    slowest = head.call("requests_dump", {"slowest": 5}, timeout=10)
    assert any(r["rid"] == rid for r in slowest)
    serve.delete("llm-obs")


def test_llm_tp_deployment_gang_resources(serve_rt):
    """A tp=2 engine deploys through build_llm_app: replica resources are
    DERIVED from the tp degree ({'TPU': 2} STRICT_PACK gang — reference:
    vllm_models.py:128-153 placement from TP×PP), the replica worker
    shards the engine over a 2-device mesh (virtual CPU devices via the
    deployment's runtime_env), and generation matches the tp=1
    deployment's greedy stream."""
    from ray_tpu.llm import build_llm_app, placement_for_engine

    bundles, strategy = placement_for_engine(tp=2)
    assert bundles == [{"TPU": 2.0}] and strategy == "STRICT_PACK"
    bundles, strategy = placement_for_engine(tp=8, pp=2)
    assert bundles == [{"TPU": 8.0}] * 2 and strategy == "PACK"

    model_cfg = {"n_layers": 2}
    eng_cfg = {"page_size": 8, "total_pages": 64, "max_batch": 4,
               "max_seq_len": 128, "seed": 7}
    env = {"env_vars": {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
    }}
    app_tp = build_llm_app(model_cfg, {**eng_cfg, "tp": 2}, name="llm-tp2",
                           runtime_env=env)
    h_tp = serve.run(app_tp, timeout_s=300)
    out_tp = h_tp.remote(
        {"prompt_ids": [5, 17, 42, 9], "max_tokens": 6}).result(timeout=300)

    app_1 = build_llm_app(model_cfg, eng_cfg, name="llm-tp1",
                          runtime_env=env)
    h_1 = serve.run(app_1, timeout_s=300)
    out_1 = h_1.remote(
        {"prompt_ids": [5, 17, 42, 9], "max_tokens": 6}).result(timeout=300)
    assert out_tp["token_ids"] == out_1["token_ids"]

    # the tp replica really reserved its chip gang on the node
    avail = serve_rt.available_resources()
    assert avail.get("TPU", 0) <= 6.0, avail
    serve.delete("llm-tp2")
    serve.delete("llm-tp1")


# ---------------------------------------------------------------------------
# The serve loop's hand-over order, on an in-process LLMServer (no cluster):
# a step's tokens reach their waiters from inside the NEXT step, and at once
# when the engine runs dry (llm/serve_llm.py: _turn). Whatever the order,
# every waiter gets token for token what engine.generate() gives.
# ---------------------------------------------------------------------------

_MODEL = {"n_layers": 2, "dtype": jnp.float32}
_ENGINE = dict(page_size=8, total_pages=96, max_batch=4, max_seq_len=96,
               prefill_chunk=16, prefill_rows=2, decode_chunk=4, seed=11)
#: (prompt, max_tokens): one that ends at prefill (a budget of one token),
#: short and long ones, more requests than slots (the last ones queue)
_REQUESTS = [(list(range(3, 3 + n)), m) for n, m in (
    (40, 9), (7, 1), (23, 12), (5, 7), (30, 10), (12, 1), (9, 16), (17, 5))]


def _reference(eos):
    """{index: (tokens, finish reason)} from generate(), one at a time."""
    eng = InferenceEngine(LlamaConfig.tiny(**_MODEL), eos_token=eos,
                          **_ENGINE)
    out = {}
    for i, (prompt, max_tokens) in enumerate(_REQUESTS):
        rid = eng.add_request(prompt, max_tokens)
        done = {}
        while rid not in done:
            done.update(eng.step())
        out[i] = (done[rid], eng.finish_reason(rid))
    return out


@pytest.fixture(scope="module")
def eos_token():
    """A token some request emits in mid-output, so that as EOS it stops
    that request early ("stop") and leaves others to their budget."""
    free = _reference(None)
    toks = free[0][0]
    assert len(toks) == 9
    return next(t for i, t in enumerate(toks) if i >= 2 and t not in toks[:i])


@pytest.fixture(scope="module")
def served(eos_token):
    """One LLMServer whose serve.wait sleeps 10 s unless woken: a token
    held across a sleep shows as a stream that takes 10 s to end."""
    server = LLMServer(model_config=_MODEL,
                       engine_config=dict(_ENGINE, eos_token=eos_token))

    class Wake(threading.Event):
        def wait(self, timeout=None):
            return super().wait(10.0)

    server._wake = Wake()
    server._wake.set()
    return server


def _ask(server, i, how, out):
    prompt, max_tokens = _REQUESTS[i]
    request = {"prompt_ids": prompt, "max_tokens": max_tokens}
    if how == "call":
        out[i] = (server(request)["token_ids"], None)
        return
    items = list(server.stream(request))
    assert items[-1]["done"] and not any(it.get("done") for it in items[:-1])
    pieces = [t for it in items[:-1] for t in it["token_ids"]]
    assert pieces == items[-1]["token_ids"]     # in order, nothing twice
    out[i] = (pieces, items[-1]["finish_reason"])


@pytest.mark.parametrize("how", ["stream", "call", "both"])
def test_concurrent_waiters_get_what_generate_gives(served, eos_token, how):
    """N streamed and N non-streamed requests at once, with an EOS stop,
    a length stop and requests that finish at prefill: token for token
    generate()'s, and every stream's end arrives (the last one over the
    flush, not after serve.wait's sleep)."""
    want = _reference(eos_token)
    reasons = [r for _, r in want.values()]
    assert "stop" in reasons and "length" in reasons
    assert any(len(t) < m for (t, _), (_, m) in zip(want.values(),
                                                    _REQUESTS))   # EOS cut it
    assert sum(m == 1 for _, m in _REQUESTS) == 2   # finish at prefill
    kinds = {"stream": ["stream"], "call": ["call"],
             "both": ["stream", "call"]}[how]
    outs = {kind: {} for kind in kinds}
    threads = [threading.Thread(target=_ask, args=(served, i, kind,
                                                   outs[kind]))
               for kind in kinds for i in range(len(_REQUESTS))]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    took = time.monotonic() - t0
    assert not any(t.is_alive() for t in threads)
    for kind in kinds:
        assert {i: toks for i, (toks, _) in outs[kind].items()} \
            == {i: toks for i, (toks, _) in want.items()}, kind
    if "stream" in outs:
        assert {i: r for i, (_, r) in outs["stream"].items()} \
            == {i: r for i, (_, r) in want.items()}
    # the engine's last step's tokens were flushed, not slept on
    assert took < 8.0, took
    with served._lock:
        assert not served._results and not served._token_qs
    stats = served.stats()
    assert 0 < stats["publishes_overlapped"] < stats["publishes"]


def test_a_lone_request_ends_over_the_flush(served, eos_token):
    """One request alone: the step that finishes it is the engine's last,
    no dispatch follows to carry its tokens, and the loop hands them over
    before it sleeps (10 s here)."""
    want = _reference(eos_token)
    for how in ("stream", "call"):
        before = served.stats()
        out = {}
        t0 = time.monotonic()
        _ask(served, 2, how, out)
        assert time.monotonic() - t0 < 8.0
        assert out[2][0] == want[2][0]
        after = served.stats()
        flushes = (after["publishes"] - after["publishes_overlapped"]) \
            - (before["publishes"] - before["publishes_overlapped"])
        assert flushes >= 1


def test_an_abandoned_stream_leaves_nothing_behind(served):
    """A consumer that goes away after its first item: the engine still
    finishes the request, and the late hand-over (one launch after the
    booking) parks nothing in _results."""
    prompt, _ = _REQUESTS[0]
    gen = served.stream({"prompt_ids": prompt, "max_tokens": 20})
    first = next(gen)
    assert first["token_ids"]
    gen.close()
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        with served._lock:
            if not served._abandoned and not served.engine.has_work():
                break
        time.sleep(0.01)
    time.sleep(0.05)
    with served._lock:
        assert not served._results and not served._token_qs
        assert not served._abandoned and not served._events
    assert served._held is None


@pytest.mark.parametrize("which", [6, 0])
def test_a_slow_consumer_gets_fewer_larger_items(served, eos_token, which):
    """A lane that is away while the engine goes on (the runtime resumes
    it only once its last item is acknowledged) takes everything handed
    over meanwhile as ONE item, the stream's end included: the same
    tokens in the same order, in fewer pieces (a length stop after four
    decode loops, and an EOS stop)."""
    want, reason = _reference(eos_token)[which]
    assert len(want) >= 2
    prompt, max_tokens = _REQUESTS[which]
    gen = served.stream({"prompt_ids": prompt, "max_tokens": max_tokens})
    items = [next(gen)]
    deadline = time.monotonic() + 60
    q = served._token_qs[items[0]["request_id"]]
    while not (q.queue and q.queue[-1] is None):     # the end is handed over
        if not (q.queue or served.engine.has_work()) \
                and served._held is None:
            break       # ... and this lane woke so late that it came with
            #             the first item (a loaded host): one piece, not two
        assert time.monotonic() < deadline
        time.sleep(0.01)
    items += list(gen)
    assert items[-1]["done"] and items[-1]["token_ids"] == want
    assert items[-1]["finish_reason"] == reason
    pieces = [it["token_ids"] for it in items[:-1]]
    assert [t for p in pieces for t in p] == want
    assert len(pieces) <= 2         # the first, and all the rest as one
    with served._lock:
        assert not served._token_qs and not served._abandoned
