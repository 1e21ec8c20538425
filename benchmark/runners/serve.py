"""Drive a served model: deploy through the program's own path, warm the
cell's programs, offer the mix, gather what the readers need.

Order of a run:
  set-up   cluster up -> serve.run(build_llm_app(...)) -> device probe ->
           warm-up requests (they run the mixed step, the decode loop and
           the page copy once each) -> the plain reference scores them
  window   lead-in (not measured) -> window of --seconds -> open loop only:
           requests due in the window are followed to their end
  after    counters, request log, trace stop; on the idle engine the plain
           reference scores a seeded handful of the window's own requests
           (served under a full batch, preemption and page reuse); the
           cluster goes down
"""

from __future__ import annotations

import os
import random
import shutil
import threading
import time
from typing import Dict

from benchmark import checks, client, cluster, loadgen
from benchmark.readers._stats import lookup

MODEL = "llm"


class Session:
    """A deployed replica and the calls the run makes on it."""

    def __init__(self, config: Dict, seed: int):
        self.config = config
        self.handle, self.port, self.ready_s = cluster.deploy_llm(
            config, seed, MODEL)

    def call(self, method: str, *args, timeout: float = 600):
        return getattr(self.handle, method).remote(*args).result(
            timeout=timeout)

    def probe(self) -> Dict:
        return self.call("bench_probe")

    def wait_idle(self, timeout: float = 120) -> None:
        deadline = time.monotonic() + timeout
        while self.probe()["has_work"]:
            if time.monotonic() > deadline:
                raise TimeoutError("the engine still has work")
            time.sleep(0.2)

    def warm_and_score(self, mix: Dict, seed: int) -> Dict:
        """The warm-up requests, sent together so prefill chunks and decode
        rows share steps as they will in the window; then their tokens
        against the plain reference, on an idle engine."""
        vocab = self.config["vocab_size"]
        t = [time.monotonic()]
        sample = loadgen.sample_requests(mix, seed, vocab)
        for r in sample:
            r.update(due=0.0, measured=False)
        served = client.run_open_loop(self.port, MODEL, sample,
                                      time.monotonic())
        t.append(time.monotonic())
        pair = loadgen.shared_prefix_pair(
            mix, seed, vocab, self.config["engine"]["page_size"])
        pair_out = [client.stream_completion(self.port, MODEL, r)
                    for r in pair]
        self.wait_idle()
        t.append(time.monotonic())
        scored = self.score(served, mix["reference_pad_to"])
        t.append(time.monotonic())
        return {"results": served + pair_out, "scored": scored,
                "seconds": {"sample": t[1] - t[0], "page_copy": t[2] - t[1],
                            "reference": t[3] - t[2]}}

    def score(self, served, pad_to: int):
        """The plain reference's verdict on served requests (client
        records); the engine must be idle, the reference needs the chip."""
        scored = []
        for got in served:
            if got["error"] or not got["done"]:
                continue
            ref = self.call("bench_reference_check", {
                "prompt_ids": got["prompt"], "token_ids": got["tokens"],
                "pad_to": pad_to})
            scored.append({"served": got["tokens"],
                           "max_tokens": got["max_tokens"], **ref})
        return scored

    def score_window(self, measured, mix: Dict, seed: int):
        """A seeded handful of the window's requests that ended and fit
        the reference's padded length, scored once the engine is idle."""
        pad_to = mix["reference_pad_to"]
        fit = [r for r in measured if r["done"] and not r["error"]
               and len(r["prompt"]) + len(r["tokens"]) <= pad_to]
        random.Random(seed).shuffle(fit)
        self.wait_idle()
        return self.score(fit[:mix["score_in_window"]], pad_to)


def _window_requests(plan: Dict, sess: Session, seconds: float,
                     on_open=None) -> Dict:
    """Offer the plan; returns results and the window on this clock."""
    lead = plan["lead_in_s"]
    t_open = time.monotonic() + lead + 0.25
    t_close = t_open + seconds
    box = {}

    def at_edges():
        try:
            while time.monotonic() < t_open:
                time.sleep(0.005)
            box["open_probe"] = sess.probe()
            if on_open:
                on_open()
            while time.monotonic() < t_close:
                time.sleep(0.005)
            box["close_probe"] = sess.probe()
        except BaseException as e:  # noqa: BLE001 — raised by the caller
            box["error"] = e

    edge = threading.Thread(target=at_edges, daemon=True)
    edge.start()
    if plan["kind"] == "open_loop":
        results = client.run_open_loop(sess.port, MODEL, plan["requests"],
                                       t_open)
    else:
        results = client.run_closed_loop(sess.port, MODEL, plan["queues"],
                                         t_close)
    edge.join()
    if "error" in box:
        raise box["error"]
    return {"results": results, "t_open": t_open, "t_close": t_close, **box}


def run(ctx: Dict) -> Dict:
    """ctx: config, traffic, seed, seconds, trace, chips, t_start (wall),
    rehearse. Returns the run's data for the readers (see run.py)."""
    config, mix = ctx["config"], ctx["traffic"]
    vocab = config["vocab_size"]
    timing: Dict[str, float] = {}
    with cluster.Cluster(ctx["chips"]) as cl:
        timing["cluster_up_s"] = time.time() - ctx["t_start"]
        sess = Session(config, ctx["seed"])
        timing["replica_ready_s"] = sess.ready_s
        device = sess.probe()
        if not ctx["rehearse"] and (device["platform"] != "tpu"
                                    or device["count"] != ctx["chips"]
                                    or device["paged_impl"] != "kernel"):
            raise cluster.NoAccelerator(
                f"the replica runs on {device['platform']} x "
                f"{device['count']} with paged attention "
                f"{device['paged_impl']!r}; the cell needs "
                f"{ctx['chips']} TPU chip(s) and the kernel")
        t0 = time.monotonic()
        warm = sess.warm_and_score(mix, ctx["seed"])
        timing["warm_s"] = time.monotonic() - t0
        timing.update({f"warm_{k}_s": v for k, v in warm["seconds"].items()})

        if mix["kind"] == "open_loop":
            plan = loadgen.open_loop(mix, ctx["seed"], ctx["seconds"], vocab)
        else:
            clients = lookup(config, mix["clients_from"])
            plan = loadgen.closed_loop(mix, ctx["seed"], clients, vocab)

        trace = {}
        if ctx["trace"]:
            trace["dir"] = os.path.join(ctx["scratch"], "trace")
            shutil.rmtree(trace["dir"], ignore_errors=True)

        def start_trace():
            # a few seconds in the middle of the window: traces are large
            def go():
                time.sleep(mix["trace_after_s"])
                trace["start"] = sess.call("bench_trace_start", trace["dir"])
                time.sleep(mix["trace_seconds"])
                trace["stop"] = sess.call("bench_trace_stop")
            trace["thread"] = threading.Thread(target=go, daemon=True)
            trace["thread"].start()

        win = _window_requests(plan, sess, ctx["seconds"],
                               start_trace if ctx["trace"] else None)
        if ctx["trace"]:
            trace.pop("thread").join()
        timing["setup_s"] = (win["t_open"] - time.monotonic()) \
            + (time.time() - ctx["t_start"])
        records = sess.call("request_records")
        end_probe = sess.probe()
        measured = [r for r in win["results"] if r["measured"]]
        t0 = time.monotonic()
        late = sess.score_window(measured, mix, ctx["seed"])
        timing["score_window_s"] = time.monotonic() - t0
    faults = checks.request_faults(warm["results"] + win["results"]) \
        + checks.served_tokens({"warm-up": warm["scored"], "window": late})
    compiled = win["open_probe"]["compile_counts"] \
        != win["close_probe"]["compile_counts"]
    if compiled:
        faults.append(
            f"something compiled inside the window: "
            f"{win['open_probe']['compile_counts']} -> "
            f"{win['close_probe']['compile_counts']}")
    failed = sum(1 for r in measured if checks.request_fault(r))
    return {
        "kind": mix["kind"], "config": config, "traffic": mix,
        "timing": timing, "device": end_probe,
        "results": win["results"], "measured": measured,
        "t_open": win["t_open"], "t_close": win["t_close"],
        "window_s": win["t_close"] - win["t_open"],
        "stats_open": win["open_probe"]["stats"],
        "stats_close": win["close_probe"]["stats"],
        "request_log": records, "trace": trace,
        "attempted": len(measured), "failed": failed, "faults": faults,
        "offered": loadgen.offered_work(plan),
        "notes": {"scored_warm_up": checks.score_summary(warm["scored"]),
                  "scored_window": checks.score_summary(late),
                  "compile_counts_at_close":
                      win["close_probe"]["compile_counts"],
                  "preemptions": win["close_probe"]["stats"]["preemptions"]
                  - win["open_probe"]["stats"]["preemptions"]},
    }

