"""LLMServer — the serve deployment wrapping InferenceEngine.

Role-equivalent to the reference's LLMDeployment + OpenAI surface
(reference: llm/_internal/serve/deployments/llm/vllm/vllm_deployment.py;
configs/openai_api_models.py request/response schemas): requests arriving
on any of the replica's handler threads enqueue into the engine and block
on a per-request event; a single engine thread runs the continuous-
batching loop, so concurrent requests share decode batches.

Token streaming: ``stream()`` is a generator — under serve it runs as a
streaming actor method, every yielded token batch becomes consumable
before the request finishes, and the HTTP proxy turns it into SSE
(``/v1/completions`` with ``"stream": true``, the reference's OpenAI
contract). Each item's way out of the replica, on the lane thread that
runs the generator, is a stream.deliver span of a traced run (stream()).
The runtime resumes a lane only once the caller has acknowledged its last
item, and the lane then takes everything the engine handed over meanwhile
as ONE item: a way out slower than the engine carries fewer, larger
items, never a growing backlog.
"""

from __future__ import annotations

import queue as queue_mod
import threading
import time
from typing import Any, Dict, Iterator, List, Optional

from ray_tpu.llm.engine import InferenceEngine, TraceAnnotation
from ray_tpu.llm.tokenizer import ByteTokenizer
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.util import log_plane, startup_clocks, trace_context


def _ambient_trace_id() -> str:
    """The trace_id the serve router stamped on this request's wire
    frame (restored as ambient context by the worker runtime) — linked
    into the engine's flight-recorder record so `ray_tpu trace
    --request <rid>` can merge span tree + request timeline."""
    amb = trace_context.current()
    return amb[0] if amb else ""


class LLMServer:
    """Use via serve:  serve.deployment(max_ongoing_requests=16)(LLMServer)
    then .bind(cfg_kwargs...). Accepts {"prompt_ids": [...],
    "max_tokens": N} and returns {"token_ids": [...]}."""

    def __init__(self, model_config: Optional[Dict[str, Any]] = None,
                 engine_config: Optional[Dict[str, Any]] = None,
                 tokenizer=None, model_name: str = "rtpu-llm",
                 chat_template=None):
        # this replica's start-up clocks (util/startup_clocks.py): the
        # worker's record where the runtime opened one, else one from here
        startup_clocks.begin()
        cfg = LlamaConfig.tiny(**(model_config or {}))
        self.engine = InferenceEngine(cfg, **(engine_config or {}))
        # every program the engine can dispatch, compiled and loaded now:
        # nothing compiles once the replica is ready, whatever shapes the
        # traffic reaches first
        self.engine.load_step_programs()
        self.engine.track_progress = True  # the serve loop drains it
        # hand-overs to the waiters, and those made under a running
        # program; and every start-up key, so that none is new to the
        # dict once the engine thread reads it
        stats = self.engine.stats
        stats.update(publishes=0, publishes_overlapped=0)
        for key in startup_clocks.SERVE_KEYS:
            stats.setdefault(key, 0)
        # (finished, progress) of the last step, not yet handed over
        self._held = None
        self.tokenizer = tokenizer or ByteTokenizer()
        self.model_name = model_name
        self.chat_template = chat_template or apply_chat_template
        self._results: Dict[str, List[int]] = {}
        self._events: Dict[str, threading.Event] = {}
        self._abandoned: set = set()
        # rid -> queue of incremental token lists (None = stream end);
        # fed by the engine thread, drained by stream() generators
        self._token_qs: Dict[str, "queue_mod.Queue"] = {}
        self._lock = threading.Lock()
        # stream.deliver spans whose generator another thread closed
        self._orphan_spans: List[Any] = []
        self._wake = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="llm-engine")
        self._thread.start()
        # written once, here: a window's difference of these keys is 0
        startup_clocks.finish(stats, startup_clocks.SERVE_PHASES)
        startup_clocks.log_summary(stats, startup_clocks.SERVE_PHASES,
                                   self.engine.startup_programs)

    def _loop(self) -> None:
        """The engine thread. A step's tokens (the finished sequences and
        the progress of the program it BOOKED, drained when it returns)
        are HELD and handed to their waiters from inside the NEXT
        engine.step, by its after_dispatch hook: right after that step's
        engine.dispatch and before its engine.readback, with a program on
        the device and this thread about to sleep on one. Handed over
        before the next step instead, the 16-128 stream lanes a hand-over
        wakes take the interpreter from this thread in the stretch before
        a launch (admit, pack, h2d, dispatch), which the device waits for
        wherever the engine has no program queued. So a token reaches its
        waiter one launch after it is booked; the request log's timestamps
        are the booking's, not the delivery's.

        The engine runs one program ahead where it can (llm/engine.py): a
        step launches program N+1 and then books program N, which was
        launched a step earlier. A token's way is then: computed by N,
        booked at the end of the step that launched N+1 (as soon as N has
        ended on the device), handed over under the launch of N+2, about
        one host stretch (admit .. dispatch) after N ended; what it was
        before, with the device busy meanwhile. A step that launched a
        program and booked none (the pipeline filling) returns nothing and
        holds nothing. A step that launches nothing and books the program
        in flight (the pipeline draining) calls the hook before it sleeps
        on that program: what the step before booked does not wait a whole
        program for its hand-over. Its own tokens fall under the flush
        rule.

        A step that HOLDS its launch back (a program in flight, a slot
        free, nobody waiting: InferenceEngine.step's late decision) calls
        the hook first, then sleeps in engine.hold until the flight is
        due, then admits again and launches. So the tokens of program N
        are handed over at the START of the hold under N+1, the clients
        of the rows that N ended come back during that hold (some tens of
        ms later, with the lanes that the hand-over woke long done), and
        the admission after the hold puts them into the mixed step that
        lands on the device as N+1 ends. The hook is called once in such
        a step, never again after its launch.

        Flush rule: what no dispatch will carry is handed over at once,
        in the old place: after a step that launched nothing, and before
        serve.wait when the engine has run dry. No token is held across a
        sleep. One thread, FIFO: every stream still gets step N's tokens,
        then step N+1's, then its end.

        The loop's two phases go through the engine's phase helper like
        the step's own (llm/engine.py): spans of the same trace,
        serve.wait and serve.publish {streams} (names the benchmark
        reads; serve.publish nests in engine.step unless it is a flush),
        each with cpu_us, and the counters wall_ns_publish / wall_ns_wait
        of engine.stats (serve.wait sleeps by design: the thread's CPU
        counter, cpu_ns_host, is read at its two ends and leaves it out;
        wall_ns_wait also holds the steps' engine.hold, the other sleep
        with nothing to do for the device).
        engine.stats also counts `publishes` (hand-overs that had
        something to hand over) and `publishes_overlapped` (those made
        with a program on the device). _publish wakes every stream that
        got tokens; what those lane threads then do is stream.deliver
        {tokens} (stream())."""
        while True:
            self._turn()

    def _turn(self) -> None:
        """One turn of the loop: a step and what it leaves held, or the
        flush and the sleep of an engine with no work."""
        engine = self.engine
        if not engine.has_work():
            self._hand_over(overlapped=False)
            with engine.phase("serve.wait"):
                self._wake.wait(timeout=0.05)
            self._wake.clear()
            return
        finished = engine.step(self._hand_over_under_the_device)
        self._hand_over(overlapped=False)       # it slept on no program
        progress = engine.drain_progress()
        if finished or progress:
            self._held = (finished, progress)

    def _hand_over_under_the_device(self) -> None:
        """step()'s after_dispatch hook: a program is on the device, just
        launched or still in flight."""
        self._hand_over(overlapped=True)

    def _hand_over(self, overlapped: bool) -> None:
        """Publish what the last step left held, if anything."""
        held, self._held = self._held, None
        if held is None:
            return
        with self.engine.phase("serve.publish") as span:
            streams = self._publish(*held)
            if span.is_enabled():
                span.set_metadata(streams=streams)
        stats = self.engine.stats
        stats["publishes"] += 1
        if overlapped:
            stats["publishes_overlapped"] += 1

    def _publish(self, finished: Dict[str, List[int]],
                 progress: Dict[str, List[int]]) -> int:
        """Hand one step's tokens to their waiters; returns how many
        streams got some."""
        streams = 0
        with self._lock:
            for rid, new_toks in progress.items():
                q = self._token_qs.get(rid)
                if q is not None and new_toks:
                    q.put(list(new_toks))
                    streams += 1
            for rid, toks in finished.items():
                q = self._token_qs.get(rid)
                if q is not None:
                    q.put(None)  # end of stream
                    continue
                if rid in self._abandoned:
                    self._abandoned.discard(rid)
                    continue
                self._results[rid] = toks
                ev = self._events.get(rid)
                if ev is not None:
                    ev.set()
        return streams

    def __call__(self, request: Dict[str, Any]) -> Dict[str, Any]:
        prompt = self._prompt_ids(request)
        max_tokens = int(request.get("max_tokens", 32))
        ev = threading.Event()
        rid = self.engine.add_request(prompt, max_tokens,
                                      trace_id=_ambient_trace_id())
        # ambient request id: every log record emitted while this
        # request is in flight on this thread carries request_id=rid,
        # so `ray_tpu logs --request RID` finds it
        with log_plane.request_context(rid):
            log_plane.get_logger().info(
                f"llm request start ({len(prompt)} prompt tok, "
                f"max_new {max_tokens})")
            with self._lock:
                self._events[rid] = ev
                if rid in self._results:  # engine already finished it
                    ev.set()
            self._wake.set()
            if not ev.wait(timeout=300):
                # the engine will still finish the request eventually;
                # mark it abandoned so _loop drops the late result
                # instead of leaking it (and the event) forever
                with self._lock:
                    self._events.pop(rid, None)
                    self._abandoned.add(rid)
                log_plane.get_logger().warning("llm request timed out")
                raise TimeoutError(f"LLM request {rid} timed out")
            with self._lock:
                toks = self._results.pop(rid)
                self._events.pop(rid, None)
            log_plane.get_logger().info(
                f"llm request finished ({len(toks)} tok)")
        return {"token_ids": toks, "request_id": rid}

    # ------------------------------------------------------------ streaming

    def stream(self, request: Dict[str, Any]) -> Iterator[Dict[str, Any]]:
        """Generator: yields {"token_ids": [...]} batches as the engine
        produces them, then {"done": True, "token_ids": <all>}."""
        prompt = self._prompt_ids(request)
        max_tokens = int(request.get("max_tokens", 32))
        q: "queue_mod.Queue" = queue_mod.Queue()
        with self._lock:
            rid = self.engine.add_request(prompt, max_tokens,
                                          trace_id=_ambient_trace_id())
            self._token_qs[rid] = q
        # a generator can't hold the ambient contextvar across yields
        # without leaking it into the consumer, so stamp the lifecycle
        # records explicitly instead
        with log_plane.request_context(rid):
            log_plane.get_logger().info(
                f"llm stream start ({len(prompt)} prompt tok, "
                f"max_new {max_tokens})")
        self._wake.set()
        produced: List[int] = []
        completed = False
        awake = None
        try:
            while not completed:
                item = q.get(timeout=300)
                completed = item is None
                # everything the engine handed over while this lane was
                # away leaves as ONE item: the runtime resumes a lane only
                # once its last item is acknowledged (worker_main.py:
                # _stream_out), so a way out that is slower than the engine
                # carries fewer, larger items instead of a growing backlog
                while not completed:
                    try:
                        more = q.get_nowait()
                    except queue_mod.Empty:
                        break
                    if more is None:
                        completed = True
                    else:
                        item.extend(more)
                if item is not None:
                    awake = self._lane_awake(len(item))
                    produced.extend(item)
                    yield {"token_ids": item, "request_id": rid}
                    awake = self._lane_asleep(awake)
            awake = self._lane_awake(0)         # the closing item's
            with log_plane.request_context(rid):
                log_plane.get_logger().info(
                    f"llm stream finished ({len(produced)} tok)")
            yield {"done": True, "request_id": rid,
                   "token_ids": list(produced),
                   "finish_reason": self.engine.finish_reason(rid),
                   "cached_tokens": self.engine.cached_tokens(rid)}
        finally:
            self._lane_asleep(awake)
            with self._lock:
                self._token_qs.pop(rid, None)
                if not completed:
                    # consumer went away mid-stream (disconnect/close):
                    # the engine will still finish rid — mark abandoned so
                    # _loop drops the late result instead of parking it in
                    # _results forever, and drop any already-parked result
                    self._results.pop(rid, None)
                    self._abandoned.add(rid)

    # stream.deliver {tokens}: a lane thread's time with one item, from
    # q.get returning to the same lane asking for the next (between them:
    # the yield through serve/replica.py's handle_request_streaming, the
    # worker runtime's _send_stream_item: serialise, ship, and since PR 36
    # its wait, asleep, for the caller's acknowledgement). Placed HERE
    # and not in runtime/worker_main.py: this module has jax already, the
    # runtime must not import it; and the generator's frame is the one
    # place that sees both ends on the lane. Not a `with`: a span held
    # across a yield would end on whichever thread closes an abandoned
    # generator. No cpu_us here: the lanes hold the interpreter the engine
    # thread waits for, and where the thread CPU clock is a system call
    # (5.6 us a read on the chip machine) two reads an item cost more than
    # the rest of the span ten times over.

    def _lane_awake(self, tokens: int):
        """Open the span on this lane; None (a flag test) when no trace
        runs."""
        if not TraceAnnotation.is_enabled():
            if self._orphan_spans:      # parked during a trace that ended
                self._orphan_spans.clear()
            return None
        span = TraceAnnotation("stream.deliver", tokens=tokens)
        span.__enter__()
        return span, threading.get_ident()

    def _lane_asleep(self, awake) -> None:
        """Close what _lane_awake opened, on the lane that opened it."""
        if awake is None:
            return
        span, lane = awake
        if threading.get_ident() != lane:
            # the generator is being closed by another thread (a
            # collector, a caller's close()): when the lane fell asleep is
            # not known. A TraceMe writes its event where and when it is
            # ended OR destroyed while a trace runs, so this one is kept
            # until none does (_lane_awake drops them then)
            self._orphan_spans.append(span)
            return
        span.__exit__(None, None, None)

    def _prompt_ids(self, request: Dict[str, Any]) -> List[int]:
        if "prompt_ids" in request:
            return list(request["prompt_ids"])
        prompt = request.get("prompt")
        if isinstance(prompt, str):
            return self.tokenizer.encode(prompt)
        if isinstance(prompt, list):
            return list(prompt)
        raise ValueError("request needs 'prompt' (str) or 'prompt_ids'")

    # --------------------------------------------------------- OpenAI API

    def _completion_body(self, rid: str, token_ids: List[int],
                         n_prompt: int, finish_reason: str,
                         cached: int = 0) -> Dict[str, Any]:
        return {
            "id": f"cmpl-{rid}",
            "object": "text_completion",
            "created": int(time.time()),
            "model": self.model_name,
            "choices": [{"index": 0,
                         "text": self.tokenizer.decode(token_ids),
                         "token_ids": list(token_ids),
                         "logprobs": None,
                         "finish_reason": finish_reason}],
            # prompt_tokens_details.cached_tokens: prompt tokens served
            # from the engine's prefix cache (OpenAI cached-tokens field)
            "usage": {"prompt_tokens": n_prompt,
                      "completion_tokens": len(token_ids),
                      "total_tokens": n_prompt + len(token_ids),
                      "prompt_tokens_details": {"cached_tokens": cached}},
        }

    def completions(self, request: Dict[str, Any]) -> Dict[str, Any]:
        """OpenAI-style /v1/completions, non-streaming (reference:
        llm/_internal/serve/configs/openai_api_models.py
        CompletionResponse)."""
        prompt = self._prompt_ids(request)
        out = self.__call__({"prompt_ids": prompt,
                             "max_tokens": request.get("max_tokens", 32)})
        return self._completion_body(
            out["request_id"], out["token_ids"], len(prompt),
            self.engine.finish_reason(out["request_id"]),
            self.engine.cached_tokens(out["request_id"]))

    def completions_stream(self, request: Dict[str, Any]
                           ) -> Iterator[Dict[str, Any]]:
        """OpenAI-style streaming chunks (SSE framing happens in the
        proxy); each chunk carries the newly-decoded text delta."""
        prompt = self._prompt_ids(request)
        rid = None
        for item in self.stream({"prompt_ids": prompt,
                                 "max_tokens":
                                     request.get("max_tokens", 32)}):
            rid = item["request_id"]
            if item.get("done"):
                chunk = self._completion_body(
                    rid, [], len(prompt),
                    item.get("finish_reason", "length"),
                    item.get("cached_tokens", 0))
                chunk["object"] = "text_completion.chunk"
                # the terminal chunk is where OpenAI clients read usage:
                # report the real completion count, not the empty delta
                n_out = len(item.get("token_ids", ()))
                chunk["usage"]["completion_tokens"] = n_out
                chunk["usage"]["total_tokens"] = len(prompt) + n_out
                yield chunk
                return
            chunk = self._completion_body(rid, item["token_ids"],
                                          len(prompt), None)
            chunk["object"] = "text_completion.chunk"
            chunk.pop("usage")
            yield chunk

    # ----------------------------------------------------- chat completions

    def _chat_prompt_ids(self, request: Dict[str, Any]) -> List[int]:
        messages = request.get("messages")
        if not isinstance(messages, list) or not messages:
            raise ValueError("chat request needs a non-empty 'messages' "
                             "list")
        return self.tokenizer.encode(self.chat_template(messages))

    def _chat_body(self, rid: str, content: str, n_prompt: int,
                   n_out: int, finish_reason,
                   cached: int = 0) -> Dict[str, Any]:
        return {
            "id": f"chatcmpl-{rid}",
            "object": "chat.completion",
            "created": int(time.time()),
            "model": self.model_name,
            "choices": [{"index": 0,
                         "message": {"role": "assistant",
                                     "content": content},
                         "finish_reason": finish_reason}],
            "usage": {"prompt_tokens": n_prompt,
                      "completion_tokens": n_out,
                      "total_tokens": n_prompt + n_out,
                      "prompt_tokens_details": {"cached_tokens": cached}},
        }

    def chat_completions(self, request: Dict[str, Any]) -> Dict[str, Any]:
        """OpenAI-style /v1/chat/completions, non-streaming: role-templated
        messages -> prompt, assistant message back (reference:
        llm/_internal/serve/configs/openai_api_models.py
        ChatCompletionRequest/Response)."""
        prompt = self._chat_prompt_ids(request)
        out = self.__call__({"prompt_ids": prompt,
                             "max_tokens": request.get("max_tokens", 32)})
        toks = out["token_ids"]
        return self._chat_body(
            out["request_id"], self.tokenizer.decode(toks), len(prompt),
            len(toks), self.engine.finish_reason(out["request_id"]),
            self.engine.cached_tokens(out["request_id"]))

    def chat_completions_stream(self, request: Dict[str, Any]
                                ) -> Iterator[Dict[str, Any]]:
        """OpenAI chat streaming chunks: first delta carries the role,
        then content deltas, then the terminal chunk with finish_reason +
        usage (SSE framing happens in the proxy)."""
        prompt = self._chat_prompt_ids(request)
        first = True
        for item in self.stream({"prompt_ids": prompt,
                                 "max_tokens":
                                     request.get("max_tokens", 32)}):
            rid = item["request_id"]
            if item.get("done"):
                chunk = self._chat_body(
                    rid, "", len(prompt), len(item.get("token_ids", ())),
                    item.get("finish_reason", "length"),
                    item.get("cached_tokens", 0))
                chunk["object"] = "chat.completion.chunk"
                chunk["choices"][0]["delta"] = {}
                del chunk["choices"][0]["message"]
                yield chunk
                return
            delta: Dict[str, Any] = {
                "content": self.tokenizer.decode(item["token_ids"])}
            if first:
                delta = {"role": "assistant", **delta}
                first = False
            chunk = self._chat_body(rid, "", len(prompt), 0, None)
            chunk["object"] = "chat.completion.chunk"
            chunk["choices"][0]["delta"] = delta
            del chunk["choices"][0]["message"]
            chunk.pop("usage")
            yield chunk

    def stats(self) -> Dict[str, Any]:
        out = dict(self.engine.stats)
        prefix = self.engine.prefix
        if prefix is not None:
            out["prefix_cache"] = {
                "lookups": prefix.lookups, "hits": prefix.hits,
                "hit_tokens": prefix.hit_tokens,
                "evictions": prefix.evictions,
                "cached_pages": prefix.num_cached,
                "evictable_pages": prefix.num_evictable,
            }
        return out

    def engine_report(self) -> Dict[str, Any]:
        """What this replica runs on and what it compiled (the engine's
        device_report()), plus this process's compile accounting: the
        tracker's per-kind counts (``compile_counts``: persistent-cache
        hits and misses among them), where the persistent compile cache
        lives, and ``compile_seconds``: for every callable the seam
        wrapped (llm.init_params, llm.init_kv, the step programs) one
        dict {wall_s, trace_s, lower_s, backend_s, compiles, cache_hits}
        — seconds of the calls that compiled, of them tracing, lowering
        and in the backend (a cache hit's backend seconds are the
        retrieval), and how many compiles were persistent-cache hits."""
        import os

        from ray_tpu.util import compile_cache, compile_tracker
        out = self.engine.device_report()
        tracker = compile_tracker.get_global()
        if tracker is not None:
            out["compile_counts"] = tracker.stats()["counts"]
            fields = ("wall_s", "trace_s", "lower_s", "backend_s",
                      "compiles", "cache_hits")
            out["compile_seconds"] = {}
            for name in self.engine._fns.tracked:
                st = tracker.callable_stats(name) or {}
                out["compile_seconds"][name] = {
                    k: round(st.get(k, 0), 3) for k in fields}
        out["compile_cache_dir"] = os.environ.get(compile_cache.ENV_VAR)
        out["pid"] = os.getpid()
        return out

    def plain_check(self, request: Dict[str, Any]) -> Dict[str, Any]:
        """Score {"prompt_ids", "token_ids"} — a greedy continuation some
        engine produced — against the plain forward path on THIS
        replica's weights (llm.model.plain_greedy_check)."""
        from ray_tpu.llm.model import plain_greedy_check
        eng = self.engine
        return plain_greedy_check(
            eng.params, eng.cfg, list(request["prompt_ids"]),
            list(request["token_ids"]),
            seq_len=eng.max_pages_per_seq * eng.page_size)

    def request_records(self) -> List[Dict[str, Any]]:
        """Flight-recorder snapshot of this replica's engine (wire
        dicts; [] when the recorder is disabled). The same records ship
        to the head over telemetry_push — this is the direct,
        replica-local view for tests and debugging."""
        if self.engine.request_log is None:
            return []
        return self.engine.request_log.snapshot()

    def set_overload_level(self, level: int,
                           budget_factor: float = 0.5) -> int:
        """Degradation ladder hook, invoked by the serve controller's SLO
        policy: level n runs the engine at step_token_budget *
        budget_factor**n — tighter prefill admission keeps decode TPOT
        alive for already-admitted requests at the cost of new-request
        TTFT. Level 0 restores the configured budget. Returns the
        effective budget (an unbounded base budget of 0 degrades from
        the config default so level>0 always tightens something)."""
        if not hasattr(self, "_base_token_budget"):
            self._base_token_budget = self.engine.step_token_budget
        level = max(0, int(level))
        if level == 0:
            self.engine.step_token_budget = self._base_token_budget
        else:
            base = self._base_token_budget or InferenceEngine.__init__ \
                .__kwdefaults__["step_token_budget"]
            self.engine.step_token_budget = max(
                64, int(base * (budget_factor ** level)))
        return self.engine.step_token_budget

    def check_health(self) -> None:
        if not self._thread.is_alive():
            raise RuntimeError("engine thread died")


def apply_chat_template(messages: List[Dict[str, Any]]) -> str:
    """Default role templating (reference: the router templates chat
    messages through the model's tokenizer chat template; this framework's
    byte-level tokenizer uses an explicit llama-chat-style marker form —
    swap per model via LLMServer(chat_template=...))."""
    parts = []
    for m in messages:
        role = str(m.get("role", "user"))
        content = str(m.get("content", ""))
        parts.append(f"<|{role}|>\n{content}")
    parts.append("<|assistant|>\n")
    return "\n".join(parts)


# ---------------------------------------------------------------------------
# Placement derivation: parallel degrees -> gang bundles
# ---------------------------------------------------------------------------

def placement_for_engine(tp: int = 1, pp: int = 1,
                         chips_per_host: int = 8):
    """(bundles, strategy) derived from the engine's parallel degrees —
    the reference computes the same from TP×PP engine_kwargs (reference:
    llm/_internal/serve/deployments/llm/vllm/vllm_models.py:128-153).

    TPU mapping: a tp-group must sit on ICI, so a group that fits one
    host is ONE bundle of tp chips (STRICT_PACK — same host, adjacent
    chips); a group spanning hosts becomes one whole-host bundle per
    host, PACKed so the slice stays ICI-contiguous.
    """
    world = max(1, int(tp)) * max(1, int(pp))
    if world <= chips_per_host:
        return [{"TPU": float(world)}], "STRICT_PACK"
    if world % chips_per_host:
        raise ValueError(
            f"tp*pp={world} spans hosts but is not a multiple of "
            f"chips_per_host={chips_per_host}")
    n_hosts = world // chips_per_host
    return ([{"TPU": float(chips_per_host)}] * n_hosts), "PACK"


def build_llm_app(model_config: Optional[Dict[str, Any]] = None,
                  engine_config: Optional[Dict[str, Any]] = None, *,
                  name: str = "llm", num_replicas: int = 1,
                  max_ongoing_requests: int = 16,
                  runtime_env: Optional[Dict[str, Any]] = None,
                  model_name: str = "rtpu-llm"):
    """Bind an LLMServer deployment whose replica resources are DERIVED
    from the engine's tensor-parallel degree (reference: the LLM
    deployment's placement-group shorthand, vllm_models.py:128-153).

    Every replica reserves a {"TPU": tp} gang on one host — a replica
    that runs a model holds its chips alone, tp == 1 included: a chip has
    one owner, and only a worker leased with TPU resources may see one.
    The engine process drives all tp chips through one jax Mesh, so the
    gang and the mesh are the same object. (CPU test clusters advertise
    fake ``TPU`` resources and give the replica virtual devices through
    ``runtime_env``.)

    A tp-group larger than one host's chips needs one engine process
    per host under ``jax.distributed`` — not served by this builder;
    ``placement_for_engine`` already computes the multi-host bundles
    for when the serve controller grows PG-backed replicas.
    """
    from ray_tpu import serve as serve_mod
    engine_config = dict(engine_config or {})
    bundles, _ = placement_for_engine(int(engine_config.get("tp", 1)))
    if len(bundles) > 1:
        raise NotImplementedError(
            "tp groups spanning hosts need one engine process per "
            "host (jax.distributed); shard within one host's chips "
            "or raise chips_per_host")
    ray_actor_options: Dict[str, Any] = {"resources": bundles[0]}
    if runtime_env:
        ray_actor_options["runtime_env"] = runtime_env
    dep = serve_mod.deployment(
        name=name, num_replicas=num_replicas,
        max_ongoing_requests=max_ongoing_requests,
        ray_actor_options=ray_actor_options)(LLMServer)
    return dep.bind(model_config, engine_config, None, model_name)
