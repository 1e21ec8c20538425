"""Start-up clocks: a leased worker's time from its spawn to "ready", by
phase, read where each phase happens.

ONE helper (pattern: llm/engine.py PhaseClocks) and no plane of its own.
``phase(name, into)`` is a context manager that

  - reads time.perf_counter_ns at both ends and adds the difference to
    ``into["startup_ns_<name>"]`` (the dict it was given: an engine's
    stats, a train session's) and to the process's open record, if any;
  - records ONE span ``startup.<name>`` (kind ``startup``, wall-clock
    start / end, the ambient trace context as trace_id / parent_span_id,
    so the spans of one worker's start share an identifier and hang under
    the actor-creation task that caused them) into the worker's event
    buffer: the plane train/step_profiler.py writes its spans into,
    flushed to the head's timeline (`python -m ray_tpu trace`). Best
    effort: outside a connected worker no span is recorded.

Phases do not nest. The process holds at most ONE open record (the stamp
the interval starts at, and the phases closed so far): ``begin`` opens it
(runtime/worker_main.py when the worker becomes an actor, with the node
daemon's wall stamp of the spawn, or of the lease where the worker was
pooled; in a process no daemon leased, whoever asks first, stamped then),
and books ``process``, the stamp up to now. ``finish(into)`` closes it:
what of the interval from the stamp to now no phase covered is
``startup_ns_other``, so the keys written PARTITION the interval. The keys
are written once; nothing reads a clock after that. The stamp's wall clock
is the start of the span ``startup.process``, which puts the phases on the
clock of the head's task events. A record still open when the actor's
constructor returns is held (``pause``) until whoever finishes it asks
(``begin``): the wait between, for a driver to call the actor's method, is
idle and no part of the interval.

The names (PERF.md section 3 lists them with the metric each is for):
process, import, backend, weights, pool, programs, other for a served
replica (llm/serve_llm.py, llm/engine.py, serve/replica.py); process,
import, mesh, other for a trainer's worker up to its loop's entry
(train/worker_group.py). Inside ``programs`` one span ``startup.program``
a step program (``program``), with what the compile tracker attributed to
that call.

Importing this module must not import jax (runtime/worker_main.py and
serve/controller.py run it in processes that never do).
"""

from __future__ import annotations

import contextlib
import os
import time
from typing import (Any, Dict, Iterator, List, Optional, Sequence,
                    Tuple)

from ray_tpu.util import trace_context

#: the node daemon's wall stamp (time.time_ns) of a worker's spawn, in the
#: child's environment (runtime/node.py: _spawn_worker)
SPAWN_ENV = "RTPU_SPAWN_WALL_NS"
PREFIX = "startup_ns_"
SERVE_PHASES = ("process", "import", "backend", "weights", "pool",
                "programs", "other")
TRAIN_PHASES = ("process", "import", "mesh", "other")
#: of the step programs' compiles (sums over ``program`` records): seconds
#: tracing + lowering, seconds in the backend (compile, or the persistent
#: cache's retrieval), and programs that were not cache hits
PROGRAM_KEYS = ("startup_ns_trace_lower", "startup_ns_backend_compile",
                "startup_programs_cold")
#: what a bare engine's stats hold from its constructor on (and, once
#: load_step_programs ran, startup_ns_programs and PROGRAM_KEYS) ...
ENGINE_KEYS = tuple(PREFIX + p for p in ("backend", "weights", "pool"))
#: ... and a served replica's engine.stats from LLMServer.__init__ on
SERVE_KEYS = tuple(PREFIX + p for p in SERVE_PHASES) + PROGRAM_KEYS

_perf_ns = time.perf_counter_ns


class _Record:
    """The interval in hand: its stamp on both clocks, the phases so far."""

    __slots__ = ("wall0_ns", "perf0_ns", "phases", "paused_ns")

    def __init__(self, stamp_wall_ns: Optional[int]):
        wall, perf = time.time_ns(), _perf_ns()
        # a stamp from the future (a daemon's clock is this host's: it
        # cannot be) would make `process` negative
        self.wall0_ns = min(stamp_wall_ns or wall, wall)
        self.perf0_ns = perf - (wall - self.wall0_ns)
        self.phases: Dict[str, int] = {}
        self.paused_ns: Optional[int] = None


_record: Optional[_Record] = None


def spawn_stamp() -> Optional[int]:
    """The spawn's wall stamp the node daemon left in the environment."""
    try:
        return int(os.environ.get(SPAWN_ENV, "")) or None
    except ValueError:
        return None


def begin(stamp_wall_ns: Optional[int] = None) -> None:
    """Open the process's record at ``stamp_wall_ns`` (now, without one)
    and book ``process``: the stamp up to this call. With a record open
    already (the worker's, when a replica's constructor asks) nothing
    happens, but that a held one (``pause``) goes on: its interval leaves
    out the time it was held."""
    global _record
    if _record is not None:
        if _record.paused_ns is not None:
            _record.perf0_ns += _perf_ns() - _record.paused_ns
            _record.paused_ns = None
        return
    _record = rec = _Record(stamp_wall_ns)
    now = _perf_ns()
    rec.phases["process"] = now - rec.perf0_ns
    span("startup.process", rec.wall0_ns / 1e9, time.time())


def pause() -> None:
    """The actor's constructor returned (runtime/worker_main.py). A record
    nobody finished in it is held from here to the next ``begin``: a
    trainer's worker waiting for its driver to call run(), an actor that
    builds its server in a method, are idle meanwhile, not starting."""
    if _record is not None and _record.paused_ns is None:
        _record.paused_ns = _perf_ns()


class _Phase:
    __slots__ = ("name", "into", "meta", "perf0", "wall0", "ids", "token")

    def __init__(self, name: str, into: Optional[Dict[str, int]],
                 meta: Dict[str, Any]):
        self.name, self.into, self.meta = name, into, meta

    def __enter__(self) -> "_Phase":
        # the phase's span is the ambient one while it is open: what it
        # holds (startup.program inside startup.programs) hangs under it
        ctx = trace_context.current()
        self.ids = self.token = None
        if ctx is not None:
            self.ids = (ctx[0], trace_context.new_span_id(), ctx[1])
            self.token = trace_context.activate(*self.ids[:2])
        self.wall0 = time.time()
        self.perf0 = _perf_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        ns = _perf_ns() - self.perf0
        if self.token is not None:
            trace_context.deactivate(self.token)
        for book, key in ((self.into, PREFIX + self.name),
                          (_record.phases if _record else None, self.name)):
            if book is not None:
                book[key] = book.get(key, 0) + ns
        span("startup." + self.name, self.wall0, time.time(),
             ok=exc_type is None, ids=self.ids, **self.meta)


def phase(name: str, into: Optional[Dict[str, int]] = None,
          **meta: Any) -> _Phase:
    """Clock one phase (module docstring); ``meta`` rides on its span."""
    return _Phase(name, into, meta)


def finish(into: Dict[str, int], phases: Sequence[str]) -> None:
    """Close the record ``begin`` opened: every name of ``phases`` becomes
    a key of ``into`` (0 for one that never ran), ``other`` what of the
    interval from the stamp to now they leave."""
    global _record
    # two actors of one process (local mode runs them as threads) share
    # the record, and the first to finish took it: a fresh one then
    begin()
    rec, _record = _record, None
    now = _perf_ns()
    covered = 0
    for name in phases:
        if name != "other":
            into[PREFIX + name] = rec.phases.get(name, 0)
            covered += into[PREFIX + name]
    into[PREFIX + "other"] = max(now - rec.perf0_ns - covered, 0)


@contextlib.contextmanager
def program(name: str, tracker, **meta: Any) -> Iterator[Dict[str, Any]]:
    """One step program's load inside ``programs``: yields the record it
    fills at exit — {name, **meta, wall_s, trace_s, lower_s, backend_s
    (what the compile tracker attributed to calls of ``name`` meanwhile),
    run_s (the rest: the launch and the run, if the caller blocked on it),
    cache_hit, how ("cold" | "hit", the persistent cache's | "resident":
    nothing compiled, the process had the program)} — and
    records it as ONE span ``startup.program`` (``program``: the name)."""
    split = ("trace_s", "lower_s", "backend_s", "compiles", "cache_hits")

    def read() -> Dict[str, float]:
        st = (tracker.callable_stats(name) if tracker else None) or {}
        return {k: st.get(k, 0) for k in split}

    rec: Dict[str, Any] = {"name": name, **meta}
    before, wall0, perf0 = read(), time.time(), _perf_ns()
    try:
        yield rec
    finally:
        wall_s = (_perf_ns() - perf0) / 1e9
        d = {k: v - before[k] for k, v in read().items()}
        compiled, hit = d["compiles"] > 0, d["cache_hits"] > 0
        rec.update(
            wall_s=wall_s, trace_s=d["trace_s"], lower_s=d["lower_s"],
            backend_s=d["backend_s"], cache_hit=hit,
            how="resident" if not compiled else "hit" if hit else "cold",
            run_s=max(wall_s - d["trace_s"] - d["lower_s"]
                      - d["backend_s"], 0.0))
        span("startup.program", wall0, time.time(), program=name,
             **{k: (round(v, 6) if isinstance(v, float) else v)
                for k, v in rec.items() if k != "name"})


def program_totals(programs: List[Dict[str, Any]]) -> Dict[str, int]:
    """PROGRAM_KEYS of a list of ``program`` records."""
    return {
        "startup_ns_trace_lower": int(1e9 * sum(
            p["trace_s"] + p["lower_s"] for p in programs)),
        "startup_ns_backend_compile": int(1e9 * sum(
            p["backend_s"] for p in programs)),
        "startup_programs_cold": sum(
            1 for p in programs if p["how"] == "cold")}


def summary(stats: Dict[str, int], phases: Sequence[str],
            programs: Sequence[Dict[str, Any]] = ()) -> str:
    """The operator's one line: `start-up 31.4 s: process 2.1, ...,
    programs 11.2 (llm.ragged_step[1] hit 2.9 = trace 0.4 + lower 0.3 +
    backend 0.1 + run 2.1, ...), other 0.8`."""
    def loaded(p: Dict[str, Any]) -> str:
        rows = f"[{p['rows']}]" if p.get("rows") else ""
        return (f"{p['name']}{rows} {p['how']} {p['wall_s']:.2f} = trace "
                f"{p['trace_s']:.2f} + lower {p['lower_s']:.2f} + backend "
                f"{p['backend_s']:.2f} + run {p['run_s']:.2f}")

    parts = []
    for name in phases:
        part = f"{name} {stats.get(PREFIX + name, 0) / 1e9:.2f}"
        if name == "programs" and programs:
            part += " (" + ", ".join(loaded(p) for p in programs) + ")"
        parts.append(part)
    total = sum(stats.get(PREFIX + n, 0) for n in phases) / 1e9
    return f"start-up {total:.2f} s: " + ", ".join(parts)


def log_summary(stats: Dict[str, int], phases: Sequence[str],
                programs: Sequence[Dict[str, Any]] = ()) -> None:
    """``summary`` through the log plane (worker-<id>.log)."""
    try:
        from ray_tpu.util import log_plane
        log_plane.get_logger().info(summary(stats, phases, programs))
    except Exception:  # noqa: BLE001 — a log line never stops a start
        pass


def span(name: str, start: float, end: float, ok: bool = True,
         ids: Optional[Tuple[str, str, str]] = None, **extra: Any) -> None:
    """One span of kind ``startup`` into this worker's event buffer: with
    ``ids`` as (trace_id, span_id, parent_span_id), else a child of the
    ambient trace context's span. Nothing outside a connected worker. The
    phases' own, and the serve controller's serve.replica_start."""
    try:
        from ray_tpu.core.worker import global_worker
        buf = getattr(getattr(global_worker, "backend", None),
                      "event_buffer", None)
        if buf is None:
            return
        if ids is None:
            trace_id, parent = trace_context.current() or ("", "")
            ids = (trace_id, trace_context.new_span_id(), parent)
        buf.record(name=name, task_id="startup", kind="startup",
                   start=start, end=end, ok=ok, trace_id=ids[0],
                   span_id=ids[1], parent_span_id=ids[2], **extra)
    except Exception:  # noqa: BLE001 — telemetry never fails a start
        pass
