"""Of the device-idle time between two step programs: how much of it the
engine thread was NOT running, and how much of it stream lanes were awake.

host_gaps.py names the idle after the engine-thread span it falls under.
That says where the thread WAS, not whether it ran: the program clocks
every phase of its host loop twice (llm/engine.py: PhaseClocks) and writes
the thread's CPU time inside each engine.* / serve.* span as its `cpu_us`,
so duration - cpu_us of a span is the time its thread was off the CPU
(waiting for the interpreter, descheduled, or asleep in a call). The
suspects for the interpreter are the stream lanes: each streaming request
is a generator on a lane thread of its own, which writes a stream.deliver
{tokens} span for the time it is awake with an item
(llm/serve_llm.py: LLMServer.stream).

This reader takes host_gaps.analyze's clock offset, rebuilds the same idle
intervals (trace_reduce's busy union of the first chip's plane, inside
[first engine.step's start, last engine.step's end]), and reads

  offcpu_pct  share of that idle under engine-thread spans other than
              engine.readback and serve.wait (both sleep by design and are
              read apart), each part weighted by 1 - cpu/wall of the
              innermost span covering it (a span's OWN wall and CPU: its
              children's taken out)
  lanes_pct   share of that idle during which at least one stream.deliver
              span of another thread is open (union over the lanes,
              clipped to the idle intervals)

and writes into the run's notes (`host_offcpu`): the off-CPU ms by span,
the idle ms under each span during which a lane was awake, how many lanes
were awake at once on average over the idle, the drain (idle under
engine.readback after the program ended: the thread is back from the
device and not yet running; host_gaps's "engine.readback:drain", in
neither share), stream.deliver spans and their summed ms a dispatch, and
every engine-thread span's own wall and CPU ms a dispatch, tokens a lane
span, and what the CPU clock resolves: `cpu_tick_us` (the smallest nonzero
cpu_us of the trace) and `cpu_ticks` (the spans' CPU over it). Where the
host's kernel counts thread CPU in scheduler ticks (10 ms on the chip
machine) a span's cpu_us is 0 or a whole tick: offcpu_pct is then right in
expectation and rests on `cpu_ticks` samples: 70-80 in the traced 8 s of
the cells with 48-128 streams, 20-35 in reason-1chip and reason-moe-1chip,
where it moves by twenty points between two runs of one program. READ IT
WITH ITS TICK COUNT.

Both shares, like every per-layer metric, exist only on a --trace 1 line,
whose host loop runs a quarter to a half slower than an untraced run's
(PERF.md section 5).

args: {"quantity": "offcpu_pct" | "lanes_pct", "anchors": as host_gaps}
None when host_gaps.analyze gives none (no device plane, no engine.step),
when the spans carry no cpu_us (offcpu_pct; a program before PhaseClocks)
or the trace holds no stream.deliver (lanes_pct).
"""

from __future__ import annotations

import gzip
from typing import Dict, List, Optional, Tuple

from benchmark import trace_reduce
from benchmark.readers import host_gaps

LANE_SPAN = "stream.deliver"
SLEEPS = ("engine.readback", "serve.wait")
Span = Tuple[str, int, int, Dict]       # name, start_ns, duration_ns, stats
Interval = Tuple[float, float]


def read_trace(path: str):
    """(planes as trace_reduce.read_planes gives them, the host plane's
    program spans per THREAD with their metadata: [[Span]]). Lines of one
    name (lane threads share theirs) stay apart here."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    planes: Dict[str, Dict[str, List[trace_reduce.Event]]] = {}
    threads: List[List[Span]] = []
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            mine: List[Span] = []
            for ev in line.events:
                start, dur = int(ev.start_ns), int(ev.duration_ns)
                events.append((ev.name, start, dur))
                if plane.name == host_gaps.HOST_PLANE and (
                        ev.name == LANE_SPAN
                        or host_gaps.SPAN.match(ev.name)):
                    mine.append((ev.name, start, dur, dict(ev.stats)))
            if mine:
                threads.append(mine)
    return planes, threads


def _intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The parts of `a` (sorted by start) inside `b` (disjoint, sorted)."""
    out, j = [], 0
    for start, end in a:
        while j < len(b) and b[j][1] <= start:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            lo, hi = max(start, b[k][0]), min(end, b[k][1])
            if hi > lo:
                out.append((lo, hi))
            k += 1
    return out


def _length(intervals: List[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def analyze(planes, threads: List[List[Span]],
            anchors: Optional[Dict[str, List[str]]] = None
            ) -> Optional[Dict]:
    """See the module's docstring; times in the result are ms."""
    base = host_gaps.analyze(planes, anchors)
    engine = next((t for t in threads
                   if any(s[0] == "engine.step" for s in t)), None)
    if base is None or engine is None:
        return None
    skew = base["skew_ms"] * 1e6

    # -- the same idle intervals as host_gaps, on the host's clock ----------
    device = sorted(p for p in planes if trace_reduce.DEVICE_PLANE.match(p))
    lines = planes[device[0]]
    ops = [e for ln, evs in lines.items()
           if trace_reduce.OP_LINE.match(ln) for e in evs]
    mods = [(s, s + d) for ln, evs in lines.items()
            if trace_reduce.MODULE_LINE.match(ln) for _, s, d in evs]
    _, leaves = trace_reduce._self_times(ops)
    busy = trace_reduce._union([(s + skew, e + skew)
                                for s, e in list(leaves) + mods])
    spans = sorted((s for s in engine if host_gaps.SPAN.match(s[0])),
                   key=lambda s: (s[1], -s[2]))
    steps = [s for s in spans if s[0] == "engine.step"]
    first, last = steps[0][1], steps[-1][1] + steps[-1][2]
    gaps = [(e0, s1) for (_, e0), (s1, _) in zip(busy, busy[1:])
            if e0 >= first and s1 <= last]
    idle = sum(b - a for a, b in gaps)
    if idle <= 0:
        return None
    # the idle is rebuilt here from host_gaps's pieces: the two must not
    # drift apart unseen
    if abs(idle / 1e6 - base["idle_ms"]) > 1e-6 * base["idle_ms"]:
        raise ValueError(f"idle {idle / 1e6} ms here, {base['idle_ms']} "
                         "in host_gaps.analyze: the two copies of what "
                         "counts as idle differ")
    inside = [s for s in spans if first <= s[1] and s[1] + s[2] <= last]
    dispatches = sum(1 for s in inside if s[0] == "engine.dispatch")
    out = {"idle_ms": idle / 1e6, "gaps": len(gaps),
           "dispatches": dispatches,
           "drain_ms": base["idle_ms_by_span"].get(
               "engine.readback:drain", 0.0)}

    # -- the lanes: where at least one is awake ------------------------------
    lanes = [s for t in threads if t is not engine
             for s in t if s[0] == LANE_SPAN]
    awake_idle: List[Interval] = []
    per = 1e6 * max(dispatches, 1)
    if lanes:
        awake = sorted((float(s), float(s + d)) for _, s, d, _ in lanes)
        awake_idle = _intersect(trace_reduce._union(awake), gaps)
        within = [s for s in lanes if first <= s[1] and s[1] + s[2] <= last]
        out.update(
            lanes_ms=_length(awake_idle) / 1e6,
            lanes_awake_mean=_length(_intersect(awake, gaps)) / idle,
            deliver_spans=len(within),
            deliver_spans_a_dispatch=len(within) / max(dispatches, 1),
            deliver_ms_a_dispatch=sum(s[2] for s in within) / per,
            deliver_tokens_a_span=sum(s[3].get("tokens", 0) for s in within)
            / max(len(within), 1))

    # -- the engine thread: each span's OWN wall and CPU ----------------------
    # (no clamp: a CPU clock that ticks in 10 ms reads 0 or 10 ms a span,
    # right in the sum and wrong in every term)
    if all("cpu_us" in s[3] for s in spans):
        own_wall = [float(s[2]) for s in spans]
        own_cpu = [float(s[3]["cpu_us"]) * 1e3 for s in spans]
        stack: List[int] = []
        for i, (_, start, dur, _) in enumerate(spans):
            while stack and spans[stack[-1]][1] + spans[stack[-1]][2] \
                    <= start:
                stack.pop()
            if stack:
                own_wall[stack[-1]] -= dur
                own_cpu[stack[-1]] -= float(spans[i][3]["cpu_us"]) * 1e3
            stack.append(i)
        by_span: Dict[str, float] = {}
        with_lanes: Dict[str, float] = {}
        pieces = host_gaps._innermost(
            [(n, float(s), float(s + d)) for n, s, d, _ in spans])
        for start, end, name, i in pieces:
            cover = _length(_intersect([(start, end)], gaps))
            if not cover:
                continue
            if lanes:
                with_lanes[name] = with_lanes.get(name, 0.0) + _length(
                    _intersect([(start, end)], awake_idle))
            if name not in SLEEPS and own_wall[i] > 0:
                by_span[name] = by_span.get(name, 0.0) \
                    + cover * (1.0 - own_cpu[i] / own_wall[i])
        phases: Dict[str, List[float]] = {}
        for s, wall, cpu in zip(spans, own_wall, own_cpu):
            if first <= s[1] and s[1] + s[2] <= last:
                both = phases.setdefault(s[0], [0.0, 0.0])
                both[0] += wall
                both[1] += cpu
        ticks = [c for c in own_cpu if c > 0]
        out.update(
            cpu_tick_us=min(ticks) / 1e3 if ticks else None,
            cpu_ticks=sum(own_cpu) / min(ticks) if ticks else 0.0,
            offcpu_ms=sum(by_span.values()) / 1e6,
            offcpu_ms_by_span={k: v / 1e6
                               for k, v in sorted(by_span.items())},
            own_ms_a_dispatch={k: {"wall": w / per, "cpu": c / per}
                               for k, (w, c) in sorted(phases.items())})
        if lanes:
            out["lanes_ms_by_span"] = {
                k: v / 1e6 for k, v in sorted(with_lanes.items())}
    return out


def read(data, args):
    span = data.get("trace") or {}
    path = span.get("dir") and trace_reduce.find_xplane(span["dir"])
    if not path:
        return None
    anchors = args.get("anchors") or {}
    key = repr(sorted(anchors.items()))
    cache = data.setdefault("_host_offcpu", {})
    if key not in cache:
        cache[key] = analyze(*read_trace(path), anchors)
        if cache[key] is not None:
            data.setdefault("notes", {})["host_offcpu"] = cache[key]
    found = cache[key]
    if found is None:
        return None
    quantity = args["quantity"]
    if quantity not in ("offcpu_pct", "lanes_pct"):
        raise ValueError(f"unknown quantity {quantity!r}")
    part = found.get("offcpu_ms" if quantity == "offcpu_pct" else "lanes_ms")
    return None if part is None else 100.0 * part / found["idle_ms"]
