"""Device-idle time BETWEEN step programs, named by what the engine's host
loop was doing in it.

The engine is synchronous (dispatch -> readback -> book -> next step), so
between two step programs the device waits for the host. The program
writes its phases into the profiler's own trace (llm/engine.py:
engine.step and, inside it, engine.admit / pack / h2d / dispatch / readback
/ book / metrics; llm/serve_llm.py: serve.publish, serve.wait). This reader
opens the traced run's .xplane.pb, takes the device plane's module events
and leaf-op busy union (trace_reduce's) and the host plane's engine.* and
serve.* events, puts both on ONE clock, and splits every idle interval
between two programs over the innermost span that covers each part of it.

The clock. The two planes' stamps differ by an offset nobody controls. For
every dispatch the device program cannot start before engine.dispatch began
and cannot end after engine.readback ended: device + offset >= dispatch
start, device + offset <= readback end. `anchors` (optional, names of the
runtime's own host events, which may change with the runtime) narrow that:
the program cannot start before the first `start_after` event inside the
dispatch's host window began, nor end after the last `end_before` event
there began. The offset is the middle of what all dispatches of the trace
leave; the interval goes into the run's notes with the rest of the
analysis. An empty interval (the anchors do not mean what was assumed, or
the clocks drift) is noted as inconsistent, and its middle is used.

engine.readback covers the device's run; the idle part of it before the
program began is named "engine.readback:launch" and the part after the
program ended "engine.readback:drain". Time inside engine.step that no
child covers is "engine.step". What no span covers is not attributed.

args: {"quantity": "gap_ms" | "attributed_pct" | "share_pct" | "skew_ms",
       "spans": [names]           (share_pct),
       "anchors": {"start_after": [regex], "end_before": [regex]}}
  gap_ms          median idle interval between two programs, ms
  attributed_pct  share of that idle time under any named span
  share_pct       share under the spans listed
  skew_ms         the offset applied: host clock = device clock + skew
Only gaps inside [first engine.step's start, last engine.step's end] count.
Under tp the lowest-numbered chip's plane is read (every chip runs the same
programs). None when the trace holds no device plane (CPU rehearsal) or no
engine.step (a program without the spans).
"""

from __future__ import annotations

import bisect
import re
from statistics import median
from typing import Dict, List, Optional, Tuple

from benchmark import trace_reduce

HOST_PLANE = "/host:CPU"
SPAN = re.compile(r"^(engine|serve)\.")
Interval = Tuple[float, float]


def _overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def _innermost(spans: List[Tuple[str, float, float]]):
    """Properly nested spans -> disjoint (start, end, name, parent span's
    index) pieces, each named by the innermost span covering it."""
    out: List[Tuple[float, float, str, int]] = []
    stack: List[int] = []
    cursor = 0.0

    def emit(upto: float) -> None:
        if stack and upto > cursor:
            out.append((cursor, upto, spans[stack[-1]][0], stack[-1]))

    for i, (_, start, end) in enumerate(spans):
        while stack and spans[stack[-1]][2] <= start:
            emit(spans[stack[-1]][2])
            cursor = max(cursor, spans[stack.pop()][2])
        emit(start)
        cursor = start
        stack.append(i)
    while stack:
        emit(spans[stack[-1]][2])
        cursor = max(cursor, spans[stack.pop()][2])
    return out


def analyze(planes, anchors: Optional[Dict[str, List[str]]] = None
            ) -> Optional[Dict]:
    """The whole analysis of one trace's planes
    ({plane: {line: [(name, start_ns, dur_ns)]}}, as trace_reduce reads
    them); see the module's docstring. Times in the result are ms."""
    device = sorted(p for p in planes if trace_reduce.DEVICE_PLANE.match(p))
    host = planes.get(HOST_PLANE, {})
    spans = sorted(((n, float(s), float(s + d)) for evs in host.values()
                    for n, s, d in evs if SPAN.match(n)),
                   key=lambda e: (e[1], -e[2]))
    steps = [sp for sp in spans if sp[0] == "engine.step"]
    if not device or not steps:
        return None
    lines = planes[device[0]]
    ops = [e for ln, evs in lines.items()
           if trace_reduce.OP_LINE.match(ln) for e in evs]
    mods = sorted(((n, float(s), float(s + d)) for ln, evs in lines.items()
                   if trace_reduce.MODULE_LINE.match(ln)
                   for n, s, d in evs), key=lambda e: e[1])
    if not ops or not mods:
        return None
    _, leaves = trace_reduce._self_times(ops)

    # -- each dispatch's host window and the program that ran in it -------
    windows = []                       # (dispatch start, readback end)
    readbacks = []                     # index into spans
    for i, (name, start, end) in enumerate(spans):
        if name == "engine.dispatch":
            windows.append([start, None])
        elif name == "engine.readback" and windows \
                and windows[-1][1] is None:
            windows[-1][1] = end
            readbacks.append(i)
    windows = [w for w in windows if w[1] is not None]
    mod_starts = [m[1] for m in mods]
    pairs = []                         # (window, module, readback index)
    for w, rb in zip(windows, readbacks):
        lo = max(bisect.bisect_left(mod_starts, w[0]) - 1, 0)
        hi = bisect.bisect_right(mod_starts, w[1])
        best = max(mods[lo:hi], default=None,
                   key=lambda m: _overlap(w, m[1:]))
        if best and _overlap(w, best[1:]) > 0.5 * (best[2] - best[1]):
            pairs.append((w, best, rb))
    if not pairs:
        return None

    # -- one clock ----------------------------------------------------------
    def anchor(key: str) -> List[Tuple[float, float]]:
        rx = [re.compile(p) for p in (anchors or {}).get(key, ())]
        return sorted((float(s), float(s + d)) for evs in host.values()
                      for n, s, d in evs if any(r.search(n) for r in rx))

    starts_after, ends_before = anchor("start_after"), anchor("end_before")

    def inside(events, w):
        i = bisect.bisect_left(events, (w[0], 0.0))
        j = bisect.bisect_right(events, (w[1], 0.0))
        return events[i:j]

    lo, hi = -float("inf"), float("inf")
    for w, m, _ in pairs:
        first = inside(starts_after, w)[:1]
        last = inside(ends_before, w)[-1:]
        lo = max(lo, (first[0][0] if first else w[0]) - m[1])
        hi = min(hi, (last[0][0] if last else w[1]) - m[2])
    skew = (lo + hi) / 2.0

    # -- idle between programs, on the host's clock ---------------------------
    busy = trace_reduce._union(
        [(s + skew, e + skew) for s, e in leaves]
        + [(m[1] + skew, m[2] + skew) for m in mods])
    first_step, last_step = steps[0][1], steps[-1][2]
    gaps = [(e0, s1) for (_, e0), (s1, _) in zip(busy, busy[1:])
            if e0 >= first_step and s1 <= last_step]
    if not gaps:
        return None

    pieces = []
    launch_drain = {rb: m for _, m, rb in pairs}
    for start, end, name, idx in _innermost(spans):
        m = launch_drain.get(idx)
        if m is None:
            pieces.append((start, end, name))
            continue
        began, ended = m[1] + skew, m[2] + skew
        pieces += [(start, min(end, began), name + ":launch"),
                   (max(start, began), min(end, ended), name),
                   (max(start, ended), end, name + ":drain")]
    pieces = [p for p in pieces if p[1] > p[0]]
    piece_starts = [p[0] for p in pieces]
    by_span: Dict[str, float] = {}
    for gap in gaps:
        i = max(bisect.bisect_right(piece_starts, gap[0]) - 1, 0)
        while i < len(pieces) and pieces[i][0] < gap[1]:
            cover = _overlap(gap, pieces[i][:2])
            if cover:
                by_span[pieces[i][2]] = by_span.get(pieces[i][2], 0.0) + cover
            i += 1
    lengths = [b - a for a, b in gaps]
    total = sum(lengths)
    return {
        "skew_ms": skew / 1e6, "skew_lo_ms": lo / 1e6, "skew_hi_ms": hi / 1e6,
        "consistent": lo <= hi, "dispatches_paired": len(pairs),
        "steps": len(steps), "gaps": len(gaps),
        "gap_ms": median(lengths) / 1e6, "idle_ms": total / 1e6,
        "span_ms": (last_step - first_step) / 1e6,
        "idle_ms_by_span": {k: v / 1e6 for k, v in sorted(by_span.items())},
        "unattributed_ms": (total - sum(by_span.values())) / 1e6,
    }


def read(data, args):
    span = data.get("trace") or {}
    path = span.get("dir") and trace_reduce.find_xplane(span["dir"])
    if not path:
        return None
    anchors = args.get("anchors") or {}
    key = repr(sorted(anchors.items()))
    cache = data.setdefault("_host_gaps", {})
    if key not in cache:
        cache[key] = analyze(trace_reduce.read_planes(path), anchors)
        if cache[key] is not None:
            data.setdefault("notes", {})["host_gaps"] = cache[key]
    found = cache[key]
    if found is None:
        return None
    quantity = args["quantity"]
    if quantity in ("gap_ms", "skew_ms"):
        return found[quantity]
    if quantity == "attributed_pct":
        return 100.0 * (1.0 - found["unattributed_ms"] / found["idle_ms"])
    if quantity == "share_pct":
        return 100.0 * sum(found["idle_ms_by_span"].get(n, 0.0)
                           for n in args["spans"]) / found["idle_ms"]
    raise ValueError(f"unknown quantity {quantity!r}")
