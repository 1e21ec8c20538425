"""From a profiler trace (.xplane.pb) to numbers: the one reduction every
device metric goes through.

What a TPU trace holds (jax 0.9, libtpu 0.0.34, looked at by hand, see
fixtures/): one plane per chip named "/device:TPU:<n>", with a line
"XLA Modules" (one event per execution of a jitted program, named
"<module>(<fingerprint>)") and a line "XLA Ops" (one event per executed HLO
instruction, named by its whole HLO text, "%fusion.177 = bf16[...] fusion(...)",
of which the reduction keeps the instruction's name, "fusion.177",
"_ragged_attention_pallas.5"; container instructions such as `while` span
their bodies). A line "Async XLA Ops" (copy-start/-done pairs) overlaps the
op line and is not read. Times are
nanoseconds on the device's clock.

  busy       union of the intervals of LEAF ops (a container such as `while`
             spans its body's gaps too, so only what nests nothing counts)
  span       the traced window ON THE DEVICE'S CLOCK: first leaf op's start
             to last leaf op's end. Idle shares divide by it, never by the
             host's stamps around start_trace/stop_trace (the profiler's
             start latency would count as idle)
  op time    SELF time: an op's duration minus the ops nested inside it
  module     count and summed duration of the module line's events
  idle gaps  the complement of busy inside [first op start, last op end],
             each gap named "inside_<module>" when a module event spans it,
             else "before_<module>" for the module that starts next

Everything is averaged over the chips' planes (under tp every chip runs the
same programs). Nothing here imports ray_tpu, and importing jax.profiler
initialises no backend.
"""

from __future__ import annotations

import glob
import gzip
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULE_LINE = re.compile(r"^XLA Modules$")
OP_LINE = re.compile(r"^XLA Ops$")

Event = Tuple[str, int, int]          # name, start_ns, duration_ns


def start_trace(log_dir: str) -> None:
    """Start the jax profiler in THIS process (the one that owns the chip):
    device ops and XLA's own host spans, no Python call stacks."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def find_xplane(trace_dir: str) -> Optional[str]:
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return hits[-1] if hits else None


def read_planes(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """{plane name: {line name: [(event name, start_ns, dur_ns)]}}."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    out: Dict[str, Dict[str, List[Event]]] = {}
    for plane in data.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (ev.name, int(ev.start_ns), int(ev.duration_ns))
                for ev in line.events)
    return out


def structure(planes: Dict[str, Dict[str, List[Event]]], top: int = 12):
    """What a trace holds, for a human to look at before trusting the
    patterns above."""
    out = {}
    for pname, lines in planes.items():
        out[pname] = {}
        for lname, evs in lines.items():
            by: Dict[str, List[int]] = {}
            for name, _, dur in evs:
                by.setdefault(name, []).append(dur)
            ranked = sorted(by.items(), key=lambda kv: -sum(kv[1]))[:top]
            out[pname][lname] = {
                "events": len(evs),
                "top": [[n, len(d), sum(d) / 1e9] for n, d in ranked]}
    return out


def _union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _self_times(ops: List[Event]):
    """(seconds per op name with each event's nested events taken out,
    the intervals of the LEAF events). A container instruction (`while`,
    `conditional`, `call`) spans its body, gaps included: it is busy only
    where a leaf inside it is."""
    out: Dict[str, float] = {}
    leaves: List[Tuple[int, int]] = []
    stack: List[List] = []                 # [name, start, end, self_ns, leaf]

    def close(upto: int) -> None:
        while stack and stack[-1][2] <= upto:
            name, start, end, self_ns, leaf = stack.pop()
            out[name] = out.get(name, 0.0) + max(self_ns, 0) / 1e9
            if leaf:
                leaves.append((start, end))

    for name, start, dur in sorted(ops, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][3] -= dur
            stack[-1][4] = False
        stack.append([name, start, start + dur, dur, True])
    close(1 << 62)
    return out, leaves


def module_name(event_name: str) -> str:
    return re.sub(r"\(\d+\)$", "", event_name)


def op_name(event_name: str) -> str:
    """The op line names an event by its whole HLO text ("%fusion.177 =
    bf16[16,14336]{...} fusion(...)"); tables are keyed by the
    instruction's name, and patterns are matched against the text (without
    the leading %), so "^fusion" works and so does a pattern on the
    instruction's shape or custom_call_target where the name says nothing
    ("closed_call.9", "checkpoint.20": PERF.md, what tracing must name)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


class TraceSummary:
    """Per-chip means over the device planes of one trace."""

    def __init__(self, planes: Dict[str, Dict[str, List[Event]]]):
        self.n_devices = 0
        self.busy_s = 0.0
        self.span_s = 0.0
        self.op_s: Dict[str, float] = {}
        self.op_n: Dict[str, int] = {}
        self.op_text: Dict[str, str] = {}
        self.module_s: Dict[str, float] = {}
        self.module_n: Dict[str, int] = {}
        self.gap_s: Dict[str, float] = {}
        for pname, lines in planes.items():
            if not DEVICE_PLANE.match(pname):
                continue
            raw = [e for ln, evs in lines.items() if OP_LINE.match(ln)
                   for e in evs]
            for text, _, _ in raw:
                self.op_text.setdefault(op_name(text), text.lstrip("%"))
            ops = [(op_name(n), s, d) for n, s, d in raw]
            mods = [e for ln, evs in lines.items() if MODULE_LINE.match(ln)
                    for e in evs]
            if not ops:
                continue
            self.n_devices += 1
            self_s, leaves = _self_times(ops)
            busy = _union(leaves)
            self.busy_s += sum(e - s for s, e in busy) / 1e9
            self.span_s += (busy[-1][1] - busy[0][0]) / 1e9
            for name, sec in self_s.items():
                self.op_s[name] = self.op_s.get(name, 0.0) + sec
            for name, _, _ in ops:
                self.op_n[name] = self.op_n.get(name, 0) + 1
            for name, _, d in mods:
                m = module_name(name)
                self.module_s[m] = self.module_s.get(m, 0.0) + d / 1e9
                self.module_n[m] = self.module_n.get(m, 0) + 1
            for name, sec in _gaps(busy, mods).items():
                self.gap_s[name] = self.gap_s.get(name, 0.0) + sec
        n = max(self.n_devices, 1)
        self.busy_s /= n
        self.span_s /= n
        for d in (self.op_s, self.module_s, self.gap_s):
            for k in d:
                d[k] /= n
        for d in (self.op_n, self.module_n):
            for k in d:
                d[k] = d[k] / n

    # -- what the readers ask ------------------------------------------
    @staticmethod
    def _sum(table: Dict[str, float], patterns: List[str],
             text: Optional[Dict[str, str]] = None) -> float:
        rx = [re.compile(p) for p in patterns]
        text = text or {}
        return sum(v for k, v in table.items()
                   if any(r.search(text.get(k, k)) for r in rx))

    def op_time(self, patterns: List[str]) -> float:
        return self._sum(self.op_s, patterns, self.op_text)

    def op_count(self, patterns: List[str]) -> float:
        return self._sum(self.op_n, patterns, self.op_text)

    def module_time(self, patterns: List[str]) -> float:
        return self._sum(self.module_s, patterns)

    def module_count(self, patterns: List[str]) -> float:
        return self._sum(self.module_n, patterns)

    def breakdown(self, top: int = 10) -> Dict[str, List]:
        ops = [[f"module:{k}", v] for k, v in self.module_s.items()] \
            + [[k, v] for k, v in self.op_s.items()]
        ops.sort(key=lambda kv: -kv[1])
        gaps = sorted(([k, v] for k, v in self.gap_s.items()),
                      key=lambda kv: -kv[1])
        return {"device_ops": ops[:top], "idle_gaps": gaps[:top]}


def _gaps(busy: List[Tuple[int, int]], mods: List[Event]) -> Dict[str, float]:
    """Idle seconds by name: what the device was waiting inside or for."""
    mods = sorted(mods, key=lambda e: e[1])
    starts = [m[1] for m in mods]
    out: Dict[str, float] = {}
    import bisect
    for (_, e0), (s1, _) in zip(busy, busy[1:]):
        i = bisect.bisect_right(starts, e0) - 1
        if i >= 0 and mods[i][1] + mods[i][2] >= s1:
            name = "inside_" + module_name(mods[i][0])
        else:
            j = bisect.bisect_left(starts, e0)
            name = "before_" + module_name(mods[j][0]) if j < len(mods) \
                else "after_last_module"
        out[name] = out.get(name, 0.0) + (s1 - e0) / 1e9
    return out


def summarize(trace_dir_or_file: str) -> Optional[TraceSummary]:
    path = trace_dir_or_file
    if os.path.isdir(path):
        path = find_xplane(path)
    if not path or not os.path.exists(path):
        return None
    summary = TraceSummary(read_planes(path))
    return summary if summary.n_devices else None
