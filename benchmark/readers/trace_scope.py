"""Share of the device's BUSY time spent in ops traced under a
`jax.named_scope` of the program (self time, as readers/trace_share.py).

The op line of a TPU trace names an event by its HLO text, and a fusion's
name ("fusion.150") says nothing of where it came from. The profiler keeps
each instruction's NAME PATH beside it, in the plane's event metadata (stat
"tf_op": "jit(_ragged_decode_loop)/while/body/while/body/short_conv/
dot_general:"), and a named scope is a segment of that path.
`jax.profiler.ProfileData` does not expose event metadata, so the few
fields needed are read from the .xplane.pb's protobuf wire format directly
(tsl/profiler/protobuf/xplane.proto: XSpace.planes = 1; XPlane.name = 2,
.event_metadata = 4, .stat_metadata = 5; XEventMetadata.name = 2, .stats =
5; XStat.metadata_id = 1, .str_value = 5, .ref_value = 7;
XStatMetadata.name = 2). A fused op carries ONE of its instructions'
paths: an op fused across the scope's edge counts wholly inside or wholly
outside. None where no op of the trace lies under the scope.

args: {"scope": "short_conv"}. Percent.
"""

from __future__ import annotations

import gzip
from typing import Dict, Iterator, Tuple

from benchmark import trace_reduce

PATH_STAT = "tf_op"


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message; a length-delimited
    value is a memoryview-free bytes slice, the others ints."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield field, wire, value


def _map_entry(buf: bytes) -> Tuple[int, bytes]:
    key, value = 0, b""
    for field, _, v in _fields(buf):
        if field == 1:
            key = v
        elif field == 2:
            value = v
    return key, value


def op_paths(path: str) -> Dict[str, str]:
    """{an op event's name (its whole HLO text): its name path} over the
    device planes. Keyed by the whole text: two programs of one trace each
    have a "fusion.12", and they differ in their operands."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        space = f.read()
    out: Dict[str, str] = {}
    for field, _, plane in _fields(space):
        if field != 1:
            continue
        name, events, stats = "", [], {}
        for pf, _, v in _fields(plane):
            if pf == 2:
                name = v.decode()
            elif pf == 4:
                events.append(_map_entry(v)[1])
            elif pf == 5:
                sid, meta = _map_entry(v)
                stats[sid] = next((x.decode() for f2, _, x in _fields(meta)
                                   if f2 == 2), "")
        if not trace_reduce.DEVICE_PLANE.match(name):
            continue
        for meta in events:
            ev_name, where = "", None
            for ef, _, v in _fields(meta):
                if ef == 2:
                    ev_name = v.decode()
                elif ef == 5:
                    stat = {sf: sv for sf, _, sv in _fields(v)}
                    if stats.get(stat.get(1)) == PATH_STAT:
                        where = stat[5].decode() if 5 in stat \
                            else stats.get(stat.get(7), "")
            if where:
                out[ev_name] = where
    return out


def read(data, args):
    tr = data.get("trace_summary")
    span = data.get("trace") or {}
    path = span.get("dir") and trace_reduce.find_xplane(span["dir"])
    if tr is None or not tr.busy_s or not path:
        return None
    segment = "/" + args["scope"] + "/"
    inside = {text for text, where in op_paths(path).items()
              if segment in where}
    seconds, chips = 0.0, 0
    for pname, lines in trace_reduce.read_planes(path).items():
        ops = [e for ln, evs in lines.items()
               if trace_reduce.OP_LINE.match(ln) for e in evs]
        if not trace_reduce.DEVICE_PLANE.match(pname) or not ops:
            continue
        chips += 1
        self_s, _ = trace_reduce._self_times(ops)
        seconds += sum(sec for text, sec in self_s.items() if text in inside)
    if not seconds:
        return None
    data.setdefault("notes", {})[f"scope_{args['scope']}_ops"] = len(inside)
    return 100.0 * seconds / chips / tr.busy_s
