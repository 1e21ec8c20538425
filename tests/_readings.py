"""A `per_layer` entry of BENCHMARK.json, found by what it READS for a
cell (benchmark/selftest.py:_reading_of: the reader, its arguments, the
end-to-end metric the number moves), never by its name, its place in the
list or the exact list of its cells: a `benchmark` PR may rename, fold and
append entries, and a later cell joins a list, without an edit here.

Not a test file. READINGS is the tests' own word for each reading several
files ask for: the reader and the arguments that tell it from the others
(an argument given as None is one the data file does not have).
"""

import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.selftest import _bench, _reading_of  # noqa: E402

_PREP = ["engine.admit", "engine.pack", "engine.h2d", "engine.dispatch",
         "engine.readback:launch"]
_POST = ["engine.readback:drain", "engine.book", "engine.metrics",
         "serve.publish"]
_HOST_CLOCKS = ["wall_ns_admit", "wall_ns_pack", "wall_ns_h2d",
                "wall_ns_dispatch", "wall_ns_book", "wall_ns_metrics",
                "wall_ns_other", "wall_ns_publish"]
READINGS = {
    "decode_step_ms": ("trace_module", {"per": "engine.decode_chunk"}),
    "mixed_step_ms": ("trace_module", {"per": None}),
    "mixed_step_time_pct": ("trace_module_share", {}),
    "device_idle_pct": ("trace_idle", {}),
    "engine_host_gap_ms": ("host_gaps", {"quantity": "gap_ms"}),
    "idle_prep_pct": ("host_gaps", {"quantity": "share_pct",
                                    "spans": _PREP}),
    "idle_post_pct": ("host_gaps", {"quantity": "share_pct",
                                    "spans": _POST}),
    "idle_attributed_pct": ("host_gaps", {"quantity": "attributed_pct"}),
    "trace_clock_skew_ms": ("host_gaps", {"quantity": "skew_ms"}),
    "paged_attn_time_pct": ("trace_share",
                            {"patterns": ["^_ragged_attention_pallas"]}),
    "window_attn_time_pct": ("trace_share",
                             {"patterns": ["^ragged_window_kernel"]}),
    "moe_ffn_time_pct": ("trace_share",
                         {"patterns": ["^_moe_experts_pallas"]}),
    "attn_window_proj_time_pct": ("trace_scope",
                                  {"scope": "attn_window_proj"}),
    "moe_shared_time_pct": ("trace_scope", {"scope": "moe_shared"}),
    "mla_proj_time_pct": ("trace_scope", {"scope": "mla_proj"}),
    "attn_gate_time_pct": ("trace_scope", {"scope": "attn_gate"}),
    "lm_head_time_pct": ("trace_scope", {"scope": "lm_head"}),
    "window_pages_held_pct": ("engine_clocks",
                              {"num": ["page_steps_window"]}),
    "moe_absent_pct": ("engine_clocks", {"num": ["moe_absent"]}),
    "batch_occupancy_pct": ("engine_stats", {"num": ["decode_tokens"],
                                             "den": ["decode_steps"]}),
    "replica_ready_s": ("timing", {"key": "replica_ready_s"}),
    "engine_host_ms": ("engine_clocks", {"num": _HOST_CLOCKS}),
    "chunk_rows_joined_pct": ("engine_clocks",
                              {"num": ["chunk_rows_joined"]}),
    "chunk_tokens_a_step": ("engine_stats", {"num": ["prefill_tokens"],
                                             "den": ["ragged_dispatches"]}),
    "mixed_small_shape_pct": ("engine_clocks",
                              {"num": ["ragged_small_dispatches"]}),
    "engine_h2d_ms": ("engine_clocks", {"num": ["wall_ns_h2d"]}),
    "h2d_arrays_a_dispatch": ("engine_clocks", {"num": ["h2d_arrays"]}),
    "tpot_mixed_stall_pct": ("request_log",
                             {"field": "mixed_stall_share"}),
    "queue_wait_in_flight_p50_ms": ("request_log",
                                    {"field": "wait_in_flight"})}


def entry(reading, cell: str, **args):
    """(the entry, its data file's arguments, its reader's module): the
    ONE per_layer entry that reads ``reading`` (a word of READINGS, or a
    reader's name with ``args``) with ``cell`` in its workloads. One entry
    a reading: two that read the same for one cell fail here."""
    reader, told = READINGS.get(reading, (reading, {}))
    told = {**told, **args}
    found = []
    for m in _bench()["per_layer"]:
        got, has, _ = _reading_of(m)
        has = json.loads(has)
        if got == reader and cell in m["workloads"] \
                and all(has.get(k) == v for k, v in told.items()):
            found.append((m, has))
    assert len(found) == 1, (reading, cell, [m["name"] for m, _ in found])
    return (*found[0], importlib.import_module("benchmark.readers." + reader))
