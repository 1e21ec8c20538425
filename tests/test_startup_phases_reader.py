"""benchmark/readers/startup_phases.py and the metric files that name it:
the start-up clocks a served replica writes into engine.stats, read from a
run's end probe (data["device"]["stats"], bench_probe's copy).

A file has its BENCHMARK.json entry, or carries the entry it is owed (a
`benchmark` PR copies it): this test holds the reader and either.
"""

import importlib
import json
import os

import pytest

from ray_tpu.util import startup_clocks as sc

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
METRICS = os.path.join(ROOT, "benchmark", "metrics")
S = 10 ** 9

#: a recorded probe: a replica whose phases took 2, 3, 12, 2, 0.5, 11 and
#: 0.5 s, under an outside clock (replica_ready_s) of 33.25 s
PROBE = {
    "timing": {"replica_ready_s": 33.25, "cluster_up_s": 4.4},
    "device": {"platform": "tpu", "stats": {
        "steps": 12, "decode_tokens": 99,
        "startup_ns_process": 2 * S, "startup_ns_import": 3 * S,
        "startup_ns_backend": 12 * S, "startup_ns_weights": 2 * S,
        "startup_ns_pool": S // 2, "startup_ns_programs": 11 * S,
        "startup_ns_other": S // 2,
        "startup_ns_trace_lower": 7 * S // 2,
        "startup_ns_backend_compile": 3 * S,
        "startup_programs_cold": 1}}}
#: the parent's program: the same run without the clocks; the train cell
PARENT = {"timing": {"replica_ready_s": 33.25},
          "device": {"platform": "tpu",
                     "stats": {"steps": 12, "decode_tokens": 99}}}
TRAIN = {"timing": {"cluster_up_s": 4.4},
         "device": {"platform": "tpu", "losses": [1.0]}}

WANT = {"startup_process_s": 5.0, "startup_backend_s": 12.0,
        "startup_weights_s": 2.5, "startup_programs_s": 11.0,
        "startup_other_s": 0.5, "replica_lease_poll_s": 33.25 - 31.0,
        "startup_trace_lower_s": 3.5, "startup_backend_compile_s": 3.0,
        "startup_cold_programs": 1}


def _spec(name):
    with open(os.path.join(METRICS, name + ".json")) as f:
        return json.load(f)


def test_the_files_are_the_readers_and_no_others():
    mine = sorted(
        n[:-5] for n in os.listdir(METRICS)
        if n.endswith(".json") and _spec(n[:-5])["reader"]
        == "startup_phases")
    assert mine == sorted(WANT)


@pytest.mark.parametrize("name", sorted(WANT))
def test_metric_file_reads_the_probe(name):
    spec = _spec(name)
    assert spec["name"] == name
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    assert reader.read(dict(PROBE), spec["args"]) == pytest.approx(WANT[name])
    # a program without the clocks, a cell without a replica: nothing to
    # read, and nothing raised
    assert reader.read(PARENT, spec["args"]) is None
    assert reader.read(TRAIN, spec["args"]) is None
    assert reader.read({}, spec["args"]) is None
    # every key it reads is one the program writes
    assert set(spec["args"]["keys"]) <= set(sc.SERVE_KEYS)


def test_every_key_the_program_writes_has_a_file_that_reads_it():
    read = set()
    for name in WANT:
        read.update(_spec(name)["args"]["keys"])
    assert read == set(sc.SERVE_KEYS)


@pytest.mark.parametrize("name", sorted(WANT))
def test_owed_entry_is_a_benchmark_entry(name):
    """The start-up entry is in per_layer, or its data file carries the
    one a benchmark PR can copy (`owed_entry`): the accepted entries' keys,
    an accepted layer, moves setup_s, and the serve cells as they stood
    when it was written: a PREFIX of the list of the entry that reads
    replica_ready_s (a cell accepted since joined that list; the PR that
    copies the entry appends it)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    ready = next(m for m in bench["per_layer"] if _spec(m["name"]) == _spec(
        "replica_ready_s"))
    spec = _spec(name)
    accepted = [m for m in bench["per_layer"]
                if (_spec(m["name"])["reader"], _spec(m["name"])["args"])
                == (spec["reader"], spec["args"])]
    assert len(accepted) + ("owed_entry" in spec) == 1
    entry = accepted[0] if accepted else spec["owed_entry"]
    assert set(entry) == set(ready) and entry["moves"] == "setup_s"
    assert entry["workloads"] == ready["workloads"][:len(entry["workloads"])]
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"]}
    assert entry["better"] == "lower" and entry["source"] in (
        "program_counter", "host_clock")


def test_the_phases_and_the_remainder_tile_the_outside_clock():
    """The five phase metrics sum to the replica's interval, and with the
    remainder to replica_ready_s: the outside clock these tile."""
    reader = importlib.import_module("benchmark.readers.startup_phases")
    inside = sum(reader.read(PROBE, _spec(n)["args"]) for n in (
        "startup_process_s", "startup_backend_s", "startup_weights_s",
        "startup_programs_s", "startup_other_s"))
    assert inside == pytest.approx(31.0)
    assert inside + reader.read(
        PROBE, _spec("replica_lease_poll_s")["args"]) == pytest.approx(
            PROBE["timing"]["replica_ready_s"])
