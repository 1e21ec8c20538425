"""A counter of the Phi-4-mini-flash block as the engine states it once
(InferenceEngine.stats, set by the constructor and never moved): what a
batch slot owns of recurrent state, what a token costs in the ONE layer of
the full page group, how many layers read that layer's pages. Read from the
end probe's copy of the stats (benchmark/replica.py:bench_probe), as
readers/startup_phases.py reads its keys. None where the program has no
such key (a commit before the block was served).

args: {"key": "state_bytes_per_slot" | "kv_token_layer_bytes" |
       "shared_kv_readers"}
"""


def read(data, args):
    stats = (data.get("device") or {}).get("stats") or {}
    return stats.get(args["key"])
