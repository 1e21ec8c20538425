"""LLM serving bench: TTFT + decode throughput on the real chip.

Prints one JSON line per metric, each naming the device it ran on — on a
TPU only: with no TPU it raises before measuring anything (non-zero exit,
no result line). Single process on the chip (the engine runs in-process).

Model: ~202M-param Llama-shaped config (single v5e chip; the 8B config
needs more HBM than one lite chip after KV pages). Prompt 128 tokens,
batch 8 continuous decode.

Prefix caching is ON (the engine default): COLD metrics therefore use
DISTINCT prompts per sample — same length (so the same compile bucket
and the same dispatch sequence as the original locked protocol), but
different content, so no sample silently rides the prefix cache. Warm
TTFT has its own metric (llm_ttft_prefix_hit).
"""

import json
import time

import jax
import jax.numpy as jnp

from ray_tpu.llm import InferenceEngine
from ray_tpu.llm.cache import make_kv_cache
from ray_tpu.models.llama import LlamaConfig


def main() -> None:
    from ray_tpu.accelerators.tpu import require_tpu_device
    from ray_tpu.util import compile_cache
    compile_cache.configure()
    dev = require_tpu_device()
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    cfg = LlamaConfig(vocab_size=32000, dim=1024, n_layers=8, n_heads=16,
                      n_kv_heads=8, ffn_dim=2816, dtype=jnp.bfloat16)
    eng = InferenceEngine(cfg, page_size=32, total_pages=1024,
                          max_batch=8, max_seq_len=512, seed=0,
                          decode_chunk=32, prefill_chunk=128)

    def mk_prompt(j: int, n: int = 128):
        """Distinct prompt per j (same length -> same bucket/programs)."""
        return [(7 * i + 3 + 131 * j) % cfg.vocab_size for i in range(n)]

    uniq = iter(range(1, 10_000))

    # --- TTFT: request arrival -> first token sampled (includes prefill).
    # LOCKED PROTOCOL (cross-run variance was ±40%, so the claim must
    # hold within ONE process): after the compile warmup, measure THREE
    # consecutive groups of 7 samples each and
    # report every group's p50. The target is met only if ALL THREE p50s
    # beat it — the headline value is the WORST of the three.
    eng.add_request(mk_prompt(0), max_new_tokens=1)
    t0 = time.perf_counter()
    eng.step()           # admit + prefill + first token
    ttft_cold = time.perf_counter() - t0   # includes compile
    while eng.has_work():
        eng.step()
    group_p50s = []
    ttft_pairs = []      # (external timer, flight-recorder TTFT) per sample
    for _group in range(3):
        samples = []
        for _ in range(7):
            t0 = time.perf_counter()
            rid = eng.add_request(mk_prompt(next(uniq)), max_new_tokens=1)
            eng.step()
            samples.append(time.perf_counter() - t0)
            while eng.has_work():
                eng.step()
            rec = eng.request_log.get(rid)
            if rec is not None and rec.ttft is not None:
                ttft_pairs.append((samples[-1], rec.ttft))
        group_p50s.append(sorted(samples)[len(samples) // 2])
    ttft = max(group_p50s)  # worst consecutive p50 carries the claim

    # --- flight-recorder TTFT must agree with the external timer: the
    # record clock starts at enqueue and stops at the dispatch readback,
    # so it reads <= the external sample by only the step's Python
    # bookkeeping. Tolerance max(5ms, 15%); disagreement means the
    # recorder's timeline is fiction and the bench dies here.
    assert ttft_pairs, "recorder produced no TTFT records"
    ttft_err = max(abs(ext - rec) for ext, rec in ttft_pairs)
    for ext, rec in ttft_pairs:
        tol = max(0.005, 0.15 * ext)
        assert abs(ext - rec) <= tol, \
            f"record TTFT {rec * 1e3:.2f}ms vs timer {ext * 1e3:.2f}ms " \
            f"(tolerance {tol * 1e3:.2f}ms)"

    # --- TTFT with a prefix-cache hit: a 96-token shared system prefix
    # (3 full 32-token pages, page-aligned) + a distinct 32-token tail
    # per request. After one cold request publishes the prefix pages,
    # each hit only prefills its 32-token tail through the chunk program
    # (attending to the cached pages). Same arrival->first-token clock
    # as the locked cold protocol; p50 of 7.
    system_prefix = [(11 * i + 5) % cfg.vocab_size for i in range(96)]

    def mk_hit_prompt(j: int):
        return system_prefix + [(13 * i + 7 + 97 * j) % cfg.vocab_size
                                for i in range(32)]

    eng.add_request(mk_hit_prompt(0), max_new_tokens=1)  # publish prefix
    while eng.has_work():
        eng.step()
    eng.add_request(mk_hit_prompt(1), max_new_tokens=1)  # warm chunk jit
    while eng.has_work():
        eng.step()
    hit_samples = []
    for j in range(2, 9):
        t0 = time.perf_counter()
        eng.add_request(mk_hit_prompt(j), max_new_tokens=1)
        eng.step()
        hit_samples.append(time.perf_counter() - t0)
        while eng.has_work():
            eng.step()
    ttft_hit = sorted(hit_samples)[len(hit_samples) // 2]
    hit_cached = eng.stats["cached_tokens"]

    # --- TTFT under queue depth: 8 prompts arrive AT ONCE; per-request
    # TTFT = its own first-token time minus the shared arrival instant
    # (max_new_tokens=1 makes finish time == first-token time). The
    # ragged step packs up to prefill_rows prompts per dispatch, so the
    # burst drains in ceil(8 / prefill_rows) dispatches of the SAME
    # program the solo protocol warmed.
    for _ in range(8):
        eng.add_request(mk_prompt(next(uniq)), max_new_tokens=1)
    while eng.has_work():
        eng.step()
    qd_samples = []
    for _ in range(3):
        t0 = time.perf_counter()
        pending = {eng.add_request(mk_prompt(next(uniq)), max_new_tokens=1)
                   for _ in range(8)}
        ttfts = []
        while pending:
            done = eng.step()
            now = time.perf_counter()
            for rid in done:
                if rid in pending:
                    pending.discard(rid)
                    ttfts.append(now - t0)
        qd_samples.append(sum(ttfts) / len(ttfts))
    ttft_q = sorted(qd_samples)[len(qd_samples) // 2]

    # --- steady-state decode throughput at full batch (256 new tokens =
    # 8 decode chunks; the burst admits in ONE step now, so warm 2 steps
    # and measure the remaining 6 — warming 4 of 4 chunks measured zero)
    decode_rids = [eng.add_request(mk_prompt(next(uniq)),
                                   max_new_tokens=256) for _ in range(8)]
    # warm the decode program + fill the batch
    for _ in range(2):
        eng.step()
    steps0, toks0 = eng.stats["decode_steps"], eng.stats["decode_tokens"]
    t0 = time.perf_counter()
    while eng.has_work():
        eng.step()
    dt = time.perf_counter() - t0
    toks = eng.stats["decode_tokens"] - toks0
    steps = eng.stats["decode_steps"] - steps0

    # --- record-derived serving latencies for the batch-8 decoders:
    # TTFT/TPOT straight off the flight-recorder records, ITL from the
    # per-dispatch decode entries (delta_ts / tokens-in-dispatch — the
    # honest per-token latency at decode_chunk granularity)
    def pct(xs, q):
        xs = sorted(xs)
        return xs[min(len(xs) - 1, int(q * len(xs)))] if xs else None

    drecs = [eng.request_log.get(r) for r in decode_rids]
    drecs = [r for r in drecs if r is not None and r.done]
    rec_ttfts = [r.ttft for r in drecs if r.ttft is not None]
    rec_tpots = [r.tpot for r in drecs if r.tpot is not None]
    itls = [e_dt / e_n for r in drecs
            for e_dt, e_n in r.decode_entries() if e_n]

    # --- decode throughput WHILE long prompts chunk-prefill into the
    # free slots: 6 decoders (prompt 128, 256 new tokens) run while
    # 384-token prompts (3 chunks of prefill_chunk=128 each) stream
    # through the 2 remaining slots — the chunked scheduler interleaves
    # them instead of stalling the batch for whole prefills. Reported:
    # decode tokens/s over the mixed window (compare llm_decode_throughput
    # for the interference cost).
    def mk_long(j: int):
        return [(17 * i + 9 + 103 * j) % cfg.vocab_size for i in range(384)]

    eng.add_request(mk_long(0), max_new_tokens=1)   # warm the chunk jit
    while eng.has_work():
        eng.step()
    decoders = {eng.add_request(mk_prompt(next(uniq)), max_new_tokens=256)
                for _ in range(6)}
    for _ in range(2):
        eng.step()                                  # warm + fill batch
    fed, n_longs = 1, 8
    t0 = time.perf_counter()
    d0, p0 = eng.stats["decode_tokens"], eng.stats["prefill_tokens"]
    done: set = set()
    while not decoders <= done:
        if fed < n_longs and len(eng.waiting) + len(eng._chunking) < 2:
            eng.add_request(mk_long(fed), max_new_tokens=1)
            fed += 1
        done.update(eng.step())
    dt_mix = time.perf_counter() - t0
    mix_decode = (eng.stats["decode_tokens"] - d0) / dt_mix
    mix_prefill = (eng.stats["prefill_tokens"] - p0) / dt_mix

    # --- compile-count / dispatch / padding accounting over the WHOLE
    # run above (every protocol: cold, hit, queued, steady, mixed) —
    # the one-ragged-program contract means the totals stay flat no
    # matter how the workloads above mixed lengths and occupancies.
    programs = eng.compiled_step_programs()
    # tracker ground truth (util/compile_tracker.py wraps the engine's
    # step fns): the independently measured compile count must agree
    # with the jit-cache count the O(1) invariant asserts — the bench
    # reports both so a silent divergence (compiles happening outside
    # the wrapped seam, or a program zoo the cache count misses) shows
    # up as meets_target: false here
    from ray_tpu.util import compile_tracker
    _tr = compile_tracker.get_global()
    tracker_compiles = -1
    if _tr is not None:
        tracker_compiles = sum(
            (_tr.callable_stats(n) or {}).get("compiles", 0)
            for n in ("llm.ragged_step", "llm.decode_loop",
                      "llm.copy_page"))
    dispatches = (eng.stats["ragged_dispatches"]
                  + eng.stats["decode_dispatches"]
                  + eng.stats["cow_copies"])
    per_step = dispatches / max(eng.stats["steps"], 1)
    pad_waste = 1.0 - (eng.stats["ragged_real_tokens"]
                       / max(eng.stats["ragged_slot_tokens"], 1))

    # --- int8 KV capacity: how many MORE pages (= concurrent sequences
    # at fixed sequence length) fit in the same HBM bytes when pages
    # are int8 + bf16 per-(token,head) scales instead of bf16.
    kv_fp = make_kv_cache(cfg, total_pages=8, page_size=32)
    kv_q8 = make_kv_cache(cfg, total_pages=8, page_size=32,
                          kv_dtype="int8")
    cap_ratio = (sum(x.nbytes for x in kv_fp.values())
                 / sum(x.nbytes for x in kv_q8.values()))

    # --- recorder overhead: the same decode protocol (8 prompts, 64 new
    # tokens) on two fresh engines SHARING eng's params and warm jit
    # caches, recorder on vs off. Plus the recorder's raw per-event cost
    # (one note_decode), which bounds what the engine loop can ever pay.
    def timed_run(recorder_on: bool) -> float:
        e = InferenceEngine(cfg, eng.params, page_size=32,
                            total_pages=1024, max_batch=8,
                            max_seq_len=512, decode_chunk=32,
                            prefill_chunk=128,
                            request_log=recorder_on)
        for _ in range(8):
            e.add_request(mk_prompt(next(uniq)), max_new_tokens=64)
        e.step()                       # admit + burst prefill
        t0 = time.perf_counter()
        while e.has_work():
            e.step()
        return time.perf_counter() - t0

    t_off = timed_run(False)
    t_on = timed_run(True)
    overhead = t_on / t_off - 1.0

    from ray_tpu.llm.request_log import RequestRecord
    probe_rec = RequestRecord("probe", 1, 1 << 20)
    t0 = time.perf_counter()
    for i in range(100_000):
        probe_rec.note_decode(t0 + i * 1e-6, 1)
    event_ns = (time.perf_counter() - t0) / 100_000 * 1e9

    out = [
        {"metric": "llm_ttft_p50", "value": round(ttft * 1000, 2),
         "unit": "ms", "vs_baseline": round(200.0 / (ttft * 1000), 2),
         "group_p50s_ms": [round(p * 1000, 2) for p in group_p50s],
         "meets_target": bool(all(p * 1000 < 200.0 for p in group_p50s)),
         "note": "WORST of 3 consecutive same-process p50s (7 samples "
                 "each, distinct same-length prompts so none rides the "
                 "prefix cache); 128-tok prompt prefill + argmax fused "
                 "into one program = ONE scalar readback per TTFT; 202M "
                 "model, 1 chip; baseline = 200ms north-star target"},
        {"metric": "llm_ttft_prefix_hit", "value": round(ttft_hit * 1000, 2),
         "unit": "ms", "vs_baseline": round(ttft / ttft_hit, 2),
         "meets_target": bool(ttft_hit < ttft),
         "note": "p50 of 7; 96-tok shared system prefix served from "
                 "cached KV pages + 32-tok distinct tail chunk-prefilled "
                 f"against them ({hit_cached} prompt tokens served from "
                 "cache total); baseline = cold llm_ttft_p50"},
        {"metric": "llm_ttft_queued_mean", "value": round(ttft_q * 1000, 2),
         "unit": "ms", "vs_baseline": round(200.0 / (ttft_q * 1000), 2),
         "note": "mean per-request TTFT, 8 same-bucket prompts arriving "
                 "at once; idle-batch burst admission: ONE size-8 "
                 "prefill dispatch + ONE fused group KV scatter"},
        {"metric": "llm_decode_throughput", "value": round(toks / dt, 1),
         "unit": "tokens/s",
         "vs_baseline": None,
         "note": f"batch 8 continuous decode, {steps} steps, "
                 f"{round(dt / max(steps, 1) * 1000, 2)} ms/step; "
                 "prefix cache + chunked-prefill scheduler enabled"},
        {"metric": "llm_decode_under_prefill_load",
         "value": round(mix_decode, 1), "unit": "tokens/s",
         "vs_baseline": round(mix_decode / (toks / dt), 2),
         "note": "decode tokens/s for 6 decoders while 384-tok prompts "
                 "chunk-prefill (3x128-tok chunks) through the 2 free "
                 f"slots ({round(mix_prefill, 0):.0f} prefill tok/s "
                 "alongside); baseline = unloaded llm_decode_throughput"},
        {"metric": "llm_ttft_cold_compile", "value": round(ttft_cold, 2),
         "unit": "s", "vs_baseline": None,
         "note": "first-ever request incl. XLA compile"},
        {"metric": "llm_compiled_step_programs", "value": programs,
         "unit": "programs", "vs_baseline": None,
         "meets_target": bool(programs <= 3),
         "note": "compiled step programs resident after ALL protocols "
                 "above (ragged mixed step + multi-step decode loop + "
                 "COW page copy); target <= 3 — no per-length-bucket "
                 "program zoo"},
        {"metric": "llm_tracker_compile_count", "value": tracker_compiles,
         "unit": "compiles", "vs_baseline": None,
         "meets_target": bool(tracker_compiles == programs
                              and 0 <= tracker_compiles <= 3),
         "note": "XLA compiles the compile tracker measured at the "
                 "engine's wrapped step fns over the same run — an "
                 "independent count that must equal "
                 "llm_compiled_step_programs (and stay <= 3); -1 means "
                 "the tracker was disabled"},
        {"metric": "llm_dispatches_per_step", "value": round(per_step, 3),
         "unit": "dispatches/step", "vs_baseline": None,
         "meets_target": bool(per_step <= 1.05),
         "note": f"{dispatches} device dispatches over "
                 f"{eng.stats['steps']} engine steps (ragged + decode "
                 "loops + COW copies); the ragged step serves mixed "
                 "decode+prefill in ONE dispatch"},
        {"metric": "llm_ragged_padding_waste", "value": round(pad_waste, 3),
         "unit": "fraction", "vs_baseline": None,
         "note": f"{eng.stats['ragged_real_tokens']} real of "
                 f"{eng.stats['ragged_slot_tokens']} ragged token slots "
                 "computed; padded slots attend the scratch page and are "
                 "discarded"},
        {"metric": "llm_ttft_record_agreement",
         "value": round(ttft_err * 1000, 3), "unit": "ms",
         "vs_baseline": None,
         "meets_target": True,   # asserted above: bench dies otherwise
         "note": "max |flight-recorder TTFT - external timer| over the "
                 f"{len(ttft_pairs)} locked-protocol samples; tolerance "
                 "max(5ms, 15%) enforced by assertion — the record "
                 "timeline is the timer, not an estimate"},
        {"metric": "llm_record_ttft_p50",
         "value": round((pct(rec_ttfts, 0.5) or 0.0) * 1000, 2),
         "unit": "ms", "vs_baseline": None,
         "note": "record-derived TTFT p50 of the 8 queued batch decoders "
                 f"(p99 {round((pct(rec_ttfts, 0.99) or 0.0) * 1000, 2)}"
                 "ms); includes queue wait — these arrived as one burst"},
        {"metric": "llm_record_tpot_p50",
         "value": round((pct(rec_tpots, 0.5) or 0.0) * 1000, 3),
         "unit": "ms", "vs_baseline": None,
         "note": "record-derived mean inter-token latency p50 across the "
                 "8 decoders, 256 tokens each "
                 f"(p99 {round((pct(rec_tpots, 0.99) or 0.0) * 1000, 3)}"
                 "ms); per-dispatch ITL p50 "
                 f"{round((pct(itls, 0.5) or 0.0) * 1000, 3)}ms / p99 "
                 f"{round((pct(itls, 0.99) or 0.0) * 1000, 3)}ms at "
                 "decode_chunk granularity"},
        {"metric": "llm_recorder_overhead", "value": round(overhead, 4),
         "unit": "fraction", "vs_baseline": None,
         "meets_target": bool(overhead <= 0.02),
         "note": "decode wall-time (8 reqs x 64 tok) recorder-on vs "
                 f"recorder-off, same params + warm jits; raw cost "
                 f"{event_ns:.0f}ns per note_decode event (preallocated "
                 "slots, O(1)); target <= 2% — single-run A/B, so "
                 "scheduler noise can dominate the true per-event cost"},
        {"metric": "llm_int8_kv_capacity", "value": round(cap_ratio, 2),
         "unit": "x", "vs_baseline": None,
         "meets_target": bool(cap_ratio >= 1.9),
         "note": "pages (= concurrent sequences at fixed length) per "
                 "HBM byte, kv_dtype=int8 vs bf16 at head_dim "
                 f"{cfg.head_dim}: int8 pages + bf16 per-(token,head) "
                 "scales; target >= 1.9x"},
    ]
    for line in out:
        print(json.dumps({**line, "device": device}))


if __name__ == "__main__":
    main()
