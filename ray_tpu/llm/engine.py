"""InferenceEngine — continuous batching over the paged KV cache.

Role-equivalent to the reference's vLLM engine integration (reference:
llm/_internal/serve/deployments/llm/vllm/vllm_engine.py — engine loop,
admission, scheduling), rebuilt TPU-first around ONE ragged step:

  - RAGGED SINGLE-DISPATCH STEP: every scheduler step packs the decode
    batch (one token per running sequence) and up to prefill_rows
    prefill CHUNKS (bounded by the step token budget; one row a
    prefilling sequence first, rows still free then to the NEXT chunks
    of those sequences: _deal_chunk_rows) into one ragged token batch
    and runs ONE compiled program
    (model._ragged_step_body over ops.ragged_paged_attention), in the
    SMALLEST of its few static shapes that holds the rows dealt: chunk
    rows 1, 2, 4, ... below prefill_rows and prefill_rows itself
    (model.chunk_row_shapes: {1, 2} at the default 2), so a lone
    one-chunk prompt does not pay for a second, empty row of
    prefill_chunk slots. The old engine compiled a
    per-length-bucket zoo — |len buckets| x |size buckets| prefill
    programs plus a chunk program per chunk length plus a separate
    decode program; this engine compiles O(1) programs total (the mixed
    step once a shape, decode loop, COW page copy — asserted <= 2 + the
    number of shapes, StepPrograms.program_budget), a served replica
    compiles them all before it takes a request (load_step_programs),
    and XLA never recompiles as sequences join, leave, or chunk (shape
    change outside that set is the cardinal sin of TPU serving loops);
  - pure-decode steps (no prefill work pending) run the multi-step
    decode loop instead: decode_chunk ragged steps scanned in ONE
    program with a single [K, B] readback, so steady-state decode pays
    one host round trip per K tokens;
  - PREFIX CACHE: full prompt KV pages publish into a hash-indexed
    table (llm/cache.py PrefixCache, keyed by the KV storage scheme so
    fp16 and int8 pages never cross-match) — a new request whose prompt
    shares a page-aligned prefix maps those pages read-only
    (copy-on-write when the tail must write into a shared page) and
    only prefills the tail;
  - CHUNKED PREFILL: every prompt computes in prefill_chunk-bounded
    chunks riding the mixed step under the per-step token budget —
    decode-priority scheduling, so one 2k-token prompt never stalls the
    running batch behind a monolithic prefill dispatch. The step costs
    the same with one chunk row filled or all of them, so a sequence
    that prefills with rows to spare computes several of its chunks in
    ONE step, each a row of its own over the same page table (the step
    writes every row's K/V before it attends, so a later row reads the
    earlier row's tokens from the pool as it would a step later); not
    with conv, state-space or retention layers, whose chunk rows start
    from the slot's state as the LAST step left it;
  - INT8 KV (kv_dtype="int8"): pages store int8 with bf16
    per-(token, head) scales carried in the same kv pytree — ~1.9x the
    concurrent sequences per HBM byte, quantize-on-write in the step
    program, dequantize inside the attention kernel;
  - pages allocate refcounted with decode headroom; under allocator
    pressure the engine LRU-evicts unreferenced cached pages;
  - A SECOND PAGE GROUP where some layers see a window only (llm/cache.py):
    a sequence owns pages of both groups; the window group's are taken as
    a dispatch's tokens need them (_extend_window) and given
    back behind the window when the dispatch is booked (_trim_window,
    inside engine.book), so a sequence holds a constant of them however
    long its context; its rows' page tables are COMPACT (a base and a few
    entries: the descriptor does not grow with the context for the group
    that does not); stats window_pages_freed, page_steps_full /
    page_steps_window, each group's pages in use added at every dispatch,
    and rows_inside_window, the decode row-steps of sequences no longer
    than the window;
    no prefix cache there; preemption stays recompute from 0; where
    layers without pages of their own read ONE layer's (cross attention:
    Phi-4-mini-flash, the first configuration with state a slot, a window
    group and a full group at once), stats kv_token_layer_bytes and
    shared_kv_readers say what a token costs there and how many read it;
    where the layers run several times (a looped stack: Ouro-2.6B), each
    pass on page planes of its own, stats kv_planes and kv_token_bytes
    say how many planes a token's pages span and what it costs over all;
  - ONE DESCRIPTOR a dispatch: every integer a program takes (tokens,
    positions, pages, the rows' spans, the page table) is a field of one
    flat int32 buffer kept on the host a program shape (_descriptor_turns, in
    the seam's layout: model.step_layout / decode_layout), filled in
    place by array operations over all decode rows at once, and sent in
    ONE host-to-device transfer (stats h2d_arrays: 1 a dispatch); the
    program cuts it at static offsets;
  - ONE PROGRAM AHEAD: the engine keeps one program queued behind the one
    that is running. What a dispatch does to the engine's STRUCTURES is
    known when it is packed (every decode row advances by 1 or
    decode_chunk positions, a chunk row by its chunk, a row whose
    max_new_tokens runs out ends there, a prompt whose last chunk is in
    it becomes a decode row: _take_off sets all that at the launch); only
    the tokens' VALUES, and whether one is EOS, wait for the readback
    (_book). So a step packs, sends and launches program N+1 from the
    structures as N will leave them WHILE N runs, then reads N back and
    books it; each decode row's newest token reaches N+1 on the device
    (the step programs hand every slot's newest token from one to the
    next: model._newest_from; the descriptor says -1 for a token the
    host has not read). What was predicted and turns out otherwise is
    retired late, never computed wrong: a row found to have stopped on
    EOS when N is booked is delivered then, its tokens from N+1 are
    dropped and its slot and pages released when N+1 is booked (stats
    late_retired_rows); what the engine cannot do with a program in
    flight (preempt: it folds tokens it does not have yet; a window
    group without a spare page for the look-ahead) waits until that
    program is booked (stats ahead_drains). One launch is made LATE,
    not at once: with a slot free and nobody waiting, a decode loop
    queued at once would make a request that arrives meanwhile wait a
    whole loop for a slot it could have had, so the engine holds the
    launch back until the program in flight is DUE (its expected end
    less the host's own stretch in front of a launch, both from the
    engine's clocks: _clock, step), sleeps until then, admits whoever
    came, and launches the mixed step or the loop so that it lands on
    the device as the flight ends. An arrival misses that program only
    if it comes inside the stretch, as it always did; the stretch itself
    runs under the device (stats held_launches, late_launches,
    late_mixed_launches). stats ahead_dispatches counts the dispatches
    launched with a program unbooked. The engine decides all of it from
    its own state and clocks: no setting selects it.

A step is admit -> pack -> h2d -> dispatch (of the NEXT program) ->
after_dispatch -> readback -> book (of the program that was in flight; of
the one just launched where nothing may be queued behind it, which is the
old synchronous order: dispatch -> readback -> book -> next step). A step
that holds its launch back is admit -> after_dispatch -> hold -> admit ->
pack -> h2d -> dispatch -> readback -> book. With a program queued the
host's stretch between two dispatches runs under the device; where the
order is synchronous it is device idle, as it was.
Each phase of a step goes through ONE helper (PhaseClocks.phase), which
does two things:

  - it opens a jax.profiler.TraceAnnotation, written into the profiler's
    own trace beside the device's events (a flag test when no trace runs):
    engine.step {kind, dispatch, decode_rows, real_tokens, slot_tokens}
    and, partitioning it, engine.admit {admitted}, engine.pack,
    engine.h2d, engine.dispatch, engine.readback {a sparse model's
    routing counters}, engine.book, engine.metrics, each at most once a
    step and in that order (kind, dispatch and the counts are the program
    the step BOOKED; `launched` the kind it launched, `ahead` whether the
    booked one had been launched behind another; pack .. dispatch and
    readback, book belong to different programs where the engine runs
    ahead), but for a step that HELD its launch back (`held`): it sleeps
    in engine.hold until the flight is due and opens engine.admit a
    second time after it (`late`: it then launched behind the flight
    still running; `end_late_us`: by how much the flight outran what was
    expected of it, where its readback marked the end); the serve loop adds
    serve.wait between steps and serve.publish {streams}, which it runs
    from step()'s after_dispatch hook: inside engine.step, between
    engine.dispatch and engine.readback, with a program on the device
    (llm/serve_llm.py). The names are read by
    benchmark/readers/host_gaps.py (PERF.md lists them): renaming one, or
    moving where it opens and closes, changes a metric;
  - it clocks the phase, always: time.perf_counter_ns at both ends, the
    difference added to engine.stats as wall_ns_<phase> for admit, pack,
    h2d, dispatch, readback, book, metrics, publish, wait, and `other` =
    what of engine.step no child covers, so a plain sum of the ten keys
    is the engine thread's time (engine.hold's goes to `wait`: time the
    thread sleeps by design with nothing to do for the device, like
    serve.wait, and no part of the host's work a dispatch). And the
    thread's own CPU time
    (time.thread_time_ns) as ONE counter, cpu_ns_host: what the thread
    ran outside engine.readback, engine.hold and serve.wait, which sleep
    by design. It is read at the two ends of those spans only (two reads
    a dispatch, two more a hold: the clock is a system call of 5.6 us on
    the chip machine's host). wall - cpu is the time the thread was NOT running: waiting for
    the interpreter, descheduled, or blocked in a call that sleeps. When
    a trace runs, and only then, every span also reads the CPU clock at
    both ends and carries cpu_us (on a host whose kernel counts thread
    CPU in scheduler ticks a span's cpu_us is 0 or a whole tick, 10 ms:
    right in a sum over many spans, wrong in each). Read by
    benchmark/metrics/engine_host_ms, engine_offcpu_pct,
    engine_device_wait_pct (counters, whole window), idle_offcpu_pct
    (spans), and by the llm_engine_device_wait_ratio gauge. Only the
    thread that steps the engine writes them: no lock.

The stream lanes (one actor lane thread a streaming request) write
stream.deliver {tokens} for the time they are awake with an item
(llm/serve_llm.py:LLMServer.stream): NOT an engine.* / serve.* name, those
are one thread's properly nested sequence to host_gaps.py.
"""

from __future__ import annotations

import collections
import itertools
import logging
import threading
import time
import uuid
from typing import Callable, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.llm.cache import (SCRATCH_PAGE, STATE_LEAVES, WINDOW_LEAVES,
                               PageAllocator, PrefixCache, SequenceState,
                               kv_cache_tag, page_planes,
                               prefix_cache_supported, slot_state_kinds,
                               window_first_page, window_group_pages)
from ray_tpu.llm import model as M
from ray_tpu.llm.tp import build_tp_mesh
from ray_tpu.models.llama import ATTENTION, CROSS, LlamaConfig
from ray_tpu.util import startup_clocks

TraceAnnotation = jax.profiler.TraceAnnotation
logger = logging.getLogger(__name__)

#: the phases of the engine thread, in the order a step meets them; the
#: counters wall_ns_<phase> of engine.stats (`other`: engine.step's own
#: time; publish, wait: the serve loop's, publish mostly inside a step)
PHASES = ("admit", "pack", "h2d", "dispatch", "readback", "book",
          "metrics", "other", "publish", "wait")
WALL_KEYS = tuple("wall_ns_" + p for p in PHASES)
#: the thread's CPU time outside the phases that sleep by design
CPU_KEY = "cpu_ns_host"
_SLEEPS = ("engine.readback", "engine.hold", "serve.wait")
#: span name -> its wall counter; engine.step books what its children
#: left of it, and engine.hold (a step asleep until the program in flight
#: is due: step) is time the thread waits by design, like serve.wait
_SPAN_OF = {"other": "engine.step", "publish": "serve.publish",
            "wait": "serve.wait"}
_PHASE_KEY = {_SPAN_OF.get(p, "engine." + p): "wall_ns_" + p
              for p in PHASES}
_PHASE_KEY["engine.hold"] = "wall_ns_wait"


# the two clocks, as module names: the helper runs ten times a dispatch
# and a global is the cheapest thing to call (tests script them here)
_wall_ns = time.perf_counter_ns
_cpu_ns = time.thread_time_ns
_thread = threading.get_ident
_sleep = time.sleep
_span_enter = TraceAnnotation.__enter__
_span_exit = TraceAnnotation.__exit__


class PhaseClocks:
    """The engine thread's time by phase (module docstring).
    ``phase(name)`` is the one way a span of the host loop is opened; the
    counters live in ``stats`` (the engine's), so whoever copies that dict
    has them."""

    def __init__(self, stats: Dict[str, int]):
        self.stats = stats
        # wall of every phase closed so far: engine.step takes what was
        # added while it was open off its own
        self.child_wall = 0
        # (thread, its CPU clock) when a sleeping phase last ended: the
        # CPU up to the next one's start is the host's
        self.cpu_mark = (None, 0)
        stats.update(dict.fromkeys(WALL_KEYS + (CPU_KEY,), 0))

    def phase(self, name: str) -> "_Phase":
        p = _Phase(name)
        p.clocks = self
        p.wall_key = _PHASE_KEY[name]
        p.sleeps = name in _SLEEPS
        return p


class _Phase(TraceAnnotation):
    """One phase in hand: the span it is, and its clocks. The wall clock
    is read at both ends of every phase (``wall0``, ``wall1``: a launch's
    stretch and a program's two ends are read off them, step and _clock);
    the thread's CPU clock only at
    the ends of those that sleep (what ran between two of them is
    cpu_ns_host) and, while a trace runs, at both ends of every span for
    its cpu_us. Both clocks are read in the same order at both ends and
    inside the span: cpu_us <= its duration up to the clocks'
    granularity."""

    __slots__ = ("clocks", "wall_key", "sleeps", "wall0", "wall1", "cpu0",
                 "child_wall0")

    def __enter__(self) -> "_Phase":
        _span_enter(self)
        c = self.clocks
        self.child_wall0 = c.child_wall
        self.wall0 = _wall_ns()
        if self.sleeps:
            self.cpu0 = cpu = _cpu_ns()
            thread, mark = c.cpu_mark
            if thread == _thread():
                c.stats[CPU_KEY] += cpu - mark
        else:
            self.cpu0 = _cpu_ns() if self.is_enabled() else None
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.wall1 = _wall_ns()
        wall = self.wall1 - self.wall0
        c = self.clocks
        if self.cpu0 is not None:
            cpu = _cpu_ns()
            if self.sleeps:
                c.cpu_mark = (_thread(), cpu)
            if self.is_enabled():
                self.set_metadata(cpu_us=(cpu - self.cpu0) / 1e3)
        _span_exit(self, exc_type, exc, tb)
        if self.wall_key == "wall_ns_other":
            wall -= c.child_wall - self.child_wall0
        else:
            c.child_wall += wall
        c.stats[self.wall_key] += wall


def _descriptor_turns(layout: M.Layout):
    """One program shape's integer inputs on the host, for ``next()``:
    (a flat int32 buffer in the seam's ``layout``, the numpy views of its
    fields, cut once, a list for the filler's notes on what it left in
    the buffer), TWO of them taking turns for ever. ``device_put`` may
    alias a host buffer instead of copying it (the CPU backend does) or
    still read it as it returns, so a buffer is left alone from its fill
    until the program launched with it has been read back. Two are as
    many as that can be at once: the engine fills a buffer with at most
    ONE program in flight (the one launched before), and books that one
    before it fills again, so the buffer whose turn comes belongs to a
    program that has been read back. What a buffer holds when its turn
    comes is its last fill."""
    size = M.layout_size(layout)
    return itertools.cycle([(buf, M.cut(buf, layout), []) for buf in (
        np.zeros(size, np.int32), np.zeros(size, np.int32))])


class _Flight:
    """A program launched and not booked yet: what its booking needs of
    the dispatch as it was packed (_take_off), its tokens, still on the
    device, and the two ends of its run there on the host's wall clock."""

    __slots__ = ("kind", "rows", "n_rows", "ahead", "active", "out",
                 "rows_joined", "pages_in_use", "inside_window", "began",
                 "ended", "blocked")

    def __init__(self, kind: str, rows, n_rows: int, ahead: bool):
        self.kind = kind            # "mixed" | "decode"
        self.rows = rows            # a mixed step's chunk rows
        self.n_rows = n_rows        # ... and the shape it ran in
        #: launched with the program before it still unbooked
        self.ahead = ahead
        #: [(slot, seq, tokens of this program it takes)], decode rows
        self.active: List[Tuple[int, SequenceState, int]] = []
        self.out = None             # tokens (+ counters): a device array
        self.rows_joined = 0
        self.pages_in_use = (0, 0)  # full group, window group
        self.inside_window = 0
        #: when it began to run: its dispatch's end or, launched ahead,
        #: the end of the program before it (set at THAT one's booking)
        self.began = 0
        #: its readback's end, and whether that readback BLOCKED (the
        #: program was still running when it started): `ended` is then
        #: the program's end, else only a time by which it had ended
        self.ended = 0
        self.blocked = False


def _lower_quartile(runs) -> int:
    return sorted(runs)[len(runs) // 4]


def _landed(flight: _Flight) -> bool:
    """Whether ``flight``'s program has ended on the device: its tokens
    can be read without blocking (a module name: the tests script it)."""
    return flight.out.is_ready()


#: THE LATE DECISION'S THREE CONSTANTS (InferenceEngine.step), each with
#: the measurement that chose it (my chip runs, PR 61, four traced runs of
#: reason-lfm2-1chip, every booked program's host-clocked run beside its
#: device time in the trace).
#:
#: A program's time on the DEVICE is a constant of its kind and shape:
#: decode loops 132.5-134.3 ms, one-row mixed steps 21.2-22.3, two-row
#: ones 25.4-26.1, whatever the rows hold. What the HOST clocks of it
#: (_clock: from one blocked readback's end to the next) is that plus the
#: difference of two wake-ups, each late by however long the thread
#: waited for the interpreter: medians within -3.1 .. +3.3 ms of the
#: device's, but single readings off by -52 .. +145 ms, a few in a
#: hundred. So the estimate is taken over the newest _RUNS_KEPT runs and
#: is their LOWER QUARTILE (_expected_end_ns): two wild readings on
#: either side do not move it, and it leans early, which is the cheap
#: side (a launch that lands early is queued and takes a few ms off the
#: window an arrival can still be admitted in; one that lands late
#: leaves the chip idle).
_RUNS_KEPT = 8
#: What the due time keeps clear of the flight's expected end besides
#: the stretch: the readback that marked the flight's beginning woke late
#: by 0.3-3.3 ms in the median where the stream lanes were at work (the
#: mixed steps, booked 10-20 ms after a hand-over), and the flight's end
#: is expected later by as much; at 1.5 ms the flights that landed
#: before their launch (6-10 in 8 s) were mixed steps, all but one or
#: two. A readback that still blocks for about the margin is also what
#: marks the flight's end for the next estimate.
_HOLD_MARGIN_NS = 3_000_000
#: The hold sleeps in slices and looks at the flight between them: an
#: estimate that is far off (a program's first run; rows that all ended)
#: costs one slice of idle, not the whole estimate, and the flight found
#: landed gives _clock a bound that is a slice wide. Thirteen looks in a
#: decode loop's length.
_HOLD_SLICE_NS = 10_000_000


class InferenceEngine:
    def __init__(self, cfg: LlamaConfig, params=None, *,
                 page_size: int = 16, total_pages: int = 256,
                 max_batch: int = 8, max_seq_len: int = 1024,
                 eos_token: Optional[int] = None, seed: int = 0,
                 decode_chunk: int = 8,
                 prefix_cache: Optional[bool] = True,
                 prefill_chunk: Optional[int] = 512,
                 step_token_budget: Optional[int] = 2048,
                 admit_lookahead: Optional[int] = 16,
                 admit_age_cap_s: Optional[float] = 5.0,
                 kv_dtype: Optional[str] = "model",
                 prefill_rows: Optional[int] = 2,
                 request_log: Optional[bool] = True,
                 tp: int = 1, devices=None):
        def given(name: str, value):
            """None for an argument (an engine_config key left null) is
            the default above."""
            return InferenceEngine.__init__.__kwdefaults__[name] \
                if value is None else value
        # start-up clocks (util/startup_clocks.py): backend, weights and
        # pool here, programs in load_step_programs; stats keys, once made
        startup: Dict[str, int] = {}
        with startup_clocks.phase("backend", startup):
            # the accelerator runtime's start, on its own: the weights'
            # birth below would start it anyway, and hide it in its time
            jax.devices()
        self.cfg = cfg
        self.page_size = page_size
        self.max_batch = max_batch
        self.max_pages_per_seq = -(-max_seq_len // page_size)
        self.eos_token = eos_token
        # tokens decoded per pure-decode dispatch: each dispatch costs a
        # full host<->device round trip, so K steps ride one trip (vLLM
        # multi-step scheduling); finished sequences overshoot at most
        # K-1 tokens
        self.decode_chunk = max(1, decode_chunk)
        # the scheduler's settings: a prompt (or an uncached tail) longer
        # than prefill_chunk prefills in chunks beside the decode rows;
        # step_token_budget caps the prefill tokens a step schedules (0 =
        # no cap); admission scans admit_lookahead waiting requests past a
        # head it cannot admit, until the head is admit_age_cap_s old
        self.prefill_chunk = max(1, given("prefill_chunk", prefill_chunk))
        self.step_token_budget = given("step_token_budget",
                                       step_token_budget)
        self.admit_lookahead = max(1, given("admit_lookahead",
                                            admit_lookahead))
        self.admit_age_cap_s = given("admit_age_cap_s", admit_age_cap_s)
        # ragged batch geometry: every mixed step carries max_batch
        # decode rows (one per slot, inactive slots masked by q_len=0)
        # plus chunk rows of up to prefill_chunk tokens: as many as the
        # smallest of the seam's shapes that holds the rows dealt
        # (_mixed_shape), prefill_rows at most. A fixed, small set of
        # static shapes, so prompt mix never recompiles; ragged_rows and
        # ragged_tokens are the FULL shape's
        self.prefill_rows = max(1, given("prefill_rows", prefill_rows))
        self.ragged_rows, self.ragged_tokens = self._mixed_shape(
            self.prefill_rows)
        # KV page storage scheme: "model" (cfg dtype) or "int8"
        # (quantized pages + bf16 per-token scales, ~1.9x capacity)
        self.kv_dtype = given("kv_dtype", kv_dtype)
        # tensor parallelism: tp>1 shards weights + kv-heads over a
        # ('tp',) mesh and the seam builds shard_map'd programs over it;
        # page allocator / slot bookkeeping below is layout-agnostic
        self.tp = max(1, tp)
        self.mesh = build_tp_mesh(self.tp, devices) if self.tp > 1 else None
        self._fns = M.StepPrograms(
            cfg, decode_chunk=self.decode_chunk,
            max_q_len=self.prefill_chunk, decode_rows=max_batch,
            max_pages=self.max_pages_per_seq,
            prefill_rows=self.prefill_rows,
            kv_quantized=(self.kv_dtype == "int8"), mesh=self.mesh,
            page_size=page_size)
        # weights and pool are created IN their final layout (sharded
        # over the mesh under tp): no device ever stages the whole model
        with startup_clocks.phase("weights", startup):
            self.params = jax.block_until_ready(
                self._fns.init_params(seed) if params is None
                else self._fns.place_params(params))
        # a second page group for window layers, sized from the geometry
        # above so that it never binds (llm/cache.py); 0 = no such layer
        window_pages = window_group_pages(
            cfg, page_size, max_batch, self.decode_chunk,
            self.prefill_chunk, self.prefill_rows)
        self._window = cfg.sliding_window if window_pages else 0
        with startup_clocks.phase("pool", startup):
            self.kv = jax.block_until_ready(self._fns.init_kv(
                total_pages, page_size, self.kv_dtype, max_batch,
                window_pages))
        # device_report()'s sizes, taken HERE: every step donates the
        # pool, so its arrays die under a reader on another thread
        self._param_bytes = sum(x.nbytes
                                for x in jax.tree.leaves(self.params))
        self._kv_bytes = sum(x.nbytes for x in self.kv.values())
        # recurrent layers' state (conv, state-space, retention): one
        # entry a batch slot (+ the scratch slot padding writes), part of
        # the pool pytree and of _kv_bytes; what ONE slot owns of it is
        # what max_batch costs beside the pages
        state = [x for k, x in self.kv.items() if k in STATE_LEAVES]
        self._has_state = bool(state)
        self._state_bytes = sum(x.nbytes for x in state)
        self._state_bytes_per_slot = self._state_bytes // (max_batch + 1)
        # the pool's row as held (a latent pool's is wider than the
        # latent where the kernels need whole lanes) and what one token
        # costs in one layer's pages, over every page leaf
        self._kv_row_width = self.kv["k"].shape[-1]
        self._kv_token_layer_bytes = sum(
            x.nbytes // (x.shape[0] * x.shape[1] * x.shape[3])
            for k, x in self.kv.items()
            if k not in STATE_LEAVES + WINDOW_LEAVES
            and x.shape[0])                           # no layer: no bytes
        self._held_bytes: Dict[int, int] = {}
        for leaf in jax.tree.leaves((self.params, self.kv)):
            for shard in leaf.addressable_shards:
                self._held_bytes[shard.device.id] = self._held_bytes.get(
                    shard.device.id, 0) + shard.data.nbytes
        # the XLA compile tracker (util/compile_tracker.py) the seam
        # records its compiles with, if one runs: _set_gauges
        # cross-checks the O(1)-compile invariant against it
        self._tracker = self._fns.tracker
        self._invariant_breached = False
        self.allocator = PageAllocator(total_pages)
        # the window group's pages are counted on their own
        self.window_allocator: Optional[PageAllocator] = \
            PageAllocator(window_pages) if window_pages else None
        use_prefix = given("prefix_cache", prefix_cache)
        if use_prefix and not prefix_cache_supported(cfg):
            # a hit would restore the matched pages' KV and run the
            # recurrent layers on zero state, or the window layers on a
            # window whose pages are gone: no match is taken at all
            if self._window:
                logger.warning(
                    "prefix cache off: this configuration has window "
                    "layers, whose page group frees behind the window of "
                    "%d tokens", self._window)
            else:
                logger.warning(
                    "prefix cache off: this configuration has %s layers, "
                    "whose state per batch slot (%d bytes) a page-aligned "
                    "prefix hit does not restore",
                    " and ".join(slot_state_kinds(cfg)),
                    self._state_bytes_per_slot)
            use_prefix = False
        self.prefix: Optional[PrefixCache] = \
            PrefixCache(self.allocator, page_size,
                        kv_tag=kv_cache_tag(cfg, self.kv_dtype)) \
            if use_prefix else None
        self.waiting: List[SequenceState] = []
        self.running: List[SequenceState] = []
        # admitted sequences still computing prompt KV in chunks; they
        # hold a slot + pages but stay out of the decode rows
        self._chunking: List[SequenceState] = []
        self._slots: List[Optional[SequenceState]] = [None] * max_batch
        self._req_ids = itertools.count()
        # engines count requests independently, but their records meet in
        # ONE head-side table (requests_dump keyed by rid): a per-engine
        # nonce keeps req ids unique across replicas/processes
        self._rid_nonce = uuid.uuid4().hex[:6]
        self._lock = threading.Lock()
        # device-side decode inputs (fixed shapes)
        self._page_table = np.full((max_batch, self.max_pages_per_seq),
                                   SCRATCH_PAGE, np.int32)
        self._positions = np.zeros(max_batch, np.int32)
        self._tokens = np.zeros(max_batch, np.int32)
        # ... and the decode rows' COMPACT tables in the window group, with
        # the logical page each starts at (_sync_window keeps them)
        self._page_table_win = np.full(
            (max_batch, self._fns.window_pages["decode"]), SCRATCH_PAGE,
            np.int32)
        self._page_base_win = np.zeros(max_batch, np.int32)
        # what a dispatch sends: the decode loop's descriptor and the
        # mixed step's in each of its shapes, kept across steps
        self._decode_desc = _descriptor_turns(self._fns.decode_layout)
        self._step_descs = {n: _descriptor_turns(layout) for n, layout
                            in self._fns.step_layouts.items()}
        self._slot_ids = np.arange(max_batch, dtype=np.int32)
        # the program launched and not booked, if any (step), and every
        # slot's newest token as the newest program leaves it: a device
        # array that goes from one program to the next unread
        self._flight: Optional[_Flight] = None
        self._last = self._fns.init_last(max_batch)
        # launch a program behind the one in flight where _launch's rules
        # allow. The engine decides from its own state; the tests set
        # False to hold it to one program at a time. No configuration does
        self._run_ahead = True
        # the two times a held launch is due by (step), from the engine's
        # own clocks: how long the newest programs of a kind and shape ran
        # (_clock: from the end of one blocked readback to the end of the
        # next), and the host's stretch (engine.admit's start to
        # engine.dispatch's end) in front of the newest launch of each
        # kind
        self._program_ns: Dict[Tuple[str, int], Deque[int]] = {}
        self._stretch_ns: Dict[str, int] = {}
        # a mixed step's padding where it is not 0
        self._padding = {"token_page": SCRATCH_PAGE,
                         "newest_slot": max_batch,
                         "page_table": SCRATCH_PAGE,
                         "token_page_win": SCRATCH_PAGE,
                         "page_table_win": SCRATCH_PAGE,
                         "token_state": max_batch}
        self.stats = {"steps": 0, "prefill_tokens": 0,
                      "decode_steps": 0, "decode_tokens": 0,
                      "decode_dispatches": 0, "cached_tokens": 0,
                      "ragged_dispatches": 0, "ragged_real_tokens": 0,
                      "ragged_slot_tokens": 0, "cow_copies": 0,
                      "preemptions": 0,
                      # chunk rows packed into mixed steps and, of them,
                      # rows that were a sequence's second or later in
                      # their step (_deal_chunk_rows)
                      "chunk_rows": 0, "chunk_rows_joined": 0,
                      # valid tokens packed into mixed steps and, of them,
                      # those that left the walk before its tail (every
                      # token of a chunk row but its last; 0 where the
                      # block's tail is empty: model.leaves_early)
                      "walk_tokens": 0, "walk_tokens_left": 0,
                      # mixed steps that ran a shape of fewer chunk rows
                      # than prefill_rows (of ragged_dispatches)
                      "ragged_small_dispatches": 0,
                      # host arrays sent to the device for dispatches
                      # (one descriptor each), booked WITH the dispatch:
                      # a ratio of the two over any window is exact
                      "h2d_arrays": 0,
                      # dispatches launched with the program before them
                      # still unbooked (booked with the dispatch too);
                      # rows found ended (EOS) after their next dispatch
                      # was packed; times a page group could not serve
                      # the look-ahead and the pipeline drained (_launch)
                      "ahead_dispatches": 0, "late_retired_rows": 0,
                      "ahead_drains": 0,
                      # steps that found a program in flight and nothing
                      # to queue behind it but a decode loop over a FREE
                      # slot (step: the hold-back); those that launched
                      # a program behind the flight while it still ran
                      # (at its due time); and, of those, the mixed steps
                      # (a request came during the hold). held - late:
                      # the pipeline drained (the flight had landed, no
                      # estimate yet, nothing to launch, ahead_drains)
                      "held_launches": 0, "late_launches": 0,
                      "late_mixed_launches": 0}
        # counters the step programs reduce on the device and append to
        # the tokens they return (none for a dense model): one stats key
        # each, and metadata of the dispatch's engine.readback span
        self._step_counters = M.step_counters(cfg)
        self.stats.update(dict.fromkeys(self._step_counters, 0))
        if self._has_state:
            # the state's size, and rows that started at position 0 (a
            # new or re-prefilled sequence: the step read zeros for its
            # slot's state instead of what the last owner left)
            self.stats.update(
                state_bytes=self._state_bytes,
                state_bytes_per_slot=self._state_bytes_per_slot,
                state_resets=0)
        if self._window:
            # pages the window group returned behind the window; and, added
            # at every dispatch, the pages IN USE of each group (monotonic,
            # so a window's difference is exact): their ratio is the share
            # of one lifetime's pages that the window layers hold
            # ... and the decode row-steps (the unit of decode_tokens: a
            # row of a mixed step is one, of a decode block decode_chunk)
            # whose sequence still lay inside the window when its dispatch
            # left: a window layer is a full layer to such a row, and its
            # group has freed nothing for it
            self.stats.update(window_pages_freed=0, page_steps_full=0,
                              page_steps_window=0, rows_inside_window=0)
        if cfg.kv_lora_rank or cfg.layers_of(CROSS) or cfg.ut_steps > 1:
            # what a token costs in ONE plane of the full group's pages,
            # for each block whose pages are not a layer's own K and V
            self.stats["kv_token_layer_bytes"] = self._kv_token_layer_bytes
        if cfg.kv_lora_rank:
            # a latent pool: the row as held
            self.stats["kv_row_width"] = self._kv_row_width
        if cfg.layers_of(CROSS):
            # pages that layers with none of their own read: how many
            # layers read that one layer's pages (itself counted)
            self.stats["shared_kv_readers"] = len(cfg.layers_of(CROSS)) \
                + bool(cfg.layers_of(ATTENTION))
        if cfg.ut_steps > 1:
            # a looped stack: a plane a pass and layer, and what a token
            # costs over all of them (what sets the batch)
            planes = page_planes(cfg)
            self.stats.update(
                kv_planes=planes,
                kv_token_bytes=planes * self._kv_token_layer_bytes)
        # every span of the host loop and its wall / CPU counters; the
        # serve loop opens serve.publish and serve.wait through it too
        self.phase = PhaseClocks(self.stats).phase
        self.stats.update(startup)
        #: load_step_programs' records, one a program (startup_clocks)
        self.startup_programs: List[Dict[str, object]] = []
        # per-request flight recorder (llm/request_log.py): lifecycle
        # event stream per request + TTFT/TPOT/e2e/queue-wait histograms
        # + SLO attainment; None disables every hook (seq.record stays
        # None, so the step loop pays one is-None check per event)
        if given("request_log", request_log):
            from ray_tpu.llm.request_log import FlightRecorder
            self.request_log: Optional[FlightRecorder] = FlightRecorder()
        else:
            self.request_log = None
        # tokens generated since the last drain_progress() call, per live
        # request — the incremental surface token streaming rides on
        # (reference: vLLM engine step() yielding RequestOutputs per step).
        # OPT-IN: users that never drain (generate(), bench loops) must not
        # accumulate every token ever generated
        self.track_progress = False
        self._progress: Dict[str, List[int]] = {}
        # rid -> "stop" (EOS) | "length", for OpenAI finish_reason;
        # bounded: consumers pop, non-consumers age out
        self._finish_reasons: "collections.OrderedDict[str, str]" = \
            collections.OrderedDict()
        # rid -> prompt tokens served from the prefix cache (OpenAI
        # usage.prompt_tokens_details.cached_tokens); same bounding
        self._cached_counts: "collections.OrderedDict[str, int]" = \
            collections.OrderedDict()
        # engine gauges on the PR-2 telemetry plane: worker flushes ship
        # the process registry to the head -> /metrics + `ray_tpu top`
        from ray_tpu.util import metrics as metrics_mod
        self._g_kv_util = metrics_mod.llm_kv_page_utilization_gauge()
        self._g_hit_rate = metrics_mod.llm_prefix_hit_rate_gauge()
        self._g_prefill_tps = metrics_mod.llm_prefill_tokens_per_s_gauge()
        self._g_decode_tps = metrics_mod.llm_decode_tokens_per_s_gauge()
        self._g_queue = metrics_mod.llm_queue_depth_gauge()
        self._g_programs = metrics_mod.llm_compiled_programs_gauge()
        self._g_pad_waste = metrics_mod.llm_padding_waste_gauge()
        self._g_device_wait = metrics_mod.llm_engine_device_wait_gauge()
        self._g_slo_ttft = metrics_mod.llm_slo_ttft_attainment_gauge()
        self._g_slo_tpot = metrics_mod.llm_slo_tpot_attainment_gauge()
        self._g_preempts = metrics_mod.llm_preemptions_gauge()
        self._metrics_ts = time.monotonic()
        self._metrics_last = dict(self.stats)
        # what the step in hand dispatched: engine.step's metadata
        self._step_meta: Dict[str, object] = {"kind": "none"}

    # ------------------------------------------------------------ requests

    def add_request(self, prompt: List[int], max_new_tokens: int = 32,
                    trace_id: str = "") -> str:
        if len(prompt) == 0:
            raise ValueError("empty prompt")
        if len(prompt) + max_new_tokens > \
                self.max_pages_per_seq * self.page_size:
            raise ValueError("prompt + max_new_tokens exceeds max_seq_len")
        probe = SequenceState("probe", prompt, max_new_tokens)
        if probe.pages_needed(self.page_size, headroom=1) > \
                self.allocator.total_pages - 1:
            # unsatisfiable even with an empty pool: reject now rather
            # than spinning _admit forever at the head of the queue
            raise ValueError(
                f"prompt needs more pages than the cache holds "
                f"({self.allocator.total_pages - 1} allocatable)")
        rid = f"req-{self._rid_nonce}-{next(self._req_ids)}"
        seq = SequenceState(rid, prompt, max_new_tokens,
                            enqueue_ts=time.monotonic())
        if self.request_log is not None:
            # flight-recorder lifecycle starts at enqueue; the caller's
            # trace_id (serve router span) links record <-> trace tree
            seq.record = self.request_log.start(
                rid, len(prompt), max_new_tokens, trace_id=trace_id)
        with self._lock:
            self.waiting.append(seq)
        return rid

    def has_work(self) -> bool:
        with self._lock:
            return bool(self.waiting or self.running or self._chunking
                        or self._flight is not None)

    def compiled_step_programs(self) -> int:
        """Compiled step programs resident for this engine's step fns
        (O(1) by design: the mixed ragged step once a shape, decode
        loop, COW copy)."""
        return self._fns.compiled_step_programs()

    def load_step_programs(self) -> None:
        """Compile and load every program this engine can dispatch, on
        an engine that has no work yet: the mixed step in each of its
        shapes, the decode loop and, with a prefix cache, the page copy.
        Each is RUN once on nothing but padding, through the callables a
        step uses (so the jit's own cache and the compile tracker hold
        them): every mixed row's q_len 0, every token on the scratch page
        and the scratch state slot; the decode loop as an engine whose
        slots are all free dispatches it; the scratch page copied onto
        itself. Which shapes traffic reaches first is then nobody's luck:
        nothing compiles after this returns. It books nothing of the
        steady state: no counter of a step moves, no engine.* span opens,
        no request record exists. What it does write is its own start-up
        clock (util/startup_clocks.py): the span startup.programs and the
        key startup_ns_programs and, inside it, one span startup.program
        and one record in ``startup_programs`` a program (each blocked on:
        its run_s is the run on padding), with their sums in
        startup_ns_trace_lower, startup_ns_backend_compile and
        startup_programs_cold. A served replica calls it before its engine
        thread starts (LLMServer); a bare engine compiles lazily, on first
        use."""
        fns = self._fns

        def mixed(n_rows: int) -> None:
            _, self.kv, self._last = fns.ragged_step(
                self.params, jax.device_put(self._pack_mixed([], [], n_rows)),
                self.kv, self._last)

        def decode() -> None:
            _, self.kv, _, _, self._last = fns.decode_loop(
                self.params, jax.device_put(self._pack_decode([])), self.kv,
                self._last)

        def copy() -> None:
            scratch = jnp.int32(SCRATCH_PAGE)
            self.kv = fns.copy_page(self.kv, scratch, scratch)

        turns = [("llm.ragged_step", {"rows": n}, lambda n=n: mixed(n))
                 for n in fns.row_shapes] + [("llm.decode_loop", {}, decode)]
        if self.prefix is not None:
            turns.append(("llm.copy_page", {}, copy))
        loaded = self.startup_programs = []
        with startup_clocks.phase("programs", self.stats):
            for name, meta, run in turns:
                with startup_clocks.program(name, fns.tracker,
                                            **meta) as rec:
                    run()
                    jax.block_until_ready(self.kv)
                loaded.append(rec)
        self.stats.update(startup_clocks.program_totals(loaded))

    def device_report(self) -> Dict[str, object]:
        """Where this engine runs and what it compiled: the devices that
        hold its weights, the paged-attention implementation its step
        programs took ("kernel" | "reference" — chosen from the platform,
        so a deployment can assert it never fell back), the resident
        step-program count and the most it may be (the seam's budget: 2 +
        the mixed step's shapes), and per device the bytes of weights + KV
        pages (+ recurrent state: ``state_bytes`` of ``kv_bytes``,
        ``state_bytes_per_slot`` of that a batch slot; beside
        ``kv_bytes`` what a token costs in one layer's pages and the
        pool's row width as held) it holds
        next to the allocator's own ``memory_stats()``
        (None on backends that keep none, i.e. the CPU). Safe from any
        thread while the engine steps: placement is read from the
        weights, which no step donates, and the sizes are the
        constructor's."""
        devices = sorted({shard.device
                          for leaf in jax.tree.leaves(self.params)
                          for shard in leaf.addressable_shards},
                         key=lambda d: d.id)
        per_device = []
        for d in devices:
            ms = d.memory_stats() or {}
            per_device.append({
                "id": d.id, "engine_bytes": self._held_bytes.get(d.id, 0),
                "bytes_in_use": ms.get("bytes_in_use"),
                "peak_bytes_in_use": ms.get("peak_bytes_in_use"),
                "bytes_limit": ms.get("bytes_limit")})
        return {"platform": devices[0].platform,
                "device_kind": devices[0].device_kind,
                "device_count": len(jax.devices()),
                "tp": self.tp,
                "paged_impl": self._fns.paged_impl,
                "compiled_step_programs": self.compiled_step_programs(),
                "step_program_budget": self._fns.program_budget,
                "param_bytes": self._param_bytes,
                "kv_bytes": self._kv_bytes,
                "kv_token_layer_bytes": self._kv_token_layer_bytes,
                "kv_row_width": self._kv_row_width,
                "state_bytes": self._state_bytes,
                "state_bytes_per_slot": self._state_bytes_per_slot,
                "devices": per_device}

    # ---------------------------------------------------------------- step

    def step(self, after_dispatch: Optional[Callable[[], None]] = None,
             ) -> Dict[str, List[int]]:
        """One scheduler step: admit waiting requests, LAUNCH at most one
        program — one ragged mixed dispatch (prefill chunks under the
        token budget + one decode token per running sequence, a single
        program) when prefill work is pending, one multi-step decode-loop
        dispatch (decode_chunk tokens per running sequence) when not —
        and BOOK at most one: the program that was in flight when the step
        began (the one just launched is then queued behind it and stays
        unbooked: the next step's), else the one just launched unless the
        next may be queued behind it (_next_may_follow). A step that has
        nothing to launch, or may not launch it behind the program in
        flight (_launch), books what is in flight: the pipeline is empty
        again. Returns {request_id: generated} for the sequences that the
        booking found FINISHED.

        THE LATE DECISION. A program in flight, no chunk work, a slot
        free and nobody waiting (_held_back: the normal state after every
        booking that ended a row, in a closed loop): a decode loop queued
        now would make whoever arrives during the flight wait a loop
        longer for the free slot; booking the flight first and packing
        then, as the engine did, leaves the chip idle for the whole
        stretch (admit .. dispatch). The rule protects an arrival only
        until the next program is packed, so the step moves that moment
        to where it costs nothing: it hands over what the caller holds
        (``after_dispatch``), sleeps (engine.hold) until the flight is
        DUE, which is its expected end (_expected_end_ns: when it began,
        and how long the newest programs of its kind and shape ran that
        were booked with both ends marked: _clock) less the stretch of
        the newest launches and a margin (_lead_ns), admits AGAIN, and
        launches what is due behind the flight: a mixed step if anyone
        came, else the decode loop, free slot or not (the slot decodes at
        length 1 through blank tables, as a free slot does in any loop:
        _blank_slot). Then it books the flight, whose readback blocks for
        about the margin. It never sleeps on a program that has landed:
        one found landed when the step looks is booked at once, the
        pipeline is empty and the step goes on as before; the hold looks
        between its slices (_hold) and ends with the flight if that ends
        first, and the launch is made all the same (the chip is idle). A
        flight whose due time has passed is followed at once. Before a
        kind and shape has been clocked once the step does what the
        engine did before: it books the flight, and the next step
        launches on an empty pipeline. Unchanged: a request that
        WAITS at a full batch while a row is known to end in the flight
        still drains the pipeline (_loop_may_follow), as does a page
        group that cannot serve the look-ahead (_launch); with chunk work
        pending or no slot free the launch is made ahead at once. stats
        held_launches counts the steps that came to the hold-back,
        late_launches those that launched behind the flight still
        running, late_mixed_launches the mixed steps among them.

        ``after_dispatch`` is called once, with no arguments, by every
        step that is about to sleep on a program: right after the
        engine.dispatch phase of the program the step launches, or, where
        it launches none and books the one in flight, before that
        program's engine.readback; by a step that holds, before the hold
        and not again after its launch (a finished row's client can only
        come back after the hand-over, and the hold is what it comes back
        in). Host work the caller wants done while
        the device runs and this thread would only sleep on it. A step
        that neither launches nor books never calls it. The serve loop
        hands the PREVIOUS step's tokens to their waiters there
        (llm/serve_llm.py), so a served token reaches its waiter one
        launch after it is booked (before the next readback where nothing
        is launched; at once when the engine runs dry); booking, the
        request log's timestamps (the booking's, not the delivery's) and
        every program's inputs are the same with and without it."""
        finished: Dict[str, List[int]] = {}
        with self.phase("engine.step") as span:
            self._step_meta = {"kind": "none"}
            stats = self.stats
            admit = self._admit_phase()
            flying = self._flight
            handed = held = due = False
            expected = None
            if flying is not None and self._held_back():
                held = True
                stats["held_launches"] += 1
                expected = self._expected_end_ns(flying)
                if expected is not None and not _landed(flying):
                    wake = expected - self._lead_ns()
                    if wake > _wall_ns():
                        # what the caller holds first: the finished rows'
                        # clients can only come back after it
                        if after_dispatch is not None:
                            after_dispatch()
                        handed = True
                        self._hold(flying, wake)
                        admit = self._admit_phase()
                    due = True
            launched = self._launch(finished, due)
            if launched is not None:
                self._stretch_ns[launched.kind] = launched.began - admit.wall0
            # late: behind the flight STILL running (one that landed
            # during the hold is followed all the same: the chip is idle)
            late = due and launched is not None and not _landed(flying)
            stats["late_launches"] += late
            stats["late_mixed_launches"] += late and launched.kind == "mixed"
            if flying is None and launched is not None \
                    and not self._next_may_follow():
                flying = launched             # the synchronous order
            if after_dispatch is not None and not handed \
                    and (launched is not None or flying is not None):
                after_dispatch()              # the device is running
            if flying is not None:
                self._book(flying, finished)
            self._step_meta.update(
                launched="none" if launched is None else launched.kind,
                held=held, late=late)
            if expected is not None and flying.blocked:
                # how far off the flight's expected end was (+: it ran
                # longer), where its readback marked the end
                self._step_meta["end_late_us"] = \
                    (flying.ended - expected) / 1e3
            stats["steps"] += 1
            self._update_metrics()
            if span.is_enabled():
                span.set_metadata(**self._step_meta)
        return finished

    def _hold(self, flight: "_Flight", wake: int) -> None:
        """engine.hold: sleep until ``wake`` (the flight is due), in
        slices, and no longer than the flight runs."""
        with self.phase("engine.hold"):
            left = wake - _wall_ns()
            while left > 0 and not _landed(flight):
                _sleep(min(left, _HOLD_SLICE_NS) / 1e9)
                left = wake - _wall_ns()

    def _admit_phase(self) -> "_Phase":
        """engine.admit, once: the span, closed (its wall0 is where the
        host's stretch in front of a launch starts)."""
        with self.phase("engine.admit") as span:
            admitted = self._admit()
            if span.is_enabled():
                span.set_metadata(admitted=admitted)
        return span

    # ---------------------------------------------------------- scheduling

    def _alloc_pages(self, n: int) -> Optional[List[int]]:
        """Allocate, LRU-evicting unreferenced prefix-cache pages under
        pressure — cached pages are free HBM, not reserved memory."""
        pages = self.allocator.alloc(n)
        if pages is None and self.prefix is not None:
            short = n - self.allocator.num_free
            if self.prefix.evict(short) >= short:
                pages = self.allocator.alloc(n)
        return pages

    def _release_pages(self, pages: List[int]) -> None:
        self.allocator.free(pages)
        if self.prefix is not None:
            self.prefix.note_release(pages)

    def _blank_slot(self, slot: int) -> None:
        """Leave ``slot``'s tables as a free slot's: every entry the
        scratch page, the compact table from base 0. A slot that is no
        decode row still decodes in the loop, at length 1 into the page
        its tables name, and a window row's kernel walks its table from
        the base: the tables of a sequence that has ENDED but still
        holds the slot (its last program is in flight) must not stand
        under a length that is no longer theirs (a page index out of the
        table's range halts the chip)."""
        self._page_table[slot, :] = SCRATCH_PAGE
        if self._window:
            self._page_table_win[slot, :] = SCRATCH_PAGE
            self._page_base_win[slot] = 0

    def _release_window(self, slot: int, seq: SequenceState) -> None:
        """Return every page ``seq`` holds in the window group (its
        slot's tables: _blank_slot)."""
        if seq.win_pages:
            self.window_allocator.free(seq.win_pages)
        seq.win_pages, seq.win_base = [], 0

    def _extend_window(self, seq: SequenceState, upto: int,
                       ahead: bool = False) -> Optional[int]:
        """Window-group pages for positions up to ``upto`` (a position the
        coming dispatch writes); how many were added. The group is sized
        from the engine's geometry so that it cannot run out
        (window_group_pages) between two programs: running out is a fault
        of the accounting, and raised as one. Not so ``ahead``, with a
        program in flight whose rows still hold the pages their booking
        will give back behind the window (a page a decode row, a chunk's
        pages a chunk row): None then, and the caller books that program
        first (_launch)."""
        short = max(0, upto // self.page_size + 1
                    - (seq.win_base + len(seq.win_pages)))
        if short:
            extra = self.window_allocator.alloc(short)
            if extra is None:
                if ahead:
                    return None
                raise RuntimeError(
                    f"the window page group has {short} pages too few: "
                    f"{self.window_allocator.num_free} free of "
                    f"{self.window_allocator.total_pages}")
            seq.win_pages.extend(extra)
        return short

    def _trim_window(self, seq: SequenceState, next_pos: int) -> int:
        """Free the window-group pages that lie wholly behind the window
        of ``next_pos``, the next position ``seq`` computes past the
        program being booked (and so of every later one: a program
        launched behind that one starts there); how many that were."""
        n = window_first_page(next_pos, self._window, self.page_size) \
            - seq.win_base
        if n <= 0:
            return 0
        self.window_allocator.free(seq.win_pages[:n])
        del seq.win_pages[:n]
        seq.win_base += n
        self.stats["window_pages_freed"] += n
        return n

    def _sync_window(self, slot: int, seq: SequenceState) -> None:
        """A decode row's compact table and base for the next program:
        from the first page its next position (``_positions``) still sees,
        which is the first ``seq`` holds once every program launched
        before is booked, and a page or a block's further on until then."""
        cols = self._page_table_win.shape[1]
        base = window_first_page(int(self._positions[slot]), self._window,
                                 self.page_size)
        pages = seq.win_pages[base - seq.win_base:][:cols]
        self._page_table_win[slot, :len(pages)] = pages
        self._page_table_win[slot, len(pages):] = SCRATCH_PAGE
        self._page_base_win[slot] = base

    def _unmatch(self, matched_pages: List[int]) -> None:
        """Undo a PrefixCache.match whose sequence did not admit."""
        if matched_pages:
            self._release_pages(matched_pages)

    def _admit(self) -> int:
        """Admit waiting requests into the chunked-prefill pipeline: a
        sequence reserves a decode slot + pages up front (prefix-cache
        hits map shared pages read-only, copy-on-write if its tail
        writes into a shared page) and its uncached prompt tail computes
        chunk-by-chunk on the mixed ragged step. Admission itself costs
        no device work, so it is not budgeted — chunk tokens are, as
        their rows are packed.

        Head-of-line fix: the scan continues past non-admissible
        requests (no free pages) through a bounded lookahead window
        instead of breaking at the first failure — one long prompt at
        the head no longer starves short prompts behind it. Aging
        guard: once the head has waited admit_age_cap_s, a head that
        fails for MEMORY stops the scan, so freed pages reach it
        instead of being re-captured by younger requests forever.
        Returns how many it admitted."""
        admitted: List[Tuple[SequenceState, List[int], List[int], bool]] = []
        with self._lock:
            if not self.waiting:
                return 0
            now = time.monotonic()
            # arrivals since the last scan sit at the tail (a preempted
            # sequence re-queues at the head, already stamped): one stamp
            # a request, nothing per step for those that have theirs
            for seq in reversed(self.waiting):
                if seq.record is None or seq.record.seen_ts is not None:
                    break
                seq.record.note_seen(now)
            head = self.waiting[0]
            head_aged = (now - head.enqueue_ts) > self.admit_age_cap_s
            free_slots = [i for i, s in enumerate(self._slots)
                          if s is None]
            for seq in list(self.waiting[:self.admit_lookahead]):
                if not free_slots:
                    break
                matched_pages: List[int] = []
                matched, cow = 0, False
                if self.prefix is not None:
                    matched_pages, matched, cow = \
                        self.prefix.match(seq.prompt)
                need = seq.pages_needed(self.page_size, headroom=1) \
                    - len(matched_pages) + (1 if cow else 0)
                tail_pages = self._alloc_pages(need)
                if tail_pages is None:
                    self._unmatch(matched_pages)
                    if seq.record is not None:
                        seq.record.note_stall(now)
                    if seq is head and head_aged:
                        break  # aged head waits for memory first
                    continue
                slot = free_slots.pop(0)
                self.waiting.remove(seq)
                seq.slot = slot
                seq.prefilling = True
                seq.num_computed = matched
                seq.cached_tokens = matched
                if seq.record is not None:
                    seq.record.note_admit(now, matched)
                self._slots[slot] = seq
                admitted.append((seq, matched_pages, tail_pages, cow))
        for seq, matched_pages, tail_pages, cow in admitted:
            if cow:
                # tail writes land inside the last shared page: copy it
                # on device, then drop our reference to the original
                cow_page = tail_pages.pop(0)
                orig = matched_pages[-1]
                self.kv = self._fns.copy_page(self.kv, jnp.int32(orig),
                                              jnp.int32(cow_page))
                self._release_pages([orig])
                matched_pages = matched_pages[:-1] + [cow_page]
                self.stats["cow_copies"] += 1
            seq.pages = matched_pages + tail_pages
            self.stats["cached_tokens"] += seq.cached_tokens
            self._note_cached(seq.request_id, seq.cached_tokens)
            self._chunking.append(seq)
        return len(admitted)

    # --------------------------------------------------- ragged mixed step

    def _deal_chunk_rows(self) -> List[Tuple[SequenceState, int, int]]:
        """Deal the mixed step's chunk rows: [(seq, start, n_tokens)], at
        most prefill_rows rows of at most prefill_chunk tokens, their sum
        under the step token budget. First one row a sequence, FIFO over
        the chunking queue: a sequence never waits a step longer for a
        row because another one has a long prompt. Rows still free then
        go, FIFO again, to the NEXT chunks of the sequences just dealt a
        row, each chunk a row of its own that starts where the row before
        it ended: a sequence that prefills alone computes prefill_rows
        chunks a step, not one. Two things the engine can see hold a
        sequence to its one row:

          - conv, state-space or retention layers: a chunk row starts
            from its slot's state and stores it at its end, so two rows
            of one slot in one step would both start from the old state;
          - a row that ends inside a page (cut short by the token
            budget; a prefill_chunk that is no multiple of page_size) is
            its sequence's last of the step: the write kernel moves
            whole pages, a partial one as read-modify-write, and the
            tail of one row and the head of the next on the SAME page in
            one call would race. Full chunks keep a sequence's rows on
            page boundaries: a prefix hit leaves num_computed on a page
            multiple or, copied on write, one token short of the
            prompt's end."""
        budget = self.step_token_budget \
            if self.step_token_budget > 0 else (1 << 30)
        rows: List[Tuple[SequenceState, int, int]] = []
        for seq in self._chunking:
            if len(rows) >= self.prefill_rows:
                break
            C = min(self.prefill_chunk,
                    len(seq.prompt) - seq.num_computed, budget)
            if C <= 0:
                break  # step token budget exhausted
            rows.append((seq, seq.num_computed, C))
            budget -= C
        if self._has_state:
            return rows
        for seq, start, C in list(rows):
            end = start + C
            while len(rows) < self.prefill_rows \
                    and end % self.page_size == 0:
                C = min(self.prefill_chunk, len(seq.prompt) - end, budget)
                if C <= 0:
                    break  # the prompt's end, or the budget's
                rows.append((seq, end, C))
                budget -= C
                end += C
        return rows

    def _mixed_shape(self, n_rows: int) -> Tuple[int, int]:
        """(rows, token slots) of the mixed step with ``n_rows`` chunk
        rows: the decode rows first, one token each, then the chunks."""
        return (self.max_batch + n_rows,
                self.max_batch + n_rows * self.prefill_chunk)

    def _decode_mask(self, active: List[Tuple[int, SequenceState]],
                     ) -> np.ndarray:
        """[max_batch] bool: the slots that hold a decode row."""
        on = np.zeros(self.max_batch, bool)
        on[[i for i, _ in active]] = True
        return on

    def _unread(self, active: List[Tuple[int, SequenceState]],
                ) -> np.ndarray:
        """[max_batch] bool: the decode rows whose newest token no booking
        has read yet. It is in ``_last``, on the device: the program takes
        it there (a NEGATIVE token in the descriptor says so)."""
        unread = np.zeros(self.max_batch, bool)
        unread[[i for i, seq in active if seq.unbooked]] = True
        return unread

    def _pack_mixed(self, active: List[Tuple[int, SequenceState]],
                    rows: List[Tuple[SequenceState, int, int]],
                    n_rows: int) -> np.ndarray:
        """Fill the mixed step's descriptor in its shape of ``n_rows``
        chunk rows (>= len(rows)) and return it: decode rows first (slot r
        owns ragged token r; all of them at once, from the slots' arrays
        and the mask of those that decode: a decode row's position is its
        sequence's last token's, so its kv_len is that + 1), then the
        chunk rows packed from token max_batch on; what holds no token is
        padding (q_len 0, the scratch page, the scratch state slot)."""
        ps, B = self.page_size, self.max_batch
        buf, f, filled = next(self._step_descs[n_rows])
        on = self._decode_mask(active)
        pos = np.where(on, self._positions, 0)
        f["tokens"][:B] = np.where(
            on, np.where(self._unread(active), -1, self._tokens), 0)
        f["token_pos"][:B] = pos
        f["token_page"][:B] = np.where(
            on, self._page_table[self._slot_ids, pos // ps], SCRATCH_PAGE)
        f["token_slot"][:B] = pos % ps
        f["q_start"][:B] = self._slot_ids
        f["q_len"][:B] = on
        f["kv_len"][:B] = np.where(on, pos + 1, 0)
        # a decode row's token is its slot's newest; so is the token of
        # the chunk row that ends a prompt, below
        f["newest_slot"][:B] = np.where(on, self._slot_ids, B)
        f["page_table"][:B] = self._page_table
        # each token's state slot: its sequence's batch slot, the scratch
        # slot (max_batch) for padding
        state = f.get("token_state")
        if state is not None:
            state[:B] = np.where(on, self._slot_ids, B)
        win = self._window
        if win:
            # the decode rows' compact tables, as kept; a token's page is
            # its table's entry at its logical page less the row's base
            cols = self._page_table_win.shape[1]
            f["page_table_win"][:B, :cols] = self._page_table_win
            f["page_table_win"][:B, cols:] = SCRATCH_PAGE
            f["page_base_win"][:B] = self._page_base_win
            f["token_page_win"][:B] = np.where(
                on, self._page_table_win[self._slot_ids, np.clip(
                    pos // ps - self._page_base_win, 0, cols - 1)],
                SCRATCH_PAGE)
        # past the chunk rows' tokens and rows, padding: only what this
        # buffer's LAST fill wrote there is not (a fill of a whole field
        # lets the interpreter go, and in the stretch the chip waits for)
        r_end, t_end = B + len(rows), B + sum(C for _, _, C in rows)
        r_old, t_old = filled or self._mixed_shape(n_rows)
        filled[:] = r_end, t_end
        for name, field in f.items():
            lo, hi = (r_end, r_old) if name in M.ROW_FIELDS \
                else (t_end, t_old)
            field[lo:hi] = self._padding.get(name, 0)
        t0 = B
        for j, (seq, start, C) in enumerate(rows):
            r, t1 = B + j, t0 + C
            pos = np.arange(start, start + C, dtype=np.int32)
            pages = np.asarray(seq.pages, np.int32)
            f["tokens"][t0:t1] = seq.prompt[start:start + C]
            f["token_pos"][t0:t1] = pos
            f["token_page"][t0:t1] = pages[pos // ps]
            f["token_slot"][t0:t1] = pos % ps
            f["page_table"][r, :len(pages)] = pages
            f["page_table"][r, len(pages):] = SCRATCH_PAGE
            f["q_start"][r] = t0
            f["q_len"][r] = C
            f["kv_len"][r] = start + C
            f["newest_slot"][r] = seq.slot \
                if start + C >= len(seq.prompt) else B
            if state is not None:
                state[t0:t1] = seq.slot
            if win:
                # the row's table starts at the first page its first token
                # sees (at or past what the sequence still holds)
                base = window_first_page(start, win, ps)
                own = np.asarray(seq.win_pages, np.int32)
                held = own[base - seq.win_base:][
                    :f["page_table_win"].shape[1]]
                f["page_table_win"][r, :len(held)] = held
                f["page_table_win"][r, len(held):] = SCRATCH_PAGE
                f["page_base_win"][r] = base
                f["token_page_win"][t0:t1] = own[pos // ps - seq.win_base]
            t0 = t1
        return buf

    def _launch(self, finished: Dict[str, List[int]], due: bool = False,
                ) -> Optional["_Flight"]:
        """Pack, send and launch the next program from the structures as
        the launched ones leave them, or None. ONE ragged mixed step if
        prefill work is pending: decode rows first (slot r owns ragged
        token r), then up to prefill_rows chunk rows packed from token
        max_batch on, as _deal_chunk_rows deals them (a sequence may hold
        several, one after another in position). Rows whose chunk finishes
        its prompt get their first sampled token from the SAME dispatch
        (fused argmax; the sequence's LAST row's) — no extra program, no
        extra readback. The step runs in the smallest of the seam's shapes
        that holds the rows dealt (StepPrograms.row_shapes), and its
        arrays, its counters and its span follow THAT shape; a step dealt
        prefill_rows rows is the full shape's. With no chunk work, the
        pure-decode loop over the decode rows, if there are any.

        With a program IN FLIGHT (launched and not booked: its tokens are
        on the device, and each row's newest reaches this program there,
        model._newest_from) the launch is AHEAD, and two things hold it
        back; the step then books the program in flight and the next one
        goes on from an empty pipeline:

          - a decode loop is queued behind a running program at once
            only when no batch slot is free: a request that arrives
            meanwhile could not have been admitted before a row ends
            anyway. Behind a FREE slot it is queued when the flight is
            ``due`` (step's late decision: the arrival it would have
            made wait has been admitted by then, or did not come). And
            never while a request WAITS and a row is known to end in the
            program in flight (by length; or found stopped on EOS a
            booking ago): that booking frees the slot, and the request
            would wait a loop longer for it than with one program at a
            time (_loop_may_follow). A mixed step is queued whenever
            there is chunk work for it;
          - what the engine cannot do without the tokens in flight
            (stats ahead_drains): a page group that cannot serve the
            look-ahead. _ensure_pages preempts, and preemption folds the
            generated tokens into the prompt; the window group is sized
            for one program's rows. Running ahead never preempts, evicts
            or raises: the synchronous order does, next step, if it still
            must."""
        ahead = self._flight is not None
        rows = self._deal_chunk_rows()
        if ahead and not rows and not self._loop_may_follow(due):
            return None
        for seq, start, C in rows if self._window else ():
            # the rows' pages
            if self._extend_window(seq, start + C - 1, ahead) is None:
                self.stats["ahead_drains"] += 1
                return None
        flight = None
        with self.phase("engine.pack"):
            # decode rows advance one token, or a block: pages for it
            active = self._decode_rows(
                1 if rows else self.decode_chunk, finished, ahead)
            if active is None:
                self.stats["ahead_drains"] += 1
            elif rows:
                # the smallest compiled shape that holds the deal
                n_rows = next(n for n in self._fns.row_shapes
                              if n >= len(rows))
                desc = self._pack_mixed(active, rows, n_rows)
                flight = self._take_off(active, rows, n_rows, ahead)
            elif active:
                desc = self._pack_decode(active)
                flight = self._take_off(active, rows, 0, ahead)
        if flight is None:
            return None
        with self.phase("engine.h2d"):
            desc = jax.device_put(desc)         # the ONE transfer
        with self.phase("engine.dispatch") as span:
            # kv and the newest tokens: the outputs of the program before,
            # ready or not
            if rows:
                flight.out, self.kv, self._last = self._fns.ragged_step(
                    self.params, desc, self.kv, self._last)
            else:
                flight.out, self.kv, _, _, self._last = \
                    self._fns.decode_loop(self.params, desc, self.kv,
                                          self._last)
        # it runs from now, or from the end of the program in flight: that
        # one's booking says when that was
        flight.began = span.wall1
        self._flight = flight
        return flight

    def _take_off(self, active: List[Tuple[int, SequenceState]],
                  rows: List[Tuple[SequenceState, int, int]], n_rows: int,
                  ahead: bool) -> "_Flight":
        """The engine's structures as the program just packed WILL leave
        them, set now: what a dispatch does to them is known when it is
        packed. Every decode row advances by the tokens it takes (1 of a
        mixed step, decode_chunk of a loop, or what max_new_tokens leaves:
        the row then ENDS in this program and is no row of the next), a
        chunk row by its chunk, and a prompt whose last chunk this is
        joins the decode rows (_join). Only the tokens' values, and
        whether one is EOS, wait for the booking (_book), which takes what
        it needs of the dispatch from the record returned."""
        K = 1 if rows else self.decode_chunk
        flight = _Flight("mixed" if rows else "decode", rows, n_rows, ahead)
        if self._window:
            # before the rows advance: as the synchronous booking saw them
            flight.pages_in_use = tuple(
                alloc.total_pages - 1 - alloc.num_free
                for alloc in (self.allocator, self.window_allocator))
            flight.inside_window = K * sum(
                seq.num_launched <= self._window for _, seq in active)
        # a joined row starts past what its sequence has computed
        flight.rows_joined = sum(
            start > seq.num_computed for seq, start, _ in rows)
        for slot, seq in active:
            take = min(K, seq.tokens_left)
            seq.unbooked += take
            seq.flights += 1
            seq.ended = seq.tokens_left == 0
            flight.active.append((slot, seq, take))
        if active:
            slots = np.fromiter((slot for slot, _ in active), np.int64,
                                len(active))
            pos = self._positions[slots] + K
            self._positions[slots] = pos
            if self._window:
                # rows whose window left a page behind: their tables
                w, ps = self._window - 1, self.page_size
                moved = np.maximum(pos - w, 0) // ps \
                    != np.maximum(pos - K - w, 0) // ps
                for i in np.flatnonzero(moved):
                    self._sync_window(*active[i])
            for slot, seq in active:
                if seq.ended:       # no row of the next program
                    self._blank_slot(slot)
        for seq in dict.fromkeys(seq for seq, _, _ in rows):
            seq.flights += 1
        for seq, start, C in rows:
            seq.num_computed = start + C
            if seq.num_computed >= len(seq.prompt):
                self._join(seq)
        return flight

    def _join(self, seq: SequenceState) -> None:
        """The program just packed computes the last chunk of ``seq``'s
        prompt and samples its first token (a re-admitted sequence's next
        one): publish the full prompt pages into the prefix cache, and
        make the sequence a decode row of the next program, unless that
        token is the last its max_new_tokens allows."""
        self._chunking.remove(seq)
        seq.prefilling = False
        if self.prefix is not None:
            # registering BEFORE a possible immediate finish keeps
            # recently-finished prompts reusable (their pages go
            # evictable-LRU, not back to the free list); for a preempted
            # sequence the prompt is still FOLDED here, so the pages
            # holding generated-token KV publish too. Whoever matches
            # them reads them in a LATER program: the device runs this
            # one first
            self.prefix.register(seq.prompt, seq.pages)
        seq.unbooked += 1
        if seq.tokens_left == 0:
            seq.ended = True
            return
        slot = seq.slot
        with self._lock:
            self.running.append(seq)
        self._page_table[slot, :] = SCRATCH_PAGE
        self._page_table[slot, :len(seq.pages)] = seq.pages
        self._positions[slot] = seq.num_launched - 1
        if self._window:
            self._sync_window(slot, seq)

    def _book(self, flight: "_Flight",
              finished: Dict[str, List[int]]) -> None:
        """Read ``flight``'s tokens back and book them: the values that
        _take_off left open. A row found to have stopped on EOS is
        delivered here (_finish); if it is a row of the program launched
        behind this one too, its tokens there are dropped and its slot and
        pages released when THAT one is booked."""
        mixed = flight.kind == "mixed"
        B, K = self.max_batch, 1 if mixed else self.decode_chunk
        R, Tcap = self._mixed_shape(flight.n_rows)
        flight.blocked = not _landed(flight)
        with self.phase("engine.readback") as span:
            out = np.asarray(flight.out)           # ONE readback
            out = self._note_counters(out, R if mixed else K * B, span)
            if not mixed:
                out = out.reshape(K, B)
        self._clock(flight, span.wall1)
        with self.phase("engine.book"):
            if self._flight is flight:
                self._flight = None
            now = time.monotonic()
            stats, active, rows = self.stats, flight.active, flight.rows
            stats["h2d_arrays"] += 1                # its descriptor
            stats["ahead_dispatches"] += flight.ahead
            if mixed:
                chunk_tokens = sum(C for _, _, C in rows)
                stats["ragged_dispatches"] += 1
                disp_idx = stats["ragged_dispatches"]
                real = len(active) + chunk_tokens
                stats["ragged_real_tokens"] += real
                stats["ragged_slot_tokens"] += Tcap
                if flight.n_rows < self.prefill_rows:
                    stats["ragged_small_dispatches"] += 1
                stats["prefill_tokens"] += chunk_tokens
                stats["chunk_rows"] += len(rows)
                stats["chunk_rows_joined"] += flight.rows_joined
                stats["walk_tokens"] += real
                if self._fns.leaves_early:
                    stats["walk_tokens_left"] += chunk_tokens - len(rows)
                if self._has_state:
                    stats["state_resets"] += sum(
                        start == 0 for _, start, _ in rows)
                if active:
                    stats["decode_steps"] += 1
                    stats["decode_tokens"] += len(active)
            else:
                stats["decode_steps"] += K
                stats["decode_tokens"] += K * len(active)
                stats["decode_dispatches"] += 1
                disp_idx = stats["decode_dispatches"]
                real, Tcap = K * len(active), K * B
            if self._window:
                for key, n in zip(("page_steps_full", "page_steps_window"),
                                  flight.pages_in_use):
                    stats[key] += n
                stats["rows_inside_window"] += flight.inside_window
            self._step_meta = {
                "kind": flight.kind, "dispatch": disp_idx,
                "decode_rows": len(active), "real_tokens": real,
                "slot_tokens": Tcap, "ahead": flight.ahead}
            for slot, seq, take in active:
                seq.unbooked -= take
                seq.flights -= 1
                if seq.done:
                    # found ended when the program before was booked: its
                    # tokens here are dropped, its structures free now
                    if not seq.flights:
                        self._release(slot, seq)
                    continue
                toks = (int(out[slot]),) if mixed \
                    else out[:take, slot].tolist()
                self._tokens[slot] = toks[-1]
                if self._book_tokens(seq, toks, now, mixed=mixed):
                    self._finish(slot, seq, finished, now)
                elif self._window:
                    self._trim_window(seq, seq.num_tokens - 1)
            for seq in dict.fromkeys(seq for seq, _, _ in rows):
                seq.flights -= 1
            for j, (seq, start, C) in enumerate(rows):
                if self._window:
                    self._trim_window(seq, start + C)
                if seq.record is not None:
                    seq.record.note_chunk(now, C, disp_idx)
                if start + C >= len(seq.prompt):
                    self._postfill_book(seq, int(out[B + j]), finished, now)

    def _clock(self, flight: "_Flight", ended: int) -> None:
        """``flight``'s readback ended at ``ended``: how long its program
        ran, for the next ones of its kind and shape (_expected_end_ns).
        A readback that BLOCKED marks the program's end; one that did not
        says only that the program had ended by then, a bound that is
        kept where it is under the estimate (one that was too long comes
        down in a few programs). The program queued behind it, if any,
        began when this one ended."""
        flight.ended = ended
        key = (flight.kind, flight.n_rows)
        ran, runs = ended - flight.began, self._program_ns.get(key)
        if flight.blocked:
            if runs is None:
                runs = self._program_ns[key] = collections.deque(
                    maxlen=_RUNS_KEPT)
            runs.append(ran)
        elif runs and ran < _lower_quartile(runs):
            runs.append(ran)
        behind = self._flight
        if behind is not None and behind is not flight:
            behind.began = max(behind.began, ended)

    def _postfill_book(self, seq: SequenceState, first_tok: int,
                       finished: Dict[str, List[int]], now: float) -> None:
        """Book the token the program that ended ``seq``'s prompt sampled:
        finish (EOS / a 1-token budget), or go on as the decode row that
        _join made of it."""
        if seq.restore_generated:
            # recompute re-prefill done: unfold the prompt/generated
            # split (the folded re-prefill recomputed KV for every
            # generated token; first_tok is the NEXT token after them —
            # greedy sampling makes the continuation identical)
            seq.prompt = seq.prompt[:seq.n_prompt]
            seq.generated = list(seq.restore_generated)
            seq.restore_generated = []
        seq.unbooked -= 1
        slot = seq.slot
        self._tokens[slot] = first_tok
        # a request's first token, or (re-admitted after a preemption)
        # one more that a mixed step produced: only the NEW token
        # streams, restored tokens already did
        if self._book_tokens(seq, (first_tok,), now, mixed=True):
            # it is EOS (dropped) or it used up the token budget (kept)
            self._finish(slot, seq, finished, now)
        elif not seq.ended:
            # in the decode batch for more programs than those launched:
            # reserve the decode-loop headroom NOW, before next step's
            # admission scan can hand these pages to a younger request
            # (with a program in flight: what there is)
            self._ensure_pages(slot, seq, self.decode_chunk, finished,
                               ahead=self._flight is not None)

    def _book_tokens(self, seq: SequenceState, toks, now: float, *,
                     mixed: bool) -> Optional[str]:
        """Take the tokens ONE dispatch produced for ``seq`` — one from a
        mixed step (``mixed``: a decode row's next token, a finished
        prompt's first), up to decode_chunk from a decode loop — in the
        one order there is: an EOS token ends the sequence ("stop") and
        is dropped with whatever the block decoded past it; any other is
        appended and streamed (track_progress), and the one that uses up
        max_new_tokens ends the sequence ("length") and is kept; the
        request record gets ONE entry for the dispatch (the K-step loop
        is one device round trip — per-token host timestamps would be
        fiction), before the caller finishes the sequence, so e2e covers
        every token. Returns the finish reason, or None: the sequence
        goes on and the caller feeds it its last token."""
        n_new, reason = 0, None
        for tok in toks:
            if self.eos_token is not None and tok == self.eos_token:
                reason = "stop"
                break
            seq.generated.append(tok)
            n_new += 1
            if self.track_progress:
                self._progress.setdefault(seq.request_id, []).append(tok)
            if len(seq.generated) >= seq.max_new_tokens:
                reason = "length"
                break
        if seq.record is not None:
            if n_new:
                seq.record.note_decode(now, n_new, mixed=mixed)
            else:
                # sampled, but never emitted. Stops the TTFT clock of a
                # prompt whose FIRST sample is EOS; idempotent, so for a
                # sequence that has its first token it does nothing
                seq.record.note_first(now)
        if reason is not None:
            self._note_finish(seq.request_id, reason)
        return reason

    def _finish(self, slot: int, seq: SequenceState,
                finished: Dict[str, List[int]],
                now: Optional[float] = None) -> None:
        """Deliver ``seq``, ended: its tokens, its reason, its record.
        Its slot and pages go back at once, unless it is a row of a
        program still in flight (it stopped on EOS with the next program
        packed: stats late_retired_rows), which has written into them:
        that program's booking releases them."""
        if seq.request_id not in self._finish_reasons:
            self._note_finish(seq.request_id, "length")
        if self.request_log is not None and seq.record is not None:
            self.request_log.finish(
                seq.record, time.monotonic() if now is None else now,
                self._finish_reasons.get(seq.request_id, "length"))
        seq.done = True
        finished[seq.request_id] = list(seq.generated)
        if seq.flights:
            self.stats["late_retired_rows"] += 1
            self._blank_slot(slot)      # no row of the program after
        else:
            self._release(slot, seq)

    def _release(self, slot: int, seq: SequenceState) -> None:
        self._release_pages(seq.pages)
        if self._window:
            self._release_window(slot, seq)
        self._slots[slot] = None
        self._blank_slot(slot)
        seq.slot = None
        with self._lock:
            # a prompt that finishes on its first token never joined
            if seq in self.running:
                self.running.remove(seq)

    @staticmethod
    def _goes_on(seq: Optional[SequenceState]) -> bool:
        """Whether ``seq`` is a row of the next program too, as far as the
        launched ones say: it has not ended by length in one of them, nor
        been found ended."""
        return seq is not None and not seq.ended and not seq.done

    def _loop_may_follow(self, due: bool = False) -> bool:
        """Whether a decode loop may be queued behind the program in
        flight (_launch). Not while a request waits for a slot that the
        program in flight is known to free; else at once where no slot is
        free, and behind a free slot only once the flight is ``due``
        (step decides that: the late decision)."""
        if self.waiting and not all(map(self._goes_on, self._slots)):
            return False
        return due or None not in self._slots

    def _held_back(self) -> bool:
        """Whether all that keeps a program from being queued behind the
        one in flight is a free slot that an arrival could still take:
        no chunk work (a mixed step is queued at once), nobody waiting,
        a slot free. The launch is then made when the flight is due
        (step), not when it is booked."""
        return not self._chunking and not self.waiting \
            and None in self._slots

    def _expected_end_ns(self, flight: "_Flight") -> Optional[int]:
        """When ``flight`` should end, on the wall clock: its beginning
        and the lower quartile of the newest runs of its kind and shape
        that were clocked (_clock; _RUNS_KEPT says why that); None before
        one was."""
        runs = self._program_ns.get((flight.kind, flight.n_rows))
        return flight.began + _lower_quartile(runs) if runs else None

    def _lead_ns(self) -> int:
        """How long before the flight's expected end a held launch is
        due: the host's stretch in front of a launch (the longer of the
        newest mixed step's and the newest decode loop's: which of the
        two it will be is not known yet) and the margin."""
        return max(self._stretch_ns.values()) + _HOLD_MARGIN_NS

    def _next_may_follow(self) -> bool:
        """Whether the program the NEXT step launches may be queued behind
        the one just launched, from what the engine holds now (_launch's
        rule, before the next admission): chunk work, or a request and a
        slot for it, make it a mixed step; else it is a decode loop, which
        follows at once with every slot taken and, behind a free slot,
        when the program just launched is due (the next step holds for
        that, if a program of this kind and shape has been clocked). If
        not, this step books what it launched: the synchronous order, one
        program at a time."""
        if not self._run_ahead:
            return False
        if self._chunking or self.waiting and None in self._slots:
            return True
        return self._loop_may_follow() or self._held_back() \
            and self._expected_end_ns(self._flight) is not None

    def _decode_rows(self, headroom: int, finished: Dict[str, List[int]],
                     ahead: bool = False,
                     ) -> Optional[List[Tuple[int, SequenceState]]]:
        """Give every decode row its pages for ``headroom`` more tokens
        (a row the pool cannot serve is preempted or evicted), then list
        the rows that are left: [(slot, seq)]. ``ahead``: None instead,
        if some row cannot be served (_launch)."""
        for slot, seq in list(enumerate(self._slots)):
            if self._goes_on(seq) and not seq.prefilling \
                    and not self._ensure_pages(slot, seq, headroom,
                                               finished, ahead) and ahead:
                return None
        return [(i, s) for i, s in enumerate(self._slots)
                if self._goes_on(s) and not s.prefilling]

    def _ensure_pages(self, slot: int, seq: SequenceState, headroom: int,
                      finished: Dict[str, List[int]],
                      ahead: bool = False) -> bool:
        """Pages for num_launched + headroom (a decode block may overshoot
        past EOS/max_new_tokens into the sequence's own pages). False =
        evicted for lack of cache memory or, ``ahead`` (a program is in
        flight), left as it is: nothing is preempted then."""
        need = min(seq.pages_needed(self.page_size, headroom=headroom),
                   self.max_pages_per_seq)
        while len(seq.pages) < need:
            extra = self._alloc_pages(1)
            if extra is None:
                # out of cache: preempt by recompute (vLLM's default
                # preemption mode) — release this sequence's pages and
                # re-queue it at the waiting head; repeat offenders and
                # unsatisfiable sequences finish with reason "evict"
                if not ahead:
                    self._preempt(slot, seq, finished)
                return False
            self._page_table[slot, len(seq.pages)] = extra[0]
            seq.pages.extend(extra)
        if self._window:
            # the same positions' pages in the window group
            upto = min(seq.num_launched + headroom,
                       self.max_pages_per_seq * self.page_size) - 1
            added = self._extend_window(seq, upto, ahead)
            if added is None:
                return False
            if added:
                self._sync_window(slot, seq)
        return True

    #: recompute-preemptions allowed per sequence before it finishes
    #: "evict" — bounds ping-pong livelock under a pool that cannot hold
    #: the working set
    PREEMPT_CAP = 4

    def _preempt(self, slot: int, seq: SequenceState,
                 finished: Dict[str, List[int]]) -> None:
        """Recompute preemption: drop the sequence's pages and re-queue
        it at the waiting head. Its generated tokens FOLD into the prompt
        so the re-prefill (which rides the chunked path, prefix-matching
        the just-released pages when the cache holds them) recomputes
        their KV and re-samples the continuation; _postfill_book unfolds
        the split. Greedy argmax sampling makes the continuation
        identical to the uninterrupted one."""
        now = time.monotonic()
        if seq.record is not None:
            seq.record.note_stall(now)
        # pages to RE-ADMIT the folded sequence (+1 token headroom): if
        # even an empty pool cannot hold it, recompute can never help
        need_all = -(-(seq.num_tokens + 1) // self.page_size)
        if seq.preempt_count >= self.PREEMPT_CAP \
                or need_all > self.allocator.total_pages - 1:
            self._note_finish(seq.request_id, "evict")
            self._finish(slot, seq, finished)
            return
        seq.preempt_count += 1
        self.stats["preemptions"] += 1
        if seq.record is not None:
            seq.record.note_preempt(now)
        self._release_pages(seq.pages)
        seq.pages = []
        if self._window:
            self._release_window(slot, seq)
        self._slots[slot] = None
        self._blank_slot(slot)
        seq.slot = None
        seq.restore_generated = list(seq.generated)
        seq.prompt = seq.prompt + seq.generated
        seq.generated = []
        seq.num_computed = 0
        seq.cached_tokens = 0
        seq.prefilling = False
        with self._lock:
            if seq in self.running:
                self.running.remove(seq)
            # waiting HEAD: preempted work has strictly the oldest
            # enqueue_ts, and the aged-head admission guard keeps freed
            # pages flowing to it first
            self.waiting.insert(0, seq)

    # ----------------------------------------------------- pure decode

    def _pack_decode(self, active: List[Tuple[int, SequenceState]],
                     ) -> np.ndarray:
        """Fill the decode loop's descriptor and return it: every slot's
        token (-1: unread, on the device), position and pages as the
        engine holds them; a decode row's length is its position + 1, a
        free slot's 1."""
        buf, f, _ = next(self._decode_desc)
        f["tokens"][:] = np.where(self._unread(active), -1, self._tokens)
        f["positions"][:] = self._positions
        f["seq_lens"][:] = np.where(self._decode_mask(active),
                                    self._positions + 1, 1)
        f["page_table"][:] = self._page_table
        if self._window:
            f["page_table_win"][:] = self._page_table_win
            f["page_base_win"][:] = self._page_base_win
        return buf

    def _note_counters(self, out: np.ndarray, n_tokens: int, span):
        """Split a step program's flat output into its tokens and the
        counters behind them; the counters go into ``stats`` and onto the
        readback span of this dispatch."""
        if self._step_counters:
            got = dict(zip(self._step_counters,
                           out.reshape(-1)[n_tokens:].tolist()))
            for key, n in got.items():
                self.stats[key] += n
            if span.is_enabled():
                span.set_metadata(**got)
            out = out.reshape(-1)[:n_tokens]
        return out

    def drain_progress(self) -> Dict[str, List[int]]:
        """Tokens generated since the previous drain, per request id
        (requires track_progress = True)."""
        out, self._progress = self._progress, {}
        return out

    def _note_finish(self, rid: str, reason: str) -> None:
        self._finish_reasons[rid] = reason
        while len(self._finish_reasons) > 1024:
            self._finish_reasons.popitem(last=False)

    def finish_reason(self, rid: str) -> str:
        """Why rid stopped: "stop" (EOS) or "length" (token budget)."""
        return self._finish_reasons.pop(rid, "length")

    def _note_cached(self, rid: str, n: int) -> None:
        if n <= 0:
            return
        self._cached_counts[rid] = n
        while len(self._cached_counts) > 1024:
            self._cached_counts.popitem(last=False)

    def cached_tokens(self, rid: str) -> int:
        """Prompt tokens rid served from the prefix cache (pops)."""
        return self._cached_counts.pop(rid, 0)

    # ------------------------------------------------------------- metrics

    def _update_metrics(self, force: bool = False) -> None:
        """Engine gauges for the telemetry plane, throttled to ~1/s (the
        worker telemetry flush ships this process's registry to the
        head: /metrics exposition + `python -m ray_tpu top`)."""
        now = time.monotonic()
        dt = now - self._metrics_ts
        if dt < 1.0 and not force:
            return
        with self.phase("engine.metrics"):
            self._set_gauges(now, dt)

    def _set_gauges(self, now: float, dt: float) -> None:
        s, last = self.stats, self._metrics_last
        self._metrics_last = dict(s)
        self._metrics_ts = now
        allocatable = self.allocator.total_pages - 1   # page 0 = scratch
        self._g_kv_util.set(1.0 - self.allocator.num_free / allocatable)
        cached = s["cached_tokens"]
        denom = cached + s["prefill_tokens"]
        self._g_hit_rate.set(cached / denom if denom else 0.0)
        if dt > 0:
            self._g_prefill_tps.set(
                (s["prefill_tokens"] - last["prefill_tokens"]) / dt)
            self._g_decode_tps.set(
                (s["decode_tokens"] - last["decode_tokens"]) / dt)
        # ragged-step visibility: resident compiled programs (O(1) by
        # design) and the padding fraction of ragged token slots over
        # the gauge window
        programs = self.compiled_step_programs()
        self._g_programs.set(float(programs))
        # the O(1)-programs invariant (the seam's budget: decode loop,
        # page copy, the mixed step once a shape) was test-only until
        # now: in production, cross-check against the compile tracker
        # and raise ONE llm_compile_invariant_breach cluster-journal
        # event per excursion, carrying the tracker's signature diff —
        # the exact argument whose shape moved. Re-arms if the count
        # ever drops (fresh process / cache clear).
        budget = self._fns.program_budget
        if programs > budget:
            if not self._invariant_breached and self._tracker is not None:
                self._invariant_breached = True
                culprit = self._tracker.last_recompile("llm.") or {}
                self._tracker.stage_journal_event(
                    "llm_compile_invariant_breach",
                    programs=programs, budget=budget,
                    callable=culprit.get("name", ""),
                    diff=culprit.get("diff", []),
                    signature=culprit.get("signature", []))
        else:
            self._invariant_breached = False
        d_slots = s["ragged_slot_tokens"] - last["ragged_slot_tokens"]
        if d_slots > 0:
            d_real = s["ragged_real_tokens"] - last["ragged_real_tokens"]
            self._g_pad_waste.set(1.0 - d_real / d_slots)
        # of the engine thread's time over the gauge window, the share it
        # spent blocked on the device: the rest is the chip waiting for
        # this loop (or for requests)
        d_wall = sum(s[k] - last[k] for k in WALL_KEYS)
        if d_wall > 0:
            self._g_device_wait.set(
                (s["wall_ns_readback"] - last["wall_ns_readback"]) / d_wall)
        if self.request_log is not None:
            a_ttft, a_tpot = self.request_log.slo_attainment()
            self._g_slo_ttft.set(a_ttft)
            self._g_slo_tpot.set(a_tpot)
        self._g_preempts.set(float(s["preemptions"]))
        with self._lock:
            self._g_queue.set(len(self.waiting))

    # ------------------------------------------------------------ blocking

    def generate(self, prompt: List[int], max_new_tokens: int = 32,
                 ) -> List[int]:
        """Synchronous single-request helper (tests, simple use)."""
        rid = self.add_request(prompt, max_new_tokens)
        deadline = time.monotonic() + 600
        while time.monotonic() < deadline:
            done = self.step()
            if rid in done:
                return done[rid]
            if not self.has_work():
                raise RuntimeError(f"request {rid} vanished")
        raise TimeoutError("generate timed out")
