"""A host clock and a device that move only as a script says, for the tests
of the engine's late decision (llm/engine.py: step).

Not a test file. The engine decides from three things it can observe: its
wall clock (``engine._wall_ns``), whether the program in flight has landed
(``flight.out.is_ready()``) and how long it slept (``engine._sleep``). On
the CPU all three are the machine's timing. Here the clock ticks once a
read, a program runs a fixed time a kind on a device that runs one at a
time (queued behind the one before it), its tokens are ready when that
time is up and reading them before then moves the clock there, and a sleep
moves the clock by what was asked: a test sees the same order of launches,
holds and bookings on every machine, and can have a request arrive inside
a hold.
"""

import threading
import time

import numpy as np

from ray_tpu.llm import engine as engine_mod


class _Tokens:
    """A program's output on the scripted device: what the engine reads
    back (np.asarray) and asks (is_ready)."""

    def __init__(self, out, device, ends: int):
        self.out, self.device, self.ends = out, device, ends

    def is_ready(self) -> bool:
        return self.device.now >= self.ends

    def __array__(self, dtype=None, copy=None):
        self.device.now = max(self.device.now, self.ends)   # it blocks
        return np.asarray(self.out)


class ScriptedDevice:
    #: how long a program runs, ns; a tick: what one read of the clock
    #: costs the host (a launch's stretch is some ten of them)
    RUN_NS = {"mixed": 4_000_000, "decode": 9_000_000}
    TICK_NS = 10_000

    def __init__(self, monkeypatch, run_ns=None):
        self.run_ns = dict(self.RUN_NS, **(run_ns or {}))
        self.now = 1_000_000_000
        self.free_at = 0
        #: ns the device stood idle before a program, once it had run one
        self.idle_ns = 0
        #: every sleep: (the clock when it began, ns asked for)
        self.holds = []
        #: called with the hold's index inside every sleep: arrivals
        self.during_hold = None
        # the script is this thread's: an engine thread some other test's
        # fixture left running in the process keeps the real clock
        self.thread = threading.get_ident()
        monkeypatch.setattr(engine_mod, "_wall_ns", self.wall_ns)
        monkeypatch.setattr(engine_mod, "_sleep", self.sleep)

    def wall_ns(self) -> int:
        if threading.get_ident() != self.thread:
            return time.perf_counter_ns()
        self.now += self.TICK_NS
        return self.now

    def sleep(self, seconds: float) -> None:
        if threading.get_ident() != self.thread:
            return time.sleep(seconds)
        self.holds.append((self.now, round(seconds * 1e9)))
        if self.during_hold is not None:
            self.during_hold(len(self.holds) - 1)
        self.now += round(seconds * 1e9)

    def runs(self, eng) -> None:
        """``eng``'s step programs run on this device from now on."""
        def on_device(kind: str, program):
            def run(*args):
                out, *rest = program(*args)
                begins = max(self.now, self.free_at)
                if self.free_at:
                    self.idle_ns += begins - self.free_at
                self.free_at = begins + self.run_ns[kind]
                return (_Tokens(out, self, self.free_at), *rest)
            return run
        eng._fns.ragged_step = on_device("mixed", eng._fns.ragged_step)
        eng._fns.decode_loop = on_device("decode", eng._fns.decode_loop)
