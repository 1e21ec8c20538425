"""Crash-consistent instance lifecycle tests (reference scope:
autoscaler v2 instance_manager + instance_storage semantics).

Covers the PR-11 tentpole done-criteria: every launch drives a
persisted, journaled REQUESTED→ALLOCATED→RUNNING→DRAINING→TERMINATED
record; SIGKILLing the autoscaler mid-launch and restarting it converges
to zero orphans, asserted against the provider's live-handle ledger AND
the journaled transition history; a double restart journals no duplicate
transitions.
"""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

from ray_tpu.runtime import instance_manager as im


# ----------------------------------------------------------- unit: machine


class _Journal:
    """Capture journal emissions as (event_type, fields) tuples."""

    def __init__(self):
        self.events = []

    def __call__(self, etype, **fields):
        self.events.append((etype, fields))

    def types(self, node_id=None):
        return [t for t, f in self.events
                if node_id is None or f.get("node_id") == node_id]


def test_happy_path_transitions_persist_and_journal():
    store = im.MemoryInstanceStore()
    j = _Journal()
    mgr = im.InstanceManager(store, journal=j)

    rec = mgr.request("cpu", {"CPU": 2.0}, "n1")
    assert rec.state == im.REQUESTED
    assert rec.trace_id, "request() must mint a trace id"
    assert store.load_all()["n1"]["state"] == im.REQUESTED

    mgr.transition("n1", im.ALLOCATED, metadata={"pid": 123})
    assert store.load_all()["n1"]["metadata"] == {"pid": 123}
    mgr.transition("n1", im.RUNNING)
    assert mgr.live_counts() == {"cpu": 1}
    mgr.transition("n1", im.DRAINING)
    # DRAINING holds no capacity: a drain must not block a scale-up
    assert mgr.live_counts() == {}
    mgr.transition("n1", im.TERMINATED)

    # terminal states delete the persisted key; the journal IS the history
    assert store.load_all() == {}
    assert j.types("n1") == ["instance_requested", "instance_allocated",
                             "instance_running", "instance_draining",
                             "instance_terminated"]
    # one trace id per instance, stamped on every transition
    traces = {f["trace_id"] for _, f in j.events}
    assert traces == {rec.trace_id}
    assert [s for s, _ in rec.history] == [
        im.REQUESTED, im.ALLOCATED, im.RUNNING, im.DRAINING, im.TERMINATED]


def test_invalid_transitions_rejected():
    mgr = im.InstanceManager(im.MemoryInstanceStore())
    mgr.request("cpu", {"CPU": 1.0}, "n1")
    with pytest.raises(im.InvalidTransition):
        mgr.transition("n1", im.DRAINING)   # REQUESTED cannot drain
    with pytest.raises(im.InvalidTransition):
        mgr.transition("n1", im.DEAD)       # never ran, cannot be DEAD
    mgr.transition("n1", im.LAUNCH_FAILED)
    with pytest.raises(im.InvalidTransition):
        mgr.transition("n1", im.RUNNING)    # terminal states are final
    with pytest.raises(KeyError):
        mgr.transition("ghost", im.RUNNING)


def test_reconcile_adopt_orphan_dead_drained_unrecorded():
    """All five reconcile verdicts, against a store 'restored' from a
    previous incarnation."""
    store = im.MemoryInstanceStore()
    seeder = im.InstanceManager(store)
    seeder.request("cpu", {"CPU": 1.0}, "adopt-me")       # will register
    seeder.request("cpu", {"CPU": 1.0}, "orphan-me")      # never registers
    r = seeder.request("cpu", {"CPU": 1.0}, "was-running")
    seeder.transition(r.node_id, im.ALLOCATED)
    seeder.transition(r.node_id, im.RUNNING)
    d = seeder.request("cpu", {"CPU": 1.0}, "was-draining")
    seeder.transition(d.node_id, im.ALLOCATED)
    seeder.transition(d.node_id, im.RUNNING)
    seeder.transition(d.node_id, im.DRAINING)

    j = _Journal()
    mgr = im.InstanceManager(store, journal=j)
    assert mgr.load() == 4
    killed = []
    actions = mgr.reconcile(
        registered={"adopt-me"},
        provider_live={"ghost-id": {"pid": 999999}},
        terminate=lambda rec: killed.append(rec.node_id),
        orphan_grace_s=0.0)

    assert actions["adopted"] == ["adopt-me"]
    assert actions["orphaned"] == ["orphan-me"]
    assert actions["dead"] == ["was-running"]
    assert actions["drained"] == ["was-draining"]
    assert actions["unrecorded"] == ["ghost-id"]
    assert sorted(killed) == ["ghost-id", "orphan-me"]
    assert mgr.get("adopt-me").state == im.RUNNING
    assert mgr.get("orphan-me").state == im.TERMINATED
    assert mgr.get("was-running").state == im.DEAD
    assert mgr.get("was-draining").state == im.TERMINATED
    assert "instance_unrecorded" in [t for t, _ in j.events]
    # only the adopted record still persists (it is live)
    assert set(store.load_all()) == {"adopt-me"}


def test_reconcile_grace_leaves_young_launches_pending():
    store = im.MemoryInstanceStore()
    seeder = im.InstanceManager(store)
    seeder.request("cpu", {"CPU": 1.0}, "young")
    mgr = im.InstanceManager(store)
    mgr.load()
    actions = mgr.reconcile(registered=set(), orphan_grace_s=60.0)
    assert actions["pending"] == ["young"]
    assert mgr.get("young").state == im.REQUESTED


def test_reconcile_idempotent_no_duplicate_journal():
    """A second reconcile over converged state journals nothing — a
    double autoscaler restart must not duplicate transition history."""
    store = im.MemoryInstanceStore()
    seeder = im.InstanceManager(store)
    seeder.request("cpu", {"CPU": 1.0}, "n1")
    j = _Journal()
    mgr = im.InstanceManager(store, journal=j)
    mgr.load()
    mgr.reconcile(registered={"n1"}, orphan_grace_s=0.0)
    n_events = len(j.events)
    assert j.types("n1") == ["instance_running"]
    again = mgr.reconcile(registered={"n1"}, orphan_grace_s=0.0)
    assert len(j.events) == n_events, "idempotent reconcile re-journaled"
    assert all(not v for v in again.values())

    # ...and a second load() must not clobber the in-memory RUNNING state
    # with the stale persisted copy
    mgr.load()
    assert mgr.get("n1").state == im.RUNNING


def test_instance_manager_imports_without_jax():
    """CI-hygiene satellite: the autoscaler daemon imports this module;
    it must never pull in the accelerator stack (same contract as
    llm/request_log.py)."""
    code = ("import sys\n"
            "import ray_tpu.runtime.instance_manager\n"
            "import ray_tpu.util.fault_injector\n"
            "import ray_tpu.autoscaler\n"
            "print('jax' in sys.modules)\n")
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False", out.stdout


# ----------------------------------------------- integration: full journal


#: deadline for anything that waits on a process to boot (a python
#: import, a daemon registering, a reconcile pass): seconds alone, tens of
#: seconds beside five other xdist workers' clusters
_BOOT_S = 120


def _wait(predicate, timeout, period=0.2, desc="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        val = predicate()
        if val:
            return val
        time.sleep(period)
    raise AssertionError(f"timed out waiting for {desc}")


def _boot_head(session):
    from ray_tpu.runtime.cluster_backend import start_head
    from ray_tpu.runtime.protocol import RpcClient, RpcError
    head_proc, address = start_head(session)
    probe = RpcClient(address, name="lifecycle-test")

    def up():
        try:
            probe.call("list_nodes", timeout=5)
            return True
        except RpcError:
            return False
    _wait(up, _BOOT_S, desc="head boot")
    return head_proc, address, probe


def _instance_events(probe, node_id):
    evs = probe.call("events_dump", {}, timeout=10)
    return [e for e in evs if e.get("node_id") == node_id
            and (e["type"].startswith("instance_")
                 or e["type"] == "node_launch_failed")]


def test_full_lifecycle_journal_chain():
    """One launch end to end: `events` replays the whole
    REQUESTED→ALLOCATED→RUNNING→DRAINING→TERMINATED chain in order, every
    event carrying the instance's single trace id, and the scale-up /
    scale-down decisions join on that same trace."""
    from ray_tpu.autoscaler import (Autoscaler, LocalNodeProvider,
                                    NodeTypeSpec)

    session = os.urandom(4).hex()
    head_proc, address, probe = _boot_head(session)
    scaler = Autoscaler(
        address, LocalNodeProvider(address, session),
        node_types={"w": NodeTypeSpec({"CPU": 1.0}, max_workers=1,
                                      min_workers=1)},
        idle_timeout_s=1.0, poll_period_s=0.2).start()
    try:
        # min_workers floor launches with no demand; wait for RUNNING
        rec = _wait(
            lambda: next((r for r in scaler.im.records(im.RUNNING)), None),
            45, desc="node to reach RUNNING")
        nid = rec.node_id
        # the persisted record rides the head's KV table while live
        assert probe.call("kv_get", {"key": im.KV_PREFIX + nid},
                          timeout=5)["state"] == im.RUNNING

        # drop the floor -> idle drain -> DRAINING -> TERMINATED
        scaler.node_types["w"].min_workers = 0
        _wait(lambda: scaler.im.get(nid).state == im.TERMINATED, 30,
              desc="idle drain to TERMINATED")

        chain = _instance_events(probe, nid)
        assert [e["type"] for e in chain] == [
            "instance_requested", "instance_allocated", "instance_running",
            "instance_draining", "instance_terminated"], chain
        traces = {e["trace_id"] for e in chain}
        assert len(traces) == 1 and rec.trace_id in traces
        # scaling decisions join the same trace
        decisions = [e for e in probe.call("events_dump", {}, timeout=10)
                     if e["type"].startswith("autoscaler_scale")
                     and e.get("node_id") == nid]
        assert {e["type"] for e in decisions} == {"autoscaler_scale_up",
                                                 "autoscaler_scale_down"}
        assert all(e["trace_id"] == rec.trace_id for e in decisions)
        # terminal record left no KV residue and no live provider handle
        assert probe.call("kv_keys", {"prefix": im.KV_PREFIX},
                          timeout=5) == []
        assert scaler.provider.list_live() == {}
    finally:
        scaler.stop()
        probe.close()
        head_proc.terminate()
        try:
            head_proc.wait(timeout=5)
        except Exception:
            head_proc.kill()


# ----------------------------------------------------- chaos: crash launch


def _spawn_runner(address, opts, log_path, fault=""):
    """The autoscaler as its own process, its output (and that of the node
    daemons it launches, which inherit it) appended to ``log_path``: a
    pipe would close under the daemons when the test drops a killed
    runner's handle, and nobody drains it while they live."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    if fault:
        env["RTPU_FAULT_INJECT"] = fault
    else:
        env.pop("RTPU_FAULT_INJECT", None)
    with open(log_path, "a", encoding="utf-8") as log:
        return subprocess.Popen(
            [sys.executable, "-m", "ray_tpu.autoscaler", address,
             json.dumps(opts)],
            env=env, stdout=log, stderr=subprocess.STDOUT)


def _restart_reconciles(probe):
    """How many restarts have replayed persisted records so far (each
    journals one ``autoscaler_restart_reconcile`` when it finds any)."""
    return len(probe.call("events_dump",
                          {"type": "autoscaler_restart_reconcile"},
                          timeout=10))


def _node_state(probe, ledger_path, log_path):
    """What a failed liveness assertion needs to be read: the provider's
    ledger, each owned pid's state, the head's node table and the tail
    of the runners' (and their daemons') output."""
    def read(path):
        try:
            with open(path, encoding="utf-8", errors="replace") as f:
                return f.read()
        except OSError as e:
            return repr(e)
    ledger = read(ledger_path)
    pids = {}
    for line in ledger.splitlines():
        try:
            pid = json.loads(line)["pid"]
        except (ValueError, KeyError):
            continue
        status = read(f"/proc/{pid}/status")
        pids[pid] = next((ln for ln in status.splitlines()
                          if ln.startswith("State:")), status)
    return {"ledger": ledger, "pids": pids,
            "nodes": probe.call("list_nodes", timeout=5),
            "log_tail": read(log_path)[-3000:]}


def _kill_ledger_pids(ledger_path):
    try:
        with open(ledger_path, encoding="utf-8") as f:
            for line in f:
                try:
                    entry = json.loads(line)
                except ValueError:
                    continue
                if entry.get("op") == "create":
                    try:
                        os.kill(int(entry["pid"]), signal.SIGKILL)
                    except (ProcessLookupError, PermissionError):
                        pass
    except FileNotFoundError:
        pass


@pytest.mark.chaos
def test_sigkill_mid_launch_restart_converges_no_orphans(tmp_path):
    """The tentpole crash-consistency criterion: SIGKILL the autoscaler
    BETWEEN create_node and the ALLOCATED persist; restart it; the
    write-ahead REQUESTED record re-adopts the node that registered while
    the autoscaler was down. Zero orphans asserted against the provider's
    live-handle ledger AND the journaled transition history; a second
    kill+restart journals no duplicate transitions."""
    from ray_tpu.autoscaler import LocalNodeProvider

    session = os.urandom(4).hex()
    ledger = str(tmp_path / "provider.ledger")
    log = str(tmp_path / "runners.log")
    opts = {"session": session, "ledger_path": ledger,
            "poll_period_s": 0.2,
            "node_types": {"w": {"resources": {"CPU": 1.0},
                                 "max_workers": 1, "min_workers": 1}}}
    head_proc, address, probe = _boot_head(session)
    runner = None
    try:
        # --- crash: dies by SIGKILL right after the provider create
        runner = _spawn_runner(address, opts, log,
                               fault="autoscaler.post_create=kill9")
        assert runner.wait(timeout=_BOOT_S) == -signal.SIGKILL
        keys = _wait(lambda: probe.call(
            "kv_keys", {"prefix": im.KV_PREFIX}, timeout=5), 10,
            desc="write-ahead record")
        assert len(keys) == 1
        record = probe.call("kv_get", {"key": keys[0]}, timeout=5)
        nid = record["node_id"]
        # died before ALLOCATED could persist — that is the crash window
        assert record["state"] == im.REQUESTED
        # ...but the provider ledger already owns the subprocess
        provider = LocalNodeProvider(address, session, ledger_path=ledger)
        assert set(provider.list_live()) == {nid}
        # the launched daemon registers with the head on its own
        _wait(lambda: any(n["node_id"] == nid and n["alive"]
                          for n in probe.call("list_nodes", timeout=5)),
              _BOOT_S, desc="orphan node registration")

        # --- restart: reconcile must adopt, not orphan-kill or relaunch
        runner = _spawn_runner(address, opts, log)
        _wait(lambda: probe.call(
            "kv_get", {"key": im.KV_PREFIX + nid},
            timeout=5)["state"] == im.RUNNING, _BOOT_S,
            desc="adoption to RUNNING")
        types = [e["type"] for e in _instance_events(probe, nid)]
        assert types == ["instance_requested", "instance_running"], types
        traces = {e["trace_id"] for e in _instance_events(probe, nid)}
        assert len(traces) == 1
        # zero orphans: provider owns exactly the adopted node, nothing
        # was terminated, nothing unrecorded, no second launch
        assert set(provider.list_live()) == {nid}, \
            _node_state(probe, ledger, log)
        assert probe.call("kv_keys", {"prefix": im.KV_PREFIX},
                          timeout=5) == [im.KV_PREFIX + nid]
        evs = probe.call("events_dump", {}, timeout=10)
        assert not [e for e in evs if e["type"] in
                    ("instance_terminated", "instance_unrecorded",
                     "node_launch_failed")], evs

        # --- double restart: idempotency, no duplicate journal entries
        seen = _restart_reconciles(probe)
        runner.send_signal(signal.SIGKILL)
        runner.wait(timeout=30)
        runner = _spawn_runner(address, opts, log)
        # the pass that could duplicate a transition is the restarted
        # runner's FIRST, which replays the persisted record and journals
        # that it did: wait for it (a loaded machine can spend a fixed
        # sleep importing), then let a few ordinary passes follow
        _wait(lambda: _restart_reconciles(probe) > seen, _BOOT_S,
              desc="the second restart's reconcile")
        time.sleep(1.0)
        assert runner.poll() is None, _node_state(probe, ledger, log)
        types = [e["type"] for e in _instance_events(probe, nid)]
        assert types == ["instance_requested", "instance_running"], \
            f"double restart duplicated transitions: {types}"
        assert set(provider.list_live()) == {nid}, \
            _node_state(probe, ledger, log)
    finally:
        if runner is not None:
            runner.kill()
        _kill_ledger_pids(ledger)
        probe.close()
        head_proc.terminate()
        try:
            head_proc.wait(timeout=5)
        except Exception:
            head_proc.kill()


@pytest.mark.chaos
def test_requested_orphan_terminated_after_restart(tmp_path):
    """Crash BEFORE create_node: the write-ahead REQUESTED record exists
    but no machine does. The restarted autoscaler must terminate the
    orphan record past the grace window and journal it — no handle leak,
    no zombie KV entry."""
    session = os.urandom(4).hex()
    ledger = str(tmp_path / "provider.ledger")
    base = {"session": session, "ledger_path": ledger,
            "poll_period_s": 0.2,
            "config": {"instance_orphan_grace_s": 0.5}}
    opts1 = {**base, "node_types": {"w": {"resources": {"CPU": 1.0},
                                          "max_workers": 1,
                                          "min_workers": 1}}}
    # the restarted incarnation keeps min_workers=0 so the orphan kill is
    # the ONLY lifecycle activity to assert on
    opts2 = {**base, "node_types": {"w": {"resources": {"CPU": 1.0},
                                          "max_workers": 1,
                                          "min_workers": 0}}}
    log = str(tmp_path / "runners.log")
    head_proc, address, probe = _boot_head(session)
    runner = None
    try:
        runner = _spawn_runner(address, opts1, log,
                               fault="autoscaler.pre_create=kill9")
        assert runner.wait(timeout=_BOOT_S) == -signal.SIGKILL
        keys = _wait(lambda: probe.call(
            "kv_keys", {"prefix": im.KV_PREFIX}, timeout=5), 10,
            desc="write-ahead record")
        nid = probe.call("kv_get", {"key": keys[0]}, timeout=5)["node_id"]
        time.sleep(1.0)  # age the record past the 0.5s orphan grace

        runner = _spawn_runner(address, opts2, log)
        _wait(lambda: probe.call("kv_keys", {"prefix": im.KV_PREFIX},
                                 timeout=5) == [], _BOOT_S,
              desc="orphan record cleanup")
        chain = _instance_events(probe, nid)
        assert [e["type"] for e in chain] == [
            "instance_requested", "instance_terminated"], chain
        assert chain[-1].get("detail") == "orphaned-launch"
        # nothing was ever created: the ledger owns no live pid
        from ray_tpu.autoscaler import LocalNodeProvider
        assert LocalNodeProvider(address, session,
                                 ledger_path=ledger).list_live() == {}
    finally:
        if runner is not None:
            runner.kill()
        _kill_ledger_pids(ledger)
        probe.close()
        head_proc.terminate()
        try:
            head_proc.wait(timeout=5)
        except Exception:
            head_proc.kill()


@pytest.mark.chaos
def test_stillborn_node_journaled_as_launch_failed(fault_injector):
    """Satellite: a launched daemon that dies before registering becomes
    LAUNCH_FAILED, journaled as ``node_launch_failed`` with node_type and
    exit info — visible in `events`, not a silent log line."""
    from ray_tpu.autoscaler import (Autoscaler, LocalNodeProvider,
                                    NodeTypeSpec)

    session = os.urandom(4).hex()
    head_proc, address, probe = _boot_head(session)
    # armed via env so only the autoscaler-spawned daemons (which inherit
    # it) die at boot; the already-running head is unaffected
    os.environ[fault_injector.ENV_VAR] = "node.boot=exit:3"
    scaler = Autoscaler(
        address, LocalNodeProvider(address, session),
        node_types={"w": NodeTypeSpec({"CPU": 1.0}, max_workers=1,
                                      min_workers=1)},
        idle_timeout_s=5.0, poll_period_s=0.2).start()
    try:
        failed = _wait(
            lambda: [e for e in probe.call("events_dump",
                                           {"type": "node_launch_failed"},
                                           timeout=5)
                     if e.get("detail") == "died-pre-register"],
            45, desc="node_launch_failed journal entry")
        ev = failed[0]
        assert ev["node_type"] == "w"
        assert ev["exit_info"] == "3"
        assert ev["trace_id"]
        rec = scaler.im.get(ev["node_id"])
        assert rec is not None and rec.state == im.LAUNCH_FAILED
    finally:
        os.environ.pop(fault_injector.ENV_VAR, None)
        scaler.stop()
        probe.close()
        head_proc.terminate()
        try:
            head_proc.wait(timeout=5)
        except Exception:
            head_proc.kill()
