"""Autoscaler tests (reference scope: autoscaler v2 reconciler +
cluster_utils.AutoscalingCluster over the fake node provider)."""

import time

import pytest

import ray_tpu as rt
from ray_tpu.autoscaler import Autoscaler, AutoscalingCluster


def test_bin_packing_counts_nodes():
    from ray_tpu.autoscaler import NodeTypeSpec
    a = Autoscaler.__new__(Autoscaler)
    a.node_types = {"cpu": NodeTypeSpec({"CPU": 2.0}, max_workers=8)}
    # 3 x 1-CPU shapes fit in 2 nodes; a 4-CPU shape can never fit
    assert a._nodes_needed([{"CPU": 1.0}] * 3) == {"cpu": 2}
    assert a._nodes_needed([{"CPU": 4.0}]) == {}
    assert a._nodes_needed([]) == {}
    assert a._nodes_needed([{"CPU": 2.0}, {"CPU": 2.0}]) == {"cpu": 2}


def test_bin_packing_heterogeneous_catalog():
    """Mixed demand bin-packs across a catalog (VERDICT r4 #6; reference:
    resource_demand_scheduler.py:102): CPU tasks open CPU hosts (best
    fit), gang bundles open exactly the slice shape that fits them,
    per-type max_workers caps planning, and a quiet type drains
    independently (covered by _reconcile's per-type quiet list)."""
    from ray_tpu.autoscaler import NodeTypeSpec
    a = Autoscaler.__new__(Autoscaler)
    v5e8 = {"TPU": 8.0, "CPU": 4.0, "TPU-v5e-8-head": 1.0}
    v5e16 = {"TPU": 16.0, "CPU": 8.0, "TPU-v5e-16-head": 1.0}
    a.node_types = {
        "cpu": NodeTypeSpec({"CPU": 4.0}, max_workers=4),
        "v5e-8": NodeTypeSpec(v5e8, max_workers=2),
        "v5e-16": NodeTypeSpec(v5e16, max_workers=2),
    }
    # pure CPU demand never opens a slice
    assert a._nodes_needed([{"CPU": 1.0}] * 6) == {"cpu": 2}
    # a small gang bundle picks the SMALL slice; a big one the big slice
    assert a._nodes_needed([{"TPU-v5e-8-head": 1.0}]) == {"v5e-8": 1}
    assert a._nodes_needed([{"TPU-v5e-16-head": 1.0}]) == {"v5e-16": 1}
    # mixed wave: right mix — one bin per gang, CPU tasks packed into
    # the cpu host AND the slices' spare CPUs (true bin-packing: a slice
    # host's free CPUs absorb CPU tasks before a second host opens)
    need = a._nodes_needed(
        [{"CPU": 2.0}, {"TPU-v5e-8-head": 1.0}, {"CPU": 2.0},
         {"TPU-v5e-16-head": 1.0}, {"CPU": 2.0}])
    assert need == {"cpu": 1, "v5e-8": 1, "v5e-16": 1}, need
    # plain chip demand prefers the slice it wastes least of
    assert a._nodes_needed([{"TPU": 8.0}]) == {"v5e-8": 1}
    # per-type cap: live + planned never exceeds max_workers
    need = a._nodes_needed([{"TPU-v5e-8-head": 1.0}] * 5,
                           live={"v5e-8": 1})
    assert need == {"v5e-8": 1}, need


def test_scale_up_then_down():
    cluster = AutoscalingCluster(
        head_resources={"CPU": 1.0},
        worker_node_type={"CPU": 2.0},
        max_workers=2,
        idle_timeout_s=6.0)
    try:
        rt.init(address=cluster.address, _system_config={
            "infeasible_grace_s": 60.0,
        })

        @rt.remote(num_cpus=2)
        def heavy(i):
            time.sleep(1.0)
            return i

        # head node has 1 CPU: these shapes are infeasible until the
        # autoscaler reacts to the recorded demand
        t0 = time.monotonic()
        out = rt.get([heavy.remote(i) for i in range(4)], timeout=120)
        assert sorted(out) == [0, 1, 2, 3]
        assert len(rt.nodes()) >= 2, "no worker node was launched"

        # drain: nodes idle past the timeout must be terminated. Pure
        # poll-with-deadline — the budget covers idle_timeout_s plus the
        # driver's fast-lease pool idle-drain (a pooled lease keeps the
        # worker non-idle until it drains back), with headroom for a
        # loaded CI host. Assert on the poll's own final observation —
        # re-reading after the loop could race a node flap.
        deadline = time.monotonic() + 90
        alive = [n for n in rt.nodes() if n["Alive"]]
        while time.monotonic() < deadline and len(alive) != 1:
            time.sleep(0.5)
            alive = [n for n in rt.nodes() if n["Alive"]]
        assert len(alive) == 1, f"idle nodes never scaled down: {alive}"
        rt.shutdown()
    finally:
        try:
            rt.shutdown()
        except Exception:
            pass
        cluster.shutdown()


def test_heterogeneous_mixed_demand_end_to_end():
    """One Autoscaler over a CPU-host + TPU-slice catalog: a mixed wave
    (CPU tasks + a gang PG) launches the right node mix, and each type
    drains independently once its demand clears (VERDICT r4 #6
    done-criterion)."""
    import os

    from ray_tpu.autoscaler import LocalNodeProvider, NodeTypeSpec
    from ray_tpu.runtime.cluster_backend import start_head, start_node
    from ray_tpu.runtime.protocol import RpcClient, RpcError
    from ray_tpu.util.placement_group import (placement_group,
                                              remove_placement_group)

    session = os.urandom(4).hex()
    head_proc, address = start_head(session)
    static_node = start_node(address, session, resources={"CPU": 1.0})
    probe = RpcClient(address, name="hetero-test")
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        try:
            if any(n["alive"] for n in probe.call("list_nodes", timeout=5)):
                break
        except RpcError:
            pass
        time.sleep(0.1)

    slice_shape = {"TPU": 8.0, "CPU": 4.0, "TPU-v5e-8-head": 1.0}
    provider = LocalNodeProvider(address, session)
    scaler = Autoscaler(
        address, provider,
        node_types={
            "cpu": NodeTypeSpec({"CPU": 2.0}, max_workers=2),
            "v5e-8": NodeTypeSpec(slice_shape, max_workers=1),
        },
        idle_timeout_s=3.0, poll_period_s=0.3).start()
    try:
        rt.init(address=address,
                _system_config={"infeasible_grace_s": 60.0})

        @rt.remote(num_cpus=2)
        def heavy(i):
            time.sleep(0.5)
            return i

        pg = placement_group([{"TPU-v5e-8-head": 1}],
                             strategy="STRICT_PACK")
        out = rt.get([heavy.remote(i) for i in range(4)], timeout=120)
        assert sorted(out) == [0, 1, 2, 3]
        assert pg.wait(60), "gang bundle never placed"
        # the right MIX: at least one cpu node and exactly one slice
        types = {t for t, _ in scaler._handles}
        assert "cpu" in types and "v5e-8" in types, scaler._handles
        slice_nodes = [n for n in rt.nodes() if n["Alive"]
                       and n["Resources"].get("TPU-v5e-8-head")]
        assert len(slice_nodes) == 1, slice_nodes

        # demand clears -> BOTH types drain back to their min (0)
        remove_placement_group(pg)
        deadline = time.monotonic() + 45
        while time.monotonic() < deadline:
            alive = [n for n in rt.nodes() if n["Alive"]]
            if len(alive) == 1:   # only the static head node remains
                break
            time.sleep(0.5)
        alive = [n for n in rt.nodes() if n["Alive"]]
        assert len(alive) == 1, \
            f"idle nodes never scaled down: {[n['Resources'] for n in alive]}"
    finally:
        try:
            rt.shutdown()
        except Exception:
            pass
        scaler.stop()
        probe.close()
        for proc in (static_node, head_proc):
            try:
                proc.terminate()
                proc.wait(timeout=5)
            except Exception:
                proc.kill()


def test_tpu_slice_gang_scale_up_and_drain():
    """A pending STRICT_PACK slice-head PG drives ONE slice creation
    through the (mocked) GCE TPU API; once the slice 'joins' and the PG
    is removed, idle drain deletes the slice via the API (VERDICT #6
    done-criterion; reference: autoscaler/_private/gcp/node_provider.py)."""
    from ray_tpu.providers.gcp_tpu import TpuVmNodeProvider
    from ray_tpu.runtime.cluster_backend import start_head, start_node
    from ray_tpu.runtime.protocol import RpcClient, RpcError
    from ray_tpu.util.placement_group import (placement_group,
                                              remove_placement_group)
    import os

    class FakeGceHttp:
        def __init__(self):
            self.requests = []

        def request(self, method, url, body=None):
            self.requests.append((method, url, body))
            return {"name": "operations/fake-op", "done": True}

    session = os.urandom(4).hex()
    head_proc, address = start_head(session)
    static_node = start_node(address, session, resources={"CPU": 1.0})
    probe = RpcClient(address, name="gang-test")
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        try:
            if any(n["alive"] for n in probe.call("list_nodes", timeout=5)):
                break
        except RpcError:
            pass
        time.sleep(0.1)

    fake = FakeGceHttp()
    provider = TpuVmNodeProvider(
        project="proj", zone="us-central2-b",
        accelerator_type="v5litepod-8", runtime_version="tpu-ubuntu2204",
        head_addr=address, session=session, http=fake)
    slice_shape = TpuVmNodeProvider.slice_node_type("v5litepod-8")
    scaler = Autoscaler(address, provider, node_type=slice_shape,
                        max_workers=1, idle_timeout_s=2.0,
                        poll_period_s=0.3).start()
    joined = None
    try:
        rt.init(address=address,
                _system_config={"infeasible_grace_s": 60.0})
        pg = placement_group([{"TPU-v5e-8-head": 1}],
                             strategy="STRICT_PACK")
        # pending gang bundle -> exactly one slice-create API call. Wait
        # for the POST itself: the first request is the restart
        # reconcile's GET (list_live), the create a pass or more later
        def posts():
            return [r for r in fake.requests if r[0] == "POST"]
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and not posts():
            time.sleep(0.1)
        creates = posts()
        assert len(creates) == 1, fake.requests
        method, url, body = creates[0]
        assert "tpu.googleapis.com" in url and "nodes?nodeId=rtpu-" in url
        assert body["acceleratorType"] == "v5litepod-8"
        assert address in body["metadata"]["startup-script"]
        # capped at max_workers: no second create even while pending
        time.sleep(1.0)
        assert len(posts()) == 1

        # 'slice boots': stand in for the TPU VM with a local daemon that
        # registers under the provisioned node identity + slice resources
        node_id = scaler._handles[0][1].rtpu_node_id
        joined = start_node(address, session, resources=slice_shape,
                            node_id=node_id)
        assert pg.wait(30), "gang PG never placed on the joined slice"
        remove_placement_group(pg)

        # idle past the timeout -> the slice is RELEASED via the API
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if any(r[0] == "DELETE" for r in fake.requests):
                break
            time.sleep(0.2)
        deletes = [r for r in fake.requests if r[0] == "DELETE"]
        assert len(deletes) == 1, fake.requests
        assert deletes[0][1].endswith(url.split("?nodeId=")[1]), deletes
    finally:
        rt.shutdown()
        scaler.stop()
        probe.close()
        for proc in (joined, static_node, head_proc):
            if proc is None:
                continue
            try:
                proc.terminate()
                proc.wait(timeout=5)
            except Exception:
                proc.kill()
