"""ServeController — deployment reconciliation + autoscaling.

Role-equivalent to the reference's controller stack (reference:
serve/_private/controller.py:84 with run_control_loop at :369,
deployment_state.py:2339 DeploymentStateManager reconcile,
autoscaling_state.py:82 + serve/autoscaling_policy.py:85): a single named
actor holds target state per deployment; a reconcile thread converges
actual replica actors to the target (start missing, stop extra, replace
dead) and adjusts the target from observed queue lengths when an
autoscaling config is present.

Two autoscaling policies:

* the default queue policy (``target_ongoing_requests``), and
* ``policy: "slo"`` — the serving control loop: windowed TTFT/TPOT SLO
  attainment (read from the head's request table, fed by the engines'
  flight recorders) drives replica count up on breach and drains down on
  sustained headroom; when attainment keeps falling AT max replicas a
  degradation ladder tightens engine admission (``set_overload_level``
  scales the engine's ``step_token_budget`` down per level) and finally sheds
  requests to a cheaper multiplexed model via the routing table's
  ``shed_to`` field. Every decision is journaled into the head's
  ClusterEventJournal so ``events --follow`` replays a whole storm.
"""

from __future__ import annotations

import logging
import threading
import time
import uuid
from typing import Any, Dict, List, Optional

import ray_tpu
from ray_tpu.exceptions import ActorError
from ray_tpu.serve.replica import Replica
from ray_tpu.util import startup_clocks, trace_context

logger = logging.getLogger("ray_tpu.serve")

CONTROLLER_NAME = "__serve_controller__"
SERVE_NAMESPACE = "serve"


def windowed_attainment(records: List[dict], now_wall: float,
                        window_s: float, ttft_target_s: float,
                        tpot_target_s: float) -> "tuple[float, int]":
    """(attainment, n) over flight-recorder request records (wire dicts
    from the head's ``requests_dump``) that FINISHED within the trailing
    window. A request attains when its TTFT meets the target AND its
    TPOT (when it produced >1 token) does too. No finished traffic in
    the window reads as 1.0 — an idle service is not in breach."""
    n = met = 0
    for r in records:
        if not r.get("done"):
            continue
        t0, e2e = r.get("t0_wall"), r.get("e2e")
        if t0 is None or e2e is None or t0 + e2e < now_wall - window_s:
            continue
        n += 1
        ttft, tpot = r.get("ttft"), r.get("tpot")
        if (ttft is None or ttft <= ttft_target_s) and \
                (tpot is None or tpot <= tpot_target_s):
            met += 1
    return (met / n if n else 1.0), n


class _DeploymentState:
    def __init__(self, name: str, spec: Dict[str, Any]):
        self.name = name
        self.spec = spec
        self.target_replicas = spec["num_replicas"]
        self.replicas: List[Any] = []          # live ActorHandles
        self.ready: set = set()                # actor-id hexes that passed
        #                                        a health probe (constructed)
        # actor-id hex -> the serve.replica_start span in hand, of a
        # replica no health probe has found ready yet (_start_replica)
        self.starting: Dict[str, Dict[str, Any]] = {}
        self.draining: List[Any] = []          # scale-down victims finishing
        self.drain_deadline: Dict[str, float] = {}
        self.version = 0
        self.last_scale_ts = 0.0
        self.last_health_ts = 0.0
        self.deleted = False
        # crash-loop damping (reference: DeploymentState DEPLOY_FAILED
        # after bounded attempts): consecutive replica deaths back off the
        # respawn exponentially and eventually mark the deployment
        # unhealthy instead of burning a worker process per tick.
        self.consecutive_failures = 0
        self.backoff_until = 0.0
        self.unhealthy_reason: Optional[str] = None
        # SLO control-loop state (autoscaling_config policy == "slo")
        self.overload_level = 0          # degradation ladder position
        self.shed_to = ""                # routing-table shed target
        self.slo_breach_streak = 0       # consecutive breaches AT max
        self.slo_ok_streak = 0           # consecutive over-target evals
        self.last_slo_eval = 0.0


class ServeController:
    """Actor body. Created with max_concurrency > 1 so the reconcile
    thread runs beside RPC handling."""

    RECONCILE_PERIOD_S = 0.25

    def __init__(self):
        import collections
        self._lock = threading.RLock()
        self._deployments: Dict[str, _DeploymentState] = {}
        self._apps: Dict[str, list] = {}  # app name -> deployment names
        self._proxy = None
        self._proxy_port: Optional[int] = None
        self._stop = threading.Event()
        # push-based routing (reference: serve LongPollHost,
        # _private/long_poll.py:204): every routing-table version bump is
        # published on the cluster pubsub broker; routers subscribe and
        # refresh IMMEDIATELY instead of waiting out a staleness window.
        # Events queue under the lock and publish off-thread (publishing
        # is an RPC to the head).
        self._route_events = collections.deque()
        self._route_kick = threading.Event()
        threading.Thread(target=self._route_publish_loop, daemon=True,
                         name="serve-routes-pub").start()
        self._thread = threading.Thread(target=self._reconcile_loop,
                                        daemon=True, name="serve-reconcile")
        self._thread.start()

    # single definition lives in router.py (subscriber side)
    from ray_tpu.serve.router import ROUTE_TOPIC as ROUTE_TOPIC

    def _bump_version(self, st: "_DeploymentState") -> None:
        """Routing table changed (call under self._lock): bump + queue a
        push notification for subscribed routers."""
        st.version = st.version + 1
        self._route_events.append((st.name, st.version))
        self._route_kick.set()

    def _route_publish_loop(self) -> None:
        from ray_tpu.util import pubsub
        while not self._stop.is_set():
            self._route_kick.wait(timeout=0.5)
            self._route_kick.clear()
            latest: Dict[str, int] = {}
            while self._route_events:
                name, v = self._route_events.popleft()
                latest[name] = max(v, latest.get(name, -1))
            for name, v in latest.items():
                try:
                    pubsub.publish(self.ROUTE_TOPIC,
                                   {"deployment": name, "version": v})
                except Exception:  # noqa: BLE001 — routers fall back to
                    pass           # the lazy staleness refresh

    # ----------------------------------------------------------------- API

    #: spec keys whose change requires replacing replica actors
    _RESTART_KEYS = ("serialized_callable", "init_args", "init_kwargs",
                     "max_ongoing_requests", "resources", "runtime_env")

    def deploy(self, name: str, spec: Dict[str, Any]) -> bool:
        """Set/replace a deployment's target state. spec keys:
        serialized_callable, init_args, init_kwargs, num_replicas,
        max_ongoing_requests, resources, user_config, autoscaling_config.

        Redeploys are minimally disruptive (reference deployment_state
        version semantics): a changed callable/init/resources replaces
        replicas; a changed user_config reconfigures them in place; a
        changed num_replicas only scales.
        """
        with self._lock:
            existing = self._deployments.get(name)
            if existing is None:
                self._deployments[name] = _DeploymentState(name, spec)
                return True
            old = existing.spec
            existing.spec = spec
            existing.target_replicas = spec["num_replicas"]
            existing.deleted = False
            existing.unhealthy_reason = None
            existing.consecutive_failures = 0
            existing.backoff_until = 0.0
            if any(old.get(k) != spec.get(k) for k in self._RESTART_KEYS):
                self._drain(existing)
            elif old.get("user_config") != spec.get("user_config") \
                    and spec.get("user_config") is not None:
                for h in existing.replicas:
                    try:
                        h.reconfigure.remote(spec["user_config"])
                    except Exception:  # noqa: BLE001
                        pass
        return True

    def delete_deployment(self, name: str) -> bool:
        with self._lock:
            st = self._deployments.get(name)
            if st is None:
                return False
            st.deleted = True
            st.target_replicas = 0
        return True

    def get_routing_table(self, name: str) -> Dict[str, Any]:
        with self._lock:
            st = self._deployments.get(name)
            if st is None:
                return {"version": -1, "replicas": []}
            return {"version": st.version, "replicas": list(st.replicas),
                    "shed_to": st.shed_to}

    def status(self) -> Dict[str, Any]:
        with self._lock:
            return {
                name: {
                    "target_replicas": st.target_replicas,
                    "live_replicas": len(st.replicas),
                    # constructed + probe-confirmed (live counts replicas
                    # whose __init__ may still be running or crash-looping)
                    "ready_replicas": sum(
                        1 for h in st.replicas
                        if h.actor_id.hex() in st.ready),
                    "draining": len(st.draining),
                    "version": st.version,
                    "deleted": st.deleted,
                    "unhealthy_reason": st.unhealthy_reason,
                    "overload_level": st.overload_level,
                    "shed_to": st.shed_to,
                } for name, st in self._deployments.items()}

    def set_app(self, app: str, names: List[str]) -> List[str]:
        """Record app membership; returns the deployments a previous
        apply created that the new spec DROPPED (declarative diff —
        the caller deletes them)."""
        with self._lock:
            before = set(self._apps.get(app, []))
            self._apps[app] = list(names)
            return sorted(before - set(names))

    def list_deployments(self) -> List[str]:
        with self._lock:
            return [n for n, st in self._deployments.items()
                    if not st.deleted]

    def ensure_proxy(self, port: int) -> int:
        """Start (once) the HTTP proxy actor; returns the bound port.

        The slow parts (actor creation + 30s port wait) run outside the
        state lock; a sentinel under the lock keeps startup single-shot.
        """
        with self._lock:
            if self._proxy is not None and self._proxy_port is not None:
                return self._proxy_port
            starting = self._proxy is not None
        if starting:  # another thread is mid-startup: wait for the port
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                with self._lock:
                    if self._proxy_port is not None:
                        return self._proxy_port
                time.sleep(0.1)
            raise TimeoutError("proxy startup in progress but stuck")
        from ray_tpu.serve.proxy import HTTPProxy
        me = ray_tpu.get_actor(CONTROLLER_NAME, namespace=SERVE_NAMESPACE)
        proxy_cls = ray_tpu.remote(max_concurrency=32)(HTTPProxy)
        proxy = proxy_cls.remote(me, port)
        with self._lock:
            self._proxy = proxy
        try:
            bound = ray_tpu.get(proxy.bound_port.remote(), timeout=30)
        except BaseException:
            # failed startup must not wedge the sentinel: clear it so the
            # next ensure_proxy attempt can start fresh
            with self._lock:
                self._proxy = None
            try:
                ray_tpu.kill(proxy)
            except Exception:  # noqa: BLE001
                pass
            raise
        with self._lock:
            self._proxy_port = bound
        return bound

    def graceful_shutdown(self) -> bool:
        self._stop.set()
        with self._lock:
            for st in self._deployments.values():
                st.deleted = True
                self._drain(st)
            self._deployments.clear()
            if self._proxy is not None:
                try:
                    ray_tpu.kill(self._proxy)
                except Exception:  # noqa: BLE001
                    pass
                self._proxy = None
        return True

    # ------------------------------------------------------------ reconcile

    def _drain(self, st: _DeploymentState) -> None:
        # draining victims included: _drain is the hard-stop path
        # (redeploy/shutdown) and the reconcile loop that would otherwise
        # reap them may be stopping too
        for h in st.replicas + st.draining:
            try:
                ray_tpu.kill(h)
            except Exception:  # noqa: BLE001
                pass
        st.replicas = []
        st.draining = []
        st.drain_deadline.clear()
        st.ready.clear()
        st.starting.clear()
        self._bump_version(st)

    def _start_replica(self, st: _DeploymentState):
        spec = st.spec
        rid = f"{st.name}#{uuid.uuid4().hex[:6]}"
        opts = {
            "max_concurrency": max(2, spec.get("max_ongoing_requests", 8)),
            "concurrency_groups": {"control": 2},
            "num_cpus": spec.get("resources", {}).get("CPU", 0.1),
        }
        extra = {k: v for k, v in spec.get("resources", {}).items()
                 if k != "CPU"}
        if extra:
            opts["resources"] = extra
        if spec.get("runtime_env"):
            # per-deployment env (env_vars/working_dir) travels to the
            # replica worker (reference: serve replicas inherit the
            # deployment's ray_actor_options runtime_env)
            opts["runtime_env"] = spec["runtime_env"]
        cls = ray_tpu.remote(**opts)(Replica)
        # the controller's side of a replica's start, ONE span
        # serve.replica_start: from this request to the health poll that
        # first finds the replica ready (_check_replica_health closes it).
        # The creation is submitted under it, so the replica's own
        # start-up spans (util/startup_clocks.py) share its trace; its
        # length less theirs is the lease and the poll's latency
        span = {"start": time.time(), "replica_id": rid, "polls": 0,
                "trace": (trace_context.new_trace_id(),
                          trace_context.new_span_id())}
        tok = trace_context.activate(*span["trace"])
        try:
            handle = cls.remote(st.name, rid, spec["serialized_callable"],
                                tuple(spec.get("init_args") or ()),
                                dict(spec.get("init_kwargs") or {}),
                                spec.get("user_config"))
        finally:
            trace_context.deactivate(tok)
        st.starting[handle.actor_id.hex()] = span
        return handle

    def _reconcile_loop(self) -> None:
        while not self._stop.is_set():
            try:
                self._reconcile_once()
            except Exception:  # noqa: BLE001 — loop must survive anything
                logger.exception("serve reconcile iteration failed")
            self._stop.wait(self.RECONCILE_PERIOD_S)

    MAX_CONSECUTIVE_FAILURES = 5
    DRAIN_TIMEOUT_S = 10.0

    def _reconcile_once(self) -> None:
        with self._lock:
            states = list(self._deployments.values())
        now = time.monotonic()
        for st in states:
            self._check_replica_health(st)
            self._autoscale(st)
            self._process_draining(st)
            with self._lock:
                delta = st.target_replicas - len(st.replicas)
                version_at_plan = st.version
            if delta > 0 and st.unhealthy_reason is None \
                    and now >= st.backoff_until:
                # create OUTSIDE the lock (head RPC per replica — holding
                # the lock here would stall every router's
                # get_routing_table for the whole scale-up)
                fresh = [self._start_replica(st) for _ in range(delta)]
                with self._lock:
                    if st.version != version_at_plan:
                        # a concurrent deploy() drained/changed the spec
                        # mid-creation: these replicas were built from the
                        # OLD spec — discard them instead of registering
                        # stale code into the routing table
                        stale = fresh
                    else:
                        st.replicas.extend(fresh)
                        self._bump_version(st)
                        stale = []
                for h in stale:
                    st.starting.pop(h.actor_id.hex(), None)
                    try:
                        ray_tpu.kill(h)
                    except Exception:  # noqa: BLE001
                        pass
            with self._lock:
                delta = st.target_replicas - len(st.replicas)
                if delta < 0:
                    # graceful scale-down: victims leave the routing table
                    # immediately (version bump) but keep running until
                    # their in-flight requests finish (_process_draining)
                    victims = st.replicas[delta:]
                    st.replicas = st.replicas[:delta]
                    self._bump_version(st)
                    deadline = now + self.DRAIN_TIMEOUT_S
                    for h in victims:
                        st.draining.append(h)
                        st.drain_deadline[h.actor_id.hex()] = deadline
                if st.deleted and not st.replicas and not st.draining:
                    self._deployments.pop(st.name, None)

    def _process_draining(self, st: _DeploymentState) -> None:
        """Kill drained victims once idle (or past the drain deadline)."""
        if not st.draining:
            return
        now = time.monotonic()
        keep = []
        for h in st.draining:
            key = h.actor_id.hex()
            idle = False
            try:
                ref = h.stats.remote()
                ready, _ = ray_tpu.wait([ref], num_returns=1, timeout=1.0)
                if ready:
                    idle = ray_tpu.get(ref)["ongoing"] == 0
            except Exception:  # noqa: BLE001 — dead already: reap below
                idle = True
            if idle or now >= st.drain_deadline.get(key, 0.0):
                st.drain_deadline.pop(key, None)
                try:
                    ray_tpu.kill(h)
                except Exception:  # noqa: BLE001
                    pass
            else:
                keep.append(h)
        st.draining = keep

    HEALTH_PERIOD_S = 1.0

    def _check_replica_health(self, st: _DeploymentState) -> None:
        """Probe replicas in one batch; drop dead ones (reconcile restarts
        them). Mirrors deployment_state's health-check transition. A slow
        or still-constructing replica is NOT dead — only an ActorError
        reply counts."""
        now = time.monotonic()
        if now - st.last_health_ts < self.HEALTH_PERIOD_S or not st.replicas:
            return
        st.last_health_ts = now
        probes = [(h, h.health_check.remote()) for h in st.replicas]
        try:
            ready, _ = ray_tpu.wait([r for _, r in probes],
                                    num_returns=len(probes), timeout=2.0)
        except Exception:  # noqa: BLE001
            return
        ready_ids = {r.id() for r in ready}
        dead = []
        for h, ref in probes:
            span = st.starting.get(h.actor_id.hex())
            if span is not None:
                span["polls"] += 1
            if ref.id() not in ready_ids:
                continue
            try:
                ray_tpu.get(ref)
                st.ready.add(h.actor_id.hex())
                if span is not None:
                    self._replica_started(st, h.actor_id.hex())
            except ActorError:
                dead.append(h)
                st.ready.discard(h.actor_id.hex())
                st.starting.pop(h.actor_id.hex(), None)
            except Exception:  # noqa: BLE001 — app error in user
                pass                         # check_health: keep for now
        if dead:
            logger.warning("serve: %d dead replica(s) in %s",
                           len(dead), st.name)
            with self._lock:
                st.replicas = [h for h in st.replicas if h not in dead]
                self._bump_version(st)
                st.consecutive_failures += len(dead)
                if st.consecutive_failures >= self.MAX_CONSECUTIVE_FAILURES:
                    st.unhealthy_reason = (
                        f"{st.consecutive_failures} consecutive replica "
                        f"failures; redeploy to retry")
                    logger.error("serve: deployment %s marked unhealthy "
                                 "(%s)", st.name, st.unhealthy_reason)
                else:
                    st.backoff_until = time.monotonic() + min(
                        0.5 * (2 ** st.consecutive_failures), 30.0)
        elif ready_ids and st.consecutive_failures:
            st.consecutive_failures = 0
            st.backoff_until = 0.0

    @staticmethod
    def _replica_started(st: _DeploymentState, actor_hex: str) -> None:
        """Record the serve.replica_start span of a replica a health
        probe found ready for the first time."""
        span = st.starting.pop(actor_hex)
        startup_clocks.span(
            "serve.replica_start", span["start"], time.time(),
            ids=(*span["trace"], ""), replica_id=span["replica_id"],
            deployment=st.name, polls=span["polls"])

    def _autoscale(self, st: _DeploymentState) -> None:
        cfg = st.spec.get("autoscaling_config")
        if not cfg or st.deleted or not st.replicas:
            return
        if cfg.get("policy") == "slo":
            self._autoscale_slo(st, cfg)
            return
        now = time.monotonic()
        if now - st.last_scale_ts < cfg.get("upscale_delay_s", 1.0):
            return
        # one batched wait over all replicas (a per-replica 2s wait loop
        # would let one stalled replica starve the whole reconcile thread)
        probes = [(h, h.stats.remote()) for h in st.replicas]
        try:
            ready, _ = ray_tpu.wait([r for _, r in probes],
                                    num_returns=len(probes), timeout=2.0)
        except Exception:  # noqa: BLE001
            return
        ready_ids = {r.id() for r in ready}
        total_ongoing = 0
        polled = 0
        for h, ref in probes:
            if ref.id() not in ready_ids:
                continue
            try:
                total_ongoing += ray_tpu.get(ref)["ongoing"]
                polled += 1
            except Exception:  # noqa: BLE001
                pass
        if polled == 0:
            return
        target_per = max(cfg.get("target_ongoing_requests", 2), 1e-6)
        desired = int(round(total_ongoing / target_per)) or \
            (1 if total_ongoing else 0)
        desired = max(cfg.get("min_replicas", 1),
                      min(cfg.get("max_replicas", 8), desired))
        if desired != st.target_replicas:
            logger.info("serve autoscale %s: %d -> %d (ongoing=%d)",
                        st.name, st.target_replicas, desired, total_ongoing)
            st.target_replicas = desired
            st.last_scale_ts = now

    # ------------------------------------------------- SLO control loop

    def _head_client(self):
        """The head RpcClient of the worker this controller actor runs
        in — the path to the request table (requests_dump) and the
        cluster event journal (journal_record)."""
        from ray_tpu.core.worker import global_worker
        return global_worker.backend.head

    def _journal(self, etype: str, **fields) -> None:
        """Best-effort control-loop decision record in the head's event
        journal — `events --follow` replays a storm from these."""
        try:
            self._head_client().call("journal_record",
                                     {"type": etype, **fields}, timeout=5)
        except Exception:  # noqa: BLE001
            pass

    def _autoscale_slo(self, st: _DeploymentState, cfg: dict) -> None:
        """The SLO reflex arc, one evaluation per serve_slo_eval_period_s:

        attainment < target, below max  -> +1 replica (scale out beats
                                           degrading)
        attainment < target AT max      -> after overload_steps straight
                                           breaches, climb the ladder:
                                           tighten engine admission one
                                           level; at the top, shed to the
                                           cheaper ``shed_model_id``
        attainment >= target            -> unwind shedding, then the
                                           ladder, one level per eval;
                                           then after scale_down_evals of
                                           sustained headroom, drain one
                                           replica (graceful: victims
                                           leave the routing table and
                                           finish in-flight first)
        """
        from ray_tpu.core.config import GlobalConfig
        now = time.monotonic()
        period = cfg.get("slo_eval_period_s",
                         GlobalConfig.serve_slo_eval_period_s)
        if now - st.last_slo_eval < period:
            return
        st.last_slo_eval = now
        window = cfg.get("slo_window_s", GlobalConfig.serve_slo_window_s)
        try:
            records = self._head_client().call("requests_dump", {},
                                               timeout=5) or []
        except Exception:  # noqa: BLE001 — no signal, no decision
            return
        attainment, n = windowed_attainment(
            records, time.time(), window,
            GlobalConfig.llm_slo_ttft_ms / 1e3,
            GlobalConfig.llm_slo_tpot_ms / 1e3)
        try:
            from ray_tpu.util import metrics as metrics_mod
            metrics_mod.serve_slo_attainment_gauge().set(
                attainment, tags={"deployment": st.name})
        except Exception:  # noqa: BLE001
            pass
        target = cfg.get("target_attainment",
                         GlobalConfig.serve_slo_target_attainment)
        min_r, max_r = cfg.get("min_replicas", 1), cfg.get("max_replicas", 8)
        if attainment < target:
            st.slo_ok_streak = 0
            self._journal("serve_slo_breach", deployment=st.name,
                          attainment=round(attainment, 4), target=target,
                          window_n=n, replicas=st.target_replicas,
                          overload_level=st.overload_level)
            if st.target_replicas < max_r:
                st.slo_breach_streak = 0
                st.target_replicas += 1
                st.last_scale_ts = now
                logger.info("serve slo %s: scale up to %d "
                            "(attainment %.3f < %.3f)", st.name,
                            st.target_replicas, attainment, target)
                self._journal("serve_autoscale", deployment=st.name,
                              direction="up", to=st.target_replicas,
                              reason="slo_attainment",
                              attainment=round(attainment, 4))
                return
            # at max replicas: degrade instead of queue collapse
            st.slo_breach_streak += 1
            steps = cfg.get("overload_steps",
                            GlobalConfig.serve_overload_steps)
            max_level = cfg.get("overload_max_level",
                                GlobalConfig.serve_overload_max_level)
            if st.slo_breach_streak < steps:
                return
            st.slo_breach_streak = 0
            if st.overload_level < max_level:
                self._set_overload(st, cfg, st.overload_level + 1)
            elif cfg.get("shed_model_id") and not st.shed_to:
                with self._lock:
                    st.shed_to = cfg["shed_model_id"]
                    self._bump_version(st)
                logger.warning("serve slo %s: shedding to %s", st.name,
                               st.shed_to)
                self._journal("serve_overload_shed_on",
                              deployment=st.name, shed_to=st.shed_to)
            return
        # over target: recover — unwind the ladder before packing down
        st.slo_breach_streak = 0
        if st.shed_to:
            with self._lock:
                st.shed_to = ""
                self._bump_version(st)
            self._journal("serve_overload_shed_off", deployment=st.name,
                          attainment=round(attainment, 4))
            return
        if st.overload_level > 0:
            self._set_overload(st, cfg, st.overload_level - 1)
            if st.overload_level == 0:
                self._journal("serve_slo_recovered", deployment=st.name,
                              attainment=round(attainment, 4))
            return
        st.slo_ok_streak += 1
        down_evals = cfg.get("scale_down_evals",
                             GlobalConfig.serve_slo_scale_down_evals)
        if st.slo_ok_streak >= down_evals and st.target_replicas > min_r:
            st.slo_ok_streak = 0
            st.target_replicas -= 1
            st.last_scale_ts = now
            logger.info("serve slo %s: drain down to %d (sustained "
                        "headroom)", st.name, st.target_replicas)
            self._journal("serve_autoscale", deployment=st.name,
                          direction="down", to=st.target_replicas,
                          reason="slo_headroom",
                          attainment=round(attainment, 4))

    def _set_overload(self, st: _DeploymentState, cfg: dict,
                      level: int) -> None:
        """Move the degradation ladder and push the admission budget to
        every replica (fire-and-forget generic method dispatch — a
        callable without set_overload_level just raises replica-side and
        the request is dropped there)."""
        from ray_tpu.core.config import GlobalConfig
        level = max(0, level)
        if level == st.overload_level:
            return
        factor = cfg.get("overload_budget_factor",
                         GlobalConfig.serve_overload_budget_factor)
        st.overload_level = level
        logger.warning("serve slo %s: overload level -> %d", st.name, level)
        self._journal("serve_overload_level", deployment=st.name,
                      level=level, budget_factor=factor)
        for h in list(st.replicas):
            try:
                h.handle_request.remote("set_overload_level",
                                        (level, factor), {})
            except Exception:  # noqa: BLE001
                pass
