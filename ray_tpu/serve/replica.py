"""Replica — the actor that hosts one copy of a deployment's callable.

Role-equivalent to the reference's replica actor (reference:
serve/_private/replica.py): constructs the user class from its serialized
form, tracks ongoing-request counts for the router's pow-2 choice and the
controller's autoscaler, and exposes health/reconfigure hooks.
"""

from __future__ import annotations

import inspect
import threading
import time
from typing import Any, Dict, Optional, Tuple

import cloudpickle

import ray_tpu
from ray_tpu.serve.multiplex import MUX_KWARG, _set_request_model_id
from ray_tpu.util import startup_clocks


class Replica:
    def __init__(self, deployment_name: str, replica_id: str,
                 serialized_callable: bytes, init_args: Tuple,
                 init_kwargs: Dict[str, Any],
                 user_config: Optional[Dict[str, Any]] = None):
        self.deployment_name = deployment_name
        self.replica_id = replica_id
        # unpickling the user class imports what it needs (ray_tpu.llm
        # and jax, for a served model): its own start-up phase
        with startup_clocks.phase("import"):
            target = cloudpickle.loads(serialized_callable)
        if inspect.isclass(target):
            self.callable = target(*init_args, **init_kwargs)
        else:
            if init_args or init_kwargs:
                raise TypeError("function deployments take no init args")
            self.callable = target
        self._lock = threading.Lock()
        self._ongoing = 0
        self._total = 0
        self._started = time.time()
        if user_config is not None:
            self.reconfigure(user_config)

    def handle_request(self, method_name: str, args: Tuple,
                       kwargs: Dict[str, Any]) -> Any:
        """One request. Runs on one of the replica actor's concurrency
        threads (max_ongoing_requests maps to actor max_concurrency)."""
        _set_request_model_id(kwargs.pop(MUX_KWARG, ""))
        with self._lock:
            self._ongoing += 1
            self._total += 1
        try:
            if method_name == "__call__":
                target = self.callable
            else:
                target = getattr(self.callable, method_name, None)
                if target is None:
                    raise AttributeError(
                        f"deployment {self.deployment_name} has no method "
                        f"{method_name!r}")
            return target(*args, **kwargs)
        finally:
            with self._lock:
                self._ongoing -= 1

    def handle_request_streaming(self, method_name: str, args: Tuple,
                                 kwargs: Dict[str, Any]):
        """Streaming variant: the target must return an iterable/generator;
        each item is yielded onward, so under ``num_returns="streaming"``
        the caller consumes items while the request is still running
        (reference: replica.py streaming responses over the generator
        protocol)."""
        model_id = kwargs.pop(MUX_KWARG, "")
        _set_request_model_id(model_id)
        with self._lock:
            self._ongoing += 1
            self._total += 1
        try:
            if method_name == "__call__":
                target = self.callable
            else:
                target = getattr(self.callable, method_name, None)
                if target is None:
                    raise AttributeError(
                        f"deployment {self.deployment_name} has no method "
                        f"{method_name!r}")
            out = target(*args, **kwargs)
            if isinstance(out, (str, bytes, dict, set)) or \
                    not hasattr(out, "__iter__"):
                # iterating a dict/str would silently stream keys or
                # characters — surface the contract violation instead
                raise TypeError(
                    f"streaming call to {self.deployment_name}."
                    f"{method_name} returned {type(out).__name__}, "
                    f"expected a generator/iterable of items")
            it = iter(out)
            while True:
                # a lazy generator body runs during next(), and another
                # request may have run on this thread between our yields
                # — re-assert the request's model id each pull
                _set_request_model_id(model_id)
                try:
                    item = next(it)
                except StopIteration:
                    break
                yield item
        finally:
            with self._lock:
                self._ongoing -= 1

    # stats/health run on the "control" concurrency group so the
    # controller's probes never queue behind slow user requests occupying
    # every handler lane (reference: replica system-message concurrency).
    @ray_tpu.method(concurrency_group="control")
    def stats(self) -> Dict[str, Any]:
        with self._lock:
            out = {"replica_id": self.replica_id,
                   "ongoing": self._ongoing,
                   "total": self._total,
                   "uptime_s": time.time() - self._started}
        mux = self._multiplexed_model_ids()
        if mux is not None:
            out["multiplexed_model_ids"] = mux
        return out

    def _multiplexed_model_ids(self):
        """Loaded-model ids across any @serve.multiplexed members of the
        deployment (reference: MultiplexedReplicaInfo pushed to the
        controller; here surfaced via stats for observability/tests)."""
        from ray_tpu.serve.multiplex import _MultiplexedDescriptor
        cls = type(self.callable)
        found = None
        for name in dir(cls):
            if isinstance(getattr(cls, name, None), _MultiplexedDescriptor):
                bound = getattr(self.callable, name)
                found = (found or []) + bound.cache.model_ids()
        return found

    @ray_tpu.method(concurrency_group="control")
    def health_check(self) -> bool:
        user_check = getattr(self.callable, "check_health", None)
        if callable(user_check):
            user_check()
        return True

    @ray_tpu.method(concurrency_group="control")
    def reconfigure(self, user_config: Dict[str, Any]) -> bool:
        fn = getattr(self.callable, "reconfigure", None)
        if callable(fn):
            fn(user_config)
        return True
