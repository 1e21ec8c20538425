"""Sharded train-step builder: one pjit program per run.

This replaces the reference's per-framework backend plugins (reference:
python/ray/train/backend.py:32 Backend ABC, train/torch/train_loop_utils.py
:165 DDP/FSDP wrapping): on TPU the "backend" is the compiled program —
gradient reduction, FSDP gathers and TP collectives all come from the
shardings, not from a process-group library.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def shard_params(params, mesh: Mesh, specs):
    """device_put a param pytree by its PartitionSpec pytree."""
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    return jax.device_put(params, shardings)


def shard_batch(batch, mesh: Mesh, spec: Optional[P] = None):
    """Shard array dim0 over the data axes (dp+fsdp); other dims replicated."""
    def put(x):
        s = spec if spec is not None else P(("dp", "fsdp"))
        return jax.device_put(x, NamedSharding(mesh, s))
    return jax.tree.map(put, batch)


def make_train_step(loss_fn: Callable[[Any, Any], jax.Array],
                    optimizer,
                    donate: bool = True):
    """Build (init_fn, step_fn).

    loss_fn(params, batch) -> scalar loss. optimizer: an optax
    GradientTransformation. Both functions are jitted; sharding propagates
    from the committed input arrays (use shard_params first), so the same
    step runs 1-chip or any dp/fsdp/tp/pp/sp mesh unchanged.

    Where a compile tracker runs (util/compile_tracker.py) both go through
    its seam, as ``train.init`` and ``train.step``: every compile is
    recorded with its signature and its seconds by phase, so a step that
    compiles a second time is `train.step compiles: 2` with the diff that
    caused it (`python -m ray_tpu compiles`), at two cache-size probes and
    a clock pair a call.
    """
    import optax

    from ray_tpu.util import compile_tracker

    @jax.jit
    def init_fn(params):
        return optimizer.init(params)

    @functools.partial(jax.jit, donate_argnums=(0, 1) if donate else ())
    def step_fn(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        gnorm = optax.global_norm(grads)
        return params, opt_state, {"loss": loss, "grad_norm": gnorm}

    tracker = compile_tracker.ensure_started()
    if tracker is None:
        return init_fn, step_fn
    return (tracker.wrap(init_fn, name="train.init"),
            tracker.wrap(step_fn, name="train.step"))
