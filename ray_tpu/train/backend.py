"""JAX distributed backend — multi-host worker bootstrap.

Role-equivalent to the reference's torch process-group setup (reference:
python/ray/train/torch/config.py:66 _setup_torch_process_group — NCCL/gloo
rendezvous from rank 0), as the TPU-native analog (SURVEY.md §7 layer 6):
every train worker process calls ``jax.distributed.initialize`` against
one coordinator, after which ``jax.devices()`` is the GLOBAL device set
and a single Mesh spans all hosts — collectives compile onto ICI/DCN, no
NCCL wrapper.

On real TPU pods each worker (1 per host) just calls initialize() and the
TPU runtime discovers topology. Test meshes emulate a pod with N CPU
processes × K virtual devices (``platform='cpu'``,
``local_device_count=K`` — the reference's fake-multi-node trick,
SURVEY.md §4 item (d)).
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional

from ray_tpu.parallel.mesh import MeshSpec, build_mesh

_initialized = False


def setup_jax_worker(dist: Dict[str, Any]) -> None:
    """Bootstrap this worker process into the global JAX runtime.

    dist keys: coordinator (host:port), num_processes, process_id,
    platform (None = ambient), local_device_count (CPU emulation only).
    MUST run before any collective/mesh work; safe to call once per
    process (jax.distributed tolerates re-init attempts with an error we
    surface clearly).
    """
    platform = dist.get("platform")
    n_local = dist.get("local_device_count")
    if platform == "cpu":
        # env must be set before the backend initializes; jax.config is
        # authoritative even if jax was already imported (but not yet used)
        os.environ["JAX_PLATFORMS"] = "cpu"
        if n_local:
            import re
            flags = os.environ.get("XLA_FLAGS", "")
            # REPLACE an inherited device-count flag (e.g. the test
            # driver's 8-device mesh env), don't merely append
            flags = re.sub(
                r"--xla_force_host_platform_device_count=\d+", "", flags)
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{n_local}").strip()
        import jax
        jax.config.update("jax_platforms", "cpu")
    global _initialized
    if _initialized:
        return  # worker reuse within one group/restart
    import jax
    if dist["num_processes"] > 1:
        # NOTE: must run before ANY backend query (even
        # jax.process_count() would initialize a single-process backend
        # and the later initialize() could not register remote devices)
        jax.distributed.initialize(
            coordinator_address=dist["coordinator"],
            num_processes=dist["num_processes"],
            process_id=dist["process_id"],
            cluster_detection_method="deactivate")
    _initialized = True


def global_mesh(spec: Optional[MeshSpec] = None):
    """The job-wide device mesh (call after setup_jax_worker)."""
    import jax
    return build_mesh(spec or MeshSpec(dp=-1), devices=jax.devices())


def process_index() -> int:
    import jax
    return jax.process_index()
