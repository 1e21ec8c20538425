"""TPU accelerator manager: topology detection + gang resources.

Role-equivalent to the reference's TPU manager (reference:
python/ray/_private/accelerators/tpu.py:70 — chip-count validation at
:14,143, TPU_VISIBLE_CHIPS/TPU_CHIPS_PER_HOST_BOUNDS at :31,39,
`TPU-{version}` resources at :310, `TPU-{pod_type}-head` gang resource at
:330,377) redesigned for this framework's scheduler:

 - each TPU host advertises ``TPU`` (chip count), ``TPU-{version}`` (e.g.
   TPU-v5p), and — on worker 0 of a slice — ``TPU-{pod_type}-head`` (e.g.
   TPU-v5p-16-head), the gang resource a placement-group bundle reserves to
   claim a whole ICI slice atomically;
 - leased workers get ``TPU_VISIBLE_CHIPS`` so concurrent workers on one
   host never fight over chips (the TPU runtime allows one owner per chip);
 - detection is env-driven (GKE-style TPU_* variables) plus a count of
   the host's TPU device files — never a jax probe, which would claim
   the chips — since a metadata server is not assumed.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Tuple

# chips per host must divide the host's physical complement
# (reference: tpu.py:14 — valid per-host chip counts)
VALID_CHIPS_PER_HOST = (1, 2, 4, 8)

TPU_VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"
TPU_CHIPS_PER_HOST_BOUNDS_ENV = "TPU_CHIPS_PER_HOST_BOUNDS"

# single-host bounds by chip count (reference: tpu.py:31-39 constants)
_BOUNDS_BY_COUNT = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1", 8: "2,4,1"}


class TPUAcceleratorManager:
    """Static helpers; instantiated nowhere (mirrors the reference ABC)."""

    # ------------------------------------------------------------- detection

    @staticmethod
    def local_chip_count() -> int:
        """Chips this host can actually open, counted from the device
        files the TPU driver exposes (``/dev/accel*`` up to v4,
        ``/dev/vfio/<n>`` from v5e on). Never touches jax: initialising a
        TPU backend here would claim the chips this count is for."""
        n = len(glob.glob("/dev/accel*"))
        return n or sum(os.path.basename(p).isdigit()
                        for p in glob.glob("/dev/vfio/*"))

    @staticmethod
    def detect() -> Optional[dict]:
        """Detect this host's TPU topology without importing jax.

        Returns {version, pod_type, worker_id, num_chips} or None when the
        host has no TPU. TPU_ACCELERATOR_TYPE / TPU_WORKER_ID (the GKE/GCE
        path of the reference) name the slice; the chip count is what the
        host's device files expose when there are any — the env describes
        the slice a VM belongs to, which can be more than a container on
        it may open (a one-chip share of a v5litepod-4 host) — and the
        generation's per-host complement otherwise.
        """
        accel = os.environ.get("TPU_ACCELERATOR_TYPE")  # e.g. "v5p-16"
        if not accel:
            return None
        return {"version": accel.split("-")[0], "pod_type": accel,
                "worker_id": int(os.environ.get("TPU_WORKER_ID", "0")),
                "num_chips": TPUAcceleratorManager.local_chip_count()
                or TPUAcceleratorManager._chips_per_host(accel)}

    # full-host chip complement per TPU generation (reference: tpu.py:143
    # topology tables — v2-v4/v5p hosts carry 4 chips, v5e/v6e up to 8)
    _PER_HOST = {"v2": 4, "v3": 4, "v4": 4, "v5p": 4,
                 "v5e": 8, "v5litepod": 8, "v6e": 8}

    @staticmethod
    def _chips_per_host(pod_type: str) -> int:
        version, _, suffix = pod_type.rpartition("-")
        try:
            total = int(suffix)
        except ValueError:
            return 4
        per = TPUAcceleratorManager._PER_HOST.get(version, 4)
        return min(total, per)

    # ------------------------------------------------------------- resources

    @staticmethod
    def node_resources(info: Optional[dict] = None) -> Dict[str, float]:
        """Resources a TPU host advertises to the scheduler.

        ``TPU-{pod_type}-head`` appears only on worker 0 so a single-bundle
        PG reservation of it gang-claims the whole slice (reference:
        tpu.py:330,377).
        """
        if info is None:
            info = TPUAcceleratorManager.detect()
        if info is None:
            return {}
        res = {
            "TPU": float(info["num_chips"]),
            f"TPU-{info['version']}": float(info["num_chips"]),
        }
        if info["worker_id"] == 0:
            res[f"TPU-{info['pod_type']}-head"] = 1.0
        return res

    @staticmethod
    def validate_chip_request(n: int) -> None:
        if n not in VALID_CHIPS_PER_HOST:
            raise ValueError(
                f"requested {n} TPU chips; a worker may hold "
                f"{VALID_CHIPS_PER_HOST} (reference tpu.py chip-count rule)")

    @staticmethod
    def visibility_env(chip_ids: List[int]) -> Dict[str, str]:
        """Env for a worker that owns `chip_ids` on this host (reference:
        tpu.py:31,39 — set before the TPU runtime initializes)."""
        n = len(chip_ids)
        env = {TPU_VISIBLE_CHIPS_ENV: ",".join(str(c) for c in chip_ids)}
        bounds = _BOUNDS_BY_COUNT.get(n)
        if bounds:
            env[TPU_CHIPS_PER_HOST_BOUNDS_ENV] = bounds
        return env


class ChipAllocator:
    """Per-node assignment of physical chip ids to leased workers."""

    def __init__(self, num_chips: int):
        self.free: List[int] = list(range(num_chips))
        self.assigned: Dict[bytes, List[int]] = {}

    def allocate(self, worker_id: bytes, n: int) -> Optional[List[int]]:
        if len(self.free) < n:
            return None
        chips, self.free = self.free[:n], self.free[n:]
        self.assigned[worker_id] = chips
        return chips

    def release(self, worker_id: bytes) -> None:
        chips = self.assigned.pop(worker_id, None)
        if chips:
            self.release_chips(chips)

    def release_chips(self, chips: List[int]) -> None:
        """Return chips not (or no longer) tied to a worker id (e.g. a
        spawn that failed between allocation and registration)."""
        self.free.extend(chips)
        self.free.sort()
