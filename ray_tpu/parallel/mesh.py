"""Device-mesh construction and sharding helpers.

The reference expresses parallelism degrees as config knobs executed by
external engines (reference: python/ray/llm/_internal/serve/configs/
vllm_models.py:129,133 tensor/pipeline_parallel_size; train/torch/
train_loop_utils.py:165 DDP/FSDP wrap). Here the degrees *are* the mesh:
a `MeshSpec` names each axis and `build_mesh` lays devices out so that the
innermost axes (tp, sp) map to adjacent ICI neighbours.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


AXIS_ORDER = ("pp", "dp", "fsdp", "sp", "tp")


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Parallelism degrees for one job. -1 on at most one axis = "fill".

    Example: MeshSpec(fsdp=-1, tp=4) on 32 chips → pp1 × dp1 × fsdp8 × sp1 × tp4.

    Multi-slice (ICI × DCN) hybrid: ``dcn_dp``/``dcn_pp`` add an OUTER
    data/pipeline dimension that spans slices over the data-center network,
    while pp/dp/fsdp/sp/tp describe the per-slice (ICI) layout. The built
    mesh still has the five canonical axes — the dp axis is
    ``dcn_dp × dp`` with the slice dimension MAJOR, so gradient
    all-reduces decompose hierarchically (reduce inside the slice on ICI,
    then once across slices on DCN — the scaling-book recipe) and tp/sp/
    fsdp collectives never leave a slice. The reference has no in-tree
    equivalent (its multi-slice story is config stubs,
    python/ray/llm/_internal/serve/.../vllm_models.py:129-150).
    """

    pp: int = 1
    dp: int = 1
    fsdp: int = 1
    sp: int = 1
    tp: int = 1
    # outer, DCN-spanning degrees (1 = single slice)
    dcn_dp: int = 1
    dcn_pp: int = 1

    def degrees(self) -> dict:
        """Per-slice (ICI) degrees only."""
        return {a: getattr(self, a) for a in AXIS_ORDER}

    @property
    def num_slices(self) -> int:
        return self.dcn_dp * self.dcn_pp

    def resolve(self, n_devices: int) -> "MeshSpec":
        """Fill the single -1 axis so slices × inner == n_devices."""
        d = self.degrees()
        for a, v in d.items():
            if v != -1 and v < 1:
                raise ValueError(f"axis {a!r} degree must be -1 or >= 1, got {v}")
        if self.dcn_dp < 1 or self.dcn_pp < 1:
            raise ValueError("dcn degrees must be >= 1")
        if n_devices % self.num_slices:
            raise ValueError(
                f"{n_devices} devices not divisible into "
                f"{self.num_slices} slices")
        per_slice = n_devices // self.num_slices
        fill = [a for a, v in d.items() if v == -1]
        if len(fill) > 1:
            raise ValueError(f"at most one -1 axis, got {fill}")
        fixed = math.prod(v for v in d.values() if v != -1)
        if fill:
            if per_slice % fixed:
                raise ValueError(
                    f"{per_slice} devices/slice not divisible by fixed "
                    f"degrees {fixed}")
            d[fill[0]] = per_slice // fixed
        elif fixed != per_slice:
            raise ValueError(
                f"mesh {d} needs {fixed} devices per slice, have "
                f"{per_slice}")
        return MeshSpec(**d, dcn_dp=self.dcn_dp, dcn_pp=self.dcn_pp)

    @property
    def size(self) -> int:
        return math.prod(self.degrees().values()) * self.num_slices


def device_count() -> int:
    """Global device count across all hosts."""
    return len(jax.devices())


def local_device_count() -> int:
    """Devices attached to THIS host (multi-host: a slice of the global set)."""
    return jax.local_device_count()


def _group_by_slice(devices: Sequence[jax.Device],
                    num_slices: int) -> list:
    """Partition devices into per-slice groups, ICI order preserved.

    TPU multislice exposes `slice_index` on each device; multi-process CPU
    emulation groups by process_index (each worker process stands in for a
    slice); otherwise fall back to contiguous equal chunks (single-process
    virtual meshes)."""
    per = len(devices) // num_slices
    for attr in ("slice_index", "process_index"):
        keys = sorted({getattr(d, attr, None) for d in devices}
                      - {None})
        if len(keys) == num_slices:
            groups = [[d for d in devices
                       if getattr(d, attr, None) == k] for k in keys]
            if all(len(g) == per for g in groups):
                return groups
    n_procs = len({getattr(d, "process_index", 0) for d in devices})
    if n_procs > 1:
        # contiguous chunking across REAL process boundaries breaks the
        # slice-locality guarantee (tp/fsdp neighbours would straddle
        # DCN) — surface it instead of silently degrading
        import warnings
        warnings.warn(
            f"devices span {n_procs} processes but neither slice_index "
            f"nor process_index groups them into {num_slices} equal "
            f"slices; falling back to contiguous chunks whose inner-axis "
            f"collectives may cross slice (DCN) boundaries", stacklevel=3)
    return [list(devices[i * per:(i + 1) * per])
            for i in range(num_slices)]


def build_mesh(spec: MeshSpec,
               devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build a named Mesh with tp innermost (adjacent ICI neighbours).

    `jax.devices()` returns devices in torus-local order on TPU, so a simple
    reshape keeps the innermost mesh axes on the shortest ICI paths (the
    scaling-book recipe; contrast reference NCCL group setup in
    python/ray/util/collective/collective_group/nccl_collective_group.py).

    With dcn_dp/dcn_pp set, devices are first grouped by slice and laid
    out so the slice dimension is the MAJOR dimension of dp/pp: every
    tp/sp/fsdp neighbour pair sits inside one slice (ICI), and dp/pp
    collectives cross DCN only between the per-slice blocks.
    """
    devices = list(devices if devices is not None else jax.devices())
    spec = spec.resolve(len(devices))
    shape = tuple(spec.degrees()[a] for a in AXIS_ORDER)
    if spec.num_slices == 1:
        dev_array = np.asarray(devices).reshape(shape)
        return Mesh(dev_array, AXIS_ORDER)
    slices = _group_by_slice(devices, spec.num_slices)
    full_shape = (spec.dcn_pp * spec.pp, spec.dcn_dp * spec.dp,
                  spec.fsdp, spec.sp, spec.tp)
    dev_array = np.empty(full_shape, dtype=object)
    sid = 0
    for a in range(spec.dcn_pp):
        for b in range(spec.dcn_dp):
            block = np.asarray(slices[sid]).reshape(shape)
            dev_array[a * spec.pp:(a + 1) * spec.pp,
                      b * spec.dp:(b + 1) * spec.dp] = block
            sid += 1
    return Mesh(dev_array, AXIS_ORDER)


def shard_map_compat(fn, mesh: Mesh, in_specs, out_specs, axis_names=None):
    """``jax.shard_map`` with the replication (vma) check off — the
    kernels and hand-written collectives inside these blocks carry no
    varying-axis annotations.

    axis_names: optional set of mesh axes to treat as MANUAL; the rest stay
    auto (GSPMD keeps sharding them) — used to run the pipeline/ring loops
    manually while fsdp/tp remain compiler-managed. None = every mesh axis
    is manual.
    """
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs,
                         axis_names=frozenset(axis_names or ()),
                         check_vma=False)


def named_sharding(mesh: Mesh, *axes) -> NamedSharding:
    """NamedSharding(mesh, P(*axes)); axes may be None/str/tuple per dim."""
    return NamedSharding(mesh, P(*axes))


def shard_constraint(x, mesh: Mesh, *axes):
    """with_sharding_constraint under an explicit mesh (no-op outside jit)."""
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*axes)))
