"""Device mesh construction for dp/fsdp/tp/sp/ep axes — including hybrid
ICI x DCN meshes spanning multiple TPU slices.

TPU-native core: a ``jax.sharding.Mesh`` over all global devices, with ICI-
friendly axis ordering (innermost axes map to physically-adjacent chips so tp/sp
collectives ride the fastest links — `jax.experimental.mesh_utils` handles the
physical layout).

Multi-slice (SURVEY §5.8): ``MeshConfig(dcn_dp=..., dcn_pp=...)`` builds a
hybrid mesh where ONLY the dp and pp axes cross slice boundaries — gradient
all-reduce and pipeline stage hand-offs are the traffic patterns that
amortize DCN latency (one transfer per step), while tp/sp/ep collectives
stay strictly inside a slice's ICI.  This is the mesh recipe of
``mesh_utils.create_hybrid_device_mesh`` (and the scaling-book's
"data-parallel across slices, model-parallel within" rule); on hardware the
slice boundary is discovered from device attributes, and on the virtual CPU
platform contiguous device blocks stand in for slices so the sharding
compiles + executes in tests.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

AXES = ("pp", "dp", "fsdp", "tp", "sp", "ep")


@dataclass(frozen=True)
class MeshConfig:
    """Sizes per logical axis; -1 on at most one axis means 'absorb the rest'.

    ``dcn_dp``/``dcn_pp`` extend the dp/pp axes ACROSS slices over DCN: the
    final logical axis size is ``dcn_axis * ici_axis`` with the DCN factor
    major, so neighboring positions along dp/pp stay within a slice and only
    the outermost hop crosses slices."""

    dp: int = -1
    fsdp: int = 1
    tp: int = 1
    sp: int = 1
    ep: int = 1
    pp: int = 1
    dcn_dp: int = 1
    dcn_pp: int = 1

    @property
    def n_slices(self) -> int:
        return self.dcn_dp * self.dcn_pp

    def resolve(self, n_devices: int) -> Dict[str, int]:
        """ICI (per-slice) sizes; wildcards absorb per-slice devices."""
        if n_devices % self.n_slices:
            raise ValueError(
                f"{n_devices} devices not divisible into {self.n_slices} "
                f"slices (dcn_dp={self.dcn_dp}, dcn_pp={self.dcn_pp})")
        per_slice = n_devices // self.n_slices
        sizes = {"pp": self.pp, "dp": self.dp, "fsdp": self.fsdp,
                 "tp": self.tp, "sp": self.sp, "ep": self.ep}
        wild = [k for k, v in sizes.items() if v == -1]
        if len(wild) > 1:
            raise ValueError(f"at most one axis may be -1, got {wild}")
        fixed = int(np.prod([v for v in sizes.values() if v != -1]))
        if wild:
            if per_slice % fixed:
                raise ValueError(
                    f"{per_slice} per-slice devices not divisible by fixed "
                    f"axes product {fixed}")
            sizes[wild[0]] = per_slice // fixed
        total = int(np.prod(list(sizes.values())))
        if total != per_slice:
            raise ValueError(
                f"mesh {sizes} covers {total} devices but {per_slice} are "
                f"present per slice")
        return sizes


def mesh_shape_for(n_devices: int, tp: int = 1, sp: int = 1, fsdp: int = 1,
                   ep: int = 1) -> Dict[str, int]:
    return MeshConfig(dp=-1, fsdp=fsdp, tp=tp, sp=sp, ep=ep).resolve(n_devices)


def _group_by_slice(devices, n_slices: int):
    """Partition devices into slices: by the hardware's slice index when the
    platform exposes one, else contiguous equal blocks (virtual platforms)."""
    by_idx: Dict[int, list] = {}
    for d in devices:
        idx = getattr(d, "slice_index", None)
        if idx is None:
            break
        by_idx.setdefault(idx, []).append(d)
    else:
        if len(by_idx) == n_slices:
            return [by_idx[k] for k in sorted(by_idx)]
        if len(by_idx) % n_slices == 0 and len(by_idx) > n_slices:
            # more physical slices than DCN groups: fold evenly
            keys = sorted(by_idx)
            per = len(keys) // n_slices
            return [sum((by_idx[k] for k in keys[i * per:(i + 1) * per]), [])
                    for i in range(n_slices)]
    per = len(devices) // n_slices
    return [list(devices[i * per:(i + 1) * per]) for i in range(n_slices)]


def build_mesh(config: Optional[MeshConfig] = None, devices=None):
    """Build a Mesh over the given (default: all global) devices.

    Axis order is (pp, dp, fsdp, sp, tp, ep) outer→inner: tp/ep innermost so
    their all-to-all/all-gather traffic lands on the closest ICI neighbors.
    With ``dcn_dp``/``dcn_pp`` > 1 the mesh is hybrid: per-slice ICI meshes
    stacked so dp/pp get a DCN-major extra factor while every other axis
    stays inside one slice.
    """
    import jax
    from jax.experimental import mesh_utils
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    config = config or MeshConfig()
    sizes = config.resolve(len(devices))
    order = ("pp", "dp", "fsdp", "sp", "tp", "ep")
    ici_shape = tuple(sizes[a] for a in order)

    def slice_mesh(devs):
        # On TPU this picks the ICI-aware device order and raises when the
        # shape does not fit the physical topology — no flat-reshape fallback,
        # which would be a wrong ICI order taken in silence.
        return mesh_utils.create_device_mesh(ici_shape, devices=devs)

    if config.n_slices == 1:
        return Mesh(slice_mesh(devices), order)

    # hybrid: stack per-slice meshes as (dcn_pp, dcn_dp, *ici_shape), then
    # merge the DCN factors into the pp/dp dims (DCN-major)
    groups = _group_by_slice(devices, config.n_slices)
    stack = np.stack([slice_mesh(g) for g in groups])
    stack = stack.reshape((config.dcn_pp, config.dcn_dp) + ici_shape)
    # (dcn_pp, dcn_dp, pp, dp, fsdp, sp, tp, ep)
    #   -> (dcn_pp, pp, dcn_dp, dp, fsdp, sp, tp, ep) -> merge pairs
    stack = np.transpose(stack, (0, 2, 1, 3, 4, 5, 6, 7))
    final_shape = (config.dcn_pp * sizes["pp"], config.dcn_dp * sizes["dp"]) \
        + ici_shape[2:]
    return Mesh(stack.reshape(final_shape), order)


def ambient_mesh():
    """The mesh activated by ``jax.set_mesh`` around the current trace, if
    any.  The attention kernels and the residual-stream constraint lay
    themselves out over it."""
    import jax

    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def local_mesh(axis: str = "dp"):
    """A 1-axis mesh over this process's addressable devices (single-host DP)."""
    import jax
    from jax.sharding import Mesh

    devs = np.asarray(jax.local_devices())
    return Mesh(devs, (axis,))
