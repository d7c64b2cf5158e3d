"""Simulation mesh: lane/group placement over the visible JAX devices.

The simulator's multi-device story (paper §4.2 multi-GPU, ISSUE 9) has
two tiers, both built on one 1-D ``jax.sharding.Mesh`` whose single axis
is :data:`LANE_AXIS`:

* **lane sharding** — a ``run_batch`` / trajectory run of K lanes splits
  the lanes into contiguous :class:`LaneShard` slices, one per mesh
  device.  Each device runs *its* lane slice of every wave against its
  own partition of the block store (lane keys never collide), so there
  are zero collectives; the only gather is the host-side readout
  (:func:`gather_lanes`).
* **block sharding** — a single large state's SV groups are placed per
  the plan's ``StagePlan.device_slot`` round-robin (:func:`device_slots`
  mirrors it).  Stage boundaries exchange only the *encoded wire* blobs
  through the host store — the engine's exchange ledger
  (``SimStats.exchange_bytes``) accounts every byte whose block changed
  owners.

This module replaces the LLM-training sharding rules that used to live
in :mod:`repro.distributed.sharding` (quarantined — see
``analysis/quarantine.txt``): a state-vector simulator shards *lanes and
blocks*, not parameter pytrees.

Everything here is deliberately explicit-placement (``jax.device_put``
per shard) rather than GSPMD: the Pallas codec kernels must see plain
per-device arrays, and explicit shards keep the store-key partition —
the thing checkpoints and the exchange ledger reason about — trivially
auditable.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = [
    "LANE_AXIS",
    "LaneShard",
    "activate_mesh",
    "device_slots",
    "gather_lanes",
    "lane_sharding",
    "lane_spec",
    "make_lane_mesh",
    "make_lane_shards",
    "sim_devices",
]

#: the one mesh axis of the simulation tier: independent lanes (batch
#: lanes / noise trajectories), or — for a single-lane run — the
#: round-robin slot dimension its SV groups are placed over
LANE_AXIS = "lanes"


def sim_devices(n_devices: int | None = None,
                devices: Sequence[Any] | None = None) -> list:
    """The device list one simulation mesh is built over.

    ``devices`` (default: ``jax.devices()``) is truncated to
    ``n_devices`` when given; asking for more devices than are visible is
    a ``ValueError`` (on a CPU host pass
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` — or ``qsim
    --devices N``, which sets it there — to create virtual devices).
    """
    devs = list(devices) if devices is not None else list(jax.devices())
    if not devs:
        raise ValueError("no JAX devices visible")
    if n_devices is None:
        return devs
    if n_devices < 1:
        raise ValueError(f"n_devices={n_devices} must be >= 1")
    if n_devices > len(devs):
        raise ValueError(
            f"requested {n_devices} devices but only {len(devs)} "
            f"{devs[0].platform} device(s) are visible (a CPU host gets "
            f"virtual ones from XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_devices})")
    return devs[:n_devices]


def make_lane_mesh(mesh_shape: tuple[int, ...] | int | None = None,
                   devices: Sequence[Any] | None = None) -> Mesh:
    """Build the 1-D simulation mesh (axis :data:`LANE_AXIS`).

    ``mesh_shape`` is ``(n_devices,)`` (or a bare int); ``None`` spans
    every visible device.  Only 1-D meshes exist in the simulation tier
    — lanes and block slots are both laid out along the one axis.
    """
    if isinstance(mesh_shape, int):
        mesh_shape = (mesh_shape,)
    if mesh_shape is not None:
        if len(mesh_shape) != 1:
            raise ValueError(
                f"simulation meshes are 1-D (lanes axis); got "
                f"mesh_shape={mesh_shape!r}")
        n = int(mesh_shape[0])
    else:
        n = None
    devs = sim_devices(n, devices)
    return Mesh(np.array(devs), (LANE_AXIS,))


def activate_mesh(mesh: Mesh):
    """Context manager activating ``mesh`` (``jax.set_mesh``)."""
    return jax.set_mesh(mesh)


def lane_spec() -> PartitionSpec:
    """PartitionSpec splitting a leading lane axis over the mesh."""
    return PartitionSpec(LANE_AXIS)


def lane_sharding(mesh: Mesh) -> NamedSharding:
    """NamedSharding placing an (L, ...) lane stack over ``mesh``."""
    return NamedSharding(mesh, lane_spec())


@dataclass(frozen=True)
class LaneShard:
    """One device's contiguous lane slice of a batched run.

    ``lanes`` indexes the run's lane axis (and thereby its
    ``lane_offsets`` row block and its store-key range) — the shard's
    partition of the block store is ``[lane.start * n_blocks,
    lane.stop * n_blocks)`` shifted by the chunk base.
    """

    device: Any
    lanes: slice

    @property
    def n_lanes(self) -> int:
        return self.lanes.stop - self.lanes.start


def make_lane_shards(devices: Sequence[Any], n_lanes: int
                     ) -> list[LaneShard]:
    """Contiguous, near-even lane shards over ``devices``.

    The first ``n_lanes % len(devices)`` shards get one extra lane
    (``np.array_split`` semantics); devices with zero lanes are dropped,
    so K < D simply uses K devices.  A ragged split is legal but costs
    one extra jit specialization per distinct shard width — the plan
    verifier surfaces non-divisible lane counts as a warning.
    """
    if n_lanes < 1:
        raise ValueError(f"n_lanes={n_lanes} must be >= 1")
    d = max(1, len(devices))
    base, extra = divmod(n_lanes, d)
    shards = []
    lo = 0
    for i, dev in enumerate(devices):
        width = base + (1 if i < extra else 0)
        if width == 0:
            break
        shards.append(LaneShard(dev, slice(lo, lo + width)))
        lo += width
    return shards


def device_slots(n_groups: int, n_devices: int) -> np.ndarray:
    """Round-robin slot of every group — mirrors
    :meth:`repro.core.plan.StagePlan.device_slot`, so the engine's
    placement and the plan artifact can never drift."""
    return np.arange(n_groups, dtype=np.int64) % max(1, n_devices)


def gather_lanes(parts: Sequence[np.ndarray]) -> np.ndarray:
    """The one readout gather of a lane-sharded batch: concatenate the
    per-shard host results back into lane order (shards are contiguous,
    so a plain concatenate is the inverse of :func:`make_lane_shards`)."""
    arrs = [np.asarray(p) for p in parts]
    return arrs[0] if len(arrs) == 1 else np.concatenate(arrs)
