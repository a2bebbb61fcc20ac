"""Mesh execution plane: device topology for pod-scale multichip serving.

One server process owns a set of chips (a v5e-8 slice, or N virtual CPU
devices under ``--xla_force_host_platform_device_count``).  This module
carves them into **chip groups** — each group drives one ``DeviceLane``
(engine/dispatch.py ``LaneGroup``) and executes queries as ONE SPMD
program over its own 1-D ``segments`` mesh (``parallel/multichip.py``):
segment columns stage as sharded arrays across the group
(``device.stage_segments`` with a ``NamedSharding``), and the
per-segment combine lowers to an on-device ``psum``/``pmin``/``pmax``
over ICI instead of a host-side merge.

Topology is env-configured (read once at server construction):

  PINOT_TPU_MESH_SHAPE=LxC   L lane groups of C chips each ("2x4");
                             a bare "8" means one lane of 8 chips
  PINOT_TPU_LANES=L          L lane groups over all visible devices,
                             split evenly (devices // L chips per lane)

With neither set the topology is the **trivial single lane** — exactly
the pre-mesh serving path (one lane, no mesh, default device), so
existing deployments and tests see zero behavior change.  Tier-1 runs
simulate a pod slice with ``XLA_FLAGS=--xla_force_host_platform_device_
count=N`` (``utils/platform.virtual_cpu_mesh`` — the conftest already
forces 8).

Fallback matrix (README "Mesh execution" has the operator view):

  group size 1 + trivial topology  -> single-chip vmapped kernel (the
                                      pre-mesh path, byte-identical)
  group size >= 1, explicit shape  -> shard_map SPMD kernel over the
                                      group's mesh (size-1 groups run
                                      the same program; psum over one
                                      device is the identity)
  device failure / poisoned plan   -> the owning lane quarantines and
                                      the query serves via the host
                                      path; OTHER lanes keep serving
                                      (per-lane supervision is
                                      unchanged from the single lane)
"""
from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

SEGMENT_AXIS = "segments"  # mirrors parallel.multichip.SEGMENT_AXIS


@dataclass(frozen=True)
class ChipGroup:
    """One lane's slice of the server's devices.  ``mesh`` is the 1-D
    ``segments`` Mesh the group's kernels shard over, or None for the
    trivial single-chip group (the pre-mesh fallback path)."""

    index: int
    devices: Tuple[Any, ...] = ()
    mesh: Any = None  # jax.sharding.Mesh | None

    @property
    def size(self) -> int:
        return max(1, len(self.devices))

    # NOTE: the group's NamedSharding is derived (and cached) by
    # QueryExecutor._mesh_placement, and placement identity by
    # device.placement_key — ONE implementation each, shared by the
    # serving path, EXPLAIN, and the staging cache.

    def snapshot(self) -> Dict[str, Any]:
        return {
            "index": self.index,
            "size": self.size,
            "deviceIds": [getattr(d, "id", None) for d in self.devices],
            "sharded": self.mesh is not None,
        }


@dataclass(frozen=True)
class MeshTopology:
    """The server's chip-group layout: ``groups[i]`` backs lane ``i``."""

    groups: Tuple[ChipGroup, ...]
    source: str = "single"  # "single" | "env" | "mesh-arg"

    @property
    def num_lanes(self) -> int:
        return len(self.groups)

    @property
    def num_devices(self) -> int:
        return sum(g.size for g in self.groups)

    @property
    def devices_per_lane(self) -> int:
        return max(g.size for g in self.groups)

    @property
    def trivial(self) -> bool:
        """True for the pre-mesh single-lane/no-mesh layout."""
        return self.num_lanes == 1 and self.groups[0].mesh is None

    @property
    def primary_mesh(self):
        return self.groups[0].mesh

    def snapshot(self) -> Dict[str, Any]:
        return {
            "shape": f"{self.num_lanes}x{self.devices_per_lane}",
            "lanes": self.num_lanes,
            "devicesPerLane": self.devices_per_lane,
            "devices": self.num_devices,
            "shardAxis": SEGMENT_AXIS if not self.trivial else None,
            "source": self.source,
            "groups": [g.snapshot() for g in self.groups],
        }

    # -- constructors --------------------------------------------------
    @staticmethod
    def single() -> "MeshTopology":
        """The trivial topology: one lane, no mesh, default device —
        the exact pre-mesh serving path.  Touches no jax state (safe
        to build before backend init)."""
        return MeshTopology(groups=(ChipGroup(index=0),), source="single")

    @staticmethod
    def from_mesh(mesh) -> "MeshTopology":
        """Legacy adapter: one lane driving an explicit Mesh (the old
        ``ServerInstance(mesh=...)`` / ``QueryExecutor(mesh=...)``
        configuration)."""
        if mesh is None:
            return MeshTopology.single()
        devices = tuple(mesh.devices.flat)
        return MeshTopology(
            groups=(ChipGroup(index=0, devices=devices, mesh=mesh),),
            source="mesh-arg",
        )

    @staticmethod
    def env_configured() -> bool:
        """True when the env requests a non-trivial topology — the
        gate that keeps default construction from touching
        ``jax.devices()`` (backend init) at all."""
        return bool(
            os.environ.get("PINOT_TPU_MESH_SHAPE")
            or os.environ.get("PINOT_TPU_LANES")
        )

    @staticmethod
    def from_env(devices: Optional[Sequence[Any]] = None) -> "MeshTopology":
        """Topology from ``PINOT_TPU_MESH_SHAPE`` / ``PINOT_TPU_LANES``
        (module docstring).  Unset env -> the trivial single lane,
        with NO backend init.  Impossible requests degrade instead of
        raising: lane count clamps to the visible device count, chips
        per lane clamp to what divides evenly — a misconfigured env
        must not take serving down."""
        if not MeshTopology.env_configured():
            return MeshTopology.single()
        if devices is None:
            import jax

            devices = jax.devices()
        devices = list(devices)
        n = len(devices)
        lanes, per_lane = _parse_topology_env(n)
        if lanes <= 1 and per_lane <= 1:
            return MeshTopology.single()
        return build_topology(devices, lanes, per_lane, source="env")


def _parse_topology_env(n_devices: int) -> Tuple[int, int]:
    """(lanes, chips per lane) from the env, clamped to ``n_devices``."""
    shape = os.environ.get("PINOT_TPU_MESH_SHAPE", "").strip().lower()
    lanes_env = os.environ.get("PINOT_TPU_LANES", "").strip()
    lanes = 0
    per_lane = 0
    if shape:
        parts = shape.replace("*", "x").split("x")
        try:
            if len(parts) == 2:
                lanes, per_lane = int(parts[0]), int(parts[1])
            elif len(parts) == 1:
                per_lane = int(parts[0])
        except ValueError:
            lanes = per_lane = 0  # junk env must not take serving down
    if lanes_env:
        try:
            lanes = int(lanes_env)
        except ValueError:
            pass
    lanes = max(1, min(lanes, n_devices)) if lanes else 0
    if not lanes:
        lanes = max(1, n_devices // per_lane) if per_lane else 1
    if not per_lane:
        per_lane = max(1, n_devices // lanes)
    per_lane = max(1, min(per_lane, n_devices // lanes))
    return lanes, per_lane


def build_topology(
    devices: Sequence[Any], lanes: int, per_lane: int, source: str = "env"
) -> "MeshTopology":
    """Partition ``devices`` into ``lanes`` groups of ``per_lane`` chips
    (clamped to what is available).  Every group gets a 1-D
    ``segments`` Mesh — including size-1 groups, whose shard_map
    program is the single-chip program with identity collectives, so
    placement (each lane pinned to ITS chip) stays uniform."""
    from pinot_tpu.parallel.multichip import default_mesh

    devices = list(devices)
    lanes = max(1, min(lanes, len(devices)))
    per_lane = max(1, min(per_lane, len(devices) // lanes))
    groups: List[ChipGroup] = []
    for i in range(lanes):
        devs = tuple(devices[i * per_lane : (i + 1) * per_lane])
        groups.append(ChipGroup(index=i, devices=devs, mesh=default_mesh(devs)))
    return MeshTopology(groups=tuple(groups), source=source)


def collective_names(plan) -> List[str]:
    """The XLA collectives a plan's cross-chip merge lowers to, from
    its output reducers (parallel/multichip.py ``_collective``) — the
    EXPLAIN ``mesh.collective`` field."""
    from pinot_tpu.engine.kernel import output_reducers

    ops = set()
    for op in output_reducers(plan).values():
        if op == "sum" or op == "sum_pair":
            ops.add("psum")
        elif op == "min":
            ops.add("pmin")
        elif op == "max":
            ops.add("pmax")
        elif op == "minmax_pair":
            ops.update(("pmin", "pmax"))
        elif op == "distinct_pairs":
            ops.update(("all_gather", "psum"))
        elif op == "none":
            ops.add("gather")  # sharded outputs gather host-side
    return sorted(ops)
