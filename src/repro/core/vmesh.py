"""JAX integration: virtual NPUs as `jax.sharding.Mesh` submeshes.

This is where the paper's routing table becomes executable: the assignment
``virtual core id -> physical core id`` chosen by the topology mapper is
materialized as the *device array layout* of a JAX Mesh.  Logical mesh
coordinates (what pjit/shard_map see) are the virtual topology; the physical
devices behind them are whatever the hypervisor allocated — exactly the
vRouter redirect of §4.1, realized at the SPMD-partitioner level.

Elastic remap (device failure) re-runs the similar-topology mapping over the
survivors and returns a new Mesh; the training runtime then re-shards its
checkpoint onto it (see train/loop.py and examples/elastic_failover.py).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

try:  # jax is required at runtime but keep import errors readable
    import jax
    from jax.sharding import Mesh
except Exception as e:  # pragma: no cover
    raise ImportError("repro.core.vmesh requires jax") from e

from .hypervisor import AllocationError, Hypervisor, VirtualNPU, VNPURequest
from .topology import Topology, mesh_2d


@dataclasses.dataclass
class DeviceTopology:
    """Binding between an NPU topology and a set of JAX devices.

    ``node_to_device[i]`` is the JAX device sitting at physical core id
    ``i``.  Devices that carry ICI ``coords`` (TPU chips) are bound by
    position: core (row, col) is the chip at ``coords`` (x=col, y=row), so
    topology neighbours are ICI neighbours.  Devices without coordinates
    (the CPU host-platform backend) are bound in enumeration order.
    """

    topo: Topology
    node_to_device: Dict[int, "jax.Device"]

    @staticmethod
    def from_devices(devices: Sequence["jax.Device"],
                     mesh_shape: Optional[Tuple[int, int]] = None,
                     torus: bool = False) -> "DeviceTopology":
        n = len(devices)
        if all(getattr(d, "coords", None) is not None for d in devices):
            devices = sorted(devices, key=lambda d: (
                tuple(d.coords[2:]), d.coords[1], d.coords[0],
                getattr(d, "core_on_chip", 0)))
            xs = {d.coords[0] for d in devices}
            ys = {d.coords[1] for d in devices}
            if mesh_shape is None and len(xs) * len(ys) == n:
                mesh_shape = (len(ys), len(xs))
        if mesh_shape is None:
            r = int(np.floor(np.sqrt(n)))
            while n % r:
                r -= 1
            mesh_shape = (r, n // r)
        if mesh_shape[0] * mesh_shape[1] != n:
            raise ValueError(f"mesh {mesh_shape} != {n} devices")
        topo = mesh_2d(*mesh_shape, torus=torus, name="pod")
        return DeviceTopology(topo, {i: d for i, d in enumerate(devices)})

    def device_for(self, node: int) -> "jax.Device":
        return self.node_to_device[node]


class VirtualMeshError(RuntimeError):
    pass


def virtual_mesh(vnpu: VirtualNPU, dt: DeviceTopology,
                 axis_names: Tuple[str, ...] = ("data", "model")) -> Mesh:
    """Materialize a virtual NPU as a JAX Mesh.

    The virtual topology must be a rectangular mesh (the common case for
    SPMD programs); its row-major node order defines the logical coordinate
    grid, and the routing-table assignment places physical devices.
    """
    vt = vnpu.virtual_topology()
    shape = vt.is_rect_mesh()
    if shape is None:
        # 1-D virtual topologies (lines/rings) are still usable as a flat mesh
        if len(axis_names) != 1:
            raise VirtualMeshError(
                "non-rectangular virtual topology needs a single axis")
        order = vt.nodes()
        devs = np.array([dt.device_for(vnpu.assignment[v]) for v in order])
        return Mesh(devs, axis_names)
    r, c = shape
    if len(axis_names) != 2:
        raise VirtualMeshError(f"2D virtual topology needs 2 axis names")
    # row-major over virtual coords
    by_coord = {vt.coords[n]: n for n in vt.nodes()}
    rows = sorted({rc[0] for rc in by_coord})
    cols = sorted({rc[1] for rc in by_coord})
    grid = np.empty((r, c), dtype=object)
    for i, rr in enumerate(rows):
        for j, cc in enumerate(cols):
            vnode = by_coord[(rr, cc)]
            grid[i, j] = dt.device_for(vnpu.assignment[vnode])
    return Mesh(grid, axis_names)


@dataclasses.dataclass
class TenantMesh:
    """A tenant's full handle: hypervisor object + JAX mesh."""
    vnpu: VirtualNPU
    mesh: Mesh
    dt: DeviceTopology


def allocate_tenant(hyp: Hypervisor, dt: DeviceTopology,
                    topology: Topology,
                    axis_names: Tuple[str, ...] = ("data", "model"),
                    node_match=None, edge_match=None,
                    **req_kwargs) -> TenantMesh:
    """One-call tenant setup: topology mapping -> routing table -> JAX mesh.

    The mapping runs through the hypervisor's MappingEngine; pass
    ``mapper="exact"|"hybrid"|"bipartite"|"rect"`` (a ``VNPURequest`` field)
    to pick a speed/accuracy point, and ``node_match``/``edge_match`` for
    heterogeneous or critical-edge-aware placement.
    """
    req = VNPURequest(topology=topology, **req_kwargs)
    vnpu = hyp.create_vnpu(req, node_match=node_match, edge_match=edge_match)
    mesh = virtual_mesh(vnpu, dt, axis_names)
    return TenantMesh(vnpu=vnpu, mesh=mesh, dt=dt)


def elastic_remap(hyp: Hypervisor, dt: DeviceTopology, tenant: TenantMesh,
                  failed_nodes: Iterable[int],
                  axis_names: Optional[Tuple[str, ...]] = None) -> TenantMesh:
    """Failure path: re-run the similar-topology mapping excluding the failed
    cores (which the hypervisor quarantines — they never rejoin the
    allocatable pool); returns a fresh TenantMesh on the surviving devices.

    This is the paper's allocator doing double duty as the fault-tolerance
    mechanism — the 'closest legal submesh' is exactly what a 1000-node
    deployment needs when a tray drops.
    """
    names = axis_names or tenant.mesh.axis_names
    vnpu = hyp.remap_vnpu(tenant.vnpu.vmid, failed_nodes)
    mesh = virtual_mesh(vnpu, dt, tuple(names))
    return TenantMesh(vnpu=vnpu, mesh=mesh, dt=dt)


def device_permutation(old: TenantMesh, new: TenantMesh) -> Dict[int, int]:
    """old physical node -> new physical node per virtual coordinate; used by
    the checkpoint layer to compute the resharding plan after a remap."""
    out = {}
    for v, p_old in old.vnpu.assignment.items():
        out[p_old] = new.vnpu.assignment[v]
    return out
