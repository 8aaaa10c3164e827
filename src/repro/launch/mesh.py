"""Production mesh definitions (TPU v5e target) + cluster topology model.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax import; tests see 1 CPU).

Usage::

    mesh = make_dev_mesh(data=len(jax.devices()))   # tests / this container
    mesh = make_production_mesh()                   # 256-chip pod
    mesh = make_production_mesh(multi_pod=True)     # 512 chips, 2 pods
    topo = topology("v100", nodes=8)                # 64 GPUs, 8 per node
    mesh = make_node_mesh(nodes=2, devices_per_node=2)   # (node, device)

Axis conventions across the repo: ``pod`` and ``data`` carry the batch
(pure data parallelism — the paper's mirrored strategy, and the axes the
training engine shards over); ``model`` carries tensor/expert parallelism
for the big LM archs; ``node`` × ``device`` is the hierarchical 2-level
layout of a multi-node cluster (paper §5: multi-worker GPU nodes and TPU
pods) — ``device`` peers talk over NVLink/ICI, ``node`` peers over the
node NIC / DCN.  ``HARDWARE`` holds the per-chip roofline constants the
benchmarks divide by; :class:`Topology` carries the per-LINK constants
the cross-node interconnect model (`cloud/interconnect.py`) divides by.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import numpy as np


# ---------------------------------------------------------------------------
# Cluster topology (paper §5: multi-node GPU / multi-pod TPU scale-out)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Link:
    """One interconnect class: sustained bandwidth (B/s, per direction and
    per participant) and per-message latency (s)."""
    bandwidth: float
    latency: float

    def transfer_s(self, nbytes: float) -> float:
        return nbytes / self.bandwidth + self.latency


@dataclasses.dataclass(frozen=True)
class Topology:
    """A 2-level cluster: ``nodes`` × ``devices_per_node`` accelerators.

    ``intra_link`` is the in-node fabric (NVLink for V100 nodes, ICI for
    TPU slices); ``inter_link`` is what crosses node boundaries (the VM
    NIC for GPU nodes; still ICI inside a TPU pod, which is exactly why
    the paper's TPU weak scaling stays linear while GPUs pay a NIC tax).
    ``peak_flops``/``hbm_bw`` are per-device roofline constants for the
    analytic planner.
    """
    name: str
    nodes: int
    devices_per_node: int
    intra_link: Link
    inter_link: Link
    device_kind: str = "v100"
    peak_flops: float = 125e12          # per device
    hbm_bw: float = 900e9               # per device

    @property
    def total_devices(self) -> int:
        return self.nodes * self.devices_per_node

    @property
    def mesh_shape(self) -> Tuple[int, int]:
        return (self.nodes, self.devices_per_node)

    axis_names: Tuple[str, str] = ("node", "device")


# Per-link constants (paper-era GCP hardware, see docs/scaling.md):
# V100 NVLink effective all-reduce bandwidth per GPU; the n1 VM NIC is
# shared by the whole 8-GPU node.  TPU ICI links stay on-fabric across
# board boundaries, so inter == intra inside a pod slice.
NVLINK = Link(bandwidth=130e9, latency=5e-6)
GPU_NIC = Link(bandwidth=12.5e9, latency=25e-6)      # 100 Gbit/s VM NIC
TPU_V2_ICI = Link(bandwidth=60e9, latency=2e-6)
TPU_V3_ICI = Link(bandwidth=70e9, latency=2e-6)
V5E_ICI = Link(bandwidth=50e9, latency=2e-6)


def gpu_topology(nodes: int, gpus_per_node: int = 8) -> Topology:
    """The paper's GPU configuration: n1 nodes with 8 V100s each, scaled
    1..16 nodes (8..128 GPUs, Fig. 2 / Fig. 5)."""
    return Topology(f"v100x{nodes * gpus_per_node}", nodes, gpus_per_node,
                    NVLINK, GPU_NIC, device_kind="v100",
                    peak_flops=125e12, hbm_bw=900e9)


def tpu_topology(version: str, cores: int) -> Topology:
    """TPU v2/v3 slices as node×device grids of 8-core boards.  Cross-board
    traffic inside a slice rides the same ICI fabric (inter == intra)."""
    ici = {"v2": TPU_V2_ICI, "v3": TPU_V3_ICI, "v5e": V5E_ICI}[version]
    per_core = {"v2": 23e12, "v3": 61e12, "v5e": 197e12}[version]
    boards = max(cores // 8, 1)
    return Topology(f"tpu_{version}-{cores}", boards, min(cores, 8),
                    ici, ici, device_kind=f"tpu_{version}",
                    peak_flops=per_core, hbm_bw=ici.bandwidth * 14)


def topology(family: str, nodes: int = 1, devices_per_node: int = 8) -> Topology:
    """Factory over the paper's configurations: ``("v100", nodes=1..16)``,
    ``("tpu_v2", cores)``, ``("tpu_v3", cores)``."""
    if family == "v100":
        return gpu_topology(nodes, devices_per_node)
    if family.startswith("tpu_"):
        return tpu_topology(family.split("_", 1)[1],
                            nodes * devices_per_node)
    raise ValueError(f"unknown topology family {family!r}")


# the paper's measured configurations, by name (Fig. 2 / Fig. 5)
TOPOLOGIES = {
    **{f"v100x{8 * n}": gpu_topology(n) for n in (1, 2, 4, 8, 16)},
    "tpu_v2-8": tpu_topology("v2", 8),
    "tpu_v3-8": tpu_topology("v3", 8),
    "tpu_v3-32": tpu_topology("v3", 32),
}


def make_mesh(shape, axes, devices=None):
    """The repo's one mesh constructor: every axis ``Auto``.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, which
    ``with_sharding_constraint`` and the builtin loop's GSPMD placement
    reject; building every mesh here keeps the whole repo on ``Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_node_mesh(nodes: int = 1, devices_per_node: int = 0,
                   topo: Topology = None):
    """Hierarchical ``(node, device)`` mesh folded onto the host's devices.

    On a real cluster each ``node`` row maps to one machine; on this
    container the host's devices (1 CPU, or N virtual devices under
    ``--xla_force_host_platform_device_count``) are folded into a VIRTUAL
    node×device grid — collectives over ``node`` and ``device`` then
    execute locally, which is how the parity tests pin hierarchical
    reduction numerics without a cluster.  Requires nodes*devices_per_node
    <= len(jax.devices()); sizes are clamped like :func:`make_dev_mesh`
    when ``devices_per_node`` is 0 (auto: fill with what exists).
    """
    if topo is not None:
        nodes, devices_per_node = topo.nodes, topo.devices_per_node
    n_avail = len(jax.devices())
    if devices_per_node <= 0:
        nodes = min(nodes, n_avail)
        devices_per_node = max(n_avail // nodes, 1)
    need = nodes * devices_per_node
    if need > n_avail:
        raise ValueError(
            f"virtual topology {nodes}x{devices_per_node} needs {need} "
            f"devices, host has {n_avail} (set "
            "--xla_force_host_platform_device_count before importing jax)")
    return make_mesh((nodes, devices_per_node), ("node", "device"))


def surviving_devices(mesh, lost_node: int):
    """The device grid of a ``(node, device)`` mesh minus one node row.

    The elastic trainer's view of a preemption: node ``lost_node``'s
    devices are gone, the remaining rows keep their order (surviving
    replicas keep their relative ranks)."""
    grid = np.asarray(mesh.devices)
    if grid.ndim != 2 or mesh.axis_names != ("node", "device"):
        raise ValueError(
            f"expected a (node, device) mesh, got {mesh.axis_names} "
            f"of shape {grid.shape}")
    if not 0 <= lost_node < grid.shape[0]:
        raise ValueError(f"lost_node {lost_node} out of range for "
                         f"{grid.shape[0]} nodes")
    keep = [r for r in range(grid.shape[0]) if r != lost_node]
    return grid[keep]


def shrink_node_mesh(mesh, lost_node: int):
    """Re-mesh after losing a node: the surviving ``(node, device)`` grid.

    Raises ``ValueError`` when the mesh has a single node — with no
    surviving capacity there is nothing to re-mesh onto (the elastic
    trainer treats that preemption as respawn-and-restart instead).
    """
    grid = surviving_devices(mesh, lost_node)
    if grid.shape[0] == 0:
        raise ValueError("cannot shrink a single-node mesh: no survivors")
    return jax.sharding.Mesh(grid, ("node", "device"))


def replica_meshes(mesh):
    """Split a ``(node, device)`` mesh into one 1-D ``device`` mesh per
    node row — the serving runtime's replica layout.

    Training shards ONE step over the whole grid; serving instead runs
    N independent generator replicas (one per node), so a preempted
    node takes out exactly one replica and `serve/replicas.ReplicaGroup`
    fails the in-flight bucket step over to a survivor.  Row order is
    preserved, so replica rank == node row == the ``node`` index a
    `train/faults.FaultPlan` ``preempt`` event targets.
    """
    grid = np.asarray(mesh.devices)
    if grid.ndim != 2 or mesh.axis_names != ("node", "device"):
        raise ValueError(
            f"expected a (node, device) mesh, got {mesh.axis_names} "
            f"of shape {grid.shape}")
    return [jax.sharding.Mesh(grid[r], ("device",))
            for r in range(grid.shape[0])]


def make_production_mesh(*, multi_pod: bool = False, data: int = 16,
                         model: int = 16):
    """Single pod: (data=16, model=16) = 256 chips (default).
    Multi-pod: (pod=2, data, model) = 512 chips; the ``pod`` axis is pure
    data parallelism (the paper's multi-worker mirrored analogue).

    ``data``/``model`` re-factorize the 256 chips per pod — the §Perf
    hillclimb's layout lever (paper Fig. 4): data*model must equal 256."""
    assert data * model == 256, (data, model)
    shape = (2, data, model) if multi_pod else (data, model)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_dev_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many real devices exist (tests/examples)."""
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, max(n // data, 1))
    return make_mesh((data, model), ("data", "model"))


HARDWARE = {
    # TPU v5e per-chip constants used by the roofline report
    "peak_flops_bf16": 197e12,     # FLOP/s
    "hbm_bw": 819e9,               # B/s
    "ici_bw": 50e9,                # B/s per link
    "chips_single_pod": 256,
    "chips_multi_pod": 512,
}
