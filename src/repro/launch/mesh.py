"""Production mesh construction (TPU v5e target).

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — required because the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before jax's first
device initialization, while smoke tests/benches must see the 1 real device.
"""

from __future__ import annotations

import jax

from repro.core.mesh import make_mesh
from repro.train.sharding import MeshPlan

__all__ = ["make_production_mesh", "default_plan", "PLANS"]


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod: (pod=2, data=16, model=16) = 512 chips.

    When more devices exist than the mesh needs (the 512-device dry-run
    lowering a single-pod mesh), the first prod(shape) devices are used.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    ndev = len(jax.devices())
    if ndev < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {ndev} — run under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 (dry-run) "
            "or on the real slice")
    return make_mesh(shape, axes)


# DPSVRG node mappings (DESIGN.md §4):
#   paper-faithful  — one node per data-parallel rank (m = 16 per pod)
#   production      — one node per pod, DP+FSDP inside (m = 2; multi-pod only)
#   full            — every (pod, data) rank is a node (m = 32; multi-pod only)
PLANS = {
    ("single", "faithful"): MeshPlan(node_axes=("data",), fsdp_axes=()),
    ("multi", "faithful"): MeshPlan(node_axes=("pod", "data"), fsdp_axes=()),
    ("multi", "production"): MeshPlan(node_axes=("pod",), fsdp_axes=("data",)),
}


def default_plan(multi_pod: bool, mapping: str = "auto") -> MeshPlan:
    if mapping == "auto":
        mapping = "production" if multi_pod else "faithful"
    return PLANS[("multi" if multi_pod else "single", mapping)]


def node_count(mesh, plan: MeshPlan) -> int:
    m = 1
    for ax in plan.node_axes:
        m *= mesh.shape[ax]
    return m
