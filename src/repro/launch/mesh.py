"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before the first jax init).

  single-pod : (data=16, model=16)            = 256 chips (one v5e pod slice)
  multi-pod  : (pod=2, data=16, model=16)     = 512 chips; `pod` is the FL
               island axis (1 island per pod, paper semantics).
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes, *, devices=None):
    """jax.make_mesh with Auto axes (its default is Explicit): sharding is
    left to GSPMD plus the constraints model code places."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def production_mesh_spec(*, multi_pod: bool = False):
    """(shape, axes) of the production mesh, without touching devices."""
    if multi_pod:
        return (2, 16, 16), ("pod", "data", "model")
    return (16, 16), ("data", "model")


def make_production_mesh(*, multi_pod: bool = False):
    shape, axes = production_mesh_spec(multi_pod=multi_pod)
    return make_mesh(shape, axes)


def abstract_production_mesh(*, multi_pod: bool = False):
    """AbstractMesh with the production shape: usable for ANALYTIC layout
    checks (only mesh.shape is consulted) without the 512-device env."""
    from repro.dist.sharding import abstract_mesh
    shape, axes = production_mesh_spec(multi_pod=multi_pod)
    return abstract_mesh(shape, axes)


def make_host_mesh():
    """Whatever this process actually has (CPU smoke tests: 1 device)."""
    n = len(jax.devices())
    return make_mesh((1, n), ("data", "model"))


def n_islands(mesh) -> int:
    return mesh.shape.get("pod", 1)
