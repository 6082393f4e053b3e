"""Production mesh construction.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — required for the dry-run's
XLA_FLAGS=--xla_force_host_platform_device_count dance and for tests that
build small meshes.

Mesh shapes (TPU v5e target):
  single-pod: (16, 16)    axes (data, model)          = 256 chips
  multi-pod:  (2, 16, 16) axes (pod, data, model)     = 512 chips

`pod` is the inter-cluster axis in the paper's clusters-of-clusters sense
(§4): data-parallel by default, or the pipeline/cluster axis when the
Cluster Builder requests stage parallelism.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AbstractMesh, AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """Arbitrary mesh (tests, elastic re-meshing)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape))


def make_abstract_mesh(shape: Sequence[int], axes: Sequence[str]):
    """Device-free mesh for spec-only planning (tests, plan inspection)."""
    return AbstractMesh(tuple(shape), tuple(axes))


def required_devices(multi_pod: bool = False) -> int:
    return 512 if multi_pod else 256
