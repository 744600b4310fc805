"""Production meshes.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first jax init.

Topology (TPU v5e): one pod = 16×16 = 256 chips; multi-pod = 2 pods = 512.
  single-pod axes: ("data", "model")         = (16, 16)
  multi-pod axes:  ("pod", "data", "model")  = (2, 16, 16)
The "model" axis carries TP + EP (intra-pod, fastest ICI); "data" carries
DP + FSDP; "pod" is pure DP (or pipeline stages, see parallel/pipeline.py)
across the slower pod interconnect.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Optional[Tuple[str, ...]] = None):
    """Arbitrary mesh (tests / reduced dry-runs).

    Axes are ``Auto``: the sharding rules here are hints the compiler
    propagates (``with_sharding_constraint``), not the explicit-sharding
    types ``jax.make_mesh`` defaults to."""
    if axes is None:
        axes = ("pod", "data", "model")[-len(shape):]
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(shape))


def dp_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
