"""Production mesh construction.

A function, not a module-level constant — importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first jax init).

Mesh axes:
  pod    — across-pod (DCN) axis: data parallel by default, pipeline
           parallel with --pp (distributed/pipeline.py)
  data   — within-pod batch/expert/ZeRO axis
  model  — tensor parallel axis (Megatron layout, paper Fig. 2)
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # Auto axes: the sharding rules and in-model constraints are hints for
    # GSPMD propagation, not Explicit-mode type assertions
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, pod: int = 1):
    """Small mesh over host devices for tests/examples."""
    if pod > 1:
        return _mesh((pod, data, model), ("pod", "data", "model"))
    return _mesh((data, model), ("data", "model"))
