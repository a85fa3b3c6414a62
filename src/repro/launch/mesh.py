"""Production mesh construction.

Target: TPU v5e pods — 16×16 = 256 chips per pod, 2 pods for the multi-pod
dry-run.  Defined as functions so importing this module never touches jax
device state (smoke tests must keep seeing 1 CPU device).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1) -> jax.sharding.Mesh:
    """Tiny mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    assert n % model == 0
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
