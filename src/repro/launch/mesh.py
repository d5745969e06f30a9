"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import, and everything else sees the real (single-CPU) device.

Mesh layout (TPU v5e pods of 256 chips):
  single-pod : (data=16, model=16)               = 256 chips
  multi-pod  : (pod=2, data=16, model=16)        = 512 chips
The 'model' axis is the innermost (fastest ICI ring) — TP/EP collectives
stay on-pod; only the DP gradient all-reduce crosses the 'pod' axis.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with Auto axis types on every axis.

    The model code relies on GSPMD propagation (``in_shardings`` on the
    jits, ``with_sharding_constraint`` on activations), which is what Auto
    axes give.  JAX >= 0.7 makes Explicit axes by default, under which
    e.g. the embedding gather raises ``ShardingTypeError``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(n_devices=None, model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = n_devices or len(jax.devices())
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"))
