"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state -- the dry-run must set XLA_FLAGS before any jax
initialization.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto(n: int):
    # the repo's specs are GSPMD hints (with_sharding_constraint, jit
    # outside a mesh context): that is Auto axes, not make_mesh's default
    return (AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 chips per pod (data x model); multi-pod adds a leading 2-pod axis.

    v5e-256 pod topology: 'data' rides the pod-internal 2D torus, 'model'
    stays within the densest links; the 'pod' axis crosses DCI.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh(shape=None, axes=("data", "model")):
    """Small mesh over whatever devices exist (tests, examples)."""
    n = len(jax.devices())
    if shape is None:
        shape = (n, 1)
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))
