"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state.  The production target is TPU v5e:
16×16 = 256 chips per pod; the multi-pod config is 2 pods = 512 chips with
a leading "pod" axis (DCN between pods, ICI within).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: shardings are
    propagated by the compiler, as the rules in ``parallel.sharding``
    assume (``jax.make_mesh`` defaults to ``Explicit`` axes)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    assert n % model == 0
    return auto_mesh((n // model, model), ("data", "model"))
