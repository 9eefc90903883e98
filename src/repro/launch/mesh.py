"""Production meshes.

``make_serving_mesh`` is the one-axis ``data`` mesh the serving engine
shards over; tests, the launcher and ``chip_smoke.py`` all build it here.

``make_production_mesh`` is the spec-mandated entry point: 16x16 = 256 chips
per pod, and 2x16x16 = 512 chips for the multi-pod dry-run.  It is a
function (never a module-level constant) so importing this module touches no
jax device state.

``make_tuned_mesh`` reshapes the *same* device order into the paper-tuner's
factored ("data", "pool", "intra") axes when a plan wants ``pools > 1`` that
the flat model axis cannot express (e.g. grok's 8 experts on a 16-wide
axis).  Device order is preserved, so ICI adjacency assumptions carry over.
"""

from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes, devices=None):
    # Auto axes: sharding is propagated by GSPMD from the constraints the
    # model code places; jax.make_mesh otherwise defaults to Explicit
    # axes, under which ops like jnp.take reject sharded operands
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_serving_mesh(n: Optional[int] = None):
    """The 1-D ``("data",)`` mesh ``Engine(rules=...)`` shards slots and
    page pools over, on the first ``n`` devices (default: all)."""
    devices = jax.devices()[:n]
    return _make_mesh((len(devices),), ("data",), devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_tuned_mesh(pools: int, *, multi_pod: bool = False,
                    model_axis: int = 16, data_axis: int = 16):
    if pools <= 1:
        return make_production_mesh(multi_pod=multi_pod)
    assert model_axis % pools == 0, (model_axis, pools)
    intra = model_axis // pools
    if multi_pod:
        return _make_mesh((2, data_axis, pools, intra),
                          ("pod", "data", "pool", "intra"))
    return _make_mesh((data_axis, pools, intra), ("data", "pool", "intra"))


def mesh_for_plan(plan, *, multi_pod: bool = False, factored: bool = False):
    """The mesh a plan runs on.  ``factored=False`` keeps the spec-mandated
    axes (pool degree expressed through divisible dims only)."""
    if factored and plan.pools > 1:
        return make_tuned_mesh(plan.pools, multi_pod=multi_pod,
                               model_axis=plan.pools * plan.intra,
                               data_axis=plan.data)
    return make_production_mesh(multi_pod=multi_pod)


def describe(mesh) -> str:
    return "x".join(f"{k}={v}" for k, v in mesh.shape.items())
