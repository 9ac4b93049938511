"""Every device mesh in the repo is built here, by ``make_mesh``.

Axes are ``AxisType.Auto``: GSPMD propagates shardings between the
constraints, which is the semantics ``distributed/sharding.py`` and the
model's ``with_sharding_constraint`` calls are written for.  (``jax.make_mesh``
defaults to Explicit axes, under which those constraints become asserts.)

Functions, not module-level constants, so importing this module never
touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init.
"""

from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(shape, axes) -> Mesh:
    """A mesh of ``shape`` named ``axes`` over the first ``prod(shape)``
    devices JAX sees."""
    shape, axes = tuple(shape), tuple(axes)
    devices = jax.devices()
    n = math.prod(shape)
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)} — on the "
            f"CPU run under XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n} (dryrun.py sets this itself)")
    return Mesh(np.asarray(devices[:n]).reshape(shape), axes,
                axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh() -> Mesh:
    """``(1, n)`` ``("data", "model")`` over the n devices JAX sees: the
    expert-parallel group spans all chips (``launch/train.py --mesh host``)."""
    return make_mesh((1, len(jax.devices())), ("data", "model"))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: 256 chips (16, 16) ("data", "model").
    Multi-pod: 2 pods = 512 chips (2, 16, 16) ("pod", "data", "model") —
    "pod" is an outer data-parallel axis crossing the inter-pod links."""
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))
