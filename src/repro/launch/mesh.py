"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state.  Single pod: 256 chips as (data=16, model=16);
multi-pod: 2 pods x 256 chips as (pod=2, data=16, model=16) where 'pod'
is the pure-DP cross-pod axis (DCN) and the inner axes are ICI.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def _make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """`jax.make_mesh` with every axis Auto (GSPMD propagates shardings)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Elastic variant: any (pods, data, model) factorization whose product
    matches the available device count."""
    return _make_mesh(shape, axes)


def make_host_mesh(shape: Optional[Tuple[int, ...]] = None,
                   axes: Optional[Tuple[str, ...]] = None):
    """Whatever this host has (CPU smoke tests: 1 device).

    Default: all host devices as (data=n, model=1).  Pass ``shape``/``axes``
    to override the factorization — e.g. ``shape=(2, 2)`` to exercise a
    real 'model' axis on 4 fake CPU devices
    (``XLA_FLAGS=--xla_force_host_platform_device_count=4``), or
    ``shape=(2, 1)`` for a submesh over the first 2 of N devices (how
    ``bench_shard.py`` measures 1 -> N scaling in one process).  The shape
    product must not exceed the host device count.
    """
    import numpy as np

    devices = jax.devices()
    n = len(devices)
    if shape is None:
        assert axes is None, "axes override requires an explicit shape"
        return _make_mesh((n, 1), ("data", "model"))
    shape = tuple(int(s) for s in shape)
    if axes is None:
        axes = ("data", "model")[:len(shape)] if len(shape) <= 2 \
            else ("pod", "data", "model")[:len(shape)]
    if len(axes) != len(shape):
        raise ValueError(f"mesh shape {shape} has {len(shape)} dims but "
                         f"axes {axes} names {len(axes)}")
    want = int(np.prod(shape))
    if want > n:
        raise ValueError(
            f"mesh shape {shape} asks for {want} devices but this host has "
            f"only {n} (len(jax.devices())); reduce the shape or raise "
            f"--xla_force_host_platform_device_count")
    if want == n:
        return _make_mesh(shape, tuple(axes))
    # submesh over the first `want` devices (jax.make_mesh always takes all)
    from jax.sharding import Mesh
    return Mesh(np.asarray(devices[:want]).reshape(shape), tuple(axes))
