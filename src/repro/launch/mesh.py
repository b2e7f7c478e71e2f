"""Mesh construction.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the 'pod' axis composes
with 'data' for gradient reduction (hierarchical reduce: reduce-scatter
intra-pod over ICI, cross-pod all-reduce over DCN — the paper's
direct-vs-mediated hierarchy at pod granularity).

Every mesh is built through :func:`make_mesh`, which marks each axis
``Auto``: the installed JAX makes ``jax.make_mesh`` axes ``Explicit`` by
default, and the library's sharding constraints and shard_map islands are
written for sharding propagation over Auto axes.

Functions, not module constants: importing this module must never touch jax
device state (the dry-run pins the device count before first jax init).
"""

from __future__ import annotations

from collections.abc import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(*, model: int = 2):
    """Small mesh over however many (host) devices exist — tests/examples."""
    n = len(jax.devices())
    model = min(model, n)
    return make_mesh((n // model, model), ("data", "model"))
