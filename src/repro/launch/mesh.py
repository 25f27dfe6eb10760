"""Mesh construction.

FUNCTIONS (not module-level constants) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS for 512 host devices
before first jax init; tests and benches see the default single device.
"""

from __future__ import annotations

from typing import Sequence

import jax


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> jax.sharding.Mesh:
    """The repository's one mesh constructor: every axis ``Auto``.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which a gather
    or take over a sharded operand with no sharding annotation raises
    ``ShardingTypeError``; the model, ``device_parallel_for`` and the
    sharding policy all rely on the compiler propagating shardings."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 = 256 chips per pod; multi_pod adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh() -> jax.sharding.Mesh:
    """Whatever devices exist locally (tests / examples): 1D 'data' mesh."""
    return make_mesh((len(jax.devices()),), ("data",))
