"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run forces a 512-device host platform before first init;
tests and benches must keep seeing a single device).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = [
    "make_production_mesh",
    "make_peel_mesh",
    "make_peel_mesh_2d",
    "make_local_mesh",
]


def _mesh(shape, axes):
    # GSPMD auto-propagation semantics (explicit-mode is jax>=0.9 default)
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single pod (256 chips) or 2×16×16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_peel_mesh(n_devices: int | None = None):
    """1-D mesh for distributed graph peeling (CD link shards / FD
    partitions)."""
    n = n_devices or len(jax.devices())
    return _mesh((n,), ("peel",))


def make_peel_mesh_2d(n_devices: int | None = None,
                      groups: int | None = None):
    """2-D ("grp", "loc") mesh for hierarchical CD collectives.

    The CD round's single logical psum runs staged over this mesh
    (``core.distributed._psum_staged`` with ``axis=("grp", "loc")``):
    reduce within each group of ``loc`` co-located devices, then across
    the ``groups`` groups — nested replica groups instead of one flat
    n-device ring.  ``groups`` defaults to the largest power of two with
    groups² ≤ n that divides n (8 → 2×4, 512 → 16×32); for n = 1 the
    mesh degenerates to (1, 1) and the staged psum is a no-op pair.
    """
    n = n_devices or len(jax.devices())
    if groups is None:
        groups = 1
        while groups * 2 * groups * 2 <= n and n % (groups * 2) == 0:
            groups *= 2
    if n % groups:
        raise ValueError(f"groups={groups} does not divide n={n}")
    return _mesh((groups, n // groups), ("grp", "loc"))


def make_local_mesh():
    """Whatever this host has — used by tests and the quickstart."""
    n = len(jax.devices())
    if n == 1:
        return _mesh((1, 1), ("data", "model"))
    m = 2 if n % 2 == 0 else 1
    return _mesh((n // m, m), ("data", "model"))
