"""Production meshes.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state — device count is locked on first jax init, and
only launch/dryrun.py (which sets XLA_FLAGS first) may build the 512-way
placeholder topology.
"""

from __future__ import annotations

import jax

SINGLE_POD = (16, 16)                 # 256 chips (v5e pod slice)
MULTI_POD = (2, 16, 16)               # 2 pods × 256 = 512 chips


def _mk(shape, axes, devices=None):
    # Auto axes: the sharding rules place parameters and GSPMD propagates
    # the rest (make_mesh defaults to Explicit axes).
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = MULTI_POD if multi_pod else SINGLE_POD
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_host_mesh():
    """1-device mesh for CPU smoke paths."""
    return _mk((1, 1), ("data", "model"))


def make_model_mesh(n: int):
    """A ('data', 'model') mesh of the first ``n`` devices, all on 'model':
    the tensor-parallel layout of one host's chips."""
    return _mk((1, n), ("data", "model"), devices=jax.devices()[:n])


def n_chips(mesh) -> int:
    n = 1
    for v in mesh.shape.values():
        n *= v
    return n
