"""Production mesh builders.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — dryrun.py must set
``--xla_force_host_platform_device_count`` before first jax init.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips; "pod" is the pure-DP
    cross-pod axis (lowest bandwidth → hierarchical gradient reduction)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests / examples), e.g. ((1, 2), ("data", "model")).

    Axes are ``Auto``: the models place arrays with
    ``with_sharding_constraint``, which refuses the ``Explicit`` axes
    ``jax.make_mesh`` builds by default."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def get_mesh(shape=None, axis_names=None, *, devices=None):
    """(dp, mp) scaling mesh for the sharded sDTW engine — the redco-style
    builder from ``repro.distributed.sharding`` (int / tuple / -1-wildcard
    shapes), re-exported here next to the production LM meshes."""
    from repro.distributed.sharding import get_mesh as _get_mesh
    return _get_mesh(shape, axis_names, devices=devices)
