"""Production mesh construction (function, not module constant — importing
this module never touches jax device state)."""
from __future__ import annotations

import numpy as np


def make_mesh(shape, axes, devices):
    """The one mesh constructor of the repo: every axis ``AxisType.Auto``.

    ``jax.make_mesh`` defaults to explicit axes, under which the sharded
    rounds' gathers raise ``ShardingTypeError``; the programs here let the
    partitioner place what they do not pin with ``shard_map``."""
    import jax
    from jax.sharding import AxisType

    return jax.make_mesh(
        shape, axes, devices=devices, axis_types=(AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """(16, 16) = 256 chips single pod; (2, 16, 16) = 512 chips across 2 pods."""
    import jax

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices for the production mesh, have {len(devs)}. "
            "Set XLA_FLAGS=--xla_force_host_platform_device_count=512 BEFORE "
            "importing jax (dryrun.py does this)."
        )
    return make_mesh(shape, axes, devs[:n])


def make_host_mesh(model: int = 1):
    """Small mesh over whatever devices exist (smoke tests, examples)."""
    import jax

    n = len(jax.devices())
    model = max(1, min(model, n))
    data = n // model
    return make_mesh((data, model), ("data", "model"), jax.devices()[: data * model])


def data_axis_size(mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in mesh.shape if a in ("pod", "data")]))
