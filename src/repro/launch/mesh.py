"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state). The dry-run process forces 512 host devices via XLA_FLAGS
(set as the first lines of dryrun.py only); the single-pod mesh then uses the
first 256 of them.
"""
from __future__ import annotations

import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    import jax
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices but only {len(devs)} present — "
            "run via launch/dryrun.py which forces 512 host devices")
    return jax.make_mesh(shape, axes, devices=devs[:n])


def make_local_mesh(axes=("data", "model")):
    """1x1 (or 1xN) mesh over whatever devices exist — smoke tests/examples."""
    import jax
    devs = jax.devices()
    from jax.sharding import Mesh
    shape = (1, len(devs)) if len(axes) == 2 else (len(devs),)
    return Mesh(np.asarray(devs).reshape(shape), axes)
