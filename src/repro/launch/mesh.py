"""Production mesh construction.

A function (not a module-level constant) so importing never touches jax
device state. Single pod: 256 chips as (data=16, model=16). Multi-pod: a
leading "pod" axis; ("pod","data") jointly form the DP domain (DESIGN.md §5).
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    assert len(devices) >= n, (
        f"need {n} devices for mesh {shape}, have {len(devices)} — run under "
        f"launch/dryrun.py (it forces 512 host devices)"
    )
    # Auto axes: the models place activations with sharding constraints,
    # which explicit axes (jax.make_mesh's default) would refuse
    return Mesh(np.asarray(devices[:n]).reshape(shape), axes)
