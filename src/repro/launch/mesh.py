"""Production mesh factory (assignment MULTI-POD DRY-RUN step 1).

A FUNCTION, not a module constant — importing this module never touches
jax device state.  Single pod: 16×16 = 256 chips (TPU v5e pod).  Multi-pod:
(2, 16, 16) = 512 chips with a leading "pod" axis (DP across pods; the
"pod" axis shards the global batch together with "data").
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int | None = None, model: int | None = None):
    """data × model mesh over this process's devices (forced host devices
    in the distributed-engine tests, the chips of one host otherwise).
    Without arguments it spans every device: 2 × k when the count is even
    (eight host devices: 2 × 4; four chips: 2 × 2), else 1 × k."""
    if data is None:
        count = len(jax.devices())
        data = 2 if count % 2 == 0 else 1
        model = count // data
    return _make_mesh((data, model), ("data", "model"))
