"""Production mesh construction (single-pod 16x16, multi-pod 2x16x16)."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """256-chip pod mesh (data, model), or 512-chip 2-pod (pod, data, model).

    A function (not a module constant) so importing never touches device
    state; the dry-run sets XLA_FLAGS for 512 host devices before any import.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(max_data: int | None = 1):
    """A (data, model) host mesh for tests / reduced runs.

    ``max_data`` caps the data axis (default 1): the CI matrix forces up to
    8 virtual host devices (ci.yml, DESIGN.md §7) and reduced-cell batch
    sizes need not divide the forced device count, so the smoke meshes stay
    single-shard unless a caller opts into more.  ``None`` spans every
    visible device.
    """
    import numpy as np

    dev = np.array(jax.devices())
    if max_data is not None:
        dev = dev[: max(1, int(max_data))]
    return jax.sharding.Mesh(dev.reshape(-1, 1), ("data", "model"))
