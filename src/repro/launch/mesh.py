"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state. Real launches take their devices from the TPU runtime; the pod-scale
dry-run alone emulates 512 CPU devices (it sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax
import).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_host_mesh(model: int = 1) -> jax.sharding.Mesh:
    """Tiny mesh over whatever devices exist (tests / CPU examples)."""
    n = len(jax.devices())
    assert n % model == 0
    return jax.make_mesh(
        (n // model, model), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
    )


def make_array_mesh(n_arrays: int | None = None) -> jax.sharding.Mesh:
    """1-D mesh over the pSRAM arrays (axis ``"array"``).

    One device hosts one array's shard of the nonzero stream
    (``repro.sparse.mesh``); the ``"array"`` axis is a data axis under the
    dist.sharding rules, so ``sparse.arrays_for_mesh`` sees it like any
    batch claim. ``n_arrays`` defaults to every local device: the chips of
    a TPU host (four on a v5e 2x2 host). To rehearse a mesh on a machine
    without them, set ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    before the first jax import; the CPU then shows N virtual devices.
    """
    import numpy as np

    devs = jax.devices()
    n = len(devs) if n_arrays is None else int(n_arrays)
    if n < 1:
        raise ValueError("need at least one array")
    if n > len(devs):
        raise ValueError(
            f"asked for {n} arrays but only {len(devs)} "
            f"{devs[0].platform} devices exist; run on a host with at least "
            f"{n} chips, or, to rehearse on a CPU, set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N before "
            "importing jax"
        )
    return jax.sharding.Mesh(np.asarray(devs[:n]), ("array",))


def chips(mesh: jax.sharding.Mesh) -> int:
    return mesh.devices.size
