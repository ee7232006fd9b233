"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state. Single pod: (16, 16) = 256 chips, axes
("data", "model"). Multi-pod: (2, 16, 16) = 512 chips with a leading "pod"
axis (pure data parallelism across pods; ICI within a pod, DCN across).
"""
from __future__ import annotations

import jax


def _auto_axes(n_axes: int) -> dict:
    return dict(axis_types=(jax.sharding.AxisType.Auto,) * n_axes)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_auto_axes(len(axes)))


def make_host_mesh(model: int = 1) -> jax.sharding.Mesh:
    """Tiny mesh over the locally available devices (tests / examples)."""
    n = len(jax.devices())
    data = max(n // model, 1)
    return jax.make_mesh((data, model), ("data", "model"),
                         **_auto_axes(2))


def make_shard_mesh(n_shards: int):
    """Mesh with a ``data`` axis of exactly ``n_shards`` devices — the shape
    the sharded serving halo collectives (ppermute ring) run over. Returns
    None when the host exposes fewer devices (callers fall back to the host
    loopback transport). CPU-only runners get multiple devices via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``."""
    import numpy as np
    devs = jax.devices()
    if len(devs) < n_shards:
        return None
    return jax.sharding.Mesh(np.asarray(devs[:n_shards]), ("data",))


def ensure_host_devices(n: int) -> bool:
    """Best-effort: expose >= ``n`` host (CPU) devices for the SPMD shard
    executor. Only effective BEFORE the jax backend initializes — appends
    the XLA host-platform flag to ``XLA_FLAGS`` (the same mechanism the CI
    multi-device job uses); once a backend exists the flag is inert and the
    caller must fall back (e.g. to the host-orchestrated executor). Returns
    whether ``n`` devices are actually available afterwards."""
    import os
    flag = "--xla_force_host_platform_device_count"
    flags = os.environ.get("XLA_FLAGS", "")
    if flag not in flags:
        os.environ["XLA_FLAGS"] = f"{flags} {flag}={int(n)}".strip()
    return len(jax.devices()) >= n


def dp_axes(mesh: jax.sharding.Mesh):
    """The data-parallel mesh axes (includes "pod" when present)."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
