"""Production meshes (importing this module never touches jax device state).

Every mesh here has Auto axes, so tests, benchmarks and the engine build
meshes the same way."""
from __future__ import annotations


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh(shape, axes)`` with Auto axes."""
    import jax

    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def _mesh_from_devices(devices, axes: tuple[str, ...]):
    import jax

    return jax.sharding.Mesh(
        devices, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips. Multi-pod: (pod=2, data=16,
    model=16) = 512 chips; the pod axis is pure data parallelism over DCN."""
    import jax

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"mesh needs {n} devices, found {len(devices)} — run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=512 for the dry-run"
        )
    import numpy as np

    return _mesh_from_devices(np.asarray(devices).reshape(shape), axes)


def make_local_mesh(axes: tuple[str, ...] = ("data",), shape: tuple[int, ...] | None = None):
    """Development mesh over whatever devices exist (tests, examples)."""
    import jax
    import numpy as np

    n = len(jax.devices())
    shape = shape or (n,)
    devices = np.asarray(jax.devices()[: int(np.prod(shape))]).reshape(shape)
    return _mesh_from_devices(devices, axes)
