"""Production mesh construction. Must be a FUNCTION so importing this module
never touches jax device state (the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax init).

Every mesh has ``Auto`` axes: the model code places data with bare
``PartitionSpec``s under ``jax.set_mesh`` and lets the compiler propagate
shardings through gathers and scatters, which ``Explicit`` axes (the
``jax.make_mesh`` default since jax 0.7) refuse.
"""
from __future__ import annotations


def _auto_mesh(shape, axes):
    import jax
    from jax.sharding import AxisType
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod (TPU v5e); 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(data: int, model: int, pods: int = 1):
    """Arbitrary mesh for tests / small runs."""
    if pods > 1:
        return _auto_mesh((pods, data, model), ("pod", "data", "model"))
    return _auto_mesh((data, model), ("data", "model"))
