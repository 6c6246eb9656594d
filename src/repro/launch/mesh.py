"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — critical for the dry-run, which must set
``XLA_FLAGS`` before the first jax device query.

Every mesh is built with ``AxisType.Auto`` axes: the model code annotates
activations with ``with_sharding_constraint`` (``distributed.constrain``),
which ``jax.make_mesh``'s default ``Explicit`` axes refuse."""
from __future__ import annotations


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto`` (see module docstring)."""
    import jax
    from jax.sharding import AxisType
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e: one pod = 16x16 = 256 chips, mesh (data=16, model=16);
    multi-pod = 2 pods = 512 chips, mesh (pod=2, data=16, model=16)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Whatever this host actually has (tests / local serving)."""
    import jax
    n = len(jax.devices())
    assert n % model_parallel == 0
    return make_mesh((n // model_parallel, model_parallel), ("data", "model"))
