"""Production mesh construction.

Functions, not module-level constants: importing this module never touches
jax device state (device count is locked at first jax init, so the dry-run
must set XLA_FLAGS before anything here runs)."""

from __future__ import annotations

import jax


def make_auto_mesh(shape, axes, devices=None):
    """jax.make_mesh with every axis of type Auto (sharding propagated by
    the compiler); `devices` defaults to `jax.devices()`."""
    return jax.make_mesh(shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 (2 pods, 512 chips).

    Axes: "pod" = inter-pod data parallelism (slower links), "data" =
    in-pod data/FSDP axis, "model" = tensor/expert/storage axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Whatever this host actually has (tests/examples); model-axis last."""
    n = len(jax.devices())
    assert n % model == 0, (n, model)
    return make_auto_mesh((n // model, model), ("data", "model"))
