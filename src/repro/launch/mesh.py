"""Production meshes (TPU v5e).

Functions, not module-level constants: importing this module never touches
jax device state. The dry-run entry point sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 *before* any jax import.
"""
from __future__ import annotations

import math

import jax


def _make_mesh(shape, axes, devices) -> jax.sharding.Mesh:
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def _take_devices(n: int, what: str) -> list:
    """The first n devices, or an error naming the mesh that cannot be built
    (a smaller mesh would quietly run a multi-chip job on fewer chips)."""
    if n > jax.device_count():
        raise RuntimeError(
            f"{what} needs {n} devices, found {jax.device_count()} "
            f"({jax.devices()[0].platform})")
    return jax.devices()[:n]


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 = 256 chips per pod; multi_pod stacks 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    devices = _take_devices(
        math.prod(shape), f"mesh {shape} (run via launch/dryrun.py, which sets "
        "xla_force_host_platform_device_count)")
    return _make_mesh(shape, axes, devices)


def make_debug_mesh(data: int = 1, model: int = 1, pod: int | None = None):
    """Tiny mesh over real devices (tests); raises RuntimeError when the
    requested shape exceeds `jax.device_count()`."""
    shape = (pod, data, model) if pod else (data, model)
    axes = ("pod", "data", "model") if pod else ("data", "model")
    devices = _take_devices(math.prod(shape), f"debug mesh {dict(zip(axes, shape))}")
    return _make_mesh(shape, axes, devices)


def make_federation_mesh(clusters: int = 1, clients: int | None = None):
    """The population mesh for device-sharded FL runs: axes
    ``("clusters", "clients")`` (see `repro.sharding.fed`).

    `clients=None` spreads all remaining devices across the client axis.
    Publish it to the drivers either explicitly (``config.mesh``) or
    ambiently via `sharding.ctx.model_mesh`::

        with model_mesh(make_federation_mesh(clusters=2, clients=4)):
            run_fed_chs(task, config)   # sharded; mesh=None configs adopt it

    Raises RuntimeError when the requested shape exceeds
    `jax.device_count()`."""
    if clients is None:
        clients = max(jax.device_count() // clusters, 1)
    devices = _take_devices(
        clusters * clients, f"federation mesh (clusters={clusters}, clients={clients})")
    return _make_mesh((clusters, clients), ("clusters", "clients"), devices)


POD_CHIPS = 256
MULTI_POD_CHIPS = 512
