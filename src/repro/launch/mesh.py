"""Production mesh construction (a FUNCTION — importing this module never
touches jax device state)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """`jax.make_mesh` with every axis `Auto`.

    The solver's shard_map programs and the LM's sharding constraints
    name mesh axes in PartitionSpecs and let the compiler place the
    rest, which needs Auto axes; `jax.make_mesh` defaults to Explicit.
    """
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds a leading 2-pod axis.

    Requires 512 placeholder devices for the dry-run
    (``XLA_FLAGS=--xla_force_host_platform_device_count=512`` — set by
    launch/dryrun.py only); single-pod uses the first 256.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)} — run "
            f"under launch/dryrun.py (it forces 512 host devices) or on "
            f"real hardware")
    return make_mesh(shape, axes, devices=devices[:n])


def make_host_mesh(data: int = 1, model: int = 1):
    """A (data, model) mesh over the first data * model devices.

    Raises when fewer devices exist: a mesh that quietly shrinks would
    run a different program than the one asked for."""
    n = len(jax.devices())
    if data < 1 or model < 1 or data * model > n:
        raise ValueError(
            f"mesh ({data}, {model}) needs {data * model} devices, "
            f"{n} {jax.default_backend()} device(s) are visible")
    return make_mesh((data, model), ("data", "model"),
                     devices=jax.devices()[:data * model])
