"""Production mesh construction.

Defined as FUNCTIONS (not module constants) so importing this module
never touches jax device state — the dry-run must set XLA_FLAGS before
the first device query, and tests must see 1 CPU device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.sharding.rules import MeshCfg

SINGLE_POD = (16, 16)                 # 256 chips: (data, model)
MULTI_POD = (2, 16, 16)               # 2 pods × 256 chips

#: Debug/test reduction meshes over 8 fake CPU devices: the flat
#: single-level shape and the (pod, data) two-level shape whose
#: reduction tree drives the hierarchical transport schedule.
FAKE_FLAT = (1, 8)
FAKE_2D = (2, 4)


def make_fake_mesh(shape=FAKE_2D, axes: tuple[str, ...] | None = None):
    """A (pod, data) mesh over fake CPU devices for the tests.

    ``shape`` is ``(pod, data)`` (append a trailing model axis by
    passing 3 entries + explicit ``axes``).  The caller's process must
    run under ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    with ``N >= prod(shape)``, as the multidevice checks do.
    """
    if axes is None:
        axes = ("pod", "data") if len(shape) == 2 else \
            ("pod", "data", "model")[:len(shape)]
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for fake mesh {shape}, have "
            f"{len(jax.devices())} — set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} before "
            "any jax import")
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    shape = MULTI_POD if multi_pod else SINGLE_POD
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(jax.devices())} "
            "— the dry-run must set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "any jax import")
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(shape))


def mesh_cfg(*, multi_pod: bool = False) -> MeshCfg:
    if multi_pod:
        return MeshCfg(("pod", "data", "model"), MULTI_POD)
    return MeshCfg(("data", "model"), SINGLE_POD)
