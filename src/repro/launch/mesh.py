"""Production mesh construction + virtual-device bring-up.

Mesh builders are FUNCTIONS (not module-level constants) so importing
this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
init; everything else sees the real device count.

:func:`ensure_host_devices` is the in-process knob the sharded mining
launcher (``repro.launch.mine``) uses: it appends
``--xla_force_host_platform_device_count=N`` to ``XLA_FLAGS`` *before*
the first backend initialization, so a single-CPU container presents N
virtual devices to :mod:`repro.core.shard` without a subprocess.
"""
from __future__ import annotations

import os

import numpy as np
import jax
from jax.sharding import AxisType

__all__ = [
    "make_production_mesh",
    "make_local_mesh",
    "make_shard_mesh",
    "ensure_host_devices",
]


def ensure_host_devices(n: int) -> int:
    """Make ``n`` devices visible and return the count actually visible.

    On the CPU platform the devices are virtual: the
    ``--xla_force_host_platform_device_count`` flag is appended to
    ``XLA_FLAGS``, which takes effect only before jax's backend
    initializes (the flag is read once, at CPU client creation) — a CPU
    caller that gets fewer back is running after init and may degrade
    to the visible set.  The flag is skipped when ``JAX_PLATFORMS``
    names another platform first; with ``JAX_PLATFORMS`` unset it is
    added, and is inert on an accelerator (it sizes the host platform
    only, and the platform JAX picks is not known before its backend
    initializes).  On an accelerator the chips are what they are: fewer
    than ``n`` raises instead of time-sharing partitions on fewer
    chips."""
    platforms = (jax.config.jax_platforms or "cpu").split(",")
    cur = os.environ.get("XLA_FLAGS", "")
    if platforms[0] == "cpu" and "--xla_force_host_platform_device_count" not in cur:
        flag = f"--xla_force_host_platform_device_count={int(n)}"
        os.environ["XLA_FLAGS"] = f"{cur} {flag}".strip()
    devs = jax.devices()
    if devs[0].platform != "cpu" and len(devs) < n:
        raise RuntimeError(
            f"{n} devices requested, {len(devs)} {devs[0].platform} "
            "device(s) present"
        )
    return len(devs)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests)."""
    return jax.make_mesh(
        (data, model), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )


def make_shard_mesh(devices):
    """1-D ``("shard",)`` mesh over an explicit mining-device list.

    Used by the sharded executor's device-collective gather
    (:func:`repro.core.shard.collective_gather`): one shard's placed
    output rows live on each mesh device and the cross-shard reduction
    lowers to a collective over this axis.  Takes the devices explicitly
    (not ``jax.devices()``) so a mine over a device subset — or a forced
    single device — reduces over exactly the devices it dispatched to.
    """
    dev_arr = np.empty(len(devices), dtype=object)
    for i, d in enumerate(devices):
        dev_arr[i] = d
    return jax.sharding.Mesh(dev_arr, ("shard",))
