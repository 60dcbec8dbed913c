"""Virtual CPU devices for the mesh-sharded benchmarks (imports no JAX).

XLA reads ``--xla_force_host_platform_device_count`` once, when JAX
starts its CPU backend, so the flag is decided here, before the caller
imports JAX — never by re-executing a process that already holds a
backend (on a TPU host that process would hold the chip).
"""
from __future__ import annotations

import os
import sys


def force_host_devices(n: int = 2) -> None:
    """Give the CPU backend ``n`` devices when ``JAX_PLATFORMS=cpu``.

    On any other platform the mesh is made of the real devices and the
    flag is left alone.  Raises if JAX is already imported.
    """
    if "jax" in sys.modules:
        raise RuntimeError("force_host_devices must run before jax is "
                           "imported")
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}").strip()
