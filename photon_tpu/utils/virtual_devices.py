"""Force an n-device virtual CPU backend for mesh tests and dryruns.

The reference exercises distributed code without a cluster via
``SparkTestUtils.sparkTest`` (local[*] SparkSession per test,
photon-test-utils SparkTestUtils.scala:43-76). The JAX analogue is a
virtual multi-device CPU backend: ``--xla_force_host_platform_device_count``
plus pinning the platform to cpu. This helper is the single copy of that
dance, shared by ``tests/conftest.py`` and ``__graft_entry__.dryrun_multichip``.
"""

from __future__ import annotations

import os
import re

_FLAG = "--xla_force_host_platform_device_count"


def force_virtual_cpu_devices(n_devices: int) -> None:
    """Pin JAX to a CPU backend with ``n_devices`` virtual devices.

    Must run before any JAX backend is initialized (i.e. before the first
    ``jax.devices()`` / jitted execution in the process). Replaces any
    existing device-count flag so the requested count always wins.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if _FLAG in flags:
        flags = re.sub(rf"{_FLAG}=\d+", f"{_FLAG}={n_devices}", flags)
    else:
        flags = f"{flags} {_FLAG}={n_devices}"
    os.environ["XLA_FLAGS"] = flags.strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.config.update("jax_platforms", "cpu")

    n_found = len(jax.devices())
    if n_found < n_devices:
        raise RuntimeError(
            f"requested {n_devices} virtual CPU devices but the backend has "
            f"{n_found} — a JAX backend was initialized before "
            "force_virtual_cpu_devices() ran (XLA reads the device-count "
            "flag only at backend creation). Call it first in the process."
        )
