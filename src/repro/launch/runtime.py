"""Process set-up shared by the entry points that can run on the chip.

Two rules every entry point (``chip_smoke.py``, ``benchmarks.run``,
``python -m repro.serve``, ``repro.launch.train``) follows:

* **The compile cache lives at a fixed place.** :func:`enable_compile_cache`
  leaves JAX's persistent compilation cache where
  ``JAX_COMPILATION_CACHE_DIR`` puts it when that is set, and otherwise
  points it at ``.jax_cache/`` in the checkout. The path is part of the
  cache's key, so it never depends on a temporary name, a process id or
  the time.
* **Host devices are forced only on the CPU.** :func:`force_host_devices`
  splits the CPU into several devices for the multi-device code paths,
  and only under ``JAX_PLATFORMS=cpu``: on a chip host the device count
  is what the host has.
"""

from __future__ import annotations

import os
from pathlib import Path

# src/repro/launch/runtime.py -> the checkout root.
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def cpu_only() -> bool:
    """Whether this process is held to the CPU (``JAX_PLATFORMS=cpu``)."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def force_host_devices(count: int) -> None:
    """Ask XLA for ``count`` CPU devices, on a CPU run only.

    Has an effect only before JAX starts its backend, and never overrides a
    device count already in ``XLA_FLAGS``.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if not cpu_only() or "host_platform_device_count" in flags:
        return
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={int(count)}".strip()
    )


def default_shards(cpu_count: int) -> int:
    """The shard count a CLI uses when none is given: ``cpu_count`` forced
    host devices on a CPU run, otherwise every device the host has."""
    if cpu_only():
        return int(cpu_count)
    import jax

    return len(jax.devices())
