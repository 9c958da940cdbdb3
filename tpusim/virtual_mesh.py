"""Virtual-mesh bootstrap — importable without the rest of the package,
so a caller can ask for its devices before anything starts a backend.
Lives directly under tpusim, whose __init__ stays import-light by design."""

from __future__ import annotations

import os
import re


def virtual_cpu_devices(n_devices: int) -> None:
    """Make the CPU platform come up with at least `n_devices` virtual
    devices. Acts only where the caller asked for the CPU
    (JAX_PLATFORMS=cpu) and only before the backend starts; on a host
    whose platform is an accelerator this does nothing — library code
    never moves such a host onto a virtual CPU mesh, and a mesh wider
    than the chips it has is an error the Simulator reports."""
    if n_devices <= 1 or os.environ.get("JAX_PLATFORMS", "") != "cpu":
        return
    flags = os.environ.get("XLA_FLAGS", "")
    have = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if have and int(have.group(1)) >= n_devices:
        return
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "", flags)
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n_devices}"
    ).strip()
