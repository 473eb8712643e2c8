"""Forced-host CPU mesh provisioning, in one place.

Used by ``tests/conftest.py`` and by ``__graft_entry__.dryrun_multichip``'s
subprocess child. The mesh must exist before the first backend use: XLA
reads ``--xla_force_host_platform_device_count`` once, when it creates
the CPU client, so the flag lands in ``XLA_FLAGS`` (replacing a smaller
pre-set count, which would leave the mesh short) and the platform is
pinned through ``jax.config`` in-process, before anything touches a
device.

This is the "fake backend" harness the reference never had (SURVEY.md §4):
real ``all_to_all`` semantics on any machine, standing in for an ICI mesh.
"""

import os
import re


def forced_flags(flags: str, n_devices: int) -> str:
    """``XLA_FLAGS`` with the forced-host-device count set to exactly
    ``n_devices`` (replacing any existing count)."""
    pat = r"--xla_force_host_platform_device_count=\d+"
    new = f"--xla_force_host_platform_device_count={n_devices}"
    if re.search(pat, flags):
        return re.sub(pat, new, flags)
    return (flags + " " + new).strip()


def force_cpu_devices(n_devices: int) -> bool:
    """Force this process onto an ``n_devices`` CPU mesh.

    Must run before the first jax backend initialization. Mutates
    ``os.environ['XLA_FLAGS']`` and pins ``jax_platforms`` — callers own
    the process (test session / dedicated subprocess). Returns True when
    jax now reports at least ``n_devices`` devices.
    """
    os.environ["XLA_FLAGS"] = forced_flags(
        os.environ.get("XLA_FLAGS", ""), n_devices
    )
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
        return len(jax.devices()) >= n_devices
    except Exception:
        return False
