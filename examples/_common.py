"""Shared bring-up for the example session scripts."""

import os
import sys

# runnable from anywhere without installing the package
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def setup():
    """Pick the backend BEFORE the first jax touch.  ``TMPI_FORCE_CPU=1``:
    the simulated 8-device CPU mesh.  Otherwise whatever JAX finds — and a
    backend that fails to start is a failure of the example, not a reason
    to report success from another one."""
    if os.environ.get("TMPI_FORCE_CPU"):
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        import jax
        jax.config.update("jax_platforms", "cpu")


def n_devices(default=None):
    import jax
    return default or len(jax.devices())
