"""Platform selection helpers.

JAX takes the accelerator it finds unless told otherwise; the program
never falls back to the CPU by itself. ``force_cpu_platform()`` is "use
the CPU because I was asked to" (tests, the multi-process CPU children
and the CPU-only tools call it before their first jax use).
"""

from __future__ import annotations


def force_cpu_platform() -> None:
    import jax

    jax.config.update("jax_platforms", "cpu")


def enable_cpu_multiprocess_collectives() -> bool:
    """Select the gloo CPU collectives backend on a CPU platform.

    Without an explicit CPU collectives implementation, a multi-process
    CPU mesh fails every cross-process program with "Multiprocess
    computations aren't implemented on the CPU backend" — jax does not
    pick gloo by itself.  Must run BEFORE the backend initializes (the
    multi-process entry point calls it ahead of
    ``jax.distributed.initialize``); only applies when the platform is
    (or is forced to) CPU, so TPU meshes are untouched.  Returns
    whether it applied."""
    import os

    import jax

    platforms = getattr(jax.config, "jax_platforms", None) \
        or os.environ.get("JAX_PLATFORMS", "")
    if "cpu" not in str(platforms):
        return False
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    return True
