"""Process-level JAX setup shared by the entry points.

* Virtual CPU mesh provisioning.  The reference tests its distributed
  paths by launching the same binary at np=1,2,4,8 on one node
  (SURVEY.md section 4); the TPU build's analog is XLA's host-platform
  device simulation.  An n-device virtual CPU mesh needs
  ``--xla_force_host_platform_device_count=n`` in ``XLA_FLAGS`` and the
  ``cpu`` platform selected, both BEFORE the first JAX backend query:
  the flags and the platform list are read once, at backend creation.
  Tests (``tests/conftest.py``) and the ``dryrun_multichip`` child
  (``__graft_entry__``) share this.
* The persistent compilation cache (:func:`enable_compile_cache`).
"""

from __future__ import annotations

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"

# the fixed in-repo cache directory (listed in .gitignore); a fixed path
# matters because the path is part of the cache key
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def host_device_count_flags(flags: str, n_devices: int) -> str:
    """Return ``flags`` amended to request >= ``n_devices`` host devices.

    Pure function: callers decide where the result goes (``os.environ`` of
    this process, or a child-process environment).
    """
    m = re.search(_COUNT_FLAG + r"=(\d+)", flags)
    if m is None:
        return (flags + f" {_COUNT_FLAG}={n_devices}").strip()
    if int(m.group(1)) < n_devices:
        return flags.replace(m.group(0), f"{_COUNT_FLAG}={n_devices}")
    return flags


def provision_host_mesh(n_devices: int):
    """Force the CPU platform with >= ``n_devices`` virtual devices.

    Returns the ``jax`` module.  Must run before the first backend query;
    afterwards the settings are frozen and this becomes a no-op (callers
    should check ``len(jax.devices())``).  The platform is set through
    ``jax.config`` as well as the environment because ``jax`` may already
    have been imported (and read ``JAX_PLATFORMS``) by the caller.
    """
    os.environ["XLA_FLAGS"] = host_device_count_flags(
        os.environ.get("XLA_FLAGS", ""), n_devices)
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
    return jax


def enable_compile_cache() -> str | None:
    """Turn on JAX's persistent compilation cache; returns its directory.

    The cache saves the cold-start compiles of a new process (a 512^3
    solve program takes tens of seconds to compile).  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, that directory is used and no
    other path is set in code; otherwise the fixed ``<repo>/.jax_cache``.
    Semantics-neutral, on by default for the CLI, the service and bench;
    ``ACG_TPU_COMPILE_CACHE=0`` turns it off (returns None).
    """
    if os.environ.get("ACG_TPU_COMPILE_CACHE", "1") == "0":
        return None
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return path
