"""JAX platform selection and the program's one compile cache.

Both must happen before the first backend use in a process: the platform
and the virtual host-device count are read once at backend initialization,
and the cache directory is read at the first compile.
"""

from __future__ import annotations

import os
import re
from typing import Optional

_COUNT_RE = r"--xla_force_host_platform_device_count=(\d+)"

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: The cache's home when the environment names none: one fixed directory in
#: the checkout (git-ignored), so every process of a run — scheduler-launched
#: tasks, replicas, the benchmark, tests — hits what any other compiled; a
#: per-run or per-pid directory would never hit.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def force_platform(platform: str,
                   min_host_devices: Optional[int] = None) -> None:
    """Select the JAX platform for this process and its children.

    Exports ``JAX_PLATFORMS`` (children inherit it) and sets the config a
    live ``jax`` import already read it into.  ``min_host_devices`` raises
    the virtual host-device count in ``XLA_FLAGS`` if it is absent or
    smaller.  Takes effect only before the first backend use; callers that
    must be sure check ``jax.devices()`` afterwards.
    """
    if min_host_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        m = re.search(_COUNT_RE, flags)
        if not m or int(m.group(1)) < min_host_devices:
            flags = re.sub(r"\s*" + _COUNT_RE, "", flags)
            os.environ["XLA_FLAGS"] = (
                flags
                + f" --xla_force_host_platform_device_count={min_host_devices}"
            ).strip()
    os.environ["JAX_PLATFORMS"] = platform
    import jax
    jax.config.update("jax_platforms", platform)


def enable_compile_cache() -> Optional[str]:
    """Turn on JAX's persistent compilation cache, placed from outside.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and no
    directory is set in code (returns ``None``); otherwise the cache lives
    in :data:`DEFAULT_CACHE_DIR` (returned).  Every program is kept, however
    quick its compile: a process of this system compiles hundreds of small
    ones at start-up, and a step that compiles in under JAX's default
    one-second threshold is still a step the next process should not pay.
    Called by ``runtime.initialize``, the fleet replica and the tests.
    """
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if os.environ.get(CACHE_ENV):
        return None
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
