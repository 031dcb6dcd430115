"""Where XLA's persistent compile cache lives — the one place that decides.

The device plane compiles one program per (batch, bucket) geometry, per
link geometry and per party set, and a run on the chip starts from a
fresh machine: without a cache every run compiles everything cold.

The rule: if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself —
that directory and no other is used and nothing here overrides it. If it
is not, the cache is ``DEFAULT_DIR``, a fixed git-ignored path inside the
checkout; never a temp name, a pid or a time, because a cache that moves
never hits. The choice is exported to the environment so every child
process inherits it.

Only programs that own a device call :func:`configure` (``chip_smoke.py``,
``benchmark/run.py``). Importing the package never does, so the tier-1
tests on the CPU leave the checkout's cache empty.
"""

from __future__ import annotations

import os
import sys

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)
# JAX's default threshold (1.0 s) would skip exactly what this fabric
# compiles most of: sub-second per-geometry echo and link steps.
MIN_COMPILE_TIME_SECS = 0.0
_ENV_MIN_SECS = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"


def configure() -> str:
    """Turn the persistent cache on for this process and every child it
    starts; call before the first compile. Never imports JAX: a launcher
    that must stay off it exports the choice through the environment,
    which JAX reads when it is imported; a process that already imported
    it gets the same values through ``jax.config``. Returns the
    directory in use."""
    jax = sys.modules.get("jax")
    path = os.environ.get(ENV_DIR)
    if not path:
        path = os.environ[ENV_DIR] = DEFAULT_DIR
        if jax is not None:
            jax.config.update("jax_compilation_cache_dir", path)
    os.environ[_ENV_MIN_SECS] = str(MIN_COMPILE_TIME_SECS)
    if jax is not None:
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs",
            MIN_COMPILE_TIME_SECS,
        )
    return path
