"""Process-level XLA environment knobs that must be set *before* jax is
imported (device topology and config defaults are fixed at first
import).  jax-free on purpose: both ``repro.launch.dryrun`` (under
``__main__``) and the ``python -m repro`` CLI call these before touching
jax."""
from __future__ import annotations

import os
import sys
import warnings

DRYRUN_DEVICE_COUNT = 512   # the multi-pod dry-run's forced host devices
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIRNAME = ".jax_cache"   # under the checkout root; git-ignored


def use_compile_cache() -> "str | None":
    """Point JAX's persistent compilation cache at a fixed directory.

    A ``JAX_COMPILATION_CACHE_DIR`` already in the environment wins and
    is left alone.  Otherwise, when this package runs from a source
    checkout (``<root>/src/repro``), the variable is set to
    ``<root>/.jax_cache``: a path that never moves, because the path is
    part of what a cache hit matches.  Setting the environment, not a
    jax config call, is what lets ``proc`` children and ``repro join``
    groups inherit the same cache.  Returns the directory in effect, or
    None (installed package, no variable set: no cache)."""
    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    if not os.path.isdir(os.path.join(root, "src", "repro")):
        return None
    os.environ[CACHE_ENV] = os.path.join(root, CACHE_DIRNAME)
    return os.environ[CACHE_ENV]


def force_host_device_count(n: int = DRYRUN_DEVICE_COUNT) -> bool:
    """Force ``n`` XLA host devices for this process.

    No-ops (with a warning) when jax is already imported — too late to
    change the topology.  An existing XLA_FLAGS is preserved: the force
    flag is appended to it, unless the user already forced a device
    count themselves (their explicit override wins).  Returns True when
    the requested count is in effect.
    """
    flag = f"--xla_force_host_platform_device_count={n}"
    if "jax" in sys.modules:
        in_effect = flag in os.environ.get("XLA_FLAGS", "")
        if not in_effect:
            warnings.warn(
                f"jax is already imported; cannot force {n} host devices "
                f"(set XLA_FLAGS={flag} before starting python)",
                RuntimeWarning, stacklevel=2)
        return in_effect
    current = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in current:
        os.environ["XLA_FLAGS"] = f"{current} {flag}".strip()
    in_effect = flag in os.environ["XLA_FLAGS"]
    if not in_effect:
        warnings.warn(
            f"XLA_FLAGS already forces a different host device count "
            f"({current!r}); leaving it in place instead of forcing {n}",
            RuntimeWarning, stacklevel=2)
    return in_effect
