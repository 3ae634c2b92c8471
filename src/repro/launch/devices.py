"""Process bootstrap shared by every entry point.

* ``force_host_device_count`` fakes a multi-device CPU host
  (tests/conftest.py, launch/train.py, launch/dryrun.py,
  benchmarks/run.py).  It must run before the first jax backend
  initialization to have any effect.
* ``enable_compile_cache`` points JAX's persistent compilation cache at
  one fixed directory (launch/train.py, launch/serve.py,
  launch/dryrun.py, chip_smoke.py).

Import is jax-free.
"""
from __future__ import annotations

import os

from repro.perf.paths import from_root

_FLAG = "xla_force_host_platform_device_count"
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def force_host_device_count(n: int, override: bool = False) -> None:
    """Append ``--xla_force_host_platform_device_count=<n>`` to XLA_FLAGS.

    ``override=False`` respects a count already present in the
    environment (e.g. CI's global setting); ``override=True`` appends
    regardless — XLA honors the last occurrence of the flag, so the
    appended value wins.  No-op on real accelerators.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if _FLAG in flags and not override:
        return
    os.environ["XLA_FLAGS"] = (flags + f" --{_FLAG}={n}").strip()


def compile_cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``.

    The path is part of each cache entry's key, so it is fixed: a temp-,
    pid- or time-derived directory would never hit.
    """
    return os.environ.get(CACHE_ENV) or from_root(".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it, and no
    other directory is set here.
    """
    path = compile_cache_dir()
    if not os.environ.get(CACHE_ENV):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path
