"""Process-level device setup shared by the entry points.

Call these from a program's ``__main__`` path, never at import: tests and
worker processes import the launchers.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: fixed cache path in the checkout: the path is part of the cache key, so
#: a directory that moved between runs would never hit
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Keep compiled programs across processes; returns the cache directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, already names the cache to JAX
    and nothing is set here. Otherwise the cache goes to ``.jax_cache`` at
    the root of the checkout.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)


def require_tpu() -> list:
    """The local devices, or SystemExit when JAX found no TPU."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"no TPU found: JAX sees {len(devices)} {devices[0].platform} device(s)"
        )
    return devices
