"""Where this process runs: the device JAX found, and the one persistent
compile cache.

The cache directory is decided here and nowhere else.  When
``JAX_COMPILATION_CACHE_DIR`` is set, that directory is the cache: nothing
in the program points JAX at another one and the variable is never
rewritten, so whoever launches the program places the cache (a chip
machine that keeps one between calls, a CI volume).  When it is unset the
entry points arm :data:`DEFAULT_CACHE_DIR`, a fixed gitignored directory
inside the checkout — fixed because a path that moves between runs never
hits.
"""

from __future__ import annotations

import logging
import os
from typing import Dict, Optional

logger = logging.getLogger("mx_rcnn_tpu")

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def compile_cache_dir(unset_default: Optional[str] = None) -> str:
    """The directory the persistent compile cache lives in:
    ``$JAX_COMPILATION_CACHE_DIR`` if set, else ``unset_default`` (an
    export store's bundled ``xla_cache/``), else :data:`DEFAULT_CACHE_DIR`."""
    return os.environ.get(CACHE_ENV) or unset_default or DEFAULT_CACHE_DIR


def enable_compile_cache(unset_default: Optional[str] = None,
                         min_compile_s: float = 0.0) -> str:
    """Arm JAX's persistent compilation cache at :func:`compile_cache_dir`
    in the live config; returns the directory.  Call before the first
    compile — JAX binds the directory on first use."""
    import jax

    cache_dir = compile_cache_dir(unset_default)
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_s)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    logger.info("persistent XLA compilation cache: %s", cache_dir)
    return cache_dir


def device_summary() -> Dict:
    """The device as JAX reports it — every result and log that carries a
    device number names it with these three fields."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


def log_runtime(cache_dir: str) -> Dict:
    """One start-up line saying where this run executes and where its
    compiled programs are cached; returns :func:`device_summary`."""
    dev = device_summary()
    logger.info("running on platform=%s device_kind=%s device_count=%d "
                "compile_cache=%s", dev["platform"], dev["device_kind"],
                dev["device_count"], cache_dir)
    return dev
