"""Process-level JAX set-up shared by the entry points.

``enable_compile_cache`` puts JAX's persistent compilation cache at one
fixed place; a program's ``main`` calls it, a library module or a test
never does.  ``device_info`` and ``measured_mode`` name the device a
measurement ran on, so no number is filed under the wrong platform.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Dict

import jax

REPO_ROOT = Path(__file__).resolve().parents[2]
DEFAULT_COMPILE_CACHE = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it by itself and
    nothing is set here.  Otherwise the cache sits at ``<repo>/.jax_cache``,
    a fixed path, so a later run finds what an earlier one compiled.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_COMPILE_CACHE))
    return str(DEFAULT_COMPILE_CACHE)


def device_info() -> Dict[str, object]:
    """The devices JAX runs on: platform, device kind and count."""
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "count": len(devices)}


def measured_mode() -> str:
    """Provenance label of a measured number, e.g. ``measured-tpu``."""
    return f"measured-{jax.devices()[0].platform}"
