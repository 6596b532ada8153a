"""Process set-up shared by the entry points (serve, train, the benchmarks
and ``chip_smoke.py``): where JAX keeps its persistent compilation cache,
and which device a run is on.

The cache directory is ``$JAX_COMPILATION_CACHE_DIR`` when that is set — JAX
reads the variable itself, so nothing else is configured — and otherwise a
fixed directory inside the checkout (``<repo>/.jax_cache``, git-ignored):
the directory is part of each entry's key, so a path that moves never hits.
The test suite deliberately does not enable it: described-topology compiles
write entries that a process without the chip cannot read back.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.
    Call before the first compilation."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_info() -> dict:
    """The device as JAX reports it: platform, device_kind and count."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def device_label() -> str:
    d = device_info()
    return f"platform={d['platform']} kind={d['kind']} count={d['count']}"
