"""On-disk caches of the entry points, kept inside the checkout.

JAX's persistent compilation cache keys entries by, among other things,
the cache directory, so a directory that moves between runs never hits.
Entry points (``chip_smoke.py``, ``launch/serve.py``, ``launch/train.py``)
call :func:`enable_compile_cache` first thing in ``main``: it honours
``$JAX_COMPILATION_CACHE_DIR`` (which JAX reads by itself) and otherwise
points the cache at one fixed, git-ignored directory of the checkout.
Library modules never set it on import.
"""

from __future__ import annotations

import os
from collections.abc import Mapping
from pathlib import Path

import jax

# <repo>/.cache — git-ignored; src/repro/launch/cache.py is three levels down
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".cache"
_ENV = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_dir(env: Mapping[str, str] = os.environ) -> Path:
    """``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.cache/jax``."""
    return Path(env[_ENV]) if env.get(_ENV) else CHECKOUT_CACHE / "jax"


def enable_compile_cache() -> Path:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    path = compile_cache_dir()
    if not os.environ.get(_ENV):
        jax.config.update("jax_compilation_cache_dir", str(path))
    return path


def pin_repro_caches() -> None:
    """Point the matmul-tile and serve-plan caches inside the checkout, so
    tiles and plans never come from a stale ``~/.cache`` of another tree."""
    os.environ["REPRO_TILE_CACHE"] = str(CHECKOUT_CACHE / "matmul_tiles.json")
    os.environ["REPRO_SERVE_PLAN_CACHE"] = str(
        CHECKOUT_CACHE / "serve_plans.json"
    )
