"""JAX's persistent compilation cache for the command-line entry points.

``enable()`` is called by ``chip_smoke.py``, ``repro.launch.train``,
``repro.launch.serve`` and ``benchmarks/run.py`` before their first
compile; importing ``repro`` never calls it, so library users keep
whatever cache they configured. Where ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX reads it itself and this sets nothing. Otherwise the cache goes
to ``.jax_cache/`` at the checkout root: a fixed path, because the
directory is part of what a later process must find again.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
