"""Persistent XLA compilation cache for the repo's entry points.

Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``scripts/recalibrate.py``) call `use_compile_cache` once at start-up;
importing this module changes nothing.  A run that finds the cache warm
skips the XLA compiles an earlier run of the same programs already paid.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["use_compile_cache"]

#: The checkout root (``src/repro/launch/`` is three levels below it).
_ROOT = pathlib.Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as JAX itself
    reads it and no other directory is set.  Otherwise the cache lives in
    ``.jax_cache/`` at the checkout root: a fixed path, because the
    path is part of what a later run must find again.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
