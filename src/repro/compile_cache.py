"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`use_compile_cache` once, before their first jitted call; importing
:mod:`repro` never touches the setting.  ``JAX_COMPILATION_CACHE_DIR``,
when set, wins and JAX reads it itself.  Otherwise the cache lives at one
fixed path inside the checkout, ``<root>/.jax_cache`` (ignored by git),
so the next run finds what this one compiled; a temporary or per-process
directory would never be read again.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Union

import jax

CACHE_DIRNAME = ".jax_cache"


def use_compile_cache(root: Union[str, Path]) -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(root).resolve() / CACHE_DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
