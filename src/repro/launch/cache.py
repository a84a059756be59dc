"""Persistent compilation cache for the entry points.

Each entry point (``launch/train.py``, ``launch/serve.py``,
``examples/train_wan_adaptiveload.py``, ``chip_smoke.py``) calls
:func:`enable_compilation_cache` once, before it compiles anything.
Library modules and tests never turn the cache on.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
sets nothing.  Otherwise the cache lives at :data:`DEFAULT_CACHE_DIR`, a
fixed path inside the checkout, so the next run finds what this one
compiled; a path built from a temporary name, a process id or the time
would never be found again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache`` (listed in ``.gitignore``)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory in use."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
