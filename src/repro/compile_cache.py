"""Where JAX's persistent compilation cache lives.

Entry points (``chip_smoke.py``, ``python -m repro.net.serve``,
``examples/fused_cnn_inference.py``, ``benchmarks/run.py``) call
:func:`use_compile_cache` once at start-up; library code never does.  A set
``JAX_COMPILATION_CACHE_DIR`` wins and nothing else is set; otherwise the
cache goes to a fixed directory inside the checkout (git-ignored), so every
run of the same checkout finds what earlier runs compiled.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at
    ``$JAX_COMPILATION_CACHE_DIR`` when set, else at
    :data:`CHECKOUT_CACHE_DIR`; returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        CHECKOUT_CACHE_DIR
    )
    jax.config.update("jax_compilation_cache_dir", path)
    return path
