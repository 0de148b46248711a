"""Where JAX's persistent compilation cache lives.

A cold 209M train + paged-serve boot compiles a train step, several
prefill buckets and the power-of-two decode windows; without a
persistent cache every process start pays all of it again. The cache's
directory is part of its key, so it must not move between runs: it is
either where the operator put it (``JAX_COMPILATION_CACHE_DIR``, which
JAX reads itself; the chart sets it to the pod's state volume, so a
rescheduled pod starts warm) or one fixed path inside the checkout —
never a temporary directory, a pid or a timestamp.
"""

from __future__ import annotations

import os
import pathlib

_CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX at the persistent compile cache; return its directory.

    Call before the first compile of the process (JAX binds the cache
    on first use). With ``JAX_COMPILATION_CACHE_DIR`` set this touches
    nothing — JAX already reads the variable, and no other directory is
    ever set in code. Idempotent.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(_CHECKOUT_CACHE))
    return str(_CHECKOUT_CACHE)
