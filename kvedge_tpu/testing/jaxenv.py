"""Force JAX onto a virtual n-device CPU mesh (test/demo environments).

Multi-chip TPU hardware is not available in CI; sharding behavior is
exercised on virtual CPU devices instead. The ordering here is
load-bearing: the environment variables cover a jax that is imported
later (and child processes), ``jax.config`` covers one that already is
— both before any backend is initialized. Used by ``tests/conftest.py``
and ``tools/demo_cluster.py``.
"""

from __future__ import annotations

import os


def force_virtual_cpu_devices(n: int = 8) -> None:
    """Point JAX at ``n`` virtual CPU devices; call before any computation."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()

    import jax

    jax.config.update("jax_platforms", "cpu")
