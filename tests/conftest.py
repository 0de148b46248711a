"""Test env: force JAX onto a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; sharding behavior is tested
on 8 virtual CPU devices per the build environment contract. See
``kvedge_tpu/testing/jaxenv.py`` for why the ordering (env vars *and*
jax.config, before any backend init) is load-bearing. JAX's persistent
compile cache stays off for the tests and the processes they start
(``kvedge_tpu/runtime/compilecache.py`` would otherwise fill
``<repo>/.jax_cache`` from every ``start_runtime``).
"""

import os
import pathlib
import shutil
import subprocess

import pytest

from kvedge_tpu.testing.jaxenv import force_virtual_cpu_devices

os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"  # child processes
force_virtual_cpu_devices(8)

import jax  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)  # this process

_NATIVE_DIR = pathlib.Path(__file__).resolve().parent.parent / "native"

# One process compiling the whole ~660-test suite accumulates XLA state
# (jit caches + loaded executables) until XLA's compiler segfaulted at
# ~619 tests — reproducibly, with 125 GB free.
# Bound the live population: clear JAX's compilation caches every N
# tests. Module-level jitted wrappers (e.g. kvcache._paged_decode_step)
# keep working — their cache entries just recompile on next use. The
# committed tools/run_tests.py sharded runner is the stronger guarantee
# (fresh process per ≤250 tests); this keeps the plain
# ``python -m pytest tests`` invocation viable too.
_CLEAR_EVERY = int(os.environ.get("KVEDGE_CLEAR_CACHES_EVERY", "150"))
_test_counter = {"n": 0}


def pytest_runtest_teardown(item, nextitem):
    _test_counter["n"] += 1
    if _CLEAR_EVERY > 0 and _test_counter["n"] % _CLEAR_EVERY == 0:
        import jax

        jax.clear_caches()


@pytest.fixture(scope="session")
def kvedge_init() -> pathlib.Path:
    """The compiled native PID-1 supervisor (native/kvedge-init.cc)."""
    if shutil.which("g++") is None or shutil.which("make") is None:
        pytest.skip("no C++ toolchain in this environment")
    subprocess.run(
        ["make", "-C", str(_NATIVE_DIR)], check=True, capture_output=True
    )
    return _NATIVE_DIR / "build" / "kvedge-init"


@pytest.fixture(scope="session")
def probe_blocks() -> dict:
    """``{"recurrent": (cfg, params), "window-block": (cfg, params)}``:
    the probe configurations of tests/test_hybrid_block.py (pattern
    m m a m) and tests/test_window_block.py (pattern f w w w, a window
    of 24), for the tests that run the server's loop, locks and ledgers
    on the blocks four of five cells serve. A patterned block takes
    ``prefix_cache=False``, and ``decode.generate`` refuses it: its
    reference is the same server, served another way."""
    from kvedge_tpu.models import hybrid
    from tests import test_hybrid_block, test_window_block

    cfgs = {"recurrent": test_hybrid_block.config_of(),
            "window-block": test_window_block.config_of()}
    return {name: (cfg, hybrid.init_params(jax.random.PRNGKey(0), cfg))
            for name, cfg in cfgs.items()}
