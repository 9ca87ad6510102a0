import os

# Tests run on CPU with 8 *virtual* devices: the sharded-dispatch tests
# (test_fleet_sharding.py) need a multi-device ('data',) mesh, and the full
# suite is verified to pass unchanged under this flag.  It must be set
# before jax initializes its backends — hence here, at conftest import time
# — and is appended so an externally supplied XLA_FLAGS still applies.
# (The 512-device dry-run configuration stays subprocess-only; see
# test_system.py.)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_FLAG = "--xla_force_host_platform_device_count=8"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + _FLAG).strip()

import numpy as np
import pytest


# XLA's CPU backend maps each compiled program's code into the process,
# some 1,500 mappings for one qos cascade pair of a new shape.  A worker
# that runs many compiling tests reaches the kernel's vm.max_map_count
# (65,530 by default), and LLVM then fails to compile with "Cannot allocate
# memory" in whichever test comes next.  Past this many mappings the jit
# caches are dropped after a test, which unmaps the programs no one holds.
_MAP_HIGH = 10_000


def _n_maps() -> int:
    try:
        with open("/proc/self/maps", "rb") as f:
            return sum(1 for _ in f)
    except OSError:
        return 0


@pytest.fixture(autouse=True)
def _bounded_code_maps():
    yield
    if _n_maps() > _MAP_HIGH:
        import gc

        import jax

        jax.clear_caches()
        gc.collect()


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True)
def _simlint_sanitizers(request):
    """Opt-in sanitizer harness: ``SIMLINT_SANITIZE=1 pytest ...`` runs
    every test under the lock-order sanitizer (raising on cycles), the
    axis sanitizer (raising on @axes contract violations), and the
    recompile sanitizer in record-only mode (first-compile-per-shape is
    legitimate inside a test; the steady-state assertions live in
    tests/test_simlint.py).  Off by default: wrapping lock creation has
    measurable overhead and the CI lint job runs the sanitized smoke on
    tests/test_engine.py explicitly."""
    if os.environ.get("SIMLINT_SANITIZE") != "1":
        yield
        return
    if request.node.get_closest_marker("no_sanitize") is not None:
        # tests that patch threading or assert sanitizer behavior manage
        # their own scopes
        yield
        return
    from repro.analysis.sanitize import (
        AxisSanitizer,
        LockOrderSanitizer,
        RecompileSanitizer,
    )

    with LockOrderSanitizer():
        with RecompileSanitizer(record_only=True):
            with AxisSanitizer():
                yield


@pytest.fixture(scope="session")
def data_mesh():
    """A ('data',) mesh over every (virtual) device — the sharded-dispatch
    mesh the analyzer/suite/fleet `mesh=` options expect."""
    from repro.launch.mesh import make_data_mesh

    return make_data_mesh()
