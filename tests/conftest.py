"""Test env: force CPU with 8 virtual devices so multi-chip sharding tests run
without TPU hardware (the driver validates the real multi-chip path via
__graft_entry__.dryrun_multichip). Must run before jax is imported."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# Tests run on the 8 virtual CPU devices: force the CPU in the config too.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Flight-recorder auto-dumps (engine faults, quarantines, breaker trips,
# recoveries — paths the fault/journal suites exercise on purpose) land
# in a per-session scratch dir instead of shedding files into /tmp.
import atexit  # noqa: E402
import tempfile  # noqa: E402

if "TPU_FLIGHT_DIR" not in os.environ:
    _flight_dir = tempfile.TemporaryDirectory(prefix="tpu-flight-tests-")
    os.environ["TPU_FLIGHT_DIR"] = _flight_dir.name
    atexit.register(_flight_dir.cleanup)


def pytest_configure(config):
    # Markers used by the tier-1 selection (`-m 'not slow'`) and the
    # fault-injection matrix (scripts/run_fault_matrix.py runs the full
    # grid; the fast subset in tests/test_faults.py stays in tier-1).
    config.addinivalue_line(
        "markers", "slow: long-running; excluded from tier-1 (-m 'not slow')"
    )
    config.addinivalue_line(
        "markers", "faults: fault-injection matrix tests"
    )


import gc  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture
def armed_heap():
    """What `cmd_serve` arms (framework/tracing.py: the heap frozen at every
    batch boundary that bound a pod, swept at the checkpoint), undone on
    exit: the tests and files that run after see the interpreter's
    collector."""
    from kubernetes_tpu.framework.tracing import PROCESS

    gc.unfreeze()  # an interpreter may start with a few hundred objects frozen
    gc.collect()
    PROCESS.heap_armed = True
    try:
        yield PROCESS
    finally:
        PROCESS.heap_armed = False
        gc.unfreeze()
        gc.enable()
