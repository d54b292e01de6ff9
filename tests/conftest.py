"""Test configuration: force an 8-virtual-device CPU backend so multi-chip
sharding paths (mesh/pjit/shard_map) are exercised without TPU hardware.

The forcing recipe (env + jax.config override, already-initialized guard)
lives in fluidframework_tpu.core.platform.force_host_platform — the shared
implementation also used by __graft_entry__.dryrun_multichip.

Every test runs under one time limit, TEST_TIME_LIMIT_S (setup, call and
teardown together): past it, the test fails with every thread's stack.
"""

import contextlib
import faulthandler
import os
import signal
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The suite runs on the forced-CPU mesh, where the cost model would route
# every single-lane catch-up scalar (mergetree/costmodel.py: the B=1
# kernel never wins on CPU). Force the device path so the kernel
# machinery stays exercised; routing itself is tested explicitly with
# the override cleared (tests/test_bulk_catchup.py::TestCostModel).
os.environ.setdefault("FLUID_TPU_FORCE_BULK", "1")

try:
    from fluidframework_tpu.core.platform import force_host_platform

    force_host_platform(8)
except ImportError:  # pragma: no cover - jax-less env: pure-Python tests only
    pass

try:
    from fluidframework_tpu.core.platform import enable_compile_cache

    enable_compile_cache()
except ImportError:  # pragma: no cover
    pass

# One time limit for every test (setup + call + teardown), about five
# times the slowest test. A test past it fails with every thread's stack.
TEST_TIME_LIMIT_S = 300


class TestTimeLimitExceeded(Exception):
    __test__ = False  # not a test class, whatever its name


def _on_time_limit(signum, frame):
    with tempfile.TemporaryFile() as f:
        faulthandler.dump_traceback(f, all_threads=True)
        f.seek(0)
        stacks = f.read().decode(errors="replace")
    raise TestTimeLimitExceeded(
        f"test exceeded its {TEST_TIME_LIMIT_S} s time limit; "
        f"every thread's stack:\n{stacks}")


@contextlib.contextmanager
def _time_limit(deadline):
    """SIGALRM at the test's deadline, for one phase. Lock acquires and
    waits are interruptible by signals, so a deadlocked test raises in
    its main thread instead of freezing its worker."""
    if threading.current_thread() is not threading.main_thread():
        yield  # signals reach the main thread only; the backstop holds
        return
    previous = signal.signal(signal.SIGALRM, _on_time_limit)
    signal.setitimer(signal.ITIMER_REAL,
                     max(deadline - time.monotonic(), 1.0))
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)


try:
    import pytest

    _DEADLINE = pytest.StashKey[float]()

    @pytest.hookimpl(wrapper=True)
    def pytest_runtest_protocol(item, nextitem):
        """Sets the test's deadline and the backstop: a test that never
        returns to the interpreter, so no signal handler can run, has its
        worker killed with every thread's stack (xdist replaces it). The
        stack goes to the worker's own stderr (capture is off between
        phases)."""
        item.stash[_DEADLINE] = time.monotonic() + TEST_TIME_LIMIT_S
        stderr = os.dup(2)
        faulthandler.dump_traceback_later(TEST_TIME_LIMIT_S + 60,
                                          exit=True, file=stderr)
        try:
            return (yield)
        finally:
            faulthandler.cancel_dump_traceback_later()
            os.close(stderr)

    @pytest.hookimpl(wrapper=True)
    def pytest_runtest_setup(item):
        with _time_limit(item.stash[_DEADLINE]):
            return (yield)

    @pytest.hookimpl(wrapper=True)
    def pytest_runtest_call(item):
        with _time_limit(item.stash[_DEADLINE]):
            return (yield)

    @pytest.hookimpl(wrapper=True)
    def pytest_runtest_teardown(item, nextitem):
        with _time_limit(item.stash[_DEADLINE]):
            return (yield)

    @pytest.fixture(autouse=True)
    def _isolate_stage_latency_histograms():
        """The stage latency histograms (telemetry/counters.observe) are
        process-global and feed the SLO verdict on /health: without
        per-test isolation, one test's serving-flush tail would flip a
        LATER test's health check to 503 under randomized ordering.
        Named counters are deliberately left alone (pre-existing
        cross-test semantics)."""
        yield
        from fluidframework_tpu.telemetry import counters

        counters.reset_histograms()
except ImportError:  # pragma: no cover - conftest imported outside pytest
    pass
