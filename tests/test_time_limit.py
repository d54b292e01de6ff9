"""The suite-wide test time limit (tests/conftest.py): a test that blocks
past it fails with every thread's stack, and the run goes on to its end."""

import os
import subprocess
import sys
import textwrap

SUITE_CONFTEST = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "conftest.py")


def test_deadlocked_test_fails_with_thread_dump_and_run_ends(tmp_path):
    # The suite's conftest, loaded under its own name with the limit
    # lowered to 2 s; its hooks are this directory's hooks.
    (tmp_path / "conftest.py").write_text(textwrap.dedent(f"""
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "suite_conftest", {SUITE_CONFTEST!r})
        suite = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(suite)
        suite.TEST_TIME_LIMIT_S = 2
        pytest_runtest_protocol = suite.pytest_runtest_protocol
        pytest_runtest_setup = suite.pytest_runtest_setup
        pytest_runtest_call = suite.pytest_runtest_call
        pytest_runtest_teardown = suite.pytest_runtest_teardown
    """))
    (tmp_path / "test_blocked.py").write_text(textwrap.dedent("""
        import threading

        def test_blocks_on_a_held_lock():
            lock = threading.Lock()
            lock.acquire()
            threading.Thread(target=lock.acquire, name="other-waiter",
                             daemon=True).start()
            lock.acquire()  # never released

        def test_runs_after_the_blocked_one():
            pass
    """))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", str(tmp_path)],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 1, out
    assert "1 failed, 1 passed" in out, out
    assert "TestTimeLimitExceeded" in out, out
    assert "2 s time limit" in out, out
    # faulthandler's dump: the blocked main thread and the other waiter.
    assert "Current thread" in out and "Thread 0x" in out, out
    assert "test_blocks_on_a_held_lock" in out, out
