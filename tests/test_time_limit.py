"""The per-test time limit (``tests/time_limit.py``), run on inner pytest
sessions in subprocesses with the limits cut to seconds."""

import os

pytest_plugins = ["pytester"]

_HERE = os.path.dirname(os.path.abspath(__file__))


def _session(pytester, body):
    pytester.makeconftest(f"""
import sys
sys.path.insert(0, {_HERE!r})
import time_limit

time_limit.SOFT_LIMIT_S = 1.0
time_limit.HARD_LIMIT_S = 4.0


def pytest_configure(config):
    config.pluginmanager.register(time_limit.TimeLimit(config), "dk-time-limit")
""")
    pytester.makepyfile(test_inner=body)


def test_a_test_past_the_soft_limit_fails_alone_and_the_next_runs(pytester):
    _session(pytester, """
import time

def test_sleeps():
    time.sleep(30)

def test_after():
    pass
""")
    result = pytester.runpytest_subprocess("-p", "no:cacheprovider", timeout=60)
    result.assert_outcomes(failed=1, passed=1)
    result.stdout.fnmatch_lines([
        "*Failed: over the per-test soft limit of 1 s, in test_inner.py::test_sleeps (call)*"])
    assert result.duration < 20


def test_a_wait_no_signal_ends_ends_its_worker_once_and_the_file_goes_on(pytester):
    """The wait stands for an XLA compile or a native call: the soft limit's
    SIGALRM is blocked in the main thread, so only the hard limit ends it.
    The crashed test fails once (xdist's crash report); the fresh worker skips
    it instead of dying on it again, and runs the test after it."""
    _session(pytester, """
import signal
import time

def test_blocks():
    signal.pthread_sigmask(signal.SIG_BLOCK, [signal.SIGALRM])
    time.sleep(60)

def test_after():
    pass
""")
    result = pytester.runpytest_subprocess(
        "-p", "no:cacheprovider", "-p", "xdist", "-n", "1", "--dist", "loadfile", "-v", "-rs",
        timeout=90)
    result.assert_outcomes(failed=1, passed=1, skipped=1)
    result.stderr.fnmatch_lines(["Timeout (0:00:0*)!", "*test_inner.py*in test_blocks"])
    result.stdout.fnmatch_lines([
        "*SKIPPED test_inner.py::test_blocks*", "*PASSED test_inner.py::test_after*",
        "worker 'gw0' crashed while running 'test_inner.py::test_blocks'",
        "*ended its worker earlier in this run*not run again*"])
    assert result.stdout.str().count("crashed while running") == 1
    assert result.duration < 45
