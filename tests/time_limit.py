"""A time limit for every test, with no plugin to install (ISSUE 37).

Each test -- setup, call and teardown together -- has ``SOFT_LIMIT_S``.  At
the soft limit SIGALRM raises in the main thread, where pytest and every
xdist worker run tests, and fails that one test by name; the run goes on.  A
wait inside C that no signal interrupts (an XLA compile, a native hub call)
meets ``HARD_LIMIT_S``: faulthandler dumps every thread's stack to stderr
and, under xdist, ends the worker.

xdist reports the dead worker's test failed and queues its unfinished tests
again, the one that ended it included, on a fresh worker, up to its restart
cap.  So a marker file named after each test stands while it runs: a test
that finds its own marker ended a worker before, and is skipped as "not run
again" there; the rest of its file runs.  Without xdist the hard limit only
dumps the stacks.

A test that needs more than the limit is made faster or marked ``slow``; the
limit is not raised.  ``tests/conftest.py`` registers :class:`TimeLimit`;
``tests/test_time_limit.py`` shortens the constants to test it.
"""

import faulthandler
import hashlib
import os
import shutil
import signal
import tempfile
import time

import pytest

# about three times the slowest 'not slow' test under the driver's six-way
# load (121.6 s at PR 37: tests/benchmark/test_benchmark_afmoe.py::
# test_whole_traced_run_of_the_harness)
SOFT_LIMIT_S = 360.0
HARD_LIMIT_S = 420.0

_MARKERS = "dk_time_limit_markers"  # the controller hands its directory to each worker
_ENDED_WORKER = pytest.StashKey[bool]()


class TimeLimit:
    def __init__(self, config):
        # faulthandler writes to a descriptor, and while a test runs pytest
        # points fd 2 at its capture file: keep the real stderr, as
        # pytest's own faulthandler plugin does
        self._stderr = os.dup(2)
        self._markers = getattr(config, "workerinput", {}).get(_MARKERS)
        self._made = None  # the controller's marker directory
        self._deadline = 0.0
        self._prev = signal.signal(signal.SIGALRM, self._soft)

    @staticmethod
    def _soft(signum, frame):
        where = os.environ.get("PYTEST_CURRENT_TEST", "?")
        pytest.fail(f"over the per-test soft limit of {SOFT_LIMIT_S:g} s, "
                    f"in {where}")

    def _arm_hard(self):
        faulthandler.dump_traceback_later(
            max(self._deadline - time.monotonic(), 0.001), file=self._stderr,
            exit=self._markers is not None)

    @pytest.hookimpl(wrapper=True)
    def pytest_runtest_protocol(self, item):
        marker = None
        if self._markers is not None:
            marker = os.path.join(
                self._markers, hashlib.sha1(item.nodeid.encode()).hexdigest())
            if os.path.exists(marker):
                item.stash[_ENDED_WORKER] = True
            else:
                open(marker, "x").close()
        self._deadline = time.monotonic() + HARD_LIMIT_S
        signal.setitimer(signal.ITIMER_REAL, SOFT_LIMIT_S)
        self._arm_hard()
        try:
            return (yield)
        finally:
            signal.setitimer(signal.ITIMER_REAL, 0)
            self._deadline = 0.0
            faulthandler.cancel_dump_traceback_later()
            if marker is not None:
                os.remove(marker)

    @pytest.hookimpl(tryfirst=True)
    def pytest_runtest_setup(self, item):
        if item.stash.get(_ENDED_WORKER, False):
            pytest.skip("ended its worker earlier in this run (the hard limit of "
                        f"{HARD_LIMIT_S:g} s or a crash: its failure and stack dump "
                        "are above); not run again")

    @pytest.hookimpl(trylast=True)
    def pytest_exception_interact(self):
        # pytest's faulthandler plugin cancels any pending dump when a phase
        # raises; the rest of the test keeps its hard limit
        if self._deadline > time.monotonic():
            self._arm_hard()

    @pytest.hookimpl(optionalhook=True)
    def pytest_configure_node(self, node):
        if self._made is None:
            self._made = tempfile.mkdtemp(prefix="dk-time-limit-")
        node.workerinput[_MARKERS] = self._made

    def pytest_unconfigure(self):
        signal.signal(signal.SIGALRM, self._prev)
        os.close(self._stderr)
        if self._made is not None:
            shutil.rmtree(self._made, ignore_errors=True)
