"""Test harness: simulate an 8-chip slice on CPU.

This is the multi-node test strategy the reference never had (SURVEY §4):
``--xla_force_host_platform_device_count=8`` gives 8 virtual XLA devices,
so every mesh/collective path runs in CI without TPU hardware.  Must be
set before jax initializes — hence here, at conftest import time.
"""

import os
import sys
import time

# repo-root modules (chip_smoke.py, __graft_entry__.py) are test subjects too;
# make them importable regardless of the CWD pytest is invoked from
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# -- tier-1 budget tripwire ----------------------------------------------------
# The driver runs the 'not slow' subset on six xdist workers under a hard
# 1,470 s timeout, and a run it cuts counts only as far as it got.  Warn
# LOUDLY at 700 s (539 s here at PR 37) so the margin erodes in plain
# sight instead of flaking first.  The tripwire only warns after the run:
# what ends a test that hangs is the per-test limit (tests/time_limit.py).
# DK_TIER1_WARN_S overrides the threshold (testing the tripwire itself).
TIER1_WARN_S = float(os.environ.get("DK_TIER1_WARN_S", "700"))
_session_t0 = time.monotonic()


def pytest_configure(config):
    from time_limit import TimeLimit

    config.pluginmanager.register(TimeLimit(config), "dk-time-limit")


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    elapsed = time.monotonic() - _session_t0
    markexpr = str(config.getoption("markexpr", "") or "")
    if "not slow" in markexpr and elapsed > TIER1_WARN_S:
        terminalreporter.write_sep(
            "=", "tier-1 budget tripwire", yellow=True, bold=True)
        terminalreporter.write_line(
            f"WARNING: the 'not slow' suite took {elapsed:.0f}s — past the "
            f"{TIER1_WARN_S:.0f}s tripwire and closing on the driver's 1470s "
            f"timeout.  Shrink the newest heavyweight tests, or slow-mark "
            f"one that takes over 30 s alone, before the run is cut.",
            yellow=True)

def require_tool(*names):
    """Shared skip-guard for cells that shell out to optional toolchain
    binaries (g++, cppcheck, clang-tidy, ...): skip — not fail — in
    containers that don't ship them.  One helper so the
    cppcheck/clang-tidy, -Wall/-Wextra/-Werror and TSAN cells can never
    drift on how 'tool missing' is decided (ISSUE 14 satellite)."""
    import shutil

    import pytest as _pytest

    for name in names:
        if shutil.which(name) is None:
            _pytest.skip(f"no {name} in this container")


def join_all(threads, what, timeout=60.0):
    """Join ``threads`` within one ``timeout`` for the lot, below the
    per-test soft limit (``tests/time_limit.py``), and fail naming ``what``
    they were doing if one is still alive: ``join`` returns None either
    way."""
    deadline = time.monotonic() + timeout
    for t in threads:
        t.join(timeout=max(deadline - time.monotonic(), 0.0))
    alive = [t.name for t in threads if t.is_alive()]
    assert not alive, f"{what}: {alive} still running after {timeout:g} s"


def native_mark():
    """``pytest.param(..., marks=native_mark())``: skip the case where the
    C++ hub cannot be built here."""
    import pytest as _pytest

    from distkeras_tpu.runtime.native import build_error, native_available

    return _pytest.mark.skipif(not native_available(),
                               reason=f"native PS unavailable: {build_error()}")


from distkeras_tpu.platform import pin_cpu_devices  # noqa: E402

pin_cpu_devices(8)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def toy_classification():
    """Linearly-separable 2-class blobs: learnable in a few SGD steps."""
    rng = np.random.default_rng(0)
    n = 1024
    half = n // 2
    x0 = rng.normal(loc=-2.0, scale=1.0, size=(half, 8)).astype(np.float32)
    x1 = rng.normal(loc=+2.0, scale=1.0, size=(half, 8)).astype(np.float32)
    x = np.concatenate([x0, x1])
    y = np.concatenate([np.zeros(half, np.int32), np.ones(half, np.int32)])
    perm = rng.permutation(n)
    return x[perm], y[perm]


@pytest.fixture(scope="session")
def toy_dataset(toy_classification):
    from distkeras_tpu.data.dataset import Dataset

    x, y = toy_classification
    onehot = np.eye(2, dtype=np.float32)[y]
    return Dataset({"features": x, "label": onehot, "label_index": y})


@pytest.fixture
def telemetry():
    """Enable the process-global registry/tracer for one test, leaving a
    clean disabled slate afterwards (other tests must keep paying only the
    disabled-mode branch)."""
    from distkeras_tpu import observability as obs

    obs.reset()
    obs.enable()
    yield obs
    obs.disable()
    obs.reset()
