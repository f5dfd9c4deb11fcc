"""Test harness: run everything on a virtual 8-device CPU mesh so multi-chip
sharding logic is exercised without TPU hardware (SURVEY.md environment
notes).

``JAX_PLATFORMS=cpu`` and the eight virtual devices are set in the
environment BEFORE jax is imported — the one mechanism that forces the
CPU here.  Real-chip mode: ``SPARK_RAPIDS_TEST_PLATFORM=tpu`` skips the
forcing so the same compare suites execute against the actual TPU
backend (the CPU oracle side of each compare still runs in numpy).
Double-precision results then go through XLA's f64 emulation (~48-bit
mantissa — see docs/compatibility.md "Double precision on TPU"), so
float comparisons are relaxed to the tolerances below.
"""

import os

TEST_PLATFORM = os.environ.get("SPARK_RAPIDS_TEST_PLATFORM", "cpu")

if TEST_PLATFORM != "tpu":
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np
import pytest

import spark_rapids_tpu  # noqa: F401  (enables x64)
from spark_rapids_tpu.utils.compile_registry import enable_persistent_cache

# Persistent XLA compilation cache across suite runs: the suite is
# compile-bound (every test's fresh execs re-jit), and cached executables
# cut repeat-run wall time substantially.  Content-addressed, safe to
# share; placed by JAX_COMPILATION_CACHE_DIR where set, else the fixed
# <checkout>/.jax_cache (delete it to force cold compiles).
enable_persistent_cache(min_compile_secs=0.5)

# f64 emulation on TPU carries ~48 mantissa bits; aggregations also reorder
# float reductions.  CPU mode keeps tight tolerances.
FLOAT_REL = 1e-4 if TEST_PLATFORM == "tpu" else 1e-6
FLOAT_ABS = 1e-6 if TEST_PLATFORM == "tpu" else 1e-9


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running integration tests excluded from the quick "
        "(-m 'not slow') tier-1 pass; still run by a direct invocation")


# Per-test wall-clock bound (ci/run_ci.sh exports PYTEST_PER_TEST_TIMEOUT):
# a wedged test — historically a cross-suite state leak around test #262 —
# fails loudly with a TimeoutError instead of hanging the whole run.
# SIGALRM-based (tests execute on the main thread); 0/unset disables.
_PER_TEST_TIMEOUT = float(os.environ.get("PYTEST_PER_TEST_TIMEOUT", "0") or 0)

if _PER_TEST_TIMEOUT > 0:
    import signal

    @pytest.hookimpl(hookwrapper=True)
    def pytest_runtest_call(item):
        def on_timeout(signum, frame):
            import faulthandler
            import sys
            # all-thread stacks: the wedged thread is usually NOT the main
            # thread (e.g. a stage worker stuck in a device transfer)
            faulthandler.dump_traceback(file=sys.stderr)
            raise TimeoutError(
                f"test exceeded PYTEST_PER_TEST_TIMEOUT="
                f"{_PER_TEST_TIMEOUT:g}s (wedged? check for leaked "
                f"worker threads / device state from earlier tests)")

        old = signal.signal(signal.SIGALRM, on_timeout)
        signal.setitimer(signal.ITIMER_REAL, _PER_TEST_TIMEOUT)
        try:
            yield
        finally:
            signal.setitimer(signal.ITIMER_REAL, 0)
            signal.signal(signal.SIGALRM, old)


# Plan-invariant verification (RAPIDS_PLAN_VERIFY=1 — ci/run_ci.sh turns
# it on): wrap TpuSparkSession.execute_with_metrics (behind execute and
# DataFrame.collect) so every plan the suite runs is structurally
# verified after collection — schema/transition consistency,
# donation-mask provenance, semaphore balance (analysis/plan_verify.py).
# Runs on the executed plan objects, so it costs microseconds per query.
if os.environ.get("RAPIDS_PLAN_VERIFY") == "1":
    from spark_rapids_tpu.analysis import plan_verify as _plan_verify
    from spark_rapids_tpu.session import TpuSparkSession as _TpuSession

    _orig_execute = _TpuSession.execute_with_metrics

    def _verified_execute(self, plan):
        out = _orig_execute(self, plan)
        _plan_verify.verify_session(self)
        return out

    _TpuSession.execute_with_metrics = _verified_execute


@pytest.fixture
def rng():
    return np.random.RandomState(42)


def assert_cols_equal(expected, actual, approx=False, msg=""):
    """Deep-compare two column value lists (None = NULL)."""
    assert len(expected) == len(actual), \
        f"{msg}: row count {len(expected)} != {len(actual)}"
    approx = approx or TEST_PLATFORM == "tpu"
    for i, (e, a) in enumerate(zip(expected, actual)):
        if e is None or a is None:
            assert e is None and a is None, f"{msg} row {i}: {e!r} != {a!r}"
        elif approx and isinstance(e, float):
            if e != e:  # NaN
                assert a != a, f"{msg} row {i}: {e!r} != {a!r}"
            else:
                assert a == pytest.approx(e, rel=FLOAT_REL, abs=FLOAT_ABS), \
                    f"{msg} row {i}: {e!r} != {a!r}"
        else:
            assert e == a, f"{msg} row {i}: {e!r} != {a!r}"


def assert_batches_equal(expected, actual, approx=False, ignore_order=False):
    """Compare two HostBatch-like pydicts."""
    e, a = expected, actual
    approx = approx or TEST_PLATFORM == "tpu"
    assert set(e.keys()) == set(a.keys()), f"{e.keys()} != {a.keys()}"
    if ignore_order:
        def keyed(d):
            cols = list(d.keys())
            rows = list(zip(*[d[c] for c in cols]))
            return sorted(rows, key=lambda r: tuple(
                (x is None, str(x)) for x in r))
        er = keyed(e)
        ar = keyed(a)
        assert len(er) == len(ar), f"row count {len(er)} != {len(ar)}"
        for i, (re_, ra) in enumerate(zip(er, ar)):
            for c, (x, y) in enumerate(zip(re_, ra)):
                if approx and isinstance(x, float) and x is not None \
                        and y is not None:
                    if x != x:
                        assert y != y
                    else:
                        assert y == pytest.approx(
                            x, rel=FLOAT_REL, abs=FLOAT_ABS), \
                            f"row {i} col {c}: {x!r} != {y!r}"
                else:
                    assert (x is None) == (y is None) and (
                        x is None or x == y or
                        (approx and isinstance(x, float)
                         and y == pytest.approx(
                             x, rel=FLOAT_REL, abs=FLOAT_ABS))), \
                        f"row {i} col {c}: {x!r} != {y!r}"
    else:
        for name in e:
            assert_cols_equal(e[name], a[name], approx=approx, msg=name)
