"""Test configuration.

The suite ASKS for the CPU: a virtual 8-device CPU mesh, so multi-chip
sharding paths compile and execute without TPU hardware (the chip is
reached through chip_smoke.py, never through tests). Must run before
any JAX backend initializes.
"""

import os

from tendermint_tpu.utils.jaxenv import (
    filter_cpu_aot_noise,
    force_cpu_platform,
    is_cpu_aot_noise,
)

force_cpu_platform(8)
# The AOT loader warns (one ~3KB feature-dump line, twice) on EVERY
# persistent-cache executable load — known false positives (see
# filter_cpu_aot_noise) that bury real stderr from failing tests.
# Three layers, because pytest's fd-level capture dup2's over fd 2
# between tests and bypasses any one filter (TM_RAW_CPP_STDERR=1
# bypasses all three):
#  1. the fd filter below — covers collection time and capture-off
#     (-s) runs;
#  2. a report hook scrubbing noise lines from captured-stderr
#     sections — covers what a FAILING test prints;
#  3. an interpreter-exit fd filter (registered at unconfigure, after
#     capture is done with fd 2) — covers the teardown burst of AOT
#     loads from compile-thread joins that used to flood the last
#     screen of every suite run.
filter_cpu_aot_noise()
# Isolate the on-disk valset-table cache per test run: suites reuse
# fixed valset keys (b"valset-key-1", ...), so a shared dir would leak
# one run's built tables into the next and flip build-path assertions
# (e.g. the failed-build latch test would load from disk instead).
import atexit  # noqa: E402
import shutil  # noqa: E402
import tempfile  # noqa: E402

if "TM_TABLES_CACHE_DIR" not in os.environ:
    _tables_tmp = tempfile.mkdtemp(prefix="tm_tables_test_")
    os.environ["TM_TABLES_CACHE_DIR"] = _tables_tmp
    atexit.register(shutil.rmtree, _tables_tmp, True)
# TOML-loaded node configs default to the TPU provider; the suite pins
# cpu so node tests don't spawn background XLA compiles. The TPU
# provider path has dedicated tests (test_tpu_provider.py,
# test_ops_ed25519.py).
os.environ["TM_CRYPTO_PROVIDER"] = "cpu"

import contextlib  # noqa: E402
import faulthandler  # noqa: E402
import hashlib  # noqa: E402
import sys  # noqa: E402

import pytest  # noqa: E402


def _scrub_aot_noise(text: str) -> str:
    lines = [ln for ln in text.splitlines() if not is_cpu_aot_noise(ln)]
    return "\n".join(lines)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_makereport(item, call):
    rep = yield
    if os.environ.get("TM_RAW_CPP_STDERR") != "1":
        rep.sections = [
            (title, _scrub_aot_noise(content) if "stderr" in title else content)
            for title, content in rep.sections
        ]
    return rep


def pytest_unconfigure(config):
    # LIFO atexit: registering the install here (after every
    # module-level import already registered its own hooks, e.g. the
    # verifier's compile-thread join at import time) makes it run
    # FIRST at interpreter exit — so the join-triggered AOT loads warn
    # into the filter, not the terminal. Capture has restored the real
    # fd 2 by the time atexit runs, so the filter wraps the real
    # stderr. Deliberately NOT restored: a restore hook registered now
    # would run BEFORE those earlier-registered join hooks (LIFO) and
    # unwrap fd 2 just ahead of the burst it exists to filter. The
    # filter's pump thread forwards non-noise lines until interpreter
    # finalization; only C++ static-destructor output after that point
    # can be dropped.
    import atexit

    atexit.register(filter_cpu_aot_noise)
    controller = config.pluginmanager.getplugin("dsession")
    if controller is not None:  # xdist's, its workers gone: drop _hang_guard's marks
        shutil.rmtree(_attempts_dir(controller.nodemanager.testrunuid), ignore_errors=True)


# A test stuck inside native code (an XLA executable that never
# returns) cannot be interrupted from Python: unguarded it pins its
# process until the whole run is cut, and nothing says where. The guard
# makes the process write every thread's stack and exit. Under xdist
# (--dist loadfile) the controller then reports the crash and hands the
# file, that test included, to a fresh worker: so the stacks go to a
# file of the run, and there they fail the test at once and the rest of
# the file runs. In one process there is no rerun to tell: the stacks
# go to stderr (captured with the rest unless run with -s).
_HANG_LIMIT_S = 600


def _attempts_dir(run_uid):
    return os.path.join(tempfile.gettempdir(), f"tm_test_attempts_{run_uid}")


@pytest.fixture(autouse=True)
def _hang_guard(request):
    run = os.environ.get("PYTEST_XDIST_TESTRUNUID")
    mark = None
    if run is not None:
        os.makedirs(_attempts_dir(run), exist_ok=True)
        mark = os.path.join(
            _attempts_dir(run), hashlib.sha1(request.node.nodeid.encode()).hexdigest()
        )
        if os.path.exists(mark):
            with open(mark) as fh:
                pytest.fail(
                    "an earlier worker of this run did not come back from this test "
                    f"(stuck for {_HANG_LIMIT_S} s, or crashed); its stacks:\n{fh.read()}"
                )
    with open(mark, "w") if mark else contextlib.nullcontext(sys.__stderr__) as fh:
        faulthandler.dump_traceback_later(_HANG_LIMIT_S, exit=True, file=fh)
        yield
        faulthandler.cancel_dump_traceback_later()
    if mark:
        os.remove(mark)


def load_check_metrics_lint():
    """The scripts/check_metrics.py module (it lives outside the
    package, so tests load it by path — here once, shared by
    test_metrics.py and test_check_metrics.py)."""
    import importlib.util

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts",
        "check_metrics.py",
    )
    spec = importlib.util.spec_from_file_location("check_metrics", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="session")
def cpu_mesh():
    import jax
    from jax.sharding import Mesh
    import numpy as np

    devs = np.array(jax.devices("cpu")[:8])
    return Mesh(devs, ("batch",))
