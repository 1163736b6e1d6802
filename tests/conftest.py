"""Test configuration: force an 8-device virtual CPU mesh for JAX tests.

Multi-chip TPU hardware is unavailable in CI; all sharding/parallelism
tests run against ``--xla_force_host_platform_device_count=8`` CPU devices,
the moral equivalent of the reference's CPU-only CI exercising its CUDA
build (reference .github/workflows/push.yaml:30-48).
"""

import contextlib
import faulthandler
import os
import signal
import sys
import threading

import pytest

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "0")
# Tests are hermetic about the compile cache: none by default (the
# program's own default is <checkout>/.jax_cache), and never one placed
# from outside — the hit/miss cases bind their own temporary directories,
# which config.compile_cache_dir() would ignore under
# JAX_COMPILATION_CACHE_DIR.
os.environ["TDX_CACHE_DIR"] = ""
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

# The suite's sharding cases need the 8 virtual devices above, which only
# the CPU platform provides; pin it through the config API too, so a
# jax_platforms value set by an earlier import cannot win.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-second cases (long hang injection) excluded from the "
        "tier-1 run's -m 'not slow'; `make chaos-test` includes them",
    )
    config.addinivalue_line(
        "markers",
        f"wall_limit(seconds): the test's own wall limit, for one that "
        f"needs more than the default {WALL_LIMIT_S} s",
    )
    # The stream the wall limit dumps stacks to, taken while no capture
    # is on (a capturing stream may have no descriptor).
    global _stacks_to
    _stacks_to = os.fdopen(os.dup(sys.__stderr__.fileno()), "w")

    # A donated buffer that the lowering cannot alias to an output is an
    # error, as it is in ``serve.programs.compile_serving_program`` itself
    # (ROADMAP S1): a test that jits a serving program's body on its own
    # must not pass over the warning either.  (``transport``'s cast
    # program ignores it locally, by design.)
    config.addinivalue_line(
        "filterwarnings", "error:Some donated buffers were not usable")


# Each phase of a test (setup, call, teardown) has a wall limit, so that
# a hang costs one test and not the whole run.  The slowest test takes
# about a third of it on a loaded CI box.
WALL_LIMIT_S = 300
# Past the limit by this much, a main thread stuck in native code (where
# the alarm's handler cannot run) ends the process, with every thread's
# stack on stderr.
_HARD_GRACE_S = 60
_stacks_to = None


class WallLimitExceeded(BaseException):
    """Not an ``Exception``: the code under test (an engine requeueing a
    failed step, say) must not catch it as one of its own faults."""


def _on_alarm(signum, frame):
    faulthandler.dump_traceback(_stacks_to, all_threads=True)
    raise WallLimitExceeded("the test ran into its wall limit; every "
                            "thread's stack went to stderr")


@contextlib.contextmanager
def _wall_limit(item):
    """Arm the alarm over one phase of ``item``, in the main thread only
    (where a signal handler runs)."""
    if threading.current_thread() is not threading.main_thread():
        yield
        return
    mark = item.get_closest_marker("wall_limit")
    limit = mark.args[0] if mark else WALL_LIMIT_S
    old = signal.signal(signal.SIGALRM, _on_alarm)
    signal.setitimer(signal.ITIMER_REAL, limit)
    faulthandler.dump_traceback_later(limit + _HARD_GRACE_S, exit=True,
                                      file=_stacks_to)
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    with _wall_limit(item):
        return (yield)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    with _wall_limit(item):
        return (yield)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_teardown(item):
    with _wall_limit(item):
        return (yield)
