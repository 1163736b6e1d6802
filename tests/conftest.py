"""Test configuration: force an 8-device virtual CPU mesh for JAX tests.

Multi-chip TPU hardware is unavailable in CI; all sharding/parallelism
tests run against ``--xla_force_host_platform_device_count=8`` CPU devices,
the moral equivalent of the reference's CPU-only CI exercising its CUDA
build (reference .github/workflows/push.yaml:30-48).
"""

import os

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

    # A donated buffer that the lowering cannot alias to an output is an
    # error, as it is in ``serve.programs.compile_serving_program`` itself
    # (ROADMAP S1): a test that jits a serving program's body on its own
    # must not pass over the warning either.  (``transport``'s cast
    # program ignores it locally, by design.)
    config.addinivalue_line(
        "filterwarnings", "error:Some donated buffers were not usable")
