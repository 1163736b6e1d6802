"""torchdistx_tpu — a TPU-native framework with the capabilities of torchdistX.

Two frontends share one core idea (fake tensors + deferred, replayable
initialization):

* the **torch frontend** (:mod:`torchdistx_tpu.fake`,
  :mod:`torchdistx_tpu.deferred_init`) mirrors the reference API surface —
  ``fake_mode``, ``deferred_init``, ``materialize_tensor``,
  ``materialize_module`` — via Python dispatch interposition;
* the **JAX frontend** provides the same capabilities for JAX/flax models
  via abstract evaluation, and the JAX bridge compiles recorded torch init
  graphs to XLA programs that materialize parameters directly into sharded
  TPU HBM (``torchdistx_tpu.abstract`` / ``torchdistx_tpu.jax_bridge``).
"""

# Single source of truth is the VERSION file (setup.py reads it; the
# nightly/release pipelines stamp it via scripts/set_version.py).  An
# installed package reports its wheel metadata; a source checkout falls
# back to reading VERSION directly.
def _read_version() -> str:
    import pathlib

    # A source checkout answers from VERSION itself — an egg-info left
    # behind by an earlier build in the same tree can be stale.
    vf = pathlib.Path(__file__).resolve().parent.parent / "VERSION"
    try:
        return vf.read_text().strip()
    except OSError:
        pass
    try:
        from importlib.metadata import PackageNotFoundError, version

        return version("torchdistx_tpu")
    except Exception:
        return "0+unknown"


__version__ = _read_version()

# Deferred init promises the SAME parameter values whatever mesh they
# materialize onto.  Only the partitionable threefry is sharding-invariant
# by construction: under the legacy one a jitted random.normal with sharded
# out_shardings produces different draws per sharding — breaking that
# promise (and any cross-mesh loss oracle built on it).  It is jax's
# default; pin it so an environment override cannot void the contract.
def _configure_jax() -> None:
    try:
        import jax
    except ImportError:  # pure-torch-frontend installs carry no jax
        return
    jax.config.update("jax_threefry_partitionable", True)


_configure_jax()
