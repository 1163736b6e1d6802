"""Profiling / tracing hooks (XLA-level) + deprecated host-timer shims.

``trace`` wraps ``jax.profiler`` (view in TensorBoard/XProf).  Named
regions on the profiler's timeline come from
:mod:`torchdistx_tpu.observe`: while telemetry is enabled every
``observe.span`` also opens a ``jax.profiler.TraceAnnotation`` of its
name, so a capture made with ``trace`` holds the program's spans on
``/host:CPU`` beside the device's operations.  ``observe.span`` is also
the block-until-ready aware host timer, and ``observe.StepMeter`` is the
training-loop successor of ``StepTimer``.  ``Timer`` and ``StepTimer``
survive as deprecation shims with their original semantics (and, when
telemetry is enabled, their measurements now flow into the shared tracer
too).
"""

from __future__ import annotations

import contextlib
import time
import warnings
from typing import Any, Iterator, Optional

import jax

from .. import observe


@contextlib.contextmanager
def trace(logdir: str) -> Iterator[None]:
    """Capture an XLA profile for the enclosed region.

    The Python call tracer is off and the host tracer at level 2, as in
    the benchmark's traced runs: an event for every Python call makes a
    few seconds of trace tens of MB and slows the host whose gaps the
    capture is there to explain, while ``TraceAnnotation``s (the
    program's spans among them) are kept."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class Timer:
    """DEPRECATED shim: use ``observe.span(name)`` (same block-until-ready
    semantics, plus the measurement lands in the exported trace).

    >>> with Timer() as t:
    ...     out = step(state, batch)
    ...     t.block_on(out)
    >>> t.elapsed
    """

    def __init__(self):
        warnings.warn(
            "torchdistx_tpu.utils.profiling.Timer is deprecated; use "
            "torchdistx_tpu.observe.span(...) instead.",
            DeprecationWarning,
            stacklevel=2,
        )
        self.elapsed: Optional[float] = None
        self._blocked: Any = None
        self._span = None

    def __enter__(self) -> "Timer":
        self._span = observe.span("utils.Timer", category="compat")
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def block_on(self, value: Any) -> Any:
        self._blocked = value
        return value

    def __exit__(self, *exc) -> None:
        if self._blocked is not None:
            jax.block_until_ready(self._blocked)
            self._blocked = None  # don't pin device arrays past the scope
        self.elapsed = time.perf_counter() - self._t0
        span, self._span = self._span, None
        span.__exit__(None, None, None)


class StepTimer(observe.StepMeter):
    """DEPRECATED shim: use :class:`torchdistx_tpu.observe.StepMeter`
    (same ``start``/``stop``/``steps``/``total``/``mean`` surface, plus
    per-step spans and tokens-per-second / MFU gauges)."""

    def __init__(self):
        warnings.warn(
            "torchdistx_tpu.utils.profiling.StepTimer is deprecated; use "
            "torchdistx_tpu.observe.StepMeter instead.",
            DeprecationWarning,
            stacklevel=2,
        )
        super().__init__(name="utils.StepTimer")
