"""What compiled, and for how long: a bounded, always-on log fed by jax's
own duration events.

A clock around a bring-up phase says the phase was slow; it cannot say
which program was traced, lowered or compiled in it, and jax's
persistent-cache *miss* events miss a compile that never asks the cache
or is too quick to persist.  jax reports every trace, every lowering to
MLIR and every backend compile as a duration event with the function's
name; :func:`on_duration` is the listener for them (and :func:`on_scalar`
for the mark jax leaves as one STARTS: every jitted ``jnp`` helper met
while a function is traced or lowered reports a trace of its own, some
thousand in a serving bring-up, and only the outermost trace or lowering
of a thread is logged: its seconds cover the rest).
The compile service registers both beside its cache-outcome listener
(``compile_service._install_cache_listener``), and so does
``abstract.deferred_init``, the first step of the jax-native path, which
never loads the compile service.  The listener is process-wide: a caller's own
``jax.jit`` is seen like the program's.  It costs nothing outside a
compile.

Each entry is ``(perf_counter, event, seconds, function name or "")``
with ``event`` one of :data:`EVENTS`.  jax wraps the persistent-cache
lookup INSIDE its backend-compile event, so a program loaded from the
cache arrives as a retrieval event followed by a backend-compile event
that covers it; the pair is folded into one ``cache_retrieval`` entry
carrying the function's name, and ``backend_compile`` is left to mean
what it says.

Counters (always on): ``tdx.jax.backend_compile_s`` /
``tdx.jax.backend_compiles`` (real compiles only) and ``tdx.jax.lower_s``
(trace + lowering).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import List, Tuple

# jax 0.9.0: jax/_src/dispatch.py (the first three), jax/_src/compiler.py.
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_JAX_EVENTS = {
    _TRACE: "trace",
    _LOWER: "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
}
EVENTS = tuple(_JAX_EVENTS.values())

# Entries kept: a serving bring-up logs about five a program, a train
# step a few dozen; the oldest go first.
_MAX_ENTRIES = 4096

Entry = Tuple[float, str, float, str]

_log: "deque[Entry]" = deque(maxlen=_MAX_ENTRIES)
_tls = threading.local()
_installed = False
_install_lock = threading.Lock()


def on_scalar(event: str, value: float, **kw) -> None:
    """The ``jax.monitoring`` scalar listener: a trace or a lowering has
    started on this thread."""
    if event == _TRACE or event == _LOWER:
        _tls.depth = getattr(_tls, "depth", 0) + 1


def on_duration(event: str, seconds: float, **kw) -> None:
    """The ``jax.monitoring`` duration listener."""
    kind = _JAX_EVENTS.get(event)
    if kind is None:
        return
    if kind in ("trace", "lower"):
        _tls.depth = max(0, getattr(_tls, "depth", 0) - 1)
        if _tls.depth:
            return  # inside another trace or lowering, which covers it
    if kind == "cache_retrieval":
        # Closes inside the backend-compile event of the same program, on
        # the same thread: remembered until that one arrives with the name.
        _tls.retrieved = True
        return
    from . import counter  # the package imports this module

    if kind == "backend_compile":
        if getattr(_tls, "retrieved", False):
            _tls.retrieved = False
            kind = "cache_retrieval"
        else:
            counter("tdx.jax.backend_compile_s").inc(seconds)
            counter("tdx.jax.backend_compiles").inc()
    else:
        counter("tdx.jax.lower_s").inc(seconds)
    _log.append((time.perf_counter(), kind, float(seconds),
                 str(kw.get("fun_name") or "")))


def install() -> None:
    """Register the two listeners with jax, once a process."""
    global _installed
    with _install_lock:
        if not _installed:
            from jax._src import monitoring

            monitoring.register_scalar_listener(on_scalar)
            monitoring.register_event_duration_secs_listener(on_duration)
            _installed = True


def entries() -> List[Entry]:
    """The log, oldest first."""
    return list(_log)


def clear() -> None:
    _log.clear()
