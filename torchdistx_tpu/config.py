"""Framework-level runtime configuration.

The reference's configuration is build-time only (CMake ``TORCHDIST_*``
options, SURVEY.md §5 "Config / flag system"); its runtime API is bare
boolean toggles.  Here the runtime knobs live in one typed, documented
surface, resolved from environment variables once at import and
overridable per-scope::

    import torchdistx_tpu.config as tdx_config
    print(tdx_config.get())                # effective config
    with tdx_config.override(native=False):
        ...                                # Python graph walks only

Environment variables (read at first import):

======================  ====================================================
``TDX_NATIVE``          "0" disables the C++ graph engine (default on when
                        the library is built).
``TDX_CACHE_DIR``       Persistent XLA compilation-cache directory used by
                        the compile service (materializers and serving
                        programs).  Unset means ``<checkout>/.jax_cache``;
                        "" disables.  ``JAX_COMPILATION_CACHE_DIR``, when
                        set, overrides both — a cache placed from outside
                        is never re-pointed in code (see
                        :func:`compile_cache_dir`).
``TDX_REGISTRY_DIR``    Shared compile-artifact registry directory
                        (:mod:`torchdistx_tpu.registry`): when set (and a
                        local ``TDX_CACHE_DIR`` is bound), both
                        materialization engines fetch published init-program
                        executables from it before compiling and publish
                        what they compile — the pod-scale warm path (""
                        disables; see docs/registry.md).
``TDX_RNG_CHUNK``       Row-chunk element count for large RNG draws in the
                        jax bridge (compile-time control; see
                        jax_bridge/ops.py).
``TDX_MATERIALIZE_PIPELINE``
                        Materialization engine mode: ``auto`` (default)
                        splits the recorded init graph along structural
                        groups and pipelines per-group compile/execute when
                        the model is large enough; ``off`` forces the
                        monolithic single-program path (see
                        docs/performance.md).
``TDX_COMPILE_WORKERS`` Thread-pool size for the pipelined materializer's
                        concurrent lower+compile stage (0 = auto-size from
                        the host's CPU count; XLA compilation releases the
                        GIL, so workers overlap for real on multi-core
                        hosts).
``TDX_COMPILE_DEADLINE_S``
                        Watchdog deadline (seconds) for each materialization
                        stage (lower / compile / execute dispatch): a stage
                        running longer is abandoned on its worker thread and
                        retried — a wedged XLA compile can no longer hang
                        the pipeline (0 disables; see docs/robustness.md).
``TDX_MATERIALIZE_RETRIES``
                        Per-STAGE retry budget of the self-healing
                        materialization ladder — each program's compile
                        ladder and execute ladder get this many retries
                        (default 2; the compile ladder's final retry
                        bypasses the persistent cache so a poisoned entry
                        cannot fail every attempt).
``TDX_MATERIALIZE_RESUME_DIR``
                        Directory for materialization progress manifests:
                        when set, the pipelined engine commits each
                        completed group's outputs there, and a rerun after
                        an interrupted materialization (fault,
                        ``MaterializationError``, SIGTERM) skips the
                        already-materialized groups ("" disables).
``TDX_MATERIALIZE_OVERLAP_DEPTH``
                        In-flight slot count of the pipelined engine's
                        double-buffered dispatcher (default 2): up to this
                        many executed-but-uncommitted groups stay in
                        flight, so group *k+1*'s execution overlaps group
                        *k*'s output commit/transfer.  1 serializes
                        execute→commit per group (see
                        docs/performance.md §transport).
``TDX_MATERIALIZE_DONATE``
                        "0" disables buffer donation in the materialize
                        transport layer (the commit/upcast programs and
                        device→device transfers consume their inputs by
                        default — pass-through slots alias buffers, spent
                        staging buffers free at consumption; see
                        docs/performance.md §transport).
``TDX_MATERIALIZE_INIT_DTYPE``
                        Opt-in low-precision init fast path (e.g.
                        ``bf16``): slots the parameter cast-mask permits
                        are computed/stored by the init program in this
                        dtype — halving the bytes the transport moves —
                        and upcast to their contract dtype on device by a
                        donated-buffer program.  Exact-bitwise when the
                        contract dtype already is the init dtype;
                        documented tolerance otherwise ("" disables; see
                        docs/performance.md §transport).
``TDX_MATERIALIZE_BATCH_PUT``
                        "0" disables per-sharding batching of host→device
                        transfers (resume loads fall back to one
                        ``jax.device_put`` per array — the pre-transport
                        behavior, kept as an escape hatch / A-B knob).
``TDX_RESHARD_CHUNK_MB``
                        Host-memory budget (MiB, default 64) for one
                        transfer chunk in :mod:`torchdistx_tpu.reshard`:
                        checkpoint redistribution streams leaf-by-leaf and
                        splits any leaf whose per-shard slice exceeds this
                        budget into bounded slab reads, so resharding never
                        materializes a full unsharded leaf on one host (see
                        docs/robustness.md §Resharding).
``TDX_LOG_LEVEL``       Logging level name for the framework logger.
``TDX_TRACE_DIR``       Directory for runtime telemetry traces: when set,
                        :mod:`torchdistx_tpu.observe` collects spans across
                        record/compile/materialize/train and flushes a
                        Chrome-trace JSON file (Perfetto-loadable) there at
                        process exit ("" disables).
``TDX_METRICS_PATH``    File for the telemetry counter registry: Prometheus
                        text format if the path ends in ``.prom``, JSON
                        lines otherwise ("" disables).  ``%h``/``%p`` in the
                        path expand to hostname/pid at write time (opt-in:
                        paths without the tokens are used verbatim), so
                        concurrent hosts and subprocesses of one run cannot
                        clobber each other's file — ``tools/tdx_trace.py
                        fleet`` merges the per-host/per-pid results back.
``TDX_FLIGHT_DIR``      Directory for flight-recorder post-mortem dumps
                        (:mod:`torchdistx_tpu.observe.flightrec`): when set,
                        an always-on bounded ring of recent telemetry events
                        is kept per process and dumped atomically there on
                        watchdog kills, materialization failures, chaos
                        injections, serve faults, SIGTERM drains, and
                        unhandled exceptions ("" disables).  ``%h``/``%p``
                        expand like ``TDX_METRICS_PATH``.
``TDX_METRICS_EXPORT_S``
                        Period (seconds) of the background metrics-exporter
                        thread: when > 0, the counter registry (and the
                        serve SLO percentile gauges) are re-exported to
                        ``TDX_METRICS_PATH`` every interval, so a fleet
                        scraper sees live values instead of exit-time ones
                        (0 disables; see docs/observability.md).
``TDX_OBS_PORT``        Live telemetry HTTP port
                        (:mod:`torchdistx_tpu.observe.httpd`): when set, a
                        stdlib ThreadingHTTPServer daemon serves
                        ``/metrics`` (Prometheus text), ``/healthz`` /
                        ``/readyz`` (bring-up + liveness), ``/slo``, and
                        ``/flight`` — armed lazily on the first telemetry
                        emission, like the periodic exporter.  ``0`` binds
                        an ephemeral port and writes it to
                        ``TDX_OBS_PORT_FILE`` (unset disables; see
                        docs/observability.md §Live endpoints).
``TDX_OBS_BIND``        Bind address for the live HTTP daemon (default
                        ``127.0.0.1`` — local scrapes only; widen
                        deliberately, e.g. ``0.0.0.0``, on trusted
                        networks).
``TDX_OBS_PORT_FILE``   Where the daemon writes its bound port (one ASCII
                        integer, atomic rename) — required reading for
                        ``TDX_OBS_PORT=0``.  ``%h``/``%p`` expand like
                        ``TDX_METRICS_PATH``; default
                        ``<tempdir>/tdx-obs-%p.port``.
``TDX_FAULT_PLAN``      Deterministic fault-injection plan for the elastic
                        training stack (:mod:`torchdistx_tpu.chaos`), e.g.
                        ``"step@4=raise;save@2=corrupt:truncate"``
                        ("" disables; see docs/robustness.md).
``TDX_PREFILL_CHUNK``   Default chunk-size cap for serving chunked prefill
                        (:mod:`torchdistx_tpu.serve`): max prompt tokens a
                        lane prefills per engine tick.  0 (default) means
                        the largest prefill bucket — i.e. single-chunk for
                        any prompt that fits a bucket.  A host-side
                        scheduling knob: the compiled program set is
                        identical at every setting (see docs/serving.md
                        §Prefix sharing & chunked prefill).
``TDX_SPEC_DECODE``     "0" disables speculative decoding on the serving
                        hot path (:mod:`torchdistx_tpu.serve`): the
                        self-drafting n-gram drafter, the batched
                        ``verify-<k>`` tick, and KV rollback.  On by
                        default — greedy accept keeps every completion
                        bitwise-equal to the unbatched oracle, so the
                        kill switch trades only throughput (see
                        docs/serving.md §Speculative decoding).
``TDX_SPEC_K``          Max draft length per lane per verify tick
                        (default 4, clamped to the largest compiled
                        verify bucket).  A host-side scheduling knob:
                        the compiled ``verify-<k>`` program set is
                        fixed by ``ServeConfig.spec_buckets``, not by
                        this value.
``TDX_REQUEST_LEDGER``  "0" disables the per-request attribution ledger
                        (:mod:`torchdistx_tpu.observe.reqledger`): the
                        serve stack's per-request typed event timeline,
                        queue/prefill/decode/guardrail latency
                        attribution, tail aggregator (``/requests`` and
                        ``/tail``), and occupancy time-series.  On by
                        default — the ledger is bounded-memory and
                        samples only on events the stack already emits
                        (see docs/observability.md §Request ledger).
``TDX_LEDGER_EVENTS``   Per-request event-timeline cap (default 128):
                        older events are dropped (and counted) once a
                        request's timeline is full, so a pathological
                        request cannot grow ledger memory without bound.
``TDX_TRACE_PARENT``    Causal trace-context handoff (NOT a Config field —
                        read once by :mod:`torchdistx_tpu.observe.tracectx`
                        at adoption): a parent process that spawns work
                        stamps ``trace_id:flow_id`` into the child's
                        environment so the merged Chrome trace draws flow
                        arrows across pids/hosts.  Set by the spawners
                        (bench phases, ``warm_cache --spawn-shards``), not
                        by operators.
======================  ====================================================

Per-scope telemetry works like every other knob::

    with tdx_config.override(trace_dir="/tmp/traces"):
        materialize_module_jax(m)   # spans + counters collected
"""

from __future__ import annotations

import contextlib
import os
import threading
from dataclasses import dataclass, replace
from typing import Iterator, Optional

__all__ = [
    "Config",
    "bind",
    "compile_cache_dir",
    "expand_path",
    "get",
    "override",
    "set_flags",
]

# The checkout root (the package's parent directory): what the program
# compiles is built from the files there and cached beside them.
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


@dataclass(frozen=True)
class Config:
    native: bool = True
    cache_dir: Optional[str] = None
    registry_dir: Optional[str] = None
    rng_chunk_elems: int = 1 << 20
    log_level: str = "INFO"
    trace_dir: Optional[str] = None
    metrics_path: Optional[str] = None
    flight_dir: Optional[str] = None
    metrics_export_s: float = 0.0
    obs_port: Optional[int] = None
    obs_bind: str = "127.0.0.1"
    obs_port_file: Optional[str] = None
    fault_plan: Optional[str] = None
    materialize_pipeline: str = "auto"
    compile_workers: int = 0
    compile_deadline_s: float = 0.0
    materialize_retries: int = 2
    materialize_resume_dir: Optional[str] = None
    materialize_overlap_depth: int = 2
    materialize_donate: bool = True
    materialize_init_dtype: Optional[str] = None
    materialize_batch_put: bool = True
    reshard_chunk_mb: float = 64.0
    prefill_chunk: int = 0
    spec_decode: bool = True
    spec_k: int = 4
    request_ledger: bool = True
    ledger_events: int = 128


def _from_env() -> Config:
    cache = os.environ.get("TDX_CACHE_DIR", _DEFAULT_CACHE_DIR)
    return Config(
        native=os.environ.get("TDX_NATIVE", "1") != "0",
        cache_dir=cache or None,
        registry_dir=os.environ.get("TDX_REGISTRY_DIR", "") or None,
        rng_chunk_elems=int(os.environ.get("TDX_RNG_CHUNK", str(1 << 20))),
        log_level=os.environ.get("TDX_LOG_LEVEL", "INFO"),
        trace_dir=os.environ.get("TDX_TRACE_DIR", "") or None,
        metrics_path=os.environ.get("TDX_METRICS_PATH", "") or None,
        flight_dir=os.environ.get("TDX_FLIGHT_DIR", "") or None,
        metrics_export_s=float(os.environ.get("TDX_METRICS_EXPORT_S", "0")),
        obs_port=(
            int(os.environ["TDX_OBS_PORT"])
            if os.environ.get("TDX_OBS_PORT", "") != "" else None
        ),
        obs_bind=os.environ.get("TDX_OBS_BIND", "") or "127.0.0.1",
        obs_port_file=os.environ.get("TDX_OBS_PORT_FILE", "") or None,
        fault_plan=os.environ.get("TDX_FAULT_PLAN", "") or None,
        materialize_pipeline=os.environ.get("TDX_MATERIALIZE_PIPELINE", "auto"),
        compile_workers=int(os.environ.get("TDX_COMPILE_WORKERS", "0")),
        compile_deadline_s=float(os.environ.get("TDX_COMPILE_DEADLINE_S", "0")),
        materialize_retries=int(os.environ.get("TDX_MATERIALIZE_RETRIES", "2")),
        materialize_resume_dir=(
            os.environ.get("TDX_MATERIALIZE_RESUME_DIR", "") or None
        ),
        materialize_overlap_depth=int(
            os.environ.get("TDX_MATERIALIZE_OVERLAP_DEPTH", "2")
        ),
        materialize_donate=os.environ.get("TDX_MATERIALIZE_DONATE", "1") != "0",
        materialize_init_dtype=(
            os.environ.get("TDX_MATERIALIZE_INIT_DTYPE", "") or None
        ),
        materialize_batch_put=(
            os.environ.get("TDX_MATERIALIZE_BATCH_PUT", "1") != "0"
        ),
        reshard_chunk_mb=float(os.environ.get("TDX_RESHARD_CHUNK_MB", "64")),
        prefill_chunk=int(os.environ.get("TDX_PREFILL_CHUNK", "0")),
        spec_decode=os.environ.get("TDX_SPEC_DECODE", "1") != "0",
        spec_k=int(os.environ.get("TDX_SPEC_K", "4")),
        request_ledger=os.environ.get("TDX_REQUEST_LEDGER", "1") != "0",
        ledger_events=int(os.environ.get("TDX_LEDGER_EVENTS", "128")),
    )


_lock = threading.Lock()
_base = _from_env()
_tls = threading.local()


def expand_path(path: Optional[str]) -> Optional[str]:
    """Expand the multi-process template tokens in a telemetry path:
    ``%h`` → short hostname, ``%p`` → pid.  Opt-in — a path without the
    tokens is returned verbatim, so the single-process default behavior
    (one file/dir) is unchanged.  Applied at WRITE time by
    ``observe.flush`` / the metrics exporter / the flight recorder, so
    one config value fans out correctly across hosts and subprocesses
    (``tools/tdx_trace.py`` globs the results back together)."""
    if not path or "%" not in path:
        return path
    if "%h" in path:
        import socket

        path = path.replace("%h", socket.gethostname().split(".")[0])
    if "%p" in path:
        path = path.replace("%p", str(os.getpid()))
    return path


def compile_cache_dir() -> Optional[str]:
    """THE persistent compile-cache directory, resolved in one place:
    ``JAX_COMPILATION_CACHE_DIR`` if set (whoever placed the cache from
    outside — the chip tool, a CI runner — finds its entries again only
    if nothing re-points it, and the path is part of no key but of every
    lookup); else the effective ``cache_dir`` (``TDX_CACHE_DIR`` or an
    :func:`override`; unset env means ``<checkout>/.jax_cache``); None
    when that was disabled with ``""``."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or get().cache_dir


def get() -> Config:
    """The effective config (innermost :func:`override` scope, else the
    process-wide base)."""
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else _base


def set_flags(**kw) -> Config:
    """Permanently update the process-wide base config."""
    global _base
    with _lock:
        _base = replace(_base, **kw)
        return _base


def override(**kw):
    """Thread-local scoped override: ``with override(native=False): ...``
    (a :func:`bind` of the current effective config with ``kw`` replaced)."""
    return bind(replace(get(), **kw))


@contextlib.contextmanager
def bind(cfg: Config) -> Iterator[Config]:
    """Thread-local scope binding an EXACT ``Config``.

    :func:`override` scopes live on the calling thread's stack and are
    invisible to worker threads; subsystems that fan work out (the
    pipelined materializer's compile pool) capture ``get()`` on the
    submitting thread and re-enter it on each worker with this, so
    per-scope knobs — telemetry activation, ``rng_chunk_elems``, cache
    dir — mean the same thing on every thread of one logical operation."""
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    stack.append(cfg)
    try:
        yield cfg
    finally:
        stack.pop()
