"""The compile service: jit → lower → compile ONE JAX program, with
everything a process needs around that and nothing of the torch bridge.

Every frontend compiles through here — the torch bridge's two
materialization engines (:mod:`.jax_bridge.materialize`), the JAX
frontend (:mod:`.abstract`), the serving runtime
(:mod:`.serve.programs`) and the registry's warm scheduler
(:mod:`.registry.scheduler`) — so one set of module state decides, once
per process, what is cached where:

* **cache binding** (:func:`bind_cache` / :func:`reset_cache_binding`):
  the ONE place that points jax's persistent compilation cache at
  :func:`.config.compile_cache_dir`, with the quarantine-on-corrupt
  guard around jax's loader;
* **artifact registry** (docs/registry.md): with ``TDX_REGISTRY_DIR``
  set, :func:`compile_program` fetches → verifies → installs a published
  executable before the compile and publishes the local entry after;
* **outcome accounting**: exact hit / miss / uncached / bypass per
  compile, attributed through jax's monitoring stream on the compiling
  thread;
* **self-healing** (docs/robustness.md): the stage watchdog
  (:func:`bounded_stage`, ``TDX_COMPILE_DEADLINE_S``) and the bounded
  retry ladder (:func:`run_ladder`).

It imports jax, :mod:`.chaos`, :mod:`.observe`, :mod:`.config`,
:mod:`.registry` and the logger: a serving replica compiles its decode
program without loading torch.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, List, Optional

import jax

from . import chaos, observe
from .utils.logging import get_logger

__all__ = [
    "CompileHangError",
    "INIT_COMPILER_OPTIONS",
    "bind_cache",
    "bounded_stage",
    "compile_program",
    "compiler_options",
    "execute_compiled",
    "reset_cache_binding",
    "retryable_errors",
    "run_ladder",
]


class CompileHangError(RuntimeError):
    """A materialization stage exceeded the ``TDX_COMPILE_DEADLINE_S``
    watchdog deadline; its worker thread was abandoned (a wedged XLA
    compile cannot be cancelled from Python).  Always retryable."""

# Init programs execute once for milliseconds; optimized codegen buys
# nothing while costing ~2x compile wall time on TPU.  Ask XLA for its
# lowest effort.  Excess precision is disabled because torch replay is
# the parity oracle: XLA otherwise computes bf16 chains in f32 WITHOUT
# intermediate rounding, so a recorded bf16 add followed by a cast reads
# the unrounded value torch never produces.  Whether the active backend
# accepts the options is probed ONCE on a trivial program, so real
# compile failures on init programs propagate immediately instead of
# being retried at full effort.
INIT_COMPILER_OPTIONS = {
    "exec_time_optimization_effort": -1.0,
    "xla_allow_excess_precision": False,
}
_options_supported: Optional[dict] = None
_options_lock = threading.Lock()


def compiler_options() -> Optional[dict]:
    """The subset of INIT_COMPILER_OPTIONS the active backend accepts,
    probed per option (a backend rejecting the perf knob must not also
    silently drop the parity-critical precision knob).  ONE probe program
    is lowered and recompiled per option key; the whole probe runs under
    a lock because pipelined materialization calls this from several
    compile workers at once."""
    global _options_supported
    with _options_lock:
        if _options_supported is None:
            accepted = {}
            probe = jax.jit(lambda: jax.numpy.zeros(())).lower()
            for key, value in INIT_COMPILER_OPTIONS.items():
                try:
                    probe.compile(compiler_options={key: value})
                    accepted[key] = value
                    outcome = "accepted"
                except Exception:
                    outcome = "rejected"
                    if key == "xla_allow_excess_precision":
                        import warnings

                        warnings.warn(
                            "backend rejects xla_allow_excess_precision=False; "
                            "recorded bf16 chains may read excess-precision f32 "
                            "intermediates, losing bitwise parity with torch "
                            "replay."
                        )
                if observe.enabled():
                    # Probed once per process; the outcome is provenance a
                    # trace reader needs (a backend silently dropping the
                    # parity knob changes what the numbers mean).
                    observe.counter(
                        f"tdx.jax.compiler_option_{outcome}", option=key
                    ).inc()
                    observe.instant(
                        "jax.compiler_option_probe", category="jax",
                        option=key, outcome=outcome,
                    )
            _options_supported = accepted
        return _options_supported or None


_cache_enabled = False
_cache_latch_lock = threading.Lock()


def _bind_cache_dir(cache_dir: Optional[str]) -> None:
    """Point jax's persistent compilation cache at ``cache_dir`` (None
    unbinds).  ``jax_persistent_cache_enable_xla_caches="none"`` goes
    with every binding: at its default jax embeds the cache-dir PATH
    into CompileOptions (the XLA-side autotune/kernel caches, GPU-only
    amenities), which makes the compile-cache key a function of the
    local path — a cache warmed under one directory (a login host, the
    artifact registry's install target) could then never be hit from
    another.  jax memoizes a once-per-process "cache used?" decision at
    the FIRST compile, so any compile before the binding (even the
    PRNGKey seed computation) latches it to "unused"; ``reset_cache()``
    un-latches it so the directory set here actually binds."""
    from jax._src import compilation_cache as _cc

    jax.config.update("jax_persistent_cache_enable_xla_caches", "none")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    _cc.reset_cache()


def bind_cache() -> None:
    """Bind jax's persistent compilation cache to the directory
    :func:`.config.compile_cache_dir` resolves, so repeated
    materializations and replica bring-ups of the same model skip XLA
    compilation — the dominant cost of the cold path.  Guarded: the
    pipelined engine's workers must not race the once-per-process latch."""
    global _cache_enabled
    with _cache_latch_lock:
        if _cache_enabled:
            return
        from . import config

        cache_dir = config.compile_cache_dir()
        if cache_dir:
            _install_cache_guard()
            # Persist every program, however fast it compiled: jax's own
            # 0.1 s threshold would leave a quick one (the serving `cow`
            # program compiles in under that on a v5e) a "miss" on every
            # bring-up, and "second bring-up: all hit, zero local
            # compiles" must not depend on a program being slow to
            # compile.  TDX_CACHE_MIN_COMPILE_S restores a threshold.
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs",
                float(os.environ.get("TDX_CACHE_MIN_COMPILE_S", "0")),
            )
            _bind_cache_dir(cache_dir)
            _cache_enabled = True


def reset_cache_binding() -> None:
    """Un-latch the cache binding so the NEXT materialize re-resolves
    the cache directory (tests and tools/warm_cache.py switch
    ``cache_dir`` mid-process; normal runs never need this).  Also
    unbinds the jax-level directory: a later materialize with the cache
    disabled must report ``uncached`` and stop persisting into the
    previously bound dir, not keep using it by inertia.  A directory
    placed from outside (``JAX_COMPILATION_CACHE_DIR``) is the one
    binding there is and is never unbound or replaced."""
    global _cache_enabled
    with _cache_latch_lock:
        _cache_enabled = False
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            _bind_cache_dir(None)


# -- corrupt-cache quarantine ------------------------------------------------
#
# jax loads a persistent-cache entry by decompressing + deserializing the
# on-disk bytes; a truncated or bit-rotted entry raises there, and —
# depending on jax's raise_persistent_cache_errors config — either aborts
# the compile outright or silently degrades to a warning-and-recompile
# that leaves the poisoned entry on disk for every later process to trip
# over again.  The guard wraps the loader ONCE: a failing entry is
# QUARANTINED (renamed `<entry>.corrupt`, kept for forensics like
# checkpoint quarantine), counted in tdx.jax.cache_quarantined, and
# reported as a miss so the ladder recompiles and re-persists a clean
# entry in its place.

_cache_guard_installed = False
_cache_guard_lock = threading.Lock()


def _quarantine_cache_entry(cache_key: str) -> List[str]:
    """Rename the on-disk entry file(s) for ``cache_key`` to
    ``<name>.corrupt``; returns the names moved (empty when no cache dir
    is bound or the entry has already vanished)."""
    d = jax.config.jax_compilation_cache_dir
    if not d:
        return []
    moved: List[str] = []
    try:
        for name in os.listdir(d):
            # LRUCache stores `<key>-cache` (+ an atime stamp the LRU
            # bookkeeping owns); other CacheInterface impls store the
            # bare key.  Never re-quarantine an already-moved entry.
            if name in (f"{cache_key}-cache", cache_key):
                os.replace(
                    os.path.join(d, name), os.path.join(d, name + ".corrupt")
                )
                moved.append(name)
    except OSError:
        pass
    return moved


def _note_cache_key(cache_key: str) -> None:
    """Record a jax persistent-cache key touched by the compile running
    on THIS thread (both the get and put wrappers report here).  The
    registry publish path reads the recorded keys to know which on-disk
    cache entries the just-finished compile corresponds to."""
    rec = getattr(_mon_tls, "cache_keys", None)
    if rec is not None and cache_key not in rec:
        rec.append(cache_key)


def _registry_direct_serve(cache_key, compile_options, backend,
                           executable_devices):
    """Serve the current compile's executable straight from the fetched
    registry artifact when the local cache load missed.

    The registry installs artifacts under the jax cache-key names their
    PUBLISHER computed, but jax's key is not perfectly stable across
    traces and processes (it hashes serialized compile options whose
    incidental fields can drift) — while the registry's content address
    is, and it already pinned "same recorded computation, same output
    contract, same compile environment".  So a key mismatch must cost a
    rename, not a recompile: deserialize the artifact's payload with
    THIS compile's options and also install it under the key THIS
    process computes, healing the local cache for later compiles.  The
    caller records the normal cache-hit monitoring event, so outcome
    accounting sees an ordinary hit."""
    payloads = getattr(_mon_tls, "registry_payload", None)
    if not payloads:
        return None, None
    from jax._src import compilation_cache as _cc

    for data in payloads:
        try:
            serialized, compile_time = _cc.extract_executable_and_time(
                _cc.decompress_executable(data)
            )
            executable = backend.deserialize_executable(
                serialized, executable_devices, compile_options
            )
        except jax.errors.JaxRuntimeError as e:
            # A payload XLA refuses to load (another topology's artifact,
            # a truncated blob the CRC did not cover): try the next one,
            # else the caller compiles.
            get_logger().warning(
                "registry: direct-serve payload rejected (%s: %s)",
                type(e).__name__, str(e)[:120],
            )
            continue
        d = jax.config.jax_compilation_cache_dir
        if d:
            dst = os.path.join(d, f"{cache_key}-cache")
            tmp = f"{dst}.tdx-tmp-{os.getpid()}-{threading.get_ident()}"
            try:
                with open(tmp, "wb") as f:
                    f.write(data)
                os.replace(tmp, dst)
            except OSError:
                try:
                    os.remove(tmp)
                except OSError:
                    pass
        observe.counter("tdx.registry.direct_serves").inc()
        observe.instant(
            "registry.direct_serve", category="registry",
            key=cache_key[:40],
        )
        return executable, (compile_time if compile_time is not None else 0)
    return None, None


def _install_cache_guard() -> None:
    """Wrap ``jax._src.compilation_cache.get_executable_and_time`` with
    the quarantine-on-corrupt behavior (plus cache-key recording for the
    artifact registry, also hooked into ``put_executable_and_time``, and
    the ladder's per-thread cache bypass); installed once per process.
    Written against the installed jax's signatures: a moved internal is
    an ImportError/AttributeError here, not a silently cold cache."""
    global _cache_guard_installed
    with _cache_guard_lock:
        if _cache_guard_installed:
            return
        from jax._src import compilation_cache as _cc

        _orig = _cc.get_executable_and_time
        _orig_put = _cc.put_executable_and_time

        def _recording_put(cache_key, module_name, executable, backend,
                           compile_time):
            if getattr(_mon_tls, "bypass", False):
                return None  # the fresh-compile rung persists nothing
            _note_cache_key(cache_key)
            return _orig_put(cache_key, module_name, executable, backend,
                             compile_time)

        def _guarded(cache_key, compile_options, backend,
                     executable_devices):
            if getattr(_mon_tls, "bypass", False):
                return None, None  # the fresh-compile rung reads nothing
            _note_cache_key(cache_key)
            try:
                result = _orig(cache_key, compile_options, backend,
                               executable_devices)
            except Exception as e:  # noqa: BLE001 — any load error
                moved = _quarantine_cache_entry(cache_key)
                observe.counter("tdx.jax.cache_quarantined").inc(
                    max(1, len(moved))
                )
                observe.instant(
                    "jax.cache_quarantined", category="jax",
                    key=cache_key, error=f"{type(e).__name__}: {e}"[:200],
                    moved=len(moved),
                )
                get_logger().warning(
                    "materialize: corrupt persistent-cache entry %s "
                    "(%s: %s); quarantined %s and recompiling",
                    cache_key, type(e).__name__, str(e)[:120],
                    [m + ".corrupt" for m in moved] or "(file gone)",
                )
                result = (None, None)  # a miss: the caller recompiles
            if result[0] is None:
                # Local miss (or quarantine): a verified registry
                # artifact staged for this compile serves it directly.
                result = _registry_direct_serve(
                    cache_key, compile_options, backend, executable_devices
                )
            return result

        _cc.get_executable_and_time = _guarded
        _cc.put_executable_and_time = _recording_put
        _cache_guard_installed = True


# -- self-healing ladder ------------------------------------------------------

_RETRY_BACKOFF_BASE_S = 0.05
_RETRY_BACKOFF_MAX_S = 2.0
_retryable_cache: Optional[tuple] = None


def retryable_errors() -> tuple:
    """Exception types the materialization ladder retries: the jax/XLA
    runtime error shapes (what device loss and transient compiler
    failures surface as), the chaos fallback error, and the watchdog's
    :class:`CompileHangError`.  Everything else — ``NotImplementedError``
    from an unsupported op, ``ValueError`` from bad config — is a real
    bug and fails fast."""
    global _retryable_cache
    if _retryable_cache is None:
        _retryable_cache = (CompileHangError, chaos.InjectedRuntimeError,
                            jax.errors.JaxRuntimeError)
    return _retryable_cache


def _retry_backoff(attempt: int) -> None:
    time.sleep(min(_RETRY_BACKOFF_MAX_S,
                   _RETRY_BACKOFF_BASE_S * (2 ** (attempt - 1))))


def run_ladder(attempt_fn, *, retries: int, retryable: tuple,
                describe: str, bypass_note: bool = False):
    """THE retry ladder every materialization stage runs: call
    ``attempt_fn(attempt)`` until it returns, retrying only ``retryable``
    errors up to ``retries`` times with exponential backoff, counting
    each retry in ``tdx.jax.compile_retries``.  ``attempt_fn`` receives
    the 0-based attempt number — rungs that vary by attempt (the final
    retry's cache bypass) key off it.  The final error re-raises
    unchanged: callers choose the terminal action (wrap in
    ``jax_bridge.materialize.MaterializationError``, fail the group,
    fall back)."""
    attempt = 0
    while True:
        try:
            return attempt_fn(attempt)
        except Exception as e:  # noqa: BLE001 — classified just below
            if not isinstance(e, retryable):
                raise
            attempt += 1
            if attempt > retries:
                raise
            observe.counter("tdx.jax.compile_retries").inc()
            get_logger().warning(
                "materialize: %s failed (%s: %s); retry %d/%d%s",
                describe, type(e).__name__, str(e)[:120], attempt, retries,
                " with persistent cache bypassed"
                if bypass_note and attempt == retries else "",
            )
            _retry_backoff(attempt)


def _chaos_cache_path() -> Optional[str]:
    """The bound persistent-cache dir, the target of cache-corruption
    faults at the materialization sites."""
    return jax.config.jax_compilation_cache_dir


def bounded_stage(stage: str, fn, *, deadline: Optional[float], group: int):
    """Run one materialization stage, optionally under the compile
    watchdog: with a deadline the stage runs on a daemon thread that is
    ABANDONED on timeout (the device_health abandoned-thread recipe — a
    wedged XLA compile cannot be cancelled from Python) and the stage is
    reported retryable via :class:`CompileHangError`.  Injected chaos
    hangs on the abandoned thread wake on the cancel event instead of
    sleeping out their full argument."""
    if not deadline or deadline <= 0:
        return fn()
    box: Dict[str, object] = {}
    cancel = threading.Event()

    def _target():
        chaos.set_cancel_event(cancel)
        try:
            box["result"] = fn()
        except BaseException as e:  # noqa: BLE001 — relayed to the caller
            box["error"] = e

    t = threading.Thread(
        target=_target, daemon=True, name=f"tdx-mat-{stage}-{group}"
    )
    t.start()
    t.join(deadline)
    if t.is_alive():
        cancel.set()
        observe.counter("tdx.jax.compile_watchdog_kills").inc()
        observe.instant(
            "jax.compile_watchdog_kill", category="jax",
            stage=stage, group=group, deadline_s=deadline,
        )
        # The evidence a post-mortem needs — which spans led up to the
        # wedge — would evaporate if the process were killed next; the
        # flight recorder persists it NOW (no-op without TDX_FLIGHT_DIR).
        observe.flight_dump(
            "compile_watchdog_kill", stage=stage, group=group,
            deadline_s=deadline,
        )
        raise CompileHangError(
            f"init-program {stage} of group {group} exceeded the "
            f"{deadline}s watchdog deadline (TDX_COMPILE_DEADLINE_S); "
            f"worker thread abandoned — the stage will be retried"
        )
    if "error" in box:
        raise box["error"]
    return box["result"]


# -- compile-cache outcome accounting ---------------------------------------
#
# The hit/miss oracle is jax's own monitoring stream: a persistent-cache
# HIT records '/jax/compilation_cache/cache_hits' and a persisted MISS
# records '/jax/compilation_cache/cache_misses', both synchronously on the
# thread running the compile — so attributing events through a
# thread-local keeps the counters EXACT even with TDX_COMPILE_WORKERS
# compiles in flight at once.  A miss too fast/small to persist records
# nothing and still counts as "miss", the same boundary bench.py's warm
# stamp documents.

_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"
_mon_tls = threading.local()
_listener_installed = False
_listener_lock = threading.Lock()


def _on_jax_event(event: str, **kw) -> None:
    rec = getattr(_mon_tls, "events", None)
    if rec is not None and event in (_HIT_EVENT, _MISS_EVENT):
        rec.append(event)


def _install_cache_listener() -> None:
    """Register the jax monitoring listeners once per process: the
    cache-outcome events above, and the durations of every trace,
    lowering and backend compile (``observe.compilelog``)."""
    global _listener_installed
    with _listener_lock:
        if not _listener_installed:
            from jax._src import monitoring

            monitoring.register_event_listener(_on_jax_event)
            observe.compilelog.install()
            _listener_installed = True

# -- pod-scale artifact registry (docs/registry.md) --------------------------
#
# With TDX_REGISTRY_DIR set, every program compile consults the shared
# content-addressed registry: fetch→verify→install the published
# executable into the local persistent cache BEFORE compiling (the
# compile then loads it as an ordinary local hit), and publish the local
# cache entry AFTER a compile that produced one.  The registry key
# composes the program's content fingerprint (the caller's ``program_fp`` —
# seed-independent: the PRNG key is a runtime argument) with the
# compile-environment identity (registry.env_key).  Every registry
# failure mode degrades to a local compile.

_registry_nocache_warned = False


def _active_registry():
    """The configured :class:`.registry.ArtifactRegistry`, or None."""
    from . import config

    rdir = config.get().registry_dir
    if not rdir:
        return None
    from .registry import ArtifactRegistry

    return ArtifactRegistry(rdir)


def _warn_registry_without_cache() -> None:
    global _registry_nocache_warned
    if not _registry_nocache_warned:
        _registry_nocache_warned = True
        get_logger().warning(
            "TDX_REGISTRY_DIR is set but no local persistent cache is "
            "bound (TDX_CACHE_DIR): registry fetches need a local cache "
            "to install into — registry disabled for this run"
        )


def compile_program(init_fn, key, out_shardings, label=None, *,
                     fault_plan=None, deadline=None, bypass_cache=False,
                     program_fp=None, jit_kwargs=None,
                     init_compiler_options=True):
    """jit → lower → compile ONE program; returns
    ``(compiled, lower_s, compile_s, cache_outcome, costs)`` where
    ``costs`` is the compiler-reported accounting
    (:func:`.observe.costmodel.program_costs`: FLOPs, bytes accessed,
    argument/output/temp/peak device bytes — None when the probes are
    unavailable); the same record is attached to the ``jax.compile``
    span, folded into the HBM high-water gauge, and published into the
    registry manifest.  Safe to call from
    several threads at once — jax tracing is thread-local and the cache
    outcome is attributed through the monitoring record of whichever
    thread runs the compile (the watchdog may move it to an inner
    thread, so the record is installed there, not on the caller).

    ``key`` is the program's argument: the init PRNG key for the
    materialization engines, or a TUPLE of (abstract or concrete)
    arguments for multi-operand programs — the serving runtime
    (:mod:`torchdistx_tpu.serve.programs`) compiles its prefill/decode
    programs through here so the registry, the chaos sites, the
    watchdog, and the exact cache-outcome counters cover serving too.

    ``fault_plan`` pins the chaos plan for the ``lower`` / ``cache`` /
    ``compile`` / ``registry`` injection sites (group-number keyed; the
    monolith is group 1); ``deadline`` arms the stage watchdog;
    ``bypass_cache`` compiles with the persistent cache neither read nor
    written on the compiling thread — the ladder's fresh-compile rung:
    the final retry of a repeatedly failing program must not be able to
    fail through a poisoned cache entry the quarantine guard could not
    catch (the registry is also skipped on that
    rung: a poisoned artifact must not be able to fail every attempt).
    ``program_fp`` makes the program registry-eligible: when a registry
    is configured, its artifact is fetched into the local cache before
    the compile and the local cache entry published after.
    ``jit_kwargs`` pass through to ``jax.jit``; ``init_compiler_options``
    = False compiles at the backend's default effort (steady-state
    serving programs execute millions of times — the init programs'
    lowest-effort codegen is exactly wrong for them; the parity-critical
    excess-precision knob only matters for the torch-replay oracle,
    which serving programs are not judged against)."""
    gno = label + 1 if isinstance(label, int) else 1
    args = key if isinstance(key, tuple) else (key,)
    kw = dict(jit_kwargs or {})
    if out_shardings is not None:
        kw["out_shardings"] = out_shardings
    jitted = jax.jit(init_fn, **kw)
    opts = compiler_options() if init_compiler_options else None
    attrs = {} if label is None else {"group": label}
    _install_cache_listener()  # before the lowering: its trace is logged too
    t0 = time.perf_counter()
    with observe.span("jax.lower", category="jax", **attrs):
        def _do_lower():
            chaos.maybe_inject(
                "lower", gno, path=_chaos_cache_path(), plan=fault_plan
            )
            return jitted.lower(*args)

        lowered = bounded_stage("lower", _do_lower, deadline=deadline,
                                 group=gno)
    t_lower = time.perf_counter() - t0
    cdir = _chaos_cache_path()
    reg = regkey = reg_payload = None
    if program_fp is not None and not bypass_cache:
        reg = _active_registry()
        if reg is not None:
            if cdir:
                from .registry import registry_key

                regkey = registry_key(program_fp)
                # Under the same watchdog as the stages proper: a
                # blocking read on a dead shared filesystem is a hang
                # the raise/slow/corrupt degrade paths cannot see, and
                # the contract is that registry trouble costs savings,
                # never liveness.  A timed-out fetch is just a miss.
                try:
                    reg_payload = bounded_stage(
                        "registry-fetch",
                        lambda: reg.fetch_for_compile(
                            regkey, cdir, gno=gno, plan=fault_plan
                        ),
                        deadline=deadline, group=gno,
                    )
                except CompileHangError:
                    reg_payload = None
            else:
                _warn_registry_without_cache()
                reg = None
    t0 = time.perf_counter()
    with observe.span("jax.compile", category="jax", **attrs) as csp:
        events: List[str] = []
        cache_keys: List[str] = []

        def _do_compile():
            # Installed on whichever thread RUNS the compile (the
            # watchdog may be an inner thread).
            _mon_tls.events = events
            _mon_tls.cache_keys = cache_keys
            _mon_tls.registry_payload = (
                list(reg_payload.values()) if reg_payload else None
            )
            _mon_tls.bypass = bypass_cache
            try:
                chaos.maybe_inject("cache", gno, path=cdir, plan=fault_plan)
                chaos.maybe_inject("compile", gno, path=cdir, plan=fault_plan)
                return (
                    lowered.compile(compiler_options=opts)
                    if opts is not None else lowered.compile()
                )
            finally:
                _mon_tls.events = None
                _mon_tls.cache_keys = None
                _mon_tls.registry_payload = None
                _mon_tls.bypass = False

        compiled = bounded_stage(
            "compile", _do_compile, deadline=deadline, group=gno
        )
        if bypass_cache:
            outcome = "bypass"
        elif not jax.config.jax_compilation_cache_dir:
            outcome = "uncached"  # no persistent cache dir configured
        else:
            outcome = "hit" if _HIT_EVENT in events else "miss"
        csp.set(cache=outcome)
        # Compiler-reported accounting — probed unconditionally: the one
        # call per program compile is noise next to the compile itself,
        # and run stats / bench / the registry manifest consume the
        # numbers even when tracing is off.
        costs = observe.costmodel.program_costs(compiled)
        if costs:
            csp.set(**{f"xla_{k}": v for k, v in costs.items()})
            observe.costmodel.note_program_memory(costs)
        if observe.enabled():
            observe.counter(f"tdx.jax.compile_cache_{outcome}").inc()
    if reg is not None and outcome in ("hit", "miss") and cache_keys and cdir:
        # Publish AFTER the compile regardless of hit/miss: a hit whose
        # entry predates the registry (locally-warmed host, registry
        # added later) still gets shared; has() inside skips duplicates.
        # Watchdog-bounded like the fetch — a wedged publish must not
        # hang a materialization that already has its executable.
        try:
            bounded_stage(
                "registry-publish",
                lambda: reg.publish_from_cache(
                    regkey, cdir, cache_keys, gno=gno, plan=fault_plan,
                    meta={
                        "program_fp": program_fp,
                        # The manifest records what the compiler said this
                        # program costs — a fleet can budget HBM/FLOPs for
                        # a program it has never compiled locally.
                        **({"xla_costs": costs} if costs else {}),
                    },
                ),
                deadline=deadline, group=gno,
            )
        except CompileHangError:
            pass  # unpublished: some other host (or rerun) will
    return compiled, t_lower, time.perf_counter() - t0, outcome, costs


def execute_compiled(compiled, key, gno, *, deadline, fault_plan,
                      retries, retryable):
    """Dispatch one compiled program with the ``execute`` chaos site,
    the stage watchdog, and a bounded re-dispatch ladder (an executable
    in hand re-executes cheaply; a transient dispatch failure must not
    burn a whole recompile)."""

    def _attempt(_a):
        def _do_execute():
            chaos.maybe_inject(
                "execute", gno, path=_chaos_cache_path(), plan=fault_plan
            )
            return compiled(key)

        return bounded_stage("execute", _do_execute, deadline=deadline,
                              group=gno)

    return run_ladder(_attempt, retries=retries, retryable=retryable,
                       describe=f"execute of group {gno}")
