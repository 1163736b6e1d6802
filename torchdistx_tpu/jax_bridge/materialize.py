"""Sharded materialization: recorded torch init graphs → sharded jax.Arrays.

The north-star workflow (BASELINE.json): ``deferred_init`` a model too big
for one host, then materialize its parameters *already sharded* across a
TPU mesh.  Where the reference replays eagerly onto the recorded device
(deferred_init.cc:258-268), this compiles the recording with
``jax.jit(..., out_shardings=plan)`` so XLA partitions the entire init
computation — each device computes and stores only its own shard, and peak
host RSS stays O(largest metadata), not O(model size).

Two engines share one contract (bitwise-identical outputs, chosen by
``TDX_MATERIALIZE_PIPELINE`` — see docs/performance.md):

* **monolithic** (``off``): the whole recording traced into ONE jitted
  program — lower → compile → execute, serially;
* **pipelined** (``auto``, default): the recording split along structural
  groups (:func:`..compile.split_init_groups`) into independently jittable
  sub-programs; a thread pool lowers and compiles them concurrently (XLA
  compilation releases the GIL), and a dispatcher executes each group as
  its executable lands, streaming outputs into their planned
  ``NamedSharding``s.  Host-side Python trace, XLA compile, and device
  execution overlap instead of serializing — and at scale the split itself
  beats the monolith's superlinear compile even single-threaded.

Every program is compiled by :mod:`..compile_service` (cache binding,
artifact registry, watchdog, retry ladder — torch-free, shared with the
JAX frontend and the serving runtime); this module owns what needs the
recorded torch graph.

Both engines are **self-healing** (docs/robustness.md): every stage
(lower / compile / execute) runs under a bounded-retry ladder with an
optional watchdog (``TDX_COMPILE_DEADLINE_S``) that abandons a wedged XLA
compile instead of hanging the pool; corrupt persistent-cache entries are
quarantined on load (``<key>.corrupt``) and recompiled; a pipelined group
that exhausts its retries degrades to the monolithic program; and with
``TDX_MATERIALIZE_RESUME_DIR`` set, completed groups are committed to a
progress manifest so an interrupted materialization (fault or SIGTERM)
resumes where it left off instead of re-tracing the whole model.  Total
failure raises a typed :class:`MaterializationError` carrying which
groups succeeded.

With ``TDX_REGISTRY_DIR`` set (and a local ``TDX_CACHE_DIR`` bound), both
engines additionally consult the **pod-scale artifact registry**
(:mod:`..registry`, docs/registry.md) around every program compile: a
published executable for the same program fingerprint and compile
environment is fetched, CRC-verified, and installed into the local
persistent cache so the compile becomes an ordinary local hit; a program
compiled locally is published back for the rest of the fleet.  Registry
trouble of any kind degrades to a local compile, never an error.
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import threading
import time
import zlib
from concurrent.futures import (
    FIRST_COMPLETED,
    ThreadPoolExecutor,
    wait as _futures_wait,
)
from typing import Dict, Iterator, List, Optional, Tuple

import jax
import numpy as np
import torch
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .. import chaos, compile_service, observe, transport
from .._graph import gc_paused
from ..compile_service import CompileHangError  # re-exported: jax_bridge.__all__
from ..fake import is_fake
from ..parallel.sharding import ShardingPlan
from ..utils.logging import get_logger
from .compile import build_init_fn, group_fingerprint, split_init_groups

__all__ = [
    "CompileHangError",
    "MaterializationError",
    "materialize_tensor_jax",
    "named_fake_tensors",
    "materialize_params_jax",
    "materialize_module_jax",
    "lower_init_module",
    "lower_init_groups",
    "last_run_stats",
]


class MaterializationError(RuntimeError):
    """Materialization failed (or was drained by SIGTERM) after the full
    degradation ladder: per-stage retries, cache bypass, and — for the
    pipelined engine — the monolithic-program fallback.

    ``completed_groups`` / ``failed_groups`` are the pipelined engine's
    group indices that finished / exhausted their ladder (the monolithic
    engine is the single group ``0``).  ``resumable`` is True when a
    progress manifest was left under ``TDX_MATERIALIZE_RESUME_DIR`` — a
    rerun of the same materialization skips the committed groups.
    ``drained`` marks a SIGTERM drain (the fallback ladder is NOT
    attempted for a drain: the process is being preempted)."""

    def __init__(self, msg, *, completed_groups=(), failed_groups=(),
                 resumable=False, drained=False):
        super().__init__(msg)
        self.completed_groups = sorted(completed_groups)
        self.failed_groups = sorted(failed_groups)
        self.resumable = resumable
        self.drained = drained


# -- registry key material ---------------------------------------------------
#
# compile_service.compile_program does the fetch → verify → install and
# the publish for any program that comes with a ``program_fp``; what a
# recorded torch init program's fingerprint is made of is the bridge's
# business (it walks fakes).

def _registry_program_fp(fake_list, idxs, out_shardings, param_dtype,
                         cast_mask, transport_fp=None) -> Optional[str]:
    """Registry key material for one init program: the cross-process
    content fingerprint of the group's recorded computation
    (:func:`..compile.group_fingerprint`) composed with the output
    contract (cast policy, planned shardings) — everything the compiled
    executable depends on EXCEPT the runtime PRNG key, so one artifact
    serves every seed.  None when no stable fingerprint exists (the
    program is then simply not registry-eligible).

    ``transport_fp`` is the low-precision transport's per-slot storage
    record (:meth:`..transport.TransportPlan.fp_material`): the init
    dtype changes the compiled program, so its artifacts must never
    collide with default-path ones.  None (the default config) leaves
    the digest byte-identical to the pre-transport scheme — warmed
    registries stay valid."""
    import hashlib

    try:
        structural = group_fingerprint([fake_list[i] for i in idxs])
    except Exception:  # noqa: BLE001 — unstable chain: compile locally
        return None
    h = hashlib.sha1(b"tdx-program-fp-v1")
    h.update(structural.encode())
    for pos, i in enumerate(idxs):
        osh = out_shardings[i] if out_shardings is not None else None
        h.update(repr((pos, str(param_dtype), bool(cast_mask[i]),
                       str(osh))).encode())
    if transport_fp is not None:
        h.update(repr(("transport", transport_fp)).encode())
    return h.hexdigest()


def _cast_outputs(init_fn, param_dtype, mask=None):
    """Wrap ``init_fn`` so floating outputs are cast to ``param_dtype``
    INSIDE the compiled program: the standard TPU policy — compute init
    statistics in f32, store parameters in bf16 — with the cast fused by
    XLA, so full-precision values never exist in device memory.

    ``mask`` selects which outputs are eligible (module entry points pass
    the is-an-``nn.Parameter`` mask: float BUFFERS like RoPE ``inv_freq``
    or batchnorm running stats must keep full precision under a bf16
    param policy).  Integer/bool outputs are never cast.

    Delegates to :func:`..transport.cast_program_outputs` — the ONE
    cast primitive the transport storage cast also builds on, so the
    cast point (and what XLA fuses it into) can never drift between the
    ``param_dtype`` policy and the low-precision transport."""
    if param_dtype is None:
        return init_fn
    if mask is not None:
        return transport.cast_program_outputs(
            init_fn, [param_dtype if m else None for m in mask]
        )

    def fn(key):
        # Mask-less caller (slot count unknown until trace): every
        # floating output is eligible — same trace-time guard the
        # primitive applies.
        outs = init_fn(key)
        return transport.cast_program_outputs(
            lambda: outs, [param_dtype] * len(outs)
        )()

    return fn


# -- run-stats (bench.py reads these to split gbps into its real phases) ----

_stats_lock = threading.Lock()
_last_run_stats: Dict = {}


def last_run_stats() -> Dict:
    """Phase breakdown of the most recent materialization in this process:
    ``mode`` (monolithic|pipelined), ``n_programs``, ``workers``,
    ``lower_s`` / ``compile_s`` (summed thread-wall time across
    programs), ``execute_s`` (monolithic: device execution; pipelined:
    dispatch plus the residual device wait not hidden behind compiles),
    ``wall_s``, ``overlap`` (busy/wall; >1 means phases genuinely
    overlapped), ``cache`` (outcome → count), the transport-layer
    accounting (``bytes_donated`` — input bytes the commit programs
    consumed via donation; ``transfer_overlap`` — commit/transfer time
    hidden behind other groups' execution ÷ wall, the
    ``tdx.jax.transfer_overlap`` gauge; ``device_put_batches`` —
    per-sharding batched host→device dispatches the resume path
    issued), and — when the compiler probes are available —
    ``xla_flops`` / ``xla_bytes_accessed`` (summed over programs) and
    ``xla_peak_bytes`` (largest single-program device footprint), from
    :func:`..observe.costmodel.program_costs`."""
    with _stats_lock:
        return dict(_last_run_stats)


def _set_run_stats(**kw) -> None:
    with _stats_lock:
        _last_run_stats.clear()
        _last_run_stats.update(kw)


def _cost_stats(costs: Dict) -> Dict:
    """Fold one (or an accumulated) compiler cost record into run-stat
    keys: ``xla_flops`` (summed over programs), ``xla_bytes_accessed``,
    ``xla_peak_bytes`` (max single-program device footprint)."""
    out: Dict = {}
    if costs.get("flops"):
        out["xla_flops"] = costs["flops"]
    if costs.get("bytes_accessed"):
        out["xla_bytes_accessed"] = costs["bytes_accessed"]
    if costs.get("peak_bytes"):
        out["xla_peak_bytes"] = costs["peak_bytes"]
    return out


def _run_init(init_fn, key, out_shardings=None, *, fault_plan=None,
              program_fp=None, tplan=None):
    """Monolithic engine: one program, lower → compile → execute, each
    stage under the self-healing ladder (bounded retries with backoff;
    the final retry bypasses the persistent cache; a deadline-armed
    watchdog abandons a wedged stage).  Exhaustion raises
    :class:`MaterializationError`.

    ``tplan`` is the low-precision transport plan
    (docs/performance.md §transport): when set, ``init_fn`` already
    stores its eligible outputs in the init dtype and the commit/upcast
    program runs after execute (donated per ``TDX_MATERIALIZE_DONATE``;
    a retry whose donated inputs were consumed re-executes the init
    program to regenerate them).

    Returns with the values RESIDENT (block_until_ready) — both engines
    share that contract so "materialized" means landed, the execute span
    and ``last_run_stats`` report true device time, and the pipelined
    overlap accounting stays honest.  Init is a once-per-process path;
    async-dispatch overlap with later host code bought nothing real."""
    from .. import config

    compile_service.bind_cache()
    cfg = config.get()
    retries = max(0, cfg.materialize_retries)
    deadline = cfg.compile_deadline_s or None
    donate = cfg.materialize_donate
    retryable = compile_service.retryable_errors()
    t_wall = time.perf_counter()

    def _attempt(a):
        compiled, t_lower, t_compile, outcome, costs = compile_service.compile_program(
            init_fn, key, out_shardings, fault_plan=fault_plan,
            deadline=deadline,
            bypass_cache=(retries > 0 and a == retries),
            program_fp=program_fp,
        )
        t0 = time.perf_counter()
        with observe.span("jax.execute", category="jax") as esp:
            # The execute stage runs its own per-STAGE ladder, exactly
            # like the pipelined engine's dispatcher; exhausting it is
            # TERMINAL (wrapped non-retryable below) — re-entering the
            # outer compile ladder would recompile an executable that
            # was never the problem and square the documented budget.
            def _produce():
                return compile_service.execute_compiled(
                    compiled, key, 1, deadline=deadline,
                    fault_plan=fault_plan, retries=retries,
                    retryable=retryable,
                )

            try:
                out = _produce()
                donated = 0
                if tplan is not None:
                    out, donated = transport.commit_outputs(
                        out, tplan, donate=donate, producer=_produce,
                        retries=retries, retryable=retryable,
                    )
            except Exception as e:  # noqa: BLE001 — classified below
                if isinstance(e, retryable):
                    raise MaterializationError(
                        f"monolithic execute failed after {retries} "
                        f"retries: {type(e).__name__}: {e}",
                        failed_groups=[0],
                    ) from e
                raise
            esp.block_on(out)
            if donated:
                esp.set(donated_bytes=donated)
        jax.block_until_ready(out)
        return (out, t_lower, t_compile, time.perf_counter() - t0, outcome,
                a, costs, donated)

    try:
        (out, t_lower, t_compile, t_exec, outcome, attempts,
         costs, donated) = compile_service.run_ladder(
            _attempt, retries=retries, retryable=retryable,
            describe="monolithic program", bypass_note=True,
        )
    except Exception as e:  # noqa: BLE001 — classified just below
        if not isinstance(e, retryable):
            raise
        raise MaterializationError(
            f"monolithic init program failed after {retries} "
            f"retries: {type(e).__name__}: {e}",
            failed_groups=[0],
        ) from e
    _set_run_stats(
        mode="monolithic", n_programs=1, workers=1,
        lower_s=t_lower, compile_s=t_compile, execute_s=t_exec,
        wall_s=time.perf_counter() - t_wall,
        overlap=1.0, cache={outcome: 1}, retries=attempts,
        bytes_donated=int(donated), transfer_overlap=0.0,
        device_put_batches=0,
        **(_cost_stats(costs) if costs else {}),
    )
    return out


# -- partial-progress resume -------------------------------------------------
#
# With TDX_MATERIALIZE_RESUME_DIR set, the pipelined engine commits each
# completed group's outputs (raw bytes + CRC32) under the resume dir,
# keyed by a cross-process-stable content fingerprint of the group's
# recorded computation (compile.group_fingerprint + seed / dtype policy /
# sharding).  A rerun after an interrupted materialization loads the
# committed groups from disk instead of re-lowering/compiling/executing
# them; a fully successful materialization clears its progress state.
# Manifest writes are atomic (tmp + rename) and happen only on the
# dispatcher thread.

_RESUME_MANIFEST = "MATERIALIZE_PROGRESS.json"


def _np_dtype(name: str) -> np.dtype:
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes  # bf16 etc. when numpy alone can't resolve it

        return np.dtype(getattr(ml_dtypes, name))


def _load_resume_manifest(rdir: str) -> Dict[str, dict]:
    try:
        with open(os.path.join(rdir, _RESUME_MANIFEST)) as f:
            m = json.load(f)
        if m.get("version") == 1 and isinstance(m.get("groups"), dict):
            return m["groups"]
    except (OSError, ValueError):
        pass
    return {}


def _write_resume_manifest(rdir: str, groups: Dict[str, dict]) -> None:
    path = os.path.join(rdir, _RESUME_MANIFEST)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"version": 1, "groups": groups, "pid": os.getpid(),
                   "time": time.time()}, f)
    os.replace(tmp, path)


def _commit_resume_group(rdir: str, groups: Dict[str, dict], fp: str,
                         idxs: List[int], values: List) -> None:
    """Persist one completed group: outputs first (raw bytes + CRC32),
    then the manifest entry — manifest ⇒ payload, same commit-order
    discipline as checkpoints."""
    gdir = os.path.join(rdir, fp)
    os.makedirs(gdir, exist_ok=True)
    outs = []
    for j, v in enumerate(values):
        arr = np.asarray(v)
        data = arr.tobytes()
        rel = f"out_{j:04d}.bin"
        with open(os.path.join(gdir, rel), "wb") as f:
            f.write(data)
        outs.append({"file": rel, "shape": list(arr.shape),
                     "dtype": str(arr.dtype), "crc32": zlib.crc32(data)})
    groups[fp] = {"indices": list(idxs), "outputs": outs}
    _write_resume_manifest(rdir, groups)


def _try_resume_group(rdir: str, fp: str, rec: dict, idxs: List[int],
                      out_shardings, *,
                      batch_put: bool = True) -> Optional[Tuple[List, int]]:
    """Load one committed group's outputs back onto the devices with
    their planned shardings; None (recompute) on ANY mismatch — wrong
    indices, missing file, CRC failure, bad shape.  Returns
    ``(values, n_device_put_batches)``.

    Transfers go through :func:`..transport.batched_device_put` — ONE
    dispatch per distinct ``NamedSharding`` in the group instead of one
    per array, so resuming a many-leaf group no longer pays per-leaf
    dispatch overhead (``batch_put=False`` keeps the legacy per-leaf
    path as an A/B escape hatch, ``TDX_MATERIALIZE_BATCH_PUT=0``)."""
    if rec.get("indices") != list(idxs):
        return None
    if len(rec.get("outputs") or ()) != len(idxs):
        return None  # truncated manifest entry: a hole, not a resume
    arrs: List[np.ndarray] = []
    try:
        for o in rec["outputs"]:
            with open(os.path.join(rdir, fp, o["file"]), "rb") as f:
                data = f.read()
            if zlib.crc32(data) != o["crc32"]:
                return None
            arr = np.frombuffer(data, dtype=_np_dtype(o["dtype"]))
            arrs.append(arr.reshape(o["shape"]))
    except Exception:  # noqa: BLE001 — any load failure: recompute
        return None
    try:
        if batch_put:
            shardings = (
                [out_shardings[i] for i in idxs]
                if out_shardings is not None else None
            )
            return transport.batched_device_put(arrs, shardings)
        vals: List = []
        for i, arr in zip(idxs, arrs):
            if out_shardings is not None:
                vals.append(jax.device_put(arr, out_shardings[i]))
            else:
                vals.append(jax.numpy.asarray(arr))
        return vals, 0
    except Exception:  # noqa: BLE001 — any reshard failure: recompute
        return None


def _clear_resume_state(rdir: str) -> None:
    """A materialization completed: its progress manifest and committed
    group payloads are spent — remove them so stale outputs can never be
    resumed into a later, different materialization.  Every
    fingerprint-named payload dir is swept, not only manifest-listed
    ones: a dir orphaned by a CRC-failed entry (popped from the
    manifest) or a crash between payload and manifest writes would
    otherwise leak parameter-sized bytes forever."""
    try:
        names = os.listdir(rdir)
    except OSError:
        return
    for name in names:
        p = os.path.join(rdir, name)
        if (len(name) == 40 and all(c in "0123456789abcdef" for c in name)
                and os.path.isdir(p)):
            shutil.rmtree(p, ignore_errors=True)
    try:
        os.remove(os.path.join(rdir, _RESUME_MANIFEST))
    except OSError:
        pass


def _pipeline_workers() -> int:
    """Compile-worker count: TDX_COMPILE_WORKERS, else sized from the
    host (floor 4 — even a small host overlaps async dispatch with
    GIL-free compile; the floor keeps the program split, which wins on
    compile superlinearity alone, from degenerating to one bin)."""
    from .. import config

    w = config.get().compile_workers
    if w > 0:
        return w
    return max(4, min(8, os.cpu_count() or 1))


def _pipeline_max_programs(n_nodes: int) -> int:
    """Program-count target, a function of the RECORDING alone (never of
    the host): finer splits for big recordings — XLA compile is
    superlinear in module size, so large models want small programs
    (~48 nodes each) even when compiles run serially — floored at 8 so
    a worker pool has slack, capped so per-program fixed cost (jit
    dispatch, cache key/put) stays negligible.  Host-independence is a
    contract: ``tools/warm_cache.py`` may warm the cache on a login host
    with a different core count than the consumer, and the warmed
    program set must still match exactly."""
    return min(32, max(8, n_nodes // 48))


# Below this many recorded nodes a model's compile time is dominated by
# fixed per-program overhead (~tens of ms each on CPU), so splitting it
# can only lose; the pipelined engine falls back to the monolith.
_PIPELINE_MIN_NODES = 32


def _plan_pipeline(fake_list) -> Optional[List[List[int]]]:
    """The per-group program split for ``fake_list``, or None when the
    pipelined engine would not help (single group, or model too small)."""
    from .compile import collect_nodes

    nodes = collect_nodes(fake_list)
    if len(nodes) < _PIPELINE_MIN_NODES:
        return None
    bins = split_init_groups(
        fake_list,
        max_programs=_pipeline_max_programs(len(nodes)),
        nodes=nodes,
    )
    return bins if len(bins) >= 2 else None


def _group_fp(fake_list, idxs, out_shardings, param_dtype, cast_mask,
              seed, transport_fp=None) -> Optional[str]:
    """Resume-manifest key for one group: the content fingerprint of its
    recorded computation composed with everything else the output values
    depend on (seed, cast policy, planned shardings, and — when the
    low-precision transport is active — the per-slot storage dtypes,
    whose rounding changes the committed values).  None when a stable
    fingerprint cannot be built (the group is then simply never
    resumed)."""
    import hashlib

    try:
        structural = group_fingerprint([fake_list[i] for i in idxs])
    except Exception:  # noqa: BLE001 — unstable chain: recompute, never skip
        return None
    h = hashlib.sha1(structural.encode())
    for i in idxs:
        osh = out_shardings[i] if out_shardings is not None else None
        h.update(repr((i, seed, str(param_dtype), bool(cast_mask[i]),
                       str(osh))).encode())
    if transport_fp is not None:
        h.update(repr(("transport", transport_fp)).encode())
    return h.hexdigest()


def _transport_plan(fake_list, idxs, out_shardings, param_dtype, cast_mask,
                    init_dtype) -> Optional["transport.TransportPlan"]:
    """The :class:`..transport.TransportPlan` for one program's slots
    (None in default config — the engines then run their bitwise-pinned
    path with zero transport work).  The contract dtype per slot is what
    the DEFAULT path would deliver: ``param_dtype`` where the cast mask
    permits, the recorded dtype otherwise — the fast path changes how
    bytes move, never which dtype lands."""
    if init_dtype is None:
        return None
    import jax.numpy as jnp

    from ._dtypes import jax_dtype

    finals = []
    mask = []
    for i in idxs:
        try:
            d = jnp.dtype(jax_dtype(fake_list[i].dtype))
        except NotImplementedError:
            return None  # exotic dtype in the group: default path
        m = bool(cast_mask[i])
        if (param_dtype is not None and m
                and jnp.issubdtype(d, jnp.floating)):
            d = jnp.dtype(param_dtype)
        finals.append(d)
        mask.append(m)
    osh = (
        [out_shardings[i] for i in idxs]
        if out_shardings is not None else None
    )
    return transport.plan_transport(finals, mask, init_dtype, osh)


def _run_init_pipelined(fake_list, bins, key, out_shardings, param_dtype,
                        cast_mask, *, seed=0, fault_plan=None,
                        init_dtype=None):
    """Pipelined engine: concurrent per-group build/lower/compile on a
    worker pool, execution dispatched as each executable lands through a
    DOUBLE-BUFFERED commit queue (docs/performance.md §transport).

    Workers overlap three ways: Python tracing of group B proceeds while
    group A sits in GIL-free XLA compilation; compiles of several groups
    run truly concurrently on multi-core hosts; and the dispatcher's
    execute of finished groups (async device work) overlaps the remaining
    compiles.  Outputs stream straight into their planned NamedShardings
    — there is no gather or reorder step, each slot is written once.

    Groups with real commit WORK (a low-precision upcast or a resume
    write) enter a bounded in-flight queue of
    ``TDX_MATERIALIZE_OVERLAP_DEPTH`` (default 2) slots: group *k+1*'s
    execution overlaps group *k*'s output commit/transfer, bounding
    transient memory while hiding transfer time — the hidden fraction
    is exported as ``tdx.jax.transfer_overlap`` and each metered
    group's ``jax.commit`` span carries its ``exec_gbps``.  Groups with
    no commit work stay fully asynchronous (default config pays zero
    per-group residency waits).  ``init_dtype`` arms the low-precision
    transport for eligible slots (storage cast inside each group
    program, donated upcast at commit).

    Fault tolerance (docs/robustness.md): each group runs the bounded
    retry ladder (backoff; final retry bypasses the persistent cache)
    with the optional stage watchdog; a group that exhausts its ladder
    marks the run failed, and after the surviving groups land the engine
    raises :class:`MaterializationError` — the caller degrades to the
    monolithic program.  With ``TDX_MATERIALIZE_RESUME_DIR`` set,
    completed groups are committed to a progress manifest as they land
    (fingerprint-keyed; forced resident first), already-committed groups
    from an interrupted run are loaded from disk instead of recompiled,
    and a SIGTERM drains: stop dispatching, commit what finished, raise
    ``MaterializationError(drained=True)``."""
    from .. import config

    log = get_logger()
    compile_service.bind_cache()
    workers = _pipeline_workers()
    results: List = [None] * len(fake_list)
    outcomes: Dict[str, int] = {}
    # The caller's effective config, re-entered on every worker thread:
    # override() scopes are thread-local, and a worker resolving the
    # BASE config instead would break both per-scope telemetry
    # activation and — worse — tracing-time knobs like rng_chunk_elems,
    # whose divergence between engines would break bitwise parity.
    eff_cfg = config.get()
    retries = max(0, eff_cfg.materialize_retries)
    deadline = eff_cfg.compile_deadline_s or None
    depth = max(1, eff_cfg.materialize_overlap_depth)
    donate = eff_cfg.materialize_donate
    batch_put = eff_cfg.materialize_batch_put
    retryable = compile_service.retryable_errors()
    rdir = eff_cfg.materialize_resume_dir
    tplans = [
        _transport_plan(fake_list, idxs, out_shardings, param_dtype,
                        cast_mask, init_dtype)
        for idxs in bins
    ]
    n_put_batches = 0

    manifest: Dict[str, dict] = {}
    fps: List[Optional[str]] = [None] * len(bins)
    resumed: set = set()
    if rdir:
        os.makedirs(rdir, exist_ok=True)
        manifest = _load_resume_manifest(rdir)
        for gi, idxs in enumerate(bins):
            fps[gi] = _group_fp(
                fake_list, idxs, out_shardings, param_dtype, cast_mask,
                seed,
                tplans[gi].fp_material() if tplans[gi] else None,
            )
            rec = manifest.get(fps[gi]) if fps[gi] else None
            if rec is None:
                continue
            loaded = _try_resume_group(rdir, fps[gi], rec, idxs,
                                       out_shardings, batch_put=batch_put)
            if loaded is None:
                manifest.pop(fps[gi], None)  # stale/corrupt: recompute
                continue
            vals, nput = loaded
            n_put_batches += nput
            for i, v in zip(idxs, vals):
                results[i] = v
            resumed.add(gi)
        if resumed:
            observe.counter("tdx.jax.groups_resumed").inc(len(resumed))
            outcomes["resumed"] = len(resumed)
            log.info(
                "materialize: resumed %d/%d committed group(s) from %s",
                len(resumed), len(bins), rdir,
            )

    # SIGTERM drain (announced preemption): stop dispatching, keep the
    # committed progress, raise a resumable MaterializationError.  Only
    # armed when there is a manifest to leave and we own the main
    # thread's signal handling.
    drain = {"requested": False}
    drain_handled = False
    prev_handler = None
    handler_installed = False
    if rdir and threading.current_thread() is threading.main_thread():
        def _on_sigterm(signum, frame):  # noqa: ARG001 — signal signature
            drain["requested"] = True

        prev_handler = signal.signal(signal.SIGTERM, _on_sigterm)
        handler_installed = True

    def build_and_compile(gi: int, idxs: List[int]):
        sub = [fake_list[i] for i in idxs]
        with config.bind(eff_cfg), observe.span(
            "jax.pipeline.group", category="jax", group=gi,
            n_outputs=len(sub),
        ):
            program_fp = (
                _registry_program_fp(
                    fake_list, idxs, out_shardings, param_dtype, cast_mask,
                    tplans[gi].fp_material() if tplans[gi] else None,
                )
                if eff_cfg.registry_dir else None
            )

            def _attempt(a):
                fn = build_init_fn(sub)
                if param_dtype is not None:
                    fn = _cast_outputs(
                        fn, param_dtype, [cast_mask[i] for i in idxs]
                    )
                fn = transport.wrap_storage(fn, tplans[gi])
                osh = (
                    tuple(out_shardings[i] for i in idxs)
                    if out_shardings is not None else None
                )
                return compile_service.compile_program(
                    fn, key, osh, label=gi, fault_plan=fault_plan,
                    deadline=deadline,
                    bypass_cache=(retries > 0 and a == retries),
                    program_fp=program_fp,
                )

            return compile_service.run_ladder(
                _attempt, retries=retries, retryable=retryable,
                describe=f"group {gi} compile", bypass_note=True,
            )

    t_wall = time.perf_counter()
    t_lower = t_compile = t_exec = 0.0
    agg_costs: Dict[str, float] = {}
    failed: Dict[int, BaseException] = {}
    completed: set = set(resumed)
    inflight: List[Dict] = []
    tracker = transport.OverlapTracker()
    bytes_donated = 0

    def _commit_entry(ent) -> None:
        """Commit one in-flight executed group: run the low-precision
        upcast (donated per config), wait for residency, account the
        dispatch→resident rate, then write the resume entry.  Only
        groups with real commit WORK (a transport plan, or a resume
        entry to write) enter this path — a default-config group stays
        fully async and lands at the end barrier, exactly the
        pre-transport behavior.  An async execution failure surfaces at
        the residency wait — classified like any execute failure
        (→ ladder → monolithic fallback), not a crash."""
        nonlocal t_exec, bytes_donated
        gi, idxs = ent["gi"], ent["idxs"]
        outs = ent["outs"]
        t0 = time.perf_counter()
        try:
            with observe.span(
                "jax.commit", category="jax", group=gi
            ) as csp:
                if tplans[gi] is not None:
                    outs, dn = transport.commit_outputs(
                        outs, tplans[gi], donate=donate,
                        producer=ent["producer"], retries=retries,
                        retryable=retryable,
                    )
                    bytes_donated += dn
                    if dn:
                        csp.set(donated_bytes=dn)
                jax.block_until_ready(outs)
                # Dispatch→resident duration vs how long the dispatcher
                # actually WAITED here: the difference is transfer time
                # hidden behind other groups' execution/compiles.
                wait = time.perf_counter() - t0
                dur = time.perf_counter() - ent["t0"]
                hidden = tracker.note(dur, wait)
                nbytes = sum(int(v.size) * v.dtype.itemsize for v in outs)
                csp.set(
                    bytes=nbytes,
                    exec_gbps=nbytes / dur / 1e9 if dur > 0 else 0.0,
                    hidden_s=round(hidden, 4),
                )
        except Exception as e:  # noqa: BLE001 — classified just below
            t_exec += time.perf_counter() - t0
            if not isinstance(e, retryable):
                raise
            failed[gi] = e
            log.error(
                "materialize: group %d failed at commit (%s: %s)",
                gi, type(e).__name__, str(e)[:160],
            )
            return
        t_exec += time.perf_counter() - t0
        for i, v in zip(idxs, outs):
            results[i] = v
        completed.add(gi)
        if rdir and fps[gi]:
            # Residency was forced above; the progress write itself is
            # an OPTIONAL amenity: a full disk, or np.asarray refusing
            # a non-fully-addressable sharded output (multi-host), must
            # cost the resume entry, never the materialization.
            try:
                _commit_resume_group(
                    rdir, manifest, fps[gi], idxs,
                    [results[i] for i in idxs],
                )
            except Exception as e:  # noqa: BLE001
                log.warning(
                    "materialize: progress commit of group %d failed "
                    "(%s: %s); resume will recompute it",
                    gi, type(e).__name__, e,
                )

    try:
        with observe.span(
            "jax.pipeline", category="jax", n_programs=len(bins),
            workers=workers, depth=depth,
        ) as psp:
            pool = ThreadPoolExecutor(
                max_workers=workers, thread_name_prefix="tdx-compile"
            )
            try:
                futs = {
                    pool.submit(build_and_compile, gi, bins[gi]): gi
                    for gi in range(len(bins)) if gi not in resumed
                }
                pending = set(futs)
                while pending and not drain["requested"]:
                    # A short wait timeout (handler armed only) keeps the
                    # dispatcher responsive to a SIGTERM that arrives
                    # while every worker is deep in a long compile.
                    done, pending = _futures_wait(
                        pending,
                        timeout=0.25 if handler_installed else None,
                        return_when=FIRST_COMPLETED,
                    )
                    for fut in done:
                        if drain["requested"]:
                            break
                        gi = futs[fut]
                        idxs = bins[gi]
                        try:
                            compiled, tl, tc, outcome, costs = fut.result()
                        except Exception as e:  # noqa: BLE001
                            if not isinstance(e, retryable):
                                raise
                            failed[gi] = e
                            log.error(
                                "materialize: group %d exhausted its retry "
                                "ladder (%s: %s)", gi, type(e).__name__,
                                str(e)[:160],
                            )
                            continue
                        t_lower += tl
                        t_compile += tc
                        if costs:
                            # flops/bytes sum across programs; peak is the
                            # largest single program (groups execute one at
                            # a time per device at worst, concurrently at
                            # best — max is the honest per-program figure).
                            for k in ("flops", "bytes_accessed"):
                                if costs.get(k):
                                    agg_costs[k] = agg_costs.get(k, 0.0) + costs[k]
                            if costs.get("peak_bytes"):
                                agg_costs["peak_bytes"] = max(
                                    agg_costs.get("peak_bytes", 0.0),
                                    costs["peak_bytes"],
                                )
                        outcomes[outcome] = outcomes.get(outcome, 0) + 1
                        t0 = time.perf_counter()
                        try:
                            with observe.span(
                                "jax.execute", category="jax", group=gi
                            ):
                                # async dispatch; lands sharded
                                outs = compile_service.execute_compiled(
                                    compiled, key, gi + 1,
                                    deadline=deadline, fault_plan=fault_plan,
                                    retries=retries, retryable=retryable,
                                )
                        except Exception as e:  # noqa: BLE001
                            t_exec += time.perf_counter() - t0
                            if not isinstance(e, retryable):
                                raise
                            failed[gi] = e
                            log.error(
                                "materialize: group %d execute exhausted its "
                                "retry ladder (%s: %s)", gi,
                                type(e).__name__, str(e)[:160],
                            )
                            continue
                        t_exec += time.perf_counter() - t0
                        if tplans[gi] is None and not (rdir and fps[gi]):
                            # No commit work: stay fully async (results
                            # land at the end barrier) — forcing a
                            # per-group residency wait here would only
                            # serialize dispatch against device work.
                            for i, v in zip(idxs, outs):
                                results[i] = v
                            completed.add(gi)
                            continue
                        inflight.append({
                            "gi": gi, "idxs": idxs, "outs": outs, "t0": t0,
                            # Idempotent regeneration for the donation
                            # retry ladder: the PRNG key is never donated,
                            # so re-executing the group program is safe.
                            "producer": (
                                lambda c=compiled, g=gi: compile_service.execute_compiled(
                                    c, key, g + 1, deadline=deadline,
                                    fault_plan=fault_plan, retries=retries,
                                    retryable=retryable,
                                )
                            ),
                        })
                        # Double-buffered commit: keep up to `depth`
                        # executed groups in flight, so the NEXT group's
                        # execution overlaps this one's commit/transfer
                        # while transient memory (low-precision staging
                        # plus final buffers) stays bounded.
                        while len(inflight) >= depth:
                            _commit_entry(inflight.pop(0))
            except BaseException:
                pool.shutdown(wait=True, cancel_futures=True)
                raise
            pool.shutdown(wait=True, cancel_futures=drain["requested"])

            # Whatever is still in flight is EXECUTED work — commit it
            # even on a drain: committed progress is what the drain is
            # for, and the devices already paid for these groups.
            while inflight:
                _commit_entry(inflight.pop(0))

            if drain["requested"]:
                drain_handled = True
                observe.flight_dump(
                    "sigterm_drain",
                    completed_groups=sorted(completed), n_groups=len(bins),
                    resumable=bool(rdir),
                )
                raise MaterializationError(
                    f"materialization drained on SIGTERM with "
                    f"{len(completed)}/{len(bins)} groups committed",
                    completed_groups=completed,
                    failed_groups=set(range(len(bins))) - completed,
                    resumable=bool(rdir), drained=True,
                )
            if failed:
                raise MaterializationError(
                    f"{len(failed)} of {len(bins)} init program groups "
                    f"failed after retries: " + "; ".join(
                        f"group {gi}: {type(e).__name__}: {str(e)[:80]}"
                        for gi, e in sorted(failed.items())
                    ),
                    completed_groups=completed, failed_groups=set(failed),
                    resumable=bool(rdir),
                )

            # Groups WITH commit work were forced resident above by the
            # double-buffered drain; async default-config groups and
            # resumed device_puts land at this barrier — execute_s is
            # dispatch plus the per-group commit waits plus this
            # residual.  A device-side failure of an async dispatch
            # surfaces HERE; it must enter the ladder (→ monolithic
            # fallback) as a typed error, not escape raw — which group
            # failed is not attributable at the barrier, so no committed
            # value is trusted.
            t0 = time.perf_counter()
            try:
                jax.block_until_ready(results)
            except Exception as e:  # noqa: BLE001 — classified just below
                if not isinstance(e, retryable):
                    raise
                raise MaterializationError(
                    f"asynchronous execution failure after dispatch: "
                    f"{type(e).__name__}: {e}",
                    completed_groups=(),
                    failed_groups=set(range(len(bins))),
                ) from e
            t_exec += time.perf_counter() - t0
            wall = time.perf_counter() - t_wall
            busy = t_lower + t_compile + t_exec
            overlap = busy / wall if wall > 0 else 1.0
            transfer_overlap = tracker.overlap(wall)
            psp.set(overlap=round(overlap, 3), cache=dict(outcomes),
                    transfer_overlap=transfer_overlap)
            if observe.enabled():
                observe.gauge("tdx.jax.pipeline_overlap").set(
                    round(overlap, 3)
                )
                observe.gauge("tdx.jax.transfer_overlap").set(
                    transfer_overlap
                )
    finally:
        if handler_installed:
            signal.signal(signal.SIGTERM, prev_handler)
            if drain["requested"] and not drain_handled:
                # The notice landed after the last drain check (final
                # device wait, bookkeeping): the materialization is done,
                # but the preemption must not be SWALLOWED — re-deliver
                # it to the just-restored handler (the enclosing
                # application's, e.g. run_elastic's drain, or the
                # default action).
                os.kill(os.getpid(), signal.SIGTERM)
    if rdir:
        _clear_resume_state(rdir)  # success: the progress is spent
    _set_run_stats(
        mode="pipelined", n_programs=len(bins), workers=workers,
        lower_s=t_lower, compile_s=t_compile, execute_s=t_exec,
        wall_s=wall, overlap=round(overlap, 3), cache=outcomes,
        bytes_donated=int(bytes_donated),
        transfer_overlap=transfer_overlap,
        device_put_batches=n_put_batches,
        **(_cost_stats(agg_costs) if agg_costs else {}),
    )
    return tuple(results)


def _materialize_values(fake_list, out_shardings, seed, param_dtype,
                        cast_mask):
    """The ONE instrumented materialization core both public entry points
    share: engine selection (monolithic vs pipelined), the
    ``jax.materialize`` span, bytes / GB/s accounting, and the last rung
    of the degradation ladder — a pipelined run whose groups exhausted
    their retries falls back to the monolithic off-mode program (bitwise
    identical by construction) before a typed
    :class:`MaterializationError` is allowed to escape."""
    from .. import config

    t0 = time.perf_counter()
    with observe.span(
        "jax.materialize", category="jax", n_outputs=len(fake_list),
        backend=jax.default_backend() if observe.enabled() else None,
    ) as sp, gc_paused():
        mode = config.get().materialize_pipeline
        if mode not in ("off", "auto"):
            raise ValueError(
                f"TDX_MATERIALIZE_PIPELINE={mode!r}: expected 'off' or 'auto'"
            )
        # Pinned ONCE on the caller's thread: a thread-local
        # tdx_config.override(fault_plan=...) scope must bind even though
        # the lower/compile sites fire on pool worker threads.
        fault_plan = chaos.active_plan()
        bins = _plan_pipeline(fake_list) if mode == "auto" else None
        key = jax.random.PRNGKey(seed)
        init_dtype = transport.resolve_init_dtype(
            config.get().materialize_init_dtype
        )

        def _whole_fp(tplan=None):
            # The whole-model program's registry fingerprint — computed
            # only when a registry is configured (a full graph walk).
            if not config.get().registry_dir:
                return None
            return _registry_program_fp(
                fake_list, list(range(len(fake_list))), out_shardings,
                param_dtype, cast_mask,
                tplan.fp_material() if tplan is not None else None,
            )

        try:
            values = _run_engines(
                fake_list, bins, key, out_shardings, seed, param_dtype,
                cast_mask, fault_plan, _whole_fp, init_dtype,
            )
        except MaterializationError as e:
            # The whole ladder is spent and the error is about to escape
            # to the application: persist the post-mortem ring now.  A
            # SIGTERM drain already dumped (reason=sigterm_drain) inside
            # the engine — don't double-report a survived preemption as
            # a failure.
            if not e.drained:
                observe.flight_dump(
                    "materialization_error", error=str(e)[:400],
                    failed_groups=list(e.failed_groups),
                    completed_groups=list(e.completed_groups),
                    resumable=e.resumable,
                )
            raise
        if observe.enabled():
            # Both engines block before returning, so this is a
            # bookkeeping pass, not a second sync.
            n_bytes = sum(int(v.size) * v.dtype.itemsize for v in values)
            dt = time.perf_counter() - t0
            gbps = n_bytes / dt / 1e9  # unrounded: toy models are ~1e-6
            sp.set(bytes=n_bytes, gbps=gbps)
            observe.counter("tdx.jax.bytes_materialized").inc(n_bytes)
            observe.gauge("tdx.jax.materialize_gbps").set(gbps)
            # The ROADMAP's gap headline needs a denominator: report the
            # achieved rate as a fraction of what this host→device link
            # measures end to end.  Cached-only: probing HERE would run
            # the device_puts inside the open span (and inside bench's
            # timed region on the first call), skewing both — bench
            # probes after its timed region, warming the cache.
            lbw = observe.costmodel.link_bandwidth_gbps(cached_only=True)
            if lbw:
                util = gbps / lbw
                sp.set(link_bandwidth_gbps=round(lbw, 3),
                       link_utilization=util)
                observe.gauge("tdx.jax.link_utilization").set(util)
    return values


def _run_engines(fake_list, bins, key, out_shardings, seed, param_dtype,
                 cast_mask, fault_plan, _whole_fp, init_dtype=None):
    """Engine selection + the monolithic-fallback rung, extracted from
    :func:`_materialize_values` so the failure-dump wrapper there reads
    straight-line."""
    from .. import config

    def _monolith_fn_and_plan():
        tplan = _transport_plan(
            fake_list, range(len(fake_list)), out_shardings, param_dtype,
            cast_mask, init_dtype,
        )
        fn = transport.wrap_storage(
            _cast_outputs(build_init_fn(fake_list), param_dtype, cast_mask),
            tplan,
        )
        return fn, tplan

    if bins is None:
        init_fn, tplan = _monolith_fn_and_plan()
        return _run_init(init_fn, key, out_shardings,
                         fault_plan=fault_plan,
                         program_fp=_whole_fp(tplan), tplan=tplan)
    try:
        return _run_init_pipelined(
            fake_list, bins, key, out_shardings, param_dtype,
            cast_mask, seed=seed, fault_plan=fault_plan,
            init_dtype=init_dtype,
        )
    except MaterializationError as e:
        if e.drained:
            raise  # preemption: no fallback, the progress is saved
        observe.counter("tdx.jax.pipeline_fallbacks").inc()
        observe.instant(
            "jax.pipeline_fallback", category="jax",
            failed_groups=list(e.failed_groups),
        )
        get_logger().error(
            "materialize: pipelined engine failed (%s); falling "
            "back to the monolithic program", e,
        )
        init_fn, tplan = _monolith_fn_and_plan()
        try:
            values = _run_init(init_fn, key, out_shardings,
                               fault_plan=fault_plan,
                               program_fp=_whole_fp(tplan), tplan=tplan)
        except MaterializationError as e2:
            # The whole ladder is spent; surface the pipelined
            # run's partial progress so a rerun can resume it.
            e2.completed_groups = e.completed_groups
            e2.failed_groups = e.failed_groups
            e2.resumable = e.resumable
            raise
        rdir = config.get().materialize_resume_dir
        if rdir:
            _clear_resume_state(rdir)  # monolith delivered it all
        return values


def named_fake_tensors(module: torch.nn.Module) -> Dict[str, torch.Tensor]:
    """All fake parameters and buffers of ``module`` by qualified name,
    deduplicated by identity (tied weights appear once, under their first
    name)."""
    out: Dict[str, torch.Tensor] = {}
    seen: Dict[int, str] = {}
    for name, t in _named_entries(module):
        if t is None or not is_fake(t):
            continue
        if id(t) in seen:
            continue
        seen[id(t)] = name
        out[name] = t
    return out


def _named_entries(module: torch.nn.Module) -> Iterator[Tuple[str, torch.Tensor]]:
    yield from module.named_parameters(remove_duplicate=False)
    yield from module.named_buffers(remove_duplicate=False)


def _names_and_shardings(
    fakes: Dict[str, torch.Tensor],
    mesh: Optional[Mesh],
    plan: Optional[ShardingPlan],
):
    """(names, fake_list, out_shardings) for a fake dict — the single
    place the plan-to-NamedSharding mapping lives, so lowered, live, and
    pipelined materialization can never diverge."""
    names = list(fakes.keys())
    fake_list = [fakes[n] for n in names]
    out_shardings = None
    if mesh is not None:
        plan = plan or ShardingPlan()
        out_shardings = plan.shardings_for(
            names, [tuple(f.shape) for f in fake_list], mesh
        )
    return names, fake_list, out_shardings


def _init_and_shardings(
    fakes: Dict[str, torch.Tensor],
    mesh: Optional[Mesh],
    plan: Optional[ShardingPlan],
):
    """Shared plumbing: (names, init_fn, out_shardings) for a fake dict —
    the monolithic program the export/lowering paths ship."""
    names, fake_list, out_shardings = _names_and_shardings(fakes, mesh, plan)
    return names, build_init_fn(fake_list), out_shardings


def materialize_params_jax(
    fakes: Dict[str, torch.Tensor],
    *,
    mesh: Optional[Mesh] = None,
    plan: Optional[ShardingPlan] = None,
    seed: int = 0,
    param_dtype=None,
) -> Dict[str, jax.Array]:
    """Materialize a dict of fake tensors as (sharded) jax.Arrays.

    One or several XLA programs (see the engine note in the module
    docstring) compute all requested tensors; with ``mesh`` + ``plan``
    each output lands directly in device memory with its planned
    ``NamedSharding``.  RNG uses per-op keys (fold_in of ``seed`` and the
    recorded op number), so results are independent of sharding layout,
    program split, and materialization order.

    ``param_dtype`` (e.g. ``jnp.bfloat16``) casts floating
    ``nn.Parameter`` entries inside the compiled program — init
    statistics are computed at recorded precision, parameter storage is
    ``param_dtype``, and the full-precision values never exist in device
    memory.  Buffers (float or otherwise) keep their recorded dtype:
    RoPE ``inv_freq`` / batchnorm running stats must stay full precision
    under a bf16 param policy.
    """
    # Tracing/interpreting the graph allocates like recording does
    # (Box/lens objects, jaxpr eqns); same GC pause, same rationale.
    names, fake_list, out_shardings = _names_and_shardings(fakes, mesh, plan)
    mask = [isinstance(fakes[n], torch.nn.Parameter) for n in names]
    values = _materialize_values(
        fake_list, out_shardings, seed, param_dtype, mask
    )
    return dict(zip(names, values))


def materialize_tensor_jax(
    tensor: torch.Tensor,
    *,
    mesh: Optional[Mesh] = None,
    spec: Optional[PartitionSpec] = None,
    seed: int = 0,
    param_dtype=None,
) -> jax.Array:
    """Materialize one fake tensor as a (sharded) jax.Array.

    Runs through the same instrumented core as the module entry points
    (``jax.materialize`` span, bytes/GB/s accounting, engine selection).
    ``param_dtype`` casts the result inside the compiled program when it
    is floating — the tensor is named explicitly here, so no
    parameter-vs-buffer distinction applies (unlike the module entry
    points, which never cast buffers)."""
    if not is_fake(tensor):
        raise ValueError("`tensor` is not fake; nothing to materialize.")
    out_shardings = None
    if mesh is not None:
        out_shardings = (NamedSharding(mesh, spec or PartitionSpec()),)
    return _materialize_values(
        [tensor], out_shardings, seed, param_dtype, [True]
    )[0]


def lower_init_module(
    module: torch.nn.Module,
    *,
    mesh: Optional[Mesh] = None,
    plan: Optional[ShardingPlan] = None,
    param_dtype=None,
):
    """Trace and *lower* (without compiling or executing) the full sharded
    init program of a deferred-init module.

    Returns ``(lowered, names)``: a ``jax.stages.Lowered`` whose StableHLO
    can be inspected/serialized, and the parameter names its outputs
    correspond to.  This is the host-side half of the north-star workflow
    at any scale: a login host can deferred-init a 70B model (fakes, zero
    storage) and produce the GSPMD-partitioned init program for the pod
    without ever holding a parameter — the step a reference
    (torchdistX) user has no counterpart for.

    ``param_dtype`` changes the exported program's floating PARAMETER
    output dtypes (buffers keep recorded precision), exactly as
    :func:`materialize_module_jax` would — an exported program and a live
    materialization with the same policy produce the same dtypes.

    The PRNG key is a *runtime argument* of the program, not baked in:
    pass it when executing, e.g.
    ``lowered.compile(compiler_options=dict(
    compile_service.INIT_COMPILER_OPTIONS))(jax.random.PRNGKey(seed))`` — the same options
    :func:`materialize_module_jax` uses (low-effort codegen, since init
    programs execute once, and ``xla_allow_excess_precision=False``,
    without which bf16 chains lose bitwise parity with torch replay).
    """
    from .. import config

    fakes = named_fake_tensors(module)
    names, init_fn, out_shardings = _init_and_shardings(fakes, mesh, plan)
    mask = [isinstance(fakes[n], torch.nn.Parameter) for n in names]
    if param_dtype is not None:
        init_fn = _cast_outputs(init_fn, param_dtype, mask)
    # The exported program must be the one a live materialize under the
    # same config would compile — including the low-precision transport
    # storage cast, so warmed caches and export artifacts stay valid
    # when TDX_MATERIALIZE_INIT_DTYPE is armed.
    init_dtype = transport.resolve_init_dtype(
        config.get().materialize_init_dtype
    )
    if init_dtype is not None:
        fake_list = [fakes[n] for n in names]
        init_fn = transport.wrap_storage(
            init_fn,
            _transport_plan(fake_list, range(len(fake_list)), out_shardings,
                            param_dtype, mask, init_dtype),
        )
    jitted = jax.jit(init_fn, out_shardings=out_shardings)
    with observe.span("jax.lower", category="jax", n_outputs=len(names)):
        lowered = jitted.lower(jax.random.PRNGKey(0))
    return lowered, names


def lower_init_groups(
    module: torch.nn.Module,
    *,
    mesh: Optional[Mesh] = None,
    plan: Optional[ShardingPlan] = None,
    param_dtype=None,
    max_programs: Optional[int] = None,
):
    """Per-group lowered init programs — the exact program set the
    pipelined engine will compile for this module under the current
    config (same split policy, same out_shardings, same cast masks).

    Yields ``(lowered, names)`` per group.  ``tools/warm_cache.py``
    compiles these (plus the whole-model program) into the persistent
    cache on a login host so pod-scale cold starts become cache hits;
    returns an empty list when the model is below the pipeline threshold
    (the engine would run monolithic — warm that via
    :func:`lower_init_module`)."""
    from .. import config

    fakes = named_fake_tensors(module)
    names, fake_list, out_shardings = _names_and_shardings(fakes, mesh, plan)
    mask = [isinstance(fakes[n], torch.nn.Parameter) for n in names]
    init_dtype = transport.resolve_init_dtype(
        config.get().materialize_init_dtype
    )
    if max_programs is None:
        bins = _plan_pipeline(fake_list)
    else:
        bins = split_init_groups(fake_list, max_programs=max_programs)
        if len(bins) < 2:
            bins = None
    out = []
    key = jax.random.PRNGKey(0)
    for idxs in bins or []:
        fn = build_init_fn([fake_list[i] for i in idxs])
        if param_dtype is not None:
            fn = _cast_outputs(fn, param_dtype, [mask[i] for i in idxs])
        # Same storage-cast decision the pipelined engine makes for this
        # group under the current config (warm_cache parity).
        fn = transport.wrap_storage(
            fn,
            _transport_plan(fake_list, idxs, out_shardings, param_dtype,
                            mask, init_dtype),
        )
        osh = (
            tuple(out_shardings[i] for i in idxs)
            if out_shardings is not None else None
        )
        jitted = (
            jax.jit(fn, out_shardings=osh) if osh is not None else jax.jit(fn)
        )
        with observe.span(
            "jax.lower", category="jax", n_outputs=len(idxs)
        ):
            out.append((jitted.lower(key), [names[i] for i in idxs]))
    return out


def materialize_module_jax(
    module: torch.nn.Module,
    *,
    mesh: Optional[Mesh] = None,
    plan: Optional[ShardingPlan] = None,
    seed: int = 0,
    param_dtype=None,
) -> Dict[str, jax.Array]:
    """Materialize every fake parameter/buffer of a deferred-init torch
    module directly into sharded device memory, returning a flat state
    dict of jax.Arrays (tied weights share one array, listed once).

    This is the TPU counterpart of the reference's
    ``materialize_module`` + FSDP ``param_init_fn`` flow: the torch module
    stays fake (zero host storage); the *values* live sharded on the mesh.
    """
    fakes = named_fake_tensors(module)
    if not fakes:
        return {}
    return materialize_params_jax(
        fakes, mesh=mesh, plan=plan, seed=seed, param_dtype=param_dtype
    )
