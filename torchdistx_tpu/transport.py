"""Streaming materialize transport (docs/performance.md §The transport
layer): batched per-sharding ``device_put``, donated commit buffers, and
the opt-in low-precision init fast path.

The materialization engines already stream group outputs straight into
their planned ``NamedSharding``s; this module owns everything that moves
or re-types those bytes afterwards:

* :func:`batched_device_put` — coalesce per-leaf host→device transfers
  into ONE ``jax.device_put`` dispatch per distinct sharding (the resume
  path used to pay one Python dispatch per array);
* :func:`plan_transport` / :func:`commit_outputs` — the
  ``TDX_MATERIALIZE_INIT_DTYPE`` fast path: slots the parameter
  cast-mask permits are computed and stored by the init program in the
  init dtype (e.g. bf16 — XLA fuses the cast into the producers, so the
  full-precision values never land in device memory and the bytes the
  program writes are halved), then upcast to their contract dtype on
  device by a donated-buffer commit program.  With donation
  (``TDX_MATERIALIZE_DONATE``, default on) pass-through slots alias
  their input buffer (zero-copy, pinned by pointer equality in
  tests/test_materialize_transport.py) and spent low-precision staging
  buffers are freed at consumption instead of lingering until GC.

Donation interacts with the self-healing retry ladder
(docs/robustness.md): a donated buffer consumed by a failed attempt
cannot be fed to the retry — :func:`commit_outputs` re-runs the
producer program to regenerate its inputs, and the FINAL retry compiles
a non-donating commit program so a failure mode tied to donation can
never exhaust every attempt.

Parity contract: the commit program is a pure per-slot ``astype``, so
where the contract dtype already equals the init dtype (a bf16-recorded
graph, or ``param_dtype=bf16``) the fast path is exact-bitwise against
the default path; anywhere an f32 contract rides a bf16 transport the
values are the bf16-rounded defaults (documented tolerance — see
docs/performance.md).  The default configuration never enters this
module's cast paths at all, so the engines' off↔auto bitwise guarantee
is untouched.
"""

from __future__ import annotations

import threading
import warnings
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from . import observe

__all__ = [
    "TransportPlan",
    "batched_device_put",
    "cast_program_outputs",
    "commit_outputs",
    "plan_transport",
    "resolve_init_dtype",
]

_INIT_DTYPE_ALIASES = {
    "bf16": "bfloat16",
    "f16": "float16",
    "fp16": "float16",
    "f32": "float32",
    "fp32": "float32",
}


def resolve_init_dtype(name: Optional[str]):
    """The jnp dtype named by ``TDX_MATERIALIZE_INIT_DTYPE`` (aliases
    ``bf16``/``f16``/``fp16`` accepted), or None when unset.  A name
    that is not a floating dtype is a configuration error, not a
    degrade."""
    if not name:
        return None
    try:
        dt = jnp.dtype(_INIT_DTYPE_ALIASES.get(name.lower(), name))
    except TypeError:
        raise ValueError(
            f"TDX_MATERIALIZE_INIT_DTYPE={name!r} is not a dtype name "
            f"(expected e.g. 'bf16')"
        ) from None
    if not jnp.issubdtype(dt, jnp.floating):
        raise ValueError(
            f"TDX_MATERIALIZE_INIT_DTYPE={name!r}: the init fast path "
            f"only applies to floating dtypes"
        )
    return dt


class TransportPlan:
    """Per-program transport decisions: which output slots the init
    program stores in the low-precision init dtype (``storage[i]``,
    None = keep the contract dtype) and what each slot's contract dtype
    is (``final[i]`` — what the default path would deliver).  Built by
    :func:`plan_transport`; None means the program has no transport
    work and the engines run their default path untouched."""

    __slots__ = ("final", "storage", "out_shardings")

    def __init__(self, final, storage, out_shardings):
        self.final = tuple(final)
        self.storage = tuple(storage)
        self.out_shardings = (
            tuple(out_shardings) if out_shardings is not None else None
        )

    @property
    def converts(self) -> bool:
        return any(s is not None for s in self.storage)

    def fp_material(self) -> Optional[tuple]:
        """What of this plan must enter a program/resume fingerprint:
        the per-slot storage dtypes (they change both the compiled
        program and — under tolerance — the produced values).  None when
        the plan converts nothing (fingerprints must stay byte-stable
        with the pre-transport ones in default config)."""
        if not self.converts:
            return None
        return tuple(str(s) if s is not None else None for s in self.storage)


def plan_transport(final_dtypes, cast_mask, init_dtype,
                   out_shardings=None) -> Optional[TransportPlan]:
    """Build the :class:`TransportPlan` for one program's output slots.

    A slot rides the low-precision transport only when the cast mask
    permits it (same mask as ``param_dtype``: parameters, never
    buffers), its contract dtype is floating, and the init dtype is
    actually NARROWER — an f16/bf16 contract under a bf16 init dtype is
    left alone (equal width: nothing to save, and a cross-16-bit-format
    hop would silently change values).  Returns None when no slot
    qualifies (or ``init_dtype`` is None): the engines then run their
    default, bitwise-guaranteed path with zero added work."""
    if init_dtype is None:
        return None
    idt = jnp.dtype(init_dtype)
    final = [jnp.dtype(d) for d in final_dtypes]
    storage = [
        idt
        if m and jnp.issubdtype(d, jnp.floating) and d.itemsize > idt.itemsize
        else None
        for d, m in zip(final, cast_mask)
    ]
    if not any(s is not None for s in storage):
        return None
    return TransportPlan(final, storage, out_shardings)


def cast_program_outputs(
    init_fn: Callable[..., Tuple[jax.Array, ...]],
    dtypes: Sequence[Optional[Any]],
) -> Callable[..., Tuple[jax.Array, ...]]:
    """Wrap an init program so output slot *i* is cast to ``dtypes[i]``
    INSIDE the compiled program (None keeps the slot's traced dtype;
    non-floating slots are never cast).  The torch-bridge cast policies
    — ``param_dtype`` storage (``jax_bridge.materialize._cast_outputs``)
    and the low-precision init fast path (:func:`wrap_storage`,
    docs/performance.md §transport) — both build on this one
    primitive, so the cast point, and therefore what XLA fuses it into,
    is identical across the monolithic engine, the pipelined engine,
    and the export path."""
    if not any(d is not None for d in dtypes):
        return init_fn
    dts = tuple(dtypes)

    def fn(*args):
        outs = init_fn(*args)
        return tuple(
            o.astype(d)
            if d is not None and jnp.issubdtype(o.dtype, jnp.floating)
            else o
            for o, d in zip(outs, dts)
        )

    return fn


def wrap_storage(init_fn: Callable, plan: Optional[TransportPlan]):
    """Apply the plan's storage cast to an init program (a no-op wrapper
    for a None plan) — the per-slot ``astype`` lands INSIDE the compiled
    program via :func:`cast_program_outputs`, so XLA fuses it
    into the producing ops and full-precision values never reach the
    output buffers."""
    if plan is None:
        return init_fn
    return cast_program_outputs(init_fn, plan.storage)


# -- batched per-sharding device_put ------------------------------------------


def _nbytes(a) -> int:
    try:
        return int(a.size) * a.dtype.itemsize
    except Exception:  # noqa: BLE001 — exotic leaf: don't break accounting
        return 0


def batched_device_put(arrays: Sequence, shardings=None, *,
                       donate: bool = False) -> Tuple[List, int]:
    """Transfer ``arrays`` with ONE ``jax.device_put`` dispatch per
    distinct sharding instead of one per array; returns
    ``(values_in_input_order, n_batches)`` and counts each dispatch in
    ``tdx.jax.device_put_batches``.

    ``shardings`` is a matching sequence of shardings (or None: one
    batch to the default device).  ``donate`` consumes device-array
    sources (host numpy sources are never donated — there is no device
    buffer to reclaim); it is applied per batch only when every member
    is a committed ``jax.Array``, so a mixed batch degrades to a copy,
    never an error."""
    arrays = list(arrays)
    if not arrays:
        return [], 0
    if shardings is None:
        vals = jax.device_put(arrays)
        observe.counter("tdx.jax.device_put_batches").inc()
        return list(vals), 1
    if len(shardings) != len(arrays):
        raise ValueError(
            f"batched_device_put: {len(arrays)} arrays but "
            f"{len(shardings)} shardings"
        )
    groups: dict = {}
    order: List = []
    for i, sh in enumerate(shardings):
        if sh not in groups:
            groups[sh] = []
            order.append(sh)
        groups[sh].append(i)
    out: List = [None] * len(arrays)
    for sh in order:
        idxs = groups[sh]
        batch = [arrays[i] for i in idxs]
        kw = {}
        if donate and all(isinstance(a, jax.Array) for a in batch):
            kw["donate"] = True
        try:
            vals = jax.device_put(batch, sh, **kw)
        except TypeError:
            # A jax without the donate kwarg: plain transfer.
            vals = jax.device_put(batch, sh)
        for i, v in zip(idxs, vals):
            out[i] = v
        observe.counter("tdx.jax.device_put_batches").inc()
    return out, len(order)


# -- the donated commit/upcast program ----------------------------------------
#
# One compiled program per (shapes, src dtypes, dst dtypes, shardings,
# donate) signature, cached for the life of the process: a repeated
# materialization of the same model reuses the commit executables like
# any other program.  The first invocation of a donating signature runs
# under a warning filter: slots whose source and destination byte widths
# differ cannot alias their donated buffer, and XLA's "Some donated
# buffers were not usable" is expected there, not actionable.

_commit_cache: dict = {}
_commit_lock = threading.Lock()


def _commit_program(shapes, src_dtypes, dst_dtypes, out_shardings, donate):
    key = (
        tuple(shapes),
        tuple(str(d) for d in src_dtypes),
        tuple(str(d) for d in dst_dtypes),
        None if out_shardings is None else tuple(str(s) for s in out_shardings),
        bool(donate),
    )
    with _commit_lock:
        ent = _commit_cache.get(key)
        if ent is None:
            dst = tuple(jnp.dtype(d) for d in dst_dtypes)

            def fn(*xs):
                return tuple(x.astype(d) for x, d in zip(xs, dst))

            kw = {}
            if out_shardings is not None:
                kw["out_shardings"] = tuple(out_shardings)
            if donate:
                kw["donate_argnums"] = tuple(range(len(dst)))
            ent = {"fn": jax.jit(fn, **kw), "warmed": False,
                   "lock": threading.Lock()}
            _commit_cache[key] = ent
    return ent, ent["fn"]


def commit_outputs(outs: Sequence, plan: TransportPlan, *,
                   donate: bool, producer: Optional[Callable] = None,
                   retries: int = 0, retryable: tuple = ()):
    """Run one program's outputs through the commit/upcast program,
    blocking until the final values are resident; returns
    ``(final_outs, donated_bytes)``.

    With ``donate``, the commit program consumes ALL slots: converting
    slots (init-dtype → contract dtype) free their staging buffer at
    consumption, pass-through slots alias theirs (zero-copy).  Without
    it, only converting slots enter the program and pass-through slots
    are returned untouched (routing them through would buy a copy).

    Retry ladder: a retryable failure re-attempts up to ``retries``
    times.  If the failed attempt already consumed donated inputs they
    cannot be fed again — ``producer`` (the init program re-execute,
    idempotent: its PRNG key is never donated) regenerates them — and
    the final retry uses a non-donating commit program, so donation
    itself can never be the reason every rung fails."""
    conv = [i for i, s in enumerate(plan.storage) if s is not None]
    if not conv:
        return tuple(outs), 0
    outs = list(outs)
    attempt = 0
    while True:
        use_donate = donate and not (retries > 0 and attempt >= retries)
        try:
            if any(
                getattr(o, "is_deleted", None) and o.is_deleted()
                for o in outs
            ):
                if producer is None:
                    raise RuntimeError(
                        "commit retry: donated inputs were consumed and no "
                        "producer is available to regenerate them"
                    )
                outs = list(producer())
            idxs = list(range(len(outs))) if use_donate else conv
            sub = [outs[i] for i in idxs]
            src = [plan.storage[i] or plan.final[i] for i in idxs]
            ent, fn = _commit_program(
                [tuple(o.shape) for o in sub], src,
                [plan.final[i] for i in idxs],
                None if plan.out_shardings is None
                else [plan.out_shardings[i] for i in idxs],
                use_donate,
            )
            if use_donate and not ent["warmed"]:
                # Per-ENTRY lock: only the first call of this donating
                # signature runs under the warnings filter (the
                # "donated buffers were not usable" compile warning is
                # expected for width-changing slots); an unrelated
                # signature's commit never waits on it.  catch_warnings
                # touches process-global filter state — a concurrent
                # warm of a different signature may leak or eat one
                # warning, which is cosmetic.
                with ent["lock"]:
                    if not ent["warmed"]:
                        with warnings.catch_warnings():
                            warnings.filterwarnings(
                                "ignore", message=".*donated buffers.*"
                            )
                            res = fn(*sub)
                        ent["warmed"] = True
                    else:
                        res = fn(*sub)
            else:
                res = fn(*sub)
            jax.block_until_ready(res)
            donated = 0
            if use_donate:
                donated = sum(
                    _nbytes(o) for o in sub
                    if getattr(o, "is_deleted", None) and o.is_deleted()
                )
                if donated:
                    observe.counter("tdx.jax.bytes_donated").inc(donated)
            final = list(outs)
            for i, v in zip(idxs, res):
                final[i] = v
            return tuple(final), donated
        except Exception as e:  # noqa: BLE001 — classified just below
            if not isinstance(e, retryable) or attempt >= retries:
                raise
            attempt += 1
            observe.counter("tdx.jax.commit_retries").inc()
            observe.instant(
                "jax.commit_retry", category="jax", attempt=attempt,
                error=f"{type(e).__name__}: {e}"[:160],
            )


def commit_cache_clear() -> None:
    """Drop the process-wide commit-program cache (tests)."""
    with _commit_lock:
        _commit_cache.clear()


# -- execute↔transfer overlap accounting --------------------------------------


class OverlapTracker:
    """Accounting for the double-buffered dispatcher: per METERED group
    (one with real commit work — an upcast or a resume write) it records
    the dispatch→resident duration and how much of it the dispatcher
    actually WAITED (blocked) for — the difference is the group's
    execute+commit pipeline time hidden behind other groups' work.
    ``overlap()`` is that hidden time ÷ wall, the
    ``tdx.jax.transfer_overlap`` gauge; per-group durations sum, so a
    value over 1 means several groups' pipelines overlapped.  Groups
    with no commit work never enter the tracker (they stay fully async),
    so a default-config run reports 0, never a phantom overlap."""

    __slots__ = ("hidden_s", "wait_s", "n")

    def __init__(self):
        self.hidden_s = 0.0
        self.wait_s = 0.0
        self.n = 0

    def note(self, dur_s: float, wait_s: float) -> float:
        hidden = max(0.0, dur_s - wait_s)
        self.hidden_s += hidden
        self.wait_s += wait_s
        self.n += 1
        return hidden

    def overlap(self, wall_s: float) -> float:
        if wall_s <= 0:
            return 0.0
        return round(self.hidden_s / wall_s, 3)
