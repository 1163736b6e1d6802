"""Deterministic fault injection for the elastic training stack.

The reference torchdistx is fail-fast by design (SURVEY.md §5: "Failure
detection: ABSENT"); this subsystem exists so that every failure mode the
recovery stack (:mod:`torchdistx_tpu.utils.failures`) claims to handle can
be *injected on demand* and proven survived — in CI, on CPU, bit-for-bit
deterministically.  Fault plans are keyed by step and site; see
:mod:`.plan` for the grammar and :doc:`docs/robustness` for the failure
model.

Activation, in precedence order:

1. programmatic — ``chaos.install(chaos.parse_plan("step@4=raise"))``
   (or pass the text straight to :func:`install`);
2. config — ``TDX_FAULT_PLAN`` / ``tdx_config.override(fault_plan=...)``,
   parsed lazily and cached per plan string.

Injection points call :func:`maybe_inject`, which is a cheap no-op when
no plan is active — production code pays one attribute read and one
config read per site.

Fault kinds and what they model:

===========  ==========================================================
``raise``    an ``XlaRuntimeError`` mid-step — the shape TPU chip loss
             and un-announced preemption surface as
``hang``     a step that never returns — the wedged-chip mode a raised
             exception can never represent (round 5's VERDICT saw the
             accelerator wedge for an entire round)
``corrupt``  post-commit checkpoint damage (truncate or bit-flip) — the
             half-written / bit-rotted checkpoint a naive resume crashes
             on; at the materialization sites (``lower`` / ``compile`` /
             ``execute`` / ``cache``) it damages the persistent XLA
             compile-cache entries on disk instead (the poisoned-cache
             model); at the ``reshard`` site it bit-flips the engine's
             in-flight transfer chunk buffer (the torn-DMA model — no
             file is touched; the reshard verify stage catches it)
``slow``     a save that takes extra seconds — checkpoint latency
             hiding the preemption deadline
``preempt``  SIGTERM to self — the *announced* preemption notice; at the
             ``fleet`` site it kills only the replica THREAD
             (:class:`ReplicaPreempted`), modeling replica loss
``flap``     an INTERMITTENT, RECURRING ``raise`` — the flaky host that
             faults on a duty-cycle fraction of its matches
             (deterministic pattern, never spent; arg = duty cycle in
             ``(0, 1]``, default 0.5).  At the ``fleet`` site the
             replica survives each fault (its batch requeues) so the
             fault keeps recurring — the workload the per-replica
             circuit breaker (docs/serving.md §Guardrails) trips on
===========  ==========================================================

The materialization sites fire inside the record→compile→materialize
pipeline (``lower`` / ``cache`` / ``compile`` / ``execute`` in
:mod:`torchdistx_tpu.compile_service`, under the engines of
:mod:`torchdistx_tpu.jax_bridge.materialize`), keyed by the
1-based program-group number instead of the training step (the
monolithic engine is group 1); see docs/robustness.md.  The
``registry`` site fires inside the shared compile-artifact registry's
fetch and publish operations (:mod:`torchdistx_tpu.registry`), same
group-number keying; ``corrupt`` there damages the published artifacts
(:func:`corrupt_registry_dir`) so the CRC self-verification and
quarantine path is exercised for real.  The ``reshard`` site fires once
per transfer chunk inside :mod:`torchdistx_tpu.reshard` (1-based chunk
number): a failed reshard quarantines nothing and leaves the source
checkpoint untouched — it surfaces as a typed ``ReshardError``
(docs/robustness.md §Resharding).  The ``fleet`` site fires inside a
fleet replica's serving thread, keyed by 1-based replica id (not step):
the controller (:mod:`torchdistx_tpu.serve.fleet`) requeues the dead
replica's requests onto survivors — a replica kill costs latency, never
a token (docs/serving.md §Fleet).
"""

from __future__ import annotations

import threading
from typing import List, Optional, Union

from .inject import (
    InjectedRuntimeError,
    ReplicaPreempted,
    corrupt_cache_dir,
    corrupt_checkpoint,
    corrupt_registry_dir,
    execute,
    execute_replica_fault,
    set_cancel_event,
)
from .plan import KINDS, SITES, Fault, FaultPlan, parse_plan

__all__ = [
    "Fault",
    "FaultPlan",
    "InjectedRuntimeError",
    "KINDS",
    "ReplicaPreempted",
    "SITES",
    "active_plan",
    "clear",
    "corrupt_cache_dir",
    "corrupt_checkpoint",
    "corrupt_registry_dir",
    "execute_replica_fault",
    "install",
    "maybe_inject",
    "parse_plan",
    "set_cancel_event",
]

_lock = threading.Lock()
_installed: Optional[FaultPlan] = None
_env_cache: "tuple[str, FaultPlan] | None" = None  # (plan text, parsed)


def install(plan: Union[FaultPlan, str, None]) -> Optional[FaultPlan]:
    """Set the process-wide fault plan (text is parsed).  ``None`` clears.
    Returns the installed plan."""
    global _installed
    with _lock:
        _installed = parse_plan(plan) if isinstance(plan, str) else plan
        return _installed


def clear() -> None:
    """Remove the installed plan and drop the config-parse cache."""
    global _installed, _env_cache
    with _lock:
        _installed = None
        _env_cache = None


def active_plan() -> Optional[FaultPlan]:
    """The plan injections consult: the installed one, else a cached
    parse of the effective config's ``fault_plan`` text."""
    global _env_cache
    with _lock:
        if _installed is not None:
            return _installed
    from .. import config

    text = config.get().fault_plan
    if not text:
        return None
    with _lock:
        if _env_cache is None or _env_cache[0] != text:
            _env_cache = (text, parse_plan(text))
        return _env_cache[1]


def maybe_inject(
    site: str,
    step: int,
    *,
    path: Optional[str] = None,
    plan: Optional[FaultPlan] = None,
) -> List[Fault]:
    """Fire any faults due at ``(site, step)``; no-op without a plan.

    Returns the faults that fired (after side effects; a ``raise`` fault
    propagates instead of returning).  Call sites pass ``path`` for
    checkpoint-directory faults (``corrupt``).  ``plan`` pins an explicit
    plan — ``run_elastic`` resolves :func:`active_plan` once on its main
    thread and pins it, because a thread-local
    ``tdx_config.override(fault_plan=...)`` scope is invisible to the
    watchdog worker threads the step site executes on."""
    if plan is None:
        plan = active_plan()
    if plan is None:
        return []
    fired = plan.take(site, step)
    for fault in fired:
        execute(fault, path=path)
    return fired
