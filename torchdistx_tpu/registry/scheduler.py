"""Sharded multi-host warm scheduler over the artifact registry.

``tools/warm_cache.py`` used to warm one host's cache by compiling EVERY
init program locally; across a pod that is O(model × hosts) duplicated
compile work.  This scheduler splits the program list across hosts
deterministically — each program's registry key hashes to one *owner*
(:func:`shard_owner`), every host compiles exactly its owned subset and
publishes, then fills the rest from the registry — so a fleet-wide warm
costs O(model / hosts) compile per host plus fetches.

Liveness: a program whose owner never publishes (dead host, wedged
compile) is **stolen** after ``steal_after_s`` — the waiting host
compiles it locally and publishes for everyone else
(``tdx.registry.steals``).  A dead host therefore degrades the warm to
extra local compiles; it can never hang it, and a consumer that starts
before the warm finishes still degrades to PR 5's self-healing local
compile ladder.

Drive it via ``python tools/warm_cache.py --hosts N --host-id i
--registry-dir /shared/registry`` (one invocation per host, any launch
order), or in-process via :func:`warm_sharded`.  With ``hosts=1`` and no
registry it is the plain local warm with per-program outcome reporting.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

from .. import observe
from ..utils.logging import get_logger
from .store import ArtifactRegistry, registry_key

__all__ = [
    "ProgramReport",
    "ProgramSpec",
    "plan_group_specs",
    "shard_owner",
    "warm_sharded",
]


@dataclass
class ProgramSpec:
    """One init program of the warm set: the whole-model program or one
    pipelined group, with its registry address (None when the recording
    has no stable fingerprint — such programs are compiled by every host
    and never published)."""

    name: str                    # "whole" | "group-<gi>"
    idxs: List[int]              # output slots into the model's fake list
    program_fp: Optional[str]
    registry_key: Optional[str]

    @property
    def label(self) -> Optional[int]:
        """The pipelined engine's group label (chaos sites and spans key
        off it; the whole-model program is label None → group 1)."""
        return None if self.name == "whole" else int(self.name.split("-")[1])


@dataclass
class ProgramReport:
    """Per-program outcome of one host's warm.

    ``outcome`` vocabulary: ``published`` (compiled here and published),
    ``compiled`` (compiled here, nothing published — no registry or no
    stable key), ``fetched`` (filled from another host's artifact),
    ``cached`` (the local persistent cache already had it),
    ``stolen`` (owner missed the deadline; compiled here and published),
    ``unwarmed`` (failed — the tool exits non-zero)."""

    program: str
    outputs: int
    outcome: str
    seconds: float
    owner: Optional[int] = None
    cache: Optional[str] = None   # jax compile-cache outcome: hit|miss|...
    error: Optional[str] = None

    def as_dict(self) -> Dict[str, Any]:
        d = {"program": self.program, "outputs": self.outputs,
             "outcome": self.outcome, "seconds": round(self.seconds, 3)}
        if self.owner is not None:
            d["owner"] = self.owner
        if self.cache is not None:
            d["cache"] = self.cache
        if self.error is not None:
            d["error"] = self.error
        return d


def classify_warm_outcome(cache_outcome: str, *, fetched: bool,
                          published: bool) -> str:
    """THE warm-outcome vocabulary (`ProgramReport.outcome`), shared by
    the init-program warm (:func:`warm_sharded`) and the serving warm
    (:func:`...serve.programs.warm_serving`) so their report lines can
    never diverge: a local-cache hit is ``fetched`` only when registry
    bytes actually moved during this compile (else ``cached``); a
    compile is ``published`` only when its artifact is now in the
    registry (else ``compiled``)."""
    if cache_outcome == "hit":
        return "fetched" if fetched else "cached"
    return "published" if published else "compiled"


def shard_owner(key: str, hosts: int) -> int:
    """Deterministic owner of one registry key in ``[0, hosts)`` — a pure
    function of the key, so every host computes the same partition
    regardless of list order, launch order, or process boundaries."""
    return int(key[:8], 16) % max(1, hosts)


def _active_init_dtype():
    """The low-precision transport dtype of the CURRENT config — the
    warm must build (and fingerprint) the exact programs a consumer
    under the same config will request (docs/performance.md
    §transport)."""
    from .. import config as tdx_config
    from .. import transport

    return transport.resolve_init_dtype(
        tdx_config.get().materialize_init_dtype
    )


def _spec_for(name: str, idxs: List[int], fake_list, out_shardings,
              param_dtype, mask, registry_dir: Optional[str],
              init_dtype=None) -> ProgramSpec:
    from ..jax_bridge import materialize as mat

    tplan = mat._transport_plan(fake_list, idxs, out_shardings,
                                param_dtype, mask, init_dtype)
    fp = mat._registry_program_fp(
        fake_list, idxs, out_shardings, param_dtype, mask,
        tplan.fp_material() if tplan is not None else None,
    )
    rk = registry_key(fp) if (fp and registry_dir) else None
    return ProgramSpec(name, list(idxs), fp, rk)


def plan_group_specs(fake_list, out_shardings, param_dtype, mask,
                     registry_dir: Optional[str]) -> List[ProgramSpec]:
    """The per-group program specs the pipelined engine will request for
    this recording under the current config — same split policy, same
    shardings, same cast masks and transport storage dtypes
    (host-independent by contract, exactly like ``lower_init_groups``)."""
    from ..jax_bridge import materialize as mat

    init_dtype = _active_init_dtype()
    bins = mat._plan_pipeline(fake_list) or []
    return [
        _spec_for(f"group-{gi}", idxs, fake_list, out_shardings,
                  param_dtype, mask, registry_dir, init_dtype)
        for gi, idxs in enumerate(bins)
    ]


def warm_sharded(factory, cache_dir: str, *,
                 registry_dir: Optional[str] = None,
                 hosts: int = 1, host_id: int = 0,
                 mesh=None, plan=None, param_dtype=None,
                 skip_whole: bool = False, skip_groups: bool = False,
                 steal_after_s: float = 120.0, poll_s: float = 0.5,
                 seconds_budget: Optional[float] = None) -> dict:
    """Warm this host's persistent cache (and the shared registry) with a
    module factory's init programs; returns a summary dict with
    per-program outcome reports (see :class:`ProgramReport`).

    With ``hosts > 1`` the program list is sharded by
    :func:`shard_owner`: owned programs are compiled and published,
    the rest polled from the registry and stolen past ``steal_after_s``.
    ``seconds_budget`` bounds the fill phase's WAITING (defaults to
    ``steal_after_s`` plus an allowance); the compiles themselves — and
    the registry IO around them — are bounded by the materialization
    watchdog, so arm ``TDX_COMPILE_DEADLINE_S`` when a deployment
    script needs a hard ceiling on the whole warm.
    """
    import jax
    import torch

    from .. import compile_service, transport
    from .. import config as tdx_config
    from ..deferred_init import deferred_init
    from ..jax_bridge import materialize as mat

    if hosts < 1 or not (0 <= host_id < hosts):
        raise ValueError(
            f"host_id must be in [0, hosts); got host_id={host_id} "
            f"hosts={hosts}"
        )
    if hosts > 1 and not registry_dir:
        raise ValueError(
            "a sharded warm (hosts > 1) needs --registry-dir: without a "
            "shared registry the hosts cannot exchange artifacts"
        )

    t0 = time.perf_counter()
    log = get_logger()
    os.makedirs(cache_dir, exist_ok=True)
    reg = ArtifactRegistry(registry_dir) if registry_dir else None
    reports: List[ProgramReport] = []

    module = deferred_init(factory)
    fakes = mat.named_fake_tensors(module)
    names, fake_list, out_shardings = mat._names_and_shardings(
        fakes, mesh, plan
    )
    mask = [isinstance(fakes[n], torch.nn.Parameter) for n in names]
    key = jax.random.PRNGKey(0)

    def owned(spec: ProgramSpec) -> bool:
        # Keyless programs (unstable fingerprint) cannot be exchanged:
        # every host compiles them itself.
        if reg is None or spec.registry_key is None or hosts <= 1:
            return True
        return shard_owner(spec.registry_key, hosts) == host_id

    def compile_spec(spec: ProgramSpec) -> ProgramReport:
        t = time.perf_counter()
        fetches_before = observe.counter("tdx.registry.fetch_hit").value
        fn = mat.build_init_fn([fake_list[i] for i in spec.idxs])
        if param_dtype is not None:
            fn = mat._cast_outputs(
                fn, param_dtype, [mask[i] for i in spec.idxs]
            )
        fn = transport.wrap_storage(
            fn,
            mat._transport_plan(fake_list, spec.idxs, out_shardings,
                                param_dtype, mask, _active_init_dtype()),
        )
        osh = (
            tuple(out_shardings[i] for i in spec.idxs)
            if out_shardings is not None else None
        )
        # compile_program does the whole registry dance when program_fp
        # is set: fetch→verify→install before the compile, publish after
        # — the same path the materialization engines run, including the
        # TDX_COMPILE_DEADLINE_S watchdog over compiles AND registry IO.
        _, _tl, _tc, cache_outcome, _costs = compile_service.compile_program(
            fn, key, osh, label=spec.label,
            program_fp=spec.program_fp if reg is not None else None,
            deadline=tdx_config.get().compile_deadline_s or None,
        )
        outcome = classify_warm_outcome(
            cache_outcome,
            # "fetched" only when bytes actually moved from the registry
            # during THIS compile; a warm local cache reports "cached".
            fetched=(observe.counter("tdx.registry.fetch_hit").value
                     > fetches_before),
            published=bool(reg is not None and spec.registry_key
                           and reg.has(spec.registry_key)),
        )
        return ProgramReport(
            program=spec.name, outputs=len(spec.idxs), outcome=outcome,
            seconds=time.perf_counter() - t,
            owner=(shard_owner(spec.registry_key, hosts)
                   if spec.registry_key else None),
            cache=cache_outcome,
        )

    def run_spec(spec: ProgramSpec, relabel: Optional[str] = None) -> None:
        try:
            rep = compile_spec(spec)
            if relabel and rep.cache != "hit":
                rep.outcome = relabel
        except Exception as e:  # noqa: BLE001 — one bad program ≠ a dead warm
            log.error("warm: program %s failed (%s: %s)", spec.name,
                      type(e).__name__, str(e)[:160])
            rep = ProgramReport(
                program=spec.name, outputs=len(spec.idxs),
                outcome="unwarmed", seconds=0.0,
                owner=(shard_owner(spec.registry_key, hosts)
                       if spec.registry_key else None),
                error=f"{type(e).__name__}: {str(e)[:200]}",
            )
        reports.append(rep)

    with tdx_config.override(
        cache_dir=cache_dir, registry_dir=registry_dir or None
    ):
        compile_service.reset_cache_binding()  # bind THIS cache dir even mid-process
        compile_service.bind_cache()
        try:
            # The whole-model program first (export-path parity; also the
            # interrupted-warm contract: the monolith commits before any
            # group work starts).
            whole: Optional[ProgramSpec] = None
            if not skip_whole:
                whole = _spec_for(
                    "whole", list(range(len(fake_list))), fake_list,
                    out_shardings, param_dtype, mask, registry_dir,
                    _active_init_dtype(),
                )
                if owned(whole):
                    run_spec(whole)
            group_specs = (
                plan_group_specs(fake_list, out_shardings, param_dtype,
                                 mask, registry_dir)
                if not skip_groups else []
            )
            fill: List[ProgramSpec] = []
            if whole is not None and not owned(whole):
                fill.append(whole)
            for spec in group_specs:
                if owned(spec):
                    run_spec(spec)
                else:
                    fill.append(spec)

            # Fill phase: poll for other hosts' artifacts; steal past the
            # deadline so a dead owner degrades to a local compile.
            steal_at = time.monotonic() + max(0.0, steal_after_s)
            budget = seconds_budget if seconds_budget is not None else (
                max(0.0, steal_after_s) + 600.0
            )
            hard_stop = time.monotonic() + budget
            while fill:
                progressed = False
                for spec in list(fill):
                    assert reg is not None and spec.registry_key
                    if reg.has(spec.registry_key):
                        run_spec(spec)
                        fill.remove(spec)
                        progressed = True
                if not fill:
                    break
                now = time.monotonic()
                if now >= steal_at or now >= hard_stop:
                    for spec in fill:
                        log.warning(
                            "warm: stealing %s (owner host %d missed the "
                            "%.1fs deadline)", spec.name,
                            shard_owner(spec.registry_key, hosts),
                            steal_after_s,
                        )
                        run_spec(spec, relabel="stolen")
                        # Counted AFTER the fact: an owner that published
                        # in the window between the last poll and this
                        # compile turns the steal into a plain fetch, and
                        # the telemetry must match the report.
                        if reports[-1].outcome == "stolen":
                            observe.counter("tdx.registry.steals").inc()
                            observe.instant(
                                "registry.steal", category="registry",
                                program=spec.name,
                                owner=shard_owner(spec.registry_key, hosts),
                            )
                    fill = []
                    break
                if not progressed:
                    time.sleep(min(poll_s, max(0.0, steal_at - now)))
        finally:
            compile_service.reset_cache_binding()

    outcomes: Dict[str, int] = {}
    for r in reports:
        outcomes[r.outcome] = outcomes.get(r.outcome, 0) + 1
    try:
        cache_entries = len(os.listdir(cache_dir))
    except OSError:
        cache_entries = 0
    return {
        "programs": sum(1 for r in reports if r.outcome != "unwarmed"),
        "outputs": sum(r.outputs for r in reports
                       if r.outcome != "unwarmed"),
        "cache_entries": cache_entries,
        "seconds": round(time.perf_counter() - t0, 2),
        "backend": jax.default_backend(),
        "cache_dir": cache_dir,
        "registry_dir": registry_dir,
        "hosts": hosts,
        "host_id": host_id,
        "outcomes": outcomes,
        "program_reports": [r.as_dict() for r in reports],
        "unwarmed": [r.program for r in reports if r.outcome == "unwarmed"],
    }
