"""Continuous-batching serve loop with deferred-init replica bring-up.

The inference-serving runtime's control plane.  One :class:`ServeEngine`
is one replica: a fixed-lane decode batch (``ServeConfig.max_batch``), a
paged KV pool (:mod:`.kv_cache`), an admission queue, and the compiled
prefill/decode programs (:mod:`.programs`).  The loop interleaves:

1. **admission** — waiting requests are admitted while a batch lane and
   enough pages for their prompt are free; admission first consults the
   prefix cache (:mod:`.prefix`): the longest cached page-aligned
   prefix's pages are MAPPED into the new sequence's table
   (``alloc_shared`` — zero prefill FLOPs for the reused tokens) and
   only the suffix is prefilled.  A suffix that fits one chunk runs at
   admission (that's still the TTFT point); longer suffixes prefill
   **chunked** — ``ServeConfig.prefill_chunk`` tokens per engine tick,
   interleaved with decode steps — which is also how prompts LARGER
   than the largest prefill bucket serve instead of being rejected;
2. **decode** — ONE batched step for every fully-prefilled lane through
   the decode program (ragged paged attention over each lane's own
   context length); one token per lane per step; mid-prefill lanes sit
   the step out;
3. **retirement** — lanes that hit EOS / their token budget / the
   context cap release their page references *immediately* (a page
   frees when its last reference drops — shared prefix pages survive in
   the cache), so the next step's admission can hand pages to waiting
   requests.  A finished prefill inserts its prompt's full pages into
   the prefix cache first, so later requests with the same preamble
   reuse them.

Shared pages are COPY-ON-WRITE: the only write a grower can aim at a
shared page (recomputing the last prompt position of a fully-cached
page-aligned prompt) first duplicates the page through the compiled
``cow`` program and remaps the grower's table — a cached page's
contents never change while anyone else can read them.  Under pool
pressure the engine EVICTS cache leaves (LRU) before it will preempt a
running lane.

Decode is **speculative** by default (``TDX_SPEC_DECODE=0`` kills it):
a host-side n-gram drafter (:class:`.prefix.NgramDrafter`) fed by
admitted prompts and each lane's own emitted tokens proposes up to
``spec_k`` tokens per lane, and one bucketed ``verify-<k>`` program
call scores all k+1 positions for every lane at once.  Greedy accept
keeps the longest draft prefix matching the verify argmaxes plus one
corrected (or bonus) token; :meth:`PagedKVCache.rollback` retracts the
rejected positions' K/V, so cache state and every emitted token stay
bitwise what plain decode would produce — speculation is purely a
throughput knob (docs/serving.md §Speculative decoding).

A replica with no drafter whose plain tick reads nothing but its tokens
**decodes ahead** (docs/serving.md §Decoding ahead): each step dispatches
its tick, its input tokens merged on the device from the tick before, and
only then reads the tick before and the step's prompt rows, so the host's
part of a tick (emission, retirement, the next admission and tables) runs
while the chip works.  One tick is in flight at most; the token streams are
the synchronous engine's, token for token.

When the pool cannot cover a lane's growth the engine **preempts** the
youngest lane (frees its pages, requeues the whole request at the front
of the queue — greedy decode regenerates it identically), the vLLM
recompute-preemption policy: page exhaustion costs latency, never a
wrong or dropped response.  The chaos ``serve`` site fires at the top of
every step; an injected (or real) runtime fault mid-batch requeues every
active lane the same way.

**Replica bring-up** (:func:`spin_up_replica`) is the deferred-init
story end-to-end: ``abstract.deferred_init`` fakes the model (zero
storage), the init program is compiled through
``compile_service.compile_program`` — so a registry-warmed replica FETCHES
it rather than compiling — and executes straight into (sharded) device
memory; the prefill/decode programs ride the same path.  With
``TDX_REGISTRY_DIR`` pre-warmed (``tools/warm_cache.py --decode``), a
new replica's first token is gated by cache fetches, not XLA compiles
(``make serve-smoke`` pins zero local compiles).

Telemetry (docs/observability.md): ``tdx.serve.tokens_per_s``,
``ttft_s`` / ``queue_wait_s`` / ``token_latency_s`` (histograms),
``queue_depth``, ``kv_pages_in_use`` (from the allocator),
``preempted_requests``, plus ``requests_completed`` / ``prefills`` /
``decode_steps`` / ``attended_tokens`` / ``decode_lane_ticks`` counters
and ``serve.step`` / ``serve.prefill`` / ``serve.spin_up`` spans; inside
a step ``serve.admit``, ``serve.tick.tables``, ``serve.program`` (with
its ``.launch`` and ``.wait`` children), ``serve.tick.d2h``,
``serve.tick.emit`` and ``serve.gauges`` name the host's moments: a
traced tick waits for the device where an untraced one does, no more.
SLOs (docs/observability.md §SLOs): every engine feeds sliding windows
over TTFT, per-token latency, and queue wait
(:class:`~torchdistx_tpu.observe.slo.ServeSLO`), published as
``tdx.serve.slo.*_p{50,95,99}_s`` gauges — live via the periodic
exporter when ``TDX_METRICS_EXPORT_S`` is set.  A step fault or a
preemption also dumps the flight recorder (``TDX_FLIGHT_DIR``), so a
replica that survived a fault leaves the evidence.
"""

from __future__ import annotations

import functools
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import chaos, compile_service, observe, transport
from ..observe import reqledger
from ..models import PRESETS, TransformerConfig, olmo_hybrid
from ..ops import mla as mla_ops
from ..ops.paged_attention import kv_blocks_walked
from ..utils.logging import get_logger
from .kv_cache import (OutOfPages, PagedKVCache, init_pools, init_state,
                       init_window_pool,
                       pool_sharding, state_sharding)
from .prefix import NgramDrafter, PrefixCache
from .programs import (
    ResolvedServeConfig,
    ServeConfig,
    compile_serving_program,
    make_model,
    model_family,
    serve_program_specs,
)

__all__ = ["Request", "ServeEngine", "oracle_generate", "spin_up_replica"]


@jax.jit
def _greedy(logits):
    """The greedy choice of every lane, made on the device beside the
    logits.  ``np.argmax`` of one row of 25,024 floats took about 70 us on
    the chip's host (numpy's float argmax is a scalar loop where the CPU
    lacks AVX-512; 10 us where it has it): 8.7 of the 14 ms of host time
    in a decode tick of 128 lanes, all of it with the device idle
    (PERF.md section 6, PR 34).  First index of the maximum, as numpy's."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


@jax.jit
def _merge(prev, host):
    """The input tokens of a tick dispatched ahead: a lane that decoded in
    the tick before (``host`` -1) takes that tick's greedy token, still on
    the device; a lane whose last token came to the host (through a
    prefill) takes ``host``.  ``[max_batch]`` whatever the traffic, so the
    benchmark's warm-up meets it (docs/serving.md §Decoding ahead)."""
    return jnp.where(host < 0, prev, host)


@jax.jit
def _row(logits, slot):
    """Row ``slot`` of a plain tick's logits.  The slot is an OPERAND: one
    compiled program serves every lane, and a replica's first retirement in
    a decode tick has met it.  A row taken with an index array
    (``logits[np.array(slots)]``) would compile once for every count of
    lanes that retire together, inside a serving window (PERF.md section 6,
    PRs 35 and 36)."""
    return jax.lax.dynamic_index_in_dim(logits, slot, axis=0, keepdims=False)


class _TickRow:
    """One lane's logits of a plain decode tick, left on the device: the
    tick's whole ``[max_batch, vocab]`` array and the lane's slot.  Whoever
    reads it as an array (``np.asarray``: ``_retire``, once a lane) brings
    that one row to the host, bit for bit the row that a fetch of the whole
    array held.  ``row`` is that row when it was cut on the device already,
    right behind its tick (a tick dispatched ahead, for a lane whose budget
    ends with it: cut later, the row would queue behind the next tick)."""

    __slots__ = ("logits", "slot", "row")

    def __init__(self, logits, slot: int, row=None):
        self.logits, self.slot, self.row = logits, slot, row

    def __array__(self, dtype=None, copy=None):
        observe.counter("tdx.serve.logit_rows_fetched").inc()
        row = _row(self.logits, self.slot) if self.row is None else self.row
        return np.asarray(row, dtype)


class _Tick:
    """A plain decode tick dispatched and not read yet (docs/serving.md
    §Decoding ahead): its ``(slot, lane)`` pairs, so that a token read late
    reaches only the lane it was made for; its logits and greedy tokens on
    the device; the rows cut behind it for the lanes whose budget it ends;
    whether it was dispatched while the tick before it was unread
    (``ahead``); and when its tables began (``t0``)."""

    __slots__ = ("lanes", "logits", "tokens", "rows", "ahead", "t0")

    def __init__(self, lanes, ahead: bool, t0: float):
        self.lanes, self.ahead, self.t0 = lanes, ahead, t0
        self.logits = self.tokens = None  # set when the tick is dispatched
        self.rows = {}


@dataclass
class Request:
    """One generation request.  ``arrival_step`` simulates staggered
    arrivals for continuous-batching tests and soaks (a request is not
    admissible before that engine step).

    ``deadline_s`` is an END-TO-END deadline, measured from first
    submission: past it the request is expired while queued AND
    cancelled mid-decode (its lane's pages freed immediately, the
    requester handed a typed ``deadline`` rejection carrying
    tokens-so-far — docs/serving.md §Guardrails).  ``priority`` feeds
    the fleet's brownout (low-priority work is shed under sustained
    pressure); the engine itself treats priorities equally."""

    rid: str
    tokens: List[int]
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    arrival_step: int = 0
    deadline_s: Optional[float] = None
    priority: int = 1


@dataclass
class _Lane:
    """One active batch lane."""

    req: Request
    seq_id: int
    slot: int
    length: int = 0                # tokens currently in the KV cache
    generated: List[int] = field(default_factory=list)
    admitted_step: int = 0
    prefilling: bool = False       # mid-chunked-prefill; decode skips it
    spec_k: int = 0                # current draft length cap (adaptive)
    ahead: int = 0                 # positions of ticks dispatched, unread


class ServeEngine:
    """One serving replica; see the module docstring for the loop."""

    def __init__(
        self,
        family: str,
        cfg: TransformerConfig,
        params,
        *,
        serve_cfg: Optional[ServeConfig] = None,
        mesh=None,
        plan=None,
        seed: int = 0,
        param_dtype=None,
        on_token: Optional[Callable[[str, int], None]] = None,
        on_complete: Optional[Callable[[str, List[int], np.ndarray],
                                       None]] = None,
        on_cancel: Optional[Callable[[str, List[int], bool], None]] = None,
        slo_name: str = "serve",
    ):
        self.family = family
        self.cfg = cfg
        self.params = params
        # Weight-version stamp (checkpoint step + manifest digest) of
        # the params this engine serves; None until a rollover installs
        # versioned weights.  Surfaced on /readyz and the request
        # ledger so a half-rolled fleet is visible at a glance.
        self.weight_version: Optional[str] = None
        self.scfg: ResolvedServeConfig = (serve_cfg or ServeConfig()).resolve(cfg)
        self.mesh, self.plan = mesh, plan
        self._seed, self._param_dtype = seed, param_dtype
        self.on_token = on_token
        self.on_complete = on_complete
        # Deadline-cancellation notifier: (rid, tokens_so_far, was_active)
        # — was_active distinguishes a cancelled LANE (pages were freed
        # mid-decode) from an expired waiting request.
        self.on_cancel = on_cancel
        self.cancelled: Dict[str, List[int]] = {}  # rid -> tokens at cancel
        self._draining = False
        self.kv = PagedKVCache(self.scfg.kv_config(cfg))
        self.prefix = PrefixCache(self.kv)
        self._init_pools()
        # Chunk-boundary chaos faults (``serve@N=raise:chunk``) are
        # deferred here by step() and fired BETWEEN prefill chunks —
        # the mid-chunked-prefill fault the failure matrix pins.
        self._pending_chunk_faults: List[chaos.Fault] = []
        # Same deferral for ``raise:verify`` — fired right before the
        # next speculative verify tick (docs/serving.md §Speculative
        # decoding failure matrix).
        self._pending_verify_faults: List[chaos.Fault] = []
        # Speculative decoding (docs/serving.md §Speculative decoding):
        # a host-side n-gram drafter proposes tokens the batched
        # verify-<k> program checks; greedy accept keeps every output
        # bitwise-oracle, so TDX_SPEC_DECODE=0 trades only throughput.
        self._drafter: Optional[NgramDrafter] = (
            NgramDrafter() if self.scfg.spec_decode else None)
        self.spec_drafted = 0      # draft tokens sent to verify
        self.spec_accepted = 0     # draft tokens accepted
        self.spec_verify_ticks = 0  # batched verify calls
        self._programs: Dict[str, object] = {}
        # name -> how often the compiled program was dispatched (every
        # call site goes through _program): what chip_smoke.py reads to
        # prove each program family executed.
        self.program_calls: Dict[str, int] = {}
        self._spec_cache: Optional[Dict[str, object]] = None
        self.waiting: deque[Request] = deque()
        self.active: Dict[int, _Lane] = {}      # slot -> lane
        self._delivered: Dict[str, int] = {}    # rid -> tokens streamed
        self.results: Dict[str, List[int]] = {}
        self.final_logits: Dict[str, np.ndarray] = {}
        self._step_no = 0
        self._next_seq = 1
        self._t0: Optional[float] = None
        self._tokens_out = 0
        self._retryable = compile_service.retryable_errors()
        from ..observe import slo as _slo

        # Fleet replicas pass a per-replica ``slo_name`` so the /slo
        # endpoint (and the fleet autoscaler) see each replica's windows
        # instead of a last-writer-wins mush.
        self.slo = _slo.ServeSLO(name=slo_name)
        # Live percentile export for fleet scrapers; no-op unless
        # TDX_METRICS_EXPORT_S > 0 (the first engine's SLO wins the
        # exporter slot — one replica per process is the deployment
        # shape).
        _slo.ensure_exporter(self.slo)
        # Handle resolved once: the registry lookup is lock + key-tuple
        # work, and _decode_tick is the hot path.
        self._tok_hist = observe.histogram(
            "tdx.serve.token_latency_s",
            buckets=(0.001, 0.005, 0.02, 0.1, 0.5, 2.0, 10.0),
        )
        self._decode_steps = observe.counter("tdx.serve.decode_steps")
        # Per decode / verify tick: the context tokens its lanes attend
        # over and the lanes it decodes (the work a tick's time buys).
        self._attended = observe.counter("tdx.serve.attended_tokens")
        self._lane_ticks = observe.counter("tdx.serve.decode_lane_ticks")
        # Per decode tick: the blocks one call of the decode kernel walks
        # (each attention layer's call walks as many), by the kernel's
        # own arithmetic on the pool as one tp shard of it sees it.
        self._kv_blocks = observe.counter("tdx.serve.attn_kv_blocks")
        _, _, kv_local, page, head_dim = self.k_pages.sharding.shard_shape(
            self.k_pages.shape)
        if self.kv.cfg.latent:
            self._walked = functools.partial(
                mla_ops.blocks_walked, page_size=page, width=head_dim,
                dtype=self.k_pages.dtype)
        else:
            self._walked = functools.partial(
                kv_blocks_walked, page_size=page, kv_heads=kv_local,
                head_dim=head_dim, dtype=self.k_pages.dtype)
        self._n_admitted = 0  # lifetime; serve.admit reports a tick's share
        # The afmoe family: what its expert layers routed to the experts
        # this replica holds, from the counts every call brings back.
        self._moe_pairs = observe.counter("tdx.serve.moe_routed_pairs")
        self._moe_hit = observe.counter("tdx.serve.moe_experts_hit")
        self._moe_max = observe.counter("tdx.serve.moe_pairs_max_expert")
        # The olmo_hybrid family: positions advanced through the delta
        # rule, summed over its linear layers, by decode ticks and by
        # prefill / chunk calls (always on).
        self._gdn_layers = (olmo_hybrid.n_linear_layers(cfg)
                            if cfg.olmo_hybrid is not None else 0)
        self._gdn_decode = observe.counter("tdx.serve.gdn_decode_positions")
        self._gdn_prefill = observe.counter("tdx.serve.gdn_prefill_positions")
        # The pangu_ultra_moe family: latent rows its decode ticks read and
        # query-key pairs its prefill and chunk calls attend, summed over
        # its latent-attention layers (always on).
        self._mla_layers = cfg.n_layers if cfg.mla is not None else 0
        self._mla_decode = observe.counter("tdx.serve.mla_decode_tokens")
        self._mla_prefill = observe.counter("tdx.serve.mla_prefill_pairs")
        # Decoding ahead (docs/serving.md): the plain tick in flight, the
        # prompts' last rows dispatched this step and not read yet, when a
        # tick was last read; plain ticks dispatched while the tick before
        # was unread, and lane-ticks run for a lane that had retired (the
        # mechanism's cost).  Always on.
        self._tick: Optional[_Tick] = None
        self._rows: List[tuple] = []
        self._t_read = 0.0
        self._ahead_ticks = observe.counter("tdx.serve.decode_ticks_ahead")
        self._discarded = observe.counter("tdx.serve.lane_ticks_discarded")

    # -- pools ----------------------------------------------------------------
    #
    # The engine OWNS the pools and the recurrent state.  Every program
    # that takes them consumes them (they are donated) and returns them in
    # the same buffers, so each call site rebinds ``self.k_pages``,
    # ``self.v_pages`` and ``self.state`` from the call's outputs in the
    # statement that makes the call, and nothing else may keep a reference
    # to an array across a call: it would be a deleted array afterwards
    # (its shape, dtype and sharding stay readable).

    def _init_pools(self) -> None:
        """Allocate the zeroed pools and what else every model program
        of the family threads behind them (``self.state``): a hybrid
        stack's recurrent layer group (ssm, conv; a lane's slot is the
        lane's index), the afmoe family's window layer group and the
        held experts' running pair counts, the pangu_ultra_moe family's
        pair counts, else nothing.  ``_pairs_at``: where in ``state`` the
        pair counts are (None without an expert layer)."""
        cfg, kv = self.cfg, self.kv.cfg
        sharding = pool_sharding(self.mesh, cfg.kv_heads)
        self.k_pages, self.v_pages = init_pools(kv, cfg.dtype, sharding)
        self.state: tuple = ()
        self._pairs_at: Optional[int] = None
        if kv.latent:
            from ..models import afmoe

            self.state = (jnp.zeros((afmoe.n_expert_layers(cfg),
                                     cfg.experts.held_experts), jnp.int32),)
            self._pairs_at = 0
            self._pairs_seen = np.zeros(self.state[0].shape, np.int32)
        elif kv.state is not None:
            self.state = init_state(
                kv.state, cfg.dtype,
                state_sharding(self.mesh, kv.state.d_inner))
        elif kv.window is not None:
            from ..models import afmoe

            self.state = (
                init_window_pool(kv, cfg.dtype, sharding),
                jnp.zeros((afmoe.n_expert_layers(cfg),
                           cfg.afmoe.held_experts), jnp.int32))
            self._pairs_at = 1
            self._pairs_seen = np.zeros(self.state[1].shape, np.int32)

    def _pools_lost(self) -> bool:
        """Whether a call that consumed the pools or the state failed
        after taking them: the arrays the engine still names are deleted."""
        return any(a.is_deleted()
                   for a in (self.k_pages, self.v_pages, *self.state)
                   if a is not None)

    # -- program cache ------------------------------------------------------

    def _all_specs(self) -> Dict[str, object]:
        """name → ServeProgramSpec for every program this replica shape
        can run (decode + all prefill buckets), built ONCE — the spec
        construction re-traces the model's init, so spin_up_replica
        seeds this cache with the list it already built."""
        if self._spec_cache is None:
            specs = serve_program_specs(
                self.family, self.cfg, ServeConfig(
                    max_batch=self.scfg.max_batch,
                    page_size=self.scfg.page_size,
                    n_pages=self.scfg.n_pages,
                    max_pages_per_seq=self.scfg.max_pages_per_seq,
                    prefill_buckets=self.scfg.prefill_buckets,
                    max_new_tokens=self.scfg.max_new_tokens,
                    prefill_chunk=self.scfg.prefill_chunk or None,
                    prefix_cache=self.scfg.prefix_cache,
                    spec_buckets=self.scfg.spec_buckets,
                    spec_decode=self.scfg.spec_decode,
                    spec_k=self.scfg.spec_k,
                    n_window_pages=self.scfg.n_window_pages or None,
                ),
                seed=self._seed, param_dtype=self._param_dtype,
                mesh=self.mesh, plan=self.plan,
                include_init=False,
            )
            self._spec_cache = {s.name: s for s in specs}
        return self._spec_cache

    def _program(self, name: str):
        """The compiled program for ``name`` ('decode' or
        'prefill-<bucket>'), compiled through the registry path on first
        use."""
        prog = self._programs.get(name)
        if prog is None:
            spec = self._all_specs().get(name)
            if spec is None:  # pragma: no cover — name is engine-built
                raise ValueError(f"unknown serving program {name!r}")
            prog, _ = compile_serving_program(spec)
            self._programs[name] = prog
        self.program_calls[name] = self.program_calls.get(name, 0) + 1
        return prog

    def warmup(self) -> Dict[str, str]:
        """Compile decode + every prefill bucket now (spin-up does this
        so the first request pays no compile); returns name → cache
        outcome — the zero-local-compile gate reads these."""
        outcomes: Dict[str, str] = {}
        for name, spec in self._all_specs().items():
            if name not in self._programs:
                prog, outcome = compile_serving_program(spec)
                self._programs[name] = prog
                outcomes[name] = outcome
        return outcomes

    # -- public API ---------------------------------------------------------

    def submit(self, req: Request) -> None:
        need = self.kv.cfg.pages_for(len(req.tokens) + 1)
        if need > self.kv.cfg.usable_pages:
            raise ValueError(
                f"request {req.rid}: prompt of {len(req.tokens)} tokens "
                f"needs {need} pages but the pool only has "
                f"{self.kv.cfg.usable_pages}"
            )
        if len(req.tokens) + req.max_new_tokens > self.scfg.max_context:
            raise ValueError(
                f"request {req.rid}: prompt + budget "
                f"({len(req.tokens)} + {req.max_new_tokens}) exceeds "
                f"max_context={self.scfg.max_context}"
            )
        if not req.tokens:
            raise ValueError(f"request {req.rid}: empty prompt")
        if req.max_new_tokens < 1:
            # A zero budget would still emit prefill's first token,
            # diverging from the oracle (which generates nothing).
            raise ValueError(
                f"request {req.rid}: max_new_tokens must be >= 1, got "
                f"{req.max_new_tokens}"
            )
        req._submit_t = time.perf_counter()
        # The deadline is END-TO-END: anchor it ONCE, at first submit.
        # A requeued request re-entering a (new) engine keeps its
        # original deadline — the client has been waiting the whole
        # time (mirrors the _submit_t queue-wait contract).
        if req.deadline_s is not None and not hasattr(req, "_deadline_t"):
            req._deadline_t = req._submit_t + req.deadline_s
        # Ledger anchor: first-enqueue wins (a fleet submit already
        # minted the record; a hedge/requeue hop only logs a dispatch).
        reqledger.on_enqueue(req.rid, priority=req.priority,
                             deadline_s=req.deadline_s,
                             n_prompt=len(req.tokens))
        reqledger.on_event(req.rid, "dispatch", replica=self.slo.name)
        self.waiting.append(req)
        self._gauges()

    def run(self, requests: Sequence[Request] = (), *,
            max_steps: int = 100_000) -> Dict[str, List[int]]:
        """Submit ``requests`` and drive the loop until every request
        completed (or ``max_steps``); returns the replica's cumulative
        rid → generated-tokens map (results persist across ``run``
        calls, like any server's response log)."""
        for r in requests:
            self.submit(r)
        if (self._drafter is not None and not len(self._drafter)
                and len(self.prefix)):
            # A fresh drafter on a warm radix tree (e.g. spec toggled on
            # a long-lived replica) seeds itself from the preambles the
            # tree already proved hot.
            self._drafter.warm_from_prefix(self.prefix)
        if self._t0 is None:
            self._t0 = time.perf_counter()
        start = self._step_no  # budget is per CALL; _step_no is lifetime
        while (self.waiting or self.active) and (
                self._step_no - start) < max_steps:
            self.step()
        self._drain_ahead()
        if self.waiting or self.active:
            raise RuntimeError(
                f"serve loop hit max_steps={max_steps} with "
                f"{len(self.waiting)} waiting / {len(self.active)} active"
            )
        return dict(self.results)

    def drain(self, *, max_steps: int = 100_000) -> List[Request]:
        """Scale-down hook: finish every IN-FLIGHT lane (admission is
        suspended — a draining replica gets no new work), then hand back
        whatever was still waiting unadmitted.  A fault mid-drain
        requeues its lanes into ``waiting`` like any other step fault,
        so the leftovers a drain returns are exactly the requests the
        fleet must redistribute onto survivors."""
        self._draining = True
        try:
            start = self._step_no
            while self.active and (self._step_no - start) < max_steps:
                self.step()
            if self.active:
                raise RuntimeError(
                    f"drain hit max_steps={max_steps} with "
                    f"{len(self.active)} lanes still active"
                )
            self._drain_ahead()
            leftover = list(self.waiting)
            self.waiting.clear()
            # A drained replica holds no sequences; drop the prefix
            # cache's references too so every refcount returns to zero
            # (the zero-leak drain contract the tests pin).
            self.prefix.clear()
            return leftover
        finally:
            self._draining = False

    def requeue_active(self, *, reason: str = "fault") -> int:
        """Preempt every active lane back into ``waiting`` (recompute
        policy — greedy decode regenerates identically).  The fleet's
        ``flap`` fault path uses this: an intermittent replica fault
        costs the batch a replay, not the replica its life.  Returns
        the number of lanes requeued."""
        self._drain_ahead()
        n = len(self.active)
        for slot in list(self.active):
            self._preempt(slot, reason=reason)
        return n

    def cancel(self, rid: str, *, reason: str = "cancel") -> Optional[List[int]]:
        """Cancel one request mid-flight: an active lane is evicted and
        its KV pages freed IMMEDIATELY (they go back to the pool this
        step, not at retirement); a waiting request is simply removed.
        Returns the tokens generated so far (``[]`` if never admitted),
        or ``None`` if the engine doesn't hold ``rid``.  Removing a lane
        between decode steps cannot perturb the survivors: each lane's
        decode reads only its own slot row and page table, exactly as
        when a neighbor retires (bitwise-pinned in tests).  Does NOT
        invoke ``on_cancel`` — the caller initiated this and already
        knows; only the engine-initiated deadline sweep notifies."""
        for slot, lane in list(self.active.items()):
            if lane.req.rid != rid:
                continue
            self.active.pop(slot)
            self.kv.free(lane.seq_id)
            self._discarded.inc(lane.ahead)
            self._delivered.pop(rid, None)
            self.cancelled[rid] = list(lane.generated)
            observe.instant("serve.cancel", category="serve", rid=rid,
                            reason=reason, step=self._step_no,
                            tokens=len(lane.generated),
                            flow=reqledger.flow_id(rid))
            reqledger.on_abort(rid, replica=self.slo.name, reason=reason)
            self._gauges()
            return list(lane.generated)
        for req in list(self.waiting):
            if req.rid == rid:
                self.waiting.remove(req)
                self.cancelled[rid] = []
                observe.instant("serve.cancel", category="serve", rid=rid,
                                reason=reason, step=self._step_no, tokens=0,
                                flow=reqledger.flow_id(rid))
                reqledger.on_abort(rid, replica=self.slo.name, reason=reason)
                self._gauges()
                return []
        return None

    def _expire_deadlines(self) -> None:
        """The per-decode-tick deadline check: cancel every lane and
        waiting request past its end-to-end deadline, freeing lane
        pages immediately, and notify ``on_cancel`` with tokens-so-far
        — a doomed request must stop burning pool pages the admitted
        work is starving for (docs/serving.md §Guardrails)."""
        now = time.perf_counter()
        doomed = [
            lane.req.rid for lane in self.active.values()
            if getattr(lane.req, "_deadline_t", None) is not None
            and now > lane.req._deadline_t
        ] + [
            req.rid for req in self.waiting
            if getattr(req, "_deadline_t", None) is not None
            and now > req._deadline_t
        ]
        for rid in doomed:
            was_active = any(lane.req.rid == rid
                             for lane in self.active.values())
            toks = self.cancel(rid, reason="deadline")
            if toks is None:  # pragma: no cover — rid just enumerated
                continue
            # Terminal for the ledger: spent prefill/decode time becomes
            # guardrail time (the cancel above already ended the attempt).
            reqledger.on_reject(rid, reason="deadline", tokens=len(toks))
            if self.on_cancel is not None:
                self.on_cancel(rid, toks, was_active)

    def install_params(self, params, *, version: Optional[str] = None) -> None:
        """Swap the weights this engine serves (blue-green rollover:
        the GREEN replica is spun up registry-warm on the fleet's
        current params, then the restored step-N+1 tree is installed
        before it serves).  Programs read ``self.params`` at call time,
        so the swap needs no recompile; it is only legal while no lane
        is active, and it clears the prefix cache — KV computed under
        the old weights must never be decoded under the new ones
        (stale-KV corruption is exactly the torn output the rollover
        canary exists to prevent)."""
        if self.active:
            raise RuntimeError(
                f"install_params with {len(self.active)} active lanes; "
                f"drain first"
            )
        self._tick = None  # with no lane active it holds retired lanes only
        self.prefix.clear()
        self.params = params
        self.weight_version = version

    def release_kv(self) -> None:
        """Free the replica's KV pool (the end of a drain): drop the
        page tensors and reset the allocator.  The engine can still
        report results; it can no longer serve."""
        if self.active:
            raise RuntimeError(
                f"release_kv with {len(self.active)} active lanes; "
                f"drain first"
            )
        self._tick = None
        self.k_pages = self.v_pages = None
        self.state = ()
        self.kv = PagedKVCache(self.scfg.kv_config(self.cfg))
        self.prefix = PrefixCache(self.kv)
        self._gauges()

    def outstanding_tokens(self) -> int:
        """Remaining token budget across waiting + active requests — the
        load signal the fleet router balances on.  Safe to call from
        another thread: the snapshot may be momentarily stale (it's a
        routing heuristic, not an invariant), never wrong-by-crash."""
        for _ in range(8):
            try:
                waiting = list(self.waiting)
                lanes = list(self.active.values())
            except RuntimeError:  # resized mid-iteration; retry
                continue
            return (
                sum(r.max_new_tokens for r in waiting)
                + sum(max(1, lane.req.max_new_tokens - len(lane.generated))
                      for lane in lanes)
            )
        return len(self.waiting) + len(self.active)  # coarse fallback

    def step(self) -> None:
        """One engine tick: chaos site → chunked-prefill advance →
        admission (+prefill) → one batched decode step → retirement.  A
        retryable runtime fault mid-batch requeues every active lane
        (recompute preemption).  A fault raised by a program call that
        had already consumed the pools leaves them deleted: they are
        allocated anew and the prefix cache, whose pages' content went
        with them, is dropped; the requeued lanes recompute over the new
        pools (docs/serving.md failure matrix).  A tick dispatched ahead is
        read and handed over before the requeue, where it can be read."""
        self._step_no += 1
        if self._t0 is None:
            self._t0 = time.perf_counter()
        with observe.span(
            "serve.step", category="serve", step=self._step_no,
            active=len(self.active), waiting=len(self.waiting),
        ):
            try:
                self._take_serve_faults()
                admitted = self._n_admitted
                with observe.span("serve.admit", category="serve") as sp:
                    with observe.span(
                            "serve.admit.deadlines", category="serve",
                            scanned=len(self.active) + len(self.waiting)):
                        self._expire_deadlines()
                    self._advance_prefill()
                    self._admit()
                    sp.set(admitted=self._n_admitted - admitted,
                           waiting=len(self.waiting))
                if self._pending_chunk_faults:
                    # A chunk fault due on a step with no chunk
                    # boundary to defer to still fires (a plan's fault
                    # is never silently dropped).
                    chaos.execute(self._pending_chunk_faults.pop(0))
                self._decode_step()
                if self._pending_verify_faults:
                    # Same never-dropped contract as chunk faults: a
                    # verify fault due on a step with no verify tick
                    # (spec off, no decodable lanes) fires anyway.
                    chaos.execute(self._pending_verify_faults.pop(0))
            except self._retryable as e:
                get_logger().warning(
                    "serve: step %d fault (%s: %s); requeueing %d active "
                    "requests", self._step_no, type(e).__name__,
                    str(e)[:120], len(self.active),
                )
                pools_lost = self._pools_lost()
                observe.instant("serve.fault", category="serve",
                                step=self._step_no, error=type(e).__name__,
                                pools_lost=pools_lost)
                # Survived — but the post-mortem must not depend on the
                # survival: persist the ring before the requeue rewrites
                # the engine state (no-op without TDX_FLIGHT_DIR).
                observe.flight_dump(
                    "serve_fault", step=self._step_no,
                    error=f"{type(e).__name__}: {e}"[:300],
                    active=len(self.active), waiting=len(self.waiting),
                )
                # Prompts whose last rows were not read go back with the
                # other lanes; the tick in flight is read first where it
                # can be (it was dispatched before the faulted call).
                self._rows.clear()
                try:
                    self._drain_ahead()
                except self._retryable:
                    pass  # it failed too: its lanes recompute
                for slot in list(self.active):
                    self._preempt(slot, reason="fault")
                if pools_lost:
                    # Every lane is back in the queue and holds no page;
                    # what still does is the prefix cache, and what its
                    # pages held is gone; the allocator starts over with
                    # the pools (a state slot's first call starts it from
                    # zero whatever it held).
                    self.prefix.clear()
                    self.kv.reset()
                    self._init_pools()
                    observe.counter("tdx.serve.pool_rebuilds").inc()
            with observe.span("serve.gauges", category="serve"):
                self._gauges()

    # -- admission / prefill ------------------------------------------------

    def _take_serve_faults(self) -> None:
        """The serve chaos site, taken by hand instead of through
        :func:`chaos.maybe_inject`: ``raise:chunk`` faults are DEFERRED
        to the next prefill-chunk boundary (the mid-chunked-prefill
        fault docs/serving.md's failure matrix pins), ``raise:verify``
        to the next speculative verify tick (mid-verify, after drafts
        were taken and capacity extended — the worst rollback moment);
        everything else executes immediately, exactly as maybe_inject
        would."""
        plan = chaos.active_plan()
        if plan is None:
            return
        for fault in plan.take("serve", self._step_no):
            if fault.kind == "raise" and fault.arg == "chunk":
                self._pending_chunk_faults.append(fault)
            elif fault.kind == "raise" and fault.arg == "verify":
                self._pending_verify_faults.append(fault)
            else:
                chaos.execute(fault)

    def _run_program(self, name: str, *args, lanes: int, attended: int,
                     positions: int = 0, kv_blocks: int = 0,
                     window_tokens: int = 0, fetch: bool = True,
                     greedy: bool = False, defer: bool = False,
                     then: Optional[Callable] = None,
                     ahead: Optional[int] = None):
        """Call the compiled model program ``name`` on the params, the
        pools, the recurrent state (a hybrid stack) and ``args`` under
        ``serve.program`` and bring its result to the host under
        ``serve.tick.d2h`` (``bytes``: what came).  That is the logits;
        nothing with ``fetch`` False (a chunk that is not a prompt's last,
        whose logits nobody reads); with ``greedy`` (a plain decode tick)
        every row's greedy choice, made on the device, while the logits
        stay there: ``(logits on the device, tokens on the host)``.  A row
        of 65,536 floats is 262 KB; 128 of them took 11.7 ms of a 37 ms
        tick to fetch, for a host that reads a row only when its lane
        retires (``_TickRow``; PERF.md section 6, PR 36).

        ``serve.program`` runs from the call until what the engine reads
        is ready, and its children tile it in the order the host runs
        them: ``serve.program.launch`` for each dispatch (``call``: the
        ``program``, then a plain tick's ``greedy`` choice, dispatched
        before anything waits) and ``serve.program.wait`` for each wait
        (the afmoe family's pair counts, then, while telemetry is on, the
        tokens or logits).  Off, that last span is the shared no-op and
        does not wait: the fetch under ``serve.tick.d2h`` does, so a traced
        call makes the same device calls and host waits as an untraced one
        (its copy queued behind the call, where the untraced fetch queues
        it) and ``serve.tick.d2h`` is what is left of the copy.

        ``attended`` is the context the program's ``lanes`` attend over,
        counted before anything retires; ``positions`` the real positions
        the call advances its lanes by, all lanes together (a decode
        tick's live lanes, a verify tick's rows, a prefill's or a chunk's
        tokens): with Gated DeltaNet layers, times their number, they go
        to ``tdx.serve.gdn_decode_positions`` (decode) or
        ``tdx.serve.gdn_prefill_positions``; ``kv_blocks`` the blocks the
        decode kernel walks for it (a program that attends through jnp
        gathers walks none).  With a window group ``window_tokens`` is
        what the lanes attend over in a window layer (``min(context,
        window)`` each), and the call's pair counts come to the host with
        the logits: ``routed_pairs`` (pairs that landed on a held expert,
        over the expert layers) and ``experts_hit`` (held experts with at
        least one) on the span, and the ``tdx.serve.moe_*`` counters.

        With ``defer`` (a replica that decodes ahead, docs/serving.md) the
        call is read later in the step or in the next one: the copy of what
        will be read (the tokens or the logits) is queued behind the call,
        traced or not, nothing waits, ``then(logits, what will be read)``
        runs inside the span after the launches, and what will be read
        comes back on the device; ``ahead`` goes on the span."""
        gdn = positions * self._gdn_layers
        if gdn:
            (self._gdn_decode if name == "decode"
             else self._gdn_prefill).inc(gdn)
        extra = {} if ahead is None else {"ahead": ahead}
        if self._mla_layers and name == "decode":
            extra["mla_attended"] = attended * self._mla_layers
            self._mla_decode.inc(extra["mla_attended"])
        elif self._mla_layers:
            # Positions [attended - positions, attended), each with its
            # causal context: n s + n (n + 1) / 2 pairs a layer.
            n, s = positions, attended - positions
            extra["mla_pairs"] = (n * s + n * (n + 1) // 2) * self._mla_layers
            self._mla_prefill.inc(extra["mla_pairs"])
        with observe.span("serve.program", category="serve", program=name,
                          lanes=lanes, attended_tokens=attended,
                          kv_blocks=kv_blocks, positions=positions,
                          **extra) as sp:
            with observe.span("serve.program.launch", category="serve",
                              program=name, call="program"):
                logits, self.k_pages, self.v_pages, *state = self._program(
                    name)(self.params, self.k_pages, self.v_pages,
                          *self.state, *args)
            self.state = tuple(state)
            # The pair counts ride to the host with the logits: a chunk
            # whose logits nobody reads waits for nothing, so the host
            # prepares the next call while the device runs this one, and
            # its pairs are counted with the next call that is fetched
            # (the device keeps a running sum).  With telemetry on every
            # call is waited for anyway and the span gets its own counts.
            if self._pairs_at is not None and (fetch or observe.enabled()):
                with observe.span("serve.program.wait", category="serve",
                                  program=name):
                    seen = np.asarray(self.state[self._pairs_at])
                pairs, self._pairs_seen = seen - self._pairs_seen, seen
                routed, hit = int(pairs.sum()), int((pairs > 0).sum())
                sp.set(routed_pairs=routed, experts_hit=hit)
                if self.kv.cfg.window is not None:
                    sp.set(window_tokens=window_tokens)
                self._moe_pairs.inc(routed)
                self._moe_hit.inc(hit)
                self._moe_max.inc(int(pairs.max()))
            coming = logits
            if fetch and greedy:
                with observe.span("serve.program.launch", category="serve",
                                  program=name, call="greedy"):
                    coming = _greedy(logits)
            if defer:
                coming.copy_to_host_async()
                if then is not None:
                    then(logits, coming)
            else:
                if fetch and observe.enabled():
                    # Queue the copy behind the call before waiting, as the
                    # untraced fetch does: waiting first would add a round
                    # trip (0.4 ms on the chip's host) that only traced
                    # ticks paid.
                    coming.copy_to_host_async()
                _wait_traced(name, coming)
        if defer:
            return coming
        if not fetch:
            return None
        with observe.span("serve.tick.d2h", category="serve", program=name,
                          bytes=coming.nbytes):
            host = np.asarray(coming)
        return (logits, host) if greedy else host

    def _slot_arg(self, lane: _Lane) -> tuple:
        """The last operands of a one-sequence program: a hybrid stack's
        lane slot, the afmoe family's window-group row and its first
        position (nothing for the other families)."""
        if self.kv.cfg.window is not None:
            rows, first = self.kv.window_rows([lane.seq_id])
            return jnp.asarray(rows), jnp.asarray(first)
        if self.kv.cfg.state is None:
            return ()
        return (jnp.asarray([self.kv.state_slot(lane.seq_id)], jnp.int32),)

    def _window_room(self, lane: _Lane, start: int, end: int) -> None:
        """Before a program writes positions ``[start, end)`` of ``lane``:
        move its window group's pages along (a no-op without the group),
        preempting the youngest other lane while the group's pool is
        short."""
        while True:
            try:
                self.kv.window_advance(lane.seq_id, start, end)
                return
            except OutOfPages:
                victim = self._youngest_other(lane)
                if victim is None:
                    raise
                self._preempt(victim, reason="window_pages")

    def _window_tokens(self, context: int) -> int:
        w = self.kv.cfg.window
        return 0 if w is None else min(context, w.window)

    def _free_slot(self) -> Optional[int]:
        for s in range(self.scfg.max_batch):
            if s not in self.active:
                return s
        return None

    def _admit(self) -> None:
        if self._draining:
            return  # a draining replica finishes lanes, admits nothing
        while self.waiting:
            req = self.waiting[0]
            if req.arrival_step > self._step_no:
                break
            slot = self._free_slot()
            if slot is None:
                break
            shared = (self.prefix.match(req.tokens)
                      if self.scfg.prefix_cache else [])
            need = self.kv.cfg.pages_for(len(req.tokens)) - len(shared)
            # Cache leaves are strictly cheaper to give up than running
            # lanes; evict LRU ones (never this request's own matched
            # prefix) until the suffix fits.
            while (need > self.kv.free_pages
                   and self.prefix.evict(exclude=set(shared))):
                pass
            if need > self.kv.free_pages:
                break  # retirement will free pages; keep FIFO order
            if (self.kv.cfg.window is not None and self.kv.cfg.pages_for(
                    min(len(req.tokens), self._chunk_cap()))
                    > self.kv.window_free_pages):
                break  # the window group is short of the first chunk's pages
            self.waiting.popleft()
            self._prefill(req, slot, shared)

    def _chunk_cap(self) -> int:
        # Guard for directly-constructed ResolvedServeConfigs whose
        # prefill_chunk kept the field default 0 (resolve() always pins
        # a positive cap).
        return self.scfg.prefill_chunk or self.scfg.prefill_buckets[-1]

    def _prefill(self, req: Request, slot: int,
                 shared: Sequence[int]) -> None:
        """Admit one request: map its cached prefix pages (``shared``),
        then prefill the suffix — in one shot through the classic
        bucketed program when it fits a single chunk, else chunk by
        chunk across engine ticks (``_advance_prefill``)."""
        L = len(req.tokens)
        # Queue wait = submit → the moment a lane+pages were granted.
        # A requeued (preempted/faulted) request measures from its
        # ORIGINAL submit — the client has been waiting the whole time.
        # One clock read; a request that never passed submit() (direct
        # test harness) contributes no sample rather than a zero.
        sub = getattr(req, "_submit_t", None)
        if sub is not None:
            wait = time.perf_counter() - sub
            observe.histogram("tdx.serve.queue_wait_s").observe(wait)
            self.slo.observe_queue_wait(wait)
        sid = self._next_seq
        self._next_seq += 1
        if shared:
            self.kv.alloc_shared(sid, shared, L)
        else:
            self.kv.alloc(sid, L, slot=slot)
        # Reused tokens never re-prefill — but the LAST prompt position
        # must run (its logits are the first generated token), so a
        # fully-cached prompt recomputes exactly one token (and that
        # write is the one copy-on-write case: it lands in a shared
        # page).
        start = min(len(shared) * self.scfg.page_size, L - 1)
        if start > 0:
            observe.counter("tdx.serve.prefix_hits").inc()
            observe.counter("tdx.serve.prefix_tokens_reused").inc(start)
        if getattr(req, "_prefilled", False):
            # Preempted (or faulted) and admitted again: neither its pages
            # nor its recurrent state were kept, so the prompt is prefilled
            # a second time.
            observe.counter("tdx.serve.recomputed_tokens").inc(L - start)
        req._prefilled = True
        reqledger.on_admit(req.rid, replica=self.slo.name,
                           prefix_tokens=start)
        lane = _Lane(req=req, seq_id=sid, slot=slot, length=start,
                     admitted_step=self._step_no, prefilling=True,
                     spec_k=self.scfg.spec_k)
        if self._drafter is not None:
            # The prompt's n-grams are the drafter's cheapest signal:
            # shared preambles recur across requests, and tiny greedy
            # models echo their prompts.
            self._drafter.observe(req.tokens)
        try:
            with observe.span(
                "serve.prefill", category="serve", rid=req.rid, tokens=L,
                reused=start,
            ):
                if (not shared and L <= self.scfg.prefill_buckets[-1]
                        and L <= self._chunk_cap()):
                    # Classic single-shot path: fresh prompt, one chunk.
                    bucket = self.scfg.bucket_for(L)
                    name = f"prefill-{bucket}"
                    with observe.span("serve.tick.tables", category="serve",
                                      program=name):
                        self._window_room(lane, 0, L)
                        toks = np.zeros((1, bucket), np.int32)
                        toks[0, :L] = req.tokens
                        row = np.asarray(
                            [self.kv.table_row(sid,
                                               self.scfg.max_pages_per_seq)],
                            np.int32,
                        )
                        args = (jnp.asarray(toks),
                                jnp.asarray([L], jnp.int32),
                                jnp.asarray(row), *self._slot_arg(lane))
                    logits = self._run_program(
                        name, *args, lanes=1, attended=L, positions=L,
                        window_tokens=self._window_tokens(L),
                        defer=self._decodes_ahead())
                    lane.length = L
                    reqledger.on_event(req.rid, "prefill", bucket=bucket,
                                       n=L, replica=self.slo.name)
                else:
                    name, logits = self._run_chunk(lane)  # None → more
        except BaseException:
            # The request left the queue and its pages are allocated,
            # but it is not in `active` yet — step()'s fault handler
            # cannot see it.  Undo here so a mid-prefill fault (device,
            # or a chaos compile/cache-site fault through the lazy
            # program compile) costs latency, never a dropped request
            # or leaked pages; retryable errors then requeue the rest
            # of the batch in step().
            self.kv.free(sid)
            self.waiting.appendleft(req)
            observe.counter("tdx.serve.preempted_requests").inc()
            observe.instant("serve.preempt", category="serve",
                            rid=req.rid, reason="prefill_fault",
                            step=self._step_no,
                            flow=reqledger.flow_id(req.rid))
            reqledger.on_abort(req.rid, replica=self.slo.name,
                               reason="prefill_fault")
            raise
        self.active[slot] = lane
        self._n_admitted += 1
        observe.counter("tdx.serve.prefills").inc()
        observe.counter("tdx.serve.prefill_tokens").inc(L - start)
        if logits is not None:
            self._finish_prefill(lane, logits, name)

    def _run_chunk(self, lane: _Lane):
        """One prefill chunk for ``lane``: copy-on-write its first page
        if shared, run the bucketed chunk program over the next
        ``prefill_chunk`` prompt tokens.  Returns the program's name and
        the final position's logits when the prompt is complete (still on
        the device where the replica decodes ahead), else ``None``."""
        req = lane.req
        L = len(req.tokens)
        s = lane.length
        n = min(L - s, self._chunk_cap())
        bucket = self.scfg.bucket_for(n)
        name = f"chunk-{bucket}"
        with observe.span("serve.tick.tables", category="serve",
                          program=name):
            # Only a chunk's FIRST page can be shared (later pages were
            # written by this very sequence's earlier chunks); cow_page
            # no-ops at refcount 1, so this is unconditional.
            self._cow_for(lane, s // self.scfg.page_size)
            self._window_room(lane, s, s + n)
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :n] = req.tokens[s:s + n]
            row = np.asarray(
                [self.kv.table_row(lane.seq_id, self.scfg.max_pages_per_seq)],
                np.int32,
            )
            args = (jnp.asarray(toks), jnp.asarray([s], jnp.int32),
                    jnp.asarray([s + n], jnp.int32), jnp.asarray(row),
                    *self._slot_arg(lane))
        last = s + n >= L
        logits = self._run_program(
            name, *args, lanes=1, attended=s + n, positions=n,
            window_tokens=self._window_tokens(s + n), fetch=last,
            defer=last and self._decodes_ahead())
        lane.length = s + n
        observe.counter("tdx.serve.prefill_chunks").inc()
        reqledger.on_chunk(req.rid, bucket=bucket, n_tokens=n,
                           replica=self.slo.name)
        return name, logits

    def _cow_for(self, lane: _Lane, page_index: int) -> None:
        """Give ``lane`` a private copy of its ``page_index``-th page if
        that page is shared, cloning the contents through the compiled
        ``cow`` program.  Under pool exhaustion: evict cache leaves,
        then preempt the youngest OTHER lane — each preemption/eviction
        drops references, so the loop always terminates (worst case the
        refcount falls to 1 and the copy becomes unnecessary)."""
        while True:
            try:
                moved = self.kv.cow_page(lane.seq_id, page_index)
                break
            except OutOfPages:
                if self.prefix.evict():
                    continue
                victim = self._youngest_other(lane)
                if victim is not None:
                    self._preempt(victim, reason="pages")
                    continue
                raise  # pragma: no cover — ref>1 implies an evictee
        if moved is not None:
            src, dst = moved
            with observe.span("serve.program", category="serve",
                              program="cow", lanes=1, attended_tokens=0,
                              kv_blocks=0, positions=0):
                with observe.span("serve.program.launch", category="serve",
                                  program="cow", call="program"):
                    self.k_pages, self.v_pages = self._program("cow")(
                        self.k_pages, self.v_pages,
                        jnp.asarray([src], jnp.int32),
                        jnp.asarray([dst], jnp.int32),
                    )
                _wait_traced("cow", self.k_pages)
            observe.counter("tdx.serve.cow_copies").inc()
            reqledger.on_cow(lane.req.rid, replica=self.slo.name)

    def _youngest_other(self, lane: _Lane) -> Optional[int]:
        others = [s for s in self.active if s != lane.slot]
        if not others:
            return None
        return max(others, key=lambda s: (self.active[s].admitted_step, s))

    def _advance_prefill(self) -> None:
        """One chunk for every mid-prefill lane — chunked prefill
        interleaves with decode at engine-tick granularity, so a long
        prompt cannot lock the batch out for its whole prefill.  A
        deferred ``raise:chunk`` chaos fault fires HERE, between
        chunks."""
        for slot in sorted(self.active):
            lane = self.active.get(slot)
            if lane is None or not lane.prefilling:
                continue
            if self._pending_chunk_faults:
                chaos.execute(self._pending_chunk_faults.pop(0))
            name, logits = self._run_chunk(lane)
            if logits is not None:
                self._finish_prefill(lane, logits, name)

    def _finish_prefill(self, lane: _Lane, logits, program: str) -> None:
        """The prompt's K/V is fully written: publish its full pages to
        the prefix cache (BEFORE the first emit — retirement may free
        the sequence immediately, and the cache's references are what
        keep the pages alive), then deliver the first token (TTFT).
        Logits still on the device (a replica that decodes ahead) are read
        after the step's tick is dispatched (``_decode_ahead_step``); the
        lane sits that tick out.  ``program`` is the call that made them."""
        if not isinstance(logits, np.ndarray):
            self._rows.append((lane, program, logits))
            return
        lane.prefilling = False
        req = lane.req
        L = len(req.tokens)
        with observe.span("serve.tick.emit", category="serve",
                          program="prefill", tokens=1):
            nfull = L // self.scfg.page_size
            if nfull and self.scfg.prefix_cache:
                self.prefix.insert(
                    req.tokens[:nfull * self.scfg.page_size],
                    self.kv.page_ids(lane.seq_id)[:nfull],
                )
            # A re-prefill after preemption replays a first token the
            # client already received — it must not contribute a (huge,
            # bogus) TTFT sample; prefills/prefill_tokens keep counting,
            # they measure engine work, not delivery.
            first_delivery = self._delivered.get(req.rid, 0) == 0
            self._emit(lane, int(np.argmax(logits)), logits)
            if first_delivery:
                # One clock read; no fabricated zero sample for a request
                # that never passed submit() (same contract as queue wait).
                sub = getattr(req, "_submit_t", None)
                if sub is not None:
                    ttft = time.perf_counter() - sub
                    observe.histogram("tdx.serve.ttft_s").observe(ttft)
                    self.slo.observe_ttft(ttft)

    # -- decode ---------------------------------------------------------------

    def _decodes(self, lane: _Lane) -> bool:
        """Whether ``lane`` takes part in the next tick: it is prefilled,
        and the ticks dispatched for it and not read (``ahead``) leave its
        budget and the context cap room for one more token.  A lane whose
        budget ends with the tick in flight sits the next one out."""
        return (not lane.prefilling
                and len(lane.generated) + lane.ahead < lane.req.max_new_tokens
                and lane.length + lane.ahead < self.scfg.max_context)

    def _decodable(self) -> List[int]:
        return [s for s in sorted(self.active) if self._decodes(self.active[s])]

    def _ensure_capacity(self) -> None:
        """Every decoding lane must own a page slot for its next token;
        evict prefix-cache leaves first, then preempt the youngest
        lanes, until the pool covers the rest.  Mid-prefill lanes sit
        decode out — their growth is the chunk path's business."""
        for slot in sorted(self.active,
                           key=lambda s: (self.active[s].admitted_step, s)):
            lane = self.active.get(slot)
            if lane is None or not self._decodes(lane):
                continue
            while True:
                try:
                    self.kv.extend(lane.seq_id, lane.length + lane.ahead + 1)
                    break
                except OutOfPages:
                    if self.prefix.evict():
                        continue
                    victim = max(
                        self.active,
                        key=lambda s: (self.active[s].admitted_step, s),
                    )
                    self._preempt(victim, reason="pages")
                    if victim == slot:
                        break  # this lane itself was the youngest

    def _decodes_ahead(self) -> bool:
        """Whether this replica dispatches a plain tick before it reads the
        one before (docs/serving.md §Decoding ahead): it has no drafter,
        which needs a tick's tokens to propose for the next, and its plain
        tick reads nothing but its tokens (the afmoe and pangu_ultra_moe
        families' read the held experts' pair counts, carried in the
        donated state)."""
        return self._drafter is None and self._pairs_at is None

    def _decode_step(self) -> None:
        if self._decodes_ahead():
            self._decode_ahead_step()
            return
        if not self._decodable():
            return
        if self._drafter is not None:
            self._spec_decode_step()
        else:
            self._plain_decode_step()

    def _decode_ahead_step(self) -> None:
        """Dispatch this step's tick, then read the one before and the
        step's prompt rows while it runs: the host's part of a tick (the
        emit, retirement, callbacks, the next step's admission and tables)
        overlaps the chip's.  At most one tick is in flight; a step with no
        lane to decode only reads (drains)."""
        prev = self._tick
        tick, tokens = self._dispatch_tick(prev)
        if tick is None:
            self._drain_ahead()
        else:
            self._tick = tick
            if prev is not None:
                self._emit_tick(prev, tokens)
        if not self._rows:
            return
        rows, self._rows = self._rows, []
        for lane, program, logits in rows:
            host = _read_late(program, logits)
            if self.active.get(lane.slot) is lane:  # not preempted meanwhile
                self._finish_prefill(lane, host, program)

    def _dispatch_tick(self, prev: Optional[_Tick]):
        """Build and dispatch a plain tick without reading it; returns the
        tick (None with no lane to decode) and ``prev``'s tokens.  A lane's
        position moves one a tick whatever its token, so room is reserved
        before ``prev`` is read; a lane that decoded in ``prev`` takes its
        token on the device (``_merge``).  Inside the call's span, after its
        launches, the rows of the lanes whose budget this tick ends are cut
        behind it and ``prev``'s tokens are read (``_read_late``)."""
        with observe.span("serve.tick.tables", category="serve",
                          program="decode"):
            t0 = time.perf_counter()
            self._ensure_capacity()
            slots = self._decodable()
            if not slots:
                return None, None
            B = self.scfg.max_batch
            maxp = self.scfg.max_pages_per_seq
            tokens = np.zeros((B,), np.int32)
            positions = np.zeros((B,), np.int32)
            table = np.zeros((B, maxp), np.int32)
            lanes = [(s, self.active[s]) for s in slots]
            table[slots] = self.kv.table_rows(
                [lane.seq_id for _, lane in lanes], maxp)
            ending, merge = [], False
            for slot, lane in lanes:
                positions[slot] = lane.length + lane.ahead
                if lane.ahead:
                    tokens[slot], merge = -1, True  # ``prev``'s, on the device
                else:
                    tokens[slot] = (lane.generated[-1] if lane.generated
                                    else lane.req.tokens[-1])
                lane.ahead += 1
                if not self._decodes(lane):
                    ending.append(slot)
            toks = jnp.asarray(tokens)
            if merge:
                toks = _merge(prev.tokens, toks)
            args = (toks, jnp.asarray(positions), jnp.asarray(table))
            n_lanes = len(slots)
            attended = int(positions.sum()) + n_lanes
            kv_blocks = self._walked(positions[slots] + 1)
            self._decode_steps.inc()
            self._attended.inc(attended)
            self._kv_blocks.inc(kv_blocks)
            self._lane_ticks.inc(n_lanes)
            if prev is not None:
                self._ahead_ticks.inc()
            tick, read = _Tick(lanes, prev is not None, t0), []

            def behind(logits, greedy):
                tick.logits, tick.tokens = logits, greedy
                for slot in ending:
                    with observe.span("serve.program.launch",
                                      category="serve", program="decode",
                                      call="row"):
                        tick.rows[slot] = row = _row(logits, slot)
                        row.copy_to_host_async()
                if prev is not None:
                    read.append(_read_late("decode", prev.tokens))

        self._run_program(
            "decode", *args, lanes=n_lanes, attended=attended,
            positions=n_lanes, kv_blocks=kv_blocks, greedy=True, defer=True,
            then=behind, ahead=int(prev is not None))
        return tick, read[0] if read else None

    def _emit_tick(self, tick: _Tick, tokens: np.ndarray) -> None:
        """Hand ``tick``'s tokens over to the lanes it was dispatched for
        that are still active (a lane that retired, was cancelled or
        preempted meanwhile gets nothing).  Token latency runs from the
        read before, or from the tick's tables for a tick dispatched with
        none unread."""
        with observe.span("serve.tick.emit", category="serve",
                          program="decode") as sp:
            live = [(s, lane) for s, lane in tick.lanes
                    if self.active.get(s) is lane]
            sp.set(tokens=len(live))
            now = time.perf_counter()
            dt = now - (self._t_read if tick.ahead else tick.t0)
            self._t_read = now
            if live:
                self._tok_hist.observe(dt, n=len(live))
                self.slo.observe_token_latency(dt, n=len(live))
            if reqledger.enabled():
                for _, lane in live:
                    reqledger.on_decode(lane.req.rid, n_lanes=len(tick.lanes),
                                        replica=self.slo.name)
            for slot, lane in live:
                lane.ahead -= 1
                lane.length += 1
                self._emit(lane, int(tokens[slot]),
                           _TickRow(tick.logits, slot, tick.rows.get(slot)))
            # The tick's device arrays are released here, under the span.
            tick.logits = tick.tokens = tick.rows = None

    def _drain_ahead(self) -> None:
        """Read the tick in flight, if any, and hand its tokens over: before
        a step with no lane to decode, ``drain()``, the fault handler's
        requeue, and whatever reads the engine's lanes as settled."""
        tick, self._tick = self._tick, None
        if tick is not None:
            self._emit_tick(tick, _read_late("decode", tick.tokens))

    def _plain_decode_step(self) -> None:
        with observe.span("serve.tick.tables", category="serve",
                          program="decode"):
            self._ensure_capacity()
            slots = self._decodable()
            if not slots:
                return
            t_step = time.perf_counter()
            B = self.scfg.max_batch
            maxp = self.scfg.max_pages_per_seq
            tokens = np.zeros((B,), np.int32)
            positions = np.zeros((B,), np.int32)
            table = np.zeros((B, maxp), np.int32)
            # One batched table build for the whole tick (the per-lane
            # Python loop was the decode hot path's host-side tax).
            seq_ids = [self.active[s].seq_id for s in slots]
            table[slots] = self.kv.table_rows(seq_ids, maxp)
            for slot in slots:
                lane = self.active[slot]
                tokens[slot] = (lane.generated[-1] if lane.generated
                                else lane.req.tokens[-1])
                positions[slot] = lane.length
            args = (jnp.asarray(tokens), jnp.asarray(positions),
                    jnp.asarray(table))
            window_tokens = 0
            if self.kv.cfg.window is not None:
                # The window group's rows (live pages only) and where each
                # begins, behind the full group's table.
                w = self.kv.cfg.window
                wrows = np.zeros((B, w.max_pages_per_seq), np.int32)
                wfirst = np.zeros((B,), np.int32)
                wrows[slots], wfirst[slots] = self.kv.window_rows(seq_ids)
                args += (jnp.asarray(wrows), jnp.asarray(wfirst))
                window_tokens = int(np.minimum(positions[slots] + 1,
                                               w.window).sum())
            n_lanes = len(slots)
            # A lane at position p attends over p + 1 tokens, its new one
            # included (idle lanes sit at 0 and attend over nothing).
            attended = int(positions.sum()) + n_lanes
            kv_blocks = self._walked(positions[slots] + 1)
        logits, greedy = self._run_program(
            "decode", *args, lanes=n_lanes, attended=attended,
            positions=n_lanes,
            kv_blocks=kv_blocks, window_tokens=window_tokens, greedy=True)
        with observe.span("serve.tick.emit", category="serve",
                          program="decode", tokens=n_lanes):
            # Per-token latency: every lane's next token took this step's
            # wall time (the fetch above forced the device work) — one
            # sample PER LANE, so the distribution weights a 4-wide step
            # as the four token deliveries it was.
            dt = time.perf_counter() - t_step
            self._tok_hist.observe(dt, n=n_lanes)
            self.slo.observe_token_latency(dt, n=n_lanes)
            if reqledger.enabled():
                # One coalesced timeline event per decode stretch per
                # lane; the enabled() gate is hoisted so the off path
                # costs one check per tick, not one per lane.
                for slot in slots:
                    lane = self.active.get(slot)
                    if lane is not None:
                        reqledger.on_decode(lane.req.rid, n_lanes=n_lanes,
                                            replica=self.slo.name)
            for slot in slots:
                lane = self.active.get(slot)
                if lane is None:  # pragma: no cover — nothing retires mid-loop
                    continue
                lane.length += 1
                self._emit(lane, int(greedy[slot]), _TickRow(logits, slot))
            self._decode_steps.inc()
            self._attended.inc(attended)
            self._kv_blocks.inc(kv_blocks)
            self._lane_ticks.inc(n_lanes)

    # -- speculative decode (docs/serving.md §Speculative decoding) ---------

    def _drafts_for(self, slots: List[int]) -> Dict[int, List[int]]:
        """Per-slot draft proposals, clamped so no draft can outrun the
        request's token budget or the context cap (tokens verified past
        either would be discarded — wasted verify width)."""
        drafts: Dict[int, List[int]] = {}
        for slot in slots:
            lane = self.active[slot]
            req = lane.req
            k = min(
                lane.spec_k,
                req.max_new_tokens - len(lane.generated) - 1,
                self.scfg.max_context - lane.length - 1,
            )
            if k <= 0:
                drafts[slot] = []
                continue
            drafts[slot] = self._drafter.draft(
                req.tokens + lane.generated, k)
        return drafts

    def _ensure_spec_capacity(self, drafts: Dict[int, List[int]]) -> None:
        """Like :meth:`_ensure_capacity` but covering each lane's draft
        window too.  Under pool pressure a lane's OWN draft is shed
        before anyone gets preempted — speculation is optional, lanes
        are not."""
        for slot in sorted(self.active,
                           key=lambda s: (self.active[s].admitted_step, s)):
            lane = self.active.get(slot)
            if lane is None or lane.prefilling:
                continue
            while True:
                try:
                    self.kv.extend(
                        lane.seq_id,
                        lane.length + len(drafts.get(slot, ())) + 1)
                    break
                except OutOfPages:
                    if self.prefix.evict():
                        continue
                    if drafts.get(slot):
                        drafts[slot] = []
                        continue
                    victim = max(
                        self.active,
                        key=lambda s: (self.active[s].admitted_step, s),
                    )
                    self._preempt(victim, reason="pages")
                    if victim == slot:
                        break  # this lane itself was the youngest

    def _spec_decode_step(self) -> None:
        """Draft → one batched verify tick → greedy accept + rollback.
        The ``verify-<k>`` program scores all k+1 positions of every
        lane in ONE call (a zero-draft lane occupies a width-1 ragged
        row — exact decode semantics); greedy accept takes the longest
        draft prefix matching the program's own argmaxes plus one
        corrected (or bonus) token, then KV rollback retracts the
        rejected positions — every emitted token is the token plain
        decode would have produced, speculation only changes how many
        arrive per tick."""
        with observe.span("serve.tick.drafts", category="serve"):
            drafts = self._drafts_for(self._decodable())
        if not any(drafts.values()) and not self._pending_verify_faults:
            # Nothing proposed anywhere (cold drafter): plain decode is
            # the same tick at width 1, without the rollback tax.
            self._plain_decode_step()
            return
        with observe.span("serve.tick.tables", category="serve",
                          program="verify"):
            self._ensure_spec_capacity(drafts)
            # COW guard for the write at position ``length`` (no-op at
            # refcount 1, like the chunk path) — BEFORE the page tables
            # are snapshotted: a cow under pool pressure can preempt a
            # lane, and a stale table row would let the verify tick
            # scatter a dead lane's K/V into a freshly reused page.
            for slot in self._decodable():
                lane = self.active.get(slot)
                if lane is not None:
                    self._cow_for(lane, lane.length // self.scfg.page_size)
            slots = self._decodable()
            if not slots:
                return
            if self._pending_verify_faults:
                # The deferred ``raise:verify`` chaos fault: after
                # drafting and capacity growth, before the verify call —
                # the step fault handler must requeue lanes whose KV
                # already covers speculative positions.
                chaos.execute(self._pending_verify_faults.pop(0))
            t_step = time.perf_counter()
            B = self.scfg.max_batch
            maxp = self.scfg.max_pages_per_seq
            kb = self.scfg.spec_bucket_for(
                max(len(drafts.get(s, ())) for s in slots) or 1)
            tokens = np.zeros((B, kb + 1), np.int32)
            start = np.zeros((B,), np.int32)
            end = np.zeros((B,), np.int32)
            table = np.zeros((B, maxp), np.int32)
            table[slots] = self.kv.table_rows(
                [self.active[s].seq_id for s in slots], maxp
            )
            for slot in slots:
                lane = self.active[slot]
                d = drafts.get(slot, ())
                tokens[slot, 0] = (lane.generated[-1] if lane.generated
                                   else lane.req.tokens[-1])
                if d:
                    tokens[slot, 1:1 + len(d)] = d
                start[slot] = lane.length
                end[slot] = lane.length + len(d) + 1
            args = (jnp.asarray(tokens), jnp.asarray(start),
                    jnp.asarray(end), jnp.asarray(table))
        n_lanes = len(slots)
        # A lane verifying d drafts from position p attends over
        # p + d + 1 tokens at its last row: its ``end``.
        attended = int(end.sum())
        logits = self._run_program(f"verify-{kb}", *args, lanes=n_lanes,
                                   attended=attended,
                                   positions=int(end.sum() - start.sum()))
        with observe.span("serve.tick.emit", category="serve",
                          program=f"verify-{kb}") as sp:
            dt = time.perf_counter() - t_step
            ledger_on = reqledger.enabled()
            total_emitted = 0
            for slot in slots:
                lane = self.active.get(slot)
                if lane is None:  # pragma: no cover — nothing retires mid-loop
                    continue
                d = drafts.get(slot, [])
                rows = logits[slot]  # [kb+1, vocab]
                accepted = 0
                emitted: List[int] = []
                for i, guess in enumerate(d):
                    t = int(np.argmax(rows[i]))
                    emitted.append(t)
                    if t != guess:
                        break  # first wrong draft; t is the corrected token
                    accepted += 1
                if accepted == len(d):
                    # Clean sweep: the last verified position yields one
                    # bonus token for free.
                    emitted.append(int(np.argmax(rows[len(d)])))
                self.spec_drafted += len(d)
                self.spec_accepted += accepted
                if d:
                    # Per-lane k adaptation on the trailing outcome: grow
                    # back toward the configured cap on a clean sweep,
                    # back off when under half the draft survived.
                    if accepted == len(d):
                        lane.spec_k = min(lane.spec_k + 1, self.scfg.spec_k)
                    elif accepted * 2 < len(d):
                        lane.spec_k = max(1, lane.spec_k - 1)
                if ledger_on:
                    reqledger.on_spec(lane.req.rid, drafted=len(d),
                                      accepted=accepted,
                                      emitted=len(emitted),
                                      n_lanes=n_lanes, replica=self.slo.name)
                # Token-level rollback: the verify tick wrote K/V for
                # every position in [length, length+len(d)]; positions
                # past the accepted prefix hold rejected-draft state —
                # retract them so the cache is bitwise what plain decode
                # would have built before the next tick can read it.
                self.kv.rollback(lane.seq_id, lane.length + accepted + 1)
                for i, tok in enumerate(emitted):
                    lane.length += 1
                    self._emit(lane, tok, rows[i])
                    total_emitted += 1
                    if lane.slot not in self.active:
                        break  # retired (eos / budget); KV already freed
            self.spec_verify_ticks += 1
            if total_emitted:
                # Every token delivered this tick took the tick's wall
                # time (they arrive together — that IS the speedup): one
                # sample per token, the plain path's weighting contract.
                self._tok_hist.observe(dt, n=total_emitted)
                self.slo.observe_token_latency(dt, n=total_emitted)
            sp.set(tokens=total_emitted)
            self._decode_steps.inc()
            self._attended.inc(attended)
            self._lane_ticks.inc(n_lanes)

    def _emit(self, lane: _Lane, token: int,
              logits: "np.ndarray | _TickRow") -> None:
        """Hand ``token`` over for ``lane``; its ``logits`` are read only
        if that ends the lane (``final_logits``)."""
        lane.generated.append(token)
        if self._drafter is not None:
            # One (order-gram -> token) pair per emitted token: the
            # lane's own stream is the drafter's best predictor of the
            # lane's future (greedy decode is deterministic).
            seq = lane.req.tokens + lane.generated
            self._drafter.observe(seq[-(self._drafter.order + 1):])
        # Recompute preemption replays a requeued request from scratch
        # (greedy decode regenerates the SAME prefix); positions the
        # client already received must not stream twice, and the
        # tokens_per_s gauge counts DELIVERED tokens, not redone work.
        pos = len(lane.generated)
        rid = lane.req.rid
        if pos > self._delivered.get(rid, 0):
            self._delivered[rid] = pos
            self._tokens_out += 1
            if self.on_token is not None:
                self.on_token(rid, token)
        req = lane.req
        done = (
            (req.eos_id is not None and token == req.eos_id)
            or len(lane.generated) >= req.max_new_tokens
            or lane.length >= self.scfg.max_context
        )
        if done:
            self._retire(lane, logits)

    def _retire(self, lane: _Lane, logits: "np.ndarray | _TickRow") -> None:
        self.kv.free(lane.seq_id)
        self.active.pop(lane.slot, None)
        self._discarded.inc(lane.ahead)  # an eos found after its next tick
        self._delivered.pop(lane.req.rid, None)
        self.results[lane.req.rid] = list(lane.generated)
        self.final_logits[lane.req.rid] = np.asarray(logits, np.float32)
        observe.counter("tdx.serve.requests_completed").inc()
        reqledger.on_finish(lane.req.rid, replica=self.slo.name,
                            tokens=len(lane.generated))
        if self.on_complete is not None:
            self.on_complete(lane.req.rid, list(lane.generated),
                             self.final_logits[lane.req.rid])

    def _preempt(self, slot: int, *, reason: str) -> None:
        """Evict a lane and requeue its whole request at the queue front
        (recompute policy: greedy decode regenerates identically)."""
        lane = self.active.pop(slot)
        self.kv.free(lane.seq_id)
        self.waiting.appendleft(lane.req)
        observe.counter("tdx.serve.preempted_requests").inc()
        observe.instant("serve.preempt", category="serve",
                        rid=lane.req.rid, reason=reason,
                        step=self._step_no,
                        flow=reqledger.flow_id(lane.req.rid))
        reqledger.on_abort(lane.req.rid, replica=self.slo.name,
                           reason=reason)
        # Fault-driven preemptions already dumped at the step level with
        # the full batch context; page-exhaustion preemptions dump here
        # (throttled per reason inside the recorder).
        if reason != "fault":
            observe.flight_dump(
                "serve_preempt", rid=lane.req.rid, preempt_reason=reason,
                step=self._step_no, pages_in_use=self.kv.pages_in_use,
            )

    # -- telemetry ----------------------------------------------------------

    def _gauges(self) -> None:
        if not observe.enabled():
            return
        observe.gauge("tdx.serve.queue_depth").set(len(self.waiting))
        observe.gauge("tdx.serve.active_requests").set(len(self.active))
        if self._t0 is not None:
            dt = time.perf_counter() - self._t0
            if dt > 0:
                observe.gauge("tdx.serve.tokens_per_s").set(
                    round(self._tokens_out / dt, 3)
                )
        # Live prefix-sharing state (docs/observability.md §Serving):
        # visible on /metrics without a bench run.
        observe.gauge("tdx.serve.prefix_nodes").set(self.prefix.page_count())
        observe.gauge("tdx.serve.prefix_hit_rate").set(
            round(self.prefix.hit_rate(), 4))
        if self.spec_drafted:
            # Speculative-decoding economics (docs/observability.md):
            # drafted/accepted totals plus the realized accept rate —
            # the fraction of proposed tokens the verify tick kept.
            observe.gauge("tdx.serve.spec_drafted").set(self.spec_drafted)
            observe.gauge("tdx.serve.spec_accepted").set(self.spec_accepted)
            observe.gauge("tdx.serve.spec_accept_rate").set(
                round(self.spec_accepted / self.spec_drafted, 4))
        self.kv.publish_gauges()
        if reqledger.enabled():
            reqledger.occupancy_sample(
                replica=self.slo.name,
                decode_busy=len(self.active),
                decode_lanes=self.scfg.max_batch,
                kv_pages_free=self.kv.free_pages,
                kv_pages_shared=self.kv.shared_pages,
                prefix_hit_rate=self.prefix.hit_rate(),
                queue_depth=len(self.waiting),
            )
        # Percentile publication sorts the windows — cheap, but not
        # per-tick cheap; refresh every 32 ticks and whenever the loop
        # drains (the periodic exporter also republishes on its own
        # clock regardless of tick rate).
        if self._step_no % 32 == 0 or not (self.waiting or self.active):
            self.slo.publish()


def _wait_traced(program: str, value) -> None:
    """The last wait of a program call, ``serve.program.wait``: for
    ``value`` to be ready while telemetry is on; off, the span is the
    shared no-op and nothing waits (the host's next read of ``value``
    does, where it reads one)."""
    with observe.span("serve.program.wait", category="serve",
                      program=program) as sp:
        sp.block_on(value)


def _read_late(program: str, value) -> np.ndarray:
    """Bring ``value`` to the host, the result of a call dispatched before
    another one was (docs/serving.md §Decoding ahead): its copy was queued
    when the call was dispatched, so what the host waits for is the call.
    ``serve.program.wait`` (``ahead`` 1) around ``serve.tick.d2h``, traced
    or not the same one wait."""
    with observe.span("serve.program.wait", category="serve",
                      program=program, ahead=1):
        with observe.span("serve.tick.d2h", category="serve",
                          program=program, bytes=value.nbytes):
            return np.asarray(value)


# ---------------------------------------------------------------------------
# replica bring-up + oracle
# ---------------------------------------------------------------------------


def spin_up_replica(
    model: "str | TransformerConfig" = "tiny",
    *,
    family: Optional[str] = None,
    serve_cfg: Optional[ServeConfig] = None,
    mesh=None,
    plan=None,
    seed: int = 0,
    param_dtype=None,
    sample_len: int = 8,
    warm: bool = True,
    on_token=None,
    on_complete=None,
    on_cancel=None,
    health_component: str = "serve",
    slo_name: str = "serve",
) -> ServeEngine:
    """Bring up one serving replica: ``deferred_init`` the model (fakes,
    zero storage) → compile/fetch the init program through the artifact
    registry → materialize params (sharded onto ``mesh`` when given) →
    compile/fetch the prefill + decode programs.  With a pre-warmed
    registry every one of these is a cache fetch, not an XLA compile —
    the autoscaling bring-up contract (docs/serving.md).

    ``model`` is a zoo preset name (family inferred from it) or a
    :class:`TransformerConfig` (then pass ``family``).

    ``health_component`` / ``slo_name`` namespace the bring-up state
    machine and latency windows per replica — the fleet controller
    (:mod:`.fleet`) passes ``fleet/rN`` / ``serve-rN`` so ``/readyz``
    and ``/slo`` can tell replicas apart; a standalone replica keeps the
    historical ``serve`` names.
    """
    if isinstance(model, str):
        cfg = PRESETS[model]
        if not isinstance(cfg, TransformerConfig):
            raise ValueError(f"preset {model!r} is not a decoder LM")
        family = family or model_family(model)
    else:
        cfg = model
        family = family or "llama"
    t0 = time.perf_counter()
    # Bring-up state machine behind /readyz (observe.health): a load
    # balancer must not route here until the program set is
    # compiled/fetched and warm.
    observe.health.set_state(health_component, "spin_up")
    with observe.span(
        "serve.spin_up", category="serve", family=family,
        warm=bool(warm),
    ) as sp:
        # Children for what a clock around the bring-up cannot split:
        # the deferred-init trace that builds the specs, the init
        # program's load (compile or cache fetch), its run, the engine
        # with its pools, and the program set's warm-up.
        with observe.span("serve.spin_up.specs", category="serve"):
            specs = serve_program_specs(
                family, cfg, serve_cfg, seed=seed, param_dtype=param_dtype,
                mesh=mesh, plan=plan, sample_len=sample_len,
            )
        init = specs[0]
        assert init.name == "init"
        with observe.span("serve.spin_up.init_load", category="serve"):
            compiled, init_outcome = compile_serving_program(init)
        with observe.span("serve.spin_up.init_run", category="serve"):
            values = compiled()
            if init.tplan is not None:
                # Low-precision transport (TDX_MATERIALIZE_INIT_DTYPE): the
                # init program delivered eligible params in the init dtype;
                # upcast them on device to the contract dtypes the lowered
                # prefill/decode signatures expect (donated staging buffers,
                # same retry contract as the materialization engines).
                from .. import config as _tdx_config

                cfg_eff = _tdx_config.get()
                values, _donated = transport.commit_outputs(
                    values, init.tplan,
                    donate=cfg_eff.materialize_donate,
                    producer=lambda: compiled(),
                    retries=max(0, cfg_eff.materialize_retries),
                    retryable=compile_service.retryable_errors(),
                )
            params = jax.tree.unflatten(init.treedef, list(values))
            jax.block_until_ready(values)
        with observe.span("serve.spin_up.pools", category="serve") as psp:
            engine = ServeEngine(
                family, cfg, params, serve_cfg=serve_cfg, mesh=mesh,
                plan=plan, seed=seed, param_dtype=param_dtype,
                on_token=on_token, on_complete=on_complete,
                on_cancel=on_cancel, slo_name=slo_name,
            )
            psp.block_on((engine.k_pages, engine.v_pages, engine.state))
            windowed = engine.kv.cfg.window is not None
            psp.set(pool_bytes=engine.k_pages.nbytes + engine.v_pages.nbytes,
                    state_bytes=sum(a.nbytes for a in engine.state
                                    if not windowed),
                    window_pool_bytes=(engine.state[0].nbytes if windowed
                                       else 0))
        # The spec list above already paid the model's deferred-init
        # trace; hand it to the engine so warmup/lazy compiles reuse it.
        engine._spec_cache = {s.name: s for s in specs if s.name != "init"}
        outcomes = {"init": init_outcome}
        observe.health.set_state(health_component, "warming")
        if warm:
            with observe.span("serve.spin_up.warmup", category="serve"):
                outcomes.update(engine.warmup())
        engine.bring_up_outcomes = outcomes
        engine.bring_up_seconds = time.perf_counter() - t0
        observe.health.set_state(health_component, "serving")
        sp.set(seconds=round(engine.bring_up_seconds, 3), **{
            f"cache_{k}": v for k, v in outcomes.items()
        })
    return engine


@functools.lru_cache(maxsize=16)
def _oracle_forward(family: str, cfg: TransformerConfig):
    """The stock model's forward over one sequence, compiled once a
    length: the last position's logits."""
    model = make_model(family, cfg)
    return jax.jit(lambda params, toks: model.apply(params, toks)[0, -1])


def oracle_generate(
    family: str,
    cfg: TransformerConfig,
    params,
    prompt: Sequence[int],
    max_new_tokens: int,
    eos_id: Optional[int] = None,
):
    """The no-batching, no-cache greedy oracle: full forward over the
    growing sequence through the stock flax model, argmax each step.
    Returns ``(generated_tokens, final_step_logits)`` — what the engine
    must reproduce for the same request, whatever batching, paging,
    preemption, or faults happened along the way.

    Each step is one compiled forward at the sequence's exact length
    (no padding, so every reduction runs over what the stock model
    reads), held per ``(family, cfg)``: a length seen before compiles
    nothing."""
    forward = _oracle_forward(family, cfg)
    toks = list(prompt)
    out: List[int] = []
    logits_last = None
    for _ in range(max_new_tokens):
        logits_last = np.asarray(
            forward(params, np.asarray([toks], np.int32)), np.float32)
        t = int(np.argmax(logits_last))
        out.append(t)
        toks.append(t)
        if eos_id is not None and t == eos_id:
            break
        if len(toks) >= cfg.max_seq_len:
            break
    return out, logits_last
