"""Paged KV-cache: fixed-size pages in a preallocated device pool.

The serving engine's memory manager.  Instead of one contiguous
[B, max_seq, KV, D] cache per sequence (whose worst-case reservation is
what kills batch size), K/V live in a pool of fixed-size **pages**
([n_pages, kv_heads, page_size, head_dim] per layer, allocated once at
replica bring-up), and each sequence owns an ordered list of page ids —
its **page table**.  Admission cost is ``ceil(len / page_size)`` pages,
growth is one page at a time, retirement returns pages to the free list
immediately for waiting requests; external fragmentation is zero by
construction and internal fragmentation is bounded by one page per
sequence (the vLLM/PagedAttention memory model, arXiv:2604.15464's
layout).

Split of responsibilities:

* **host side (this class)** — the free list, per-sequence page tables,
  alloc/extend/free, and the occupancy / fragmentation gauges.  Pure
  Python bookkeeping; every mutation is O(pages touched).
* **device side** — the pools themselves are jax arrays owned by the
  engine and threaded *functionally* through the compiled prefill /
  decode programs (which scatter new K/V into pages and gather context
  through the page table via :func:`torchdistx_tpu.ops.paged_attention`).

Page 0 is reserved as the **null page**: batch-padding slots and
prompt-padding positions route their writes there, so padded lanes of a
fixed-shape program never touch a live sequence's memory and need no
masking in the scatter.  The null page is never handed out and never
read (idle lanes carry ``length == 0``).

**Prefix sharing** (:mod:`.prefix`) makes pages multi-reader: every
allocated page carries a host-side **refcount** — one reference per
live page table that maps it plus one per prefix-cache node that holds
it.  :meth:`PagedKVCache.alloc_shared` admits a sequence whose leading
pages are another prompt's already-written prefix (the shared pages'
refcounts rise, only the suffix allocates fresh pages);
:meth:`PagedKVCache.free` decrements and returns a page to the free
list only when its count hits zero; and :meth:`PagedKVCache.cow_page`
is the copy-on-write step — a sequence about to WRITE into a page it
shares swaps in a fresh page first (the engine device-copies the
contents), so no reader of a shared page ever observes a mutation.

**Two kinds of state** (the start of ROADMAP D7).  A cache is described
by its layer groups: the attention layers' pages above, and, for a
stack with recurrent layers (``models/jamba.py``,
``models/olmo_hybrid.py``), a :class:`StateCacheConfig`: one **slot** a
batch lane holding that lane's recurrent state in every recurrent
(Mamba or Gated DeltaNet) layer, of a fixed size whatever the context.  A slot is not paged and not shared: it is bound to a sequence
when the sequence is allocated (``alloc(..., slot=lane)``) and dropped
with it (:meth:`PagedKVCache.free`) — there is nothing to return to a
free list, and nothing of it survives a preemption, so a preempted
request prefills again from its tokens.  The device arrays
(:func:`init_state`) are the engine's, like the pools; the FIRST
program that writes a slot for a new sequence starts from zero whatever
the slot held (``serve/programs.py``), which is what "zeroed on
admission" means here, and each binding counts one
``tdx.serve.state_resets``.

**A third kind: the window group** (D7's third row).  A stack whose
attention is windowed in some layers (``models/afmoe.py``) keeps those
layers' keys and values in a pool of their own
(:class:`WindowCacheConfig`, :func:`init_window_pool`), with its own
free list and its own null page 0, because what a sequence holds there
stops growing: a query at position ``t`` reads positions ``(t - window,
t]``, so a page whose last position lies behind that is returned to the
free list WHILE THE SEQUENCE LIVES (:meth:`PagedKVCache.window_advance`;
:meth:`PagedKVCache.extend` does it for the one position of a decode
tick).  A decoding sequence holds at most ``window / page_size + 1``
pages there whatever its context; a prefill chunk of ``n`` positions
holds ``(window + n) / page_size + 1`` while it runs.  The table row of
the group (:meth:`PagedKVCache.window_rows`) holds the LIVE pages only,
first live page first, and comes with the position of that page's
first token, from which the programs count positions in the row.  A
released page's content is gone: nothing of the group can be shared
with a later reader, and a preempted sequence prefills again.

**A latent group** (:attr:`KVCacheConfig.latent`): a stack of latent
attention (``models/mla.py``) caches one row a token and layer, the
normed latent and the rotary key that every head shares, and no values:
a value is read from the same row (its first ``kv_rank`` columns).  Its
pool is the K pool with one "head" as wide as the row, ``[L, P, 1, page,
row]``, and the V pool is empty (no page: zero bytes, so the programs
carry it as every family does).  The free list, the page tables, the
null page and the gauges are the full group's.

Telemetry (docs/observability.md): ``tdx.serve.kv_pages_in_use``,
``tdx.serve.kv_occupancy`` (used token slots / allocated slots in live
pages — the internal-fragmentation complement),
``tdx.serve.kv_pool_pages``, ``tdx.serve.kv_pages_free``, and
``tdx.serve.kv_pages_shared`` (refcount > 1 — the live copy-on-write
exposure) gauges, refreshed by :meth:`PagedKVCache.publish_gauges` (the
engine calls it once a step, not on every mutation); with a state group also
``tdx.serve.state_slots_in_use`` and ``tdx.serve.state_slots_peak``
(gauges) and ``tdx.serve.state_resets`` (counter, always on); with a
window group ``tdx.serve.window_pages_in_use`` and
``tdx.serve.window_pages_peak`` (gauges) and
``tdx.serve.window_pages_released`` (counter, always on: pages returned
behind a live sequence's window).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from .. import observe

__all__ = ["KVCacheConfig", "OutOfPages", "PagedKVCache", "StateCacheConfig",
           "WindowCacheConfig", "init_pools", "init_state",
           "init_window_pool", "pool_sharding", "state_sharding"]


class OutOfPages(RuntimeError):
    """The pool cannot satisfy an alloc/extend; the engine responds by
    deferring admission or preempting a sequence, never by failing the
    request."""


@dataclass(frozen=True)
class StateCacheConfig:
    """The recurrent layer group of a cache: ``lanes`` slots, each the
    state of one sequence in all ``n_layers`` recurrent layers.  The
    state is float32 ``[L, lanes, d_state, d_inner]`` — its wide side
    minor: a Mamba layer's ``[16, d_inner]`` (a minor dim of ``d_state``
    = 16 would pad to the chip's 128 lanes, eight times the bytes), a
    Gated DeltaNet layer's ``[d_k, H * d_v]`` (a minor dim of ``d_v`` =
    192 would pad to 256) — and the conv tail (the last ``d_conv - 1``
    inputs of the depthwise conv) ``[L, d_conv-1, lanes, conv_channels]``
    in the activation dtype, lanes second-minor for the same reason.
    The conv runs over ``d_inner`` channels in a Mamba layer and over
    q, k and v's (``2 H d_k + H d_v``) in a delta-rule layer:
    ``conv_channels`` None is ``d_inner``."""

    n_layers: int
    d_inner: int
    d_state: int
    d_conv: int
    lanes: int
    conv_channels: Optional[int] = None

    def ssm_shape(self) -> Tuple[int, int, int, int]:
        return (self.n_layers, self.lanes, self.d_state, self.d_inner)

    def conv_shape(self) -> Tuple[int, int, int, int]:
        return (self.n_layers, self.d_conv - 1, self.lanes,
                self.conv_channels or self.d_inner)


@dataclass(frozen=True)
class WindowCacheConfig:
    """The window layer group of a cache: ``n_layers`` layers whose
    queries read the last ``window`` positions only, in a pool of
    ``n_pages`` pages (page 0 the group's null page) of the cache's page
    size and heads.  ONE array holds keys and values, ``[2 * n_layers,
    n_pages, KV, page, D]``: layer ``j``'s keys at row ``j``, its values
    at ``n_layers + j``, so that a page's values lie ``n_layers *
    n_pages`` flat rows behind its keys.  ``max_pages_per_seq`` is the
    width of the group's table row: the pages a prefill chunk's window
    can span."""

    n_layers: int
    window: int
    n_pages: int
    max_pages_per_seq: int

    @property
    def usable_pages(self) -> int:
        return self.n_pages - 1


@dataclass(frozen=True)
class KVCacheConfig:
    """Shape of the cache, by layer group: the attention layers' pool
    (one K and one V pool over ``n_layers`` layers: the layers that read
    the whole context; with ``latent``, one pool of rows ``head_dim`` wide
    under one kv head, and an empty V pool), where the stack has
    recurrent layers their ``state`` group, and where it has windowed
    attention layers their ``window`` group."""

    n_layers: int
    kv_heads: int
    head_dim: int
    page_size: int = 16
    n_pages: int = 64  # includes the reserved null page 0
    state: Optional[StateCacheConfig] = None
    window: Optional[WindowCacheConfig] = None
    latent: bool = False

    @property
    def usable_pages(self) -> int:
        return self.n_pages - 1

    @property
    def tokens_capacity(self) -> int:
        """Token slots available to live sequences (null page excluded)."""
        return self.usable_pages * self.page_size

    def pages_for(self, n_tokens: int) -> int:
        """Pages needed to hold ``n_tokens`` of context."""
        return max(0, -(-n_tokens // self.page_size))

    def pool_shape(self) -> Tuple[int, int, int, int, int]:
        """[L, P, KV, page, D] — the per-pool (K or V) array shape.
        Token rows and head dim are the minor dims: the layout the
        decode kernel's (page, kv head) block needs to be Mosaic-legal
        (:mod:`torchdistx_tpu.ops.paged_attention`)."""
        return (self.n_layers, self.n_pages, self.kv_heads,
                self.page_size, self.head_dim)

    def v_pool_shape(self) -> Tuple[int, int, int, int, int]:
        """The V pool's shape: the K pool's, or no page at all in a latent
        group, whose values are the first columns of its rows."""
        L, P, KV, page, D = self.pool_shape()
        return (L, 0 if self.latent else P, KV, page, D)

    def window_pool_shape(self) -> Tuple[int, int, int, int, int]:
        """[2 * Lw, Pw, KV, page, D]: the window group's one array."""
        w = self.window
        return (2 * w.n_layers, w.n_pages, self.kv_heads, self.page_size,
                self.head_dim)


@dataclass
class _Seq:
    pages: List[int] = field(default_factory=list)
    length: int = 0  # tokens currently stored
    # The window group: the live pages, and which page of the sequence
    # the first of them is (pages behind it went back to the free list).
    wpages: List[int] = field(default_factory=list)
    wfirst: int = 0


class PagedKVCache:
    """Host-side page allocator: free list + per-sequence page tables.

    The device pools are NOT stored here (the engine owns them and
    threads them through its compiled programs); :meth:`pool_shape` and
    :func:`init_pools` build them.
    """

    def __init__(self, cfg: KVCacheConfig):
        if cfg.n_pages < 2:
            raise ValueError(
                f"n_pages must be >= 2 (page 0 is the reserved null "
                f"page), got {cfg.n_pages}"
            )
        if cfg.page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {cfg.page_size}")
        self.cfg = cfg
        # LIFO free list: recently-freed pages are reused first (their
        # pool slices are most likely still warm in device caches).
        self._free: List[int] = list(range(cfg.n_pages - 1, 0, -1))
        self._seqs: Dict[int, _Seq] = {}
        # Per-page refcounts: one reference per live page table mapping
        # the page, plus one per prefix-cache node holding it.  A page
        # returns to the free list only at refcount zero.
        self._ref: Dict[int, int] = {}
        # The recurrent group's slots: sequence -> the lane whose slot
        # holds its state (empty for a cache of pages only).
        self._slot_of: Dict[int, int] = {}
        self.state_slots_peak = 0
        self._resets = observe.counter("tdx.serve.state_resets")
        # The window group's own free list (its page 0 is its null page).
        self._wfree: List[int] = [] if cfg.window is None else list(
            range(cfg.window.n_pages - 1, 0, -1))
        self.window_pages_peak = 0
        self._wreleased = observe.counter("tdx.serve.window_pages_released")

    # -- queries ------------------------------------------------------------

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return self.cfg.usable_pages - len(self._free)

    @property
    def shared_pages(self) -> int:
        """Pages with more than one reference (prefix-shared right
        now) — the live copy-on-write exposure."""
        return sum(1 for v in self._ref.values() if v > 1)

    @property
    def state_slots_in_use(self) -> int:
        return len(self._slot_of)

    @property
    def window_free_pages(self) -> int:
        return len(self._wfree)

    @property
    def window_pages_in_use(self) -> int:
        w = self.cfg.window
        return 0 if w is None else w.usable_pages - len(self._wfree)

    def window_page_ids(self, seq_id: int) -> List[int]:
        return list(self._seqs[seq_id].wpages)

    def state_slot(self, seq_id: int) -> int:
        """The slot that holds the sequence's recurrent state."""
        return self._slot_of[seq_id]

    def length(self, seq_id: int) -> int:
        return self._seqs[seq_id].length

    def page_ids(self, seq_id: int) -> List[int]:
        return list(self._seqs[seq_id].pages)

    def has(self, seq_id: int) -> bool:
        return seq_id in self._seqs

    def ref(self, page: int) -> int:
        """The page's current refcount (0 for free/unknown pages)."""
        return self._ref.get(page, 0)

    def occupancy(self) -> float:
        """Used token slots / allocated slots in live pages (1.0 = no
        internal fragmentation; 0.0 when nothing is allocated)."""
        alloc = sum(len(s.pages) for s in self._seqs.values())
        if not alloc:
            return 0.0
        used = sum(s.length for s in self._seqs.values())
        return used / (alloc * self.cfg.page_size)

    def fragmentation(self) -> float:
        """Wasted fraction of allocated slots (``1 - occupancy`` over
        live pages): the tail-page waste bound the paged layout trades
        for zero external fragmentation."""
        return 0.0 if not self._seqs else 1.0 - self.occupancy()

    def can_fit(self, n_tokens: int,
                window_tokens: Optional[int] = None) -> bool:
        """Whether a new sequence of ``n_tokens`` fits: its pages in the
        full group and, with a window group, the pages of its first
        ``window_tokens`` positions there (its first prefill chunk; all
        of it by default)."""
        if self.cfg.pages_for(n_tokens) > len(self._free):
            return False
        if self.cfg.window is None:
            return True
        first = n_tokens if window_tokens is None else min(
            n_tokens, window_tokens)
        return self.cfg.pages_for(first) <= len(self._wfree)

    # -- mutations ----------------------------------------------------------

    def _bind_slot(self, seq_id: int, slot: Optional[int]) -> None:
        """Give the new sequence its recurrent-state slot (a cache with
        a state group only; else ``slot`` is not looked at).  The slot's
        old contents are dead from here on: the sequence's first program
        call starts from zero."""
        if self.cfg.state is None:
            return
        if slot is None or not (0 <= slot < self.cfg.state.lanes):
            raise ValueError(
                f"sequence {seq_id} needs a state slot in "
                f"[0, {self.cfg.state.lanes}), got {slot!r}")
        if slot in self._slot_of.values():
            raise ValueError(f"state slot {slot} is held by a live sequence")
        self._slot_of[seq_id] = slot
        self.state_slots_peak = max(self.state_slots_peak,
                                    len(self._slot_of))
        self._resets.inc()

    def alloc(self, seq_id: int, n_tokens: int,
              slot: Optional[int] = None) -> List[int]:
        """Allocate pages for a new sequence holding ``n_tokens`` (and,
        with a state group, bind recurrent-state slot ``slot`` to it);
        returns its page ids.  Raises :class:`OutOfPages` (allocating
        nothing) when the free list cannot cover it."""
        if seq_id in self._seqs:
            raise ValueError(f"sequence {seq_id} already allocated")
        need = self.cfg.pages_for(n_tokens)
        if need > len(self._free):
            raise OutOfPages(
                f"need {need} pages for {n_tokens} tokens, "
                f"{len(self._free)} free"
            )
        self._bind_slot(seq_id, slot)
        pages = [self._free.pop() for _ in range(need)]
        for p in pages:
            self._ref[p] = 1
        self._seqs[seq_id] = _Seq(pages=pages, length=n_tokens)
        return list(pages)

    def alloc_shared(self, seq_id: int, shared_pages: Sequence[int],
                     n_tokens: int) -> List[int]:
        """Allocate a sequence whose LEADING pages are another prompt's
        already-written prefix: the shared pages' refcounts rise (their
        contents are never rewritten without :meth:`cow_page`), fresh
        pages cover only the suffix.  Returns the full page table.
        Raises :class:`OutOfPages` changing nothing when the free list
        cannot cover the suffix."""
        if seq_id in self._seqs:
            raise ValueError(f"sequence {seq_id} already allocated")
        if self.cfg.state is not None:
            raise ValueError(
                "a cache with a recurrent state group shares no pages: a "
                "prefix's pages hold no state to resume from")
        if self.cfg.window is not None:
            raise ValueError(
                "a cache with a window group shares no pages: a prefix's "
                "window pages are gone once its first reader has moved on")
        shared = list(shared_pages)
        need = self.cfg.pages_for(n_tokens) - len(shared)
        if need < 0:
            raise ValueError(
                f"{len(shared)} shared pages exceed the "
                f"{self.cfg.pages_for(n_tokens)} pages {n_tokens} tokens "
                f"need"
            )
        for p in shared:
            if self._ref.get(p, 0) < 1:
                raise ValueError(f"shared page {p} is not allocated")
        if need > len(self._free):
            raise OutOfPages(
                f"need {need} fresh pages for {n_tokens} tokens "
                f"({len(shared)} shared), {len(self._free)} free"
            )
        for p in shared:
            self._ref[p] += 1
        fresh = [self._free.pop() for _ in range(need)]
        for p in fresh:
            self._ref[p] = 1
        self._seqs[seq_id] = _Seq(pages=shared + fresh, length=n_tokens)
        return shared + fresh

    def retain(self, pages: Iterable[int]) -> None:
        """Add one reference to each page (the prefix cache holding a
        prompt's pages past the sequence's lifetime)."""
        for p in pages:
            if self._ref.get(p, 0) < 1:
                raise ValueError(f"cannot retain free page {p}")
            self._ref[p] += 1

    def release(self, pages: Iterable[int]) -> int:
        """Drop one reference from each page, returning those that hit
        zero to the free list; returns how many pages were freed."""
        freed = []
        for p in pages:
            n = self._ref.get(p, 0)
            if n < 1:
                raise ValueError(f"cannot release free page {p}")
            if n == 1:
                del self._ref[p]
                freed.append(p)
            else:
                self._ref[p] = n - 1
        if freed:
            self._free.extend(reversed(freed))
        return len(freed)

    def cow_page(self, seq_id: int,
                 page_index: int) -> Optional[Tuple[int, int]]:
        """Copy-on-write: the sequence is about to WRITE into the page at
        ``page_index`` of its table.  Exclusively-owned pages need
        nothing (returns ``None``); a shared page is swapped for a fresh
        one — the caller must device-copy src → dst before writing —
        and the caller's reference moves to the copy.  Returns
        ``(src, dst)`` page ids, or raises :class:`OutOfPages` (changing
        nothing) when no fresh page is free."""
        seq = self._seqs[seq_id]
        src = seq.pages[page_index]
        if self._ref[src] == 1:
            return None
        if not self._free:
            raise OutOfPages(
                f"sequence {seq_id} needs a copy-on-write page, 0 free"
            )
        dst = self._free.pop()
        self._ref[src] -= 1
        self._ref[dst] = 1
        seq.pages[page_index] = dst
        return src, dst

    def window_advance(self, seq_id: int, start: int, end: int) -> int:
        """Make the window group hold the pages that the program writing
        positions ``[start, end)`` of ``seq_id`` needs — those of
        positions ``(start - window, end)`` — returning the pages that
        now lie wholly behind the window to the free list first.  Returns
        how many were returned.  Raises :class:`OutOfPages`, changing
        nothing, when the free list (with what would be returned) cannot
        cover the rest; a no-op for a cache without the group."""
        w = self.cfg.window
        if w is None:
            return 0
        seq, page = self._seqs[seq_id], self.cfg.page_size
        first = max(0, start - w.window + 1) // page
        behind = min(max(0, first - seq.wfirst), len(seq.wpages))
        kept = len(seq.wpages) - behind
        # With nothing live left the row restarts at the window's first page.
        new_first = seq.wfirst + behind if kept else max(seq.wfirst, first)
        add = max(0, (end - 1) // page + 1 - (new_first + kept))
        if kept + add > w.max_pages_per_seq:
            raise ValueError(
                f"sequence {seq_id}: positions [{start}, {end}) and their "
                f"window span {kept + add} pages, the group's table row "
                f"holds {w.max_pages_per_seq}")
        if add > len(self._wfree) + behind:
            raise OutOfPages(
                f"sequence {seq_id} needs {add} more window pages, "
                f"{len(self._wfree)} free and {behind} to return")
        if behind:
            self._wfree.extend(reversed(seq.wpages[:behind]))
            del seq.wpages[:behind]
            self._wreleased.inc(behind)
        seq.wfirst = new_first
        seq.wpages.extend(self._wfree.pop() for _ in range(add))
        if behind or add:
            self.window_pages_peak = max(self.window_pages_peak,
                                         self.window_pages_in_use)
        return behind

    def extend(self, seq_id: int, new_length: int) -> List[int]:
        """Grow ``seq_id`` to hold ``new_length`` tokens, allocating at
        most the pages the growth needs; returns the pages ADDED to the
        full group.  With a window group the sequence's window moves with
        it (:meth:`window_advance` for the new positions): past the
        window the group's oldest page goes back to its free list.  On
        :class:`OutOfPages` nothing changes in either group — the engine
        preempts a victim and retries."""
        seq = self._seqs[seq_id]
        if new_length < seq.length:
            raise ValueError(
                f"extend cannot shrink: {seq.length} -> {new_length}"
            )
        need = self.cfg.pages_for(new_length) - len(seq.pages)
        if need > len(self._free):
            raise OutOfPages(
                f"sequence {seq_id} needs {need} more pages, "
                f"{len(self._free)} free"
            )
        if self.cfg.window is not None and new_length > seq.length:
            self.window_advance(seq_id, seq.length, new_length)
        added = [self._free.pop() for _ in range(max(0, need))]
        for p in added:
            self._ref[p] = 1
        seq.pages.extend(added)
        seq.length = new_length
        return added

    def rollback(self, seq_id: int, new_length: int) -> int:
        """Token-level rollback (speculative decoding): shrink ``seq_id``
        to ``new_length`` tokens, dropping THIS sequence's reference to
        every trailing page the shorter length no longer needs.  Dropped
        pages return to the free list at refcount zero; a trailing page
        some other reader still holds (COW sharing) merely loses this
        table's reference — the reader's contents are untouched.  The
        partial tail page is truncated by bookkeeping alone: positions
        past ``new_length`` are never attended (attention masks on
        length) and are overwritten before they are ever valid again, so
        after rollback the cache state is exactly what plain decode
        would have produced.  Returns how many pages left this table.
        The inverse edge of :meth:`extend`, which deliberately refuses
        to shrink."""
        seq = self._seqs[seq_id]
        if not (0 <= new_length <= seq.length):
            raise ValueError(
                f"rollback target {new_length} outside [0, {seq.length}]"
            )
        keep = self.cfg.pages_for(new_length)
        dropped = seq.pages[keep:]
        del seq.pages[keep:]
        seq.length = new_length
        if dropped:
            self.release(dropped)
        # The window group loses its trailing pages alike (what it gave
        # back behind the window stays gone: a rollback reaches a few
        # positions, never a window).
        wkeep = max(0, keep - seq.wfirst)
        self._wfree.extend(reversed(seq.wpages[wkeep:]))
        del seq.wpages[wkeep:]
        return len(dropped)

    def free(self, seq_id: int) -> int:
        """Retire a sequence, dropping one reference from each of its
        pages; pages whose refcount hits zero return to the free list
        (shared prefix pages survive for their other readers).  Returns
        how many pages were actually freed.  Unknown ids are a no-op
        (retire paths race with preemption paths by design)."""
        seq = self._seqs.pop(seq_id, None)
        if seq is None:
            return 0
        self._slot_of.pop(seq_id, None)  # the state is dropped, not saved
        self._wfree.extend(reversed(seq.wpages))
        freed = []
        for p in seq.pages:
            if self._ref[p] == 1:
                del self._ref[p]
                freed.append(p)
            else:
                self._ref[p] -= 1
        self._free.extend(reversed(freed))
        return len(freed)

    def reset(self) -> None:
        """Free every sequence and every outstanding reference (replica
        drain): one free-list rebuild, not N :meth:`free` calls."""
        self._seqs.clear()
        self._ref.clear()
        self._slot_of.clear()
        self._free = list(range(self.cfg.n_pages - 1, 0, -1))
        if self.cfg.window is not None:
            self._wfree = list(range(self.cfg.window.n_pages - 1, 0, -1))

    # -- batch views --------------------------------------------------------

    def table_row(self, seq_id: int, max_pages: int) -> List[int]:
        """The sequence's page table padded with the null page to a
        fixed-width row (the decode program's [B, max_pages] operand)."""
        pages = self._seqs[seq_id].pages
        if len(pages) > max_pages:
            raise ValueError(
                f"sequence {seq_id} holds {len(pages)} pages > "
                f"max_pages={max_pages}"
            )
        return pages + [0] * (max_pages - len(pages))

    def table_rows(self, seq_ids: Sequence[int],
                   max_pages: int) -> np.ndarray:
        """The batched decode operand: one null-padded page-table row
        per sequence, built in a single pass ([len(seq_ids), max_pages]
        int32) instead of a per-lane Python loop on the decode tick."""
        rows = np.zeros((len(seq_ids), max_pages), np.int32)
        for i, sid in enumerate(seq_ids):
            pages = self._seqs[sid].pages
            if len(pages) > max_pages:
                raise ValueError(
                    f"sequence {sid} holds {len(pages)} pages > "
                    f"max_pages={max_pages}"
                )
            rows[i, :len(pages)] = pages
        return rows

    def window_rows(self, seq_ids: Sequence[int]):
        """The window group's operands for these sequences: (one
        null-padded row of LIVE pages each, ``[n, max_pages_per_seq]``
        int32; the position of each row's first token, ``[n]`` int32).
        A program counts a sequence's positions in the group from that
        first token."""
        w = self.cfg.window
        rows = np.zeros((len(seq_ids), w.max_pages_per_seq), np.int32)
        first = np.zeros((len(seq_ids),), np.int32)
        for i, sid in enumerate(seq_ids):
            seq = self._seqs[sid]
            rows[i, :len(seq.wpages)] = seq.wpages
            first[i] = seq.wfirst * self.cfg.page_size
        return rows, first

    # -- telemetry ----------------------------------------------------------

    def publish_gauges(self) -> None:
        """Set every gauge of the pool from its state now (telemetry on
        only).  The mutations leave the gauges alone: each ``set`` is an
        event in the tracer, and a decode tick makes a mutation a lane;
        the engine publishes once a step, under ``serve.gauges``."""
        if not observe.enabled():
            return
        observe.gauge("tdx.serve.kv_pages_in_use").set(self.pages_in_use)
        observe.gauge("tdx.serve.kv_pool_pages").set(self.cfg.usable_pages)
        observe.gauge("tdx.serve.kv_occupancy").set(round(self.occupancy(), 4))
        observe.gauge("tdx.serve.kv_pages_free").set(len(self._free))
        observe.gauge("tdx.serve.kv_pages_shared").set(self.shared_pages)
        if self.cfg.state is not None:
            observe.gauge("tdx.serve.state_slots_in_use").set(
                len(self._slot_of))
            observe.gauge("tdx.serve.state_slots_peak").set(
                self.state_slots_peak)
        if self.cfg.window is not None:
            observe.gauge("tdx.serve.window_pages_in_use").set(
                self.window_pages_in_use)
            observe.gauge("tdx.serve.window_pages_peak").set(
                self.window_pages_peak)


def pool_sharding(mesh, kv_heads: int, tp_axis: str = "tp"):
    """Where the pools live on a replica mesh — decided here, not left
    to sharding propagation: kv heads split over the tensor-parallel
    axis when it divides them (each tp shard then attends its own heads;
    :func:`..programs.build_decode_fn` runs the decode kernel per shard),
    replicated over every other axis.  None without a mesh."""
    if mesh is None:
        return None
    from jax.sharding import NamedSharding, PartitionSpec as P

    tp = mesh.shape.get(tp_axis, 1)
    if tp > 1 and kv_heads % tp == 0:
        return NamedSharding(mesh, P(None, None, tp_axis))
    return NamedSharding(mesh, P())


def state_sharding(mesh, d_inner: int, tp_axis: str = "tp"):
    """Where the recurrent state lives on a replica mesh: channels split
    over the tensor-parallel axis when it divides them (the mixer's
    ``in_proj`` columns are split the same way, ``models/plans.py``),
    replicated over every other axis.  None without a mesh.  Both arrays
    (:func:`init_state`) have the channels as their last dim."""
    if mesh is None:
        return None
    from jax.sharding import NamedSharding, PartitionSpec as P

    tp = mesh.shape.get(tp_axis, 1)
    if tp > 1 and d_inner % tp == 0:
        return NamedSharding(mesh, P(None, None, None, tp_axis))
    return NamedSharding(mesh, P())


def init_state(cfg: StateCacheConfig, dtype,
               sharding=None) -> Tuple["jax.Array", "jax.Array"]:
    """The zeroed recurrent state ``(ssm, conv)``: float32
    ``[L, lanes, d_state, d_inner]`` and ``dtype``
    ``[L, d_conv-1, lanes, d_inner]``."""
    import jax.numpy as jnp

    return (jnp.zeros(cfg.ssm_shape(), jnp.float32, device=sharding),
            jnp.zeros(cfg.conv_shape(), dtype, device=sharding))


def init_window_pool(cfg: KVCacheConfig, dtype, sharding=None) -> "jax.Array":
    """The zeroed window group, keys and values in one array
    (:class:`WindowCacheConfig`): ``[2 * Lw, Pw, KV, page, D]``."""
    import jax.numpy as jnp

    return jnp.zeros(cfg.window_pool_shape(), dtype, device=sharding)


def init_pools(cfg: KVCacheConfig, dtype,
               sharding=None) -> Tuple["jax.Array", "jax.Array"]:
    """The zeroed device pools (k_pages, v_pages), [L, P, KV, page, D]
    (a latent group's V pool has no page), committed to ``sharding``
    (:func:`pool_sharding`) when given."""
    import jax.numpy as jnp

    return (jnp.zeros(cfg.pool_shape(), dtype, device=sharding),
            jnp.zeros(cfg.v_pool_shape(), dtype, device=sharding))
