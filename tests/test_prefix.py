"""Prefix-sharing tests (ISSUE 16 tentpole): radix-tree prefix cache +
refcounted copy-on-write pages + chunked prefill.

Property layer (no device work): under random interleavings of
admit / extend / retire / preempt / evict / cow, every page's refcount
equals the number of live page tables referencing it plus the number of
prefix-tree nodes holding it; copy-on-write never swaps a page out from
under another reader; a drain leaves every refcount at zero.

Engine layer: a shared-prefix storm is bitwise-equal to the unbatched
oracle with sharing ON and OFF (with prefix hits > 0 in the ON arm);
the fully-cached page-aligned prompt exercises the one legal write into
a shared page through COW; a chaos fault BETWEEN prefill chunks
(``serve@N=raise:chunk``) requeues without leaking pages or corrupting
a shared prefix.
"""

import random
from collections import Counter

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchdistx_tpu import chaos, observe
from torchdistx_tpu.models import TransformerConfig
from torchdistx_tpu.serve import (
    KVCacheConfig,
    NgramDrafter,
    OutOfPages,
    PagedKVCache,
    PrefixCache,
    Request,
    ServeConfig,
    ServeEngine,
    oracle_generate,
    prefix_affinity,
    serve_program_specs,
)
from torchdistx_tpu.serve.programs import compile_serving_program

LLAMA = TransformerConfig(
    vocab_size=128, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
    d_ff=64, max_seq_len=64, dtype=jnp.float32,
)
SCFG = ServeConfig(max_batch=2, page_size=8, n_pages=16,
                   max_pages_per_seq=3, prefill_buckets=(8, 16),
                   prefill_chunk=6)


@pytest.fixture(scope="module")
def engine():
    specs = serve_program_specs("llama", LLAMA, SCFG)
    init = specs[0]
    compiled, _ = compile_serving_program(init)
    params = jax.tree.unflatten(init.treedef, list(compiled()))
    eng = ServeEngine("llama", LLAMA, params, serve_cfg=SCFG)
    return eng


def _check_oracle(eng, reqs, out):
    for r in reqs:
        want, _ = oracle_generate(
            eng.family, eng.cfg, eng.params, r.tokens, r.max_new_tokens,
            r.eos_id,
        )
        assert out[r.rid] == want, (r.rid, out[r.rid], want)


# ---------------------------------------------------------------------------
# property layer: refcount bookkeeping under random interleavings
# ---------------------------------------------------------------------------


def _expected_refs(kv: PagedKVCache, tree: PrefixCache) -> Counter:
    want = Counter()
    for sid in list(kv._seqs):
        want.update(kv.page_ids(sid))
    want.update(tree.pages())
    return want


def _assert_refs_consistent(kv: PagedKVCache, tree: PrefixCache) -> None:
    want = _expected_refs(kv, tree)
    have = {p: kv.ref(p) for p in want}
    assert dict(want) == have, (dict(want), have)
    # ...and nothing else holds a count, and the free list + live pages
    # partition the pool exactly (no leak, no double-free).
    assert set(kv._ref) == set(want)
    assert sorted(list(want) + kv._free) == list(
        range(1, kv.cfg.n_pages)), "free list and live pages must partition"


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_refcounts_equal_live_references_under_random_interleaving(seed):
    rng = random.Random(seed)
    cfg = KVCacheConfig(n_layers=1, kv_heads=1, head_dim=4,
                        page_size=4, n_pages=rng.randrange(8, 14))
    kv = PagedKVCache(cfg)
    tree = PrefixCache(kv)
    next_sid = 1
    prompts: dict = {}  # sid -> token list
    for _ in range(400):
        op = rng.random()
        if op < 0.35:  # admit (with sharing when the tree matches)
            toks = [rng.randrange(4) for _ in range(rng.randrange(1, 13))]
            shared = tree.match(toks)
            need = cfg.pages_for(len(toks)) - len(shared)
            if need <= kv.free_pages:
                sid = next_sid
                next_sid += 1
                if shared:
                    kv.alloc_shared(sid, shared, len(toks))
                else:
                    kv.alloc(sid, len(toks))
                prompts[sid] = toks
        elif op < 0.5 and prompts:  # publish a prompt's full blocks
            sid = rng.choice(list(prompts))
            toks = prompts[sid]
            nfull = len(toks) // cfg.page_size
            if nfull:
                tree.insert(toks[:nfull * cfg.page_size],
                            kv.page_ids(sid)[:nfull])
        elif op < 0.65 and prompts:  # grow (decode)
            sid = rng.choice(list(prompts))
            try:
                kv.extend(sid, kv.length(sid) + rng.randrange(1, 4))
            except OutOfPages:
                pass
        elif op < 0.8 and prompts:  # retire / preempt
            sid = rng.choice(list(prompts))
            kv.free(sid)
            del prompts[sid]
        elif op < 0.9:  # evict one LRU cache leaf
            tree.evict()
        elif prompts:  # copy-on-write a random owned page
            sid = rng.choice(list(prompts))
            idx = rng.randrange(len(kv.page_ids(sid)))
            try:
                kv.cow_page(sid, idx)
            except OutOfPages:
                pass
        _assert_refs_consistent(kv, tree)
    # Drain: retire everything, clear the cache — all refcounts zero.
    for sid in list(prompts):
        kv.free(sid)
    tree.clear()
    assert kv.pages_in_use == 0
    assert not kv._ref
    assert len(tree) == 0


@pytest.mark.parametrize("seed", [0, 7])
def test_cow_never_unmaps_a_page_from_other_readers(seed):
    """cow_page moves ONLY the writer's reference: every other table
    that mapped the src page still maps it afterwards, the tree still
    holds it, and the writer gets a fresh private page."""
    rng = random.Random(seed)
    cfg = KVCacheConfig(n_layers=1, kv_heads=1, head_dim=4,
                        page_size=4, n_pages=16)
    kv = PagedKVCache(cfg)
    tree = PrefixCache(kv)
    toks = [1, 2, 3, 4, 5, 6, 7, 8]  # two full pages
    kv.alloc(1, len(toks))
    tree.insert(toks, kv.page_ids(1))
    readers = []
    for sid in range(2, 2 + rng.randrange(1, 4)):
        kv.alloc_shared(sid, tree.match(toks), len(toks))
        readers.append(sid)
    writer = readers[-1]
    idx = rng.randrange(2)
    src = kv.page_ids(writer)[idx]
    before = {sid: kv.page_ids(sid) for sid in [1] + readers[:-1]}
    moved = kv.cow_page(writer, idx)
    assert moved is not None
    s, dst = moved
    assert s == src and dst != src
    assert kv.page_ids(writer)[idx] == dst
    assert kv.ref(dst) == 1
    for sid, pages in before.items():
        assert kv.page_ids(sid) == pages, "readers' tables must not move"
    assert src in tree.pages()
    _assert_refs_consistent(kv, tree)
    # A page owned by exactly one reference needs no copy.
    assert kv.cow_page(writer, idx) is None


def test_tree_match_is_page_aligned_and_lru_evicts_leaves():
    cfg = KVCacheConfig(n_layers=1, kv_heads=1, head_dim=4,
                        page_size=4, n_pages=16)
    kv = PagedKVCache(cfg)
    tree = PrefixCache(kv)
    kv.alloc(1, 10)  # 3 pages: two full blocks + a partial tail
    toks = list(range(10))
    tree.insert(toks, kv.page_ids(1)[:2])
    assert len(tree) == 2
    # Only FULL blocks match; the partial tail never enters the tree.
    assert tree.match(toks) == kv.page_ids(1)[:2]
    assert tree.match(toks[:7]) == kv.page_ids(1)[:1]
    assert tree.match(toks[:3]) == []
    assert tree.match([9] * 8) == []
    assert tree.match_len(toks) == 8
    # A second branch sharing the first block:
    kv.alloc_shared(2, tree.match(toks[:4]), 8)
    branch = toks[:4] + [7, 7, 7, 7]
    tree.insert(branch, kv.page_ids(2))
    assert len(tree) == 3
    kv.free(1)
    kv.free(2)
    # Eviction takes leaves only (LRU): the shared root block must
    # survive until both branches are gone.
    root_page = tree.match(toks[:4])[0]
    assert tree.evict() and len(tree) == 2
    assert tree.evict() and len(tree) == 1
    assert tree.pages() == [root_page]
    assert tree.evict() and len(tree) == 0
    assert not tree.evict()
    assert kv.pages_in_use == 0


def test_rollback_retracts_pages_and_refcounts():
    """Token-level rollback (speculative decoding): the trailing pages a
    shorter length no longer needs return to the free list; a rollback
    that stays within the tail page is bookkeeping only."""
    cfg = KVCacheConfig(n_layers=1, kv_heads=1, head_dim=4,
                        page_size=4, n_pages=16)
    kv = PagedKVCache(cfg)
    kv.alloc(1, 10)                          # 3 pages
    assert kv.rollback(1, 10) == 0           # no-op at the same length
    assert kv.rollback(1, 9) == 0            # same page count, shorter
    assert kv.length(1) == 9
    assert kv.rollback(1, 5) == 1            # drops the third page
    assert len(kv.page_ids(1)) == 2
    assert kv.rollback(1, 0) == 2
    assert kv.page_ids(1) == []
    with pytest.raises(ValueError, match="rollback target"):
        kv.rollback(1, 1)                    # beyond the current length
    with pytest.raises(ValueError, match="rollback target"):
        kv.rollback(1, -1)
    kv.free(1)
    assert kv.pages_in_use == 0
    assert not kv._ref


def test_rollback_on_shared_pages_drops_only_own_reference():
    """Rolling a lane back through COW-shared territory retracts only
    THAT lane's references: the tree and every other reader keep the
    pages, contents untouched."""
    cfg = KVCacheConfig(n_layers=1, kv_heads=1, head_dim=4,
                        page_size=4, n_pages=16)
    kv = PagedKVCache(cfg)
    tree = PrefixCache(kv)
    toks = [1, 2, 3, 4, 5, 6, 7, 8]          # two full pages
    kv.alloc(1, len(toks))
    tree.insert(toks, kv.page_ids(1))
    kv.alloc_shared(2, tree.match(toks), len(toks))
    shared = kv.page_ids(2)
    kv.extend(2, 9)                          # a private third page
    assert kv.rollback(2, 8) == 1            # drops only the private page
    assert kv.page_ids(2) == shared
    assert kv.rollback(2, 3) == 1            # back into the shared blocks
    assert kv.ref(shared[1]) == 2            # seq 1 + the tree survive
    assert kv.page_ids(1) == shared
    assert set(tree.pages()) == set(shared)
    _assert_refs_consistent(kv, tree)
    kv.free(1)
    kv.free(2)
    tree.clear()
    assert kv.pages_in_use == 0


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_spec_rollback_refcounts_under_random_accept_reject(seed):
    """The speculative-decode KV contract (ISSUE 19): random verify
    cycles — extend by k+1, accept a random draft prefix, roll back the
    rest — interleaved with sharing, COW, frees, and evictions keep
    every refcount equal to its live references, and a drain leaves all
    of them zero."""
    rng = random.Random(1000 + seed)
    cfg = KVCacheConfig(n_layers=1, kv_heads=1, head_dim=4,
                        page_size=4, n_pages=rng.randrange(10, 16))
    kv = PagedKVCache(cfg)
    tree = PrefixCache(kv)
    next_sid = 1
    lanes: dict = {}  # sid -> token list (kept in sync with kv.length)
    for _ in range(400):
        op = rng.random()
        if op < 0.3:  # admit (with sharing when the tree matches)
            toks = [rng.randrange(4) for _ in range(rng.randrange(1, 13))]
            shared = tree.match(toks)
            need = cfg.pages_for(len(toks)) - len(shared)
            if need <= kv.free_pages:
                sid = next_sid
                next_sid += 1
                if shared:
                    kv.alloc_shared(sid, shared, len(toks))
                else:
                    kv.alloc(sid, len(toks))
                lanes[sid] = toks
        elif op < 0.45 and lanes:  # publish a prompt's full blocks
            sid = rng.choice(list(lanes))
            toks = lanes[sid]
            nfull = len(toks) // cfg.page_size
            if nfull:
                tree.insert(toks[:nfull * cfg.page_size],
                            kv.page_ids(sid)[:nfull])
        elif op < 0.75 and lanes:  # one verify tick: extend, accept, roll back
            sid = rng.choice(list(lanes))
            k = rng.randrange(1, 5)
            length = kv.length(sid)
            try:
                kv.extend(sid, length + k + 1)
            except OutOfPages:
                continue
            accepted = rng.randrange(0, k + 1)
            kv.rollback(sid, length + accepted + 1)
            lanes[sid] = lanes[sid] + [rng.randrange(4)
                                       for _ in range(accepted + 1)]
        elif op < 0.85 and lanes:  # retire / preempt
            sid = rng.choice(list(lanes))
            kv.free(sid)
            del lanes[sid]
        elif op < 0.92:  # evict one LRU cache leaf
            tree.evict()
        elif lanes:  # copy-on-write a random owned page
            sid = rng.choice(list(lanes))
            pages = kv.page_ids(sid)
            if pages:
                try:
                    kv.cow_page(sid, rng.randrange(len(pages)))
                except OutOfPages:
                    pass
        _assert_refs_consistent(kv, tree)
    for sid in list(lanes):
        kv.free(sid)
    tree.clear()
    assert kv.pages_in_use == 0
    assert not kv._ref
    assert len(tree) == 0


# ---------------------------------------------------------------------------
# the n-gram drafter (speculative decoding's proposer)
# ---------------------------------------------------------------------------


def test_ngram_drafter_observe_draft_recency_and_capacity():
    d = NgramDrafter(order=2, max_entries=4)
    assert len(d) == 0
    assert d.draft([1, 2, 3], 4) == []       # empty map proposes nothing
    assert d.observe([1, 2, 3, 4, 5]) == 3   # (1,2)->3 (2,3)->4 (3,4)->5
    assert len(d) == 3 and d.observed == 3
    assert d.draft([0, 1, 2], 3) == [3, 4, 5]
    assert d.draft([0, 1, 2], 2) == [3, 4]   # k caps the walk
    assert d.draft([9, 9], 3) == []          # unknown tail
    assert d.draft([1], 3) == []             # context shorter than order
    assert d.draft([0, 1, 2], 0) == []
    d.observe([2, 3, 9])                     # recency: last writer wins
    assert d.draft([1, 2], 2) == [3, 9]
    d.observe([7, 7, 7])                     # the 4th entry fills the cap
    assert len(d) == 4
    d.observe([8, 8, 8])                     # at capacity: new gram dropped
    assert len(d) == 4
    assert d.draft([8, 8], 1) == []
    d.observe([1, 2, 6])                     # ...but known grams update
    assert d.draft([1, 2], 1) == [6]
    assert d.proposed > 0
    with pytest.raises(ValueError, match="order"):
        NgramDrafter(order=0)
    with pytest.raises(ValueError, match="max_entries"):
        NgramDrafter(max_entries=0)


def test_token_streams_feed_drafter_warmup():
    """token_streams() replays every cached root-to-leaf prefix;
    warm_from_prefix absorbs them so a fresh replica drafts the hot
    preambles without re-reading any request."""
    cfg = KVCacheConfig(n_layers=1, kv_heads=1, head_dim=4,
                        page_size=4, n_pages=16)
    kv = PagedKVCache(cfg)
    tree = PrefixCache(kv)
    assert tree.token_streams() == []
    toks = [1, 2, 3, 4, 5, 6, 7, 8]
    kv.alloc(1, len(toks))
    tree.insert(toks, kv.page_ids(1))
    kv.alloc_shared(2, tree.match(toks[:4]), 8)
    branch = toks[:4] + [9, 9, 9, 9]
    tree.insert(branch, kv.page_ids(2))
    assert sorted(tree.token_streams()) == sorted([toks, branch])
    d = NgramDrafter(order=2)
    assert d.warm_from_prefix(tree) == 12    # 6 gram pairs per stream
    assert len(d) == 8                       # shared-root grams dedup
    assert d.draft([1, 2], 2) == [3, 4]
    assert d.draft([9, 9], 1) == [9]
    kv.free(1)
    kv.free(2)
    tree.clear()
    assert kv.pages_in_use == 0


def test_table_rows_matches_per_row_view():
    cfg = KVCacheConfig(n_layers=1, kv_heads=1, head_dim=4,
                        page_size=4, n_pages=16)
    kv = PagedKVCache(cfg)
    kv.alloc(1, 10)
    kv.alloc(2, 3)
    rows = kv.table_rows([2, 1], 4)
    assert rows.dtype == np.int32 and rows.shape == (2, 4)
    assert rows.tolist() == [kv.table_row(2, 4), kv.table_row(1, 4)]
    with pytest.raises(ValueError, match="max_pages"):
        kv.table_rows([1], 2)


def test_prefix_affinity_policy():
    replicas = [
        {"load": 5, "match": 0},
        {"load": 9, "match": 8},
        {"load": 1, "match": 0},
    ]
    pick, hit = prefix_affinity(
        replicas, lambda h: h["load"], lambda h: h["match"])
    assert pick is replicas[1] and hit  # longest prefix wins over load
    for r in replicas:
        r["match"] = 0
    pick, hit = prefix_affinity(
        replicas, lambda h: h["load"], lambda h: h["match"])
    assert pick is replicas[2] and not hit  # degenerates to least work
    assert prefix_affinity([], lambda h: 0, lambda h: 0) == (None, False)


# ---------------------------------------------------------------------------
# engine layer: sharing + chunking on the real hot path
# ---------------------------------------------------------------------------


def test_shared_prefix_storm_matches_oracle_and_reuses_pages(engine):
    """Requests sharing a page-aligned preamble: bitwise-oracle outputs,
    prefix hits counted, reused pages never re-prefilled (the
    prefill_tokens counter only covers suffixes), and a drain leaves
    every refcount at zero."""
    preamble = [(3 * i + 1) % 128 for i in range(8)]  # one full page
    # Arrivals spaced so each follower admits after the leader's prefill
    # published the preamble block (two chunks at prefill_chunk=6).
    reqs = [Request(f"p{i}", preamble + [i + 1, i + 2],
                    max_new_tokens=3, arrival_step=2 * i)
            for i in range(4)]
    observe.enable(True)
    try:
        hits0 = observe.counter("tdx.serve.prefix_hits").value
        reused0 = observe.counter("tdx.serve.prefix_tokens_reused").value
        out = engine.run(reqs)
        hits = observe.counter("tdx.serve.prefix_hits").value - hits0
        reused = (observe.counter("tdx.serve.prefix_tokens_reused").value
                  - reused0)
    finally:
        observe.enable(None)
    _check_oracle(engine, reqs, out)
    assert hits >= 3, hits          # every follower matched the preamble
    assert reused >= 3 * 8, reused
    engine.drain()
    assert engine.kv.pages_in_use == 0
    assert not engine.kv._ref


def test_sharing_off_arm_is_identical(engine):
    """prefix_cache=False must serve the same storm to the same tokens
    (the bench phases' control arm)."""
    eng_off = ServeEngine(
        "llama", LLAMA, engine.params,
        serve_cfg=ServeConfig(max_batch=2, page_size=8, n_pages=16,
                              max_pages_per_seq=3, prefill_buckets=(8, 16),
                              prefill_chunk=6, prefix_cache=False),
    )
    eng_off._programs.update(engine._programs)
    preamble = [(5 * i + 2) % 128 for i in range(8)]
    reqs = [Request(f"o{i}", preamble + [i + 3], max_new_tokens=3)
            for i in range(3)]
    out = eng_off.run(reqs)
    _check_oracle(eng_off, reqs, out)
    assert len(eng_off.prefix) == 0  # the off arm never populates the tree
    assert eng_off.kv.pages_in_use == 0


def test_fully_cached_aligned_prompt_cows_the_shared_tail(engine):
    """A page-aligned prompt that is FULLY cached recomputes exactly its
    last token — the one write aimed at a shared page; COW must give the
    grower a private copy (counted) and the outputs stay bitwise-equal
    to the oracle."""
    prompt = [(7 * i + 11) % 128 for i in range(16)]  # exactly two pages
    observe.enable(True)
    try:
        cow0 = observe.counter("tdx.serve.cow_copies").value
        out = engine.run([Request("cw0", prompt, max_new_tokens=2)])
        out2 = engine.run([Request("cw1", prompt, max_new_tokens=2)])
        cows = observe.counter("tdx.serve.cow_copies").value - cow0
    finally:
        observe.enable(None)
    assert cows >= 1, "the fully-cached admit must copy-on-write"
    want, _ = oracle_generate(engine.family, engine.cfg, engine.params,
                              prompt, 2)
    assert out["cw0"] == want and out2["cw1"] == want
    engine.drain()
    assert engine.kv.pages_in_use == 0


def test_chunked_prefill_interleaves_decode(engine):
    """While a long prompt prefills chunk-by-chunk, a short request
    admitted behind it starts DECODING before the long prefill finishes
    — the head-of-line-blocking fix chunking exists for."""
    long_req = Request("lng", [(11 * i + 5) % 128 for i in range(18)],
                       max_new_tokens=2)
    short = Request("sht", [9, 2, 9], max_new_tokens=4, arrival_step=1)
    first_tok_step: dict = {}
    prev = engine.on_token
    engine.on_token = lambda rid, tok: first_tok_step.setdefault(
        rid, engine._step_no)
    try:
        out = engine.run([long_req, short])
    finally:
        engine.on_token = prev
    _check_oracle(engine, [long_req, short], out)
    # 18 tokens at chunk 6 = 3 chunks = 3 engine ticks of prefill; the
    # short request's first token lands before the long one's.
    assert first_tok_step["sht"] < first_tok_step["lng"], first_tok_step
    engine.drain()
    assert engine.kv.pages_in_use == 0


def test_chaos_fault_between_chunks_requeues_without_leaks(engine):
    """serve@N=raise:chunk fires BETWEEN prefill chunks: the mid-prefill
    lane requeues (recompute), nothing leaks, shared prefixes stay
    intact, and outputs equal the fault-free oracle."""
    preamble = [(13 * i + 3) % 128 for i in range(8)]
    warm = Request("ck-warm", preamble + [1, 2], max_new_tokens=2)
    engine.run([warm])  # seed the tree with the shared preamble
    tree_pages = set(engine.prefix.pages())
    assert tree_pages
    reqs = [
        Request("ck-long", preamble + [(i * 3 + 1) % 128 for i in range(10)],
                max_new_tokens=3),
        Request("ck-short", [4, 4, 4], max_new_tokens=3),
    ]
    observe.enable(True)
    # _step_no is lifetime; target the tick where ck-long's SECOND chunk
    # would run (admission + first chunk land on the next tick).
    chaos.install(f"serve@{engine._step_no + 2}=raise:chunk")
    try:
        before = observe.counter("tdx.serve.preempted_requests").value
        rebuilds = observe.counter("tdx.serve.pool_rebuilds").value
        out = engine.run(reqs)
        assert not chaos.active_plan().pending()
        assert (observe.counter("tdx.serve.preempted_requests").value
                > before)
        assert observe.counter("tdx.serve.pool_rebuilds").value == rebuilds
    finally:
        chaos.clear()
        observe.enable(None)
    _check_oracle(engine, reqs, out)
    # The shared preamble survived the fault path un-corrupted and
    # un-freed (the fault fired between programs: the pools were whole).
    assert tree_pages <= set(engine.prefix.pages())
    engine.drain()
    assert engine.kv.pages_in_use == 0
    assert not engine.kv._ref
