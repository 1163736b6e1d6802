"""Paged KV-cache allocator unit tests (ISSUE 7): free-list accounting,
page-table views, the null-page reservation, gauges, and the
exhaustion/retirement lifecycle the serving engine is built on."""

import pytest

from torchdistx_tpu import observe
from torchdistx_tpu.serve import KVCacheConfig, OutOfPages, PagedKVCache
from torchdistx_tpu.serve.kv_cache import init_pools


def _cfg(**kw):
    base = dict(n_layers=2, kv_heads=2, head_dim=8, page_size=4, n_pages=8)
    base.update(kw)
    return KVCacheConfig(**base)


def test_config_math():
    cfg = _cfg()
    assert cfg.usable_pages == 7
    assert cfg.tokens_capacity == 28
    assert cfg.pages_for(0) == 0
    assert cfg.pages_for(1) == 1
    assert cfg.pages_for(4) == 1
    assert cfg.pages_for(5) == 2
    assert cfg.pool_shape() == (2, 8, 2, 4, 8)  # [L, P, KV, page, D]


def test_null_page_reserved_and_validation():
    kv = PagedKVCache(_cfg())
    pages = kv.alloc(1, 9)  # 3 pages
    assert 0 not in pages
    assert len(pages) == 3
    with pytest.raises(ValueError, match="already allocated"):
        kv.alloc(1, 1)
    with pytest.raises(ValueError):
        PagedKVCache(_cfg(n_pages=1))


def test_alloc_extend_free_roundtrip():
    kv = PagedKVCache(_cfg())
    kv.alloc(1, 3)
    assert kv.pages_in_use == 1 and kv.free_pages == 6
    assert kv.extend(1, 4) == []          # still fits the tail page
    added = kv.extend(1, 5)               # crosses a page boundary
    assert len(added) == 1 and kv.pages_in_use == 2
    with pytest.raises(ValueError, match="cannot shrink"):
        kv.extend(1, 3)
    assert kv.free(1) == 2
    assert kv.pages_in_use == 0 and kv.free_pages == 7
    assert kv.free(1) == 0  # idempotent


def test_pages_recycled_to_waiting_sequences():
    kv = PagedKVCache(_cfg())
    kv.alloc(1, 12)  # 3 pages
    kv.alloc(2, 16)  # 4 pages -> pool full
    assert kv.free_pages == 0
    with pytest.raises(OutOfPages):
        kv.alloc(3, 1)
    first = set(kv.page_ids(1))
    kv.free(1)
    reused = set(kv.alloc(3, 12))
    assert reused == first  # LIFO reuse of the freed pages


def test_out_of_pages_leaves_state_unchanged():
    kv = PagedKVCache(_cfg())
    kv.alloc(1, 24)  # 6 pages of 7
    kv.alloc(2, 4)   # the 7th
    with pytest.raises(OutOfPages):
        kv.extend(2, 9)  # would need 2 more
    assert kv.length(2) == 4
    assert len(kv.page_ids(2)) == 1
    assert kv.free_pages == 0


def test_occupancy_and_fragmentation():
    kv = PagedKVCache(_cfg())
    assert kv.occupancy() == 0.0 and kv.fragmentation() == 0.0
    kv.alloc(1, 4)   # exactly one full page
    assert kv.occupancy() == 1.0
    kv.alloc(2, 1)   # one page, one slot used
    # 5 used slots over 8 allocated
    assert kv.occupancy() == pytest.approx(5 / 8)
    assert kv.fragmentation() == pytest.approx(3 / 8)


def test_table_row_padding_and_overflow():
    kv = PagedKVCache(_cfg())
    pages = kv.alloc(1, 6)  # 2 pages
    row = kv.table_row(1, 4)
    assert row[:2] == pages and row[2:] == [0, 0]
    with pytest.raises(ValueError, match="max_pages"):
        kv.table_row(1, 1)


def test_gauges_track_pool_state():
    """The gauges are set by ``publish_gauges`` (the engine's once a
    step), not by the transitions themselves."""
    observe.enable(True)
    try:
        kv = PagedKVCache(_cfg())
        n0 = len(observe.tracer().events)
        kv.alloc(1, 5)
        assert len(observe.tracer().events) == n0  # no sample a transition
        kv.publish_gauges()
        snap = {r["name"]: r["value"]
                for r in observe.counters().snapshot()
                if r["type"] == "gauge"}
        assert snap["tdx.serve.kv_pages_in_use"] == 2
        assert snap["tdx.serve.kv_pool_pages"] == 7
        kv.free(1)
        kv.publish_gauges()
        snap = {r["name"]: r["value"]
                for r in observe.counters().snapshot()
                if r["type"] == "gauge"}
        assert snap["tdx.serve.kv_pages_in_use"] == 0
    finally:
        observe.enable(None)


def test_init_pools_shape_dtype():
    import jax.numpy as jnp

    cfg = _cfg()
    k, v = init_pools(cfg, jnp.bfloat16)
    assert k.shape == cfg.pool_shape() == v.shape
    assert k.dtype == jnp.bfloat16
    assert float(jnp.sum(jnp.abs(k))) == 0.0


def test_reset_frees_everything():
    kv = PagedKVCache(_cfg())
    kv.alloc(1, 8)
    kv.alloc(2, 8)
    kv.reset()
    assert kv.pages_in_use == 0
    assert not kv.has(1) and not kv.has(2)


# -- the window layer group (PR 34) -------------------------------------------


def _wcfg(**kw):
    from torchdistx_tpu.serve.kv_cache import WindowCacheConfig

    base = dict(n_layers=2, window=16, n_pages=12, max_pages_per_seq=8)
    base.update(kw)
    return _cfg(n_layers=1, n_pages=64, window=WindowCacheConfig(**base))


def test_window_group_has_its_own_pool_and_null_page():
    from torchdistx_tpu.serve.kv_cache import init_window_pool

    cfg = _wcfg()
    # keys and values of both layers in one array: [2 * Lw, Pw, KV, page, D]
    assert cfg.window_pool_shape() == (4, 12, 2, 4, 8)
    assert init_window_pool(cfg, "float32").shape == (4, 12, 2, 4, 8)
    kv = PagedKVCache(cfg)
    assert (kv.window_free_pages, kv.window_pages_in_use) == (11, 0)
    kv.alloc(1, 9)
    assert kv.window_page_ids(1) == []       # the programs' positions bring it
    kv.window_advance(1, 0, 9)
    assert 0 not in kv.window_page_ids(1) and len(kv.window_page_ids(1)) == 3
    assert kv.pages_in_use == 3 and kv.window_pages_in_use == 3


def test_pages_behind_the_window_return_while_the_sequence_lives():
    observe.reset()
    kv = PagedKVCache(_wcfg())               # window 16 = 4 pages of 4
    kv.alloc(1, 40)
    released = []
    for start in range(0, 40, 8):            # chunks of 8 positions
        released.append(kv.window_advance(1, start, start + 8))
        # positions (start - 16, start + 8): at most 16 + 8 = 24 -> 6-7 pages
        assert len(kv.window_page_ids(1)) <= 7
    assert released == [0, 0, 0, 2, 2]
    rows, first = kv.window_rows([1])
    # chunk [32, 40) reads from position 17: page 4 is the row's first
    assert first.tolist() == [16] and rows.shape == (1, 8)
    assert (rows[0, :6] > 0).all() and (rows[0, 6:] == 0).all()
    assert observe.counter("tdx.serve.window_pages_released").value == 4
    # decode: one position a tick; a page goes back every page_size ticks
    held = []
    for length in range(41, 60):
        kv.extend(1, length)
        held.append(len(kv.window_page_ids(1)))
    assert max(held) <= 16 // 4 + 1          # window / page + 1
    assert kv.length(1) == 59 and len(kv.page_ids(1)) == 15   # full group grows
    assert kv.window_pages_peak == 6          # (window + chunk) / page
    assert kv.free(1) == 15
    assert kv.window_pages_in_use == 0 and kv.pages_in_use == 0


def test_window_out_of_pages_changes_nothing_and_can_fit_counts_both_groups():
    kv = PagedKVCache(_wcfg(n_pages=8))      # 7 usable window pages
    kv.alloc(1, 16)
    kv.window_advance(1, 0, 16)              # 4 pages
    kv.alloc(2, 20)
    before = (kv.window_page_ids(1), kv.window_free_pages)
    with pytest.raises(OutOfPages, match="window pages"):
        kv.window_advance(2, 0, 16)          # needs 4, 3 free
    assert (kv.window_page_ids(1), kv.window_free_pages) == before
    assert kv.window_page_ids(2) == []
    assert kv.can_fit(8, 8) and not kv.can_fit(16, 16)
    assert kv.can_fit(400, 8) is False       # the full group says no
    # extend: a refusal by either group leaves both as they were
    kv.window_advance(2, 0, 12)              # the last 3 pages
    full_before = kv.page_ids(1)
    with pytest.raises(OutOfPages):
        kv.extend(1, 17)                     # a fifth window page: none free
    assert kv.page_ids(1) == full_before and kv.length(1) == 16
    kv.free(2)                               # preemption: both groups freed
    assert kv.window_free_pages == 3 and kv.page_ids(1) == full_before
    kv.extend(1, 17)
    assert len(kv.window_page_ids(1)) == 5


def test_window_rows_rollback_reset_and_no_sharing():
    kv = PagedKVCache(_wcfg())
    kv.alloc(1, 10)
    kv.window_advance(1, 0, 10)
    kv.alloc(2, 3)
    kv.window_advance(2, 0, 3)
    rows, first = kv.window_rows([2, 1])
    assert rows.shape == (2, 8) and first.tolist() == [0, 0]
    assert rows[1, :3].tolist() == kv.window_page_ids(1)
    assert kv.rollback(1, 5) == 1            # both groups lose the third page
    assert len(kv.window_page_ids(1)) == 2 and len(kv.page_ids(1)) == 2
    with pytest.raises(ValueError, match="window group shares no pages"):
        kv.alloc_shared(3, kv.page_ids(1), 12)
    with pytest.raises(ValueError, match="table row"):
        kv.window_advance(2, 3, 3 + 9 * 4)   # 9 pages and more in a row of 8
    kv.reset()
    assert kv.window_free_pages == 11 and kv.free_pages == 63


def test_a_cache_without_the_group_ignores_it():
    kv = PagedKVCache(_cfg())
    kv.alloc(1, 5)
    assert kv.window_advance(1, 0, 5) == 0 and kv.window_pages_in_use == 0
    assert kv.can_fit(8, 4)
