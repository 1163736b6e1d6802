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
    observe.enable(True)
    try:
        kv = PagedKVCache(_cfg())
        kv.alloc(1, 5)
        snap = {r["name"]: r["value"]
                for r in observe.counters().snapshot()
                if r["type"] == "gauge"}
        assert snap["tdx.serve.kv_pages_in_use"] == 2
        assert snap["tdx.serve.kv_pool_pages"] == 7
        kv.free(1)
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
