"""Paged KV cache + allocator tests, and paged-vs-dense numerics."""

import pytest

from lmrs_tpu.config import EngineConfig, ModelConfig
from lmrs_tpu.engine.api import GenerationRequest
from lmrs_tpu.engine.jax_engine import JaxEngine
from lmrs_tpu.engine.kv_cache import OutOfPages, PageAllocator, PagedKVCache


def test_allocator_alloc_free_cycle():
    a = PageAllocator(8)
    assert a.free_count == 7  # page 0 reserved (null page)
    p1 = a.alloc(3)
    assert len(set(p1)) == 3
    assert 0 not in p1
    assert a.free_count == 4
    a.free(p1)
    assert a.free_count == 7


def test_allocator_exhaustion():
    a = PageAllocator(4)
    a.alloc(3)
    with pytest.raises(OutOfPages):
        a.alloc(2)


def test_allocator_rejects_bad_free():
    a = PageAllocator(4)
    with pytest.raises(ValueError):
        a.free([99])
    with pytest.raises(ValueError):
        a.free([0])  # reserved null page may never be freed


def test_allocator_rejects_double_free():
    """Freeing a page already on the free list must raise, not corrupt the
    pool (a double-freed page would be handed to two sequences at once)."""
    a = PageAllocator(8)
    pages = a.alloc(3)
    a.free(pages)
    with pytest.raises(ValueError):
        a.free([pages[0]])
    # a rejected batch must leave the pool untouched (validate-then-mutate)
    live = a.alloc(1)
    with pytest.raises(ValueError):
        a.free(live + [pages[1]])  # second id is free -> whole call rejected
    assert a.refcount(live[0]) == 1  # the live page kept its reference
    a.free(live)
    assert a.free_count == 7
    # freeing the same id twice IN ONE CALL needs refcount >= 2
    p = a.alloc(1)
    with pytest.raises(ValueError):
        a.free([p[0], p[0]])
    a.incref(p)
    a.free([p[0], p[0]])  # ref 2 -> 0: legal
    assert a.free_count == 7


def test_allocator_refcount_sharing():
    """incref'd pages return to the free list only at refcount zero, and
    refcount-0 pages can never gain holders."""
    a = PageAllocator(8)
    pages = a.alloc(2)
    a.incref(pages)
    assert [a.refcount(p) for p in pages] == [2, 2]
    a.free(pages)  # one holder left
    assert a.free_count == 5
    a.free(pages)  # last holder: pages return
    assert a.free_count == 7
    assert all(a.refcount(p) == 0 for p in pages)
    with pytest.raises(ValueError):
        a.incref([pages[0]])  # free page cannot gain a holder


def test_cache_admission_math():
    cfg = ModelConfig(vocab_size=64, dim=32, n_layers=1, n_heads=4, n_kv_heads=2,
                      hidden_dim=64, max_seq_len=256, dtype="float32")
    c = PagedKVCache(cfg, num_pages=8, page_size=16, max_pages_per_slot=4)
    assert c.pages_needed(1) == 1
    assert c.pages_needed(16) == 1
    assert c.pages_needed(17) == 2
    assert c.can_admit(7 * 16)  # 8 pages minus the reserved null page
    assert not c.can_admit(7 * 16 + 1)
    seq = c.open_sequence(40)  # 3 pages
    assert len(seq.pages) == 3
    c.grow(seq, 60)  # 4 pages
    assert len(seq.pages) == 4
    with pytest.raises(OutOfPages):
        c.grow(seq, 100)  # exceeds max_pages_per_slot
    c.close_sequence(seq)
    assert c.allocator.free_count == 7


def test_page_recycling_does_not_corrupt():
    """Two batches through the same engine must reuse freed pages without
    leaking state: greedy output for an identical request must be identical
    before and after the pool has been heavily recycled."""
    mc = ModelConfig(vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
                     hidden_dim=128, max_seq_len=256, dtype="float32")
    eng = JaxEngine(EngineConfig(backend="jax", scheduler="continuous",
                                 max_tokens=8, max_batch_slots=2, page_size=32,
                                 num_pages=16, seed=0), mc)
    probe = GenerationRequest(prompt="canonical probe text", temperature=0.0,
                              max_new_tokens=8)
    before = eng.generate_batch([probe])[0].text
    # churn the pool with other requests
    churn = [GenerationRequest(prompt=f"churn {i} " * (3 + i), request_id=i,
                               temperature=0.9, max_new_tokens=8) for i in range(7)]
    eng.generate_batch(churn)
    after = eng.generate_batch([probe])[0].text
    assert before == after
    # all pages returned except those the prefix cache retains (each held at
    # exactly one reference — the cache's own)
    sched = eng._scheduler
    cached = sched._prefix_cache.cached_pages if sched._prefix_cache else 0
    assert (sched.cache.allocator.free_count
            == sched.cache.num_pages - 1 - cached)  # -1: null page


def test_backpressure_small_pool():
    """A pool that fits only one sequence at a time must still complete all
    requests (admission waits for pages instead of failing)."""
    mc = ModelConfig(vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
                     hidden_dim=128, max_seq_len=256, dtype="float32")
    eng = JaxEngine(EngineConfig(backend="jax", scheduler="continuous",
                                 max_tokens=8, max_batch_slots=4, page_size=32,
                                 num_pages=0,  # floor: B * max_pages_per_slot
                                 seed=0), mc)
    # shrink the pool artificially to 1 slot's worth
    sched = eng._scheduler
    reqs = [GenerationRequest(prompt="p" * 40, request_id=i, temperature=0.4,
                              max_new_tokens=8) for i in range(5)]
    out = eng.generate_batch(reqs)
    assert [r.request_id for r in out] == list(range(5))
    assert all(r.error is None for r in out)


def test_pool_floor_makes_every_request_admittable():
    """Admission has no fail-fast branch by design (ADVICE r2: it was
    unreachable): the constructor floors the pool at one full-length
    sequence + the null page, prompts truncate at submit, and decode trims
    at max_len — so even a worst-case request admits and completes.  This
    test pins the INVARIANT that removal rests on."""
    mc = ModelConfig(vocab_size=512, dim=64, n_layers=1, n_heads=4, n_kv_heads=2,
                     hidden_dim=128, max_seq_len=8192, dtype="float32")
    eng = JaxEngine(EngineConfig(backend="jax", scheduler="continuous",
                                 max_tokens=8, max_batch_slots=1, page_size=128,
                                 num_pages=2, seed=0), mc)
    sched = eng._scheduler
    # num_pages=2 asked for a 2-page budget; the floor must win
    assert sched.cache.num_pages >= sched.cache.max_pages_per_slot + 1
    big = GenerationRequest(prompt="x" * 7000, request_id=0, temperature=0.0,
                            max_new_tokens=8)
    small = GenerationRequest(prompt="ok", request_id=1, temperature=0.0,
                              max_new_tokens=4)
    out = eng.generate_batch([big, small])
    assert out[0].error is None and out[0].completion_tokens <= 8
    assert out[1].error is None
