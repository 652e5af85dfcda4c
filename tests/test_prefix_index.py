"""Cross-session shared-prefix index: unit tests for the chained content
hash (``page_digests``) and the weak ``PrefixPageIndex``, plus a seeded
random-walk state machine over interleaved admit / extend / evict / COW /
crash sequences against a real ``SessionCachePool`` + ``PagedKVAllocator``.

After every op the walk asserts the structural invariants the sharing
design rests on:

- free-list + refcount accounting balances (used + free == allocatable);
- no page is ever both free and referenced;
- the content index never maps a hash to a released page;
- the pool's entries account for every outstanding reference;
- every entry's gathered bytes equal a freshly computed lane for its
  token prefix — i.e. no sharer ever observes another session's writes
  (the copy-on-write guarantee), even across donor eviction and crashes.

The deterministic seeds always run; a hypothesis-driven seed sweep rides
along where the optional dependency is installed (see _hypothesis_support).
"""

import numpy as np
import jax.numpy as jnp
import pytest

from _hypothesis_support import given, settings, st

from repro.models import ModelConfig
from repro.serving import CacheEntry, PagedKVAllocator, SessionCachePool
from repro.serving.paged_kv import SCRATCH_PAGE, PrefixPageIndex, page_digests

PS = 4  # page size used throughout


# ---------------------------------------------------------------------------
# page_digests: chained content hash
# ---------------------------------------------------------------------------

def test_page_digests_counts_full_pages_only():
    ids = list(range(11))                      # 2 full pages + partial tail
    assert len(page_digests(ids, PS)) == 2
    assert page_digests(ids[:3], PS) == []     # sub-page prefix: nothing
    assert page_digests([], PS) == []
    assert len(page_digests(ids, PS, limit=1)) == 1
    assert len(page_digests(ids, PS, limit=0)) == 0
    assert len(page_digests(ids, PS, limit=99)) == 2


def test_page_digests_chained_commit():
    """Digest i commits to the ENTIRE prefix [0, (i+1)*ps), not block i
    alone: equal later blocks after an early divergence must NOT collide,
    while a shared head shares exactly its leading digests."""
    a = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12]
    b = list(a)
    b[0] = 99                                   # diverge inside page 0
    da, db = page_digests(a, PS), page_digests(b, PS)
    assert all(x != y for x, y in zip(da, db))  # chain poisons every digest
    c = a[:8] + [77, 77, 77, 77]                # shared head, new page 2
    dc = page_digests(c, PS)
    assert dc[:2] == da[:2] and dc[2] != da[2]
    # determinism across calls
    assert page_digests(a, PS) == da


# ---------------------------------------------------------------------------
# PrefixPageIndex: weak digest -> page mapping
# ---------------------------------------------------------------------------

def test_prefix_index_run_and_first_writer_wins():
    idx = PrefixPageIndex()
    d = page_digests(list(range(16)), PS)       # 4 chained digests
    idx.register(d[0], 5)
    idx.register(d[1], 6)
    idx.register(d[3], 8)                       # gap at d[2]
    assert idx.lookup_run(d) == [5, 6]          # run stops at the gap
    assert idx.lookup_run(d[2:]) == []
    idx.register(d[0], 9)                       # duplicate digest: ignored
    idx.register(d[2], 6)                       # duplicate page: ignored
    assert idx.lookup_run(d) == [5, 6]
    assert len(idx) == 3 and sorted(idx.pages()) == [5, 6, 8]


def test_prefix_index_drop_page():
    idx = PrefixPageIndex()
    d = page_digests(list(range(8)), PS)
    idx.register(d[0], 3)
    idx.register(d[1], 4)
    idx.drop_page(3)
    assert idx.lookup_run(d) == []              # head gone => no run
    assert idx.lookup_run(d[1:]) == [4]         # deeper digests still live
    idx.drop_page(3)                            # idempotent
    idx.drop_page(999)                          # unknown page: no-op
    assert len(idx) == 1
    # dropped digest can be re-registered to a new page (recycled content)
    idx.register(d[0], 7)
    assert idx.lookup_run([d[0]]) == [7]


# ---------------------------------------------------------------------------
# Random-walk state machine: admit / extend / evict / COW / crash
# ---------------------------------------------------------------------------

_cfg = ModelConfig(
    name="micro-idx", arch_type="dense", n_layers=1, d_model=16, n_heads=2,
    n_kv_heads=1, d_ff=16, vocab_size=4096, param_dtype="float32",
    compute_dtype="float32",
)
WIDTH = 32  # dense lane width (slots); 8 pages of PS


def _lane(ids):
    """Synthetic dense B=1 KV lane whose value at slot j is a chained hash
    of tokens [0, j] — mirroring real KV, where position j depends on the
    full causal prefix. Exact in float32 (< 2**20), so byte-compare works."""
    dh = _cfg.d_model // _cfg.n_heads
    k = np.zeros((_cfg.n_layers, 1, WIDTH, _cfg.n_kv_heads, dh), np.float32)
    h = 0
    for j, t in enumerate(ids):
        h = (h * 8191 + int(t) + 1) % (1 << 20)
        k[:, 0, j] = float(h)
    return [{"k": jnp.asarray(k), "v": jnp.asarray(-k)}]


def _check_invariants(alloc, pool):
    free = alloc._free
    # 1. accounting balances, free list is duplicate-free, scratch reserved
    assert alloc.used_pages + alloc.n_free == alloc.n_pages - 1
    assert len(set(free)) == len(free) and SCRATCH_PAGE not in free
    assert alloc.refcount(SCRATCH_PAGE) == 0
    # 2. no page both free and referenced; every non-free page is referenced
    for p in range(1, alloc.n_pages):
        assert (alloc.refcount(p) > 0) == (p not in free), p
    # 3. the index never names a released page
    for p in alloc.index.pages():
        assert alloc.refcount(p) > 0, p
    # 4. pool entries account for every outstanding reference (the pool is
    #    the allocator's sole client in this walk)
    held = [p for e in pool._entries.values() if e.paged for p in e.pages]
    refs = {p: alloc.refcount(p) for p in range(1, alloc.n_pages)}
    assert sum(refs.values()) == len(held)
    for p in set(held):
        assert refs[p] == held.count(p), p


def _check_contents(alloc, pool, expected):
    """COW isolation: every entry's gathered bytes must equal a lane
    recomputed from ITS OWN token prefix — regardless of which physical
    pages it shares with whom, and of any donor eviction in between."""
    for key, entry in pool._entries.items():
        ids = expected[key]
        assert entry.token_ids == ids
        want = _lane(ids)[0]["k"][0, 0, : len(ids)]
        got = pool.materialize(entry, len(ids), WIDTH)
        assert int((got[0]["kv_pos"] >= 0).sum()) == len(ids)
        assert jnp.array_equal(got[0]["k"][0, 0, : len(ids)], want)
        assert jnp.array_equal(got[0]["v"][0, 0, : len(ids)], -want)


BASE = [7, 3, 11, 5, 2, 13, 17, 19]  # two full shared-prompt pages


def _walk(seed, n_ops=120, check_every=6):
    rng = np.random.default_rng(seed)
    alloc = PagedKVAllocator(_cfg, page_size=PS, n_pages=16)
    pool = SessionCachePool(capacity=4, allocator=alloc)
    keys = [f"s{i}" for i in range(5)]
    expected = {}

    def ids_for(key, n_extra):
        """Shared base prefix + per-key suffix: admissions collide on the
        base pages (cross-session sharing) then diverge mid-page (COW)."""
        n_base = int(rng.integers(2, len(BASE) + 1))
        suffix = [
            100 + keys.index(key) * 37 + i for i in range(n_extra)
        ]
        return BASE[:n_base] + suffix

    for step in range(n_ops):
        op = rng.choice(
            ["admit", "extend", "evict", "crash"], p=[0.45, 0.3, 0.2, 0.05]
        )
        key = keys[int(rng.integers(len(keys)))]
        # a rejected put leaves the key's existing entry (if any) in place
        rejects = pool.rejects
        if op == "admit":
            ids = ids_for(key, int(rng.integers(0, 9)))
            pool.put(key, CacheEntry(list(ids), _lane(ids)),
                     low_priority=bool(rng.integers(2)))
            if key in pool and pool.rejects == rejects:
                expected[key] = ids
        elif op == "extend":
            cur = pool.peek(key)
            if cur is None:
                continue
            ids = list(cur.token_ids) + [
                200 + int(t) for t in rng.integers(0, 50, int(rng.integers(1, 5)))
            ]
            if len(ids) > WIDTH:
                continue
            pool.put(key, CacheEntry(list(ids), _lane(ids)))
            if key in pool and pool.rejects == rejects:
                expected[key] = ids
        elif op == "evict":
            pool.invalidate(key)
        else:  # crash: node restart drops all resident state at once
            pool.clear()
        expected = {k: v for k, v in expected.items() if k in pool}
        _check_invariants(alloc, pool)
        if step % check_every == 0:
            _check_contents(alloc, pool, expected)
    _check_contents(alloc, pool, expected)
    # drain: releasing everything must return the allocator to pristine
    pool.clear()
    _check_invariants(alloc, pool)
    assert alloc.used_pages == 0 and alloc.n_free == alloc.n_pages - 1
    assert len(alloc.index) == 0


@pytest.mark.parametrize("seed", [0, 1, 7])
def test_shared_index_random_walk(seed):
    _walk(seed)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_shared_index_random_walk_property(seed):
    _walk(seed, n_ops=60, check_every=10)


def test_store_shares_then_releases_on_alloc_failure():
    """store() under page exhaustion: the protective shared increfs must be
    rolled back — a failed store leaves refcounts and the index exactly as
    they were (no page leaked, no phantom sharing)."""
    alloc = PagedKVAllocator(_cfg, page_size=PS, n_pages=5)  # 4 allocatable
    a = BASE[:8] + [101]
    pa = alloc.store(_lane(a), len(a), a)                    # 3 pages
    assert pa is not None and len(pa) == 3
    before = {p: alloc.refcount(p) for p in pa}
    b = BASE[:8] + [102, 103, 104, 105, 106]                 # needs 2 fresh
    assert alloc.store(_lane(b), len(b), b) is None          # only 1 free
    assert {p: alloc.refcount(p) for p in pa} == before
    assert alloc.n_free == 1 and len(alloc.index) == 2
