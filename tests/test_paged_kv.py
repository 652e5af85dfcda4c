"""Block-granular paged session KV (docs/architecture.md, "Paged session
KV"): allocator mechanics and page accounting, greedy equivalence of the
paged batched server against the full-width path, page-budgeted pool
eviction / tenant capacity, and the slot-overflow + decode run-off
regressions. Hypothesis property tests cover the SessionCachePool stats
invariants and the allocator's free-list/refcount accounting."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import (
    ModelConfig,
    decode_step,
    decode_step_paged,
    init_params,
    prefill,
)
from repro.serving import (
    BatchedServer,
    CacheEntry,
    PagedKVAllocator,
    SessionCachePool,
)
from repro.serving.paged_kv import SCRATCH_PAGE
from repro.tokenizer import get_tokenizer


@pytest.fixture(scope="module")
def cfg():
    return ModelConfig(
        name="tiny-paged", arch_type="dense", n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=128, vocab_size=4096, param_dtype="float32",
        compute_dtype="float32",
    )


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(jax.random.key(0), cfg)


@pytest.fixture(scope="module")
def tok(cfg):
    return get_tokenizer(cfg.vocab_size, seed=0)


# ---------------------------------------------------------------------------
# Allocator mechanics
# ---------------------------------------------------------------------------

def test_alloc_refcount_free(cfg):
    alloc = PagedKVAllocator(cfg, page_size=4, n_pages=8)
    assert alloc.n_free == 7                   # page 0 reserved as scratch
    a = alloc.alloc(3)
    assert len(a) == 3 and SCRATCH_PAGE not in a and len(set(a)) == 3
    assert alloc.used_pages == 3
    alloc.incref(a[:1])                        # a[0] now shared (ref 2)
    alloc.decref(a)
    assert alloc.used_pages == 1 and alloc.refcount(a[0]) == 1
    alloc.decref(a[:1])
    assert alloc.used_pages == 0 and alloc.n_free == 7
    assert alloc.alloc(8) is None              # over budget: None, no change
    assert alloc.n_free == 7
    assert alloc.resident_kv_bytes == 0
    assert alloc.total_kv_bytes == 7 * alloc.page_bytes


def test_pages_for(cfg):
    alloc = PagedKVAllocator(cfg, page_size=4, n_pages=4)
    assert alloc.pages_for(1) == 1 and alloc.pages_for(4) == 1
    assert alloc.pages_for(5) == 2 and alloc.pages_for(0) == 1


def test_store_gather_roundtrip(cfg, params):
    """dense -> pages -> dense must be bit-exact on every valid slot and
    mask everything beyond n_valid (including sub-page trims)."""
    max_len = 64
    ids = (np.arange(23)[None] * 7 % cfg.vocab_size).astype(np.int32)
    _, dense, _ = prefill(params, cfg, jnp.asarray(ids), max_len=max_len)
    alloc = PagedKVAllocator(cfg, page_size=16, n_pages=8)
    pages = alloc.store(dense, 23)
    assert len(pages) == 2 and alloc.used_pages == 2
    back = alloc.gather(pages, 23, max_len)
    valid = back[0]["kv_pos"] >= 0
    assert int(valid.sum()) == 23
    vm = valid[None, :, :, None, None]
    assert jnp.array_equal(
        jnp.where(vm, back[0]["k"], 0), jnp.where(vm, dense[0]["k"], 0)
    )
    assert jnp.array_equal(
        jnp.where(vm, back[0]["v"], 0), jnp.where(vm, dense[0]["v"], 0)
    )
    trimmed = alloc.gather(pages, 10, max_len)   # retry/resend trim view
    assert int((trimmed[0]["kv_pos"] >= 0).sum()) == 10


@pytest.mark.slow
def test_decode_step_paged_matches_dense(cfg, params):
    """The model-layer tentpole: paged decode (scatter into page cells +
    gather through the table) is exactly the full-width decode."""
    max_len = 64
    n = 37
    ids = (np.arange(n)[None] * 11 % cfg.vocab_size).astype(np.int32)
    logits, dense, pos = prefill(params, cfg, jnp.asarray(ids), max_len=max_len)

    alloc = PagedKVAllocator(cfg, page_size=16, n_pages=8)
    pages = alloc.store(dense, n)              # 3 pages cover pos < 48
    gathered = alloc.gather(pages, n, max_len)
    kv_pos = gathered[0]["kv_pos"]
    pools = alloc.pools
    table = jnp.asarray(alloc.table_for(pages, max_len))[None, :]

    tok_i = jnp.argmax(logits, -1).astype(jnp.int32)
    caches, pos_d = dense, pos
    tok_d = tok_p = tok_i
    pos_p = pos
    for _ in range(10):
        ld, caches = decode_step(params, cfg, caches, tok_d[:, None], pos_d)
        lp, pools, kv_pos = decode_step_paged(
            params, cfg, pools, table, kv_pos, tok_p[:, None], pos_p
        )
        assert jnp.array_equal(ld, lp)
        pos_d, pos_p = pos_d + 1, pos_p + 1
        tok_d = jnp.argmax(ld[:, 0], -1).astype(jnp.int32)
        tok_p = jnp.argmax(lp[:, 0], -1).astype(jnp.int32)
        assert jnp.array_equal(tok_d, tok_p)


def test_paged_write_step_drops_at_capacity(cfg, params):
    """Regression: a lane whose position reaches table capacity (exactly
    mp * page_size tokens) used to have its write *clamped* into the last
    page — silently overwriting the resident K/V of the token actually
    stored in that cell. The out-of-range write must be dropped instead."""
    from repro.models.cache import paged_write_step

    ps, mp, n_pages = 4, 3, 8
    kv, dh = cfg.n_kv_heads, cfg.d_head
    key = jax.random.key(1)
    pool_k = jax.random.normal(key, (n_pages, ps, kv, dh))
    pool_v = pool_k + 1.0
    table = jnp.asarray([[1, 2, 3]], jnp.int32)            # full lane: 3 pages
    k_new = jnp.ones((1, 1, kv, dh))
    v_new = jnp.ones((1, 1, kv, dh))

    # control: the last in-range position lands in the last page's tail cell
    pos = jnp.asarray([mp * ps - 1], jnp.int32)
    pk, pv = paged_write_step(pool_k, pool_v, k_new, v_new, pos, table, ps)
    assert jnp.array_equal(pk[3, ps - 1], k_new[0, 0])

    # at capacity: the write is dropped, resident KV is untouched
    pos = jnp.asarray([mp * ps], jnp.int32)
    pk, pv = paged_write_step(pool_k, pool_v, k_new, v_new, pos, table, ps)
    assert jnp.array_equal(pk, pool_k) and jnp.array_equal(pv, pool_v)


def test_decode_step_paged_kv_pos_drops_at_capacity(cfg, params):
    """The position table must drop the at-capacity update too: relabeling
    the last slot with the overflow position would mark a stale K/V cell
    causal for the current query."""
    width = 8
    kv_pos = jnp.asarray([[0, 1, 2, 3, 4, 5, 6, 7]], jnp.int32)
    from repro.serving import PagedKVAllocator

    alloc = PagedKVAllocator(cfg, page_size=4, n_pages=4)
    pages = alloc.alloc(2)
    table = jnp.asarray(alloc.table_for(pages, width))[None, :]
    tok = jnp.zeros((1, 1), jnp.int32)
    _, _, new_kv_pos = decode_step_paged(
        params, cfg, alloc.pools, table, kv_pos, tok, jnp.asarray([width], jnp.int32)
    )
    assert jnp.array_equal(new_kv_pos, kv_pos)


def _random_pools(cfg, n_pages, ps):
    """Pool stacks shaped as a node allocates them, filled with noise so
    that every write shows."""
    from repro.models import layer_groups
    from repro.models.cache import init_paged_pool

    pools = [init_paged_pool(cfg, spec.n_blocks, n_pages, ps)
             for spec in layer_groups(cfg)]
    keys = iter(jax.random.split(jax.random.key(2), 2 * len(pools)))
    return [{n: jax.random.normal(next(keys), p[n].shape, p[n].dtype)
             for n in ("k", "v")} for p in pools]


def _written_cells(before, after):
    """The (layer, page, slot) cells in which any K or V pool of any group
    differs between two lists of pool stacks."""
    cells = set()
    for b, a in zip(before, after):
        for n in ("k", "v"):
            diff = np.any(np.asarray(b[n]) != np.asarray(a[n]), axis=-1)
            cells |= set(zip(*(i.tolist() for i in np.nonzero(diff))))
    return cells


@pytest.mark.parametrize("impl", ["reference", "pallas"])
@pytest.mark.parametrize("step", ["decode", "chunk"])
def test_paged_steps_write_only_their_cells(cfg, params, step, impl):
    """The layer scan carries each group's whole pool stack and updates it
    in place, so a step must leave every cell it does not write as it was.
    After one decode step and one chunk step, every layer's pool differs
    from its input exactly at the (page, slot) cells of the tokens
    written; the dropped writes change nothing: a lane at table capacity,
    chunk rows inside the read-only shared pages below ``n_skip``, and the
    chunk's bucket padding."""
    from repro.models.prefill import prefill_chunk_paged

    c = cfg.replace(attn_impl=impl)
    ps, mp = 4, 3
    pools = _random_pools(c, 12, ps)
    if step == "decode":
        # lane 0 writes position 5 (page 2, slot 1); lane 1 is at capacity;
        # lane 2 writes position 2 (page 7, slot 2)
        table = jnp.asarray([[1, 2, 3], [4, 5, 6], [7, 8, 9]], jnp.int32)
        pos = jnp.asarray([5, mp * ps, 2], jnp.int32)
        iota = jnp.arange(mp * ps)
        kv_pos = jnp.where(iota[None] <= jnp.minimum(pos, mp * ps - 1)[:, None],
                           iota[None], -1).astype(jnp.int32)
        tok = jnp.asarray([[7], [8], [9]], jnp.int32)
        _, out, _ = decode_step_paged(params, c, pools, table, kv_pos, tok, pos)
        written = {(2, 1), (7, 2)}
    else:
        # an 8-row bucket holding 5 rows at position 2, page 1 shared
        # (n_skip=1): positions 2, 3 are dropped, 4..6 land in page 2's
        # slots 0..2, and the padding rows (7..9) are dropped
        table = jnp.asarray([[1, 2, 3]], jnp.int32)
        tokens = jnp.asarray([[3, 1, 4, 1, 5, 9, 2, 6]], jnp.int32)
        _, out = prefill_chunk_paged(
            params, c, pools, table, tokens, jnp.asarray([2], jnp.int32),
            jnp.asarray([5], jnp.int32), n_skip=1,
        )
        written = {(2, 0), (2, 1), (2, 2)}
    expect = {(layer,) + cell for layer in range(cfg.n_layers) for cell in written}
    assert _written_cells(pools, out) == expect


# ---------------------------------------------------------------------------
# Pool page accounting (deterministic; the pool is the sole allocator client)
# ---------------------------------------------------------------------------

def test_pool_page_accounting(cfg, params):
    # share_prefixes off: this test pins down the *unshared* accounting
    # identity (every entry page is a distinct physical page); the
    # cross-session dedup accounting has its own test below
    max_len = 64
    ids = (np.arange(40)[None] % cfg.vocab_size).astype(np.int32)
    _, dense, _ = prefill(params, cfg, jnp.asarray(ids), max_len=max_len)
    alloc = PagedKVAllocator(cfg, page_size=16, n_pages=9, share_prefixes=False)
    pool = SessionCachePool(capacity=8, allocator=alloc)

    pool.put("a", CacheEntry(list(range(40)), caches=dense))      # 3 pages
    pool.put("b", CacheEntry(list(range(20)), caches=dense))      # 2 pages
    assert pool.peek("a").paged and pool.peek("a").caches is None
    assert pool.pages_in_use == 5 == alloc.used_pages

    # divergent match invalidates and frees the entry's pages
    entry, usable = pool.match("b", [99, 98])
    assert entry is None and usable == 0
    assert pool.pages_in_use == 3 == alloc.used_pages

    # page-budgeted insert: needs 3 pages, only 5 free at capacity 8 is
    # fine; then a put that cannot fit reclaims the LRU entry
    pool.put("c", CacheEntry(list(range(33)), caches=dense))      # 3 pages
    assert alloc.used_pages == 6
    pool.put("d", CacheEntry(list(range(48)), caches=dense))      # 3 pages
    assert "a" not in pool and pool.evictions >= 1                # LRU evicted
    assert pool.pages_in_use == alloc.used_pages

    # low-priority puts never reclaim: fill the pool, then prime-insert
    free = alloc.n_free
    big = CacheEntry(list(range(free * 16 + 1)), caches=dense)
    pool.put("p", big, low_priority=True)
    assert "p" not in pool and pool.rejects == 1
    assert pool.pages_in_use == alloc.used_pages

    pool.clear()
    assert alloc.used_pages == 0 and pool.pages_in_use == 0


def test_pool_page_accounting_shared(cfg, params):
    """Cross-session dedup accounting: entries with a common token prefix
    share physical pages — logical pages_in_use exceeds used_pages by the
    dedup, unique_pages equals the physical count, and releasing one sharer
    keeps the page alive for the other."""
    max_len = 64
    ids = (np.arange(40)[None] % cfg.vocab_size).astype(np.int32)
    _, dense, _ = prefill(params, cfg, jnp.asarray(ids), max_len=max_len)
    alloc = PagedKVAllocator(cfg, page_size=16, n_pages=9)
    pool = SessionCachePool(capacity=8, allocator=alloc)

    pool.put("a", CacheEntry(list(range(40)), caches=dense))   # 3 pages
    pool.put("b", CacheEntry(list(range(20)), caches=dense))   # 2, first shared
    assert pool.pages_in_use == 5           # logical: each entry's own view
    assert alloc.used_pages == 4            # physical: page 0 deduped
    s = pool.stats()
    assert s["unique_pages"] == 4
    shared_page = pool.peek("a").pages[0]
    assert pool.peek("b").pages[0] == shared_page
    assert alloc.refcount(shared_page) == 2

    # donor eviction keeps the shared page alive for the sharer
    pool.invalidate("a")
    assert alloc.refcount(shared_page) == 1
    assert alloc.used_pages == 2 == pool.pages_in_use
    # ... and the index still names only live pages
    for pg in alloc.index.pages():
        assert alloc.refcount(pg) > 0
    pool.clear()
    assert alloc.used_pages == 0 and len(alloc.index) == 0


def test_cow_divergence_mid_page_isolated(cfg, params):
    """Copy-on-write isolation: two sessions sharing a full-page prefix and
    diverging MID-page must share exactly the full common pages and nothing
    else — each one's materialized bytes equal its own from-scratch prefill,
    so neither ever observes the other's writes."""
    max_len = 64
    ids_a = list(range(32)) + [500, 501, 502, 503, 504, 505, 506, 507]
    ids_b = list(range(32)) + [500, 501, 600, 601, 602, 603, 604, 605]
    # same first 2 pages, divergence at token 34 — inside page 2
    _, dense_a, _ = prefill(
        params, cfg, jnp.asarray(np.asarray(ids_a)[None], np.int32), max_len=max_len
    )
    _, dense_b, _ = prefill(
        params, cfg, jnp.asarray(np.asarray(ids_b)[None], np.int32), max_len=max_len
    )
    alloc = PagedKVAllocator(cfg, page_size=16, n_pages=9)
    pool = SessionCachePool(capacity=8, allocator=alloc)
    pool.put("a", CacheEntry(list(ids_a), caches=dense_a))
    pool.put("b", CacheEntry(list(ids_b), caches=dense_b))
    pa, pb = pool.peek("a").pages, pool.peek("b").pages
    assert pa[:2] == pb[:2]                  # full common pages: shared
    assert pa[2] != pb[2]                    # divergent page: fresh copy
    assert all(alloc.refcount(p) == 2 for p in pa[:2])
    for key, dense, n in (("a", dense_a, 40), ("b", dense_b, 40)):
        back = pool.materialize(pool.peek(key), n, max_len)
        valid = back[0]["kv_pos"] >= 0
        vm = valid[None, :, :, None, None]
        assert jnp.array_equal(
            jnp.where(vm, back[0]["k"], 0), jnp.where(vm, dense[0]["k"], 0)
        )
        assert jnp.array_equal(
            jnp.where(vm, back[0]["v"], 0), jnp.where(vm, dense[0]["v"], 0)
        )


@pytest.mark.slow
def test_cow_three_way_donor_eviction(cfg, params):
    """Donor eviction with live sharers: three sessions share the donor's
    prefix pages; evicting the donor must keep those pages resident (the
    sharers' refs pin them), keep the index mapping alive so LATER
    admissions still match, and keep every surviving entry's bytes exact."""
    max_len = 64
    base = list(range(32))
    mk = lambda suff: base + [700 + suff * 13 + i for i in range(6)]
    dense = {}
    for name, s in (("donor", 0), ("b", 1), ("c", 2), ("late", 3)):
        ids = mk(s)
        _, d, _ = prefill(
            params, cfg, jnp.asarray(np.asarray(ids)[None], np.int32),
            max_len=max_len,
        )
        dense[name] = (ids, d)
    alloc = PagedKVAllocator(cfg, page_size=16, n_pages=12)
    pool = SessionCachePool(capacity=8, allocator=alloc)
    for name in ("donor", "b", "c"):
        ids, d = dense[name]
        pool.put(name, CacheEntry(list(ids), caches=d))
    shared = pool.peek("donor").pages[:2]
    assert pool.peek("b").pages[:2] == shared == pool.peek("c").pages[:2]
    assert all(alloc.refcount(p) == 3 for p in shared)

    pool.invalidate("donor")                  # donor gone, sharers remain
    assert all(alloc.refcount(p) == 2 for p in shared)
    assert set(shared) <= set(alloc.index.pages())

    ids, d = dense["late"]                    # post-eviction admission still
    pool.put("late", CacheEntry(list(ids), caches=d))   # matches the run
    assert pool.peek("late").pages[:2] == shared
    assert all(alloc.refcount(p) == 3 for p in shared)
    for name in ("b", "c", "late"):
        ids, d = dense[name]
        back = pool.materialize(pool.peek(name), len(ids), max_len)
        valid = back[0]["kv_pos"] >= 0
        vm = valid[None, :, :, None, None]
        assert jnp.array_equal(
            jnp.where(vm, back[0]["k"], 0), jnp.where(vm, d[0]["k"], 0)
        )
    pool.clear()
    assert alloc.used_pages == 0 and len(alloc.index) == 0


def test_same_key_growth_reuses_own_pages(cfg, params):
    """Regression: replacing a key's own paged entry under page pressure
    frees the superseded pages first — a growing session must not evict
    every other tenant just to update itself."""
    max_len = 64
    ids = (np.arange(40)[None] % cfg.vocab_size).astype(np.int32)
    _, dense, _ = prefill(params, cfg, jnp.asarray(ids), max_len=max_len)
    alloc = PagedKVAllocator(cfg, page_size=16, n_pages=6)  # 5 allocatable
    pool = SessionCachePool(capacity=8, allocator=alloc)
    pool.put("a", CacheEntry(list(range(40)), caches=dense))  # 3 pages
    pool.put("b", CacheEntry(list(range(10)), caches=dense))  # 1 page
    # growing "a" to 4 pages: 1 free + its own 3 released >= 4 — "b" stays
    pool.put("a", CacheEntry(list(range(50)), caches=dense))
    assert "b" in pool and pool.peek("a").pos == 50
    assert pool.evictions == 0 and pool.rejects == 0
    assert alloc.used_pages == pool.pages_in_use == 5


# ---------------------------------------------------------------------------
# Server equivalence + page-moving reuse (shared servers: one compile set)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def servers(cfg, params):
    full = BatchedServer(
        cfg, params, n_slots=2, max_len=128,
        session_pool=SessionCachePool(capacity=4),
    )
    paged = BatchedServer(
        cfg, params, n_slots=2, max_len=128,
        session_pool=SessionCachePool(capacity=4),
        paged=True, page_size=16,
    )
    return full, paged


def _run(server, ids, key=None, max_new=6):
    rid = server.submit(ids, max_new=max_new, cache_key=key)
    fin = {f.request_id: f for f in server.run_to_completion()}
    return fin[rid]


def test_paged_server_greedy_equivalent(cfg, params, tok, servers):
    full, paged = servers
    reqs = [tok.encode(f"request {i} about robots and lidar") for i in range(5)]
    rids_f = [full.submit(r, max_new=6) for r in reqs]
    rids_p = [paged.submit(r, max_new=6) for r in reqs]
    fin_f = {f.request_id: f.token_ids for f in full.run_to_completion()}
    fin_p = {f.request_id: f.token_ids for f in paged.run_to_completion()}
    assert [fin_f[r] for r in rids_f] == [fin_p[r] for r in rids_p]
    # keyless requests release every page at finish
    assert paged.allocator.used_pages == 0


@pytest.mark.slow
def test_paged_session_reuse_matches_full_width(tok, servers):
    """Multi-turn sessions: write-back moves the slot's pages into the pool
    entry, and the next turn's admission shares them — token-for-token equal
    to the full-width pool path, same reuse accounting."""
    full, paged = servers
    ctx = []
    for turn in range(3):
        ids = ctx + tok.encode(f"turn {turn}: describe the sensor stack")
        f = _run(full, ids, key="sess-eq")
        p = _run(paged, ids, key="sess-eq")
        assert f.token_ids == p.token_ids
        assert f.reused_tokens == p.reused_tokens
        assert f.cache_hit == p.cache_hit == (turn > 0)
        ctx = ids + f.token_ids
    # the paged entry holds pages for its actual tokens, not max_len
    entry = paged.session_pool.peek("sess-eq")
    assert entry.paged
    assert len(entry.pages) == paged.allocator.pages_for(entry.pos)


def test_write_back_moves_pages_zero_copy(tok, servers):
    """After a keyed request finishes, the slot's pages ARE the pool
    entry's pages (refcount 1 — moved, not copied), and the next turn's
    admission shares the full prefix pages instead of reallocating them."""
    _, paged = servers
    f1 = _run(paged, tok.encode("a context that spans multiple pages " * 3),
              key="mv")
    entry = paged.session_pool.peek("mv")
    assert entry.paged and all(
        paged.allocator.refcount(p) == 1 for p in entry.pages
    )
    pages_before = list(entry.pages)
    n_full = entry.pos // paged.allocator.page_size  # fully-shared prefix pages
    f2 = _run(paged, entry.token_ids + tok.encode("next turn"), key="mv")
    assert f2.cache_hit and f2.reused_tokens == entry.pos
    entry2 = paged.session_pool.peek("mv")
    assert entry2.pages[:n_full] == pages_before[:n_full]  # moved, not copied
    assert all(paged.allocator.refcount(p) == 1 for p in entry2.pages)


def test_overlong_direct_submit_truncates(cfg, tok, servers):
    """Regression: a >max_len submission straight into BatchedServer.submit
    (bypassing the service shim) used to trip the _insert_slot assert and
    kill the node service. Both server modes must degrade by truncation —
    oldest tokens dropped, max_new capped — like the blocking shim."""
    for server in servers:
        huge = tok.encode("an endless rambling context " * 60)
        assert len(huge) > server.max_len
        f = _run(server, huge, key=None, max_new=8)
        assert 1 <= len(f.token_ids) <= 8


def test_decode_runoff_stops_cleanly(cfg, tok, servers):
    """A slot whose pos reaches cache width mid-decode must stop at the
    boundary (no silent mode="drop" KV loss) and leave a usable pool entry:
    the next turn of the session still admits and reuses (the strict-prefix
    resend below also exercises the paged tail-page swap path)."""
    for server in servers:
        filler = tok.encode("long session history " * 30)[: server.max_len - 10]
        f = _run(server, filler, key="runoff", max_new=500)
        # truncate_for_cache reserves at most 16 generation slots near the cap
        assert 1 <= len(f.token_ids) <= 16
        entry = server.session_pool.peek("runoff")
        assert entry is not None and entry.pos <= server.max_len
        f2 = _run(server, entry.token_ids[: server.max_len // 2], key="runoff",
                  max_new=4)
        assert f2.cache_hit and len(f2.token_ids) >= 1
        server.session_pool.invalidate("runoff")


def test_paged_prime_writes_pages(cfg, tok, servers):
    """BatchedServer.prime on the paged server lands the warm-start KV in
    pages (best-effort, low priority), and admission reuses it."""
    _, paged = servers
    ctx = tok.encode("replicated context from a keygroup peer")
    assert paged.prime("roam", ctx)
    entry = paged.session_pool.peek("roam")
    assert entry.paged and entry.source == "prime"
    f = _run(paged, ctx + tok.encode("fresh prompt"), key="roam")
    assert f.cache_hit and f.warm_start and f.reused_tokens == len(ctx)
    paged.session_pool.invalidate("roam")


def test_prime_already_covered_true_under_page_pressure(cfg, tok, servers):
    """Regression: a prime whose entry already covers the sequence is a
    no-op success even with zero free pages — the free-page guard must not
    run before the covers-everything check."""
    _, paged = servers
    ctx = tok.encode("already primed context")
    assert paged.prime("cover", ctx)
    held = paged.allocator.alloc(paged.allocator.n_free)  # exhaust the pool
    try:
        assert paged.allocator.n_free == 0
        assert paged.prime("cover", ctx)          # covered: still True
        assert not paged.prime("fresh-key", ctx)  # genuinely needs pages
    finally:
        paged.allocator.decref(held)
        paged.session_pool.invalidate("cover")


def test_concurrent_same_key_admissions_are_isolated(cfg, params, tok, servers):
    """Regression: two in-flight requests sharing a cache_key (client retry)
    must not share a live tail page — the tail-page swap at admission keeps
    slot KV isolated, so both decode exactly like the full-width server."""
    full, paged = servers
    ctx = tok.encode("session history for a duplicated retry")
    outs = {}
    for srv in (full, paged):
        _run(srv, ctx, key="dup", max_new=6)
        base = srv.session_pool.peek("dup").token_ids
        ids = base + tok.encode("the retried question")
        r1 = srv.submit(ids, max_new=6, cache_key="dup")
        r2 = srv.submit(ids, max_new=6, cache_key="dup")
        fin = {f.request_id: f for f in srv.run_to_completion()}
        srv.finished.clear()
        outs[srv.paged] = (fin[r1].token_ids, fin[r2].token_ids)
        srv.session_pool.invalidate("dup")
    assert outs[False] == outs[True]
    assert outs[True][0] == outs[True][1]


@pytest.mark.slow
def test_full_width_server_shares_paged_engine_pool(cfg, tok):
    """Mixed topology: a paged single-stream engine and a full-width
    batched server share one node pool. The server must materialize paged
    entries on admission (not assume entry.caches), and its dense
    write-back is re-paged by the pool."""
    from repro.serving import JaxLLMService

    svc = JaxLLMService.create(
        "tiny-paged", cfg, max_len=128, page_size=16, kv_pages=33
    )
    pool = svc.engine.session_pool
    ctx = tok.encode("context replicated from a peer node")
    assert svc.prime("mix", ctx)
    assert pool.peek("mix").paged

    srv = BatchedServer(cfg, svc.engine.params, n_slots=2, max_len=128,
                        session_pool=pool)  # full-width server, paged pool
    f = _run(srv, ctx + tok.encode("fresh prompt"), key="mix")
    assert f.cache_hit and f.warm_start and f.reused_tokens == len(ctx)
    entry = pool.peek("mix")
    assert entry.paged and entry.pos > len(ctx)  # write-back re-paged


@pytest.mark.slow
def test_tight_budget_session_recovers_by_evicting_donor(cfg, params, tok):
    """Regression: when the only reclaimable pages belong to the request's
    own reuse-donor entry (excluded from normal reclaim), admission must
    evict the donor and admit cold instead of raising 'pool too small' and
    killing the node service."""
    srv = BatchedServer(
        cfg, params, n_slots=1, max_len=64,
        session_pool=SessionCachePool(capacity=4),
        paged=True, page_size=16, kv_pages=1 + 3,
    )
    ids = tok.encode("a session that nearly fills the page pool")[:20]
    f1 = _run(srv, ids, key="big", max_new=8)
    entry = srv.session_pool.peek("big")
    assert entry is not None
    ids2 = entry.token_ids + tok.encode("more and more context words here")
    f2 = _run(srv, ids2, key="big", max_new=8)   # raised before the fix
    assert len(f2.token_ids) >= 1


def test_echo_prime_shorter_prefix_is_noop():
    """Regression (Echo twin parity): re-delivering an older, shorter
    context version must not truncate the held prefix or relabel its
    provenance — same as prime_session_pool's covers-everything no-op."""
    from repro.edge import EchoLLMService

    svc = EchoLLMService(model="m", vocab_size=1000, kv_reuse=True)
    p = [1, 2, 3, 4]
    r = svc.completion([], p, 8, cache_key="k")    # serve: holds p + gen
    held = svc._kv_prefix["k"]
    assert svc._kv_source["k"] == "serve"
    assert svc.prime("k", held[:3])                # stale shorter re-delivery
    assert svc._kv_prefix["k"] == held             # not truncated
    assert svc._kv_source["k"] == "serve"          # not relabeled


@pytest.mark.slow
def test_pool_exhaustion_mid_decode_degrades_gracefully(cfg, params, tok):
    """A page-multiple prompt admitted into a pool with no growth headroom
    still generates at least one token (admission covers pos n), and a slot
    that cannot grow mid-decode retires cleanly instead of crashing."""
    srv = BatchedServer(
        cfg, params, n_slots=1, max_len=64,
        session_pool=SessionCachePool(capacity=2),
        paged=True, page_size=16, kv_pages=1 + 3,
    )
    ids = [(i * 13) % cfg.vocab_size for i in range(32)]  # exactly 2 pages
    rid = srv.submit(ids, max_new=40, cache_key=None)
    fin = {f.request_id: f for f in srv.run_to_completion()}
    # pages cover 48 positions; decode stops at the boundary with the
    # 16 tokens that fit — never zero, never an exception
    assert 1 <= len(fin[rid].token_ids) <= 16
    assert srv.allocator.used_pages == 0  # everything released


# ---------------------------------------------------------------------------
# Tenant capacity: ≥2x sessions resident within the same KV budget
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_paged_doubles_resident_sessions_in_same_budget(cfg, params, tok):
    """Budget = 2 full-width lanes of KV bytes. A full-width pool fits 2
    session entries in that budget; the paged pool keeps all 4 tenants'
    actual KV resident in the same bytes, so every tenant's second turn is
    a pool hit while the full-width pool thrashes."""
    max_len, n_tenants = 128, 4
    lane_pages = max_len // 16
    paged = BatchedServer(
        cfg, params, n_slots=2, max_len=max_len,
        session_pool=SessionCachePool(capacity=8),
        paged=True, page_size=16, kv_pages=1 + 2 * lane_pages,
    )
    full = BatchedServer(
        cfg, params, n_slots=2, max_len=max_len,
        session_pool=SessionCachePool(capacity=2),   # same byte budget
    )
    lane_bytes = full._cache_bytes(full.caches) // full.n_slots
    assert paged.allocator.total_kv_bytes == 2 * lane_bytes

    base = {i: tok.encode(f"tenant {i} context about robots") for i in range(n_tenants)}
    hist = {}
    for i in range(n_tenants):
        f = _run(paged, base[i], key=f"t{i}", max_new=4)
        hist[i] = base[i] + f.token_ids
        g = _run(full, base[i], key=f"t{i}", max_new=4)
        assert g.token_ids == f.token_ids  # same budget, same outputs
    follow = {i: hist[i] + tok.encode("next") for i in range(n_tenants)}
    paged_hits = sum(
        _run(paged, follow[i], key=f"t{i}", max_new=4).cache_hit
        for i in range(n_tenants)
    )
    full_hits = sum(
        _run(full, follow[i], key=f"t{i}", max_new=4).cache_hit
        for i in range(n_tenants)
    )
    assert paged_hits == n_tenants        # >= 2x tenants warm per budget
    assert full_hits <= n_tenants // 2    # entry-counted LRU thrashes
    assert len(paged.session_pool) == n_tenants
    assert paged.allocator.resident_kv_bytes <= paged.allocator.total_kv_bytes
    assert len(full.session_pool) <= 2


# ---------------------------------------------------------------------------
# Fused paged-attention kernel on the serving path
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_pallas_paged_server_greedy_equivalent(cfg, params, tok):
    """End-to-end equivalence of the decode inner loop's two executions:
    a paged BatchedServer with ``attn_impl="pallas"`` (fused kernel
    attending through the page table, interpret mode on CPU) must emit
    greedy tokens identical to the paged gather-reference server —
    including multi-turn page reuse, where admission increfs shared prefix
    pages and the kernel reads them in place."""
    servers = {}
    for impl in ("reference", "pallas"):
        servers[impl] = BatchedServer(
            cfg.replace(attn_impl=impl), params, n_slots=2, max_len=128,
            session_pool=SessionCachePool(capacity=4),
            paged=True, page_size=16,
        )
    reqs = [tok.encode(f"request {i} about the lidar rig") for i in range(3)]
    outs = {}
    for impl, srv in servers.items():
        rids = [srv.submit(r, max_new=6) for r in reqs]
        fin = {f.request_id: f.token_ids for f in srv.run_to_completion()}
        outs[impl] = [fin[r] for r in rids]
        srv.finished.clear()
    assert outs["reference"] == outs["pallas"]

    ctx = []
    for turn in range(2):
        ids = ctx + tok.encode(f"turn {turn}: what changed?")
        fins = {impl: _run(srv, ids, key="kq") for impl, srv in servers.items()}
        assert fins["reference"].token_ids == fins["pallas"].token_ids
        assert fins["reference"].reused_tokens == fins["pallas"].reused_tokens
        assert fins["reference"].cache_hit == fins["pallas"].cache_hit == (turn > 0)
        ctx = ids + fins["reference"].token_ids


@pytest.mark.slow
def test_cross_session_sharing_token_identical(cfg, params, tok):
    """Tentpole e2e equivalence: N tenants with an identical multi-page
    system prompt, served with sharing on (reference + pallas cascade) and
    sharing off — greedy outputs token-identical everywhere, while the
    sharing servers hold strictly fewer physical pages and record the
    cross-session hits."""
    base = tok.encode("system: you are a helpful edge assistant. " * 6)
    assert len(base) >= 48                      # spans >= 3 full 16-pages
    reqs = [
        base + tok.encode(f"tenant {i}: what do you see?") for i in range(4)
    ]
    variants = {
        "ref_on": ("reference", True),
        "ref_off": ("reference", False),
        "pallas_on": ("pallas", True),
    }
    outs, srvs = {}, {}
    for name, (impl, share) in variants.items():
        srv = BatchedServer(
            cfg.replace(attn_impl=impl), params, n_slots=2, max_len=128,
            session_pool=SessionCachePool(capacity=8),
            paged=True, page_size=16, share_prefixes=share,
        )
        rids = [
            srv.submit(list(r), max_new=5, cache_key=f"t{i}")
            for i, r in enumerate(reqs)
        ]
        fin = {f.request_id: f.token_ids for f in srv.run_to_completion()}
        outs[name] = [fin[r] for r in rids]
        srvs[name] = srv
    assert outs["ref_on"] == outs["ref_off"] == outs["pallas_on"]

    on, off = srvs["ref_on"], srvs["ref_off"]
    # sharing dedups the common prompt pages: strictly fewer physical pages
    # resident for the same logical state, and the hits are accounted
    assert on.allocator.used_pages < off.allocator.used_pages
    s_on = on.session_pool.stats()
    assert s_on["shared_hits"] >= 3 and s_on["shared_tokens"] >= 3 * 48
    assert s_on["unique_pages"] < s_on["pages_in_use"]
    off_s = off.session_pool.stats()
    assert off_s["shared_hits"] == 0
    assert off_s["unique_pages"] == off_s["pages_in_use"]
    # invariants hold on every server: accounting balances, index only
    # names live pages
    for srv in srvs.values():
        alloc = srv.allocator
        assert alloc.used_pages + alloc.n_free == alloc.n_pages - 1
        for pg in alloc.index.pages():
            assert alloc.refcount(pg) > 0


# ---------------------------------------------------------------------------
# Full-width vs paged equivalence sweep under page pressure
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_full_width_vs_paged_equivalence_sweep(cfg, params, tok):
    """Interleaved multi-tenant sessions with a page budget tight enough to
    force reclaim: outputs must stay token-identical to the full-width
    server — reuse is a performance optimization, never a correctness
    dependency."""
    max_len = 128
    full = BatchedServer(
        cfg, params, n_slots=4, max_len=max_len,
        session_pool=SessionCachePool(capacity=16),
    )
    paged = BatchedServer(
        cfg, params, n_slots=4, max_len=max_len,
        session_pool=SessionCachePool(capacity=16),
        paged=True, page_size=16, kv_pages=1 + 3 * (max_len // 16),
    )
    sessions = {i: tok.encode(f"tenant {i} opening question") for i in range(6)}
    for rnd in range(3):
        rids_f = {
            i: full.submit(list(ids), max_new=5, cache_key=f"s{i}")
            for i, ids in sessions.items()
        }
        rids_p = {
            i: paged.submit(list(ids), max_new=5, cache_key=f"s{i}")
            for i, ids in sessions.items()
        }
        fin_f = {f.request_id: f for f in full.run_to_completion()}
        fin_p = {f.request_id: f for f in paged.run_to_completion()}
        for i in sessions:
            tf, tp = fin_f[rids_f[i]].token_ids, fin_p[rids_p[i]].token_ids
            assert tf == tp, (rnd, i)
            sessions[i] = sessions[i] + tf + tok.encode(f"round {rnd} follow-up")
        full.finished.clear()
        paged.finished.clear()
    # page accounting stayed consistent under pressure
    alloc = paged.allocator
    assert alloc.used_pages == paged.session_pool.pages_in_use
    assert alloc.used_pages + alloc.n_free == alloc.n_pages - 1


