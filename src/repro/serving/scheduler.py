"""Multi-tenant continuous-batching scheduler (beyond-paper: the paper's
evaluation is single-client and names multi-tenant scalability as future
work, §5).

Slot-based continuous batching: a fixed decode batch of ``n_slots`` shares
one batched KV cache. Every step() decodes all decode-ready slots in a
single jitted call. Finished sequences free their slot for the next queued
request — the standard vLLM-style loop.

Paged mode runs *unified steps* (docs/architecture.md, "Chunked paged
prefill"): admission only plans — it reserves pages, shares resident
prefix pages, and enqueues the un-covered prompt tokens as a chunk plan —
and each step() first drains up to ``prefill_chunk_tokens`` prompt tokens
from the plans (page-aligned B=1 chunks computed straight into the lane's
pages by :class:`~repro.serving.chunked_prefill.PagedPrefiller`; no dense
intermediate, no write-through), then decodes the decode-ready lanes. A
long-context admission therefore costs resident tenants a bounded
per-token latency bump per step instead of one monolithic prefill stall
(``prefill_chunk_tokens=None`` restores the stall behavior — the
benchmark baseline). Plans drain in strict FIFO admission order, which is
what makes same-wave prefix sharing safe: a later admission may incref an
earlier *active* slot's fully-covered prompt pages, because the donor's
chunks always complete before the reader's first chunk runs.

The scheduler can share a :class:`~repro.serving.session_cache.
SessionCachePool` with the rest of the node (``session_pool``): a request
submitted with a ``cache_key`` prefix-matches the pool on admission and,
on a hit, chunk-prefills only its new-token suffix into the slot
(:func:`repro.models.prefill_append`) instead of prefilling from scratch;
when the request finishes, its slot's KV state is written back to the pool
under the same key. This closes the loop with the migration warm-start
path (docs/architecture.md, "Migration warm-start"): a context primed on
replication arrival speeds up the continuous-batching path too, not just
the single-stream Context Manager path.

:class:`BatchedLLMService` mounts the server as a node's LLM Service on the
submit/await serving path (docs/architecture.md, "Async serving path"):
concurrent sessions on one edge node share the decode batch and the session
KV pool, with per-request ``queue_ms``/``batch_size`` accounting flowing
back into :class:`~repro.core.protocol.Timing`.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import lru_cache, partial
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.manager import ServiceCapabilities, ServiceResult
from ..models import (
    ModelConfig,
    decode_step,
    decode_step_paged,
    init_params,
    make_decode_caches,
    prefill,
    prefill_append,
    supports_append,
)
from ..models.cache import trim_cache_prefix
from ..store.network import Network
from ..tokenizer import EOS, IM_END, ByteLevelBPE, get_tokenizer
from .chunked_prefill import PagedPrefiller, prime_fill_pages
from .engine import _bucket, chunked_append, prime_session_pool, truncate_for_cache
from .paged_kv import SCRATCH_PAGE, PagedKVAllocator
from .sampling import sample
from .session_cache import (
    CacheEntry,
    SessionCachePool,
    longest_common_prefix,
    warm_source_of,
)


@lru_cache(maxsize=None)
def _server_fns(cfg: ModelConfig, max_len: int):
    """The jitted steps of a :class:`BatchedServer`, shared by every server
    of one configuration: nodes that serve the same model trace and compile
    each shape once per device, not once per node."""

    @jax.jit
    def prefill_one(params, tokens, true_len):
        return prefill(params, cfg, tokens, max_len=max_len, true_len=true_len)

    @jax.jit
    def append_one(params, caches, tokens, p0, true_len):
        return prefill_append(params, cfg, caches, tokens, p0, true_len=true_len)

    @partial(jax.jit, donate_argnums=(1,))
    def decode(params, caches, tokens, pos):
        return decode_step(params, cfg, caches, tokens, pos)

    @partial(jax.jit, donate_argnums=(1, 3))
    def decode_paged(params, pools, table, kv_pos, tokens, pos,
                     shared_pages=None):
        return decode_step_paged(
            params, cfg, pools, table, kv_pos, tokens, pos, shared_pages,
        )

    return prefill_one, append_one, decode, decode_paged


@dataclass
class SlotState:
    request_id: int
    pos: int
    generated: List[int] = field(default_factory=list)
    max_new: int = 128
    done: bool = False
    # session-pool bookkeeping (None when submitted without a cache_key)
    cache_key: Optional[str] = None
    token_ids: List[int] = field(default_factory=list)
    reused_tokens: int = 0
    warm_start: bool = False
    warm_source: str = "none"    # "tokens" | "pages" | "none"
    # peak number of occupied slots observed while this request decoded
    batch_size: int = 1
    # chunked-prefill plan (paged mode): prompt tokens not yet in pages.
    # The slot joins the decode batch only once the plan drains.
    prefilled: bool = False
    pending: List[int] = field(default_factory=list)
    prefill_p0: int = 0      # absolute position of the next chunk
    n_skip: int = 0          # leading read-only shared-prefix pages
    # latency accounting (wall clock)
    ttft_ms: float = 0.0
    gaps_ms: List[float] = field(default_factory=list)
    last_tok_t: Optional[float] = None


@dataclass
class FinishedRequest:
    request_id: int
    token_ids: List[int]
    submitted_at: float
    finished_at: float
    # session-KV reuse accounting (0 / False without a pool hit)
    cache_hit: bool = False
    reused_tokens: int = 0
    warm_start: bool = False
    warm_source: str = "none"    # "tokens" | "pages" | "none"
    # peak decode batch this request shared (1 = it ran alone)
    batch_size: int = 1
    # wall-clock latency: submit -> first generated token determined, and
    # the per-token decode gap distribution (time between consecutive
    # generated tokens — inflated for residents while other tenants'
    # prefill chunks share their steps, which is exactly the interference
    # the chunk budget bounds)
    ttft_ms: float = 0.0
    decode_p50_ms: float = 0.0
    decode_p99_ms: float = 0.0


class BatchedServer:
    def __init__(
        self,
        cfg: ModelConfig,
        params,
        n_slots: int = 4,
        max_len: int = 512,
        stop_tokens=(EOS, IM_END),
        session_pool: Optional[SessionCachePool] = None,
        paged: bool = False,
        page_size: int = 16,
        kv_pages: Optional[int] = None,
        share_prefixes: bool = True,
        prefill_chunk_tokens: Optional[int] = 64,
        device: Optional[jax.Device] = None,
    ) -> None:
        assert cfg.attn_variant == "full" and cfg.arch_type in ("dense", "moe", "vlm"), (
            "batched server currently supports full-cache attention archs"
        )
        # params, caches and page pool are committed to one device, so the
        # jitted prefill and decode steps run there (one chip per node in a
        # multi-chip fleet); host-built step inputs are put there directly
        self.device = device if device is not None else jax.devices()[0]
        self.cfg, self.params = cfg, jax.device_put(params, self.device)
        self.n_slots, self.max_len = n_slots, max_len
        self.stop_tokens = set(stop_tokens)
        self.session_pool = session_pool
        self.paged = paged
        # per-step prompt-token budget for chunked prefill (paged mode):
        # each step drains at most this many prompt tokens from the chunk
        # plans before decoding, so a long admission can never stall the
        # resident decoders for its whole prefill. None = unbounded (the
        # full-prefill stall baseline).
        self.prefill_chunk_tokens = prefill_chunk_tokens
        self.slots: List[Optional[SlotState]] = [None] * n_slots
        self.queue: List = []
        self.finished: List[FinishedRequest] = []
        self._submit_times: Dict[int, float] = {}
        self._next_tok = np.zeros((n_slots,), np.int32)
        self._req_seq = 0
        # decode steps that ran the shared-prefix (cascade) pass
        self.cascade_steps = 0

        if paged:
            # Block-granular KV: one shared page pool backs every decode
            # lane AND every session-pool entry; slots hold page lists sized
            # to their actual token count (docs/architecture.md, "Paged
            # session KV"). Default page budget equals the full-width
            # worst case — callers shrink it to trade memory for tenants.
            assert supports_append(cfg), (
                "paged batched serving requires full-cache dense/moe groups"
            )
            assert max_len % page_size == 0, (max_len, page_size)
            if kv_pages is None:
                cap = session_pool.capacity if session_pool is not None else 0
                kv_pages = 1 + (n_slots + cap) * (max_len // page_size)
            self.allocator = PagedKVAllocator(
                cfg, page_size=page_size, n_pages=kv_pages,
                share_prefixes=share_prefixes, device=self.device,
            )
            if session_pool is not None:
                assert session_pool.allocator is None, (
                    "session pool already bound to another allocator"
                )
                session_pool.allocator = self.allocator
                # pages are the memory bound now; lift the entry-count cap
                # so it can never evict before the page budget does (every
                # entry holds >= 1 page)
                session_pool.capacity = max(session_pool.capacity, kv_pages)
            self.caches = None
            self.slot_pages: List[List[int]] = [[] for _ in range(n_slots)]
            self._table = np.full(
                (n_slots, max_len // page_size), SCRATCH_PAGE, np.int32
            )
            self._kv_pos = self._put(np.full((n_slots, max_len), -1, np.int32))
            # chunked-prefill machinery: one driver shared by all lanes, a
            # strict-FIFO drain order over mid-prefill slots, and an iota
            # row for setting a lane's kv_pos once its plan completes
            self._prefiller = PagedPrefiller(cfg, self.params, self.allocator)
            self._prefill_fifo: List[int] = []
            self._iota = self._put(np.arange(max_len, dtype=np.int32))
        else:
            self.allocator = None
            self.caches = self._put(make_decode_caches(
                cfg, n_slots, max_len,
                dtype=jnp.float32 if cfg.compute_dtype == "float32" else None,
            ))

        (self._prefill_one, self._append_one, self._decode,
         self._decode_paged) = _server_fns(cfg, max_len)
        # host-side so mid-prefill lanes can be excluded from decode writes
        # (their entry is pushed past the trimmed table per step) without a
        # device round-trip per lane
        self._pos = np.zeros((n_slots,), np.int32)

    def _put(self, x):
        return jax.device_put(x, self.device)

    # ------------------------------------------------------------------
    def submit(
        self, token_ids: List[int], max_new: int = 32, cache_key: Optional[str] = None
    ) -> int:
        """Queue a request. With ``cache_key`` and a ``session_pool``, the
        request reuses any cached KV prefix for that key on admission and
        registers its final KV state back under the key on completion.

        Overlong inputs are truncated here, at the queue boundary — oldest
        tokens dropped, generation budget capped to the remaining slots —
        exactly like the single-stream service's
        :func:`~repro.serving.engine.truncate_for_cache` path, so a too-long
        context degrades identically on every submission path instead of
        tripping the slot-capacity assert and killing the node service."""
        ids, max_new = truncate_for_cache(
            [], list(token_ids), self.max_len, max_new
        )
        rid = self._req_seq
        self._req_seq += 1
        self.queue.append((rid, ids, max_new, cache_key))
        self._submit_times[rid] = time.perf_counter()
        return rid

    @property
    def busy(self) -> bool:
        return any(s is not None for s in self.slots) or bool(self.queue)

    # -- KV memory accounting (benchmarks/paged_kv_bench.py) -------------
    @staticmethod
    def _cache_bytes(caches) -> int:
        total = 0
        for leaf in jax.tree.leaves(caches):
            if leaf.ndim >= 4:  # k/v tensors only; kv_pos bookkeeping excluded
                total += leaf.size * leaf.dtype.itemsize
        return total

    def resident_kv_bytes(self) -> int:
        """KV bytes held between steps: pages in use for the paged server
        (slots and session-pool entries share the pool); for the full-width
        server, the always-allocated batched lanes plus every pool entry
        (entries of a shared pool may themselves be paged — counted at
        their page cost)."""
        if self.paged:
            return self.allocator.resident_kv_bytes
        total = self._cache_bytes(self.caches)
        pool = self.session_pool
        if pool is not None:
            for e in pool._entries.values():
                if e.paged and pool.allocator is not None:
                    total += len(e.pages) * pool.allocator.page_bytes
                elif e.caches is not None:
                    total += self._cache_bytes(e.caches)
        return total

    def total_kv_bytes(self) -> int:
        """Worst-case KV budget this server can consume."""
        if self.paged:
            return self.allocator.total_kv_bytes
        total = self._cache_bytes(self.caches)
        pool = self.session_pool
        if pool is None:
            return total
        if pool.allocator is not None:
            # shared pool bound to a paged allocator elsewhere on the node:
            # the pool's budget is its page pool, not entry-count * lane
            # (capacity is lifted to the page count in that mode)
            return total + pool.allocator.total_kv_bytes
        per_lane = self._cache_bytes(self.caches) // max(1, self.n_slots)
        return total + pool.capacity * per_lane

    # -- slot admission -------------------------------------------------
    def _insert_slot(
        self, idx: int, rid: int, ids: List[int], max_new: int,
        cache_key: Optional[str] = None,
    ) -> bool:
        """Admit one queued request into free slot ``idx``. Returns False
        (paged mode only) when the page pool can't cover the request even
        after reclaiming evictable session entries — the caller keeps it
        queued and retries once running slots release pages."""
        n = len(ids)
        # Loud capacity check for BOTH admission paths: submit() truncates
        # at the queue boundary, so tripping this means a caller bypassed
        # the queue — the reuse path's scatter writes use mode="drop" and
        # would otherwise silently lose KV past max_len and register a
        # poisoned pool entry.
        assert n < self.max_len, (n, self.max_len)
        entry, usable = None, 0
        if self.session_pool is not None and cache_key is not None:
            entry, usable = self.session_pool.match(cache_key, ids)

        if self.paged:
            # paged admission only PLANS (pages + chunk queue); no model
            # compute runs here — step() drains the plan
            return self._admit_paged(
                idx, rid, ids, max_new, entry, usable, cache_key
            )

        if entry is not None and usable > 0:
            if entry.paged:
                # a full-width server sharing a pool whose entries are
                # paged (e.g. with a paged single-stream engine on the
                # same node): gather to a dense view, kv_pos masked to
                # `usable`
                base = self.session_pool.materialize(entry, usable, self.max_len)
            else:
                base = entry.caches
                if usable < entry.pos:
                    base = trim_cache_prefix(base, usable)
            logits, one_caches, pos = self._append_suffix(base, ids[usable:], usable)
        else:
            usable = 0
            logits, one_caches, pos = self._bucketed_prefill(ids)

        new_caches = []
        for big, small in zip(self.caches, one_caches):
            merged = {}
            for k in big:
                if isinstance(big[k], dict):
                    merged[k] = {kk: self._put_entry(big[k][kk], small[k][kk], idx, kk)
                                 for kk in big[k]}
                else:
                    merged[k] = self._put_entry(big[k], small[k], idx, k)
            new_caches.append(merged)
        self.caches = new_caches
        warm_source = (
            warm_source_of(entry.source)
            if entry is not None and usable > 0 else "none"
        )

        self._pos[idx] = int(pos[0])
        self._next_tok[idx] = int(jnp.argmax(logits[0]))
        now = time.perf_counter()
        self.slots[idx] = SlotState(
            request_id=rid, pos=n, max_new=max_new,
            cache_key=cache_key, token_ids=list(ids), reused_tokens=usable,
            warm_start=warm_source != "none",
            warm_source=warm_source, prefilled=True,
            ttft_ms=(now - self._submit_times[rid]) * 1e3, last_tok_t=now,
        )
        return True

    def _bucketed_prefill(self, ids: List[int]):
        """From-scratch B=1 prefill at a bucketed shape so the jitted
        prefill compiles once per bucket, not once per distinct prompt
        length (true_len masks padding)."""
        n = len(ids)
        s = min(self.max_len, _bucket(n, 16))
        toks = np.zeros((1, s), np.int32)
        toks[0, :n] = np.asarray(ids, np.int32) % self.cfg.vocab_size
        return self._prefill_one(
            self.params, *self._put((toks, np.array([n], np.int32)))
        )

    # -- paged admission ------------------------------------------------
    def _alloc_pages(
        self, m: int, exclude: Optional[str] = None
    ) -> Optional[List[int]]:
        """Allocate ``m`` pages, reclaiming page-budgeted LRU session
        entries (never ``exclude`` — the entry being reused) on pressure."""
        pages = self.allocator.alloc(m)
        if pages is None and self.session_pool is not None:
            self.session_pool.reclaim(m, exclude=exclude)
            pages = self.allocator.alloc(m)
        return pages

    def _reclaimable_pages(self, exclude: Optional[str]) -> int:
        """Pages the pool could actually return to the free list by evicting
        every entry except ``exclude``: only pages whose sole reference is
        the entry count (pages shared with a live slot survive eviction)."""
        pool = self.session_pool
        if pool is None:
            return 0
        return sum(
            1
            for k, e in pool._entries.items()
            if k != exclude and e.paged
            for p in e.pages
            if self.allocator.refcount(p) == 1
        )

    def _admit_paged(
        self, idx: int, rid: int, ids: List[int], max_new: int,
        entry: Optional[CacheEntry], usable: int, cache_key: Optional[str],
    ) -> bool:
        """Paged slot admission only PLANS: pick the best shared prefix,
        incref its pages, allocate fresh pages out to ``n + 1`` positions
        (the first decode token writes at pos ``n``, so admission itself
        guarantees at least one generated token even if the pool is
        exhausted afterwards), and enqueue the un-covered prompt tokens as
        a chunk plan. No model compute runs here — :meth:`step` drains the
        plan in page-aligned chunks straight into the lane's pages
        (:class:`~repro.serving.chunked_prefill.PagedPrefiller`),
        interleaved with resident decodes under ``prefill_chunk_tokens``.

        Three share candidates, best coverage wins, earlier wins ties
        (an entry hit keeps ``reused_tokens`` parity with the full-width
        server; a wave match is the weakest claim — its donor is still
        mid-flight):

        - the key's own pool entry; its coverage may end mid-page, in
          which case the donor's tail page is whole-page device-copied
          into this lane's first fresh page (the copied prefix is causal
          KV, the stale bytes beyond it are overwritten by the first
          chunk);
        - the cross-session content-hash index (docs/architecture.md,
          "Cross-session shared-prefix paging") — full pages only;
        - a same-wave active lane's prompt (:meth:`_same_wave_match`).

        Shared pages are read-only by construction: ``n_skip`` makes the
        chunk scatter drop any write landing in them — copy-on-write.
        Coverage is capped at ``n - 1`` so the final chunk always computes
        the request's first-token logits.

        A feasibility check runs first: if the fresh pages needed exceed
        free + genuinely reclaimable (refcount-1 entry pages, donor
        excluded), fail fast — before any incref, device page copy, or
        reclaim — so a blocked request neither destroys other tenants'
        warm entries for nothing nor pays wasted page churn per retry
        tick."""
        alloc, pool = self.allocator, self.session_pool
        ps = alloc.page_size
        n = len(ids)
        usable = min(usable, n - 1)
        cross = alloc.match_prefix(ids, n - 1)
        wave = self._same_wave_match(ids)
        kind, cover = ("entry", usable) if usable > 0 else ("none", 0)
        if len(cross) * ps > cover:
            kind, cover = "cross", len(cross) * ps
        if len(wave) * ps > cover:
            kind, cover = "wave", len(wave) * ps
        warm_source = warm_source_of(entry.source) if kind == "entry" else "none"

        skip = cover // ps  # leading read-only full shared pages
        tail_src: Optional[int] = None
        if kind == "entry" and cover % ps:
            tail_src = entry.pages[skip]
        fresh_needed = alloc.pages_for(n + 1) - skip
        if fresh_needed > alloc.n_free + self._reclaimable_pages(cache_key):
            return False
        if kind == "entry":
            shared = list(entry.pages[:skip])
        elif kind == "cross":
            shared = list(cross[:skip])
        elif kind == "wave":
            shared = list(wave[:skip])
        else:
            shared = []
        if shared:
            # incref BEFORE any reclaim (_alloc_pages below): eviction of
            # the donor entry must not release pages we are about to share
            alloc.incref(shared)
        fresh = self._alloc_pages(fresh_needed, exclude=cache_key)
        if fresh is None:
            if shared:
                alloc.decref(shared)
            return False
        pages = shared + fresh
        if tail_src is not None:
            alloc.copy_page(tail_src, fresh[0])
        if kind in ("cross", "wave") and pool is not None:
            pool.shared_hits += 1
            pool.shared_tokens += cover

        self.slot_pages[idx] = pages
        self._table[idx, :] = alloc.table_for(pages, self.max_len)
        self._pos[idx] = n
        self.slots[idx] = SlotState(
            request_id=rid, pos=n, max_new=max_new,
            cache_key=cache_key, token_ids=list(ids), reused_tokens=cover,
            warm_start=warm_source != "none", warm_source=warm_source,
            prefilled=False, pending=list(ids[cover:]), prefill_p0=cover,
            n_skip=skip,
        )
        self._prefill_fifo.append(idx)
        return True

    def _same_wave_match(self, ids: List[int]) -> List[int]:
        """Shared-prefix pages from an ACTIVE lane's prompt. The content
        index only sees pages once a chunk completes (progressive
        ``register_pages`` in :meth:`_drain_prefill`), so admissions
        landing in the same step as their donor would miss it — match the
        other slots' prompt tokens directly instead. Only the donor's full
        prompt pages count, capped at ``n - 1`` reader tokens. Safe under
        the strict FIFO plan drain: the donor admitted earlier, so its
        chunks covering these pages complete before this reader's first
        chunk runs, and the donor's decode writes land at ``pos >= lcp``
        — never inside the shared region."""
        if not self.allocator.share_prefixes:
            return []
        ps = self.allocator.page_size
        best: List[int] = []
        for j, st in enumerate(self.slots):
            if st is None or not self.slot_pages[j]:
                continue
            lcp = longest_common_prefix(st.token_ids, ids)
            full = min(lcp, len(ids) - 1) // ps
            if full > len(best):
                best = list(self.slot_pages[j][:full])
        return best

    def _drain_prefill(self) -> None:
        """Drain up to ``prefill_chunk_tokens`` prompt tokens from the
        chunk plans, strict FIFO admission order. Chunks end on page
        boundaries (except a plan's final, possibly ragged, chunk) so
        every completed chunk leaves fully-written pages, which are
        content-indexed right away — later same-wave admissions share
        them. A plan's last chunk yields the request's first decode token:
        ttft stops there and the lane joins the decode batch this very
        step."""
        if not self._prefill_fifo:
            return
        alloc = self.allocator
        ps = alloc.page_size
        budget = self.prefill_chunk_tokens
        if budget is not None:
            budget = max(ps, budget)
        spent = 0
        while self._prefill_fifo:
            if budget is not None and spent >= budget:
                break
            idx = self._prefill_fifo[0]
            st = self.slots[idx]
            assert st is not None and not st.prefilled, idx
            left = len(st.pending)
            cap = 256 if budget is None else min(256, budget - spent)
            c = min(left, cap)
            if c < left:
                # end the chunk on a page boundary: completed pages are
                # final and indexable, and the next chunk starts aligned
                aligned = (st.prefill_p0 + c) // ps * ps - st.prefill_p0
                if aligned > 0:
                    c = aligned
            chunk, st.pending = st.pending[:c], st.pending[c:]
            logits = self._prefiller.run_chunk(
                self.slot_pages[idx], chunk, st.prefill_p0, n_skip=st.n_skip
            )
            st.prefill_p0 += c
            spent += c
            # progressively index this lane's fully-covered prompt pages:
            # 32 tenants with one system prompt arriving as a wave share
            # them as soon as the first tenant's chunks write them
            covered = min(st.prefill_p0, len(st.token_ids)) // ps
            if covered > 0:
                alloc.register_pages(
                    st.token_ids[: covered * ps], self.slot_pages[idx][:covered]
                )
            if not st.pending:
                self._prefill_fifo.pop(0)
                st.prefilled = True
                self._next_tok[idx] = int(jnp.argmax(logits))
                now = time.perf_counter()
                st.ttft_ms = (now - self._submit_times[st.request_id]) * 1e3
                st.last_tok_t = now
                # kv_pos becomes real only now: slot == position for the
                # whole prompt, invalid beyond (layout invariant)
                self._kv_pos = self._kv_pos.at[idx].set(
                    jnp.where(self._iota < st.pos, self._iota, -1)
                )

    def _shared_prefix_run(self, width: int) -> List[int]:
        """Longest run of leading pages IDENTICAL across every
        decode-ready lane's table, power-of-two bucketed (down) so the
        shared-pass kernel compiles at most log2(MP) shapes, and capped
        below ``width`` so the per-lane suffix grid keeps >= 1 page.
        Identical page ids across >= 2 lanes means refcount >= 2, hence
        inside every holder's read-only shared region (a lane's writable
        tail page is exclusively held by construction) — so the run is
        stable for the whole step and holds positions [0, run*page_size)
        for every lane. Mid-prefill lanes are excluded: they don't attend
        this step (their batched-decode output is garbage-unread), so
        they must not shorten the residents' shared run."""
        active = [
            self.slot_pages[i]
            for i, s in enumerate(self.slots)
            if s is not None and s.prefilled
        ]
        if len(active) < 2:
            return []
        first = active[0]
        limit = min(min(len(p) for p in active), width - 1)
        run = 0
        while run < limit and all(p[run] == first[run] for p in active[1:]):
            run += 1
        if run == 0:
            return []
        b = 1
        while b * 2 <= run:
            b *= 2
        return first[:b]

    def _append_suffix(self, caches, suffix_ids: List[int], p0: int):
        """Chunk-prefill ``suffix_ids`` into B=1 ``caches`` starting at p0
        (the reuse path of slot admission; smaller chunks/buckets than the
        single-stream engine — batched requests tend to be short)."""
        return chunked_append(
            self._append_one, self.params, caches, suffix_ids, p0,
            self.cfg.vocab_size, chunk=128, bucket=16,
        )

    @staticmethod
    def _put_entry(big: jnp.ndarray, small: jnp.ndarray, idx: int, name: str):
        if name in ("k", "v"):            # (L,B,T,KV,Dh)
            t = min(big.shape[2], small.shape[2])
            return big.at[:, idx, :t].set(small[:, 0, :t])
        if name == "kv_pos":              # (B,T)
            t = min(big.shape[1], small.shape[1])
            return big.at[idx, :t].set(small[0, :t])
        # ssm states: (L,B,...)
        return big.at[:, idx].set(small[:, 0])

    # -- slot completion -> pool write-back -----------------------------
    def _release_to_pool(self, idx: int, st: SlotState) -> None:
        """Register the finished slot's KV state in the session pool so the
        next turn of this session — on this path or the single-stream
        engine path — is suffix-only. Paged mode *moves* the slot's pages
        into the pool entry (zero-copy; pages past the kept prefix are
        freed); full-width mode copies the slot's lane out of the batched
        caches."""
        prefix = st.token_ids + st.generated
        if self.paged:
            pages = self.slot_pages[idx]
            keep = self.allocator.pages_for(len(prefix))
            if keep < len(pages):
                self.allocator.decref(pages[keep:])
            self.slot_pages[idx] = []
            self.session_pool.put(
                st.cache_key,
                CacheEntry(token_ids=prefix, pages=pages[:keep], source="serve"),
            )
            return
        one = []
        for c in self.caches:
            if not isinstance(c, dict) or "kv_pos" not in c:
                return  # non-full-cache group: skip pooling entirely
            one.append({
                "k": c["k"][:, idx : idx + 1],
                "v": c["v"][:, idx : idx + 1],
                "kv_pos": c["kv_pos"][idx : idx + 1],
            })
        one = trim_cache_prefix(one, len(prefix))
        self.session_pool.put(
            st.cache_key, CacheEntry(token_ids=prefix, caches=one, source="serve")
        )

    def _finish_slot(self, idx: int, st: SlotState) -> None:
        """Retire a slot: write its KV back to the session pool (or free its
        pages), record the FinishedRequest, and open the slot."""
        if self.session_pool is not None and st.cache_key is not None:
            self._release_to_pool(idx, st)
        elif self.paged and self.slot_pages[idx]:
            self.allocator.decref(self.slot_pages[idx])
            self.slot_pages[idx] = []
        if self.paged:
            # inactive lanes keep decoding into the scratch page until the
            # slot is re-admitted; their kv_pos row is junk but unread
            self._table[idx, :] = SCRATCH_PAGE
        gaps = st.gaps_ms
        self.finished.append(
            FinishedRequest(
                st.request_id,
                st.generated,
                self._submit_times.pop(st.request_id),
                time.perf_counter(),
                cache_hit=st.reused_tokens > 0,
                reused_tokens=st.reused_tokens,
                warm_start=st.warm_start,
                warm_source=st.warm_source,
                batch_size=st.batch_size,
                ttft_ms=st.ttft_ms,
                decode_p50_ms=float(np.percentile(gaps, 50)) if gaps else 0.0,
                decode_p99_ms=float(np.percentile(gaps, 99)) if gaps else 0.0,
            )
        )
        self.slots[idx] = None

    def _admit_from_queue(self) -> None:
        """FIFO-fair admission: walk the WHOLE queue in order, admitting
        each feasible request into a free slot and *skipping* (not
        blocking on) requests the page pool can't cover yet — a huge
        head-of-line request waits for pages without starving smaller
        tenants queued behind it, and it keeps its queue position, so it
        still admits first once pages free up (no permanent starvation:
        nothing jumps ahead of it in the queue itself)."""
        free = [i for i in range(self.n_slots) if self.slots[i] is None]
        if not free or not self.queue:
            return
        admitted_any = False
        remaining: List = []
        for item in self.queue:
            if not free:
                remaining.append(item)
                continue
            rid, ids, max_new, cache_key = item
            if self._insert_slot(free[0], rid, ids, max_new, cache_key):
                free.pop(0)
                admitted_any = True
            else:
                remaining.append(item)
        self.queue = remaining
        if admitted_any or not self.queue:
            return
        if any(s is not None for s in self.slots):
            return  # out of pages: retry once running slots release them
        # nothing active, nothing admitted — last resort before declaring
        # the pool too small: the only reclaimable pages may belong to the
        # head request's own session entry (excluded from reclaim as the
        # reuse donor) — evict it and admit cold rather than killing the
        # node service
        rid, ids, max_new, cache_key = self.queue[0]
        if (
            self.session_pool is not None and cache_key is not None
            and cache_key in self.session_pool
        ):
            self.session_pool.invalidate(cache_key)
            if self._insert_slot(free[0], rid, ids, max_new, cache_key):
                self.queue.pop(0)
                return
        raise RuntimeError(
            f"paged KV pool too small: request of {len(ids)} tokens "
            f"cannot be admitted with {self.allocator.n_free} free "
            f"pages of {self.allocator.page_size} and nothing left "
            "to evict — raise kv_pages or lower max_len"
        )

    def step(self) -> None:
        """One unified scheduler tick. Paged mode: admit queued requests
        FIFO-fairly, drain up to ``prefill_chunk_tokens`` prompt tokens
        from the chunk plans (straight into pages), then decode the
        decode-ready lanes in one batched call — prefill chunks and decode
        share every step, so a long admission costs residents a bounded
        latency bump per step instead of a monolithic stall. Full-width
        mode keeps the classic loop: admission prefills in one shot and
        every occupied slot decodes."""
        self._admit_from_queue()
        if self.paged:
            # drain BEFORE counting decoders: a plan completing within this
            # step's budget decodes its first token this very step
            self._drain_prefill()
        n_active = sum(s is not None for s in self.slots)
        if n_active == 0:
            return
        for st in self.slots:
            if st is not None:
                st.batch_size = max(st.batch_size, n_active)

        if self.paged:
            # grow-on-demand: each decode-ready slot needs a page covering
            # the position it is about to write; a slot that cannot get one
            # (pool exhausted, nothing evictable) retires cleanly with the
            # tokens it has — never a silent mode="drop" KV loss.
            # Mid-prefill lanes reserved their whole span at admission.
            ps = self.allocator.page_size
            for idx, st in enumerate(self.slots):
                if st is None or not st.prefilled:
                    continue
                if st.pos >= len(self.slot_pages[idx]) * ps:
                    fresh = self._alloc_pages(1, exclude=st.cache_key)
                    if fresh is None:
                        self._finish_slot(idx, st)
                        continue
                    self.slot_pages[idx].append(fresh[0])
                    self._table[idx, len(self.slot_pages[idx]) - 1] = fresh[0]
            ready = [
                i for i, s in enumerate(self.slots)
                if s is not None and s.prefilled
            ]
            if not ready:
                return  # every occupied lane is mid-prefill
            tokens = self._put(self._next_tok[:, None])
            # page-width bucketing: run the jitted decode at the smallest
            # power-of-two page width covering the longest *decode-ready*
            # lane, not at max_len — the kernel's grid (pallas) or the
            # gathered view (reference) then scales with what sessions
            # actually hold. The layout invariant (slot == position) makes
            # the trimmed attention identical: every ready lane's tokens
            # live in its own pages, all inside the trimmed width. At most
            # log2(MP) decode shapes compile.
            mp = self._table.shape[1]
            need = max(len(self.slot_pages[i]) for i in ready)
            w = 1
            while w < max(1, need):
                w *= 2
            w = min(w, mp)
            wp = w * ps
            # mid-prefill lanes ride the batched call but touch nothing:
            # their decode position is pushed past the trimmed table, so
            # the KV scatter and the kv_pos relabel both drop
            # (models/cache.py OOB sentinel), and their logits lane is
            # never read below
            dec_pos = self._pos.copy()
            for i, s in enumerate(self.slots):
                if s is None or not s.prefilled:
                    dec_pos[i] = wp
            # cross-session shared-prefix split (pallas only — the
            # reference path's gathered view has no per-page DMA to save):
            # pages every ready lane starts with are attended once per
            # unique page for the whole batch instead of once per lane
            sp = None
            if self.cfg.attn_impl == "pallas":
                run = self._shared_prefix_run(w)
                if run:
                    sp = self._put(np.asarray(run, np.int32))
                    self.cascade_steps += 1
            if w < mp:
                logits, pools, kvp = self._decode_paged(
                    self.params, self.allocator.pools,
                    self._put(self._table[:, :w]),
                    self._kv_pos[:, :wp], tokens, self._put(dec_pos), sp,
                )
                self._kv_pos = self._kv_pos.at[:, :wp].set(kvp)
            else:
                logits, pools, self._kv_pos = self._decode_paged(
                    self.params, self.allocator.pools, self._put(self._table),
                    self._kv_pos, tokens, self._put(dec_pos), sp,
                )
            self.allocator.pools = pools
        else:
            logits, self.caches = self._decode(
                self.params, self.caches, *self._put((self._next_tok[:, None], self._pos))
            )
        nxt = np.asarray(sample(logits[:, 0]))
        now = time.perf_counter()

        for idx, st in enumerate(self.slots):
            if st is None or not st.prefilled:
                continue
            tok = int(self._next_tok[idx])
            st.generated.append(tok)
            st.pos += 1
            self._pos[idx] += 1
            # per-token decode gap: inflated for residents while other
            # tenants' prefill chunks share their steps — exactly the
            # interference the chunk budget bounds
            if st.last_tok_t is not None:
                st.gaps_ms.append((now - st.last_tok_t) * 1e3)
            st.last_tok_t = now
            if (
                tok in self.stop_tokens
                or len(st.generated) >= st.max_new
                or st.pos >= self.max_len - 1
            ):
                self._finish_slot(idx, st)
            else:
                self._next_tok[idx] = int(nxt[idx])

    # -- churn -----------------------------------------------------------
    def crash(self) -> None:
        """Process crash: queue, in-flight slots, and the session KV pool
        are all volatile device/process state — drop everything. Paged mode
        returns every page to the free list (the allocator survives as the
        restarted process's fresh pool)."""
        self.queue.clear()
        self._submit_times.clear()
        for idx in range(self.n_slots):
            self.slots[idx] = None
            if self.paged and self.slot_pages[idx]:
                self.allocator.decref(self.slot_pages[idx])
                self.slot_pages[idx] = []
        if self.paged:
            self._table[:, :] = SCRATCH_PAGE
            self._prefill_fifo.clear()
        if self.session_pool is not None:
            self.session_pool.clear()
        self.finished.clear()

    def run_to_completion(self, max_steps: int = 10_000) -> List[FinishedRequest]:
        steps = 0
        while self.busy and steps < max_steps:
            self.step()
            steps += 1
        return self.finished

    # -- migration warm-start -------------------------------------------
    def prime(self, cache_key: str, token_ids: List[int]) -> bool:
        """Pre-warm the shared session pool with the KV state of
        ``token_ids`` — the batched twin of
        :meth:`repro.serving.engine.InferenceEngine.prime`, called off the
        serving hot path when a replicated tokenized context lands on this
        node. A later ``submit(..., cache_key=...)`` for the session then
        admits with a suffix-only chunk plan. Guard/extension/provenance
        semantics live in :func:`repro.serving.engine.prime_session_pool`
        (shared with the single-stream engine); in paged mode the KV is
        chunk-prefilled straight into fresh pages (``_prime_paged_fill``)
        instead of through a dense lane."""
        warm, _ = prime_session_pool(
            self.session_pool, cache_key, list(token_ids),
            self.max_len, self.max_len - 2,
            self._append_suffix, self._bucketed_prefill,
            paged_fill=self._prime_paged_fill if self.paged else None,
        )
        return warm

    def _prime_paged_fill(
        self, token_ids: List[int], entry: Optional[CacheEntry], usable: int
    ) -> Optional[List[int]]:
        return prime_fill_pages(
            self.session_pool, self._prefiller, token_ids, entry, usable
        )

    def install_shipped_pages(
        self,
        cache_key: str,
        token_ids: List[int],
        payloads: List[bytes],
        have_pages: int,
    ) -> bool:
        """Install digest-verified shipped KV pages into the shared session
        pool — the batched twin of
        :meth:`repro.serving.engine.InferenceEngine.install_shipped_pages`.
        Paged servers only (a full-width server has no page pool to import
        into — the shipper falls back to token recompute)."""
        if not self.paged or self.session_pool is None:
            return False
        paged_fill = lambda ids, entry, usable: prime_fill_pages(  # noqa: E731
            self.session_pool, self._prefiller, ids, entry, usable,
            shipped=payloads, ship_have=have_pages,
        )
        warm, _ = prime_session_pool(
            self.session_pool, cache_key, list(token_ids),
            self.max_len, self.max_len - 2,
            self._append_suffix, self._bucketed_prefill,
            paged_fill=paged_fill, source="ship",
        )
        return warm


@dataclass
class _PendingBatched:
    """Per-request bookkeeping between BatchedLLMService.submit and the
    pump observing its FinishedRequest (all times are sim-clock ms)."""

    on_done: Callable[[ServiceResult], None]
    submitted_ms: float
    n_input: int
    admitted_ms: Optional[float] = None


class BatchedLLMService:
    """The :class:`BatchedServer` mounted as a node's LLM Service — the
    multi-tenant serving path of the submit/await API redesign.

    Satisfies :class:`~repro.core.manager.LLMServiceProtocol` with
    ``capabilities().batched`` set: concurrent sessions on the node share
    the server's continuous decode batch and session KV pool, so N tenants
    cost ~one batched decode stream instead of N serialized single streams.

    Sim-clock model: each :meth:`submit` enqueues into the server and
    ensures a *pump* event chain is running. Every pump executes exactly one
    ``server.step()`` (real JAX work, wall-measured) and lays that duration
    onto the sim clock, so requests admitted together genuinely share each
    step's cost. Per request, ``queue_ms`` is submit→slot-admission wait
    and ``inference_ms`` is admission→completion (its share of the batch's
    prefill + decode steps); ``batch_size`` reports the peak batch it rode
    in. ``completion()`` is the blocking shim: submit, pump synchronously,
    return — used by serialized callers and micro-benchmarks."""

    def __init__(
        self,
        model: str,
        server: BatchedServer,
        tokenizer: ByteLevelBPE,
        tokenize_scale: float = 1.0,
        ship_prefill_ms_per_token: float = 0.0,
    ) -> None:
        self.model = model
        self.server = server
        self.tokenizer = tokenizer
        self.tokenize_scale = tokenize_scale
        # measured prefill constant for the KV-ship cost model (0 = this
        # node does not participate in page shipping)
        self.ship_prefill_ms_per_token = ship_prefill_ms_per_token
        self._pending: Dict[int, _PendingBatched] = {}
        self._pump_scheduled = False
        self._busy_until = 0.0
        self._seen_finished = 0
        self._clock_owner: Optional[Network] = None
        # bumped by crash(): pump events scheduled before the crash become
        # no-ops instead of stepping the restarted server
        self._pump_epoch = 0

    @classmethod
    def create(
        cls,
        model: str,
        cfg: ModelConfig,
        *,
        seed: int = 0,
        tokenizer_seed: int = 0,
        n_slots: int = 4,
        max_len: int = 512,
        session_cache_capacity: int = 8,
        paged: bool = False,
        page_size: int = 16,
        kv_pages: Optional[int] = None,
        share_prefixes: bool = True,
        prefill_chunk_tokens: Optional[int] = 64,
        device: Optional[jax.Device] = None,
        params=None,
    ) -> "BatchedLLMService":
        """``device`` (default ``jax.devices()[0]``) is where the service's
        params and KV live and its steps run. ``params`` lets services that
        share a device share one copy of the seeded weights; by default
        they are made from ``seed`` on that device."""
        device = device if device is not None else jax.devices()[0]
        if params is None:
            with jax.default_device(device):
                params = init_params(jax.random.key(seed), cfg)
        pool = (
            SessionCachePool(capacity=session_cache_capacity)
            if session_cache_capacity > 0 and supports_append(cfg)
            else None
        )
        server = BatchedServer(
            cfg, params, n_slots=n_slots, max_len=max_len, session_pool=pool,
            paged=paged and supports_append(cfg), page_size=page_size,
            kv_pages=kv_pages, share_prefixes=share_prefixes,
            prefill_chunk_tokens=prefill_chunk_tokens, device=device,
        )
        tok = get_tokenizer(cfg.vocab_size, seed=tokenizer_seed, name=model)
        return cls(model=model, server=server, tokenizer=tok)

    # -- LLMServiceProtocol ---------------------------------------------
    def capabilities(self) -> ServiceCapabilities:
        return ServiceCapabilities(
            prime=self.server.session_pool is not None,
            kv_reuse=self.server.session_pool is not None,
            batched=True,
            n_slots=self.server.n_slots,
        )

    def prime(self, cache_key: str, token_ids: List[int]) -> bool:
        return self.server.prime(cache_key, list(token_ids))

    # -- KV-page shipping hooks (repro.store.kv_ship) -------------------
    def measure_prefill_ms_per_token(self) -> float:
        """Measure this server's chunked-prefill cost and adopt it as the
        ship-vs-recompute constant (``ship_prefill_ms_per_token``): one
        256-token chunk into scratch-allocated pages, timed after a
        warm-up run of the same shape so compilation is excluded. Paged
        servers only; the pages are returned to the pool."""
        srv = self.server
        assert srv.paged, "prefill cost is measured on the paged path"
        n_tokens = 256
        alloc = srv.allocator
        pages = alloc.alloc(alloc.pages_for(n_tokens))
        assert pages is not None, "no free pages to measure prefill with"
        ids = [int(t) % srv.cfg.vocab_size for t in range(7, 7 + n_tokens)]
        try:
            srv._prefiller.run_chunk(pages, ids, 0).block_until_ready()
            t0 = time.perf_counter()
            srv._prefiller.run_chunk(pages, ids, 0).block_until_ready()
            ms = (time.perf_counter() - t0) * 1e3
        finally:
            alloc.decref(pages)
        self.ship_prefill_ms_per_token = ms / n_tokens
        return self.ship_prefill_ms_per_token

    def kv_ship_profile(self):
        """Shipping constants for the cost model; None when this server
        can't ship (full-width caches, no pool, or no measured prefill
        constant)."""
        srv = self.server
        if (
            not srv.paged
            or srv.session_pool is None
            or self.ship_prefill_ms_per_token <= 0
        ):
            return None
        from ..store.kv_ship import NodeShipProfile

        return NodeShipProfile(
            page_size=srv.allocator.page_size,
            page_wire_bytes=srv.allocator.page_bytes,
            prefill_ms_per_token=self.ship_prefill_ms_per_token,
        )

    def export_kv_pages(self, cache_key: str):
        """Serialize the resident full pages of ``cache_key``'s pool entry
        (native-dtype bytes — bit-exact round trip), or None."""
        pool = self.server.session_pool
        entry = pool.peek(cache_key) if pool is not None else None
        if entry is None or not entry.paged:
            return None
        alloc = self.server.allocator
        full = entry.pos // alloc.page_size
        if full <= 0:
            return None
        from ..store.kv_ship import PageShipment

        return PageShipment(
            token_ids=list(entry.token_ids[: entry.pos]),
            payloads=[
                alloc.export_page_bytes(p) for p in entry.pages[:full]
            ],
        )

    def install_kv_pages(
        self,
        cache_key: str,
        token_ids: List[int],
        payloads: List[bytes],
        have_pages: int,
    ) -> bool:
        return self.server.install_shipped_pages(
            cache_key, list(token_ids), payloads, have_pages
        )

    def resident_ship_pages(self, cache_key: str, token_ids: List[int]) -> int:
        pool = self.server.session_pool
        entry = pool.peek(cache_key) if pool is not None else None
        if entry is None or not entry.paged:
            return 0
        lcp = longest_common_prefix(
            entry.token_ids[: entry.pos], list(token_ids)
        )
        return lcp // self.server.allocator.page_size

    def resident_keys(self) -> Dict[str, int]:
        """Cache key -> resident KV token count (fleet telemetry surface).
        Active slots count too: their KV is on-device and a routed follow-up
        turn would reuse it once the slot's entry lands in the pool."""
        pool = self.server.session_pool
        resident = pool.resident_keys() if pool is not None else {}
        for st in self.server.slots:
            if st is not None and st.cache_key is not None:
                resident[st.cache_key] = max(
                    resident.get(st.cache_key, 0), st.pos
                )
        return resident

    def crash(self) -> None:
        """Process crash: drop pending bookkeeping and the server's queue/
        slots/session pool; any already-scheduled pump event is invalidated
        (the manager has failed the in-flight turns — completions must not
        fire for them)."""
        self._pump_epoch += 1
        self._pending.clear()
        self._pump_scheduled = False
        self._busy_until = 0.0
        self.server.crash()
        self._seen_finished = 0

    def submit(
        self,
        context_ids: List[int],
        prompt_ids: List[int],
        max_new_tokens: int,
        cache_key: Optional[str] = None,
        *,
        net: Network,
        on_done: Callable[[ServiceResult], None],
    ) -> None:
        if self._clock_owner is not net:
            assert not self._pending, "batched service is bound to a live cluster"
            self._clock_owner = net
            self._busy_until = 0.0
            self._pump_scheduled = False
        ids, max_new = truncate_for_cache(
            context_ids, prompt_ids, self.server.max_len, max_new_tokens
        )
        rid = self.server.submit(ids, max_new=max_new, cache_key=cache_key)
        self._pending[rid] = _PendingBatched(
            on_done=on_done, submitted_ms=net.clock.now_ms, n_input=len(ids)
        )
        self._ensure_pump(net)

    def completion(
        self,
        context_ids: List[int],
        prompt_ids: List[int],
        max_new_tokens: int,
        cache_key: Optional[str] = None,
    ) -> ServiceResult:
        """Blocking shim: run the request (and anything already queued)
        to completion on the server, contention-free accounting."""
        assert not self._pending, (
            "blocking completion() cannot interleave with in-flight "
            "submit() requests — drive the event loop instead"
        )
        ids, max_new = truncate_for_cache(
            context_ids, prompt_ids, self.server.max_len, max_new_tokens
        )
        t0 = time.perf_counter()
        rid = self.server.submit(ids, max_new=max_new, cache_key=cache_key)
        done: Dict[int, FinishedRequest] = {}
        while rid not in done:
            self.server.step()
            for f in self.server.finished[self._seen_finished:]:
                done[f.request_id] = f
            self._seen_finished = len(self.server.finished)
        self._drain_consumed()
        f = done[rid]
        return self._result_from(
            f, n_input=len(ids), inference_ms=(time.perf_counter() - t0) * 1e3,
            queue_ms=0.0,
        )

    def _drain_consumed(self) -> None:
        """Drop finished entries the service has already turned into
        results — a node-mounted server lives for the node's lifetime, and
        ``server.finished`` must not grow one entry per request forever.
        (Direct ``BatchedServer.run_to_completion`` users keep their
        accumulated list; only the mounted service drains.)"""
        if self._seen_finished == len(self.server.finished):
            self.server.finished.clear()
            self._seen_finished = 0

    # -- the pump event chain -------------------------------------------
    def _ensure_pump(self, net: Network) -> None:
        if self._pump_scheduled:
            return
        self._pump_scheduled = True
        net.schedule(
            max(net.clock.now_ms, self._busy_until),
            lambda e=self._pump_epoch: self._pump(net, e),
        )

    def _pump(self, net: Network, epoch: Optional[int] = None) -> None:
        """One scheduler tick on the sim clock: admissions are recorded at
        the tick's start, the step's wall time becomes the tick's duration,
        and completions resolve at its end."""
        if epoch is not None and epoch != self._pump_epoch:
            return  # scheduled before a crash — the server was reset
        self._pump_scheduled = False
        if not self.server.busy:
            return
        t = net.clock.now_ms
        queued_before = {q[0] for q in self.server.queue}
        w0 = time.perf_counter()
        self.server.step()
        dt = (time.perf_counter() - w0) * 1e3
        end = t + dt
        self._busy_until = end
        for rid in queued_before - {q[0] for q in self.server.queue}:
            if rid in self._pending:
                self._pending[rid].admitted_ms = t
        for f in self.server.finished[self._seen_finished:]:
            p = self._pending.pop(f.request_id, None)
            if p is None:
                continue  # submitted via the blocking shim
            admitted = p.admitted_ms if p.admitted_ms is not None else t
            result = self._result_from(
                f, n_input=p.n_input,
                inference_ms=end - admitted,
                queue_ms=admitted - p.submitted_ms,
            )
            net.schedule(end, lambda r=result, cb=p.on_done: cb(r))
        self._seen_finished = len(self.server.finished)
        self._drain_consumed()
        if self.server.busy:
            self._pump_scheduled = True
            net.schedule(end, lambda e=self._pump_epoch: self._pump(net, e))

    def _result_from(
        self,
        f: FinishedRequest,
        n_input: int,
        inference_ms: float,
        queue_ms: float,
    ) -> ServiceResult:
        stop = self.server.stop_tokens
        text = self.tokenizer.decode([t for t in f.token_ids if t not in stop])
        return ServiceResult(
            text=text,
            token_ids=list(f.token_ids),
            inference_ms=inference_ms,
            cache_hit=f.cache_hit,
            reused_tokens=f.reused_tokens,
            prefill_tokens=n_input - f.reused_tokens,
            warm_start=f.warm_start,
            warm_source=f.warm_source,
            queue_ms=queue_ms,
            batch_size=f.batch_size,
            ttft_ms=f.ttft_ms,
            decode_p50_ms=f.decode_p50_ms,
            decode_p99_ms=f.decode_p99_ms,
        )
