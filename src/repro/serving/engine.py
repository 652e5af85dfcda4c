"""JAX inference engine — the real LLM Service behind the Context Manager.

Design mirrors the paper's modified llama.cpp server (§4.1): the completion
entry point takes a *pre-tokenized context* plus prompt token ids, so stored
session history is never re-tokenized. Greedy decoding, temperature 0,
max 128 new tokens — the paper's settings.

Two serving-path optimizations extend the paper's idea down the stack:

- **Session-level KV-cache reuse** — the decode caches of each served turn
  are kept in a capacity-bounded LRU :class:`SessionCachePool` keyed by the
  request's ``cache_key`` (the session's context key). A returning turn
  longest-common-prefix matches its ``context_ids + prompt_ids`` against the
  cached token prefix, reuses the matching KV state, and *incrementally*
  prefills only the new-token suffix in bounded chunks
  (:func:`repro.models.prefill_append`) — per-turn prefill cost is O(new
  tokens), not O(history). Any prefix mismatch (stale replica, edited
  history) falls back to a full prefill, so reuse is never required for
  correctness. The pool update happens after generation, off the measured
  hot path — mirroring the paper's asynchronous context update (§4.2.1).
- **Batched host sync in decode** — the decode loop keeps sampled tokens on
  device and only syncs to the host every ``sync_every`` steps (one transfer
  for the whole window), scanning the window for stop tokens host-side; at
  most ``sync_every - 1`` speculative decode steps are discarded after a
  stop. This removes the per-token blocking ``int(tok)`` round-trip.

Prompt lengths are bucketed (multiples of ``bucket``) so the jitted prefill
compiles once per bucket, not per request; padded positions are masked via
``true_len``. Append chunks are likewise bucketed and capped at
``append_chunk`` slots so jit compiles stay bounded. The decode loop reuses
one jitted step with donated caches.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.manager import ServiceCapabilities, ServiceResult
from ..store.network import Network
from ..models import (
    ModelConfig,
    decode_step,
    decode_step_paged,
    init_params,
    prefill,
    prefill_append,
    supports_append,
)
from ..models.cache import trim_cache_prefix
from ..tokenizer import EOS, IM_END, ByteLevelBPE, get_tokenizer
from .chunked_prefill import PagedPrefiller, prime_fill_pages
from .sampling import sample
from .session_cache import (
    CacheEntry,
    SessionCachePool,
    longest_common_prefix,
    warm_source_of,
)


def _bucket(n: int, step: int) -> int:
    return max(step, ((n + step - 1) // step) * step)


def truncate_for_cache(
    context_ids: List[int],
    prompt_ids: List[int],
    max_len: int,
    max_new_tokens: int,
) -> Tuple[List[int], int]:
    """Context-overflow guard shared by every real LLM Service: keep the
    prompt, drop the *oldest* context tokens, and reserve a modest
    generation budget. Returns ``(input_ids, max_new)`` sized to fit a
    ``max_len`` cache. One implementation so the single-stream and batched
    services can never disagree on what a long session's model sees."""
    context_ids, prompt_ids = list(context_ids), list(prompt_ids)
    reserve = max(1, min(max_new_tokens, 16))
    max_input = max(1, max_len - 1 - reserve)
    total = len(context_ids) + len(prompt_ids)
    if total > max_input:
        drop = total - max_input
        if drop < len(context_ids):
            context_ids = context_ids[drop:]
        else:
            context_ids = []
            prompt_ids = prompt_ids[-max_input:]
    ids = context_ids + prompt_ids
    budget = max(1, max_len - len(ids) - 1)
    return ids, min(max_new_tokens, budget)


def chunked_append(
    append_fn, params, caches, suffix_ids: List[int], p0: int,
    vocab_size: int, chunk: int, bucket: int,
):
    """Chunked incremental prefill of ``suffix_ids`` into existing B=1
    ``caches`` starting at absolute offset ``p0`` — the one loop shared by
    the single-stream engine, the warm-start prime path, and the batched
    scheduler's slot admission. Chunks are right-padded to ``bucket``
    multiples and capped at ``chunk`` slots so jit compiles stay bounded.
    ``append_fn(params, caches, tokens, pos, true_len)`` must wrap
    :func:`repro.models.prefill_append`."""
    logits, pos = None, jnp.array([p0], jnp.int32)
    i, m = 0, len(suffix_ids)
    while i < m:
        rem = m - i
        s = min(chunk, _bucket(rem, bucket))
        c = min(rem, s)
        toks = np.zeros((1, s), np.int32)
        toks[0, :c] = np.asarray(suffix_ids[i : i + c], np.int32) % vocab_size
        logits, caches, pos = append_fn(
            params, caches, jnp.asarray(toks), pos, jnp.array([c], jnp.int32)
        )
        i += c
    return logits, caches, pos


def prime_session_pool(
    pool: Optional[SessionCachePool],
    cache_key: str,
    token_ids: List[int],
    max_len: int,
    max_input: int,
    append_fn: Callable,   # (base_caches, suffix_ids, p0) -> (logits, caches, pos)
    prefill_fn: Callable,  # (ids) -> (logits, caches, pos)
    paged_fill: Optional[Callable] = None,  # (ids, entry|None, usable) -> pages|None
    source: str = "prime",  # provenance label for a FRESH install ("prime"
                            # = token recompute, "ship" = shipped KV pages)
) -> Tuple[bool, bool]:
    """Migration warm-start core shared by the single-stream engine and the
    batched scheduler (their ``prime`` methods differ only in the compute
    callables and the overflow bound ``max_input``). Returns ``(warm,
    stored)``: ``warm`` — the pool now holds KV for the full sequence;
    ``stored`` — prefill work actually ran (False for the covers-everything
    no-op).

    Guards, in order: nothing to do without a pool/tokens; a context longer
    than ``max_input`` gets truncated on the serving path and could never
    prefix-match, so priming it would be wasted work; a fresh prime into a
    full pool (entry-counted, and page-budgeted when an allocator is bound
    — checked *after* the covers-everything branch, which needs no pages)
    would be dropped by the low-priority put, so skip the prefill. A
    diverged entry is invalidated; an entry covering everything is a no-op;
    otherwise only the delta is chunk-prefilled. Extending an existing
    entry keeps its provenance and (via the low-priority put) its LRU
    position: a "serve" entry whose context replicated back is still the
    node's own hot session — relabeling or demoting it would miscount the
    next local hit as a migration warm start / make it the next eviction
    victim."""
    if pool is None or not token_ids:
        return False, False
    n = len(token_ids)
    if n > max_input:
        return False, False
    entry = pool.peek(cache_key)
    if entry is None and len(pool) >= pool.capacity:
        return False, False
    usable = 0
    if entry is not None:
        lcp = longest_common_prefix(entry.token_ids, token_ids)
        if lcp < entry.pos and lcp < n:
            pool.invalidate(cache_key)  # diverged: stale/edited history
        elif entry.pos >= n:
            return True, False          # already warm (covers everything)
        else:
            usable = lcp                # == entry.pos: extend the delta
    if (
        paged_fill is not None and pool.allocator is not None
        and (usable == 0 or entry.paged)
    ):
        # Paged prime: the KV is chunk-prefilled straight into fresh pages
        # (repro/serving/chunked_prefill.py) — no dense lane, no store
        # scatter. The callback owns sharing/feasibility (it never
        # reclaims); a dense matched entry (mixed-topology pool) falls
        # through to the dense route below instead.
        pages = paged_fill(token_ids, entry if usable > 0 else None, usable)
        if pages is None:
            return False, False
        source = entry.source if usable > 0 else source
        pool.put(
            cache_key,
            CacheEntry(token_ids=list(token_ids), pages=pages, source=source),
            low_priority=True,
        )
        pool.primes += 1
        return True, True
    # Cross-session shared prefix: another session's resident pages matching
    # this context shrink both the prefill (gather + delta instead of full)
    # and the page budget the final put will need (its store shares them).
    shared_pages: List[int] = []
    stok = 0
    if pool.allocator is not None:
        shared_pages = pool.allocator.match_prefix(token_ids, n)
        stok = len(shared_pages) * pool.allocator.page_size
    if pool.allocator is not None and (
        pool.allocator.n_free
        < pool.allocator.pages_for(n) - len(shared_pages)
    ):
        return False, False
    if usable > 0:
        base = (
            pool.materialize(entry, usable, max_len)
            if entry.paged else entry.caches
        )
        _, caches, _ = append_fn(base, token_ids[usable:], usable)
    elif stok > 0:
        base = pool.allocator.gather(shared_pages, stok, max_len)
        _, caches, _ = append_fn(base, token_ids[stok:], stok)
        pool.shared_hits += 1
        pool.shared_tokens += stok
    else:
        _, caches, _ = prefill_fn(token_ids)
    caches = trim_cache_prefix(caches, n)
    # The prime's compute must finish *here*, inside the off-hot-path window
    # (client think time): without the barrier, async-dispatched XLA work
    # would still be running when the next serving turn starts and contend
    # with its measured prefill/decode.
    jax.block_until_ready(caches)
    source = entry.source if usable > 0 else source
    pool.put(
        cache_key,
        CacheEntry(token_ids=list(token_ids), caches=caches, source=source),
        low_priority=True,
    )
    pool.primes += 1
    return True, True


@dataclass
class GenerateResult:
    """Outcome of one generation, with KV-reuse accounting."""

    token_ids: List[int]
    cache_hit: bool = False
    reused_tokens: int = 0       # prefix tokens served from the session cache
    prefill_tokens: int = 0      # tokens actually prefilled this turn
    inference_ms: float = 0.0    # hot path: prefill + decode (pool update excluded)
    cache_update_ms: float = 0.0  # session-pool update, off the hot path
    warm_start: bool = False     # hit entry was installed by prime() (migration)
    # provenance of the warm start: "tokens" (recompute prime), "pages"
    # (shipped KV pages installed digest-verified), or "none"
    warm_source: str = "none"
    ttft_ms: float = 0.0         # start -> first generated token determined
    decode_p50_ms: float = 0.0   # per-token decode latency percentiles
    decode_p99_ms: float = 0.0   # (amortized over each host-sync window)


@dataclass
class InferenceEngine:
    cfg: ModelConfig
    params: Dict
    max_len: int = 1024          # cache slots (context + generation budget)
    bucket: int = 64
    append_chunk: int = 256      # max incremental-prefill chunk (bucket multiple)
    sync_every: int = 8          # decode steps between host syncs / stop scans
    stop_tokens: Tuple[int, ...] = (EOS, IM_END)
    session_pool: Optional[SessionCachePool] = None

    _prefill_cache: Dict[int, object] = field(default_factory=dict, repr=False)
    _append_cache: Dict[int, object] = field(default_factory=dict, repr=False)
    _decode_fn: Optional[object] = field(default=None, repr=False)
    _paged_decode_cache: Dict[int, object] = field(default_factory=dict, repr=False)
    _prefiller: Optional[object] = field(default=None, repr=False)

    # keyed generations that had to leave the paged route (dense pool entry
    # from a mixed-topology pool, or page exhaustion at admission)
    paged_fallbacks: int = 0

    @classmethod
    def create(
        cls,
        cfg: ModelConfig,
        seed: int = 0,
        max_len: int = 1024,
        bucket: int = 64,
        session_cache_capacity: int = 4,
        page_size: int = 0,
        kv_pages: int = 0,
        share_prefixes: bool = True,
    ) -> "InferenceEngine":
        """With ``page_size``/``kv_pages`` > 0, the session pool stores its
        entries *paged* (docs/architecture.md, "Paged session KV"): each
        entry costs ceil(tokens/page_size) pages of the shared
        :class:`~repro.serving.paged_kv.PagedKVAllocator` instead of a full
        ``max_len``-width lane, and eviction is page-budgeted. Compute
        stays dense on this single-stream path — hits are gathered back to
        a dense view on demand."""
        params = init_params(jax.random.key(seed), cfg)
        pool = (
            SessionCachePool(capacity=session_cache_capacity)
            if session_cache_capacity > 0 and supports_append(cfg)
            else None
        )
        if pool is not None and page_size > 0 and kv_pages > 0:
            from .paged_kv import PagedKVAllocator

            assert max_len % page_size == 0, (max_len, page_size)
            pool.allocator = PagedKVAllocator(
                cfg, page_size=page_size, n_pages=kv_pages,
                share_prefixes=share_prefixes,
            )
            # pages are the memory bound now; lift the entry-count cap so
            # it can never evict before the page budget does (every entry
            # holds >= 1 page) — the many-tenant capacity win requires it
            pool.capacity = max(pool.capacity, kv_pages)
        return cls(
            cfg=cfg, params=params, max_len=max_len, bucket=bucket,
            session_pool=pool,
        )

    # -- jit plumbing -------------------------------------------------------
    def _prefill_fn(self, s: int):
        if s not in self._prefill_cache:
            cfg, max_len = self.cfg, self.max_len

            @jax.jit
            def fn(params, tokens, true_len):
                return prefill(params, cfg, tokens, max_len=max_len, true_len=true_len)

            self._prefill_cache[s] = fn
        return self._prefill_cache[s]

    def _append_fn(self, s: int):
        """Incremental prefill for a chunk of s slots (compiled per chunk
        bucket). Caches are NOT donated: the first chunk reads pool-owned
        arrays that must stay valid for other sessions / retries."""
        if s not in self._append_cache:
            cfg = self.cfg

            @jax.jit
            def fn(params, caches, tokens, p0, true_len):
                return prefill_append(params, cfg, caches, tokens, p0, true_len=true_len)

            self._append_cache[s] = fn
        return self._append_cache[s]

    def _decode(self):
        if self._decode_fn is None:
            cfg = self.cfg

            @partial(jax.jit, donate_argnums=(1,))
            def fn(params, caches, tokens, pos):
                return decode_step(params, cfg, caches, tokens, pos)

            self._decode_fn = fn
        return self._decode_fn

    def _paged_prefiller(self) -> PagedPrefiller:
        """Chunked paged prefill driver bound to the pool's allocator
        (lazy: only keyed paged generations and paged primes need it)."""
        if self._prefiller is None:
            self._prefiller = PagedPrefiller(
                self.cfg, self.params, self.session_pool.allocator
            )
        return self._prefiller

    def _paged_decode_fn(self, w: int):
        """B=1 paged decode, jitted once per power-of-two table width."""
        if w not in self._paged_decode_cache:
            cfg = self.cfg

            @partial(jax.jit, donate_argnums=(1, 3))
            def fn(params, pools, table, kv_pos, tokens, pos):
                return decode_step_paged(
                    params, cfg, pools, table, kv_pos, tokens, pos
                )

            self._paged_decode_cache[w] = fn
        return self._paged_decode_cache[w]

    # -- prefill paths ------------------------------------------------------
    def _full_prefill(self, input_ids: List[int]):
        n = len(input_ids)
        s = min(_bucket(n, self.bucket), self.max_len)
        toks = np.zeros((1, s), np.int32)
        toks[0, :n] = np.asarray(input_ids, np.int32) % self.cfg.vocab_size
        true_len = jnp.array([n], jnp.int32)
        return self._prefill_fn(s)(self.params, jnp.asarray(toks), true_len)

    def _append_prefill(self, caches, suffix_ids: List[int], p0: int):
        """Chunked incremental prefill of `suffix_ids` starting at p0."""
        return chunked_append(
            lambda params, c, toks, pos, tl: self._append_fn(toks.shape[1])(
                params, c, toks, pos, tl
            ),
            self.params, caches, suffix_ids, p0,
            self.cfg.vocab_size, self.append_chunk, self.bucket,
        )

    def _trim_for_pool(self, caches, n_valid: int):
        """Mask kv_pos beyond the kept prefix (decode may have run past a
        stop token between host syncs)."""
        return trim_cache_prefix(caches, n_valid)

    # -- migration warm-start ----------------------------------------------
    def prime(self, cache_key: str, token_ids: List[int]) -> bool:
        """Pre-warm the session pool for ``cache_key`` with the KV state of
        ``token_ids`` — the migration warm-start path (docs/architecture.md).

        Called off the serving hot path when a replicated tokenized context
        lands on this node's KV replica: the roaming client's first turn
        here then prefix-matches the primed entry and prefills only its new
        tokens instead of the whole stored history. Guard/extension/
        provenance semantics live in :func:`prime_session_pool` (shared
        with the batched scheduler); the overflow bound matches
        JaxLLMService.completion's truncation guard (max generation
        reserve 16). Returns True when the pool now holds KV for the full
        sequence."""
        pool = self.session_pool
        paged_fill = None
        if pool is not None and pool.allocator is not None:
            paged_fill = lambda ids, entry, usable: prime_fill_pages(  # noqa: E731
                pool, self._paged_prefiller(), ids, entry, usable
            )
        warm, _ = prime_session_pool(
            pool, cache_key, list(token_ids),
            self.max_len, self.max_len - 1 - 16,
            self._append_prefill, self._full_prefill,
            paged_fill=paged_fill,
        )
        return warm

    def install_shipped_pages(
        self,
        cache_key: str,
        token_ids: List[int],
        payloads: List[bytes],
        have_pages: int,
    ) -> bool:
        """Install digest-verified shipped KV pages (KV-page migration,
        docs/architecture.md "KV page shipping"): ``payloads`` hold the
        serialized full pages ``[have_pages, have_pages + len(payloads))``
        of ``token_ids``'s KV, exported by the origin engine's allocator.
        They are imported straight into fresh pages; only the partial tail
        page (and any coverage gap) is prefilled. Entry provenance is
        ``"ship"`` so the next turn's warm start reports ``"pages"``.
        Returns False when this engine can't take pages (dense pool, page
        exhaustion) — the shipper then falls back to token recompute."""
        pool = self.session_pool
        if pool is None or pool.allocator is None:
            return False
        paged_fill = lambda ids, entry, usable: prime_fill_pages(  # noqa: E731
            pool, self._paged_prefiller(), ids, entry, usable,
            shipped=payloads, ship_have=have_pages,
        )
        warm, _ = prime_session_pool(
            pool, cache_key, list(token_ids),
            self.max_len, self.max_len - 1 - 16,
            self._append_prefill, self._full_prefill,
            paged_fill=paged_fill, source="ship",
        )
        return warm

    # -- public API ------------------------------------------------------------
    def generate_ex(
        self,
        input_ids: List[int],
        max_new_tokens: int = 128,
        temperature: float = 0.0,
        cache_key: Optional[str] = None,
    ) -> GenerateResult:
        """Single-sequence generation (the Context Manager path), with
        optional session-level KV-cache reuse when ``cache_key`` is given.

        With a page-pool-backed session pool, keyed generations run fully
        paged (:meth:`_generate_paged`): chunked prefill straight into
        pages, paged decode against the page table — no ``max_len``-width
        dense cache is ever allocated for the sequence. Keyless requests
        stay on the dense-transient route (their cache dies with the call;
        pages would only add table indirection), as do the rare paged
        misfits (dense entry from a mixed-topology pool, page exhaustion) —
        counted in ``paged_fallbacks``."""
        input_ids = list(input_ids)
        n = len(input_ids)
        assert n + max_new_tokens <= self.max_len, (n, max_new_tokens, self.max_len)
        pool = self.session_pool if cache_key is not None else None
        if pool is not None and pool.allocator is not None:
            res = self._generate_paged(
                input_ids, max_new_tokens, temperature, cache_key
            )
            if res is not None:
                return res
            self.paged_fallbacks += 1
        return self._generate_dense(
            input_ids, max_new_tokens, temperature, cache_key
        )

    def _generate_paged(
        self,
        input_ids: List[int],
        max_new_tokens: int,
        temperature: float,
        cache_key: str,
    ) -> Optional[GenerateResult]:
        """Keyed generation straight on the page pool: admission plans
        pages exactly like the batched scheduler (entry share with
        tail-page copy, cross-session content-index share, fresh pages out
        to ``n + 1``), the prompt chunk-prefills directly into them, and
        decode runs :func:`repro.models.decode_step_paged` at a
        power-of-two table width with grow-on-demand per host-sync window.
        Returns None (nothing allocated, nothing counted) when the pool
        can't serve this request — the caller falls back to the dense
        route."""
        pool = self.session_pool
        alloc = pool.allocator
        ps = alloc.page_size
        n = len(input_ids)
        t0 = time.perf_counter()

        entry, usable = pool.match(cache_key, input_ids)
        if entry is not None and not entry.paged:
            return None  # mixed-topology pool: a dense entry matched
        usable = min(usable, n - 1)
        cross = alloc.match_prefix(input_ids, n - 1)
        kind, cover = ("entry", usable) if usable > 0 else ("none", 0)
        if len(cross) * ps > cover:
            kind, cover = "cross", len(cross) * ps
        warm_source = warm_source_of(entry.source) if kind == "entry" else "none"
        warm = warm_source != "none"
        skip = cover // ps
        tail_src: Optional[int] = None
        if kind == "entry" and cover % ps:
            tail_src = entry.pages[skip]
        shared = (
            list(entry.pages[:skip]) if kind == "entry"
            else list(cross[:skip]) if kind == "cross"
            else []
        )
        if shared:
            # incref before reclaim: eviction must not free the donor pages
            alloc.incref(shared)
        fresh = self._alloc_paged(
            alloc.pages_for(n + 1) - skip, exclude=cache_key
        )
        if fresh is None:
            if shared:
                alloc.decref(shared)
            return None
        pages = shared + fresh
        if tail_src is not None:
            alloc.copy_page(tail_src, fresh[0])
        if kind == "cross":
            pool.shared_hits += 1
            pool.shared_tokens += cover

        logits = self._paged_prefiller().prefill_ids(
            pages, input_ids, cover, n_skip=skip, chunk=self.append_chunk
        )
        tok = sample(logits[None, :], temperature=temperature)
        jax.block_until_ready(tok)
        ttft_ms = (time.perf_counter() - t0) * 1e3

        # decode with batched host sync, same contract as the dense route;
        # the table grows page-by-page ahead of each window's writes, and a
        # window the pool can't back is truncated (generation stops early
        # with the tokens it has — never a silent dropped write)
        iota = jnp.arange(self.max_len, dtype=jnp.int32)
        kv_full = jnp.where(iota < n, iota, -1)[None, :]
        out: List[int] = []
        gaps: List[float] = []
        pos_abs = n
        remaining = max_new_tokens
        stopped = early = False
        while remaining > 0 and not stopped and not early:
            wsteps = min(self.sync_every, remaining)
            need = alloc.pages_for(pos_abs + wsteps)
            if need > len(pages):
                more = self._alloc_paged(need - len(pages), exclude=cache_key)
                if more is None:
                    early = True
                    wsteps = min(wsteps, len(pages) * ps - pos_abs)
                    if wsteps <= 0:
                        break
                else:
                    pages = pages + more
            w = 1
            while w < len(pages):
                w *= 2
            w = min(w, self.max_len // ps)
            wp = w * ps
            table = jnp.asarray(alloc.table_for(pages, wp))[None, :]
            fn = self._paged_decode_fn(w)
            t_w = time.perf_counter()
            window = []
            for _ in range(wsteps):
                window.append(tok)
                logits, pools, kvp = fn(
                    self.params, alloc.pools, table, kv_full[:, :wp],
                    tok[:, None], jnp.array([pos_abs], jnp.int32),
                )
                alloc.pools = pools
                kv_full = kv_full.at[:, :wp].set(kvp)
                pos_abs += 1
                tok = sample(logits[:, 0], temperature=temperature)
            remaining -= wsteps
            host = np.asarray(jnp.stack(window)[:, 0])   # single device sync
            gap = (time.perf_counter() - t_w) * 1e3 / wsteps
            for t in host:
                out.append(int(t))
                gaps.append(gap)
                if int(t) in self.stop_tokens:
                    stopped = True
                    break
        inference_ms = (time.perf_counter() - t0) * 1e3

        # write-back MOVES the pages into the pool entry (zero-copy): every
        # emitted token's KV is in its page; pages past the kept prefix are
        # freed. Stale bytes inside the tail page beyond the prefix are
        # never causal for a future reuse (coverage-capped + masked).
        t1 = time.perf_counter()
        prefix = input_ids + out
        keep = alloc.pages_for(len(prefix))
        if keep < len(pages):
            alloc.decref(pages[keep:])
        pool.put(
            cache_key,
            CacheEntry(token_ids=prefix, pages=pages[:keep], source="serve"),
        )
        cache_update_ms = (time.perf_counter() - t1) * 1e3

        return GenerateResult(
            token_ids=out,
            cache_hit=cover > 0,
            reused_tokens=cover,
            prefill_tokens=n - cover,
            inference_ms=inference_ms,
            cache_update_ms=cache_update_ms,
            warm_start=warm,
            warm_source=warm_source,
            ttft_ms=ttft_ms,
            decode_p50_ms=float(np.percentile(gaps, 50)) if gaps else 0.0,
            decode_p99_ms=float(np.percentile(gaps, 99)) if gaps else 0.0,
        )

    def _alloc_paged(
        self, m: int, exclude: Optional[str] = None
    ) -> Optional[List[int]]:
        """Allocate ``m`` pages, reclaiming page-budgeted LRU session
        entries (never ``exclude`` — the entry being reused) on pressure."""
        alloc = self.session_pool.allocator
        pages = alloc.alloc(m)
        if pages is None:
            self.session_pool.reclaim(m, exclude=exclude)
            pages = alloc.alloc(m)
        return pages

    def _generate_dense(
        self,
        input_ids: List[int],
        max_new_tokens: int,
        temperature: float,
        cache_key: Optional[str],
    ) -> GenerateResult:
        """The dense-transient route: prefill into a ``max_len``-width B=1
        cache, decode against it, store/trim into the pool afterwards (the
        pool's put scatters it into pages when an allocator is bound)."""
        n = len(input_ids)
        pool = self.session_pool if cache_key is not None else None
        t0 = time.perf_counter()

        entry, usable = (None, 0)
        if pool is not None:
            entry, usable = pool.match(cache_key, input_ids)
        shared_pages: List[int] = []
        stok = 0
        if pool is not None and pool.allocator is not None:
            # cross-session shared prefix: resident pages of ANY session
            # whose content matches this context (docs/architecture.md,
            # "Cross-session shared-prefix paging")
            shared_pages, stok = pool.match_shared_prefix(input_ids)
        if stok > usable:
            # another session's pages cover more than this key's own entry:
            # gather them to a dense base (read-only copy — the donor pages
            # are never written) and prefill only the remainder
            base = pool.allocator.gather(shared_pages, stok, self.max_len)
            logits, caches, pos = self._append_prefill(
                base, input_ids[stok:], stok
            )
            hit, reused, warm_source = True, stok, "none"
            pool.shared_hits += 1
            pool.shared_tokens += stok
        elif entry is not None and usable > 0:
            if entry.paged:
                # paged entry: gather the pages into a fresh dense view with
                # kv_pos already masked to `usable` (covers the retry/resend
                # trim too)
                base = pool.materialize(entry, usable, self.max_len)
            else:
                base = entry.caches
                if usable < entry.pos:
                    # retry/resend: incoming ids stop inside the cached
                    # prefix — slots past `usable` hold tokens not in this
                    # request
                    base = self._trim_for_pool(base, usable)
            logits, caches, pos = self._append_prefill(
                base, input_ids[usable:], usable
            )
            hit, reused = True, usable
            warm_source = warm_source_of(entry.source)
        else:
            logits, caches, pos = self._full_prefill(input_ids)
            hit, reused, warm_source = False, 0, "none"
        warm = warm_source != "none"
        prefilled = n - reused

        # Decode with batched host sync: tokens stay on device; every
        # `sync_every` steps one transfer pulls the window and scans it for
        # stop tokens. Steps decoded past a stop are discarded.
        out: List[int] = []
        gaps: List[float] = []
        tok = sample(logits, temperature=temperature)
        jax.block_until_ready(tok)
        ttft_ms = (time.perf_counter() - t0) * 1e3
        decode = self._decode()
        remaining = max_new_tokens
        stopped = False
        while remaining > 0 and not stopped:
            w = min(self.sync_every, remaining)
            t_w = time.perf_counter()
            window = []
            for _ in range(w):
                window.append(tok)
                logits, caches = decode(self.params, caches, tok[:, None], pos)
                pos = pos + 1
                tok = sample(logits[:, 0], temperature=temperature)
            remaining -= w
            host = np.asarray(jnp.stack(window)[:, 0])   # single device sync
            gap = (time.perf_counter() - t_w) * 1e3 / w
            for t in host:
                out.append(int(t))
                gaps.append(gap)
                if int(t) in self.stop_tokens:
                    stopped = True
                    break
        inference_ms = (time.perf_counter() - t0) * 1e3

        # Session-pool update — off the hot path, mirroring the paper's
        # asynchronous context update (§4.2.1). Every emitted token was
        # decoded (its KV is in the cache), so the stored prefix is
        # input_ids + out; kv_pos past that is trimmed.
        cache_update_ms = 0.0
        if pool is not None:
            t1 = time.perf_counter()
            prefix = input_ids + out
            pool.put(
                cache_key,
                CacheEntry(
                    token_ids=prefix,
                    caches=self._trim_for_pool(caches, len(prefix)),
                    source="serve",
                ),
            )
            cache_update_ms = (time.perf_counter() - t1) * 1e3

        return GenerateResult(
            token_ids=out,
            cache_hit=hit,
            reused_tokens=reused,
            prefill_tokens=prefilled,
            inference_ms=inference_ms,
            cache_update_ms=cache_update_ms,
            warm_start=warm,
            warm_source=warm_source,
            ttft_ms=ttft_ms,
            decode_p50_ms=float(np.percentile(gaps, 50)) if gaps else 0.0,
            decode_p99_ms=float(np.percentile(gaps, 99)) if gaps else 0.0,
        )

    def generate(
        self,
        input_ids: List[int],
        max_new_tokens: int = 128,
        temperature: float = 0.0,
        cache_key: Optional[str] = None,
    ) -> List[int]:
        return self.generate_ex(
            input_ids, max_new_tokens=max_new_tokens,
            temperature=temperature, cache_key=cache_key,
        ).token_ids

    def warmup(self, lengths: Tuple[int, ...] = (64,)) -> None:
        for s in lengths:
            ids = list(range(min(s, 16)))
            self.generate(ids, max_new_tokens=2)


@dataclass
class JaxLLMService:
    """LLM Service (paper §3.2) backed by the JAX engine. Accepts the
    pre-tokenized context parameter — the llama.cpp /completion extension —
    plus an optional ``cache_key`` (the session's context key) enabling
    session-level KV-cache reuse: hit turns prefill only the new-token
    suffix. Context that would overflow the engine's cache is truncated
    from the *oldest* tokens (the prompt is always kept)."""

    model: str
    engine: InferenceEngine
    tokenizer: ByteLevelBPE
    kv_reuse: bool = True
    # Measured prefill cost constant for the KV-ship cost model (ms per
    # token on THIS node's accelerator; heterogeneous fleets give weak
    # nodes a larger value). 0 disables shipping for this node.
    ship_prefill_ms_per_token: float = 0.0
    # Single-stream queue model for the submit/await path: the sim time the
    # engine frees up, valid for `_clock_owner`'s clock (a service reused
    # across clusters/networks restarts at idle).
    _busy_until: float = field(default=0.0, repr=False, compare=False)
    _clock_owner: Optional[Network] = field(
        default=None, repr=False, compare=False
    )

    @classmethod
    def create(
        cls,
        model: str,
        cfg: ModelConfig,
        *,
        seed: int = 0,
        tokenizer_seed: int = 0,
        max_len: int = 2048,
        kv_reuse: bool = True,
        session_cache_capacity: int = 4,
        page_size: int = 0,
        kv_pages: int = 0,
        share_prefixes: bool = True,
    ) -> "JaxLLMService":
        engine = InferenceEngine.create(
            cfg, seed=seed, max_len=max_len,
            session_cache_capacity=session_cache_capacity if kv_reuse else 0,
            page_size=page_size, kv_pages=kv_pages,
            share_prefixes=share_prefixes,
        )
        tok = get_tokenizer(cfg.vocab_size, seed=tokenizer_seed, name=model)
        return cls(model=model, engine=engine, tokenizer=tok, kv_reuse=kv_reuse)

    def capabilities(self) -> ServiceCapabilities:
        return ServiceCapabilities(
            prime=self.kv_reuse,
            kv_reuse=self.kv_reuse,
            batched=False,
            n_slots=1,
        )

    def prime(self, cache_key: str, token_ids: List[int]) -> bool:
        """Migration warm-start entry point (called by the EdgeNode
        replication-arrival hook, off the serving hot path): prefill the
        replicated tokenized context into the engine's session pool so the
        roaming client's next turn here is suffix-only."""
        if not self.kv_reuse:
            return False
        return self.engine.prime(cache_key, list(token_ids))

    def resident_keys(self) -> Dict[str, int]:
        """Cache key -> resident KV token count (fleet telemetry surface —
        published on the node's heartbeat for residency-aware routing)."""
        pool = self.engine.session_pool
        return pool.resident_keys() if pool is not None else {}

    # -- KV-page shipping hooks (repro.store.kv_ship) -----------------------
    def kv_ship_profile(self):
        """This node's shipping constants, or None when it can't ship
        (reuse off, dense pool, or no measured prefill constant)."""
        pool = self.engine.session_pool
        if (
            not self.kv_reuse
            or pool is None
            or pool.allocator is None
            or self.ship_prefill_ms_per_token <= 0
        ):
            return None
        from ..store.kv_ship import NodeShipProfile

        alloc = pool.allocator
        return NodeShipProfile(
            page_size=alloc.page_size,
            page_wire_bytes=alloc.page_bytes,
            prefill_ms_per_token=self.ship_prefill_ms_per_token,
        )

    def export_kv_pages(self, cache_key: str):
        """Serialize the resident *full* pages of ``cache_key``'s session
        entry (native-dtype page bytes — the round trip is bit-exact).
        None when the key isn't resident as pages."""
        pool = self.engine.session_pool
        entry = pool.peek(cache_key) if pool is not None else None
        if entry is None or not entry.paged:
            return None
        alloc = pool.allocator
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
        """Install digest-verified shipped pages into the session pool
        (the KVShipper's installer hook)."""
        if not self.kv_reuse:
            return False
        return self.engine.install_shipped_pages(
            cache_key, list(token_ids), payloads, have_pages
        )

    def resident_ship_pages(self, cache_key: str, token_ids: List[int]) -> int:
        """Full prefix pages of ``token_ids`` already resident for
        ``cache_key`` — shipped deltas skip them."""
        pool = self.engine.session_pool
        entry = pool.peek(cache_key) if pool is not None else None
        if entry is None or not entry.paged or pool.allocator is None:
            return 0
        lcp = longest_common_prefix(
            entry.token_ids[: entry.pos], list(token_ids)
        )
        return lcp // pool.allocator.page_size

    def crash(self) -> None:
        """Process crash: the session KV pool is device memory — gone. The
        engine weights/jit caches are treated as re-warmed on restart (we
        model state loss, not reload time)."""
        if self.engine.session_pool is not None:
            self.engine.session_pool.clear()
        self._busy_until = 0.0
        self._clock_owner = None

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
        """Async serving entrypoint (single stream): the real JAX work runs
        eagerly here — standard discrete-event practice — and its measured
        ``inference_ms`` is laid onto the sim clock behind whatever is
        already queued on this engine. Concurrent tenants therefore pay a
        genuine head-of-line ``queue_ms`` while a batched service
        (:class:`~repro.serving.scheduler.BatchedLLMService`) overlaps
        them in one decode batch."""
        if self._clock_owner is not net:
            self._clock_owner = net
            self._busy_until = 0.0
        result = self.completion(
            context_ids, prompt_ids, max_new_tokens, cache_key=cache_key
        )
        now = net.clock.now_ms
        start = max(now, self._busy_until)
        result.queue_ms = start - now
        self._busy_until = start + result.inference_ms
        net.schedule(self._busy_until, lambda: on_done(result))

    def completion(
        self,
        context_ids: List[int],
        prompt_ids: List[int],
        max_new_tokens: int,
        cache_key: Optional[str] = None,
    ) -> ServiceResult:
        ids, max_new = truncate_for_cache(
            context_ids, prompt_ids, self.engine.max_len, max_new_tokens
        )
        res = self.engine.generate_ex(
            ids,
            max_new_tokens=max_new,
            cache_key=cache_key if self.kv_reuse else None,
        )
        gen = res.token_ids
        text = self.tokenizer.decode([t for t in gen if t not in self.engine.stop_tokens])
        return ServiceResult(
            text=text,
            token_ids=gen,
            inference_ms=res.inference_ms,
            cache_hit=res.cache_hit,
            reused_tokens=res.reused_tokens,
            prefill_tokens=res.prefill_tokens,
            cache_update_ms=res.cache_update_ms,
            warm_start=res.warm_start,
            warm_source=res.warm_source,
            ttft_ms=res.ttft_ms,
            decode_p50_ms=res.decode_p50_ms,
            decode_p99_ms=res.decode_p99_ms,
        )
