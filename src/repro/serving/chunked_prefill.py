"""Chunked paged prefill driver — prompt tokens land straight in KV pages.

The paged serving paths (batched scheduler admission, single-stream engine,
migration warm-start prime) all prefill through this one driver: the prompt
is split into page-aligned chunks and each chunk is computed by
:func:`repro.models.prefill_chunk_paged`, which scatters its K/V into the
allocator's page pool *through the page table* before attending. No dense
``max_len``-width intermediate cache ever exists and no write-through copy
runs afterwards — pages ARE the prefill destination.

Compile bounding: chunk token widths are bucketed to power-of-two multiples
of the page size and the attention table width to a power-of-two page
count, so the jitted chunk function compiles at most
O(log(max_chunk/page_size) * log(max_pages)) shapes — once per model
configuration and device, shared by every prefiller. ``n_skip`` (leading
read-only shared-prefix pages) is a traced scalar, not a compile key.

The per-call unit is one B=1 chunk (``run_chunk``): the batched scheduler
interleaves these with its batched decode under a per-step token budget
(Sarathi-style unified steps — see ``serving/scheduler.py``), while the
engine and prime paths drain a whole suffix in one loop (``prefill_ids``).
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core import tracing
from ..models import ModelConfig
from ..models.prefill import prefill_chunk_paged
from .paged_kv import PagedKVAllocator


@lru_cache(maxsize=None)
def _chunk_fn(cfg: ModelConfig):
    """The jitted chunk step of one model configuration."""

    @partial(jax.jit, donate_argnums=(1,))
    def fn(params, pools, table, tokens, p0, true_len, n_skip):
        return prefill_chunk_paged(
            params, cfg, pools, table, tokens, p0, true_len, n_skip=n_skip,
            stats=True,
        )

    return fn


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


class PagedPrefiller:
    """Runs chunked paged prefill against one allocator's page pool.

    Stateless between calls except for the jit cache and, while the tracer
    is on, the chunks' MoE counts summed on the device (:meth:`take_counts`);
    the caller owns page allocation, sharing/refcounts, and chunk
    scheduling — this class only moves tokens into pages and returns
    last-valid-position logits."""

    def __init__(
        self, cfg: ModelConfig, params, allocator: PagedKVAllocator
    ) -> None:
        self.cfg = cfg
        self.params = params
        self.alloc = allocator
        self._fn = _chunk_fn(cfg)
        self._counts = None

    def take_counts(self):
        """The MoE counts of the chunks run since the last call while the
        tracer was on, as a device array (None without any), and reset."""
        counts, self._counts = self._counts, None
        return counts

    def run_chunk(
        self,
        pages: Sequence[int],
        chunk_ids: Sequence[int],
        p0: int,
        n_skip: int = 0,
    ) -> jnp.ndarray:
        """Prefill one chunk of ``chunk_ids`` at absolute offset ``p0``
        straight into ``pages`` (the lane's page list; slots beyond the
        chunk's reach are never touched). Returns the logits at the chunk's
        last token, shape (V,). ``n_skip`` leading pages are read-only
        shared-prefix pages — attention reads them, writes to them are
        dropped."""
        alloc = self.alloc
        ps = alloc.page_size
        c = len(chunk_ids)
        assert c > 0
        # chunk bucket: pow2 multiple of the page size; table width: pow2
        # page count covering the causal prefix [0, p0 + c)
        s = ps * _pow2(-(-c // ps))
        mp = _pow2(alloc.pages_for(p0 + c))
        assert len(pages) >= alloc.pages_for(p0 + c), (len(pages), p0, c)
        # table beyond the lane's pages pads with the scratch page — never
        # read (the kernel's bound and the reference mask both stop at
        # p0 + true_len) and never written (writes land below p0 + c)
        table = alloc.table_for(list(pages)[:mp], mp * ps)
        toks = np.zeros((1, s), np.int32)
        toks[0, :c] = np.asarray(chunk_ids, np.int32) % self.cfg.vocab_size
        # host-built inputs go straight to the pool's device
        host = (table[None, :], toks, np.array([p0], np.int32),
                np.array([c], np.int32), np.int32(n_skip))
        logits, pools, counts = self._fn(
            self.params, alloc.pools, *jax.device_put(host, alloc.device)
        )
        alloc.pools = pools
        if tracing.enabled() and self.cfg.n_experts:
            self._counts = counts if self._counts is None else self._counts + counts
        return logits[0]

    def prefill_ids(
        self,
        pages: Sequence[int],
        token_ids: Sequence[int],
        start: int,
        n_skip: int = 0,
        chunk: int = 256,
    ) -> jnp.ndarray:
        """Drain the whole suffix ``token_ids[start:]`` into ``pages`` in
        ``chunk``-capped steps (the single-stream and prime paths — no
        decode to interleave with). Returns the final logits (V,)."""
        token_ids = list(token_ids)
        n = len(token_ids)
        logits: Optional[jnp.ndarray] = None
        i = start
        while i < n:
            c = min(chunk, n - i)
            logits = self.run_chunk(
                pages, token_ids[i : i + c], i, n_skip=n_skip
            )
            i += c
        assert logits is not None, (start, n)
        return logits


def prime_fill_pages(
    pool,
    prefiller: PagedPrefiller,
    token_ids: Sequence[int],
    entry,
    usable: int,
    shipped: Optional[Sequence[bytes]] = None,
    ship_have: int = 0,
) -> Optional[List[int]]:
    """:func:`_fill_pages`, traced as ``serving.latent_page_install``
    (``tracing.LATENT_PAGE_INSTALL``) where the pages are latent (MLA):
    the install of shipped pages and the prime of recomputed ones."""
    if not prefiller.cfg.is_mla:
        return _fill_pages(pool, prefiller, token_ids, entry, usable,
                           shipped, ship_have)
    with tracing.span(tracing.LATENT_PAGE_INSTALL) as sp:
        sp.set("shipped", len(shipped) if shipped is not None else 0)
        return _fill_pages(pool, prefiller, token_ids, entry, usable,
                           shipped, ship_have)


def _fill_pages(
    pool,
    prefiller: PagedPrefiller,
    token_ids: Sequence[int],
    entry,
    usable: int,
    shipped: Optional[Sequence[bytes]] = None,
    ship_have: int = 0,
) -> Optional[List[int]]:
    """Chunk-prefill ``token_ids`` straight into pages for a session-pool
    entry — the paged warm-start prime path, shared by the batched
    scheduler and the single-stream engine (their ``prime_session_pool``
    callbacks). Off the serving hot path, no decode to interleave with, so
    the whole suffix drains in one loop.

    Shares the matched ``entry``'s full pages (tail page device-copied when
    its coverage ends mid-page) or a cross-session content-index run; no
    ``n - 1`` coverage cap like admission — prime needs no logits, so a
    fully-covering share is a pure-incref prime. Returns the page list
    (refs owned by the caller's entry-to-be) or None when the pool can't
    cover the context: prime is best-effort and never reclaims other
    sessions' entries.

    With ``shipped``, this is the KV-page *install* path (KV-page
    migration): ``shipped[i]`` holds the serialized bytes of full page
    ``ship_have + i`` of ``token_ids``'s KV, already digest-verified by the
    shipper. Those pages are imported directly — no attention compute —
    and only the uncovered gap before them (shipper coverage can lag the
    pool) plus the partial tail page is chunk-prefilled."""
    alloc = prefiller.alloc
    ps = alloc.page_size
    token_ids = list(token_ids)
    n = len(token_ids)
    tail_src: Optional[int] = None
    if entry is not None and usable > 0:
        cover = usable
        shared = list(entry.pages[: cover // ps])
        if cover % ps:
            tail_src = entry.pages[cover // ps]
    else:
        shared = list(alloc.match_prefix(token_ids, n))
        cover = len(shared) * ps
    skip = len(shared)
    fresh_needed = alloc.pages_for(n) - skip
    if fresh_needed > alloc.n_free:
        return None
    if shared:
        # incref before alloc: allocation never evicts here (prime does not
        # reclaim), but keep the same discipline as admission
        alloc.incref(shared)
    fresh = alloc.alloc(fresh_needed)
    if fresh is None:
        if shared:
            alloc.decref(shared)
        return None
    pages = shared + fresh
    if tail_src is not None:
        alloc.copy_page(tail_src, fresh[0])
    if shipped is not None:
        # install: import the verified page bytes into the fresh pages,
        # compute only the gap below them and the tail beyond them. Shared
        # (refcounted) pages are never import targets: the import range
        # starts at max(skip, ship_have) and fresh pages begin at `skip`.
        want = min(n // ps, ship_have + len(shipped))
        gs = min(max(skip, ship_have), want)
        for i in range(gs, want):
            alloc.import_page_bytes(pages[i], shipped[i - ship_have])
        if cover < gs * ps:
            prefiller.prefill_ids(
                pages, token_ids[: gs * ps], cover, n_skip=skip
            )
        t0 = max(cover, want * ps)
        if t0 < n:
            prefiller.prefill_ids(pages, token_ids, t0, n_skip=skip)
    elif cover < n:
        prefiller.prefill_ids(pages, token_ids, cover, n_skip=skip)
    # the prime's compute must finish inside the off-hot-path window
    # (client think time), not contend with the next serving turn
    jax.block_until_ready(alloc.pools)
    if pool is not None and shared and entry is None:
        pool.shared_hits += 1
        pool.shared_tokens += cover
    return pages
