"""Block-granular paged KV allocator for the serving layer.

Full-width decode caches allocate ``max_len`` slots per batched-server lane
and per :class:`~repro.serving.session_cache.SessionCachePool` entry, so a
node's resident KV grows with *worst-case* context length times tenant
count — the memory wall on resource-limited edge nodes. This module replaces
that with the vLLM-style logical/physical split: one shared physical pool of
fixed-size KV pages per node service, and per-sequence *page tables* (lists
of physical page ids) sized to each sequence's actual token count.

Layout invariant: a sequence's pages, concatenated in table order,
reproduce the linear ``slot == absolute position`` layout of the full cache
exactly. Compute paths therefore stay position-masked and unchanged —
decode scatters and attends through the table
(:func:`repro.models.transformer.decode_step_paged`, gather view via
:func:`repro.models.cache.gather_pages`), and prefill lands *directly in
pages*, chunk by chunk (:func:`repro.models.prefill.prefill_chunk_paged`;
no dense ``max_len``-width intermediate exists on the paged paths) — so
the paged path is greedy-equivalent to the full-width path while resident
KV is ``used_pages * page_bytes``, not ``n_lanes * max_len``, during
prefill as well as between steps.

Ownership is reference-counted per page. Prefix reuse increfs the shared
full pages of a pool entry instead of copying the lane (a partially-filled
tail page is swapped for a fresh exclusively-held copy seeded by
:meth:`PagedKVAllocator.copy_page`, so an active lane's tail is always
private), and finished-slot write-back *moves* the slot's pages into the
pool entry — zero-copy in both directions. Page id 0 is reserved as a
scratch page: table padding and inactive batch lanes point at it, and
anything written there is garbage by design, masked via kv_pos.

Cross-session sharing (:class:`PrefixPageIndex`): beyond the session-key
boundary, every *full* page at rest is indexed by a chained content hash of
the token prefix it holds, so an admission for ANY session can discover and
share the resident pages of any other session's identical prefix — one
system prompt, a million tenants, one physical copy. The index holds no
references: a page's mapping is dropped the moment its refcount reaches
zero (``decref``), so the index can never name a released page. Sharing is
copy-on-write by construction — shared pages are never written (the paged
prefill scatter drops every write below ``n_skip`` pages; divergence or a
partial tail always lands in a fresh exclusively-held page), so a sharer
can never observe another tenant's subsequent writes.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..models import ModelConfig, layer_groups, supports_append
from ..models.cache import init_paged_pool
# Canonical digest lives with the KV-ship wire protocol (jax-free store
# layer) and is re-exported here for the original PR-7 callers.
from ..store.kv_ship import page_digests  # noqa: F401  (re-export)

# Physical page 0 is never allocated: page-table padding points here and
# inactive decode lanes write here. Its contents are garbage by design.
SCRATCH_PAGE = 0


class PrefixPageIndex:
    """Content-hash index over resident full pages: chained prefix digest
    -> physical page id. Weak by design — registering takes no page
    reference; the allocator drops a page's mapping when its refcount hits
    zero, so a lookup can never return a released (or recycled) page. One
    digest maps to at most one page and one page to at most one digest
    (first writer wins: duplicate content admitted before sharing kicked in
    simply stays unshared until its mapping's page is released)."""

    def __init__(self) -> None:
        self._by_digest: Dict[bytes, int] = {}
        self._by_page: Dict[int, bytes] = {}

    def __len__(self) -> int:
        return len(self._by_digest)

    def register(self, digest: bytes, page: int) -> None:
        if digest in self._by_digest or page in self._by_page:
            return
        self._by_digest[digest] = page
        self._by_page[page] = digest

    def lookup_run(self, digests: Sequence[bytes]) -> List[int]:
        """Longest indexed run of consecutive prefix digests, as physical
        page ids (no references taken — the caller must incref before any
        operation that could release them)."""
        pages: List[int] = []
        for d in digests:
            p = self._by_digest.get(d)
            if p is None:
                break
            pages.append(p)
        return pages

    def drop_page(self, page: int) -> None:
        d = self._by_page.pop(page, None)
        if d is not None:
            del self._by_digest[d]

    def pages(self) -> List[int]:
        return list(self._by_page)


class PagedKVAllocator:
    """Owns the shared physical KV page pool of one node service.

    ``n_pages`` counts physical pages including the reserved scratch page,
    so ``n_pages - 1`` pages are allocatable; each page holds ``page_size``
    token positions across every layer of every group. The allocator is
    deliberately policy-free: it allocates, refcounts, and moves bytes
    between the dense and paged layouts. *What* to evict under pressure is
    the :class:`~repro.serving.session_cache.SessionCachePool`'s call
    (page-budgeted LRU), and growth/requeue decisions belong to the
    :class:`~repro.serving.scheduler.BatchedServer`.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        page_size: int = 16,
        n_pages: int = 256,
        dtype=None,
        share_prefixes: bool = True,
        device: Optional[jax.Device] = None,
    ) -> None:
        assert supports_append(cfg), (
            "paged KV requires full-cache dense/moe groups "
            f"(arch={cfg.arch_type}, attn_variant={cfg.attn_variant})"
        )
        assert page_size > 0 and n_pages > 1, (page_size, n_pages)
        self.cfg = cfg
        self.page_size = page_size
        self.n_pages = n_pages
        self.share_prefixes = share_prefixes
        self.index = PrefixPageIndex()
        # the pool is committed to the service's device, so every jitted
        # step that takes it runs there
        self.device = device if device is not None else jax.devices()[0]
        self.pools: List[Dict[str, jnp.ndarray]] = [
            init_paged_pool(cfg, spec.n_blocks, n_pages, page_size, dtype,
                            device=self.device)
            for spec in layer_groups(cfg)
        ]
        # page 0 reserved as scratch; LIFO free list keeps reuse warm
        self._free: List[int] = list(range(n_pages - 1, 0, -1))
        self._ref = np.zeros(n_pages, np.int32)
        self._gather_fns: Dict[int, object] = {}
        self._scatter_fns: Dict[int, object] = {}
        self._copy_page_fn = None
        self._import_page_fn = None

    # -- accounting -----------------------------------------------------
    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return (self.n_pages - 1) - len(self._free)

    @property
    def page_bytes(self) -> int:
        """Bytes of one physical page across all layers/groups (k + v)."""
        total = 0
        for pool in self.pools:
            for name in ("k", "v"):
                a = pool[name]
                total += (a.size // a.shape[1]) * a.dtype.itemsize
        return total

    @property
    def resident_kv_bytes(self) -> int:
        return self.used_pages * self.page_bytes

    @property
    def total_kv_bytes(self) -> int:
        return (self.n_pages - 1) * self.page_bytes

    def pages_for(self, n_tokens: int) -> int:
        """Pages needed to hold ``n_tokens`` positions (at least one)."""
        return max(1, -(-n_tokens // self.page_size))

    def refcount(self, page: int) -> int:
        return int(self._ref[page])

    # -- page lifecycle -------------------------------------------------
    def alloc(self, n: int) -> Optional[List[int]]:
        """Allocate ``n`` pages (refcount 1 each), or None if the pool
        can't satisfy the request — the caller decides whether to reclaim
        via the session pool, requeue, or degrade."""
        if n <= 0:
            return []
        if len(self._free) < n:
            return None
        pages = [self._free.pop() for _ in range(n)]
        self._ref[pages] = 1
        return pages

    def incref(self, pages: Sequence[int]) -> None:
        for p in pages:
            assert p != SCRATCH_PAGE and self._ref[p] > 0, p
            self._ref[p] += 1

    def decref(self, pages: Sequence[int]) -> None:
        for p in pages:
            assert p != SCRATCH_PAGE and self._ref[p] > 0, p
            self._ref[p] -= 1
            if self._ref[p] == 0:
                # released pages must leave the content index immediately:
                # the page may be recycled for arbitrary new content, and
                # the index must never name a page nobody holds
                self.index.drop_page(p)
                self._free.append(p)

    # -- cross-session prefix sharing -----------------------------------
    def match_prefix(
        self, token_ids: Sequence[int], max_tokens: Optional[int] = None
    ) -> List[int]:
        """Longest run of resident full prefix pages matching ``token_ids``
        byte-for-byte (chained content hash), across *every* session. At
        most ``max_tokens`` leading tokens are considered. Returns physical
        page ids with NO references taken — incref before anything that
        could evict their owners."""
        if not self.share_prefixes or not token_ids:
            return []
        n = len(token_ids) if max_tokens is None else min(len(token_ids), max_tokens)
        return self.index.lookup_run(
            page_digests(token_ids, self.page_size, n // self.page_size)
        )

    def register_pages(self, token_ids: Sequence[int], pages: Sequence[int]) -> None:
        """Index the *full* pages of an at-rest sequence for cross-session
        matching (no-op per page if its content is already indexed). Only
        call for pages whose bytes are final — entry storage and finished
        slot write-back, never a live lane's tail."""
        if not self.share_prefixes:
            return
        for d, p in zip(page_digests(token_ids, self.page_size), pages):
            self.index.register(d, p)

    # -- layout moves (jitted once per dense width) ---------------------
    def table_for(self, pages: Sequence[int], width: int) -> np.ndarray:
        mp = width // self.page_size
        assert width % self.page_size == 0, (width, self.page_size)
        assert len(pages) <= mp, (len(pages), mp)
        table = np.full((mp,), SCRATCH_PAGE, np.int32)
        table[: len(pages)] = pages
        return table

    def _scatter_fn(self, width: int):
        """Write a dense (B=1, width) lane through a page table. Shared
        prefix pages receive identical bytes (the dense lane was gathered
        from them) and padding rows land in the scratch page, so one
        compile per dense width covers every admission/store."""
        if width not in self._scatter_fns:

            @partial(jax.jit, donate_argnums=(0,))
            def fn(pools, dense, table):
                out = []
                for pool, c in zip(pools, dense):
                    l = c["k"].shape[0]
                    chunk_shape = (l, -1, self.page_size) + c["k"].shape[3:]
                    out.append({
                        "k": pool["k"].at[:, table].set(
                            c["k"][:, 0].reshape(chunk_shape).astype(pool["k"].dtype)
                        ),
                        "v": pool["v"].at[:, table].set(
                            c["v"][:, 0].reshape(chunk_shape).astype(pool["v"].dtype)
                        ),
                    })
                return out

            self._scatter_fns[width] = fn
        return self._scatter_fns[width]

    def _gather_fn(self, width: int):
        if width not in self._gather_fns:

            @jax.jit
            def fn(pools, table, n_valid):
                j = jnp.arange(width, dtype=jnp.int32)
                kv_pos = jnp.where(j < n_valid, j, -1)[None, :]
                out = []
                for pool in pools:
                    l = pool["k"].shape[0]
                    k = pool["k"][:, table]          # (L, MP, ps, KV, Dh)
                    v = pool["v"][:, table]
                    flat = (l, 1, width) + pool["k"].shape[3:]
                    out.append({
                        "k": k.reshape(flat),
                        "v": v.reshape(flat),
                        "kv_pos": kv_pos,
                    })
                return out

            self._gather_fns[width] = fn
        return self._gather_fns[width]

    def copy_page(self, src: int, dst: int) -> None:
        """Device-copy one physical page's bytes (every layer of every
        group) from ``src`` into ``dst`` — the partial-tail handoff of
        chunked admission: a new lane continuing mid-page through a shared
        entry's partially filled tail page gets an exclusively-held byte
        copy to append into, instead of a dense gather + full-lane rewrite.
        Bytes beyond the valid prefix come along too; they are dead cells
        under the layout invariant (slot >= coverage is never causal) and
        are overwritten as the lane grows. One compile total — src/dst are
        traced scalars."""
        if self._copy_page_fn is None:

            @partial(jax.jit, donate_argnums=(0,))
            def fn(pools, s, d):
                return [
                    {
                        "k": pool["k"].at[:, d].set(pool["k"][:, s]),
                        "v": pool["v"].at[:, d].set(pool["v"][:, s]),
                    }
                    for pool in pools
                ]

            self._copy_page_fn = fn
        self.pools = self._copy_page_fn(
            self.pools, jnp.int32(src), jnp.int32(dst)
        )

    def export_page_bytes(self, page: int) -> bytes:
        """Serialize one physical page's bytes for shipping: per layer
        group, the K block then the V block, each ``(L, page_size, KV, Dh)``
        in the pool's native dtype, C order, concatenated. The native dtype
        (bf16 for the serving configs) makes the round trip bit-exact:
        ``import_page_bytes`` on an identically-configured pool reproduces
        the page byte-for-byte, so a shipped prime is greedy-equivalent to
        the local recompute it replaces."""
        parts: List[bytes] = []
        for pool in self.pools:
            for name in ("k", "v"):
                parts.append(np.asarray(pool[name][:, page]).tobytes())
        return b"".join(parts)

    def import_page_bytes(self, page: int, data: bytes) -> None:
        """Install bytes produced by :meth:`export_page_bytes` (on a pool
        with the same model config / page_size / dtype) into ``page``. The
        caller owns the page and is responsible for content verification —
        this is a raw byte move, the digest check happens at the shipping
        layer against the token ground truth."""
        assert page != SCRATCH_PAGE, "refusing to import into the scratch page"
        off = 0
        blocks: List[Dict[str, np.ndarray]] = []
        for pool in self.pools:
            entry: Dict[str, np.ndarray] = {}
            for name in ("k", "v"):
                a = pool[name]
                shape = (a.shape[0],) + tuple(a.shape[2:])  # (L, ps, KV, Dh)
                n_bytes = int(np.prod(shape)) * a.dtype.itemsize
                entry[name] = np.frombuffer(
                    data[off : off + n_bytes], dtype=a.dtype
                ).reshape(shape)
                off += n_bytes
            blocks.append(entry)
        assert off == len(data), (off, len(data), self.page_bytes)
        if self._import_page_fn is None:
            # donated and jitted like copy_page: an eager .at[].set would
            # hold a second copy of the whole pool per imported page

            @partial(jax.jit, donate_argnums=(0,))
            def fn(pools, blocks, d):
                return [
                    {n: pool[n].at[:, d].set(b[n]) for n in ("k", "v")}
                    for pool, b in zip(pools, blocks)
                ]

            self._import_page_fn = fn
        self.pools = self._import_page_fn(
            self.pools, jax.device_put(blocks, self.device), jnp.int32(page)
        )

    def write_through(
        self, pages: Sequence[int], dense: List[Dict], n_skip: int = 0
    ) -> None:
        """Scatter a dense B=1 lane (width = pages' span, scratch-padded)
        into ``pages``. The lane width must be a page_size multiple.
        ``n_skip`` leading pages are NOT written (their table slots are
        redirected to the scratch page): shared prefix pages are read-only
        for every sharer — that is the copy-on-write guarantee — and their
        bytes are already exactly what the dense lane holds there."""
        width = int(dense[0]["k"].shape[2])
        table = self.table_for(pages, width)
        table[: min(n_skip, len(pages))] = SCRATCH_PAGE
        self.pools = self._scatter_fn(width)(self.pools, dense, jnp.asarray(table))

    def store(
        self, dense: List[Dict], n_tokens: int,
        token_ids: Optional[Sequence[int]] = None,
    ) -> Optional[List[int]]:
        """Page an at-rest dense lane: share any resident prefix pages whose
        content matches ``token_ids`` (cross-session, incref — the write is
        skipped for them), allocate fresh pages for the rest, write the lane
        through, and index the stored full pages. Returns the page list
        (caller owns one ref per page), or None when the pool can't supply
        the fresh pages — shared refs are released again in that case."""
        shared = self.match_prefix(token_ids, n_tokens) if token_ids else []
        if shared:
            self.incref(shared)
        fresh = self.alloc(self.pages_for(n_tokens) - len(shared))
        if fresh is None:
            if shared:
                self.decref(shared)
            return None
        pages = shared + fresh
        if fresh:
            self.write_through(pages, dense, n_skip=len(shared))
        if token_ids is not None:
            self.register_pages(token_ids, pages)
        return pages

    def gather(
        self, pages: Sequence[int], n_valid: int, width: int
    ) -> List[Dict]:
        """Materialize pages as a dense B=1 cache pytree of ``width`` slots
        with kv_pos valid on [0, n_valid) — fresh buffers, safe to hand to
        compute paths that donate. Pages beyond width // page_size are not
        gathered (callers never need positions >= width)."""
        mp = width // self.page_size
        table = jnp.asarray(self.table_for(list(pages)[:mp], width))
        return self._gather_fn(width)(
            self.pools, table, jnp.int32(n_valid)
        )

    def stats(self) -> Dict[str, int]:
        return {
            "n_pages": self.n_pages - 1,
            "page_size": self.page_size,
            "used_pages": self.used_pages,
            "free_pages": self.n_free,
            "page_bytes": self.page_bytes,
            "resident_kv_bytes": self.resident_kv_bytes,
            "total_kv_bytes": self.total_kv_bytes,
            "indexed_pages": len(self.index),
        }
