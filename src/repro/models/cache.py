"""KV-cache structures for serving.

Three kinds, composable per layer-group:
- full cache      (B, T, KV, Dh) per layer — dense/global attention;
- ring cache      (B, W, KV, Dh) per layer — sliding-window layers
                  (gemma2 local layers; the long-context variant);
- SSM state       (B, H, P, N) + conv window — Mamba2/hybrid.

Caches are stacked over the layers of a group (leading L axis) so decode can
lax.scan over layers. ``kv_pos`` records the absolute position stored in each
slot (-1 = empty) — attention masks are computed from positions, so ring and
full caches share one masking rule (models/attention.py).

Sharding (launch/sharding.py): batch over ``data``, kv-heads over ``model``;
for long_500k (batch=1) the slot axis T shards over ``data`` instead —
flash-decode with GSPMD partial-softmax combine.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import dtype_of

Cache = Dict[str, jnp.ndarray]


def init_attn_cache(
    cfg: ModelConfig, n_layers: int, batch: int, slots: int, dtype=None
) -> Cache:
    dt = dtype or dtype_of(cfg.compute_dtype)
    kv, dh = cfg.n_kv_heads, cfg.d_head
    return {
        "k": jnp.zeros((n_layers, batch, slots, kv, dh), dtype=dt),
        "v": jnp.zeros((n_layers, batch, slots, kv, dh), dtype=dt),
        "kv_pos": jnp.full((batch, slots), -1, dtype=jnp.int32),
    }


def init_ssm_cache(cfg: ModelConfig, n_layers: int, batch: int, dtype=None) -> Cache:
    from .ssm import conv_dim

    dt = dtype or dtype_of(cfg.compute_dtype)
    nh, n = cfg.n_ssm_heads, cfg.ssm_state
    hd = cfg.d_inner // nh
    return {
        "h": jnp.zeros((n_layers, batch, nh, hd, n), dtype=dt),
        "conv": jnp.zeros((n_layers, batch, cfg.ssm_conv, conv_dim(cfg)), dtype=dt),
    }


def write_step(
    cache_k: jnp.ndarray,   # (B, T, KV, Dh) one layer
    cache_v: jnp.ndarray,
    k_new: jnp.ndarray,     # (B, 1, KV, Dh)
    v_new: jnp.ndarray,
    pos: jnp.ndarray,       # (B,) absolute position of the new token
    ring: bool,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    b, t = cache_k.shape[0], cache_k.shape[1]
    slot = pos % t if ring else jnp.minimum(pos, t - 1)
    bidx = jnp.arange(b)
    ck = cache_k.at[bidx, slot].set(k_new[:, 0])
    cv = cache_v.at[bidx, slot].set(v_new[:, 0])
    return ck, cv


def update_kv_pos(kv_pos: jnp.ndarray, pos: jnp.ndarray, ring: bool) -> jnp.ndarray:
    b, t = kv_pos.shape
    slot = pos % t if ring else jnp.minimum(pos, t - 1)
    return kv_pos.at[jnp.arange(b), slot].set(pos)


def append_kv_pos(
    kv_pos: jnp.ndarray,   # (B, T) existing slot positions (full cache)
    q_pos: jnp.ndarray,    # (B, S) absolute positions of the appended chunk
    valid: jnp.ndarray,    # (B, S) bool — False for bucket padding
) -> jnp.ndarray:
    """kv_pos after appending a token chunk into a *full* cache, where slot
    index == absolute position. Padded chunk positions write -1 (kept
    invalid); out-of-range slots are dropped."""
    b = kv_pos.shape[0]
    bidx = jnp.arange(b)[:, None]
    vals = jnp.where(valid, q_pos, -1).astype(jnp.int32)
    return kv_pos.at[bidx, q_pos].set(vals, mode="drop")


def trim_kv_pos(kv_pos: jnp.ndarray, n_valid) -> jnp.ndarray:
    """Invalidate every slot at index >= n_valid (full cache: slot == pos).

    Used when storing caches in the session pool: decode may have run past a
    stop token (device-side stop scan syncs every k tokens), so slots beyond
    the kept prefix hold K/V of discarded tokens and must be masked out."""
    t = kv_pos.shape[1]
    j = jnp.arange(t, dtype=jnp.int32)
    n = jnp.asarray(n_valid, jnp.int32)
    keep = j[None, :] < (n[:, None] if n.ndim == 1 else n)
    return jnp.where(keep, kv_pos, -1)


# ---------------------------------------------------------------------------
# Paged KV (serving): block-granular storage behind a page table
# ---------------------------------------------------------------------------
#
# A paged pool replaces the per-sequence full-width (B, T, KV, Dh) cache with
# a shared physical pool of fixed-size pages, (P, page_size, KV, Dh) per
# layer. A sequence is a *page table* — a list of physical page ids — whose
# concatenation reproduces the linear slot == absolute-position layout of the
# full cache exactly, so the position-masked attention rule is unchanged:
# gather the pages into a linear view, attend, and scatter the new token's
# K/V into its (page, offset) cell. Page id 0 is reserved as a scratch page:
# table padding points at it, and writes landing there (inactive batch
# lanes, scatter padding) are garbage by design, masked via kv_pos.


def init_paged_pool(
    cfg: ModelConfig, n_layers: int, n_pages: int, page_size: int, dtype=None,
    device=None,
) -> Cache:
    """Physical KV page pool for one layer group: k/v of shape
    (L, n_pages, page_size, KV, Dh), made on (and committed to) ``device``
    when one is given. kv_pos is tracked per *sequence* (B, width) by the
    owner, not per page."""
    dt = dtype or dtype_of(cfg.compute_dtype)
    shape = (n_layers, n_pages, page_size, cfg.n_kv_heads, cfg.d_head)
    return {
        "k": jnp.zeros(shape, dtype=dt, device=device),
        "v": jnp.zeros(shape, dtype=dt, device=device),
    }


def gather_pages(pool: jnp.ndarray, page_table: jnp.ndarray) -> jnp.ndarray:
    """Linearize a per-layer pool slice through a page table.

    pool: (P, page_size, KV, Dh); page_table: (B, MP) physical page ids.
    Returns (B, MP*page_size, KV, Dh) — the virtual full-width cache view
    whose slot t holds page_table[t // ps], offset t % ps."""
    b, mp = page_table.shape
    ps = pool.shape[1]
    out = pool[page_table]                      # (B, MP, ps, KV, Dh)
    return out.reshape(b, mp * ps, pool.shape[2], pool.shape[3])


def paged_write_step(
    pool_k: jnp.ndarray,    # (P, ps, KV, Dh) one layer
    pool_v: jnp.ndarray,
    k_new: jnp.ndarray,     # (B, 1, KV, Dh)
    v_new: jnp.ndarray,
    pos: jnp.ndarray,       # (B,) absolute position of the new token
    page_table: jnp.ndarray,  # (B, MP)
    page_size: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter one decode token's K/V into its (page, offset) cell. The
    owner guarantees each active lane's current tail page is exclusively
    held (fresh tail-page swap at admission), so cross-lane collisions cannot
    occur; inactive lanes point at the scratch page.

    A lane whose position has run past the table (``pos >= MP * ps``) gets
    its write *dropped* — an out-of-range sentinel page id plus
    ``mode="drop"`` — rather than clamped into the last page, which would
    silently overwrite resident KV of the token actually living in that
    cell (tests/test_paged_kv.py::test_paged_write_step_drops_at_capacity)."""
    b = pos.shape[0]
    bidx = jnp.arange(b)
    mp = page_table.shape[1]
    n_pages = pool_k.shape[0]
    page_idx = pos // page_size
    phys = page_table[bidx, jnp.minimum(page_idx, mp - 1)]
    phys = jnp.where(page_idx < mp, phys, n_pages)   # OOB sentinel -> dropped
    slot = pos % page_size
    pk = pool_k.at[phys, slot].set(k_new[:, 0], mode="drop")
    pv = pool_v.at[phys, slot].set(v_new[:, 0], mode="drop")
    return pk, pv


def paged_write_chunk(
    pool_k: jnp.ndarray,    # (P, ps, KV, Dh) one layer
    pool_v: jnp.ndarray,
    k_new: jnp.ndarray,     # (B, S, KV, Dh) — a prefill chunk's rotated K
    v_new: jnp.ndarray,
    q_pos: jnp.ndarray,     # (B, S) absolute position of each chunk token
    valid: jnp.ndarray,     # (B, S) bool — False for bucket padding
    page_table: jnp.ndarray,  # (B, MP)
    page_size: int,
    n_skip: int = 0,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter a prefill chunk's K/V into their (page, offset) cells — the
    multi-token sibling of :func:`paged_write_step`, and the write half of
    chunked paged prefill (prefill output lands straight in pages, no dense
    intermediate). Dropped via the same out-of-range sentinel +
    ``mode="drop"``: bucket-padding tokens (``valid`` False), positions past
    the table, and writes landing in the first ``n_skip`` pages —
    shared-prefix pages another session owns are read-only by construction,
    so a caller that starts a chunk inside a shared region redirects those
    slots to nowhere instead of corrupting the donor."""
    b, s = q_pos.shape
    mp = page_table.shape[1]
    n_pages = pool_k.shape[0]
    bidx = jnp.arange(b)[:, None]
    page_idx = q_pos // page_size
    phys = page_table[bidx, jnp.clip(page_idx, 0, mp - 1)]
    drop = (~valid) | (page_idx >= mp) | (page_idx < n_skip) | (q_pos < 0)
    phys = jnp.where(drop, n_pages, phys)            # OOB sentinel -> dropped
    slot = q_pos % page_size
    pk = pool_k.at[phys, slot].set(k_new.astype(pool_k.dtype), mode="drop")
    pv = pool_v.at[phys, slot].set(v_new.astype(pool_v.dtype), mode="drop")
    return pk, pv


def gather_pages_stacked(
    pool_k: jnp.ndarray,      # (L, P, ps, KV, Dh) — a layer group's K pool
    pool_v: jnp.ndarray,
    page_table: jnp.ndarray,  # (B, MP)
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Linearize an entire layer stack's K and V pools through the page
    table — the hoisted form of :func:`gather_pages` for the reference
    paged-decode path: one indexed load per pool per *step* (stacked over
    the layer axis) instead of two per *layer* of the scan. K and V are
    gathered separately rather than stacked into one take: concatenating
    the pools first would materialize a transient copy of the entire
    physical pool every step, which on a many-tenant node can exceed the
    bytes the gather itself moves. Returns ``(k, v)`` of shape
    (L, B, MP*ps, KV, Dh)."""
    l, _, ps, kv, dh = pool_k.shape
    b, mp = page_table.shape
    flat = (l, b, mp * ps, kv, dh)
    return (
        pool_k[:, page_table].reshape(flat),    # (L, B, MP, ps, KV, Dh)
        pool_v[:, page_table].reshape(flat),
    )


def trim_cache_prefix(caches, n_valid) -> list:
    """B=1 full-cache pytree with kv_pos masked beyond ``n_valid`` — the one
    trim every pool-storage path uses (serve write-back, prime, retry
    reuse): slots past the kept prefix hold K/V of discarded or
    not-yet-requested tokens and must not be attended."""
    n = jnp.asarray(n_valid, jnp.int32).reshape(1)
    return [
        {"k": c["k"], "v": c["v"], "kv_pos": trim_kv_pos(c["kv_pos"], n)}
        for c in caches
    ]


def prefill_kv_pos(batch: int, slots: int, seq_len: int, ring: bool) -> jnp.ndarray:
    """kv_pos after prefilling seq_len tokens into a cache with `slots` slots."""
    j = jnp.arange(slots)
    if not ring or seq_len <= slots:
        pos = jnp.where(j < seq_len, j, -1)
    else:
        # ring holding the last `slots` positions of [0, seq_len)
        base = seq_len - slots
        pos = base + ((j - base) % slots)
    return jnp.broadcast_to(pos, (batch, slots)).astype(jnp.int32)


def ring_from_prefill(
    k: jnp.ndarray,  # (B, S, KV, Dh) — full prefill keys for one layer
    window: int,
) -> jnp.ndarray:
    """Pack the last `window` positions into ring order (slot = pos % W)."""
    b, s = k.shape[0], k.shape[1]
    w = window
    j = jnp.arange(w)
    if s <= w:
        gather = jnp.minimum(j, s - 1)
        out = k[:, gather]
        valid = j < s
        out = jnp.where(valid[None, :, None, None], out, 0)
        return out
    base = s - w
    gather = base + ((j - base) % w)
    return k[:, gather]
