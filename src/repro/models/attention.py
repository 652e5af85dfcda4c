"""Attention: GQA with RoPE variants, logit softcap, sliding windows.

Two execution paths:
- ``reference`` — pure jnp einsum path. Used for smoke tests and for the
  multi-pod dry-run (XLA sees plain dot_generals, so cost_analysis reports
  true FLOPs/bytes and GSPMD is free to partition heads/sequence).
- ``pallas``   — the flash-attention / flash-decode kernels from
  repro.kernels (VMEM-tiled, MXU-aligned), validated against this reference
  in interpret mode.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig
from .layers import Params, apply_rope, dtype_of, softcap
from .pjit_rules import constrain

NEG_INF = -1e30


def init_attention(key, cfg: ModelConfig) -> Params:
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    dt = dtype_of(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    s = 1.0 / np.sqrt(d)
    so = 1.0 / np.sqrt(h * dh)
    p: Params = {
        "wq": (jax.random.normal(ks[0], (d, h * dh)) * s).astype(dt),
        "wk": (jax.random.normal(ks[1], (d, kv * dh)) * s).astype(dt),
        "wv": (jax.random.normal(ks[2], (d, kv * dh)) * s).astype(dt),
        "wo": (jax.random.normal(ks[3], (h * dh, d)) * so).astype(dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * dh,), dtype=dt)
        p["bk"] = jnp.zeros((kv * dh,), dtype=dt)
        p["bv"] = jnp.zeros((kv * dh,), dtype=dt)
    return p


def qkv_project(
    p: Params, x: jnp.ndarray, positions: jnp.ndarray, cfg: ModelConfig
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x: (B,S,D) -> q (B,S,H,Dh), k/v (B,S,KV,Dh), RoPE applied."""
    b, s, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, cfg.n_heads, cfg.d_head)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.d_head)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.d_head)
    q = apply_rope(cfg, q, positions)
    k = apply_rope(cfg, k, positions)
    return q, k, v


def _sdpa_reference(
    q: jnp.ndarray,        # (B,S,H,Dh)
    k: jnp.ndarray,        # (B,T,KV,Dh)
    v: jnp.ndarray,        # (B,T,KV,Dh)
    q_pos: jnp.ndarray,    # (B,S)
    kv_pos: jnp.ndarray,   # (B,T)
    kv_valid: jnp.ndarray, # (B,T) bool
    cfg: ModelConfig,
    window: int,
) -> jnp.ndarray:
    """Masked GQA SDPA. Causality/window expressed on *positions* so the same
    code serves full-seq training, prefill, ring-buffer decode, and
    sequence-sharded long-context decode."""
    b, s, h, dh = q.shape
    kvh = k.shape[2]
    g = h // kvh
    q = q.reshape(b, s, kvh, g, dh)
    scale = 1.0 / np.sqrt(dh)
    logits = jnp.einsum("bskgd,btkd->bkgst", q.astype(jnp.float32), k.astype(jnp.float32))
    logits = logits * scale
    logits = softcap(logits, cfg.attn_softcap)
    causal = kv_pos[:, None, :] <= q_pos[:, :, None]              # (B,S,T)
    mask = causal & kv_valid[:, None, :]
    if window > 0:
        mask = mask & (q_pos[:, :, None] - kv_pos[:, None, :] < window)
    logits = jnp.where(mask[:, None, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v.astype(jnp.float32))
    return out.reshape(b, s, h, dh).astype(q.dtype)


def attention_forward(
    p: Params,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    cfg: ModelConfig,
    window: int = 0,
    seq_valid: Optional[jnp.ndarray] = None,
    return_kv: bool = False,
):
    """Full-sequence self-attention (training / prefill). positions is (B,S)
    or (3,B,S) for M-RoPE. With return_kv, also returns the rotated K and V
    (for cache seeding during prefill)."""
    pos1d = positions[0] if positions.ndim == 3 else positions
    q, k, v = qkv_project(p, x, positions, cfg)
    # logical sharding: context-parallel q (seq over model) when heads can't
    # shard; K/V stay seq-replicated (GSPMD all-gathers them once per layer)
    q = constrain(q, "batch", "seq", "heads", None)
    k = constrain(k, "batch", None, "kv_heads", None)
    v = constrain(v, "batch", None, "kv_heads", None)
    b, s = pos1d.shape
    valid = seq_valid if seq_valid is not None else jnp.ones((b, s), dtype=bool)
    if cfg.attn_impl == "pallas":
        from ..kernels.flash_attention import ops as flash_ops

        out = flash_ops.flash_attention(
            q, k, v, pos1d, pos1d, valid,
            window=window, softcap=cfg.attn_softcap,
        )
    else:
        out = _sdpa_reference(q, k, v, pos1d, pos1d, valid, cfg, window)
    b, s, h, dh = out.shape
    out = out.reshape(b, s, h * dh) @ p["wo"]
    if return_kv:
        return out, k, v
    return out


def _project_q_step(
    p: Params, x: jnp.ndarray, positions: jnp.ndarray, cfg: ModelConfig
) -> jnp.ndarray:
    """Rope'd query for the current decode token: (B,1,D) -> (B,1,H,Dh)."""
    b = x.shape[0]
    q = x @ p["wq"]
    if cfg.qkv_bias:
        q = q + p["bq"]
    q = q.reshape(b, 1, cfg.n_heads, cfg.d_head)
    return apply_rope(cfg, q, positions)


def attention_decode(
    p: Params,
    x: jnp.ndarray,              # (B,1,D) — the single new token
    positions: jnp.ndarray,      # (B,1) or (3,B,1)
    k_cache: jnp.ndarray,        # (B,T,KV,Dh) — already includes this token
    v_cache: jnp.ndarray,
    kv_pos: jnp.ndarray,         # (B,T) absolute positions per slot
    kv_valid: jnp.ndarray,       # (B,T)
    cfg: ModelConfig,
    window: int = 0,
) -> jnp.ndarray:
    """Single-step decode against a KV cache (full or ring)."""
    pos1d = positions[0] if positions.ndim == 3 else positions
    b = x.shape[0]
    q = _project_q_step(p, x, positions, cfg)
    if cfg.attn_impl == "pallas":
        from ..kernels.decode_attention import ops as decode_ops

        out = decode_ops.decode_attention(
            q, k_cache, v_cache, pos1d, kv_pos, kv_valid,
            window=window, softcap=cfg.attn_softcap,
        )
    else:
        out = _sdpa_reference(q, k_cache, v_cache, pos1d, kv_pos, kv_valid, cfg, window)
    out = out.reshape(b, 1, cfg.n_heads * cfg.d_head)
    return out @ p["wo"]


def attention_decode_paged(
    p: Params,
    x: jnp.ndarray,              # (B,1,D) — the single new token
    positions: jnp.ndarray,      # (B,1) or (3,B,1)
    pool_k: jnp.ndarray,         # (P, ps, KV, Dh), or a (L, P, ps, KV*Dh) stack
    pool_v: jnp.ndarray,
    page_table: jnp.ndarray,     # (B, MP) physical page ids per lane
    kv_pos: jnp.ndarray,         # (B, MP*ps) absolute positions per virtual slot
    cfg: ModelConfig,
    window: int = 0,
    lin_k: Optional[jnp.ndarray] = None,  # (B, MP*ps, KV, Dh) pre-gathered view
    lin_v: Optional[jnp.ndarray] = None,
    shared_pages: Optional[jnp.ndarray] = None,  # (S,) common leading pages
    layer=None,                  # the layer of a stacked pool to attend
) -> jnp.ndarray:
    """Page-table-aware decode, two execution paths:

    - ``pallas`` — the fused paged-attention kernel attends *through* the
      page table (``repro.kernels.paged_attention``): K/V pages are loaded
      straight from the shared pool via scalar-prefetched table indices, so
      per-step HBM traffic is O(actual kv_len), and no linearized copy of
      the cache ever exists.
    - ``reference`` — gather each lane's pages into the linear full-cache
      view (slot == absolute position) and run the standard position-masked
      decode attention. Callers that already hold that view (the hoisted
      once-per-step gather in :func:`~repro.models.transformer.
      decode_step_paged`) pass it as ``lin_k``/``lin_v``; otherwise it is
      gathered here, per layer. The gathered view is transient and
      bit-identical to the full-width cache layout, so greedy decode
      matches the unpaged path exactly.

    ``shared_pages`` (pallas path only; the reference path's gathered view
    already reads each physical page once per *lane* and simply ignores
    it): a run of pages every lane's table starts with — the kernel then
    attends those once per unique page for the whole batch and walks only
    the per-lane suffix (docs/architecture.md, "Cross-session shared-prefix
    paging").

    With ``layer``, ``pool_k/v`` are a group's ``(L, P, ps, KV*Dh)``
    stack: the kernel reads that layer of it in place, and the reference
    path takes the caller's gathered view.
    """
    pos1d = positions[0] if positions.ndim == 3 else positions
    if cfg.attn_impl == "pallas":
        from ..kernels.paged_attention import ops as paged_ops

        b = x.shape[0]
        q = _project_q_step(p, x, positions, cfg)
        out = paged_ops.paged_attention(
            q, pool_k, pool_v, page_table, pos1d, kv_pos,
            shared_pages, layer,
            window=window, softcap=cfg.attn_softcap,
        )
        out = out.reshape(b, 1, cfg.n_heads * cfg.d_head)
        return out @ p["wo"]

    from .cache import gather_pages

    ck = lin_k if lin_k is not None else gather_pages(pool_k, page_table)
    cv = lin_v if lin_v is not None else gather_pages(pool_v, page_table)
    return attention_decode(
        p, x, positions, ck, cv, kv_pos, kv_pos >= 0, cfg, window=window
    )


def attention_append(
    p: Params,
    x: jnp.ndarray,              # (B,S,D) — a chunk of new tokens
    positions: jnp.ndarray,      # (B,S) or (3,B,S) absolute positions
    k_cache: jnp.ndarray,        # (B,T,KV,Dh) full cache (slot == position)
    v_cache: jnp.ndarray,
    kv_pos: jnp.ndarray,         # (B,T) — already updated for this chunk
    cfg: ModelConfig,
    window: int = 0,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Multi-token decode: S new tokens mid-sequence attend against a full
    KV cache holding the prior prefix. The chunk's rotated K/V are scattered
    into the cache at their absolute positions *before* attention, so
    intra-chunk causality falls out of the shared position-based mask.
    Returns (attn output, new k_cache, new v_cache)."""
    pos1d = positions[0] if positions.ndim == 3 else positions
    b, s, _ = x.shape
    q, k, v = qkv_project(p, x, positions, cfg)
    bidx = jnp.arange(b)[:, None]
    ck = k_cache.at[bidx, pos1d].set(k.astype(k_cache.dtype), mode="drop")
    cv = v_cache.at[bidx, pos1d].set(v.astype(v_cache.dtype), mode="drop")
    kv_valid = kv_pos >= 0
    if cfg.attn_impl == "pallas":
        from ..kernels.flash_attention import ops as flash_ops

        out = flash_ops.flash_attention(
            q, ck, cv, pos1d, kv_pos, kv_valid,
            window=window, softcap=cfg.attn_softcap,
        )
    else:
        out = _sdpa_reference(q, ck, cv, pos1d, kv_pos, kv_valid, cfg, window)
    out = out.reshape(b, s, cfg.n_heads * cfg.d_head) @ p["wo"]
    return out, ck, cv


def attention_chunk_paged(
    p: Params,
    x: jnp.ndarray,              # (B,S,D) — a chunk of prompt tokens
    positions: jnp.ndarray,      # (B,S) or (3,B,S) absolute positions
    valid: jnp.ndarray,          # (B,S) bool — False for bucket padding
    pool_k: jnp.ndarray,         # (P, ps, KV, Dh), or a (L, P, ps, KV*Dh) stack
    pool_v: jnp.ndarray,
    page_table: jnp.ndarray,     # (B, MP) physical page ids per lane
    p0: jnp.ndarray,             # (B,) absolute position of chunk row 0
    true_len: jnp.ndarray,       # (B,) real chunk lengths
    cfg: ModelConfig,
    window: int = 0,
    n_skip: int = 0,
    lin_k: Optional[jnp.ndarray] = None,  # (B, MP*ps, KV, Dh) pre-gathered view
    lin_v: Optional[jnp.ndarray] = None,
    layer=None,                  # the layer of a stacked pool to write and attend
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Chunked paged prefill: S prompt tokens mid-sequence, K/V scattered
    straight into their page cells *before* attention (the paged sibling of
    :func:`attention_append` — intra-chunk causality falls out of the
    positional mask), then attended through the page table. No dense
    ``max_len``-width cache is ever built.

    - ``pallas`` — ``repro.kernels.chunked_prefill``: page-table index maps
      with scalar-prefetched per-lane bounds, one page DMA per grid step.
    - ``reference`` — the caller's hoisted gathered view (``lin_k/lin_v``,
      pre-scatter) gets the chunk inserted at its absolute slots here, and
      the standard position-masked SDPA runs over it — bit-identical to
      gathering after the scatter.

    Validity needs no kv_pos array: the layout invariant (slot == absolute
    position, written contiguously) makes slot ``t`` valid exactly when
    ``t < p0 + true_len``. With ``layer``, the pools are a group's
    ``(L, P, ps, KV*Dh)`` stack, written and read at that layer in place
    (the reference path from the caller's gathered view).
    Returns (attn output, new pool_k, new pool_v)."""
    from .cache import gather_pages, paged_write_chunk

    pos1d = positions[0] if positions.ndim == 3 else positions
    b, s, _ = x.shape
    ps = pool_k.shape[1 if layer is None else 2]
    q, k, v = qkv_project(p, x, positions, cfg)
    pk, pv = paged_write_chunk(
        pool_k, pool_v, k, v, pos1d, valid, page_table, ps, n_skip=n_skip,
        layer=layer,
    )
    if cfg.attn_impl == "pallas":
        from ..kernels.chunked_prefill import ops as chunk_ops

        out = chunk_ops.chunked_prefill_attention(
            q, pk, pv, page_table, p0, true_len, layer,
            window=window, softcap=cfg.attn_softcap,
        )
        out = out.reshape(b, s, cfg.n_heads * cfg.d_head) @ p["wo"]
        return out, pk, pv

    ck = lin_k if lin_k is not None else gather_pages(pool_k, page_table)
    cv = lin_v if lin_v is not None else gather_pages(pool_v, page_table)
    t = ck.shape[1]
    bidx = jnp.arange(b)[:, None]
    # mirror the pool scatter's drop set on the linear view: padding rows
    # and shared-page slots redirect out of range
    w_pos = jnp.where(valid & (pos1d >= n_skip * ps), pos1d, t)
    ck = ck.at[bidx, w_pos].set(k.astype(ck.dtype), mode="drop")
    cv = cv.at[bidx, w_pos].set(v.astype(cv.dtype), mode="drop")
    slot = jnp.arange(t, dtype=jnp.int32)[None, :]
    kv_valid = slot < (p0 + true_len)[:, None]
    kv_pos = jnp.where(kv_valid, slot, -1)
    out = _sdpa_reference(q, ck, cv, pos1d, kv_pos, kv_valid, cfg, window)
    out = out.reshape(b, s, cfg.n_heads * cfg.d_head) @ p["wo"]
    return out, pk, pv


def project_kv_step(
    p: Params, x: jnp.ndarray, positions: jnp.ndarray, cfg: ModelConfig
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """K/V for the current decode token (to be inserted into the cache)."""
    b = x.shape[0]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        k, v = k + p["bk"], v + p["bv"]
    k = k.reshape(b, 1, cfg.n_kv_heads, cfg.d_head)
    v = v.reshape(b, 1, cfg.n_kv_heads, cfg.d_head)
    k = apply_rope(cfg, k, positions)
    return k, v
