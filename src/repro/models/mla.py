"""Multi-head latent attention (MLA; DeepSeek-V2, arXiv:2405.04434 §2.1),
without query compression (DeepSeek-V2-Lite).

Per token, ``wkv_a`` projects the hidden state to a ``kv_lora_rank``-wide
latent ``c_kv`` (normed by ``kv_norm``) and one RoPE key ``k_pe`` of
``qk_rope_dim`` that every head shares. ``wkv_b`` expands the latent into
each head's ``qk_nope_dim`` key part (``W_UK``) and ``v_head_dim`` value
(``W_UV``). Queries are ``qk_nope_dim`` + ``qk_rope_dim`` per head. RoPE is
YaRN's where the config scales it, on half-split dims.

Two forms of the same attention:

- *un-absorbed*, as published (:func:`mla_forward`, the full forward):
  keys and values are expanded per head and attended like MHA;
- *absorbed*, against the paged pool (:func:`mla_decode_paged`,
  :func:`mla_chunk_paged`): a page row is one token's ``[c_kv, k_pe]``
  (``latent_width`` lanes, zero-padded to whole 128-lane tiles:
  ``cache.latent_row``), ``W_UK`` is folded into the query, so each head's
  query is as wide and attends the shared latent rows directly (one KV
  head, group ``n_heads``); the value is the row's leading
  ``kv_lora_rank`` lanes, and the output stays in the latent space until
  ``W_UV`` and ``wo`` act on it.

On the Pallas path the paged kernels attend the latent pool
(``repro.kernels.paged_attention``, ``repro.kernels.chunked_prefill``); the
reference path attends a gathered linear view of it in ``jax.numpy``.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .cache import latent_row, paged_write_rows
from .config import ModelConfig
from .layers import Params, apply_rope, dtype_of, rms_norm, yarn_mscale

NEG_INF = -1e30


def init_mla(key, cfg: ModelConfig) -> Params:
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    dt = dtype_of(cfg.param_dtype)
    ks = jax.random.split(key, 4)

    def normal(k, shape, fan_in):
        return (jax.random.normal(k, shape) / np.sqrt(fan_in)).astype(dt)

    return {
        "wq": normal(ks[0], (d, h * (nope + rope)), d),
        "wkv_a": normal(ks[1], (d, r + rope), d),
        "kv_norm": jnp.zeros((r,), dt),          # stored as weight - 1
        "wkv_b": normal(ks[2], (r, h * (nope + vd)), r),
        "wo": normal(ks[3], (h * vd, d), h * vd),
    }


def softmax_scale(cfg: ModelConfig) -> float:
    """(qk_nope + qk_rope)^-1/2, times YaRN's mscale squared where RoPE is
    scaled (DeepSeek-V2's ``softmax_scale``)."""
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    if cfg.rope_factor > 0 and cfg.yarn_mscale_all_dim:
        m = yarn_mscale(cfg.rope_factor, cfg.yarn_mscale_all_dim)
        scale *= m * m
    return scale


def _pad_lanes(a: jnp.ndarray, width: int) -> jnp.ndarray:
    """``a`` with zero lanes appended up to ``width`` (the pool's row)."""
    pad = width - a.shape[-1]
    return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)]) if pad else a


def project(
    p: Params, x: jnp.ndarray, positions: jnp.ndarray, cfg: ModelConfig
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x (B,S,D) -> q_nope (B,S,H,nope), roped q_pe (B,S,H,rope) and the
    token's latent row (B,S,latent_width): ``c_kv`` normed, then ``k_pe``
    roped."""
    b, s, _ = x.shape
    h, r, nope = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_dim
    q = (x @ p["wq"]).reshape(b, s, h, nope + cfg.qk_rope_dim)
    q_pe = apply_rope(cfg, q[..., nope:], positions)
    kv = x @ p["wkv_a"]
    c = rms_norm(kv[..., :r], p["kv_norm"], cfg.norm_eps)
    k_pe = apply_rope(cfg, kv[..., None, r:], positions)[:, :, 0]
    return q[..., :nope], q_pe, jnp.concatenate([c, k_pe.astype(c.dtype)], -1)


def _wkv_b(p: Params, cfg: ModelConfig) -> jnp.ndarray:
    """(kv_lora_rank, H, qk_nope + v_head): W_UK then W_UV of each head."""
    return p["wkv_b"].reshape(
        cfg.kv_lora_rank, cfg.n_heads, cfg.qk_nope_dim + cfg.v_head_dim
    )


def absorb_query(
    p: Params, q_nope: jnp.ndarray, q_pe: jnp.ndarray, cfg: ModelConfig
) -> jnp.ndarray:
    """W_UK folded into the query: (B,S,H,latent_row) with zeros past
    ``latent_width``, whose dot with a pool row is the un-absorbed q.k."""
    w_uk = _wkv_b(p, cfg)[..., : cfg.qk_nope_dim]
    q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, w_uk)
    q = jnp.concatenate([q_lat, q_pe.astype(q_lat.dtype)], -1)
    return _pad_lanes(q, latent_row(cfg))


def latent_output(p: Params, o_lat: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Per-head latent outputs (B,S,H,kv_lora_rank) through W_UV and wo."""
    b, s = o_lat.shape[:2]
    w_uv = _wkv_b(p, cfg)[..., cfg.qk_nope_dim:]
    o = jnp.einsum("bshr,rhv->bshv", o_lat.astype(w_uv.dtype), w_uv)
    return o.reshape(b, s, cfg.n_heads * cfg.v_head_dim) @ p["wo"]


def sdpa(q, k, v, q_pos, kv_pos, kv_valid, scale: float) -> jnp.ndarray:
    """Position-masked attention in float32 with key width ``dk`` and value
    width ``dv`` apart. q (B,S,H,dk); k (B,T,KV,dk); v (B,T,KV,dv); q_pos
    (B,S); kv_pos, kv_valid (B,T). Returns (B,S,H,dv) in q's dtype."""
    b, s, h, dk = q.shape
    kvh, dv = k.shape[2], v.shape[-1]
    qf = q.reshape(b, s, kvh, h // kvh, dk).astype(jnp.float32)
    logits = jnp.einsum("bskgd,btkd->bkgst", qf, k.astype(jnp.float32)) * scale
    mask = (kv_pos[:, None, :] <= q_pos[:, :, None]) & kv_valid[:, None, :]
    logits = jnp.where(mask[:, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v.astype(jnp.float32))
    return out.reshape(b, s, h, dv).astype(q.dtype)


def mla_forward(
    p: Params, x: jnp.ndarray, positions: jnp.ndarray, cfg: ModelConfig,
    seq_valid: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Full-sequence MLA in the published, un-absorbed form."""
    b, s, _ = x.shape
    h, r, nope = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_dim
    q_nope, q_pe, lat = project(p, x, positions, cfg)
    kv = jnp.einsum("bsr,rhe->bshe", lat[..., :r], _wkv_b(p, cfg))
    k_pe = jnp.broadcast_to(lat[:, :, None, r:], (b, s, h, cfg.qk_rope_dim))
    k = jnp.concatenate([kv[..., :nope], k_pe], -1)
    q = jnp.concatenate([q_nope, q_pe.astype(q_nope.dtype)], -1)
    valid = seq_valid if seq_valid is not None else jnp.ones((b, s), bool)
    out = sdpa(q, k, kv[..., nope:], positions, positions, valid, softmax_scale(cfg))
    return out.reshape(b, s, h * cfg.v_head_dim) @ p["wo"]


def mla_decode_paged(
    p: Params,
    x: jnp.ndarray,              # (B,1,D) normed hidden state of the token
    positions: jnp.ndarray,      # (B,1)
    pool: jnp.ndarray,           # (L, P, ps, latent_row) stack, with layer
    page_table: jnp.ndarray,     # (B, MP)
    kv_pos: jnp.ndarray,         # (B, MP*ps)
    cfg: ModelConfig,
    page_size: int,
    lin: Optional[jnp.ndarray] = None,   # (B, MP*ps, 1, latent_row) view
    shared_pages: Optional[jnp.ndarray] = None,
    layer=None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One decode token in the absorbed form: its latent row is written to
    its page cell, then its absorbed query attends the lane's latent rows
    through the page table. Returns (attention output (B,1,D), pool)."""
    r = cfg.kv_lora_rank
    q_nope, q_pe, row = project(p, x, positions, cfg)
    row = _pad_lanes(row, pool.shape[-1])
    pool = paged_write_rows(pool, row[:, 0], positions[:, 0], page_table,
                            page_size, layer)
    q = absorb_query(p, q_nope, q_pe, cfg)
    scale = softmax_scale(cfg)
    if cfg.attn_impl == "pallas":
        from ..kernels.paged_attention import ops as paged_ops

        o = paged_ops.paged_attention(
            q, pool, None, page_table, positions, kv_pos, shared_pages, layer,
            scale=scale, v_width=r,
        )
    else:
        bidx = jnp.arange(x.shape[0])
        lin = lin.at[bidx, positions[:, 0], 0].set(
            row[:, 0].astype(lin.dtype), mode="drop")
        o = sdpa(q, lin, lin[..., :r], positions, kv_pos, kv_pos >= 0, scale)
    return latent_output(p, o, cfg), pool


def mla_chunk_paged(
    p: Params,
    x: jnp.ndarray,              # (B,S,D) normed hidden states of the chunk
    positions: jnp.ndarray,      # (B,S) absolute positions
    valid: jnp.ndarray,          # (B,S) False for bucket padding
    pool: jnp.ndarray,           # (L, P, ps, latent_row) stack, with layer
    page_table: jnp.ndarray,     # (B, MP)
    p0: jnp.ndarray,             # (B,)
    true_len: jnp.ndarray,       # (B,)
    cfg: ModelConfig,
    n_skip: int = 0,
    lin: Optional[jnp.ndarray] = None,   # (B, MP*ps, 1, latent_row) view
    layer=None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """A prefill chunk in the absorbed form: its latent rows are written
    to their page cells (``n_skip`` shared pages and padding dropped), then
    its absorbed queries attend the lane's causal prefix through the page
    table. Returns (attention output (B,S,D), pool)."""
    r = cfg.kv_lora_rank
    b = x.shape[0]
    ps = pool.shape[2]
    q_nope, q_pe, rows = project(p, x, positions, cfg)
    rows = _pad_lanes(rows, pool.shape[-1])
    pool = paged_write_rows(pool, rows, positions, page_table, ps, layer,
                            valid=valid, n_skip=n_skip)
    q = absorb_query(p, q_nope, q_pe, cfg)
    scale = softmax_scale(cfg)
    if cfg.attn_impl == "pallas":
        from ..kernels.chunked_prefill import ops as chunk_ops

        o = chunk_ops.chunked_prefill_attention(
            q, pool, None, page_table, p0, true_len, layer,
            scale=scale, v_width=r,
        )
        return latent_output(p, o, cfg), pool
    t = lin.shape[1]
    bidx = jnp.arange(b)[:, None]
    # mirror the pool write's drop set on the linear view
    w_pos = jnp.where(valid & (positions >= n_skip * ps), positions, t)
    lin = lin.at[bidx, w_pos, 0].set(rows.astype(lin.dtype), mode="drop")
    slot = jnp.arange(t, dtype=jnp.int32)[None, :]
    kv_valid = slot < (p0 + true_len)[:, None]
    kv_pos = jnp.where(kv_valid, slot, -1)
    o = sdpa(q, lin, lin[..., :r], positions, kv_pos, kv_valid, scale)
    return latent_output(p, o, cfg), pool
