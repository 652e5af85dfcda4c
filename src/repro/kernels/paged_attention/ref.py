"""Pure-jnp oracle for paged decode attention: linearize the page table
(the gather-materialize fallback's view) and take the exact masked softmax
of one query position against it.

Matches the kernel's empty-lane convention: a lane with no valid key slot
(all ``kv_pos < 0`` or ``> q_pos``) returns exact zeros. The serving paths
never read such lanes — their output is garbage-by-design — and zeros are
the only answer independent of how much of the table a bounded kernel
visits."""

from __future__ import annotations

import jax.numpy as jnp

NEG_INF = -1e30


def paged_attention_ref(
    q: jnp.ndarray,           # (B, 1, H, Dh) — rope'd query
    pool_k: jnp.ndarray,      # (P, page_size, KV, Dh) — shared pool, one layer
    pool_v: jnp.ndarray,
    page_table: jnp.ndarray,  # (B, MP) physical page ids per lane
    q_pos: jnp.ndarray,       # (B, 1) absolute position of the query
    kv_pos: jnp.ndarray,      # (B, MP*page_size), -1 = empty slot
    *,
    window: int = 0,
    softcap: float = 0.0,
    scale=None,
    v_width=None,
) -> jnp.ndarray:
    """Latent pages (``pool_v`` None, ``pool_k`` (P, page_size, W)): one
    row per token attended by every head, the value its leading
    ``v_width`` lanes."""
    b, _, h, dh = q.shape
    if pool_v is None:
        k = pool_k[page_table].reshape(b, -1, 1, dh)
        v = k[..., :v_width]
    else:
        k = pool_k[page_table].reshape(b, -1, pool_k.shape[2], dh)   # (B, MP*ps, KV, Dh)
        v = pool_v[page_table].reshape(b, -1, pool_k.shape[2], dh)
    kvh, dv = k.shape[2], v.shape[-1]
    g = h // kvh
    qq = q.reshape(b, kvh, g, dh).astype(jnp.float32)
    scale = 1.0 / (dh ** 0.5) if scale is None else scale
    logits = jnp.einsum("bkgd,btkd->bkgt", qq, k.astype(jnp.float32)) * scale
    if softcap > 0.0:
        logits = softcap * jnp.tanh(logits / softcap)
    qp = q_pos.reshape(b)
    mask = (kv_pos >= 0) & (kv_pos <= qp[:, None])
    if window > 0:
        mask = mask & (qp[:, None] - kv_pos < window)
    logits = jnp.where(mask[:, None, None, :], logits, NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m) * mask[:, None, None, :].astype(jnp.float32)
    l = jnp.sum(p, axis=-1, keepdims=True)
    l = jnp.where(l == 0.0, 1.0, l)
    out = jnp.einsum("bkgt,btkd->bkgd", p / l, v.astype(jnp.float32))
    return out.reshape(b, 1, h, dv).astype(q.dtype)
