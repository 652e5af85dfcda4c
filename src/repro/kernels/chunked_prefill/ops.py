"""Jit'd wrapper for chunked paged prefill attention: reshapes GQA heads
and derives per-lane page bounds from the chunk's end position."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..paged_attention.ops import as_layer_stack
from .kernel import chunked_prefill_pallas


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("window", "softcap", "interpret"))
def chunked_prefill_attention(
    q,            # (B, S, H, Dh) — rope'd chunk queries
    pool_k,       # (P, ps, KV, Dh), or a (L, P, ps, KV*Dh) stack with layer
    pool_v,
    page_table,   # (B, MP) physical page ids per lane
    p0,           # (B,) absolute position of chunk row 0
    true_len,     # (B,) real chunk lengths (bucketed input)
    layer=None,   # () int: the layer of a stacked pool to attend
    *,
    window: int = 0,
    softcap: float = 0.0,
    interpret: bool = None,
):
    """S chunk queries vs a paged KV pool -> (B, S, H, Dh), attending
    through the page table. The caller has already scattered the chunk's
    K/V into the pool (``paged_write_chunk``), so the pool holds the lane's
    full causal prefix [0, p0 + true_len) — intra-chunk causality falls out
    of the per-row positional mask, exactly as in the dense
    ``attention_append``. The per-lane page bound
    ``ceil((p0 + true_len) / page_size)`` relies on the layout invariant
    (slot index == absolute position for valid slots), under which no key
    at or beyond ``p0 + true_len`` can pass any read row's causal mask.
    A stacked pool is read in place at ``layer``, a one-layer pool as a
    stack of one (``paged_attention``'s rule)."""
    if interpret is None:
        interpret = _on_cpu()
    pool_k, pool_v, lyr = as_layer_stack(pool_k, pool_v, layer)
    b, s, h, dh = q.shape
    ps = pool_k.shape[2]
    kvh = pool_k.shape[3] // dh
    g = h // kvh
    mp = page_table.shape[1]
    p0 = p0.reshape(b).astype(jnp.int32)
    end = p0 + jnp.maximum(true_len.reshape(b).astype(jnp.int32), 1)
    bound = jnp.clip((end + ps - 1) // ps, 1, mp)
    # (B, S, KV, G, Dh) -> (B, KV, S*G, Dh): chunk rows and query heads of
    # one KV head share each page load as one query block
    qr = q.reshape(b, s, kvh, g, dh).transpose(0, 2, 1, 3, 4).reshape(
        b, kvh, s * g, dh
    )
    out = chunked_prefill_pallas(
        qr, pool_k, pool_v, page_table, bound, p0, lyr,
        g=g, window=window, softcap=softcap, interpret=interpret,
    )
    return out.reshape(b, kvh, s, g, dh).transpose(0, 2, 1, 3, 4).reshape(
        b, s, h, dh
    )
