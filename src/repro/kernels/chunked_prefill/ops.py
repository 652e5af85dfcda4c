"""Jit'd wrapper for chunked paged prefill attention: reshapes GQA heads
and derives per-lane page bounds from the chunk's end position."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..paged_attention.ops import as_layer_stack
from .kernel import chunked_prefill_pallas

# VMEM a grid step's query rows may take: the query block and the output
# block (both double-buffered) and the (m, l, acc) scratch. Above it the
# rows are split into blocks (latent attention: a 64-token chunk's 16
# heads of 576 lanes fit, a 256-token chunk's do not); no GQA or MHA chunk
# of up to 256 tokens at the benchmark's head widths reaches it.
ROW_VMEM_BYTES = 8 * 2**20


def row_block(rows: int, kvh: int, dk: int, dv: int) -> int:
    """Query rows a grid step of the chunked-prefill kernel carries: all of
    them, or the largest halving (a multiple of 8) whose blocks fit
    :data:`ROW_VMEM_BYTES` (bf16 q in, out; f32 acc; m, l padded to 128
    lanes)."""
    per_row = kvh * (4 * dk + 4 * dv + 4 * dv + 2 * 128 * 4)
    rb = rows
    while rb * per_row > ROW_VMEM_BYTES and rb % 16 == 0:
        rb //= 2
    return rb


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("window", "softcap", "interpret",
                                             "scale", "v_width"))
def chunked_prefill_attention(
    q,            # (B, S, H, Dh) — rope'd chunk queries
    pool_k,       # (P, ps, KV, Dh), or a (L, P, ps, KV*Dh) stack with layer
    pool_v,       # like pool_k; None for a latent pool
    page_table,   # (B, MP) physical page ids per lane
    p0,           # (B,) absolute position of chunk row 0
    true_len,     # (B,) real chunk lengths (bucketed input)
    layer=None,   # () int: the layer of a stacked pool to attend
    *,
    window: int = 0,
    softcap: float = 0.0,
    interpret: bool = None,
    scale=None,
    v_width=None,
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
    stack of one (``paged_attention``'s rule). Latent pages (``pool_v``
    None), ``scale`` and ``v_width`` as in ``paged_attention``: the output
    is then ``(B, S, H, v_width)``."""
    if interpret is None:
        interpret = _on_cpu()
    pool_k, pool_v, lyr = as_layer_stack(pool_k, pool_v, layer)
    b, s, h, dh = q.shape
    ps = pool_k.shape[2]
    kvh = pool_k.shape[3] // dh
    g = h // kvh
    dv = dh if pool_v is not None else v_width
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
        scale=scale, dv=dv, row_block=row_block(s * g, kvh, dh, dv),
    )
    return out.reshape(b, kvh, s, g, dv).transpose(0, 2, 1, 3, 4).reshape(
        b, s, h, dv
    )
