"""Jit'd wrapper for paged decode attention: reshapes GQA heads, derives
per-lane page bounds from the query position, optionally trims the table
width to a static cap."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .kernel import paged_attention_pallas, shared_prefix_pallas
from .ref import paged_attention_ref


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def as_layer_stack(pool_k, pool_v, layer):
    """``(pool_k, pool_v, layer)`` as the kernels take them: ``(L, P, ps,
    KV*Dh)`` stacks and a ``(1,)`` int32 layer index. Without a layer the
    pool is one layer's, ``(P, ps, KV, Dh)``, and becomes a stack of one
    read at layer 0. A stack already flat passes through unchanged; a
    latent pool comes without ``pool_v`` (None), and stays without."""
    if layer is None:
        pool_k, layer = pool_k[None], 0
        pool_v = None if pool_v is None else pool_v[None]
    flat = lambda a: a.reshape(a.shape[:3] + (-1,))  # noqa: E731
    return (flat(pool_k), None if pool_v is None else flat(pool_v),
            jnp.asarray(layer, jnp.int32).reshape(1))


@functools.partial(
    jax.jit, static_argnames=("window", "softcap", "max_pages", "interpret",
                              "scale", "v_width")
)
def paged_attention(
    q,            # (B, 1, H, Dh) — rope'd query token
    pool_k,       # (P, ps, KV, Dh), or a (L, P, ps, KV*Dh) stack with layer
    pool_v,       # like pool_k; None for a latent pool
    page_table,   # (B, MP) physical page ids per lane
    q_pos,        # (B, 1) absolute position of the query token
    kv_pos,       # (B, MP*page_size) absolute positions per virtual slot
    shared_pages=None,  # (S,) page ids every lane's table starts with
    layer=None,   # () int: the layer of a stacked pool to attend
    *,
    window: int = 0,
    softcap: float = 0.0,
    max_pages: Optional[int] = None,
    interpret: bool = None,
    scale: Optional[float] = None,
    v_width: Optional[int] = None,
):
    """q vs a paged KV pool -> (B, 1, H, Dh), attending through the table.

    The per-lane page bound ``ceil((q_pos + 1) / page_size)`` relies on the
    layout invariant of the paged pool (slot index == absolute position for
    valid slots), under which no key beyond the query's own page can pass
    the causal mask. ``max_pages`` additionally trims the *static* table
    width when the caller knows every lane's bound — e.g. the batched
    server's page-width bucketing — which shrinks the kernel grid itself.

    ``shared_pages`` enables the cross-session shared-prefix split
    (cascade/hydragen-style): the caller asserts that pages ``[0, S)`` of
    EVERY lane's table are exactly ``shared_pages`` (full, resident pages
    holding positions ``[0, S*page_size)``). Those pages are then attended
    once per unique page for the whole batch (one DMA serves all B lanes)
    and the per-lane kernel walks only pages ``[S, MP)``, seeded with the
    shared pass's online-softmax stats — per-step K/V traffic drops from
    O(B·kv_len) to O(unique_pages + B·suffix). The two-pass result is the
    exact continuation of the single-pass softmax recurrence.

    With ``layer``, the pool is a group's stack, ``(L, P, page_size,
    KV*Dh)`` as ``init_paged_pool`` makes it, read in place at that layer:
    the layer scan of a decode step hands the kernel the stack it updates,
    never a slice of it. Without, it is one layer's ``(P, page_size, KV,
    Dh)`` pool, read as a stack of one at layer 0.

    Latent pages (``pool_v`` None): ``pool_k`` holds one row per token that
    every query head attends (its width is the query's, so ``KV == 1``),
    and the value is the row's leading ``v_width`` lanes; the output is
    ``(B, 1, H, v_width)``. ``scale`` replaces the softmax scale
    ``1/sqrt(Dh)``.
    """
    if interpret is None:
        interpret = _on_cpu()
    pool_k, pool_v, lyr = as_layer_stack(pool_k, pool_v, layer)
    b, _, h, dh = q.shape
    ps = pool_k.shape[2]
    kvh = pool_k.shape[3] // dh
    g = h // kvh
    dv = dh if pool_v is not None else v_width
    mp = page_table.shape[1]
    if max_pages is not None and max_pages < mp:
        mp = max(1, max_pages)
        page_table = page_table[:, :mp]
        kv_pos = kv_pos[:, : mp * ps]
    qp = q_pos.reshape(b).astype(jnp.int32)
    bound = jnp.clip((qp + ps) // ps, 1, mp)   # ceil((qp+1)/ps), junk-safe
    qr = q.reshape(b, kvh, g, dh)
    start, init = 0, None
    if shared_pages is not None and shared_pages.shape[0] > 0:
        # the suffix grid must keep >= 1 page per lane (the lane's own tail
        # page is exclusively held, hence never part of the shared run)
        start = min(int(shared_pages.shape[0]), mp - 1)
        if start > 0:
            init = shared_prefix_pallas(
                qr, pool_k, pool_v, shared_pages[:start], qp, lyr,
                window=window, softcap=softcap, interpret=interpret,
                scale=scale, dv=dv,
            )
    out = paged_attention_pallas(
        qr, pool_k, pool_v, page_table, bound, qp,
        kv_pos.reshape(b, mp, ps), lyr,
        window=window, softcap=softcap, interpret=interpret,
        start=start, init=init, scale=scale, dv=dv,
    )
    return out.reshape(b, 1, h, dv)
