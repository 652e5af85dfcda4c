"""Chunked paged prefill attention — an S-token prompt chunk against a
paged KV pool (Pallas TPU).

The chunked admission path (`serving/chunked_prefill.py`) writes each
prefill chunk's rotated K/V straight into allocator pages *before*
attention (the same scatter-then-attend trick as the dense
``attention_append``), so by the time this kernel runs, the pool holds the
lane's full causal prefix [0, p0 + true_len) — prior chunks, shared-prefix
pages, and the current chunk alike. The kernel then attends *through* the
page table exactly like the paged decode kernel: the table and per-lane
page bounds are scalar-prefetch operands, the K/V BlockSpec index maps
dereference ``table[b, p]`` directly, and each grid step DMAs one physical
page into VMEM — no dense ``max_len``-width intermediate ever exists.

Grid: ``(batch, row blocks, MP)`` — page-blocks innermost, as in the
decode kernel, with the same whole-page K/V blocks of one layer of the
group's ``(L, P, ps, KV*Dh)`` stack (the layer a scalar-prefetch operand,
so the prefill step updates the stack in place and never slices it) and
the same per-page body (``paged_attention.kernel._attend_page``, a static
loop over the KV heads; latent pages as there). The only difference is the
query block: S chunk rows × G query heads share each page load, carried
per KV head as one ``(S*G, Dh)`` block with ``(m, l, acc)`` online-softmax
scratch persisting across the page dimension. Where that block would
outgrow VMEM (latent attention: 16 heads of 576 lanes a chunk row), its
rows are split into row blocks, each walking the pages on its own.

Masking needs no ``kv_pos`` input at all: chunked prefill preserves the
layout invariant (slot index == absolute position, written contiguously),
so slot ``t`` of the gathered view is valid exactly when ``t < p0 +
true_len`` — and for query row ``r`` the causal mask ``t <= p0 + r`` is
strictly tighter for every row that is read (``r < true_len``). Padded
bucket rows (``r >= true_len``) attend garbage and produce garbage — their
K/V scatter was dropped and their output row is never read, same
convention as the dense bucketed prefill.

Beyond-bound grid steps (``p >= bound[b] = ceil((p0+true_len)/ps)``) skip
compute via ``pl.when`` and clamp their index maps to the lane's last real
page, so the DMA pipeline never re-fetches — chunk cost is O(prefix
actually covered), not O(table width).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..paged_attention.kernel import NEG_INF, _attend_page, page_block


def _chunked_prefill_kernel(
    table_ref, bound_ref, p0_ref, layer_ref,  # scalar prefetch (SMEM)
    row_ref, q_ref, k_ref,              # tensor blocks
    *refs,                              # [v,] o | scratch acc, m, l
    n_pb: int, kvh: int, ps: int, window: int, softcap: float,
    scale: float, latent: bool = False,
):
    """One lane x one row block x one page, every KV head: the block's
    query rows of online softmax against the page's ps keys, causally
    masked per chunk row (latent pages: no V operand)."""
    v_ref = None if latent else refs[0]
    o_ref, acc_ref, m_ref, l_ref = refs if latent else refs[1:]
    bi = pl.program_id(0)
    ip = pl.program_id(2)

    @pl.when(ip == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(ip < bound_ref[bi])
    def _compute():
        # row r of the chunk queries absolute position p0 + r // G; page ip
        # holds absolute positions [ip*ps, (ip+1)*ps) — the layout invariant
        qp = p0_ref[bi] + row_ref[...]                  # (S*G, 1)
        kp = ip * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
        mask = kp <= qp                                 # (S*G, ps)
        if window > 0:
            mask = mask & (qp - kp < window)
        _attend_page(
            q_ref, k_ref, v_ref, mask, acc_ref, m_ref, l_ref,
            kvh=kvh, softcap=softcap, scale=scale,
        )

    @pl.when(ip == n_pb - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def chunked_prefill_pallas(
    q: jnp.ndarray,           # (B, KV, S*G, Dh) — reshaped + rope'd by ops.py
    pool_k: jnp.ndarray,      # (L, P, page_size, KV*Dh) — post-scatter stack
    pool_v,                   # like pool_k, or None for latent pages
    page_table: jnp.ndarray,  # (B, MP) int32 physical page ids per lane
    page_bound: jnp.ndarray,  # (B,) int32 — ceil((p0+true_len)/ps), in [1, MP]
    p0: jnp.ndarray,          # (B,) int32 absolute position of chunk row 0
    layer: jnp.ndarray,       # (1,) int32 — the layer of the stack to read
    *,
    g: int,
    window: int = 0,
    softcap: float = 0.0,
    interpret: bool = False,
    scale: float = None,      # softmax scale; 1/sqrt(Dh) by default
    dv: int = None,           # latent pages (pool_v None): value lanes
    row_block: int = None,    # query rows a grid step carries (all by default)
) -> jnp.ndarray:
    b, kvh, sg, dh = q.shape
    ps, row = pool_k.shape[2], pool_k.shape[3]
    mp = page_table.shape[1]
    latent = pool_v is None
    dv = dv if latent else dh
    scale = 1.0 / (dh ** 0.5) if scale is None else scale
    rb = row_block or sg
    assert sg % rb == 0, (sg, rb)

    def page_map(bi, ri, ip, table, bound, p0_, lyr):
        # beyond-bound steps re-map to the lane's last real page: the block
        # index repeats, so the pipeline skips the DMA and table padding
        # (the scratch page) is never dereferenced for an active lane
        return (lyr[0], table[bi, jnp.minimum(ip, bound[bi] - 1)], 0, 0)

    def lane_map(bi, ri, ip, table, bound, p0_, lyr):
        return (bi, 0, ri, 0)

    def rows_map(bi, ri, ip, table, bound, p0_, lyr):
        return (ri, 0)

    pools = [pool_k] if latent else [pool_k, pool_v]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, sg // rb, mp),
        in_specs=[
            pl.BlockSpec((rb, 1), rows_map),
            pl.BlockSpec((1, kvh, rb, dh), lane_map),
        ] + [page_block(ps, row, page_map) for _ in pools],
        out_specs=pl.BlockSpec((1, kvh, rb, dv), lane_map),
        scratch_shapes=[
            pltpu.VMEM((kvh, rb, dv), jnp.float32),
            pltpu.VMEM((kvh, rb, 1), jnp.float32),
            pltpu.VMEM((kvh, rb, 1), jnp.float32),
        ],
    )
    kern = functools.partial(
        _chunked_prefill_kernel,
        n_pb=mp, kvh=kvh, ps=ps, window=window, softcap=softcap, scale=scale,
        latent=latent,
    )
    # chunk row offset of each query row (rows are chunk-major, G heads each)
    rows = (jnp.arange(sg, dtype=jnp.int32) // g)[:, None]
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, sg, dv), q.dtype),
        interpret=interpret,
        name="chunked_prefill",
    )(
        page_table.astype(jnp.int32), page_bound.astype(jnp.int32),
        p0.astype(jnp.int32), layer.astype(jnp.int32), rows, q, *pools,
    )
