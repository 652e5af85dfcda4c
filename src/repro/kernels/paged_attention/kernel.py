"""Fused paged-attention decode — one query token against a paged KV pool
(Pallas TPU).

The paged serving path (`serving/paged_kv.py`) stores each sequence as a
*page table* over one shared physical pool, ``(P, page_size, KV, Dh)`` per
layer. The gather-materialize fallback linearizes that table into a
``(B, MP·page_size, KV, Dh)`` copy before attending — O(max_len) HBM
traffic per token per lane, even for a 40-token session. This kernel
attends *through* the table instead (vLLM-style paged attention): the page
table and per-lane page bounds are scalar-prefetch operands, so the K/V
``BlockSpec`` index maps dereference ``table[b, p]`` directly and each grid
step DMAs one physical page from the pool into VMEM — no linearized copy
ever exists.

Grid: ``(batch, MP)`` — page-blocks innermost. Each step's K/V block is one
whole page, ``(1, ps, KV, Dh)``: the block's trailing ``(KV, Dh)`` equals
the pool's, which is what Mosaic's tiling rule asks of the last two block
dims, and every head is served by that single DMA. The kernel loops over
the KV heads inside (a static unroll); each head's online softmax carries
``(m, l, acc)`` in VMEM scratch across the page dimension exactly like the
dense flash-decode kernel, and all G query heads of one KV head share the
head's page slice (GQA grouping). Two raggedness levers keep the cost
proportional to *actual* tokens:

- steps with ``p >= bound[b]`` (``bound = ceil(kv_len / page_size)``) skip
  all compute via ``pl.when``, and their index maps clamp to the lane's
  last real page — consecutive grid steps that map to the same block are
  not re-fetched, so inactive tail pages and the scratch page are never
  touched for an active lane;
- the wrapper (ops.py) can additionally trim the table width itself
  (``max_pages``) when the caller knows a tighter static bound.

Masking is positional (``0 <= kv_pos <= q_pos``, optional sliding window,
optional logit softcap), identical to the dense decode kernel, with one
deliberate difference: rows with *no* valid key (an empty lane) produce
exact zeros rather than a uniform average over whatever the grid happened
to visit — the fallback's output for such rows is garbage-by-design and
unread, and zeros are the only bound-independent answer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

_NT = (((1,), (1,)), ((), ()))   # (M, Dh) x (N, Dh) -> (M, N)
_NN = (((1,), (0,)), ((), ()))   # (M, K) x (K, N) -> (M, N)


def _attend_page(
    q_ref, k_ref, v_ref, mask, acc_ref, m_ref, l_ref,
    *, kvh: int, softcap: float, scale: float, lane=(),
):
    """One lane x one page of the online-softmax recurrence, every KV head.
    ``q_ref`` is the lane's ``(1, KV, G, Dh)`` block, ``k_ref``/``v_ref``
    the page's ``(1, ps, KV, Dh)`` block and ``acc/m/l_ref[lane]`` the
    lane's ``(KV, G, ·)`` state (``lane`` prefixes the index, not a ref
    view: Mosaic cannot slice the unit lane axis of the m/l scratch);
    ``mask`` is the slot mask, ``(1, ps)`` or one row per query row. ``p``
    is zeroed on masked slots (not just NEG_INF logits), so a row with no
    valid key accumulates ``l == 0`` and finalizes to exact zeros
    independent of how many pages were visited."""
    for h in range(kvh):
        st = lane + (h,)
        q = q_ref[0, h].astype(jnp.float32)             # (G, Dh)
        k = k_ref[0, :, h, :].astype(jnp.float32)       # (ps, Dh)
        v = v_ref[0, :, h, :].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, _NT, preferred_element_type=jnp.float32
        ) * scale                                       # (G, ps)
        if softcap > 0.0:
            logits = softcap * jnp.tanh(logits / softcap)
        hmask = jnp.broadcast_to(mask, logits.shape)
        logits = jnp.where(hmask, logits, NEG_INF)
        m_prev, l_prev = m_ref[st], l_ref[st]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new) * hmask.astype(jnp.float32)
        l_ref[st] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[st] = acc_ref[st] * alpha + jax.lax.dot_general(
            p, v, _NN, preferred_element_type=jnp.float32
        )
        m_ref[st] = m_new


def _paged_decode_kernel(
    table_ref, bound_ref, qpos_ref,     # scalar prefetch (SMEM)
    kvpos_ref, q_ref, k_ref, v_ref,     # tensor blocks
    *refs,                              # [acc0, m0, l0,] o | scratch acc, m, l
    n_pb: int, kvh: int, window: int, softcap: float, scale: float,
    start: int = 0, has_init: bool = False,
):
    """One lane x one page of online softmax, every KV head. With
    ``start``/``has_init`` this is the *suffix* pass of the shared-prefix
    split: the grid walks pages [start, MP) only, and the softmax state is
    seeded from the shared pass's (acc, m, l) stats instead of the empty
    state — the exact continuation of the single-pass recurrence, so the
    two-pass result is identical to walking all pages in one pass."""
    if has_init:
        acc0_ref, m0_ref, l0_ref, o_ref, acc_ref, m_ref, l_ref = refs
    else:
        o_ref, acc_ref, m_ref, l_ref = refs
    bi = pl.program_id(0)
    ip = pl.program_id(1)

    @pl.when(ip == 0)
    def _init():
        if has_init:
            acc_ref[...] = acc0_ref[0]
            m_ref[...] = m0_ref[0]
            l_ref[...] = l0_ref[0]
        else:
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(ip + start < bound_ref[bi])
    def _compute():
        qp = qpos_ref[bi]
        kp = kvpos_ref[0, 0]                            # (1, ps)
        mask = (kp >= 0) & (kp <= qp)
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


def _shared_prefix_kernel(
    pages_ref, qpos_ref,                # scalar prefetch (SMEM)
    q_ref, k_ref, v_ref,                # tensor blocks
    acc_o, m_o, l_o,                    # outputs: softmax stats, all lanes
    acc_ref, m_ref, l_ref,              # VMEM scratch (persist over ip)
    *, n_sp: int, kvh: int, ps: int, window: int, softcap: float,
    scale: float,
):
    """Shared-prefix pass: every page in ``pages`` (a run of physical pages
    holding positions [0, n_sp*ps), shared by the whole batch) is DMA'd
    ONCE and attended by all B lanes — the lane axis is innermost in the
    grid and the page's block index does not change along it, so the
    pipeline fetches the page once and every lane reads it from VMEM: K/V
    traffic is O(unique pages), not O(B * pages). Emits the per-lane
    online-softmax partial state (acc, m, l) for the suffix pass to
    continue from. Pages are full and resident by contract (the caller only
    passes a run of refcount-held full pages), so the only masking is
    causal/window — a lane whose query sits inside the run simply masks the
    tail and gets its complete answer here.

    Each grid step runs the per-lane kernel's step verbatim (one lane, one
    page, every head) rather than one product over all lanes' rows: a
    flattened ``(B*G, Dh)`` product rounds differently from B separate
    ``(G, Dh)`` ones at G == 1, and the two-pass result must stay
    bit-identical to the single pass."""
    ip = pl.program_id(0)
    bi = pl.program_id(1)

    @pl.when(ip == 0)
    def _init():
        acc_ref[bi] = jnp.zeros(acc_ref.shape[1:], jnp.float32)
        m_ref[bi] = jnp.full(m_ref.shape[1:], NEG_INF, jnp.float32)
        l_ref[bi] = jnp.zeros(l_ref.shape[1:], jnp.float32)

    qp = qpos_ref[bi]
    # slot == position inside the run: page ip holds [ip*ps, (ip+1)*ps)
    kp = ip * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
    mask = kp <= qp                                    # causal
    if window > 0:
        mask = mask & (qp - kp < window)
    _attend_page(
        q_ref, k_ref, v_ref, mask, acc_ref, m_ref, l_ref,
        kvh=kvh, softcap=softcap, scale=scale, lane=(bi,),
    )
    # the lane's output block is written back after every step (its index
    # changes along the lane axis), so it always holds the state so far
    acc_o[0] = acc_ref[bi]
    m_o[0] = m_ref[bi]
    l_o[0] = l_ref[bi]


def shared_prefix_pallas(
    q: jnp.ndarray,             # (B, KV, G, Dh) — reshaped + rope'd by ops.py
    pool_k: jnp.ndarray,        # (P, page_size, KV, Dh)
    pool_v: jnp.ndarray,
    shared_pages: jnp.ndarray,  # (S,) int32 physical page ids, positions [0, S*ps)
    q_pos: jnp.ndarray,         # (B,) int32
    *,
    window: int = 0,
    softcap: float = 0.0,
    interpret: bool = False,
):
    """Partial-softmax stats of all lanes over the shared run:
    ``(acc, m, l)`` each ``(B, KV, G, ·)`` float32."""
    b, kvh, g, dh = q.shape
    ps = pool_k.shape[1]
    n_sp = shared_pages.shape[0]
    scale = 1.0 / (dh ** 0.5)

    def page_map(ip, bi, pages, qpos):
        return (pages[ip], 0, 0, 0)

    def lane_map(ip, bi, pages, qpos):
        return (bi, 0, 0, 0)

    # VMEM holds every lane's softmax state in scratch, but only one lane's
    # q and output blocks: the pass stays within the scoped VMEM limit at
    # a few dozen lanes
    stats = lambda last: pl.BlockSpec((1, kvh, g, last), lane_map)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_sp, b),
        in_specs=[
            pl.BlockSpec((1, kvh, g, dh), lane_map),
            pl.BlockSpec((1, ps, kvh, dh), page_map),
            pl.BlockSpec((1, ps, kvh, dh), page_map),
        ],
        out_specs=[stats(dh), stats(1), stats(1)],
        scratch_shapes=[
            pltpu.VMEM((b, kvh, g, dh), jnp.float32),
            pltpu.VMEM((b, kvh, g, 1), jnp.float32),
            pltpu.VMEM((b, kvh, g, 1), jnp.float32),
        ],
    )
    kern = functools.partial(
        _shared_prefix_kernel, n_sp=n_sp, kvh=kvh, ps=ps, window=window,
        softcap=softcap, scale=scale,
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, kvh, g, dh), jnp.float32),
            jax.ShapeDtypeStruct((b, kvh, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, kvh, g, 1), jnp.float32),
        ],
        interpret=interpret,
    )(
        shared_pages.astype(jnp.int32), q_pos.astype(jnp.int32),
        q, pool_k, pool_v,
    )


def paged_attention_pallas(
    q: jnp.ndarray,           # (B, KV, G, Dh) — reshaped + rope'd by ops.py
    pool_k: jnp.ndarray,      # (P, page_size, KV, Dh) — shared pool, one layer
    pool_v: jnp.ndarray,
    page_table: jnp.ndarray,  # (B, MP) int32 physical page ids per lane
    page_bound: jnp.ndarray,  # (B,) int32 — ceil(kv_len / ps), in [1, MP]
    q_pos: jnp.ndarray,       # (B,) int32 absolute position of the query
    kv_pos: jnp.ndarray,      # (B, MP, page_size) int32, -1 = empty slot
    *,
    window: int = 0,
    softcap: float = 0.0,
    interpret: bool = False,
    start: int = 0,           # first page-block the grid visits
    init=None,                # optional (acc, m, l) stats from the shared pass
) -> jnp.ndarray:
    b, kvh, g, dh = q.shape
    ps = pool_k.shape[1]
    mp = page_table.shape[1]
    scale = 1.0 / (dh ** 0.5)
    assert 0 <= start < mp, (start, mp)
    has_init = init is not None

    def page_map(bi, ip, table, bound, qpos):
        # beyond-bound steps re-map to the lane's last real page: the block
        # index repeats, so the pipeline skips the DMA and the scratch page
        # (table padding) is never dereferenced for an active lane
        return (table[bi, jnp.minimum(ip + start, bound[bi] - 1)], 0, 0, 0)

    def kvpos_map(bi, ip, table, bound, qpos):
        return (bi, jnp.minimum(ip + start, bound[bi] - 1), 0, 0)

    def lane_map(bi, ip, table, bound, qpos):
        return (bi, 0, 0, 0)

    in_specs = [
        # a unit axis before the slots makes the block's last two dims
        # (1, ps) equal the array's — Mosaic's tiling rule for blocks
        pl.BlockSpec((1, 1, 1, ps), kvpos_map),
        pl.BlockSpec((1, kvh, g, dh), lane_map),
        pl.BlockSpec((1, ps, kvh, dh), page_map),
        pl.BlockSpec((1, ps, kvh, dh), page_map),
    ]
    if has_init:
        in_specs += [
            pl.BlockSpec((1, kvh, g, dh), lane_map),
            pl.BlockSpec((1, kvh, g, 1), lane_map),
            pl.BlockSpec((1, kvh, g, 1), lane_map),
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, mp - start),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kvh, g, dh), lane_map),
        scratch_shapes=[
            pltpu.VMEM((kvh, g, dh), jnp.float32),
            pltpu.VMEM((kvh, g, 1), jnp.float32),
            pltpu.VMEM((kvh, g, 1), jnp.float32),
        ],
    )
    kern = functools.partial(
        _paged_decode_kernel, n_pb=mp - start, kvh=kvh, window=window,
        softcap=softcap, scale=scale, start=start, has_init=has_init,
    )
    args = [
        page_table.astype(jnp.int32), page_bound.astype(jnp.int32),
        q_pos.astype(jnp.int32), kv_pos.astype(jnp.int32).reshape(b, mp, 1, ps),
        q, pool_k, pool_v,
    ]
    if has_init:
        args += list(init)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, dh), q.dtype),
        interpret=interpret,
    )(*args)
