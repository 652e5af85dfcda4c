"""Flash-decode — one query token against a long KV cache (Pallas TPU).

The decode hot spot of a serving system: q is (B, 1, H, Dh) while the cache
is (B, T, KV, Dh) with T up to 512k. The kernel blocks over the KV length
with online softmax in VMEM scratch. All G query heads of one KV head are
processed together — one (G, BK) logit tile per step keeps the MXU busy at
GQA group sizes ≥ 8 and amortizes the K/V block loads across the group
(HBM-bandwidth-bound regime, so K/V bytes are the roofline currency).

Grid: (batch, n_kv_blocks) — KV innermost. Each step's K/V block is
``(1, BK, KV, Dh)``: its trailing ``(KV, Dh)`` equals the cache's, which is
what Mosaic's tiling rule asks of the last two block dims, and one DMA
serves every head; the kernel loops over the KV heads inside (a static
unroll). The query position is a scalar-prefetch operand, and the slot
positions and validity take a unit middle axis, ``(B, 1, T)``, so their
``(1, 1, BK)`` blocks satisfy the same rule.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(
    qpos_ref,                                       # scalar prefetch (SMEM)
    kvpos_ref, valid_ref,
    q_ref, k_ref, v_ref,
    o_ref,
    acc_ref, m_ref, l_ref,
    *, nk: int, kvh: int, window: int, softcap: float, scale: float,
):
    bi = pl.program_id(0)
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    qp = qpos_ref[bi]                               # scalar position
    kp = kvpos_ref[0]                               # (1, BK)
    ok = valid_ref[0]
    mask = (kp <= qp) & (ok != 0)
    if window > 0:
        mask = mask & (qp - kp < window)

    for h in range(kvh):
        q = q_ref[0, h].astype(jnp.float32)         # (G, Dh)
        k = k_ref[0, :, h, :].astype(jnp.float32)   # (BK, Dh)
        v = v_ref[0, :, h, :].astype(jnp.float32)

        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                   # (G, BK)
        if softcap > 0.0:
            logits = softcap * jnp.tanh(logits / softcap)
        logits = jnp.where(jnp.broadcast_to(mask, logits.shape), logits, NEG_INF)

        m_prev, l_prev = m_ref[h], l_ref[h]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)
        l_ref[h] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[h] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_attention_pallas(
    q: jnp.ndarray,        # (B, KV, G, Dh) — reshaped by ops.py
    k: jnp.ndarray,        # (B, T, KV, Dh)
    v: jnp.ndarray,
    q_pos: jnp.ndarray,    # (B, 1)
    kv_pos: jnp.ndarray,   # (B, T)
    kv_valid: jnp.ndarray, # (B, T)
    *,
    window: int = 0,
    softcap: float = 0.0,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    b, kvh, g, dh = q.shape
    t = k.shape[1]
    assert t % block_k == 0, (t, block_k)
    nk = t // block_k
    scale = 1.0 / (dh ** 0.5)
    kern = functools.partial(
        _decode_kernel, nk=nk, kvh=kvh, window=window, softcap=softcap,
        scale=scale,
    )
    slots = lambda bi, ki, qpos: (bi, 0, ki)         # noqa: E731
    lane = lambda bi, ki, qpos: (bi, 0, 0, 0)        # noqa: E731
    block = lambda bi, ki, qpos: (bi, ki, 0, 0)      # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_k), slots),
            pl.BlockSpec((1, 1, block_k), slots),
            pl.BlockSpec((1, kvh, g, dh), lane),
            pl.BlockSpec((1, block_k, kvh, dh), block),
            pl.BlockSpec((1, block_k, kvh, dh), block),
        ],
        out_specs=pl.BlockSpec((1, kvh, g, dh), lane),
        scratch_shapes=[
            pltpu.VMEM((kvh, g, dh), jnp.float32),
            pltpu.VMEM((kvh, g, 1), jnp.float32),
            pltpu.VMEM((kvh, g, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, dh), q.dtype),
        interpret=interpret,
    )(q_pos.astype(jnp.int32).reshape(b),
      kv_pos.astype(jnp.int32).reshape(b, 1, t),
      kv_valid.astype(jnp.int32).reshape(b, 1, t), q, k, v)
