"""Blockwise causal flash attention (prefill/training) — Pallas TPU kernel.

TPU adaptation of FlashAttention: the online-softmax accumulator lives in
VMEM scratch that persists across the innermost (KV) grid dimension — TPU
grids execute sequentially, so the scratch carries (m, l, acc) the way a CUDA
implementation carries them in registers/SMEM. Block shapes are MXU-aligned
(q/kv blocks of 128 × head_dim) and all masking is position-based so the same
kernel serves full-causal, sliding-window, and padded sequences.

Grid: (batch, n_q_blocks, n_kv_blocks) — KV innermost. The q/K/V blocks
are ``(1, block, heads, Dh)``: their trailing ``(heads, Dh)`` equals the
arrays', which is what Mosaic's tiling rule asks of the last two block
dims, and the kernel loops over the query heads inside (a static unroll).
GQA is handled in that loop: query head h reads kv head h // group_size of
the same K/V block, so no K/V replication is materialized in HBM. Query
positions take a trailing unit axis, ``(B, S, 1)``, and slot positions and
validity a unit middle axis, ``(B, 1, T)``, so their blocks satisfy the
same rule and arrive as a column and a row of the logit tile. The output
is written head-major, ``(B, H, S, Dh)`` (Mosaic loads one head of a
``(BQ, H, Dh)`` block but cannot store one), and the wrapper puts the heads
back in place.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    qpos_ref, kvpos_ref, valid_ref,       # positions / validity blocks
    q_ref, k_ref, v_ref,                   # tensor blocks
    o_ref,                                  # output block
    acc_ref, m_ref, l_ref,                  # VMEM scratch (persist over ik)
    *, nk: int, g: int, window: int, softcap: float, scale: float,
):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    qp = qpos_ref[0]                               # (BQ, 1)
    kp = kvpos_ref[0]                              # (1, BK)
    ok = valid_ref[0]
    mask = (kp <= qp) & (ok != 0)                  # (BQ, BK)
    if window > 0:
        mask = mask & (qp - kp < window)

    for h in range(q_ref.shape[2]):
        q = q_ref[0, :, h, :].astype(jnp.float32)       # (BQ, Dh)
        k = k_ref[0, :, h // g, :].astype(jnp.float32)  # (BK, Dh)
        v = v_ref[0, :, h // g, :].astype(jnp.float32)  # (BK, Dh)

        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                  # (BQ, BK)
        if softcap > 0.0:
            logits = softcap * jnp.tanh(logits / softcap)
        logits = jnp.where(mask, logits, NEG_INF)

        m_prev = m_ref[h]                          # (BQ, 1)
        l_prev = l_ref[h]
        m_cur = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)                # (BQ, BK)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[h] = m_new
        l_ref[h] = l_new

    @pl.when(ik == nk - 1)
    def _finalize():
        for h in range(q_ref.shape[2]):
            l = l_ref[h]
            l = jnp.where(l == 0.0, 1.0, l)        # fully-masked rows -> 0
            o_ref[0, h] = (acc_ref[h] / l).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jnp.ndarray,        # (B, S, H, Dh)
    k: jnp.ndarray,        # (B, T, KV, Dh)
    v: jnp.ndarray,
    q_pos: jnp.ndarray,    # (B, S) int32
    kv_pos: jnp.ndarray,   # (B, T) int32
    kv_valid: jnp.ndarray, # (B, T) bool/int32
    *,
    window: int = 0,
    softcap: float = 0.0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    b, s, h, dh = q.shape
    t, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    assert s % block_q == 0 and t % block_k == 0, (s, t, block_q, block_k)
    nq, nk = s // block_q, t // block_k
    scale = 1.0 / (dh ** 0.5)

    grid = (b, nq, nk)
    kern = functools.partial(
        _flash_kernel, nk=nk, g=g, window=window, softcap=softcap, scale=scale
    )
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, 1), lambda bi, qi, ki: (bi, qi, 0)),
            pl.BlockSpec((1, 1, block_k), lambda bi, qi, ki: (bi, 0, ki)),
            pl.BlockSpec((1, 1, block_k), lambda bi, qi, ki: (bi, 0, ki)),
            pl.BlockSpec((1, block_q, h, dh), lambda bi, qi, ki: (bi, qi, 0, 0)),
            pl.BlockSpec((1, block_k, kvh, dh), lambda bi, qi, ki: (bi, ki, 0, 0)),
            pl.BlockSpec((1, block_k, kvh, dh), lambda bi, qi, ki: (bi, ki, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, h, block_q, dh), lambda bi, qi, ki: (bi, 0, qi, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, s, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((h, block_q, dh), jnp.float32),
            pltpu.VMEM((h, block_q, 1), jnp.float32),
            pltpu.VMEM((h, block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q_pos.astype(jnp.int32).reshape(b, s, 1),
      kv_pos.astype(jnp.int32).reshape(b, 1, t),
      kv_valid.astype(jnp.int32).reshape(b, 1, t), q, k, v
      ).transpose(0, 2, 1, 3)
