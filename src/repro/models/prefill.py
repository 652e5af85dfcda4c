"""Prefill: full-sequence forward that also seeds the decode caches.

Mirrors transformer.forward_full group by group; each scan body additionally
emits this layer's rotated K/V (or SSM final/conv state), which the scan
stacks into the (L, B, ...) cache layout decode_step consumes.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from .attention import attention_append, attention_chunk_paged, attention_forward
from .cache import (
    Cache,
    append_kv_pos,
    gather_pool_views,
    prefill_kv_pos,
    ring_from_prefill,
)
from .config import ModelConfig
from .layers import dtype_of, embed_tokens, mlp_forward, rms_norm, unembed
from .mla import mla_chunk_paged
from .moe import N_STATS, moe_serve
from .ssm import ssm_forward
from .transformer import GroupSpec, Params, ffn_serve, layer_groups, scan_or_unroll


def _dense_block_prefill(bp, x, positions, cfg, window, seq_valid):
    h, k, v = attention_forward(
        bp["attn"], rms_norm(x, bp["norm1"], cfg.norm_eps), positions, cfg,
        window=window, seq_valid=seq_valid, return_kv=True,
    )
    x = x + h
    x = x + mlp_forward(bp["mlp"], rms_norm(x, bp["norm2"], cfg.norm_eps), cfg)
    return x, k, v


def _moe_block_prefill(bp, x, positions, cfg, window, seq_valid):
    h, k, v = attention_forward(
        bp["attn"], rms_norm(x, bp["norm1"], cfg.norm_eps), positions, cfg,
        window=window, seq_valid=seq_valid, return_kv=True,
    )
    x = x + h
    m, _ = moe_serve(bp["moe"], rms_norm(x, bp["norm2"], cfg.norm_eps), cfg)
    return x + m, k, v


def _pack_attn_cache(
    k: jnp.ndarray,  # (L,B,S,KV,Dh) prefill keys
    v: jnp.ndarray,
    slots: int,
    ring: bool,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Place prefill K/V into a cache with `slots` slots (+ kv_pos)."""
    l, b, s = k.shape[0], k.shape[1], k.shape[2]
    if ring and slots < s:
        pack = jax.vmap(lambda a: ring_from_prefill(a, slots))
        ck, cv = pack(k), pack(v)
    elif slots == s:
        ck, cv = k, v
    else:
        pad = [(0, 0), (0, 0), (0, slots - s), (0, 0), (0, 0)]
        ck, cv = jnp.pad(k, pad), jnp.pad(v, pad)
    kv_pos = prefill_kv_pos(b, slots, s, ring and slots < s)
    return ck, cv, kv_pos


def prefill(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,                       # (B,S) or (B,S,K)
    max_len: int,
    positions: Optional[jnp.ndarray] = None,
    patch_embeds: Optional[jnp.ndarray] = None,
    seq_valid: Optional[jnp.ndarray] = None,
    true_len: Optional[jnp.ndarray] = None,    # (B,) real lengths (bucketed input)
) -> Tuple[jnp.ndarray, List[Cache], jnp.ndarray]:
    """Returns (last-position logits (B,V...), caches, next_pos (B,)).
    max_len = slot count for full caches (prefill len + decode budget).

    With true_len, the input is right-padded to a bucket length: padded
    positions are masked out of attention and the caches, and the returned
    logits/next_pos refer to position true_len-1. (Supported for full-cache
    dense/moe groups — the serving engine's bucketing path.) GQA models
    only: latent attention prefills into paged pools
    (:func:`prefill_chunk_paged`)."""
    assert not cfg.is_mla, "latent attention is served from paged pools"
    b, s = tokens.shape[0], tokens.shape[1]
    if true_len is not None:
        idx = jnp.arange(s, dtype=jnp.int32)
        seq_valid = idx[None, :] < true_len[:, None]
    if positions is None:
        pos1 = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        positions = (
            jnp.broadcast_to(pos1, (3, b, s)) if cfg.rope_style == "mrope" else pos1
        )
    x = embed_tokens(params["embed"], tokens, cfg).astype(dtype_of(cfg.compute_dtype))
    if patch_embeds is not None and cfg.n_patches:
        npt = patch_embeds.shape[1]
        x = x.at[:, :npt, :].set(patch_embeds.astype(x.dtype))

    caches: List[Cache] = []
    sw = cfg.sliding_window or 8192
    for spec, gp in zip(layer_groups(cfg), params["groups"]):
        if spec.kind in ("dense", "moe"):
            ring = cfg.attn_variant == "sliding_window"
            slots = min(sw, max_len) if ring else max_len
            w = cfg.window_for_layer(0)
            block = _dense_block_prefill if spec.kind == "dense" else _moe_block_prefill

            def body(x, bp, _block=block):
                x, k, v = _block(bp, x, positions, cfg, w, seq_valid)
                return x, (k, v)

            x, (ks, vs) = scan_or_unroll(body, x, gp, cfg)
            ck, cv, kv_pos = _pack_attn_cache(ks, vs, slots, ring)
            if true_len is not None:
                assert not ring, "bucketed prefill needs full caches"
                j = jnp.arange(slots, dtype=jnp.int32)
                kv_pos = jnp.where(j[None, :] < true_len[:, None], j[None, :], -1)
            caches.append({"k": ck, "v": cv, "kv_pos": kv_pos})

        elif spec.kind == "gemma_pair":
            ring_g = cfg.attn_variant == "sliding_window"
            local_w = sw
            global_w = sw if ring_g else 0
            g_slots = min(sw, max_len) if ring_g else max_len
            l_slots = min(cfg.sliding_window, max_len)

            def body(x, bp):
                x, lk, lv = _dense_block_prefill(
                    bp["local"], x, positions, cfg, local_w, seq_valid
                )
                x, gk, gv = _dense_block_prefill(
                    bp["global"], x, positions, cfg, global_w, seq_valid
                )
                return x, (lk, lv, gk, gv)

            x, (lks, lvs, gks, gvs) = scan_or_unroll(body, x, gp, cfg)
            lck, lcv, l_pos = _pack_attn_cache(lks, lvs, l_slots, True)
            gck, gcv, g_pos = _pack_attn_cache(gks, gvs, g_slots, ring_g)
            caches.append({
                "local": {"k": lck, "v": lcv, "kv_pos": l_pos},
                "global": {"k": gck, "v": gcv, "kv_pos": g_pos},
            })

        elif spec.kind == "mamba":
            def body(x, bp):
                out, final, conv = ssm_forward(
                    bp["ssm"], rms_norm(x, bp["norm"], cfg.norm_eps), cfg
                )
                return x + out, (final, conv)

            x, (hs, convs) = scan_or_unroll(body, x, gp, cfg)
            caches.append({"h": hs, "conv": convs})

        elif spec.kind == "zamba":
            ring = cfg.attn_variant == "sliding_window"
            slots = min(sw, max_len) if ring else max_len
            window = sw if ring else 0
            shared_bp = params["shared_attn"]

            def body(x, bp_group):
                h_list, c_list = [], []
                for i in range(spec.period):
                    bp_i = jax.tree.map(lambda a: a[i], bp_group)
                    out, final, conv = ssm_forward(
                        bp_i["ssm"], rms_norm(x, bp_i["norm"], cfg.norm_eps), cfg
                    )
                    x = x + out
                    h_list.append(final)
                    c_list.append(conv)
                x, k, v = _dense_block_prefill(
                    shared_bp, x, positions, cfg, window, seq_valid
                )
                return x, (jnp.stack(h_list), jnp.stack(c_list), k, v)

            x, (hs, convs, ks, vs) = scan_or_unroll(body, x, gp, cfg)
            ck, cv, kv_pos = _pack_attn_cache(ks, vs, slots, ring)
            n_cov = spec.n_blocks * spec.period
            caches.append({
                "ssm": {
                    "h": hs.reshape((n_cov,) + hs.shape[2:]),
                    "conv": convs.reshape((n_cov,) + convs.shape[2:]),
                },
                "attn": {"k": ck, "v": cv, "kv_pos": kv_pos},
            })
        else:
            raise ValueError(spec.kind)

    if true_len is not None:
        last = x[jnp.arange(b), true_len - 1][:, None, :]
        logits = unembed(params["embed"], last, cfg)
        return logits[:, 0], caches, true_len.astype(jnp.int32)
    logits = unembed(params["embed"], x[:, -1:, :], cfg)
    next_pos = jnp.full((b,), s, dtype=jnp.int32)
    return logits[:, 0], caches, next_pos


# ---------------------------------------------------------------------------
# Incremental (chunked) prefill — session-level KV-cache reuse
# ---------------------------------------------------------------------------

def _dense_block_append(bp, x, positions, ck, cv, kv_pos, cfg, window):
    h, nk, nv = attention_append(
        bp["attn"], rms_norm(x, bp["norm1"], cfg.norm_eps), positions,
        ck, cv, kv_pos, cfg, window=window,
    )
    x = x + h
    x = x + mlp_forward(bp["mlp"], rms_norm(x, bp["norm2"], cfg.norm_eps), cfg)
    return x, nk, nv


def _moe_block_append(bp, x, positions, ck, cv, kv_pos, cfg, window):
    h, nk, nv = attention_append(
        bp["attn"], rms_norm(x, bp["norm1"], cfg.norm_eps), positions,
        ck, cv, kv_pos, cfg, window=window,
    )
    x = x + h
    m, _ = moe_serve(bp["moe"], rms_norm(x, bp["norm2"], cfg.norm_eps), cfg)
    return x + m, nk, nv


def supports_append(cfg: ModelConfig) -> bool:
    """Incremental prefill is implemented for full-cache dense/moe/vlm
    groups (slot == absolute position), GQA or latent attention (the
    latter on the paged paths only). Ring/SSM/hybrid state cannot be
    extended in place the same way yet."""
    return cfg.attn_variant == "full" and all(
        spec.kind in ("dense", "moe") for spec in layer_groups(cfg)
    )


def prefill_append(
    params: Params,
    cfg: ModelConfig,
    caches: List[Cache],
    tokens: jnp.ndarray,                       # (B,S) new-token chunk
    p0: jnp.ndarray,                           # (B,) absolute start offset
    true_len: Optional[jnp.ndarray] = None,    # (B,) real chunk lengths
) -> Tuple[jnp.ndarray, List[Cache], jnp.ndarray]:
    """Prefill a token chunk starting at position offset ``p0`` into
    *existing* caches: K/V land in slots ``[p0, p0+n)``, ``kv_pos`` is
    extended, and the chunk attends against every prior valid slot — so a
    returning session only computes its new tokens (O(new) not O(history)).

    Same contract as :func:`prefill`: returns (last-valid-position logits
    (B,V), new caches, next_pos (B,)). With ``true_len`` the chunk is
    right-padded to a bucket length; padded positions write ``kv_pos = -1``
    and are overwritten by the next chunk. Supported for full-cache
    dense/moe groups only (see :func:`supports_append`), GQA only."""
    assert not cfg.is_mla, "latent attention is served from paged pools"
    assert supports_append(cfg), (
        "prefill_append requires full-cache dense/moe groups "
        f"(arch={cfg.arch_type}, attn_variant={cfg.attn_variant})"
    )
    b, s = tokens.shape[0], tokens.shape[1]
    idx = jnp.arange(s, dtype=jnp.int32)
    q_pos = p0[:, None].astype(jnp.int32) + idx[None, :]          # (B,S)
    valid = (
        idx[None, :] < true_len[:, None] if true_len is not None
        else jnp.ones((b, s), dtype=bool)
    )
    positions = (
        jnp.broadcast_to(q_pos, (3, b, s)) if cfg.rope_style == "mrope" else q_pos
    )
    x = embed_tokens(params["embed"], tokens, cfg).astype(dtype_of(cfg.compute_dtype))

    new_caches: List[Cache] = []
    for spec, gp, cache in zip(layer_groups(cfg), params["groups"], caches):
        assert spec.kind in ("dense", "moe"), spec.kind
        kv_pos = append_kv_pos(cache["kv_pos"], q_pos, valid)
        w = cfg.window_for_layer(0)
        block = _dense_block_append if spec.kind == "dense" else _moe_block_append

        def body(x, scanned, _block=block, _w=w, _kv=kv_pos):
            bp, ck, cv = scanned
            x, nk, nv = _block(bp, x, positions, ck, cv, _kv, cfg, _w)
            return x, (nk, nv)

        x, (nk, nv) = scan_or_unroll(body, x, (gp, cache["k"], cache["v"]), cfg)
        new_caches.append({"k": nk, "v": nv, "kv_pos": kv_pos})

    n_new = true_len if true_len is not None else jnp.full((b,), s, jnp.int32)
    last = x[jnp.arange(b), n_new - 1][:, None, :]
    logits = unembed(params["embed"], last, cfg)
    next_pos = (p0 + n_new).astype(jnp.int32)
    return logits[:, 0], new_caches, next_pos


# ---------------------------------------------------------------------------
# Paged chunked prefill — prompt chunks land straight in KV pages
# ---------------------------------------------------------------------------

def _attn_chunk_paged(
    bp, x, positions, valid, pool, page_table, p0, true_len, cfg, n_skip,
    lin=None, layer=None,
):
    """The attention sublayer of one block of a paged prefill chunk,
    residual added: the chunk's K/V (or latent rows) written into their
    page cells, then attended through the page table. ``pool`` and ``lin``
    by name as ``cache.pool_cells`` gives them. Returns (x, pool)."""
    h_in = rms_norm(x, bp["norm1"], cfg.norm_eps)
    if cfg.is_mla:
        h, kv = mla_chunk_paged(
            bp["attn"], h_in, positions, valid, pool["kv"], page_table, p0,
            true_len, cfg, n_skip=n_skip,
            lin=None if lin is None else lin["kv"], layer=layer,
        )
        return x + h, {"kv": kv}
    h, nk, nv = attention_chunk_paged(
        bp["attn"], h_in, positions, valid, pool["k"], pool["v"], page_table,
        p0, true_len, cfg, n_skip=n_skip,
        lin_k=None if lin is None else lin["k"],
        lin_v=None if lin is None else lin["v"], layer=layer,
    )
    return x + h, {"k": nk, "v": nv}


def prefill_chunk_paged(
    params: Params,
    cfg: ModelConfig,
    pools: List[Cache],           # per group: {"k","v"} (L, P, ps, KV*Dh)
    page_table: jnp.ndarray,      # (B, MP) physical page ids per lane
    tokens: jnp.ndarray,          # (B,S) prompt chunk (bucketed)
    p0: jnp.ndarray,              # (B,) absolute position of chunk row 0
    true_len: jnp.ndarray,        # (B,) real chunk lengths
    n_skip: int = 0,
    stats: bool = False,
):
    """Prefill a prompt chunk *directly into KV pages*: each layer scatters
    the chunk's rotated K/V into page cells through the page table before
    attending (the paged sibling of :func:`prefill_append` — no dense
    ``max_len``-width intermediate ever exists). The chunk attends against
    the lane's whole causal prefix ``[0, p0 + true_len)``, so chunk N sees
    everything chunks 0..N-1 (and any shared-prefix pages) already wrote.

    ``n_skip`` pages at the front of each lane's table are treated as
    read-only (shared-prefix pages from another session) — the scatter
    drops any write below ``n_skip * page_size``, attention still reads
    them. Rows beyond ``true_len`` are bucket padding: their pool writes
    are dropped and their outputs are garbage that no one reads.

    Pure function; jit with donate_argnums on pools. Returns
    (logits at row ``true_len - 1`` (B,V), new pools), and with ``stats``
    also the MoE layers' counts over the chunk's real rows, as
    ``decode_step_paged`` gives them. Full-cache dense/moe groups only
    (:func:`supports_append`), GQA or latent attention; same
    kernel/reference dispatch, per-step hoisted gather and in-place layer
    scan (the pools a carry, the layer index scanned) as
    ``decode_step_paged``."""
    assert supports_append(cfg), (
        "prefill_chunk_paged requires full-cache dense/moe groups "
        f"(arch={cfg.arch_type}, attn_variant={cfg.attn_variant})"
    )
    b, s = tokens.shape[0], tokens.shape[1]
    idx = jnp.arange(s, dtype=jnp.int32)
    p0 = p0.astype(jnp.int32)
    true_len = true_len.astype(jnp.int32)
    q_pos = p0[:, None] + idx[None, :]                             # (B,S)
    valid = idx[None, :] < true_len[:, None]
    positions = (
        jnp.broadcast_to(q_pos, (3, b, s)) if cfg.rope_style == "mrope" else q_pos
    )
    x = embed_tokens(params["embed"], tokens, cfg).astype(dtype_of(cfg.compute_dtype))
    use_kernel = cfg.attn_impl == "pallas"
    total = jnp.zeros((N_STATS,), jnp.int32)

    new_pools: List[Cache] = []
    for spec, gp, pool in zip(layer_groups(cfg), params["groups"], pools):
        assert spec.kind in ("dense", "moe"), spec.kind
        lin = None if use_kernel else gather_pool_views(pool, page_table, cfg)

        def body(carry, scanned, _kind=spec.kind):
            x, pool, st = carry
            bp, layer, lv = scanned
            x, pool = _attn_chunk_paged(
                bp, x, positions, valid, pool, page_table, p0, true_len, cfg,
                n_skip, lv, layer,
            )
            x, s = ffn_serve(_kind, bp, x, cfg, valid)
            return (x, pool, st if s is None else st + s), None

        layers = jnp.arange(spec.n_blocks, dtype=jnp.int32)
        (x, pool, total), _ = scan_or_unroll(
            body, (x, pool, total), (gp, layers, lin), cfg
        )
        new_pools.append(pool)

    n = jnp.maximum(true_len, 1)
    last = x[jnp.arange(b), n - 1][:, None, :]
    logits = unembed(params["embed"], last, cfg)
    if stats:
        return logits[:, 0], new_pools, total
    return logits[:, 0], new_pools
