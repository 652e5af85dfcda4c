"""Unified decoder assembly for every assigned architecture family.

A model is a sequence of *layer groups*; each group is a stack of identical
blocks executed with lax.scan over stacked parameters (keeps HLO size and
compile time O(1) in depth — mandatory for the 96-layer dry-runs):

- ``dense``      — [norm→attn, norm→mlp] ×L             (qwen2, chatglm3, nemotron, musicgen, qwen2-vl)
- ``moe``        — [norm→attn, norm→moe] ×L             (dbrx, granite)
- ``dense`` then ``moe`` — leading dense layers before the MoE ones (deepseek-v2-lite)
- ``gemma_pair`` — [local(SW) block, global block] ×L/2 (gemma2)
- ``mamba``      — [norm→mamba2] ×L                     (mamba2)
- ``zamba``      — [period× mamba + shared attn blk] ×G (zamba2; shared weights closed over)

The attention of dense and MoE blocks is GQA (``models/attention.py``) or
latent attention (``cfg.attn_type == "mla"``, ``models/mla.py``).

Three entry points, all pure functions of (params, inputs):
- ``forward_full``  — training forward; returns (logits, aux_loss)
- ``prefill``       — forward + caches for serving
- ``decode_step``   — one token against caches (serve_step of the dry-run)
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from .attention import (
    attention_decode,
    attention_decode_paged,
    attention_forward,
    init_attention,
    project_kv_step,
)
from .cache import (
    Cache,
    gather_pool_views,
    init_attn_cache,
    init_paged_pool,
    init_ssm_cache,
    paged_write_step,
    prefill_kv_pos,
    ring_from_prefill,
    update_kv_pos,
    write_step,
)
from .config import ModelConfig
from .layers import (
    Params,
    dtype_of,
    embed_tokens,
    init_embed,
    init_mlp,
    init_rms_norm,
    mlp_forward,
    rms_norm,
    unembed,
)
from .mla import init_mla, mla_decode_paged, mla_forward
from .moe import N_STATS, init_moe, moe_forward, moe_serve
from .pjit_rules import constrain
from .ssm import init_ssm, init_ssm_state, ssm_decode_step, ssm_forward


# ---------------------------------------------------------------------------
# Group layout
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class GroupSpec:
    kind: str          # dense | moe | gemma_pair | mamba | zamba
    n_blocks: int      # scan length
    period: int = 0    # zamba: mamba layers per shared-attn invocation


def layer_groups(cfg: ModelConfig) -> List[GroupSpec]:
    if cfg.layer_pattern == "local_global":
        assert cfg.n_layers % 2 == 0, "local_global needs even layer count"
        return [GroupSpec("gemma_pair", cfg.n_layers // 2)]
    if cfg.layer_pattern == "zamba_hybrid":
        period = cfg.shared_attn_period
        n_groups, rem = divmod(cfg.n_layers, period)
        groups = [GroupSpec("zamba", n_groups, period)]
        if rem:
            groups.append(GroupSpec("mamba", rem))
        return groups
    if cfg.arch_type == "ssm":
        return [GroupSpec("mamba", cfg.n_layers)]
    if cfg.n_experts > 0:
        n_dense = cfg.first_dense_layers
        moe = [GroupSpec("moe", cfg.n_layers - n_dense)]
        return ([GroupSpec("dense", n_dense)] if n_dense else []) + moe
    return [GroupSpec("dense", cfg.n_layers)]


# ---------------------------------------------------------------------------
# Block init
# ---------------------------------------------------------------------------

def _init_attn(key, cfg: ModelConfig) -> Params:
    return init_mla(key, cfg) if cfg.is_mla else init_attention(key, cfg)


def _init_dense_block(key, cfg: ModelConfig) -> Params:
    k1, k2 = jax.random.split(key)
    dt = dtype_of(cfg.param_dtype)
    return {
        "norm1": init_rms_norm(cfg.d_model, dt),
        "attn": _init_attn(k1, cfg),
        "norm2": init_rms_norm(cfg.d_model, dt),
        "mlp": init_mlp(k2, cfg),
    }


def _init_moe_block(key, cfg: ModelConfig) -> Params:
    k1, k2 = jax.random.split(key)
    dt = dtype_of(cfg.param_dtype)
    return {
        "norm1": init_rms_norm(cfg.d_model, dt),
        "attn": _init_attn(k1, cfg),
        "norm2": init_rms_norm(cfg.d_model, dt),
        "moe": init_moe(k2, cfg),
    }


def _init_mamba_block(key, cfg: ModelConfig) -> Params:
    dt = dtype_of(cfg.param_dtype)
    return {"norm": init_rms_norm(cfg.d_model, dt), "ssm": init_ssm(key, cfg)}


def _stack_init(init_fn, key, n: int, cfg: ModelConfig) -> Params:
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: init_fn(k, cfg))(keys)


def init_group(key, spec: GroupSpec, cfg: ModelConfig) -> Params:
    if spec.kind == "dense":
        return _stack_init(_init_dense_block, key, spec.n_blocks, cfg)
    if spec.kind == "moe":
        return _stack_init(_init_moe_block, key, spec.n_blocks, cfg)
    if spec.kind == "gemma_pair":
        k1, k2 = jax.random.split(key)
        return {
            "local": _stack_init(_init_dense_block, k1, spec.n_blocks, cfg),
            "global": _stack_init(_init_dense_block, k2, spec.n_blocks, cfg),
        }
    if spec.kind == "mamba":
        return _stack_init(_init_mamba_block, key, spec.n_blocks, cfg)
    if spec.kind == "zamba":
        # (n_groups, period, ...) nested stack of mamba blocks
        keys = jax.random.split(key, spec.n_blocks)
        return jax.vmap(
            lambda k: _stack_init(_init_mamba_block, k, spec.period, cfg)
        )(keys)
    raise ValueError(spec.kind)


def init_params(key, cfg: ModelConfig) -> Params:
    keys = jax.random.split(key, 8)
    params: Params = {"embed": init_embed(keys[0], cfg)}
    groups = layer_groups(cfg)
    params["groups"] = tuple(
        init_group(keys[1 + i], spec, cfg) for i, spec in enumerate(groups)
    )
    if cfg.layer_pattern == "zamba_hybrid":
        params["shared_attn"] = _init_dense_block(keys[7], cfg)
    return params


def abstract_params(cfg: ModelConfig) -> Params:
    """ShapeDtypeStruct pytree of the parameters — no allocation (dry-run)."""
    return jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))


# ---------------------------------------------------------------------------
# Block forwards (full sequence)
# ---------------------------------------------------------------------------

def _attn_full(bp, x, positions, cfg, window, seq_valid):
    h = rms_norm(x, bp["norm1"], cfg.norm_eps)
    if cfg.is_mla:
        return mla_forward(bp["attn"], h, positions, cfg, seq_valid)
    return attention_forward(
        bp["attn"], h, positions, cfg, window=window, seq_valid=seq_valid,
    )


def _dense_block_full(bp, x, positions, cfg, window, seq_valid):
    # Megatron-style sequence parallelism: the residual stream (and thus the
    # remat-saved activation) is sequence-sharded when the 'act_seq' rule is
    # bound; GSPMD inserts the gather before attention/MLP matmuls.
    x = constrain(x, "batch", "act_seq", None)
    h = _attn_full(bp, x, positions, cfg, window, seq_valid)
    x = x + h
    x = x + mlp_forward(bp["mlp"], rms_norm(x, bp["norm2"], cfg.norm_eps), cfg)
    return constrain(x, "batch", "act_seq", None)


def _moe_block_full(bp, x, positions, cfg, window, seq_valid):
    x = constrain(x, "batch", "act_seq", None)
    h = _attn_full(bp, x, positions, cfg, window, seq_valid)
    x = x + h
    m, aux = moe_forward(bp["moe"], rms_norm(x, bp["norm2"], cfg.norm_eps), cfg)
    return constrain(x + m, "batch", "act_seq", None), aux


def _mamba_block_full(bp, x, cfg, h0=None):
    x = constrain(x, "batch", "act_seq", None)
    out, final, conv_state = ssm_forward(
        bp["ssm"], rms_norm(x, bp["norm"], cfg.norm_eps), cfg, h0
    )
    return constrain(x + out, "batch", "act_seq", None), final, conv_state


# ---------------------------------------------------------------------------
# Full-sequence forward (training) — scan over stacked blocks per group
# ---------------------------------------------------------------------------

def _maybe_remat(fn, cfg: ModelConfig):
    return jax.checkpoint(fn) if cfg.remat else fn


def scan_or_unroll(body, carry, xs, cfg: ModelConfig):
    """lax.scan in production; an unrolled python loop when
    cfg.unroll_layers (dry-run cost compiles — XLA counts loop bodies once)."""
    if not cfg.unroll_layers:
        return jax.lax.scan(body, carry, xs)
    length = jax.tree.leaves(xs)[0].shape[0]
    ys_acc = None
    stack = []
    for i in range(length):
        xi = jax.tree.map(lambda a: a[i], xs)
        carry, y = body(carry, xi)
        if y is not None:
            stack.append(y)
    if stack:
        ys_acc = jax.tree.map(lambda *a: jnp.stack(a), *stack)
    return carry, ys_acc


def _run_group_full(
    spec: GroupSpec, gp: Params, x, positions, cfg: ModelConfig, seq_valid
):
    """Returns (x, aux_loss). Cache-producing variants live in prefill."""
    if spec.kind == "dense":
        w = cfg.window_for_layer(0)  # uniform groups share one window

        def body(x, bp):
            return _dense_block_full(bp, x, positions, cfg, w, seq_valid), None

        x, _ = scan_or_unroll(_maybe_remat(body, cfg), x, gp, cfg)
        return x, 0.0

    if spec.kind == "moe":
        w = cfg.window_for_layer(0)

        def body(carry, bp):
            x, aux = carry
            x, a = _moe_block_full(bp, x, positions, cfg, w, seq_valid)
            return (x, aux + a), None

        (x, aux), _ = scan_or_unroll(_maybe_remat(body, cfg), (x, 0.0), gp, cfg)
        return x, aux

    if spec.kind == "gemma_pair":
        local_w = cfg.sliding_window if cfg.attn_variant == "full" else (
            cfg.sliding_window or 8192
        )
        global_w = 0 if cfg.attn_variant == "full" else (cfg.sliding_window or 8192)

        def body(x, bp):
            x = _dense_block_full(bp["local"], x, positions, cfg, local_w, seq_valid)
            x = _dense_block_full(bp["global"], x, positions, cfg, global_w, seq_valid)
            return x, None

        x, _ = scan_or_unroll(_maybe_remat(body, cfg), x, gp, cfg)
        return x, 0.0

    if spec.kind == "mamba":
        def body(x, bp):
            x, _, _ = _mamba_block_full(bp, x, cfg)
            return x, None

        x, _ = scan_or_unroll(_maybe_remat(body, cfg), x, gp, cfg)
        return x, 0.0

    if spec.kind == "zamba":
        shared = cfg  # closure marker; actual shared params passed via partial
        raise RuntimeError("zamba groups are run by _run_zamba_full")

    raise ValueError(spec.kind)


def _run_zamba_full(
    spec: GroupSpec, gp: Params, shared_bp: Params, x, positions, cfg, seq_valid
):
    """period mamba blocks then one shared-weight attention block, ×n_groups."""

    def body(x, bp_group):
        for i in range(spec.period):
            bp_i = jax.tree.map(lambda a: a[i], bp_group)
            x, _, _ = _mamba_block_full(bp_i, x, cfg)
        w = 0 if cfg.attn_variant == "full" else (cfg.sliding_window or 8192)
        x = _dense_block_full(shared_bp, x, positions, cfg, w, seq_valid)
        return x, None

    x, _ = scan_or_unroll(_maybe_remat(body, cfg), x, gp, cfg)
    return x


def forward_full(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,                 # (B,S) or (B,S,K) audio
    positions: Optional[jnp.ndarray] = None,
    patch_embeds: Optional[jnp.ndarray] = None,  # (B,P,D) VLM stub frontend
    seq_valid: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Training/eval forward. Returns (logits, aux_loss)."""
    b = tokens.shape[0]
    s = tokens.shape[1]
    if positions is None:
        pos1 = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        positions = (
            jnp.broadcast_to(pos1, (3, b, s)) if cfg.rope_style == "mrope" else pos1
        )
    x = embed_tokens(params["embed"], tokens, cfg).astype(dtype_of(cfg.compute_dtype))
    if patch_embeds is not None and cfg.n_patches:
        # VLM: patch embeddings (from the stub vision frontend) occupy the
        # first n_patches positions of the sequence.
        npt = patch_embeds.shape[1]
        x = x.at[:, :npt, :].set(patch_embeds.astype(x.dtype))
    aux = jnp.zeros((), jnp.float32)
    for spec, gp in zip(layer_groups(cfg), params["groups"]):
        if spec.kind == "zamba":
            x = _run_zamba_full(
                spec, gp, params["shared_attn"], x, positions, cfg, seq_valid
            )
        else:
            x, a = _run_group_full(spec, gp, x, positions, cfg, seq_valid)
            aux = aux + a
    logits = unembed(params["embed"], x, cfg)
    return logits, aux


# ---------------------------------------------------------------------------
# Prefill: forward + cache construction
# ---------------------------------------------------------------------------

def make_decode_caches(
    cfg: ModelConfig, batch: int, max_len: int, dtype=None
) -> List[Cache]:
    """Empty caches matching layer_groups(cfg). max_len = total slots for
    full caches; ring caches use their window size."""
    assert not cfg.is_mla, "latent attention is served from paged pools"
    caches: List[Cache] = []
    for spec in layer_groups(cfg):
        if spec.kind in ("dense", "moe"):
            if cfg.attn_variant == "sliding_window":
                w = min(cfg.sliding_window or 8192, max_len)
                caches.append(init_attn_cache(cfg, spec.n_blocks, batch, w, dtype))
            else:
                caches.append(init_attn_cache(cfg, spec.n_blocks, batch, max_len, dtype))
        elif spec.kind == "gemma_pair":
            w = min(cfg.sliding_window, max_len)
            local = init_attn_cache(cfg, spec.n_blocks, batch, w, dtype)
            glob_slots = (
                min(cfg.sliding_window or 8192, max_len)
                if cfg.attn_variant == "sliding_window" else max_len
            )
            glob = init_attn_cache(cfg, spec.n_blocks, batch, glob_slots, dtype)
            caches.append({"local": local, "global": glob})
        elif spec.kind == "mamba":
            caches.append(init_ssm_cache(cfg, spec.n_blocks, batch, dtype))
        elif spec.kind == "zamba":
            ssm = init_ssm_cache(cfg, spec.n_blocks * spec.period, batch, dtype)
            w = (
                min(cfg.sliding_window or 8192, max_len)
                if cfg.attn_variant == "sliding_window" else max_len
            )
            attn = init_attn_cache(cfg, spec.n_blocks, batch, w, dtype)
            caches.append({"ssm": ssm, "attn": attn})
        else:
            raise ValueError(spec.kind)
    return caches


# ---------------------------------------------------------------------------
# Decode step (serve_step): one token against the caches
# ---------------------------------------------------------------------------

def _attn_ring(cfg: ModelConfig, spec_kind: str, slots: int, max_len_hint: int) -> bool:
    return cfg.attn_variant == "sliding_window" or slots < max_len_hint


def _dense_block_decode(bp, x, positions, cache_k, cache_v, kv_pos, cfg, window, ring):
    """One layer decode. cache_k/v: (B,T,KV,Dh) — this layer's slice; returns
    (x, new_k, new_v). kv_pos already updated for the current position."""
    pos1d = positions[0] if positions.ndim == 3 else positions
    h_in = rms_norm(x, bp["norm1"], cfg.norm_eps)
    k_new, v_new = project_kv_step(bp["attn"], h_in, positions, cfg)
    ck, cv = write_step(cache_k, cache_v, k_new, v_new, pos1d[:, 0], ring)
    valid = kv_pos >= 0
    h = attention_decode(
        bp["attn"], h_in, positions, ck, cv, kv_pos, valid, cfg, window=window
    )
    x = x + h
    x = x + mlp_forward(bp["mlp"], rms_norm(x, bp["norm2"], cfg.norm_eps), cfg)
    return x, ck, cv


def _moe_block_decode(bp, x, positions, cache_k, cache_v, kv_pos, cfg, window, ring):
    pos1d = positions[0] if positions.ndim == 3 else positions
    h_in = rms_norm(x, bp["norm1"], cfg.norm_eps)
    k_new, v_new = project_kv_step(bp["attn"], h_in, positions, cfg)
    ck, cv = write_step(cache_k, cache_v, k_new, v_new, pos1d[:, 0], ring)
    valid = kv_pos >= 0
    h = attention_decode(
        bp["attn"], h_in, positions, ck, cv, kv_pos, valid, cfg, window=window
    )
    x = x + h
    m, _ = moe_serve(bp["moe"], rms_norm(x, bp["norm2"], cfg.norm_eps), cfg)
    return x + m, ck, cv


def _paged_attn_sublayer(
    bp, x, positions, pool, page_table, kv_pos, cfg, window,
    page_size, lin=None, shared_pages=None, layer=None,
):
    """Shared attention sublayer of one paged decode block: scatter the
    token's K/V (or, for latent attention, its latent row) into its page
    cell, then attend through the page table (fused kernel when
    ``cfg.attn_impl == "pallas"``). ``pool`` is the block's pools by name
    (``cache.pool_cells``); with ``layer``, each is the group's whole
    ``(L, P, ps, row)`` stack, written and read at that layer in place.
    On the reference path, callers that hoisted the gather pass the
    pre-gathered linear views (``lin``, by the same names); the new token
    is inserted into them here (slot == position, with the same
    at-capacity drop as the pool scatter) so they stay bit-identical to
    gathering after the scatter. Returns (x + attention, pool)."""
    pos1d = positions[0] if positions.ndim == 3 else positions
    h_in = rms_norm(x, bp["norm1"], cfg.norm_eps)
    if cfg.is_mla:
        h, kv = mla_decode_paged(
            bp["attn"], h_in, positions, pool["kv"], page_table, kv_pos, cfg,
            page_size, None if lin is None else lin["kv"], shared_pages, layer,
        )
        return x + h, {"kv": kv}
    k_new, v_new = project_kv_step(bp["attn"], h_in, positions, cfg)
    pk, pv = paged_write_step(
        pool["k"], pool["v"], k_new, v_new, pos1d[:, 0], page_table, page_size,
        layer,
    )
    lin_k = lin_v = None
    if lin is not None:
        bidx = jnp.arange(x.shape[0])
        slot = pos1d[:, 0]
        lin_k = lin["k"].at[bidx, slot].set(
            k_new[:, 0].astype(lin["k"].dtype), mode="drop")
        lin_v = lin["v"].at[bidx, slot].set(
            v_new[:, 0].astype(lin["v"].dtype), mode="drop")
    h = attention_decode_paged(
        bp["attn"], h_in, positions, pk, pv, page_table, kv_pos, cfg,
        window=window, lin_k=lin_k, lin_v=lin_v, shared_pages=shared_pages,
        layer=layer,
    )
    return x + h, {"k": pk, "v": pv}


def ffn_serve(kind: str, bp, x, cfg: ModelConfig, valid=None):
    """The feed-forward sublayer of a served block, residual added: the
    dense MLP, or the dropless MoE layer (``moe.moe_serve``) whose counts
    over the ``valid`` tokens come back beside it (None for a dense
    block)."""
    h = rms_norm(x, bp["norm2"], cfg.norm_eps)
    if kind == "moe":
        m, stats = moe_serve(bp["moe"], h, cfg, valid)
        return x + m, stats
    return x + mlp_forward(bp["mlp"], h, cfg), None


def decode_step_paged(
    params: Params,
    cfg: ModelConfig,
    pools: List[Cache],           # per group: {"k","v"} (L, P, ps, KV*Dh)
    page_table: jnp.ndarray,      # (B, MP) physical page ids per lane
    kv_pos: jnp.ndarray,          # (B, MP*ps) shared across full-cache groups
    tokens: jnp.ndarray,          # (B,1)
    pos: jnp.ndarray,             # (B,) absolute position of this token
    shared_pages: Optional[jnp.ndarray] = None,  # (S,) common leading pages
    stats: bool = False,
):
    """serve_step against a *paged* KV pool: the batch's resident KV state
    is the shared page pool plus per-lane page tables sized to actual token
    counts, not B full-width lanes. Full-cache dense/moe groups only (the
    same family :func:`~repro.models.prefill.supports_append` covers), with
    GQA or latent attention. Returns ``(logits, pools, kv_pos)``, and with
    ``stats`` also the MoE layers' counts summed over layers
    (``moe.N_STATS`` int32: pairs routed, pairs held here, held experts
    hit) over the lanes whose position lies within the table.
    Pure function; jit with donate_argnums on pools and kv_pos.

    The layer scan carries each group's ``(L, P, ps, KV*Dh)`` pools and
    scans only the layer params and the layer index: every layer writes its
    token straight into the stack, so with the pools donated the step
    updates them in place and no layer's pool is ever copied.

    With ``cfg.attn_impl == "pallas"`` each layer attends straight through
    the page table (``repro.kernels.paged_attention``), reading the stack
    at its layer — no linearized cache copy and no per-layer slice is ever
    built. The reference path gathers instead, hoisted:
    one K and one V gather per group per *step* (``gather_pages_stacked``)
    rather than two per layer, with the new token inserted into the view
    inside each block. Callers may pass a ``page_table``/``kv_pos`` pair
    trimmed to fewer pages than the lanes' full width (the batched server's
    page-width bucketing): the layout invariant (slot == position) makes
    attention over the trimmed width identical as long as every lane's
    tokens fit in it.

    ``shared_pages`` (pallas path only, ignored by the reference path):
    a run of physical pages every lane's table starts with — the kernel
    attends them once per unique page for the whole batch instead of once
    per lane (docs/architecture.md, "Cross-session shared-prefix
    paging")."""
    b = tokens.shape[0]
    pos1 = pos[:, None].astype(jnp.int32)
    positions = (
        jnp.broadcast_to(pos1, (3, b, 1)) if cfg.rope_style == "mrope" else pos1
    )
    x = embed_tokens(params["embed"], tokens, cfg).astype(dtype_of(cfg.compute_dtype))
    page_size = next(iter(pools[0].values())).shape[2]
    # drop-mode update: a lane at table capacity keeps its last slot intact
    # instead of relabeling it with the overflow position (the K/V write is
    # likewise dropped — see paged_write_step)
    new_kv_pos = kv_pos.at[jnp.arange(b), pos].set(
        pos.astype(jnp.int32), mode="drop"
    )
    use_kernel = cfg.attn_impl == "pallas"
    valid = (pos < page_table.shape[1] * page_size)[:, None]
    total = jnp.zeros((N_STATS,), jnp.int32)

    new_pools: List[Cache] = []
    for spec, gp, pool in zip(layer_groups(cfg), params["groups"], pools):
        assert spec.kind in ("dense", "moe"), (
            f"paged decode requires full-cache dense/moe groups, got {spec.kind}"
        )
        lin = None if use_kernel else gather_pool_views(pool, page_table, cfg)

        def body(carry, scanned, _kind=spec.kind):
            x, pool, st = carry
            bp, layer, lv = scanned
            x, pool = _paged_attn_sublayer(
                bp, x, positions, pool, page_table, new_kv_pos, cfg, 0,
                page_size, lv, shared_pages, layer,
            )
            x, s = ffn_serve(_kind, bp, x, cfg, valid)
            return (x, pool, st if s is None else st + s), None

        layers = jnp.arange(spec.n_blocks, dtype=jnp.int32)
        (x, pool, total), _ = scan_or_unroll(
            body, (x, pool, total), (gp, layers, lin), cfg
        )
        new_pools.append(pool)

    logits = unembed(params["embed"], x, cfg)
    if stats:
        return logits, new_pools, new_kv_pos, total
    return logits, new_pools, new_kv_pos


def decode_step(
    params: Params,
    cfg: ModelConfig,
    caches: List[Cache],
    tokens: jnp.ndarray,          # (B,1) or (B,1,K)
    pos: jnp.ndarray,             # (B,) absolute position of this token
) -> Tuple[jnp.ndarray, List[Cache]]:
    """serve_step: one new token, updated caches. Pure function; jit with
    donate_argnums on caches. GQA models only: latent attention is served
    from paged pools (:func:`decode_step_paged`)."""
    assert not cfg.is_mla, "latent attention is served from paged pools"
    b = tokens.shape[0]
    pos1 = pos[:, None].astype(jnp.int32)                    # (B,1)
    positions = (
        jnp.broadcast_to(pos1, (3, b, 1)) if cfg.rope_style == "mrope" else pos1
    )
    x = embed_tokens(params["embed"], tokens, cfg).astype(dtype_of(cfg.compute_dtype))

    new_caches: List[Cache] = []
    for spec, gp, cache in zip(layer_groups(cfg), params["groups"], caches):
        if spec.kind in ("dense", "moe"):
            slots = cache["k"].shape[2]
            ring = cfg.attn_variant == "sliding_window"
            kv_pos = update_kv_pos(cache["kv_pos"], pos, ring)
            window = (cfg.sliding_window or 8192) if ring else 0
            block_fn = _dense_block_decode if spec.kind == "dense" else _moe_block_decode

            def body(x, scanned, _fn=block_fn, _w=window, _ring=ring, _kv=kv_pos):
                bp, ck, cv = scanned
                x, nk, nv = _fn(bp, x, positions, ck, cv, _kv, cfg, _w, _ring)
                return x, (nk, nv)

            x, (nk, nv) = scan_or_unroll(body, x, (gp, cache["k"], cache["v"]), cfg)
            new_caches.append({"k": nk, "v": nv, "kv_pos": kv_pos})

        elif spec.kind == "gemma_pair":
            lw = cfg.sliding_window
            l_ring = True
            g_ring = cfg.attn_variant == "sliding_window"
            gw = (cfg.sliding_window or 8192) if g_ring else 0
            l_kv = update_kv_pos(cache["local"]["kv_pos"], pos, l_ring)
            g_kv = update_kv_pos(cache["global"]["kv_pos"], pos, g_ring)

            def body(x, scanned):
                bp, lck, lcv, gck, gcv = scanned
                x, nlk, nlv = _dense_block_decode(
                    bp["local"], x, positions, lck, lcv, l_kv, cfg, lw, l_ring
                )
                x, ngk, ngv = _dense_block_decode(
                    bp["global"], x, positions, gck, gcv, g_kv, cfg, gw, g_ring
                )
                return x, (nlk, nlv, ngk, ngv)

            x, (nlk, nlv, ngk, ngv) = scan_or_unroll(
                body, x,
                (gp, cache["local"]["k"], cache["local"]["v"],
                 cache["global"]["k"], cache["global"]["v"]), cfg,
            )
            new_caches.append({
                "local": {"k": nlk, "v": nlv, "kv_pos": l_kv},
                "global": {"k": ngk, "v": ngv, "kv_pos": g_kv},
            })

        elif spec.kind == "mamba":
            def body(x, scanned):
                bp, h, conv = scanned
                out, st = ssm_decode_step(
                    bp["ssm"], rms_norm(x, bp["norm"], cfg.norm_eps),
                    {"h": h, "conv": conv}, cfg,
                )
                return x + out, (st["h"], st["conv"])

            x, (nh, nconv) = scan_or_unroll(body, x, (gp, cache["h"], cache["conv"]), cfg)
            new_caches.append({"h": nh, "conv": nconv})

        elif spec.kind == "zamba":
            ring = cfg.attn_variant == "sliding_window"
            window = (cfg.sliding_window or 8192) if ring else 0
            a_kv = update_kv_pos(cache["attn"]["kv_pos"], pos, ring)
            # reshape ssm cache to (n_groups, period, B, ...) for nested scan
            ssm_h = cache["ssm"]["h"].reshape(
                (spec.n_blocks, spec.period) + cache["ssm"]["h"].shape[1:]
            )
            ssm_c = cache["ssm"]["conv"].reshape(
                (spec.n_blocks, spec.period) + cache["ssm"]["conv"].shape[1:]
            )
            shared_bp = params["shared_attn"]

            def body(x, scanned):
                bp_g, h_g, c_g, ck, cv = scanned
                new_h, new_c = [], []
                for i in range(spec.period):
                    bp_i = jax.tree.map(lambda a: a[i], bp_g)
                    out, st = ssm_decode_step(
                        bp_i["ssm"], rms_norm(x, bp_i["norm"], cfg.norm_eps),
                        {"h": h_g[i], "conv": c_g[i]}, cfg,
                    )
                    x = x + out
                    new_h.append(st["h"])
                    new_c.append(st["conv"])
                x, nk, nv = _dense_block_decode(
                    shared_bp, x, positions, ck, cv, a_kv, cfg, window, ring
                )
                return x, (jnp.stack(new_h), jnp.stack(new_c), nk, nv)

            x, (nh, nconv, nk, nv) = scan_or_unroll(
                body, x, (gp, ssm_h, ssm_c, cache["attn"]["k"], cache["attn"]["v"]), cfg
            )
            new_caches.append({
                "ssm": {
                    "h": nh.reshape(cache["ssm"]["h"].shape),
                    "conv": nconv.reshape(cache["ssm"]["conv"].shape),
                },
                "attn": {"k": nk, "v": nv, "kv_pos": a_kv},
            })
        else:
            raise ValueError(spec.kind)

    logits = unembed(params["embed"], x, cfg)
    return logits, new_caches
