"""Mixture-of-Experts FFN (dbrx: 16e top-4 fine-grained; granite: 40e top-8).

Dispatch is the static-shape, sort-based capacity algorithm: tokens are
argsorted by expert id, each expert takes up to C = ceil(T·K/E · cf) slots,
overflow drops (capacity-based, GShard-style) — so compiled FLOPs equal
*active* FLOPs (E·C·D·F ≈ T·K·D·F·cf), which is what the roofline's
MoE MODEL_FLOPS check expects.

Under pjit, experts shard over the ``model`` mesh axis (expert parallelism)
and tokens over ``data``; the dispatch gather/scatter becomes the all-to-all
the paper pool's MoE entries call for. The hillclimb pass compares this
GSPMD-auto layout against an explicit shard_map all_to_all schedule.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig
from .layers import Params, dtype_of


def init_moe(key, cfg: ModelConfig) -> Params:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    dt = dtype_of(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    s_in, s_out = 1.0 / np.sqrt(d), 1.0 / np.sqrt(f)
    p: Params = {
        "router": (jax.random.normal(ks[0], (d, e)) * s_in).astype(dt),
        "w_up": (jax.random.normal(ks[1], (e, d, f)) * s_in).astype(dt),
        "w_down": (jax.random.normal(ks[2], (e, f, d)) * s_out).astype(dt),
    }
    if cfg.mlp_type == "swiglu":
        p["w_gate"] = (jax.random.normal(ks[3], (e, d, f)) * s_in).astype(dt)
    return p


def expert_capacity(cfg: ModelConfig, n_tokens: int) -> int:
    c = int(np.ceil(n_tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor))
    return max(8, int(np.ceil(c / 8)) * 8)  # pad to lane-friendly multiple


def _sort_dispatch(xf, top_idx, top_w, e: int, cap: int):
    """Sort-based capacity dispatch. xf (T,D); top_idx/top_w (T,K).
    Returns (xe (E,C,D), slot_token (E*C,), slot_w (E*C,))."""
    t, d = xf.shape
    k = top_idx.shape[1]
    flat_expert = top_idx.reshape(-1)
    flat_token = jnp.repeat(jnp.arange(t), k)
    flat_w = top_w.reshape(-1)

    order = jnp.argsort(flat_expert, stable=True)
    se, st, sw = flat_expert[order], flat_token[order], flat_w[order]
    counts = jnp.bincount(flat_expert, length=e)
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(t * k) - starts[se]
    keep = pos_in_e < cap
    dest = jnp.where(keep, se * cap + pos_in_e, e * cap)

    slot_token = jnp.full((e * cap + 1,), t, dtype=jnp.int32)
    slot_token = slot_token.at[dest].set(st.astype(jnp.int32), mode="drop")
    slot_w = jnp.zeros((e * cap + 1,), dtype=jnp.float32)
    slot_w = slot_w.at[dest].set(sw, mode="drop")
    slot_token, slot_w = slot_token[:-1], slot_w[:-1]

    xpad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], axis=0)
    xe = xpad[slot_token].reshape(e, cap, d)
    return xe, slot_token, slot_w


def _expert_mlp(p: Params, xe: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """xe (E,C,D) through per-expert MLPs (weights (E,D,F)/(E,F,D))."""
    up = jnp.einsum("ecd,edf->ecf", xe, p["w_up"])
    if cfg.mlp_type in ("swiglu", "geglu"):
        act_fn = jax.nn.silu if cfg.mlp_type == "swiglu" else jax.nn.gelu
        act = act_fn(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])) * up
    elif cfg.mlp_type == "relu2":
        r = jax.nn.relu(up)
        act = r * r
    else:
        act = jax.nn.gelu(up)
    return jnp.einsum("ecf,efd->ecd", act, p["w_down"])


def _route(p: Params, xf: jnp.ndarray, cfg: ModelConfig):
    """Router probs + top-k + Switch aux loss. xf (T,D)."""
    e, k = cfg.n_experts, cfg.top_k
    router_logits = (xf @ p["router"]).astype(jnp.float32)       # (T,E)
    probs = jax.nn.softmax(router_logits, axis=-1)
    top_vals, top_idx = jax.lax.top_k(probs, k)                  # (T,K)
    top_w = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)
    me = jnp.mean(probs, axis=0)                                  # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_idx, e, dtype=jnp.float32), axis=1), axis=0
    )
    aux = e * jnp.sum(me * ce) * cfg.router_aux_coef
    return top_idx, top_w, aux


def moe_forward(
    p: Params, x: jnp.ndarray, cfg: ModelConfig
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B,S,D) -> (out, aux_loss). Static shapes throughout.

    moe_impl="gspmd": single-program formulation; GSPMD chooses the
    collectives for the dispatch gather/scatter (baseline).
    moe_impl="shard_map": explicit expert parallelism — local routing per
    data shard, all_to_all over the model/expert axis, local expert matmuls,
    reverse all_to_all (§Perf hillclimb; requires active sharding rules).
    """
    if cfg.moe_impl == "shard_map":
        from .pjit_rules import current_rules

        rules = current_rules()
        if rules is not None and rules.get("_mesh") is not None:
            return _moe_forward_shard_map(p, x, cfg, rules)

    b, s, d = x.shape
    e = cfg.n_experts
    t = b * s
    cap = expert_capacity(cfg, t)
    xf = x.reshape(t, d)
    top_idx, top_w, aux = _route(p, xf, cfg)
    xe, slot_token, slot_w = _sort_dispatch(xf, top_idx, top_w, e, cap)
    ye = _expert_mlp(p, xe, cfg)                                   # (E,C,D)
    ye_flat = ye.reshape(e * cap, d) * slot_w[:, None].astype(ye.dtype)
    out = jnp.zeros((t + 1, d), ye.dtype).at[slot_token].add(ye_flat)[:t]
    return out.reshape(b, s, d).astype(x.dtype), aux.astype(jnp.float32)


def _moe_forward_shard_map(
    p: Params, x: jnp.ndarray, cfg: ModelConfig, rules
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Explicit expert-parallel MoE: shard_map over (batch=data, expert=model).

    The activation x is sharded over ``data`` and REPLICATED over ``model``
    (standard Megatron layout), so each model-row device already holds every
    local token: it routes them, slices out ITS experts' capacity buffers,
    runs the local expert MLPs, combines its experts' outputs locally, and a
    single psum over ``model`` completes the token outputs — identical wire
    cost to a dense row-parallel MLP (one (T_loc, D) all-reduce per layer).
    A first iteration used all_to_all as if tokens were model-sharded; with
    replicated x that ships msize identical copies (measured 16× FLOP and
    a2a inflation) — see EXPERIMENTS.md §Perf.

    The shard_map runs over an Auto-typed view of the rules' mesh. The
    model's sharding is GSPMD-style (``pjit_rules.constrain``), and
    ``jax.make_mesh`` now defaults to Explicit axes: over those, the output
    would carry a data-sharded type into the rest of the model, and the
    weight gradient of the unembedding, a contraction over that sharded
    batch, has no unambiguous output sharding."""
    from jax.sharding import AxisType, Mesh
    from jax.sharding import PartitionSpec as P

    mesh = rules["_mesh"]
    mesh = Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))
    dp = rules.get("batch") or ("data",)
    model_axis = "model"
    msize = mesh.shape[model_axis]
    e = cfg.n_experts
    assert e % msize == 0, (e, msize)
    b, s, d = x.shape

    has_gate = "w_gate" in p

    def body(xb, router, *weights):
        # xb (B_loc, S, D); expert weights local: (E_loc, D, F)
        if has_gate:
            w_up, w_gate, w_down = weights
        else:
            (w_up, w_down), w_gate = weights, None
        b_loc = xb.shape[0]
        t_loc = b_loc * s
        xf = xb.reshape(t_loc, d)
        # routing stats must be averaged globally BEFORE the me·ce product
        # (mean of per-shard products ≠ the global-batch Switch loss)
        router_logits = (xf @ router).astype(jnp.float32)
        probs = jax.nn.softmax(router_logits, axis=-1)
        top_vals, top_idx = jax.lax.top_k(probs, cfg.top_k)
        top_w = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)
        me = jax.lax.pmean(jnp.mean(probs, axis=0), dp)
        ce_stat = jax.lax.pmean(
            jnp.mean(
                jnp.sum(jax.nn.one_hot(top_idx, e, dtype=jnp.float32), axis=1),
                axis=0,
            ),
            dp,
        )
        aux = e * jnp.sum(me * ce_stat) * cfg.router_aux_coef
        cap = expert_capacity(cfg, t_loc)
        xe, slot_token, slot_w = _sort_dispatch(xf, top_idx, top_w, e, cap)
        # slice MY experts' buffers (x is replicated over model — the tokens
        # are already here; no all_to_all needed)
        e_loc = e // msize
        midx = jax.lax.axis_index(model_axis)
        xr = jax.lax.dynamic_slice_in_dim(xe, midx * e_loc, e_loc, axis=0)
        st_r = jax.lax.dynamic_slice_in_dim(
            slot_token.reshape(e, cap), midx * e_loc, e_loc, axis=0
        ).reshape(e_loc * cap)
        sw_r = jax.lax.dynamic_slice_in_dim(
            slot_w.reshape(e, cap), midx * e_loc, e_loc, axis=0
        ).reshape(e_loc * cap)
        pe = {"w_up": w_up, "w_down": w_down}
        if w_gate is not None:
            pe["w_gate"] = w_gate
        yr = _expert_mlp(pe, xr, cfg)                      # (E_loc, C, D)
        yr_flat = yr.reshape(e_loc * cap, d) * sw_r[:, None].astype(yr.dtype)
        partial = jnp.zeros((t_loc + 1, d), yr.dtype).at[st_r].add(yr_flat)[:t_loc]
        # each device contributed its experts; one TP-style all-reduce
        out = jax.lax.psum(partial, model_axis)
        return out.reshape(b_loc, s, d).astype(xb.dtype), aux

    expert_spec = P(model_axis, None, None)
    weights = (p["w_up"], p["w_gate"], p["w_down"]) if has_gate else (
        p["w_up"], p["w_down"]
    )
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(dp, None, None), P(None, None)) + (expert_spec,) * len(weights),
        out_specs=(P(dp, None, None), P()),
        check_vma=False,
    )
    out, aux = fn(x, p["router"], *weights)
    return out, aux.astype(jnp.float32)
