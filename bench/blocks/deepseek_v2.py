"""The DeepSeek-V2 block (``model_type`` ``deepseek_v2``: DeepSeek-V2-Lite),
as the benchmark knows it: its sizes, the program's weight layout, the
plain reference forward and the operations and bytes of its kernels.

The reference is a float32 forward pass in ``jax.numpy``, written from the
published description (DeepSeek-V2, arXiv:2405.04434; YaRN,
arXiv:2309.00071): RMSNorm with the weight stored as ``weight - 1``;
multi-head latent attention without query compression, in its published,
un-absorbed form (keys and values expanded per head from the normed
latent, one RoPE key shared by the heads); YaRN frequencies on half-split
rotary dims; a dense SwiGLU first layer, then DeepSeekMoE layers: a
softmax router over every published expert, greedy top-k, the gate weights
neither renormalised nor scaled where the file says so, the routed experts
of the held share only, and the shared experts. No kernel, cache, paging
or batching; every product at HIGHEST precision. It imports nothing of the
program, and casts each layer's weights to float32 inside its scan.

The held share: the deployment splits each MoE layer's experts over the
chips of an expert-parallel group, and the cell serves one chip's share
(``deployment.experts_held``, first and count). The router scores all
``deployment.n_routed_experts_published`` experts; a token's routed output
is the sum over the held experts it picked.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.flops import BF16, pages
from bench.reference import HIGHEST, _fq, _mm, _rms


@dataclass(frozen=True)
class Dims:
    layers: int
    dense_layers: int         # first_k_dense_replace
    d_model: int
    heads: int
    q_nope: int
    q_rope: int
    v_head: int
    kv_rank: int
    d_ff: int                 # the dense layers' SwiGLU
    moe_ff: int               # one routed or shared expert's SwiGLU
    experts: int              # the router's outputs (published)
    held_first: int
    held: int                 # routed experts whose weights are here
    shared: int
    top_k: int
    norm_topk: bool
    routed_scale: float
    vocab: int
    rope_theta: float
    norm_eps: float
    yarn: Tuple[float, int, float, float, float, float]
    # factor, original max positions, beta_fast, beta_slow, mscale, mscale_all_dim

    @property
    def latent(self) -> int:
        return self.kv_rank + self.q_rope

    @property
    def moe_layers(self) -> int:
        return self.layers - self.dense_layers


def dims_of(conf: Dict) -> Dims:
    rs = conf["rope_scaling"]
    dep = conf["deployment"]
    first, held = dep["experts_held"]
    if held != conf["n_routed_experts"]:
        raise ValueError("the file's n_routed_experts is the held share's count")
    return Dims(
        layers=conf["num_hidden_layers"],
        dense_layers=conf["first_k_dense_replace"],
        d_model=conf["hidden_size"],
        heads=conf["num_attention_heads"],
        q_nope=conf["qk_nope_head_dim"],
        q_rope=conf["qk_rope_head_dim"],
        v_head=conf["v_head_dim"],
        kv_rank=conf["kv_lora_rank"],
        d_ff=conf["intermediate_size"],
        moe_ff=conf["moe_intermediate_size"],
        experts=dep["n_routed_experts_published"],
        held_first=first,
        held=held,
        shared=conf["n_shared_experts"],
        top_k=conf["num_experts_per_tok"],
        norm_topk=bool(conf["norm_topk_prob"]),
        routed_scale=float(conf["routed_scaling_factor"]),
        vocab=conf["vocab_size"],
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        yarn=(float(rs["factor"]), int(rs["original_max_position_embeddings"]),
              float(rs["beta_fast"]), float(rs["beta_slow"]), float(rs["mscale"]),
              float(rs["mscale_all_dim"])),
    )


def check_program_config(d: Dims, cfg) -> None:
    """Raise unless the program's ``ModelConfig`` runs the sizes the file
    states (the benchmark's reference follows the file)."""
    factor, orig, fast, slow, ms, ms_all = d.yarn
    pairs = {
        "num_hidden_layers": (d.layers, cfg.n_layers),
        "first_k_dense_replace": (d.dense_layers, cfg.first_dense_layers),
        "hidden_size": (d.d_model, cfg.d_model),
        "num_attention_heads": (d.heads, cfg.n_heads),
        "qk_nope_head_dim": (d.q_nope, cfg.qk_nope_dim),
        "qk_rope_head_dim": (d.q_rope, cfg.qk_rope_dim),
        "v_head_dim": (d.v_head, cfg.v_head_dim),
        "kv_lora_rank": (d.kv_rank, cfg.kv_lora_rank),
        "intermediate_size": (d.d_ff, cfg.d_ff),
        "moe_intermediate_size": (d.moe_ff, cfg.expert_width),
        "n_routed_experts_published": (d.experts, cfg.n_experts),
        "experts_held": ((d.held_first, d.held), tuple(cfg.held_experts)),
        "n_shared_experts": (d.shared, cfg.n_shared_experts),
        "num_experts_per_tok": (d.top_k, cfg.top_k),
        "norm_topk_prob": (d.norm_topk, cfg.router_norm_topk),
        "routed_scaling_factor": (d.routed_scale, float(cfg.routed_scale)),
        "vocab_size": (d.vocab, cfg.vocab_size),
        "rope_theta": (d.rope_theta, float(cfg.rope_theta)),
        "rms_norm_eps": (d.norm_eps, float(cfg.norm_eps)),
        "rope_scaling": (d.yarn, (float(cfg.rope_factor), cfg.rope_original_max,
                                  cfg.yarn_beta_fast, cfg.yarn_beta_slow,
                                  cfg.yarn_mscale, cfg.yarn_mscale_all_dim)),
    }
    bad = {k: v for k, v in pairs.items() if v[0] != v[1]}
    if bad:
        raise ValueError(f"program config differs from the benchmark's file: {bad}")
    if not (cfg.attn_type == "mla" and cfg.mlp_type == "swiglu"
            and not cfg.tie_embeddings and not cfg.qkv_bias
            and cfg.rope_style == "standard" and cfg.attn_softcap == 0.0
            and cfg.logit_softcap == 0.0 and cfg.arch_type == "moe"):
        raise ValueError("program config is not the DeepSeek-V2 block the reference follows")


# ----------------------------------------------------------------------------
# weights
# ----------------------------------------------------------------------------

def make(key, d: Dims, w: Dict) -> Dict:
    """``{"embed": {"tok", "final_norm", "lm_head"}, "groups": (dense,
    moe)}``, each group's leaves stacked over its layers: the dense
    layers' SwiGLU under ``mlp``; the MoE layers' ``moe`` holding the
    router over every published expert, the held experts' ``w_up``,
    ``w_gate`` (E, D, F) and ``w_down`` (E, F, D), and the shared experts
    as one SwiGLU under ``shared``. Norm weights are stored as
    ``weight - 1``, as the program stores them."""
    dt = jnp.dtype(w["dtype"])
    D, H, V = d.d_model, d.heads, d.vocab
    ks = iter(jax.random.split(key, 32))

    def normal(shape, std):
        return (jax.random.normal(next(ks), shape, jnp.float32) * std).astype(dt)

    def attn(L):
        return {
            "wq": normal((L, D, H * (d.q_nope + d.q_rope)), D ** -0.5),
            "wkv_a": normal((L, D, d.latent), D ** -0.5),
            "kv_norm": normal((L, d.kv_rank), w["norm_std"]),
            "wkv_b": normal((L, d.kv_rank, H * (d.q_nope + d.v_head)), d.kv_rank ** -0.5),
            "wo": normal((L, H * d.v_head, D), (H * d.v_head) ** -0.5),
        }

    def swiglu(lead, f):
        return {
            "w_up": normal(lead + (D, f), D ** -0.5),
            "w_gate": normal(lead + (D, f), D ** -0.5),
            "w_down": normal(lead + (f, D), f ** -0.5),
        }

    def block(L, ffn):
        return {"norm1": normal((L, D), w["norm_std"]), "attn": attn(L),
                "norm2": normal((L, D), w["norm_std"]), **ffn}

    L0, L1 = d.dense_layers, d.moe_layers
    moe = swiglu((L1, d.held), d.moe_ff)
    moe["router"] = normal((L1, D, d.experts), D ** -0.5)
    moe["shared"] = swiglu((L1,), d.shared * d.moe_ff)
    tok = jax.random.normal(next(ks), (V, D), jnp.float32) * w["embed_std"]
    head = jax.random.normal(next(ks), (D, V), jnp.float32) * w["embed_std"]
    special = w["special_rows"]
    head = head.at[:, :special].multiply(w["special_row_scale"])
    return {
        "embed": {"tok": tok.astype(dt), "final_norm": normal((D,), w["norm_std"]),
                  "lm_head": head.astype(dt)},
        "groups": (block(L0, {"mlp": swiglu((L0,), d.d_ff)}), block(L1, {"moe": moe})),
    }


# ----------------------------------------------------------------------------
# the plain reference
# ----------------------------------------------------------------------------

def yarn_freqs(d: Dims) -> Tuple[np.ndarray, float]:
    """YaRN's inverse frequencies of the RoPE dims, and the factor on cos
    and sin (DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding``)."""
    factor, orig, fast, slow, ms, ms_all = d.yarn
    dim, base = d.q_rope, d.rope_theta

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low, high = max(math.floor(corr(fast)), 0), min(math.ceil(corr(slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inv = extra / factor * ramp + extra * (1.0 - ramp)
    return inv.astype(np.float32), mscale(factor, ms) / mscale(factor, ms_all)


def mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * m * math.log(factor) + 1.0


def softmax_scale(d: Dims) -> float:
    m = mscale(d.yarn[0], d.yarn[5])
    return (d.q_nope + d.q_rope) ** -0.5 * m * m


def _rope(x, pos, d: Dims):
    inv, m = yarn_freqs(d)
    ang = pos[:, None].astype(jnp.float32) * inv               # (S, rope/2)
    cos, sin = jnp.cos(ang)[:, None, :] * m, jnp.sin(ang)[:, None, :] * m
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _ein(spec, a, b, quant, a_axis, b_axis):
    return jnp.einsum(spec, _fq(a, a_axis, quant), _fq(b, b_axis, quant),
                      precision=HIGHEST)


def _swiglu(m, h, quant):
    act = jax.nn.silu(_mm(h, m["w_gate"], quant)) * _mm(h, m["w_up"], quant)
    return _mm(act, m["w_down"], quant)


def _moe(m, h, d: Dims, quant):
    probs = jax.nn.softmax(_mm(h, m["router"], quant), axis=-1)   # (S, E)
    top_w, top_i = jax.lax.top_k(probs, d.top_k)
    if d.norm_topk:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    top_w = top_w * d.routed_scale
    held = jnp.arange(d.held_first, d.held_first + d.held)
    gates = jnp.sum(jnp.where(top_i[:, :, None] == held, top_w[:, :, None], 0.0),
                    axis=1)                                      # (S, held)
    up = _ein("sd,edf->sef", h, m["w_up"], quant, -1, 1)
    gate = _ein("sd,edf->sef", h, m["w_gate"], quant, -1, 1)
    y = _ein("sef,efd->sed", jax.nn.silu(gate) * up, m["w_down"], quant, -1, 1)
    routed = jnp.einsum("se,sed->sd", gates, y, precision=HIGHEST)
    return routed + _swiglu(m["shared"], h, quant)


def hidden(params, d: Dims, tokens, quant):
    """Final hidden states (S, D) of one causal sequence."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    s = tokens.shape[0]
    pos = jnp.arange(s, dtype=jnp.int32)
    x = f32(params["embed"]["tok"][tokens])
    causal = pos[None, :] <= pos[:, None]                    # (S_q, S_k)
    H, nope, r = d.heads, d.q_nope, d.kv_rank
    scale = softmax_scale(d)

    def attention(x, bp):
        a = bp["attn"]
        h = _rms(x, bp["norm1"], d.norm_eps)
        q = _mm(h, a["wq"], quant).reshape(s, H, nope + d.q_rope)
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, d)], -1)
        kv = _mm(h, a["wkv_a"], quant)
        c = _rms(kv[:, :r], a["kv_norm"], d.norm_eps)
        k_pe = _rope(kv[:, None, r:], pos, d)                 # (S, 1, rope)
        kvb = _mm(c, a["wkv_b"], quant).reshape(s, H, nope + d.v_head)
        k = jnp.concatenate(
            [kvb[..., :nope], jnp.broadcast_to(k_pe, (s, H, d.q_rope))], -1)
        scores = _ein("shd,thd->hst", q, k, quant, -1, -1) * scale
        scores = jnp.where(causal[None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        o = _ein("hst,thd->shd", p, kvb[..., nope:], quant, -1, 0)
        return x + _mm(o.reshape(s, H * d.v_head), a["wo"], quant)

    def dense(x, bp):
        bp = jax.tree.map(f32, bp)
        x = attention(x, bp)
        return x + _swiglu(bp["mlp"], _rms(x, bp["norm2"], d.norm_eps), quant), None

    def moe(x, bp):
        bp = jax.tree.map(f32, bp)
        x = attention(x, bp)
        return x + _moe(bp["moe"], _rms(x, bp["norm2"], d.norm_eps), d, quant), None

    x, _ = jax.lax.scan(dense, x, params["groups"][0])
    x, _ = jax.lax.scan(moe, x, params["groups"][1])
    return x


def logits(params, d: Dims, rows, quant):
    h = _rms(rows, params["embed"]["final_norm"].astype(jnp.float32), d.norm_eps)
    return _mm(h, params["embed"]["lm_head"].astype(jnp.float32), quant)


# ----------------------------------------------------------------------------
# operations and bytes
# ----------------------------------------------------------------------------

def attn_params(d: Dims) -> int:
    """Attention weights one token multiplies through in one layer, in the
    published form (its latent expanded to its own keys and values once)."""
    H = d.heads
    return (d.d_model * H * (d.q_nope + d.q_rope) + d.d_model * d.latent
            + d.kv_rank * H * (d.q_nope + d.v_head) + H * d.v_head * d.d_model)


def routed_pairs(d: Dims) -> float:
    """Held experts one token runs through in one MoE layer, expected under
    uniform routing: top_k x held / experts (0.75 for 8 of 64, top-6)."""
    return d.top_k * d.held / d.experts


def layer_matmul_params(d: Dims) -> Tuple[float, float]:
    """Weights one token multiplies through: (a dense layer, a MoE layer)
    — the router and the shared experts in full, the routed experts as
    :func:`routed_pairs` of one expert's."""
    swiglu = 3 * d.d_model
    moe = (d.d_model * d.experts + swiglu * d.shared * d.moe_ff
           + routed_pairs(d) * swiglu * d.moe_ff)
    return attn_params(d) + swiglu * d.d_ff, attn_params(d) + moe


def attention_flops(d: Dims, n_key_pairs: int) -> float:
    """q.k over the nope and rope dims and p.v, over ``n_key_pairs``
    (query, key) pairs, all heads (the published form)."""
    return 2.0 * d.heads * (d.q_nope + d.q_rope + d.v_head) * n_key_pairs


def _layers_flops(d: Dims, tokens: int, pairs: int) -> float:
    dense, moe = layer_matmul_params(d)
    per = d.dense_layers * dense + d.moe_layers * moe
    return 2.0 * per * tokens + d.layers * attention_flops(d, pairs)


def token_flops(d: Dims, kv_len: int, logits: bool) -> float:
    return _layers_flops(d, 1, kv_len) + (2.0 * d.d_model * d.vocab if logits else 0.0)


def chunk_flops(d: Dims, c: int, p0: int) -> float:
    """Causal: row r of the chunk attends to p0 + r + 1 keys."""
    return _layers_flops(d, c, c * p0 + c * (c + 1) // 2) + 2.0 * d.d_model * d.vocab


def key_bytes(d: Dims) -> int:
    """One token's latent row in one layer: the key, and its leading lanes
    the value."""
    return d.latent * BF16


def absorbed_flops(d: Dims, n_key_pairs: int) -> float:
    """The kernels' work in the absorbed form: every head's latent query
    against the row (latent lanes), and the probabilities against the
    value lanes, per (query, key) pair."""
    return 2.0 * d.heads * (d.latent + d.kv_rank) * n_key_pairs


def _per_layer_kind(d: Dims, flops: float, nbytes: float) -> List[Tuple[int, float, float]]:
    """The dense layers and the MoE layers run the same attention."""
    return [(d.dense_layers, flops, nbytes), (d.moe_layers, flops, nbytes)]


def paged_attention_call(d: Dims, kv_lens: Sequence[int], shared_pages: int,
                         page_size: int) -> List[Tuple[int, float, float]]:
    flops = sum(absorbed_flops(d, n) for n in kv_lens)
    n_pages = shared_pages + sum(max(0, pages(n, page_size) - shared_pages)
                                 for n in kv_lens)
    qo = len(kv_lens) * d.heads * (d.latent + d.kv_rank) * BF16
    return _per_layer_kind(d, flops, n_pages * page_size * key_bytes(d) + qo)


def chunked_prefill_call(d: Dims, c: int, p0: int, page_size: int
                         ) -> List[Tuple[int, float, float]]:
    flops = absorbed_flops(d, c * p0 + c * (c + 1) // 2)
    qo = c * d.heads * (d.latent + d.kv_rank) * BF16
    return _per_layer_kind(
        d, flops, pages(p0 + c, page_size) * page_size * key_bytes(d) + qo)
