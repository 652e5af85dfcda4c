"""Shared neural layers: norms, RoPE variants, MLPs, embeddings.

Pure-JAX, parameter pytrees are plain dicts so they stay trivially
shardable with pjit (no framework module state).
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig

Params = Dict[str, jnp.ndarray]


def dtype_of(name: str):
    return jnp.dtype(name)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + weight.astype(jnp.float32))).astype(dt)


def init_rms_norm(d: int, dtype) -> jnp.ndarray:
    return jnp.zeros((d,), dtype=dtype)  # stored as (weight - 1)


# ---------------------------------------------------------------------------
# Softcap (gemma2)
# ---------------------------------------------------------------------------

def softcap(x: jnp.ndarray, cap: float) -> jnp.ndarray:
    if cap <= 0.0:
        return x
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# RoPE — standard, ChatGLM 2D (half-rotary interleaved), and M-RoPE (3D).
# ---------------------------------------------------------------------------

def _rope_freqs(d_rot: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, d_rot, 2, dtype=jnp.float32) / d_rot))


def _apply_rotary(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x: (..., d_rot) with d_rot even; cos/sin: broadcastable (..., d_rot/2)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def rope_standard(
    x: jnp.ndarray, positions: jnp.ndarray, theta: float
) -> jnp.ndarray:
    """x: (B, S, H, Dh); positions: (B, S)."""
    d = x.shape[-1]
    freqs = _rope_freqs(d, theta)                      # (d/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B,S,d/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    return _apply_rotary(x.astype(jnp.float32), cos, sin).astype(x.dtype)


def rope_chatglm2d(
    x: jnp.ndarray, positions: jnp.ndarray, theta: float
) -> jnp.ndarray:
    """ChatGLM applies rotary to only the first half of head dims (2D RoPE:
    the remaining half passes through unrotated)."""
    d = x.shape[-1]
    d_rot = d // 2
    xr, xp = x[..., :d_rot], x[..., d_rot:]
    freqs = _rope_freqs(d_rot, theta)
    ang = positions[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    xr = _apply_rotary(xr.astype(jnp.float32), cos, sin).astype(x.dtype)
    return jnp.concatenate([xr, xp], axis=-1)


def rope_mrope(
    x: jnp.ndarray,
    positions3: jnp.ndarray,
    theta: float,
    sections: Tuple[int, int, int],
) -> jnp.ndarray:
    """Qwen2-VL M-RoPE: rotary dims split into (temporal, height, width)
    sections, each rotated by its own position id stream.

    x: (B, S, H, Dh); positions3: (3, B, S)."""
    d = x.shape[-1]
    assert sum(sections) * 2 == d, (sections, d)
    freqs = _rope_freqs(d, theta)                       # (d/2,)
    # split freq axis into the three sections
    splits = np.cumsum(sections)[:-1].tolist()
    f_parts = jnp.split(freqs, splits)
    ang_parts = [
        positions3[i][..., None].astype(jnp.float32) * f_parts[i] for i in range(3)
    ]
    ang = jnp.concatenate(ang_parts, axis=-1)           # (B,S,d/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    return _apply_rotary(x.astype(jnp.float32), cos, sin).astype(x.dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor (DeepSeek-V2's ``yarn_get_mscale``)."""
    if factor <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_freqs(cfg: ModelConfig, d_rot: int) -> Tuple[np.ndarray, float]:
    """YaRN's inverse frequencies for ``d_rot`` rotary dims and the factor
    on cos/sin (arXiv:2309.00071; DeepSeek-V2's
    ``DeepseekV2YarnRotaryEmbedding``): dims that rotate more than
    ``beta_fast`` times over the original context keep their frequency,
    those under ``beta_slow`` are divided by the factor, and a linear ramp
    blends the dims between."""
    base, factor, orig = cfg.rope_theta, cfg.rope_factor, cfg.rope_original_max
    exps = np.arange(0, d_rot, 2, dtype=np.float64) / d_rot
    extra = 1.0 / base ** exps
    inter = 1.0 / (factor * base ** exps)

    def dim_of(rotations):
        return d_rot * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(dim_of(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(dim_of(cfg.yarn_beta_slow)), d_rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d_rot // 2) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    inv = inter * (1.0 - keep) + extra * keep
    m = yarn_mscale(factor, cfg.yarn_mscale) / yarn_mscale(factor, cfg.yarn_mscale_all_dim)
    return inv.astype(np.float32), m


def rope_yarn(cfg: ModelConfig, x: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
    """Half-split rotary embedding with YaRN frequencies. x: (B, S, H, d);
    positions: (B, S)."""
    inv, m = yarn_freqs(cfg, x.shape[-1])
    ang = positions[..., None].astype(jnp.float32) * jnp.asarray(inv)
    cos, sin = jnp.cos(ang)[:, :, None, :] * m, jnp.sin(ang)[:, :, None, :] * m
    return _apply_rotary(x.astype(jnp.float32), cos, sin).astype(x.dtype)


def apply_rope(
    cfg: ModelConfig, x: jnp.ndarray, positions: jnp.ndarray
) -> jnp.ndarray:
    """positions: (B,S) for standard/chatglm2d; (3,B,S) for mrope."""
    if cfg.rope_factor > 0:
        return rope_yarn(cfg, x, positions)
    if cfg.rope_style == "standard":
        return rope_standard(x, positions, cfg.rope_theta)
    if cfg.rope_style == "chatglm2d":
        return rope_chatglm2d(x, positions, cfg.rope_theta)
    if cfg.rope_style == "mrope":
        return rope_mrope(x, positions, cfg.rope_theta, cfg.mrope_sections)
    raise ValueError(cfg.rope_style)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> Params:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    dt = dtype_of(cfg.param_dtype)
    k1, k2, k3 = jax.random.split(key, 3)
    scale_in = 1.0 / np.sqrt(d)
    scale_out = 1.0 / np.sqrt(f)
    p: Params = {
        "w_up": (jax.random.normal(k1, (d, f)) * scale_in).astype(dt),
        "w_down": (jax.random.normal(k2, (f, d)) * scale_out).astype(dt),
    }
    if cfg.mlp_type in ("swiglu", "geglu"):
        p["w_gate"] = (jax.random.normal(k3, (d, f)) * scale_in).astype(dt)
    return p


def mlp_forward(p: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    up = x @ p["w_up"]
    if cfg.mlp_type == "swiglu":
        act = jax.nn.silu(x @ p["w_gate"]) * up
    elif cfg.mlp_type == "geglu":       # gemma2: GELU-gated
        act = jax.nn.gelu(x @ p["w_gate"]) * up
    elif cfg.mlp_type == "relu2":       # nemotron-4: squared ReLU
        r = jax.nn.relu(up)
        act = r * r
    elif cfg.mlp_type == "gelu":
        act = jax.nn.gelu(up)
    else:
        raise ValueError(cfg.mlp_type)
    return act @ p["w_down"]


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def init_embed(key, cfg: ModelConfig) -> Params:
    dt = dtype_of(cfg.param_dtype)
    v, d = cfg.vocab_size, cfg.d_model
    keys = jax.random.split(key, 3)
    n_embed_tables = max(1, cfg.n_codebooks)
    p: Params = {
        "tok": (jax.random.normal(keys[0], (n_embed_tables, v, d)) * 0.02).astype(dt)
        if n_embed_tables > 1
        else (jax.random.normal(keys[0], (v, d)) * 0.02).astype(dt),
        "final_norm": init_rms_norm(d, dt),
    }
    if not cfg.tie_embeddings:
        n_heads_out = max(1, cfg.n_codebooks)
        shape = (d, n_heads_out * v) if n_heads_out > 1 else (d, v)
        p["lm_head"] = (jax.random.normal(keys[1], shape) * 0.02).astype(dt)
    return p


def embed_tokens(p: Params, tokens: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """tokens: (B,S) or (B,S,n_codebooks) for audio."""
    if cfg.n_codebooks > 1:
        # sum per-codebook embeddings (MusicGen delay-pattern streams)
        # p['tok']: (K,V,D); tokens: (B,S,K)
        out = 0.0
        for k in range(cfg.n_codebooks):
            out = out + jnp.take(p["tok"][k], tokens[..., k], axis=0)
        return out
    return jnp.take(p["tok"], tokens, axis=0)


def unembed(p: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    x = rms_norm(x, p["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        table = p["tok"] if cfg.n_codebooks <= 1 else p["tok"][0]
        logits = x @ table.T
    else:
        logits = x @ p["lm_head"]
    if cfg.n_codebooks > 1:
        b, s, _ = logits.shape
        logits = logits.reshape(b, s, cfg.n_codebooks, cfg.vocab_size)
    return softcap(logits, cfg.logit_softcap)
