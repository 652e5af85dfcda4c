"""The Qwen2 block (``model_type`` ``qwen2``: Qwen1.5 and Qwen2), as the
benchmark knows it: its sizes, the program's weight layout, the plain
reference forward and the operations and bytes of its kernels.

The reference is a float32 forward pass in ``jax.numpy``, written from the
published description (RMSNorm with the weight stored as ``weight - 1``,
rotary embedding on half-split head dims, grouped-query attention with QKV
bias, SwiGLU MLP, tied embeddings). No kernel, cache, paging or batching;
every product at HIGHEST precision. It imports nothing of the program.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.flops import BF16, pages
from bench.reference import HIGHEST, _fq, _mm, _rms, _rope


@dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    d_head: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float


def dims_of(conf: Dict) -> Dims:
    return Dims(
        layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"],
        heads=conf["num_attention_heads"],
        kv_heads=conf["num_key_value_heads"],
        d_head=conf["head_dim"],
        d_ff=conf["intermediate_size"],
        vocab=conf["vocab_size"],
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
    )


def check_program_config(d: Dims, cfg) -> None:
    """Raise unless the program's ``ModelConfig`` runs the sizes the file
    states (the benchmark's reference follows the file)."""
    pairs = {
        "num_hidden_layers": (d.layers, cfg.n_layers),
        "hidden_size": (d.d_model, cfg.d_model),
        "num_attention_heads": (d.heads, cfg.n_heads),
        "num_key_value_heads": (d.kv_heads, cfg.n_kv_heads),
        "head_dim": (d.d_head, cfg.d_head),
        "intermediate_size": (d.d_ff, cfg.d_ff),
        "vocab_size": (d.vocab, cfg.vocab_size),
        "rope_theta": (d.rope_theta, float(cfg.rope_theta)),
        "rms_norm_eps": (d.norm_eps, float(cfg.norm_eps)),
    }
    bad = {k: v for k, v in pairs.items() if v[0] != v[1]}
    if bad:
        raise ValueError(f"program config differs from the benchmark's file: {bad}")
    if not (cfg.qkv_bias and cfg.tie_embeddings and cfg.mlp_type == "swiglu"
            and cfg.rope_style == "standard" and cfg.attn_softcap == 0.0
            and cfg.logit_softcap == 0.0 and cfg.arch_type == "dense"):
        raise ValueError("program config is not the Qwen2 block the reference follows")


# ----------------------------------------------------------------------------
# weights
# ----------------------------------------------------------------------------

def make(key, d: Dims, w: Dict) -> Dict:
    """``{"embed": {"tok": (V, D), "final_norm": (D,)}, "groups": ({...},)}``
    with one dense group whose leaves are stacked over the layers. Norm
    weights are stored as ``weight - 1``, as the program stores them."""
    dt = jnp.dtype(w["dtype"])
    L, D, H, KV, Dh, F, V = d.layers, d.d_model, d.heads, d.kv_heads, d.d_head, d.d_ff, d.vocab
    ks = iter(jax.random.split(key, 16))

    def normal(shape, std):
        return (jax.random.normal(next(ks), shape, jnp.float32) * std).astype(dt)

    tok = jax.random.normal(next(ks), (V, D), jnp.float32) * w["embed_std"]
    special = w["special_rows"]
    tok = tok.at[:special].multiply(w["special_row_scale"])
    attn = {
        "wq": normal((L, D, H * Dh), D ** -0.5),
        "wk": normal((L, D, KV * Dh), D ** -0.5),
        "wv": normal((L, D, KV * Dh), D ** -0.5),
        "wo": normal((L, H * Dh, D), (H * Dh) ** -0.5),
        "bq": normal((L, H * Dh), w["bias_std"]),
        "bk": normal((L, KV * Dh), w["bias_std"]),
        "bv": normal((L, KV * Dh), w["bias_std"]),
    }
    mlp = {
        "w_up": normal((L, D, F), D ** -0.5),
        "w_down": normal((L, F, D), F ** -0.5),
        "w_gate": normal((L, D, F), D ** -0.5),
    }
    block = {
        "norm1": normal((L, D), w["norm_std"]),
        "attn": attn,
        "norm2": normal((L, D), w["norm_std"]),
        "mlp": mlp,
    }
    return {
        "embed": {"tok": tok.astype(dt), "final_norm": normal((D,), w["norm_std"])},
        "groups": (block,),
    }


# ----------------------------------------------------------------------------
# the plain reference
# ----------------------------------------------------------------------------

def hidden(params, d: Dims, tokens, quant):
    """Final hidden states (S, D) of one causal sequence."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    s = tokens.shape[0]
    pos = jnp.arange(s, dtype=jnp.int32)
    x = f32(params["embed"]["tok"][tokens])
    causal = pos[None, :] <= pos[:, None]                    # (S_q, S_k)
    g = d.heads // d.kv_heads

    def layer(x, bp):
        bp = jax.tree.map(f32, bp)
        a = bp["attn"]
        h = _rms(x, bp["norm1"], d.norm_eps)
        q = (_mm(h, a["wq"], quant) + a["bq"]).reshape(s, d.kv_heads, g, d.d_head)
        k = (_mm(h, a["wk"], quant) + a["bk"]).reshape(s, d.kv_heads, d.d_head)
        v = (_mm(h, a["wv"], quant) + a["bv"]).reshape(s, d.kv_heads, d.d_head)
        q = _rope(q.reshape(s, d.heads, d.d_head), pos, d.rope_theta)
        q = q.reshape(s, d.kv_heads, g, d.d_head)
        k = _rope(k, pos, d.rope_theta)
        scores = jnp.einsum("skgd,tkd->kgst", _fq(q, -1, quant), _fq(k, -1, quant),
                            precision=HIGHEST) / np.sqrt(d.d_head)
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("kgst,tkd->skgd", _fq(p, -1, quant), _fq(v, 0, quant),
                       precision=HIGHEST)
        x = x + _mm(o.reshape(s, d.heads * d.d_head), a["wo"], quant)
        m = bp["mlp"]
        h = _rms(x, bp["norm2"], d.norm_eps)
        act = jax.nn.silu(_mm(h, m["w_gate"], quant)) * _mm(h, m["w_up"], quant)
        return x + _mm(act, m["w_down"], quant), None

    x, _ = jax.lax.scan(layer, x, params["groups"][0])
    return x


def logits(params, d: Dims, rows, quant):
    h = _rms(rows, params["embed"]["final_norm"].astype(jnp.float32), d.norm_eps)
    tok = params["embed"]["tok"].astype(jnp.float32)
    return jnp.matmul(_fq(h, -1, quant), _fq(tok, -1, quant).T, precision=HIGHEST)


# ----------------------------------------------------------------------------
# operations and bytes
# ----------------------------------------------------------------------------

def layer_matmul_params(d: Dims) -> int:
    """Weights one token multiplies through in one layer."""
    qo = 2 * d.d_model * d.heads * d.d_head
    kv = 2 * d.d_model * d.kv_heads * d.d_head
    mlp = 3 * d.d_model * d.d_ff
    return qo + kv + mlp


def attention_flops(d: Dims, n_key_pairs: int) -> float:
    """q.k and p.v over ``n_key_pairs`` (query, key) pairs, all heads."""
    return 4.0 * d.heads * d.d_head * n_key_pairs


def token_flops(d: Dims, kv_len: int, logits: bool) -> float:
    per_layer = 2.0 * layer_matmul_params(d) + attention_flops(d, kv_len)
    return d.layers * per_layer + (2.0 * d.d_model * d.vocab if logits else 0.0)


def chunk_flops(d: Dims, c: int, p0: int) -> float:
    """Causal: row r of the chunk attends to p0 + r + 1 keys."""
    pairs = c * p0 + c * (c + 1) // 2
    per_layer = 2.0 * layer_matmul_params(d) * c + attention_flops(d, pairs)
    return d.layers * per_layer + 2.0 * d.d_model * d.vocab


def page_bytes(d: Dims, page_size: int) -> int:
    """K and V of one page in one layer."""
    return 2 * page_size * d.kv_heads * d.d_head * BF16


def paged_attention_call(d: Dims, kv_lens: Sequence[int], shared_pages: int,
                         page_size: int) -> List[Tuple[int, float, float]]:
    flops = sum(attention_flops(d, n) for n in kv_lens)
    n_pages = shared_pages + sum(max(0, pages(n, page_size) - shared_pages)
                                 for n in kv_lens)
    qo = 2 * len(kv_lens) * d.heads * d.d_head * BF16
    return [(d.layers, flops, n_pages * page_bytes(d, page_size) + qo)]


def chunked_prefill_call(d: Dims, c: int, p0: int, page_size: int
                         ) -> List[Tuple[int, float, float]]:
    flops = attention_flops(d, c * p0 + c * (c + 1) // 2)
    qo = 2 * c * d.heads * d.d_head * BF16
    return [(d.layers, flops, pages(p0 + c, page_size) * page_bytes(d, page_size) + qo)]
