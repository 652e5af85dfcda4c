"""A toy block with two kinds of layer, for the tests only: ``full`` layers
of causal attention, then ``windowed`` layers that attend to the last
``window`` keys alone. No program serves it. It shows that an architecture
enters the benchmark by a module of its own under ``bench/blocks/``
(here registered by the test), with no edit of a shared file."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.flops import BF16, pages
from bench.reference import HIGHEST, _fq, _mm, _rms, _rope


@dataclass(frozen=True)
class Dims:
    full: int
    windowed: int
    window: int
    d_model: int
    heads: int
    d_head: int
    d_ff: int
    vocab: int
    norm_eps: float


def dims_of(conf: Dict) -> Dims:
    return Dims(full=conf["full_layers"], windowed=conf["windowed_layers"],
                window=conf["window"], d_model=conf["hidden_size"],
                heads=conf["num_attention_heads"], d_head=conf["head_dim"],
                d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
                norm_eps=float(conf["rms_norm_eps"]))


def check_program_config(d: Dims, cfg) -> None:
    raise ValueError("no program serves the toy block")


def make(key, d: Dims, w: Dict) -> Dict:
    dt = jnp.dtype(w["dtype"])
    D, HD, F = d.d_model, d.heads * d.d_head, d.d_ff
    ks = iter(jax.random.split(key, 16))

    def normal(shape, std):
        return (jax.random.normal(next(ks), shape, jnp.float32) * std).astype(dt)

    def group(n):
        return {"norm1": normal((n, D), w["norm_std"]),
                "w_qkv": normal((n, D, 3 * HD), D ** -0.5),
                "w_o": normal((n, HD, D), HD ** -0.5),
                "norm2": normal((n, D), w["norm_std"]),
                "w_in": normal((n, D, F), D ** -0.5),
                "w_out": normal((n, F, D), F ** -0.5)}

    return {"embed": {"tok": normal((d.vocab, D), w["embed_std"]),
                      "final_norm": normal((D,), w["norm_std"])},
            "groups": (group(d.full), group(d.windowed))}


def hidden(params, d: Dims, tokens, quant):
    s = tokens.shape[0]
    pos = jnp.arange(s, dtype=jnp.int32)
    x = params["embed"]["tok"][tokens].astype(jnp.float32)
    causal = pos[None, :] <= pos[:, None]
    masks = (causal, causal & (pos[:, None] - pos[None, :] < d.window))

    for mask, group in zip(masks, params["groups"]):
        def layer(x, bp, mask=mask):
            bp = jax.tree.map(lambda a: a.astype(jnp.float32), bp)
            h = _rms(x, bp["norm1"], d.norm_eps)
            q, k, v = jnp.split(_mm(h, bp["w_qkv"], quant).reshape(s, 3 * d.heads, d.d_head), 3,
                                axis=1)
            q, k = _rope(q, pos, 1e4), _rope(k, pos, 1e4)
            sc = jnp.einsum("shd,thd->hst", _fq(q, -1, quant), _fq(k, -1, quant),
                            precision=HIGHEST) / np.sqrt(d.d_head)
            p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
            o = jnp.einsum("hst,thd->shd", _fq(p, -1, quant), _fq(v, 0, quant),
                           precision=HIGHEST)
            x = x + _mm(o.reshape(s, -1), bp["w_o"], quant)
            h = _rms(x, bp["norm2"], d.norm_eps)
            return x + _mm(jax.nn.gelu(_mm(h, bp["w_in"], quant)), bp["w_out"], quant), None

        x, _ = jax.lax.scan(layer, x, group)
    return x


def logits(params, d: Dims, rows, quant):
    h = _rms(rows, params["embed"]["final_norm"].astype(jnp.float32), d.norm_eps)
    tok = params["embed"]["tok"].astype(jnp.float32)
    return jnp.matmul(_fq(h, -1, quant), _fq(tok, -1, quant).T, precision=HIGHEST)


def _kinds(d: Dims):
    """(layers, keys a query at position ``n - 1`` attends) of each kind."""
    return ((d.full, lambda n: n), (d.windowed, lambda n: min(n, d.window)))


def _layer_flops(d: Dims, pairs: int, rows: int) -> float:
    weights = d.d_model * 4 * d.heads * d.d_head + 2 * d.d_model * d.d_ff
    return 2.0 * weights * rows + 4.0 * d.heads * d.d_head * pairs


def token_flops(d: Dims, kv_len: int, logits: bool) -> float:
    return (sum(n * _layer_flops(d, keys(kv_len), 1) for n, keys in _kinds(d))
            + (2.0 * d.d_model * d.vocab if logits else 0.0))


def chunk_flops(d: Dims, c: int, p0: int) -> float:
    return (sum(n * _layer_flops(d, sum(keys(p0 + r + 1) for r in range(c)), c)
                for n, keys in _kinds(d)) + 2.0 * d.d_model * d.vocab)


def _page_bytes(d: Dims, page_size: int) -> int:
    return 2 * page_size * d.heads * d.d_head * BF16


def paged_attention_call(d: Dims, kv_lens, shared_pages: int, page_size: int):
    out = []
    for n, keys in _kinds(d):
        read = sum(pages(keys(k), page_size) for k in kv_lens)
        qo = 2 * len(kv_lens) * d.heads * d.d_head * BF16
        out.append((n, 4.0 * d.heads * d.d_head * sum(keys(k) for k in kv_lens),
                    read * _page_bytes(d, page_size) + qo))
    return out


def chunked_prefill_call(d: Dims, c: int, p0: int, page_size: int):
    out = []
    for n, keys in _kinds(d):
        pairs = sum(keys(p0 + r + 1) for r in range(c))
        qo = 2 * c * d.heads * d.d_head * BF16
        out.append((n, 4.0 * d.heads * d.d_head * pairs,
                    pages(keys(p0 + c), page_size) * _page_bytes(d, page_size) + qo))
    return out
