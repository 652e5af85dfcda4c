"""Random weights from the seed, made by the benchmark (not by the program)
in the layout the program serves: one jitted call on the device, in the
dtype the configuration states.

Layout: ``{"embed": {"tok": (V, D), "final_norm": (D,)}, "groups": ({...},)}``
with one dense group whose leaves are stacked over the layers. Norm weights
are stored as ``weight - 1``, as the program stores them.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from .model import Dims


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed (more than 32 bits kept)."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _make(key, d: Dims, w: Dict) -> Dict:
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


def make_params(dims: Dims, wspec: Dict, seed: int, device) -> Dict:
    """All weights of one model from ``seed``, on ``device``, in one call."""
    fn = jax.jit(lambda k: _make(k, dims, wspec))
    with jax.default_device(device):
        params = fn(key_from_seed(seed))
    return jax.block_until_ready(params)


def check_layout(params, expected) -> None:
    """Raise unless ``params`` has the tree, shapes and dtypes of
    ``expected`` (the program's abstract parameters)."""
    got = jax.tree_util.tree_structure(params)
    want = jax.tree_util.tree_structure(expected)
    if got != want:
        raise ValueError(f"weight tree differs from the program's:\n{got}\n{want}")
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(expected)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"weight leaf {a.shape}/{a.dtype} != {b.shape}/{b.dtype}")
