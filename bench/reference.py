"""The plain reference: a float32 forward pass of the Qwen2 block in
``jax.numpy``, written from the published description (RMSNorm with the
weight stored as ``weight - 1``, rotary embedding on half-split head dims,
grouped-query attention with QKV bias, SwiGLU MLP, tied embeddings). No
kernel, cache, paging or batching; every product at HIGHEST precision.
It imports nothing of the program.

``quant="fp8"`` is the control: the same pass with every matrix product's
operands rounded to float8 e4m3 (scaled per row of the contraction), the
precision a later change would be tempted to serve at.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .model import Dims

HIGHEST = jax.lax.Precision.HIGHEST
ROWS = 256          # served tokens compared per call (padded)
MIN_BUCKET = 256    # sequence lengths are padded to a power of two >= this
E4M3_MAX = 448.0


def _fq(x, axis, quant):
    """Round ``x`` to float8 e4m3, scaled so that its largest magnitude
    along ``axis`` maps to the format's largest value; identity for f32."""
    if quant != "fp8":
        return x
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, quant):
    return jnp.matmul(_fq(x, -1, quant), _fq(w, 0, quant), precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def _rope(x, pos, theta):
    d = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * freqs          # (S, d/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _hidden(params, d: Dims, tokens, quant):
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


def _logits(params, d: Dims, rows, quant):
    h = _rms(rows, params["embed"]["final_norm"].astype(jnp.float32), d.norm_eps)
    tok = params["embed"]["tok"].astype(jnp.float32)
    return jnp.matmul(_fq(h, -1, quant), _fq(tok, -1, quant).T, precision=HIGHEST)


@partial(jax.jit, static_argnames=("d", "control"))
def _gaps(params, tokens, rows, targets, *, d: Dims, control: bool):
    """For each row: how far the reference's logit of ``targets[row]`` lies
    below its best logit. With ``control``, also that gap for the token the
    fp8 pass puts first."""
    x = _hidden(params, d, tokens, "f32")[rows]
    ref = _logits(params, d, x, "f32")
    best = jnp.max(ref, axis=-1)
    served = best - jnp.take_along_axis(ref, targets[:, None], axis=-1)[:, 0]
    if not control:
        return served, served
    xq = _hidden(params, d, tokens, "fp8")[rows]
    pick = jnp.argmax(_logits(params, d, xq, "fp8"), axis=-1)
    low = best - jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
    return served, low


def bucket(n: int) -> int:
    b = MIN_BUCKET
    while b < n:
        b *= 2
    return b


def served_gaps(params, d: Dims, input_ids: Sequence[int],
                generated: Sequence[int], control: bool = False
                ) -> Tuple[np.ndarray, np.ndarray]:
    """Gaps of every served token of one turn (its prompt ``input_ids`` as
    the model saw it, then ``generated``): the reference's best logit minus
    its logit of the served token at that position. With ``control``, the
    second array holds the gap of the fp8 pass's first choice instead."""
    ids = list(input_ids) + list(generated[:-1])
    n, g = len(input_ids), len(generated)
    assert g >= 1 and n >= 1
    sb = bucket(len(ids))
    toks = np.zeros(sb, np.int32)
    toks[: len(ids)] = ids
    out_a: List[np.ndarray] = []
    out_b: List[np.ndarray] = []
    for lo in range(0, g, ROWS):
        r = np.arange(lo, min(g, lo + ROWS))
        rows = np.full(ROWS, n - 1, np.int32)
        rows[: len(r)] = n - 1 + r
        tgt = np.zeros(ROWS, np.int32)
        tgt[: len(r)] = np.asarray(generated, np.int32)[r]
        a, b = _gaps(params, toks, rows, tgt, d=d, control=control)
        out_a.append(np.asarray(a)[: len(r)])
        out_b.append(np.asarray(b)[: len(r)])
    return np.concatenate(out_a), np.concatenate(out_b)


def turn_sample(turns: Sequence, rng: np.random.Generator, min_tokens: int,
                max_turns: int) -> List:
    """The longest finished turn, then others drawn by ``rng`` until at
    least ``min_tokens`` served tokens (or ``max_turns`` turns) are in."""
    if not turns:
        return []
    order = sorted(range(len(turns)),
                   key=lambda i: -(len(turns[i].input_ids) + len(turns[i].generated)))
    picked = [order[0]]
    rest = [int(i) for i in rng.permutation(order[1:])]
    total = len(turns[order[0]].generated)
    for i in rest:
        if total >= min_tokens or len(picked) >= max_turns:
            break
        picked.append(i)
        total += len(turns[i].generated)
    return [turns[i] for i in picked]


def gaps_of(params, d: Dims, turns: Sequence, control: bool = False
            ) -> Dict[str, float]:
    """The widest served-token gap over ``turns`` (and the control's, with
    ``control``), and how many tokens were compared."""
    worst, worst_ctrl, n = 0.0, 0.0, 0
    with jax.default_matmul_precision("highest"):
        for t in turns:
            a, b = served_gaps(params, d, t.input_ids, t.generated, control)
            worst = max(worst, float(a.max()))
            worst_ctrl = max(worst_ctrl, float(b.max()))
            n += len(a)
    return {"served_gap": worst, "control_gap": worst_ctrl, "tokens": n}
