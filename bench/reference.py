"""The comparison with the plain reference that decides ``correct``. The
reference forward pass is the block's (``bench/blocks/<model_type>.py``:
``hidden`` and ``logits``, float32 in ``jax.numpy``, no kernel, cache,
paging or batching, importing nothing of the program). This module holds
what every block shares: the building blocks of its pass (``_fq``,
``_mm``, ``_rms``, ``_rope``; every product at HIGHEST precision) and the
served-token gaps.

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


@partial(jax.jit, static_argnames=("block", "d", "control"))
def _gaps(params, tokens, rows, targets, *, block, d, control: bool):
    """For each row: how far the reference's logit of ``targets[row]`` lies
    below its best logit. With ``control``, also that gap for the token the
    fp8 pass puts first."""
    x = block.hidden(params, d, tokens, "f32")[rows]
    ref = block.logits(params, d, x, "f32")
    best = jnp.max(ref, axis=-1)
    served = best - jnp.take_along_axis(ref, targets[:, None], axis=-1)[:, 0]
    if not control:
        return served, served
    xq = block.hidden(params, d, tokens, "fp8")[rows]
    pick = jnp.argmax(block.logits(params, d, xq, "fp8"), axis=-1)
    low = best - jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
    return served, low


def bucket(n: int) -> int:
    b = MIN_BUCKET
    while b < n:
        b *= 2
    return b


def served_gaps(params, block, d, input_ids: Sequence[int],
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
        a, b = _gaps(params, toks, rows, tgt, block=block, d=d, control=control)
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


def gaps_of(params, block, d, turns: Sequence, control: bool = False
            ) -> Dict[str, float]:
    """The widest served-token gap over ``turns`` (and the control's, with
    ``control``), and how many tokens were compared."""
    worst, worst_ctrl, n = 0.0, 0.0, 0
    with jax.default_matmul_precision("highest"):
        for t in turns:
            a, b = served_gaps(params, block, d, t.input_ids, t.generated, control)
            worst = max(worst, float(a.max()))
            worst_ctrl = max(worst_ctrl, float(b.max()))
            n += len(a)
    return {"served_gap": worst, "control_gap": worst_ctrl, "tokens": n}
