"""Operations and bytes the algorithm needs, counted from the configuration's
shapes. Every count is a lower bound on the work: padding rows, empty
lanes and repeated page reads a kernel may do are not counted, so a
roofline share built on these cannot pass 100% unless the time is short."""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Sequence, Tuple

from .model import Dims

BF16 = 2
PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks_for(device_kind: str) -> Dict:
    """The published peaks of one chip of ``device_kind``; an unknown kind
    is an error."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


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
    """Model FLOPs of one token at position ``kv_len - 1`` through every
    layer, plus the vocabulary projection when its logits are computed."""
    per_layer = 2.0 * layer_matmul_params(d) + attention_flops(d, kv_len)
    return d.layers * per_layer + (2.0 * d.d_model * d.vocab if logits else 0.0)


def chunk_flops(d: Dims, c: int, p0: int) -> float:
    """Model FLOPs of one prefill chunk of ``c`` tokens at offset ``p0``
    (causal: row r attends to p0 + r + 1 keys), logits of its last row."""
    pairs = c * p0 + c * (c + 1) // 2
    per_layer = 2.0 * layer_matmul_params(d) * c + attention_flops(d, pairs)
    return d.layers * per_layer + 2.0 * d.d_model * d.vocab


def page_bytes(d: Dims, page_size: int) -> int:
    """K and V of one page in one layer."""
    return 2 * page_size * d.kv_heads * d.d_head * BF16


def pages(n_tokens: int, page_size: int) -> int:
    return -(-n_tokens // page_size)


def paged_attention_call(d: Dims, kv_lens: Sequence[int], shared_pages: int,
                         page_size: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one layer's paged decode attention over the lanes
    that decode (``kv_lens``: keys each attends, its new token included).
    A shared run of pages is read once for all lanes."""
    flops = sum(attention_flops(d, n) for n in kv_lens)
    n_pages = shared_pages + sum(max(0, pages(n, page_size) - shared_pages)
                                 for n in kv_lens)
    qo = 2 * len(kv_lens) * d.heads * d.d_head * BF16
    return flops, n_pages * page_bytes(d, page_size) + qo


def chunked_prefill_call(d: Dims, c: int, p0: int, page_size: int
                         ) -> Tuple[float, float]:
    """(FLOPs, bytes) of one layer's chunked-prefill attention: ``c`` query
    rows at offset ``p0`` against the pages of [0, p0 + c)."""
    flops = attention_flops(d, c * p0 + c * (c + 1) // 2)
    qo = 2 * c * d.heads * d.d_head * BF16
    return flops, pages(p0 + c, page_size) * page_bytes(d, page_size) + qo


def bound_seconds(flops: float, nbytes: float, peaks: Dict) -> float:
    """The least time the chip could take: compute or memory bound."""
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
