"""What every block's counts share: the bytes of a bf16 value, pages of a
length, the chip's published peaks and the least time a call could take.
The counts themselves are each block's (``bench/blocks/``). Every count is
a lower bound on the work: padding rows, empty lanes and repeated page
reads a kernel may do are not counted, so a roofline share built on these
cannot pass 100% unless the time is short."""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Iterable, Tuple

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


def pages(n_tokens: int, page_size: int) -> int:
    return -(-n_tokens // page_size)


def bound_seconds(flops: float, nbytes: float, peaks: Dict) -> float:
    """The least time the chip could take: compute or memory bound."""
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])


def layers_seconds(calls: Iterable[Tuple[int, float, float]], peaks: Dict) -> float:
    """The least time of one kernel call over every layer: ``calls`` is a
    block's ``[(n_layers, flops, bytes)]``, one entry per kind of layer."""
    return sum(n * bound_seconds(f, b, peaks) for n, f, b in calls)
