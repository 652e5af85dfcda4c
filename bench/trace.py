"""Reduction of a profiler trace to device metrics.

``collect`` reads the ``.xplane.pb`` the JAX profiler wrote and keeps what
the reduction needs, in a small JSON-able form: for every TPU plane the
events of its ``XLA Ops`` line as ``[instruction, start_ns, duration_ns]``,
and
the host spans the benchmark opened (``bench.*`` trace annotations).
``reduce`` turns that into busy time, per-kernel device time, the device
operations that took the most time, and the device's idle time sorted by
what the host was doing meanwhile.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."
IDLE_OUTSIDE = "host: event loop (clients, store, network)"
IDLE_INSIDE = "device: gaps under 20 us between ops of a program"
SHORT_GAP_NS = 20_000


def _op_name(ev) -> str:
    """An op event's HLO instruction name: the text before `` = `` of the
    instruction the event names (``%paged_attention.9``). Matching on it,
    and not on the whole text, keeps an op that takes a kernel's output as
    an operand from counting as the kernel."""
    return ev.name.split(" = ", 1)[0]


def collect(trace_dir: str) -> Dict:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend([_op_name(e), float(e.start_ns), float(e.duration_ns)]
                               for e in line.events)
            devices.append({"plane": plane.name, "ops": ops})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, float(e.start_ns), float(e.duration_ns)]
                            for e in line.events if e.name.startswith(HOST_PREFIX))
    return {"devices": devices, "host": host}


def union_ns(intervals: Sequence[Tuple[float, float]]) -> Tuple[float, List[Tuple[float, float]]]:
    """Total length of the union of ``(start, end)`` intervals, and the
    merged intervals in order."""
    merged: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    return sum(e - s for s, e in merged), merged


def _host_at(spans: List[Tuple[float, float, str]], t: float) -> str:
    """The innermost benchmark host span open at time ``t``."""
    best, best_len = IDLE_OUTSIDE, float("inf")
    for s, e, name in spans:
        if s <= t < e and e - s < best_len:
            best, best_len = name, e - s
    return best


def reduce(tr: Dict, window_s: float, kernels: Dict[str, Sequence[str]]) -> Dict:
    """Device metrics of one traced window.

    Ops are named by their HLO instruction (``%copy.53``); a loop such as
    the layer scan's ``%while`` holds the ops of its body, so its time
    overlaps theirs. ``kernels`` maps a kernel's name to prefixes of the HLO instruction
    names it runs under (the text before `` = `` in an op's name, so that
    an op that takes a kernel's output as operand does not match); its
    device time is the sum of the durations of matching ops,
    averaged over the chips. Busy time is the union of every op's interval
    on a chip, averaged over the chips."""
    if not tr["devices"]:
        raise ValueError("the trace holds no TPU plane")
    n = len(tr["devices"])
    busy, per_op, kernel_ns = 0.0, defaultdict(float), defaultdict(float)
    gaps: List[Tuple[float, float]] = []
    for dev in tr["devices"]:
        ivs = [(s, s + d) for _, s, d in dev["ops"]]
        b, merged = union_ns(ivs)
        busy += b
        gaps.extend((a[1], b_[0]) for a, b_ in zip(merged, merged[1:]))
        for inst, _, d in dev["ops"]:
            per_op[inst] += d
            for k, pats in kernels.items():
                if inst.startswith(tuple(pats)):
                    kernel_ns[k] += d
    spans = [(s, s + d, name) for name, s, d in tr["host"]]
    idle_by = defaultdict(float)
    for s, e in gaps:
        where = IDLE_INSIDE if e - s < SHORT_GAP_NS else _host_at(spans, (s + e) / 2)
        idle_by[where] += e - s
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    top_idle = sorted(idle_by.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": busy / n / 1e9,
        "window_s": window_s,
        "kernel_s": {k: v / n / 1e9 for k, v in kernel_ns.items()},
        "device_ops": [[k, v / n / 1e9] for k, v in top_ops],
        "idle_gaps": [[k, v / n / 1e9] for k, v in top_idle],
    }
