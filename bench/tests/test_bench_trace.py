"""The reduction from a trace to device metrics, on a small trace recorded
on the chip and on a hand-made one."""

import json
from pathlib import Path

import pytest

from bench import trace
from bench.harness import KERNELS

DATA = Path(__file__).resolve().parent / "data"


def _busy_by_sweep(ops):
    """Busy time counted another way: walk the op boundaries in order and
    add the time during which at least one op is open."""
    edges = sorted([(s, 1) for _, s, d in ops] + [(s + d, -1) for _, s, d in ops],
                   key=lambda e: (e[0], -e[1]))
    busy, open_, last = 0.0, 0, None
    for t, step in edges:
        if open_ > 0:
            busy += t - last
        open_ += step
        last = t
    return busy


def test_recorded_decode_step():
    tr = json.loads((DATA / "trace-decode-step.json").read_text())
    ops = tr["devices"][0]["ops"]
    out = trace.reduce(tr, 0.05, KERNELS)
    assert out["busy_s"] == pytest.approx(_busy_by_sweep(ops) / 1e9)
    paged = sum(d for name, _, d in ops if name.startswith("%paged_attention"))
    assert paged > 0
    assert out["kernel_s"]["paged_attention"] == pytest.approx(paged / 1e9)
    assert "chunked_prefill" not in out["kernel_s"]
    # every idle gap lies inside the step's host span or between ops
    span = max(s + d for _, s, d in ops) - min(s for _, s, _ in ops)
    idle = sum(v for _, v in out["idle_gaps"])
    assert idle == pytest.approx(span / 1e9 - out["busy_s"])
    assert {k for k, _ in out["idle_gaps"]} <= {"bench.step edge-0", trace.IDLE_INSIDE}
    assert len(out["device_ops"]) == 10


def test_hand_made_trace():
    ops = [["%while.1", 0, 10_000], ["%paged_attention.3", 2_000, 5_000],
           ["%chunked_prefill_attention.2", 100_000, 10_000],
           ["%fusion.7", 110_005, 1_000], ["%copy.1", 200_000, 50_000]]
    host = [["bench.prime edge-1", 20_000, 70_000],
            ["bench.prefill_chunk", 95_000, 20_000],
            ["bench.step edge-0", 120_000, 200_000]]
    tr = {"devices": [{"plane": "/device:TPU:0", "ops": ops}], "host": host}
    out = trace.reduce(tr, 300e-6, KERNELS)
    # busy: [0, 10) + [100, 110) + [110.005, 111.005) + [200, 250) us
    assert out["busy_s"] == pytest.approx((10_000 + 10_000 + 1_000 + 50_000) / 1e9)
    assert out["kernel_s"] == pytest.approx({"paged_attention": 5e-6,
                                             "chunked_prefill": 10e-6})
    idle = dict(out["idle_gaps"])
    assert idle["bench.prime edge-1"] == pytest.approx(90e-6)   # 10 -> 100 us
    assert idle[trace.IDLE_INSIDE] == pytest.approx(5e-9)        # 110 -> 110.005 us
    assert idle["bench.step edge-0"] == pytest.approx(88.995e-6)  # 111.005 -> 200 us


def test_no_tpu_plane_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce({"devices": [], "host": []}, 1.0, KERNELS)
