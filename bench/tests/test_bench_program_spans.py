"""The trace reduction puts a device idle gap down to the innermost host span
open during it, so a program span (``discedge.*``) inside a benchmark
wrapper span takes the gaps that fall inside it."""

import pytest

from bench import trace
from bench.harness import KERNELS


def _trace(host):
    ms = 1_000_000
    # ops at 0-1, 2-3 and 5-6 ms: gaps 1-2 ms and 3-5 ms
    ops = [["%fusion.1", 0.0, 1.0 * ms], ["%paged_attention.3", 2.0 * ms, 1.0 * ms],
           ["%copy.2", 5.0 * ms, 1.0 * ms]]
    return {"devices": [{"plane": "/device:TPU:0", "ops": ops}], "host": host}


def test_gap_inside_a_program_span_goes_to_it():
    ms = 1_000_000
    tr = _trace([
        ["bench.step edge-0", 0.0, 6.0 * ms],
        ["discedge.scheduler.step edge-0", 0.5 * ms, 5.0 * ms],
        ["discedge.scheduler.decode_inputs edge-0", 1.2 * ms, 0.6 * ms],
    ])
    out = trace.reduce(tr, 0.006, KERNELS)
    idle = dict(out["idle_gaps"])
    assert idle == {
        "discedge.scheduler.decode_inputs edge-0": pytest.approx(0.001),
        "discedge.scheduler.step edge-0": pytest.approx(0.002),
    }
    # the host spans change only the labels of the idle time
    bare = trace.reduce(_trace([["bench.step edge-0", 0.0, 6.0 * ms]]), 0.006, KERNELS)
    assert (out["busy_s"], out["kernel_s"], out["device_ops"]) == (
        bare["busy_s"], bare["kernel_s"], bare["device_ops"])
    assert dict(bare["idle_gaps"]) == {"bench.step edge-0": pytest.approx(0.003)}
