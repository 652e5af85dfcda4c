"""The program's tracer: off, it records nothing and makes no profiler
annotation; on, its spans nest with their parents, nodes and requests; and
the scheduler's step spans and counters agree with what a server served."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import tracing
from repro.models import init_params
from repro.serving import BatchedServer, SessionCachePool
from repro.serving.scheduler import trace_readings
from repro.store import Network

PS = 16


class _CountingAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation`` and counts what is
    made of it."""

    made: list = []

    def __init__(self, name, **kw):
        _CountingAnnotation.made.append(name)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def annotations(monkeypatch):
    _CountingAnnotation.made = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _CountingAnnotation)
    yield _CountingAnnotation.made
    tracing.stop()


def _server(cfg, budget=16):
    params = init_params(jax.random.PRNGKey(0), cfg)
    return BatchedServer(
        cfg, params, n_slots=2, max_len=128,
        session_pool=SessionCachePool(capacity=4),
        paged=True, page_size=PS, prefill_chunk_tokens=budget,
    )


def _prompts(cfg, sizes, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, size=n).tolist() for n in sizes]


def test_off_records_nothing_and_makes_no_annotation(tiny_dense_cfg, annotations):
    assert not tracing.enabled()
    # one shared do-nothing span, whatever is asked for
    assert tracing.span("a") is tracing.span("b", node="n", requests=[1, 2])
    with tracing.timed("c") as tm:
        tracing.count("x", 3)
    assert tm.ms >= 0.0
    srv = _server(tiny_dense_cfg)
    for i, ids in enumerate(_prompts(tiny_dense_cfg, [40, 20])):
        srv.submit(ids, max_new=3, cache_key=f"k{i}")
    assert srv.run_to_completion()
    assert annotations == []
    assert tracing._T.spans == [] and tracing._T.counters == {}
    assert tracing.stop().spans == []


def test_on_nests_spans_with_parents_nodes_and_requests(annotations):
    net = Network()
    tracing.start()
    with tracing.span("outer", node="n1", requests="key-1", net=net) as outer:
        outer.set("tokens", 7)
        with tracing.span("inner", requests=[3, 4]) as inner:
            tracing.count("work")
            tracing.count("work", 2)
            inner.serve(["key-1"])
            # a program never compiled before, while "inner" is innermost
            jax.jit(lambda x: x * 3.25 + 1)(jnp.arange(5.0)).block_until_ready()
        with tracing.timed("sibling", node="n2") as sib:
            pass
    with tracing.span("open at stop"):
        trace = tracing.stop()
    assert not tracing.enabled()
    by = {s.name: s for s in trace.spans}
    o, i, s = by["outer"], by["inner"], by["sibling"]
    assert o.parent is None and i.parent == o.id and s.parent == o.id
    assert (o.node, i.node, s.node) == ("n1", "n1", "n2")
    assert o.requests == ("key-1",) and i.requests == ("key-1",)
    assert o.clock_ms == 0.0 and i.clock_ms is None
    assert o.attrs == {"tokens": 7}
    assert i.compiles >= 1 and o.compiles == 0
    assert o.t0_ns <= i.t0_ns <= i.t1_ns <= s.t0_ns <= s.t1_ns <= o.t1_ns
    assert sib.ms == s.ms
    assert by["open at stop"].t1_ns >= by["open at stop"].t0_ns > 0
    assert trace.counters["work"] == 3 and trace.counters["backend_compiles"] >= 1
    assert trace.children()[o.id] == [i, s]
    assert annotations == [
        "discedge.outer n1", "discedge.inner n1", "discedge.sibling n2",
        "discedge.open at stop",
    ]


def test_span_bound(monkeypatch, annotations):
    monkeypatch.setattr(tracing, "MAX_SPANS", 2)
    tracing.start()
    for _ in range(5):
        with tracing.span("s"):
            pass
    trace = tracing.stop()
    assert len(trace.spans) == 2 and trace.dropped == 3


def test_step_spans_and_counters_match_what_was_served(tiny_dense_cfg, annotations):
    """Two prompts of 48 and 32 tokens under a 16-token budget: three
    chunks of the first (the first two steps decode nothing, so they do
    not sync), then two of the second beside decodes."""
    srv = _server(tiny_dense_cfg, budget=16)
    srv.node_id = "edge-9"
    for i, ids in enumerate(_prompts(tiny_dense_cfg, [48, 32])):
        srv.submit(ids, max_new=4, cache_key=f"k{i}")
    tracing.start()
    fin = srv.run_to_completion()
    trace = tracing.stop()
    c = trace.counters
    steps = trace.named("scheduler.step")
    assert c["steps"] == len(steps)
    assert all(s.node == "edge-9" for s in trace.spans)
    assert [(s.requests, s.attrs["c"], s.attrs["p0"])
            for s in trace.named("scheduler.prefill_chunk")] == [
        (("k0",), 16, 0), (("k0",), 16, 16), (("k0",), 16, 32),
        (("k1",), 16, 0), (("k1",), 16, 16),
    ]
    assert c["prefill_chunks"] == 5 and c["prefill_chunk_tokens"] == 80
    assert [s.attrs["synced"] for s in steps[:3]] == [False, False, True]
    assert c["unsynced_steps"] == 2
    assert c["unsynced_steps"] == sum(not s.attrs["synced"] for s in steps)
    assert c["decode_steps"] == len(steps) - 2
    assert c["chunk_decode_steps"] == 3
    assert c["decode_lanes"] == sum(len(f.token_ids) for f in fin)
    kids = trace.children()
    for s in steps[2:]:
        names = [k.name for k in kids[s.id]]
        assert names[0] == "scheduler.admit"
        assert names[-4:] == ["scheduler.decode_inputs", "scheduler.decode",
                              "scheduler.sync", "scheduler.finish"]
    for s in steps[:2]:
        assert [k.name for k in kids[s.id]] == [
            "scheduler.admit", "scheduler.prefill_chunk",
            "scheduler.decode_inputs",
        ]
    r = trace_readings(trace)
    assert r["chunk_step_share"] == 3 / c["decode_steps"]
    assert r["decode_lanes_per_step"] == c["decode_lanes"] / c["decode_steps"]
    assert 0.0 < r["step_host_ms"] < max(s.ms for s in steps)


def test_trace_readings_on_a_hand_made_trace():
    def span(i, name, parent, ms):
        return tracing.Span(id=i, name=name, parent=parent, node="n",
                            requests=(), clock_ms=None, t0_ns=0,
                            t1_ns=int(ms * 1e6))

    trace = tracing.Trace(
        spans=[
            span(0, "scheduler.step", None, 10.0),
            span(1, "scheduler.admit", 0, 1.0),
            span(2, "scheduler.sync", 0, 6.0),
            span(3, "scheduler.step", None, 2.0),
        ],
        counters={"decode_steps": 4, "chunk_decode_steps": 1, "decode_lanes": 10},
    )
    assert trace_readings(trace) == {
        "step_host_ms": (4.0 + 2.0) / 2,
        "chunk_step_share": 0.25,
        "decode_lanes_per_step": 2.5,
    }
    empty = trace_readings(tracing.Trace(spans=[], counters={}))
    assert empty == dict.fromkeys(empty, None)
