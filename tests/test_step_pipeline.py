"""The paged serving step never waits for the decode it dispatches: a step
reads back one array, the decode's input tokens, after the decode's
dispatch and outside the prefill drain; it makes no other host read; and a
second batch of the same shapes compiles nothing."""

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import tracing
from repro.models import init_params
from repro.serving import BatchedServer

ArrayImpl = type(jax.numpy.zeros(1))


@pytest.fixture
def reads(monkeypatch):
    """Record every ``jax.device_get`` with the spans open around it, and
    every other read of a device array's value on the host."""
    log = {"get": [], "other": [], "in_get": False, "in_drain": False}
    get = jax.device_get

    def device_get(x):
        log["get"].append(([s.id for s in tracing._T.stack],
                           [s.name for s in tracing._T.stack], log["in_drain"]))
        log["in_get"] = True
        try:
            return get(x)
        finally:
            log["in_get"] = False

    value = ArrayImpl._value

    def read_value(self):
        if not log["in_get"]:
            log["other"].append(self.shape)
        return value.fget(self)

    monkeypatch.setattr(jax, "device_get", device_get)
    monkeypatch.setattr(ArrayImpl, "_value", property(read_value))
    yield log
    tracing.stop()


def _cfg(kind, tiny_dense_cfg):
    if kind == "dense":
        return tiny_dense_cfg
    return get_config("deepseek-v2-lite").reduced(experts_held=(1, 2))


def _serve(srv, cfg, seed):
    srv.finished.clear()
    rng = np.random.default_rng(seed)
    for i, n in enumerate((40, 9, 23)):
        srv.submit(rng.integers(1, cfg.vocab_size, n).tolist(), max_new=5 + i)
    return srv.run_to_completion()


@pytest.mark.parametrize("kind", ["dense", "moe"])
def test_one_read_per_decode_step_after_its_dispatch(kind, tiny_dense_cfg, reads):
    cfg = _cfg(kind, tiny_dense_cfg)
    srv = BatchedServer(cfg, init_params(jax.random.key(0), cfg), n_slots=2,
                        max_len=64, paged=True, page_size=8,
                        prefill_chunk_tokens=16, stop_tokens=())
    drain = srv._drain_prefill

    def drain_marked():
        reads["in_drain"] = True
        try:
            return drain()
        finally:
            reads["in_drain"] = False

    srv._drain_prefill = drain_marked
    tracing.start()
    _serve(srv, cfg, seed=0)                   # compiles every shape
    assert tracing.stop().counters["backend_compiles"] > 0
    del reads["get"][:], reads["other"][:]
    tracing.start()
    fin = _serve(srv, cfg, seed=1)
    trace = tracing.stop()
    c = trace.counters

    assert c.get("backend_compiles", 0) == 0
    assert sorted(len(f.token_ids) for f in fin) == [5, 6, 7]
    assert c["decode_lanes"] == 5 + 6 + 7
    assert 0 <= c.get(tracing.HOST_PACED_STEPS, 0) <= c["decode_steps"]
    assert reads["other"] == []
    assert not any(in_drain for _, _, in_drain in reads["get"])
    by_id = {s.id: s for s in trace.spans}
    kids = trace.children()
    steps = trace.named("scheduler.step")
    decoded = [s for s in steps if s.attrs["synced"]]
    assert len(decoded) == c["decode_steps"]
    for s in steps:
        names = [k.name for k in kids[s.id]]
        if "scheduler.sync" not in names:
            assert "scheduler.decode" not in names
            continue
        dec = kids[s.id][names.index("scheduler.decode")]
        sync = kids[s.id][names.index("scheduler.sync")]
        assert names.index("scheduler.sync") == names.index("scheduler.decode") + 1
        assert sync.t0_ns >= dec.t1_ns
        in_step = [names_ for ids, names_, _ in reads["get"] if s.id in ids]
        # the step's one read, inside its sync span; with experts, the step
        # that leaves the server idle also reads the counts no later step
        # would
        assert in_step[0][-1] == "scheduler.sync", in_step
        assert len(in_step) == 1 or (
            kind == "moe" and s is steps[-1] and in_step[1][-1] == "scheduler.step"
        ), in_step
    assert len(reads["get"]) == len(decoded) + (kind == "moe")
    assert all(by_id[ids[0]].name == "scheduler.step" for ids, _, _ in reads["get"])
    if kind == "moe":
        n_moe = cfg.n_layers - cfg.first_dense_layers
        tokens = c["prefill_chunk_tokens"] + c["decode_lanes"]
        assert c["moe_routed_pairs"] == cfg.top_k * n_moe * tokens
