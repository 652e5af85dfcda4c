"""The paged serving step keeps what it serves: tiny dense MHA, GQA and
MLA+MoE paged servers run one staggered scenario each (prefill chunks
beside decodes, a stop token hit mid-batch, a ``max_new`` stop and a
``max_len - 1`` stop, a session reuse, a ``prime`` between steps, a
``crash()`` with a decode in flight and serving after it) with the tracer
on. The tokens, the request ids each step reports, the order of
``finished`` and the step counters equal the ones recorded before the step
kept its sampled tokens on the device.

Regenerate the data file only on purpose:
``PYTHONPATH=src python tests/test_step_identity.py --record``."""

import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import tracing
from repro.models import init_params
from repro.serving import BatchedServer, SessionCachePool
from repro.serving.scheduler import timed_step

DATA = Path(__file__).resolve().parent / "data" / "paged-step-identity.npz"
MAX_LEN = 64
COUNTERS = (
    "steps", "decode_steps", "decode_lanes", "unsynced_steps",
    "chunk_decode_steps", "prefill_chunks", "prefill_chunk_tokens",
    "cascade_steps", *tracing.MOE_COUNTERS,
)
# (config, stop token): the stop token is one the third request greedily
# emits mid-answer under these seeds, and the others do not
CASES = {
    "mha": (lambda: get_config("paper-qwen1.5-0.5b").reduced(attn_impl="pallas"), 449),
    "gqa": (lambda: get_config("qwen2-0.5b").reduced(attn_impl="reference"), 486),
    "mla-moe": (lambda: get_config("deepseek-v2-lite").reduced(
        attn_impl="pallas", experts_held=(1, 2)), 311),
}


def serve(case: str):
    """Run the scenario; return what it served and the tracer's counters."""
    make_cfg, stop = CASES[case]
    cfg = make_cfg()
    srv = BatchedServer(
        cfg, init_params(jax.random.key(0), cfg), n_slots=3, max_len=MAX_LEN,
        session_pool=SessionCachePool(capacity=4), paged=True, page_size=8,
        prefill_chunk_tokens=16, stop_tokens=(stop,),
    )
    rng = np.random.default_rng(1)

    def ids(n):
        return rng.integers(1, cfg.vocab_size, n).tolist()

    a, b, c, primed = ids(20), ids(40), ids(12), ids(30)
    common = ids(16)
    steps, finished = [], []
    seen = [0]

    def step(n=1):
        for _ in range(n):
            _, got = timed_step(srv)
            steps.append(sorted(got))
            finished.extend(srv.finished[seen[0]:])
            seen[0] = len(srv.finished)

    tracing.start()
    try:
        srv.submit(a, max_new=6, cache_key="a")        # stops at max_new
        srv.submit(b, max_new=100, cache_key="b")      # stops at max_len - 1
        step(3)
        srv.submit(c, max_new=30, cache_key="c")       # hits the stop token
        step(3)
        assert srv.prime("p", primed)
        step(4)
        out_a = next(f.token_ids for f in finished if f.request_id == 0)
        srv.submit(a + out_a + ids(5), max_new=5, cache_key="a")
        srv.submit(primed + ids(4), max_new=5, cache_key="p")
        step(12)
        srv.submit(ids(10), max_new=40, cache_key="h")
        step(8)
        assert any(s is not None and s.prefilled for s in srv.slots)
        srv.crash()                                    # a decode in flight
        seen[0] = 0
        srv.submit(common + ids(9), max_new=7, cache_key="f")
        srv.submit(common + ids(3), max_new=9, cache_key="g")
        while srv.busy:
            step()
    finally:
        trace = tracing.stop()
    return steps, finished, trace.counters, stop


def readings(case: str):
    """What the test compares, by name, as integer arrays."""
    steps, finished, counters, stop = serve(case)
    lens = [len(f.token_ids) for f in finished]
    # the scenario reaches every stop it is meant to
    assert any(f.token_ids[-1] == stop for f in finished)
    assert 6 in lens and MAX_LEN - 1 - 40 in lens
    return {
        "step_lens": [len(s) for s in steps],
        "step_ids": [i for s in steps for i in s],
        "finished": [f.request_id for f in finished],
        "token_lens": lens,
        "tokens": [t for f in finished for t in f.token_ids],
        "counters": [counters.get(k, 0) for k in COUNTERS],
    }


@pytest.mark.parametrize("case", list(CASES))
def test_paged_step_serves_what_was_recorded(case):
    want = np.load(DATA)
    for key, value in readings(case).items():
        np.testing.assert_array_equal(
            np.asarray(value, np.int64), want[f"{case}/{key}"], err_msg=key)


if __name__ == "__main__" and "--record" in sys.argv:
    np.savez_compressed(DATA, **{
        f"{case}/{key}": np.asarray(value, np.int64)
        for case in CASES for key, value in readings(case).items()
    })
    print("wrote", DATA)
