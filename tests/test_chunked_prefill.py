"""Chunked paged prefill (docs/architecture.md, "Chunked paged prefill"):
prompt tokens land straight in KV pages, split into page-aligned chunks.

Model-layer equivalence matrix: prefill_chunk_paged against the dense
prefill at every chunk-boundary shape (one page, two pages, ragged last
chunk, chunk == full prompt) x {reference, pallas} x shared-prefix
{off, on} — greedy-token identical everywhere. Server-level: the batched
scheduler's unified steps produce token-identical outputs across chunk
budgets (including None, the full-prefill stall baseline), FIFO-fair
admission never starves a small tenant behind an infeasible big one, and
the new ttft/decode-gap accounting is populated."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import (
    ModelConfig,
    init_params,
    layer_groups,
    prefill,
    prefill_chunk_paged,
)
from repro.models.cache import init_paged_pool
from repro.serving import BatchedServer, SessionCachePool

PS = 16    # page size used throughout
MP = 6     # table width (pages) for the model-layer matrix
N = 40     # prompt length: 2 full pages + a ragged half page


@pytest.fixture(
    scope="module",
    params=[
        pytest.param("reference"),
        pytest.param("pallas", marks=pytest.mark.slow),
    ],
)
def impl_cfg(request):
    return ModelConfig(
        name="tiny-chunk", arch_type="dense", n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=512,
        param_dtype="float32", compute_dtype="float32",
        attn_impl=request.param,
    )


@pytest.fixture(scope="module")
def impl_params(impl_cfg):
    return init_params(jax.random.PRNGKey(0), impl_cfg)


def _chunk_run(cfg, params, tokens, chunk, n_shared_pages=0, donor=None):
    """Prefill ``tokens`` through prefill_chunk_paged in ``chunk``-token
    steps against a fresh pool; returns the final logits (V,). With
    ``donor``, a first run writes the shared-prefix pages and the main run
    starts past them with n_skip (reads them, writes dropped)."""
    pools = [
        init_paged_pool(cfg, spec.n_blocks, 32, PS)
        for spec in layer_groups(cfg)
    ]
    table = jnp.asarray(np.arange(1, MP + 1, dtype=np.int32)[None, :])
    if donor is not None:
        _, pools = prefill_chunk_paged(
            params, cfg, pools, table,
            jnp.asarray(np.asarray(donor, np.int32)[None, :]),
            jnp.zeros((1,), jnp.int32),
            jnp.full((1,), len(donor), jnp.int32),
        )
    pos, logits = n_shared_pages * PS, None
    rest = list(tokens[n_shared_pages * PS:])
    while rest:
        c, rest = rest[:chunk], rest[chunk:]
        padded = np.zeros((chunk,), np.int32)
        padded[: len(c)] = c
        logits, pools = prefill_chunk_paged(
            params, cfg, pools, table, jnp.asarray(padded[None, :]),
            jnp.full((1,), pos, jnp.int32),
            jnp.full((1,), len(c), jnp.int32),
            n_skip=n_shared_pages,
        )
        pos += len(c)
    return np.asarray(logits[0])


@pytest.mark.parametrize(
    "chunk",
    [
        pytest.param(PS, marks=pytest.mark.slow, id="1page"),
        pytest.param(2 * PS, marks=pytest.mark.slow, id="2pages"),
        pytest.param(48, id="ragged"),
        pytest.param(N, marks=pytest.mark.slow, id="full"),
    ],
)
@pytest.mark.parametrize("shared", [False, True], ids=["cold", "sharedpfx"])
def test_chunk_boundaries_match_dense_prefill(impl_cfg, impl_params, chunk, shared):
    """Every chunk split — including a ragged last chunk and the
    degenerate one-chunk case — lands the same greedy token as the dense
    one-shot prefill, with and without leading read-only shared pages."""
    rng = np.random.default_rng(7)
    tokens = rng.integers(1, impl_cfg.vocab_size, size=N).astype(np.int32)
    ref_logits, _, _ = prefill(
        impl_params, impl_cfg, jnp.asarray(tokens[None, :]), max_len=MP * PS
    )
    ref = np.asarray(ref_logits[0])
    got = _chunk_run(
        impl_cfg, impl_params, tokens, chunk,
        n_shared_pages=2 if shared else 0,
        donor=tokens[:2 * PS] if shared else None,
    )
    assert int(ref.argmax()) == int(got.argmax())
    np.testing.assert_allclose(got, ref, atol=2e-5, rtol=2e-4)


# ---------------------------------------------------------------------------
# Server level: unified steps
# ---------------------------------------------------------------------------

def _serve(cfg, params, reqs, budget, stagger=0, max_new=6):
    """Run ``reqs`` through a paged BatchedServer with the given chunk
    budget; requests after the first are submitted ``stagger`` steps in.
    Returns ({rid: tokens}, server)."""
    srv = BatchedServer(
        cfg, params, n_slots=2, max_len=128,
        session_pool=SessionCachePool(capacity=8),
        paged=True, page_size=PS, prefill_chunk_tokens=budget,
    )
    rids = [srv.submit(list(reqs[0]), max_new=max_new, cache_key="s0")]
    for _ in range(stagger):
        srv.step()
    rids += [
        srv.submit(list(r), max_new=max_new, cache_key=f"s{i + 1}")
        for i, r in enumerate(reqs[1:])
    ]
    fin = {f.request_id: f.token_ids for f in srv.run_to_completion()}
    return [fin[r] for r in rids], srv


@pytest.mark.slow
def test_chunk_budgets_token_identical(tiny_dense_cfg):
    """The per-step chunk budget is a latency knob, not a model change:
    budgets 16 / 64 / None (stall baseline) generate identical greedy
    tokens for a resident tenant plus a long mid-flight admission."""
    cfg = tiny_dense_cfg
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    reqs = [
        rng.integers(1, cfg.vocab_size, size=20).tolist(),
        rng.integers(1, cfg.vocab_size, size=90).tolist(),
    ]
    outs = {
        b: _serve(cfg, params, reqs, b, stagger=2)[0]
        for b in (16, 64, None)
    }
    assert outs[16] == outs[64] == outs[None]
    for toks in outs[16]:
        assert len(toks) == 6


def test_latency_accounting_populated(tiny_dense_cfg):
    """The batched service's results carry ttft and per-token decode gap
    percentiles, read on the cluster clock from the steps that produced
    each token; a tenant's ttft includes its queue/chunk wait."""
    from repro.serving import BatchedLLMService
    from repro.store import Network
    from repro.tokenizer import get_tokenizer

    cfg = tiny_dense_cfg
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(5)
    reqs = [rng.integers(1, cfg.vocab_size, size=30).tolist() for _ in range(2)]
    srv = BatchedServer(
        cfg, params, n_slots=2, max_len=128,
        session_pool=SessionCachePool(capacity=4),
        paged=True, page_size=PS, prefill_chunk_tokens=16,
    )
    svc = BatchedLLMService("tiny", srv, get_tokenizer(cfg.vocab_size, seed=0))
    net = Network()
    results = []
    for i, r in enumerate(reqs):
        svc.submit([], r, 5, cache_key=f"k{i}", net=net, on_done=results.append)
    end = net.run_until_quiet()
    assert len(results) == 2
    for f in results:
        assert f.ttft_ms > 0.0
        assert f.decode_p99_ms >= f.decode_p50_ms > 0.0
        assert f.queue_ms <= f.ttft_ms <= f.queue_ms + f.inference_ms <= end


def test_fifo_fair_admission_no_starvation(tiny_dense_cfg):
    """Regression (two tenants, tight page budget): a big request the pool
    cannot cover yet must not block a small feasible one queued behind it
    — the small tenant admits into the free slot, the big one keeps its
    queue position and admits once pages free up."""
    cfg = tiny_dense_cfg
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(11)
    srv = BatchedServer(
        cfg, params, n_slots=2, max_len=128, session_pool=None,
        paged=True, page_size=PS, kv_pages=1 + 8, prefill_chunk_tokens=64,
    )
    r_res = srv.submit(rng.integers(1, 512, size=33).tolist(), max_new=40)
    r_big = srv.submit(rng.integers(1, 512, size=95).tolist(), max_new=4)
    r_small = srv.submit(rng.integers(1, 512, size=17).tolist(), max_new=4)
    # resident: 3 pages; big needs 6 of the remaining 5 -> skipped;
    # small needs 2 -> admitted into the second slot the same step
    srv.step()
    assert {s.request_id for s in srv.slots if s is not None} == {r_res, r_small}
    assert [q[0] for q in srv.queue] == [r_big]
    fin = {f.request_id: f.token_ids for f in srv.run_to_completion()}
    assert set(fin) == {r_res, r_big, r_small}
    assert all(len(t) >= 1 for t in fin.values())
    order = [f.request_id for f in srv.finished]
    assert order.index(r_small) < order.index(r_big)


@pytest.mark.slow
def test_interleave_sweep_budget_vs_stall(tiny_dense_cfg):
    """Interleave sweep across budgets and staggers: outputs stay
    token-identical, and under the budgeted servers the resident keeps
    emitting tokens *while* the long prompt is still mid-prefill (with
    None it cannot — the stall)."""
    cfg = tiny_dense_cfg
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(13)
    reqs = [
        rng.integers(1, cfg.vocab_size, size=16).tolist(),
        rng.integers(1, cfg.vocab_size, size=100).tolist(),
        rng.integers(1, cfg.vocab_size, size=50).tolist(),
    ]
    for stagger in (0, 3):
        outs = {
            b: _serve(cfg, params, reqs, b, stagger=stagger, max_new=8)[0]
            for b in (16, 32, 64, None)
        }
        vals = list(outs.values())
        assert all(v == vals[0] for v in vals[1:])
