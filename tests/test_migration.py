"""Migration-aware KV warm-start across keygroup peers.

Covers the replication-arrival hook end to end: a client roams A→B
mid-session and B's turn prefills only the new-token suffix (eager prime),
greedy outputs stay identical to the cold path, and the ``migrated`` /
``kv_warm_start`` counters surface through Timing/ServiceResult. Fast tests
run on the analytic echo service; the real-engine equivalence tests carry
``@pytest.mark.slow``. See docs/architecture.md, "Migration warm-start".
"""

import jax
import pytest

from repro.core import ContextMode, tracing
from repro.edge import EchoLLMService, EdgeCluster, LLMClient
from repro.models import ModelConfig, init_params
from repro.serving import BatchedServer, CacheEntry, JaxLLMService, SessionCachePool
from repro.store import Link
from repro.tokenizer import get_tokenizer


def _echo_cluster(warm_start, kv_reuse=True):
    return EdgeCluster.build(
        ["a", "b"],
        lambda nid: EchoLLMService(model="m", vocab_size=32000, kv_reuse=kv_reuse),
        inter_node_link=Link(latency_ms=2.0, bandwidth_mbps=100.0),
        client_link=Link(latency_ms=5.0, bandwidth_mbps=50.0),
        warm_start=warm_start,
    )


def _roam(cluster, nodes, max_new_tokens=24):
    client = LLMClient(cluster, model="m", mode=ContextMode.TOKENIZED,
                       max_new_tokens=max_new_tokens)
    resps = []
    for i, node in enumerate(nodes):
        r = client.chat(f"question {i} about robots", node)
        assert r.error is None, r.error
        resps.append(r)
        client.think(400)  # lets replication (and the prime) land
    return resps


# ---------------------------------------------------------------------------
# Cluster-level warm-start semantics (analytic service — fast)
# ---------------------------------------------------------------------------

def test_roam_turn_is_warm_start():
    """A→B roam with eager warm-start: B's turn is a primed hit that
    prefills only the prompt, and both counters surface in Timing."""
    cluster = _echo_cluster("eager")
    tracing.start()
    try:
        r1, r2, r3 = _roam(cluster, ["a", "a", "b"])
    finally:
        trace = tracing.stop()
    assert not r1.timing.migrated and not r2.timing.migrated
    assert not r1.timing.kv_warm_start and not r2.timing.kv_warm_start
    assert r2.timing.kv_cache_hit  # same-node hit, served (not primed) prefix

    t3 = r3.timing
    assert t3.migrated and t3.kv_cache_hit and t3.kv_warm_start
    assert t3.kv_reused_tokens == r3.n_context_tokens
    assert t3.prefill_tokens == r3.n_prompt_tokens
    assert cluster.node("b").warm_starts >= 1
    primes = [s for s in trace.named("node.prime") if s.node == "b"]
    assert len(primes) >= cluster.node("b").warm_starts
    assert all(s.ms >= 0.0 and s.attrs["tokens"] > 0 for s in primes)


def test_roam_without_warm_start_is_cold():
    """warm_start="off": the node switch is a pool miss + full re-prefill
    (the PR-1 baseline this PR removes)."""
    cluster = _echo_cluster("off")
    _, _, r3 = _roam(cluster, ["a", "a", "b"])
    t3 = r3.timing
    assert t3.migrated and not t3.kv_cache_hit and not t3.kv_warm_start
    assert t3.prefill_tokens == r3.n_context_tokens + r3.n_prompt_tokens
    assert cluster.warm_starts() == 0


def test_roam_back_is_warm_via_delta_prime():
    """Roaming back to A after a turn on B: B's write replicated to A and
    delta-extended A's entry, so A's turn prefills only the prompt. The
    extended entry keeps its "serve" provenance — most of the reused prefix
    was served on A itself, so the turn must NOT count as a migration warm
    start (kv_warm_start inflation regression)."""
    cluster = _echo_cluster("eager")
    resps = _roam(cluster, ["a", "a", "b", "a"])
    t4 = resps[3].timing
    assert t4.migrated and t4.kv_cache_hit
    assert not t4.kv_warm_start  # provenance preserved on delta-extension
    assert t4.prefill_tokens == resps[3].n_prompt_tokens
    assert cluster.node("a").warm_starts >= 1


def test_fresh_prime_still_counts_warm_start_after_extension():
    """The provenance fix must not swallow genuine warm starts: a first
    roam onto a node whose entry was installed (and later extended) by
    primes alone still reports kv_warm_start."""
    cluster = _echo_cluster("eager")
    resps = _roam(cluster, ["a", "a", "b"])
    t3 = resps[2].timing
    # b's entry came from primes only (turn-1 install + turn-2 extension)
    assert t3.migrated and t3.kv_cache_hit and t3.kv_warm_start


def test_warm_start_cheaper_than_cold_on_analytic_clock():
    """The analytic cost model charges only the suffix on a warm roam —
    the roam turn is strictly cheaper than the cold cluster's."""
    warm = _roam(_echo_cluster("eager"), ["a", "a", "b"])
    cold = _roam(_echo_cluster("off"), ["a", "a", "b"])
    assert warm[2].timing.inference_ms < cold[2].timing.inference_ms
    # non-roam turns cost the same in both clusters
    assert warm[0].timing.inference_ms == cold[0].timing.inference_ms


def test_raw_context_never_primes():
    """RAW mode replicates text, not tokens — nothing to prefill, so the
    hook must not prime (and must not crash on RawContext values)."""
    cluster = _echo_cluster("eager")
    client = LLMClient(cluster, model="m", mode=ContextMode.RAW)
    for node in ["a", "a", "b"]:
        r = client.chat("hello", node)
        assert r.error is None
        client.think(400)
    assert cluster.warm_starts() == 0


def test_kv_reuse_disabled_service_reports_full_prefill():
    """An echo service without kv_reuse mirrors JaxLLMService(kv_reuse=False):
    no hits, prefill_tokens = full input."""
    cluster = _echo_cluster("eager", kv_reuse=False)
    _, _, r3 = _roam(cluster, ["a", "a", "b"])
    t3 = r3.timing
    assert t3.migrated and not t3.kv_cache_hit
    assert t3.prefill_tokens == r3.n_context_tokens + r3.n_prompt_tokens
    assert cluster.warm_starts() == 0  # prime() declines without a pool


def test_stale_delivery_does_not_notify():
    """A replicated write that loses last-writer-wins must not fire the
    warm-start hook (no prime for stale context)."""
    cluster = _echo_cluster("eager")
    _roam(cluster, ["a", "b", "a", "b"])
    store = cluster.store
    before = cluster.warm_starts()
    # replay: out-of-date version delivered to b is dropped, not notified
    key_vv = list(store.replica("a", "m").items())
    assert key_vv, "session context must exist on a"
    key, vv = key_vv[0]
    stale_before = store.dropped_stale_applies
    assert not store.replica("b", "m").apply_replicated(
        key, type(vv)(vv.value, 0, 0.0, None, "a")
    )
    assert cluster.warm_starts() == before
    assert store.dropped_stale_applies == stale_before  # direct apply path


def test_low_priority_update_keeps_lru_position():
    """Regression: a prime that delta-extends a key already hot in the pool
    must keep that key's LRU position. The old behavior moved the updated
    key to the LRU end, making the node's own hot session the next eviction
    victim right after its context replicated back."""
    pool = SessionCachePool(capacity=2)
    pool.put("other", CacheEntry([3, 4], []))
    pool.put("hot", CacheEntry([1, 2], []))       # MRU
    # replication-arrival prime extends the hot serve entry off the hot path
    pool.put("hot", CacheEntry([1, 2, 5], []), low_priority=True)
    pool.put("new", CacheEntry([7, 8], []))       # evicts LRU
    assert "hot" in pool and "other" not in pool  # hot entry kept its rank
    # a normal (serving) put still promotes to MRU
    pool.put("new", CacheEntry([7, 8, 9], []))
    pool.put("x", CacheEntry([5], []))
    assert "new" in pool and "hot" not in pool


def test_prime_extension_preserves_serve_provenance_in_pool():
    """Regression companion to the Timing-counter tests above, at the pool
    level: extending a "serve" entry via a low-priority put keeps whatever
    source the caller passes — the prime paths pass the original."""
    pool = SessionCachePool(capacity=2)
    pool.put("s", CacheEntry([1, 2], [], source="serve"))
    pool.put("s", CacheEntry([1, 2, 3], [], source="serve"), low_priority=True)
    assert pool.peek("s").source == "serve"
    assert pool.peek("s").pos == 3


def test_low_priority_prime_never_evicts_serve_entries():
    """A prime for a session that only *might* roam here is inserted at the
    LRU end: on a full pool it is the immediate victim and the node's hot
    serve entries stay intact."""
    pool = SessionCachePool(capacity=2)
    pool.put("s1", CacheEntry([1, 2], []))
    pool.put("s2", CacheEntry([3, 4], []))
    pool.put("p", CacheEntry([5, 6], [], source="prime"), low_priority=True)
    assert "s1" in pool and "s2" in pool and "p" not in pool
    # with free capacity the prime survives, at LRU position
    pool2 = SessionCachePool(capacity=2)
    pool2.put("p", CacheEntry([5, 6], [], source="prime"), low_priority=True)
    pool2.put("s1", CacheEntry([1, 2], []))
    assert "p" in pool2
    pool2.put("s2", CacheEntry([3, 4], []))  # evicts the unused prime first
    assert "p" not in pool2 and "s1" in pool2 and "s2" in pool2


# ---------------------------------------------------------------------------
# Real-engine equivalence (slow: jit compiles)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def jax_cfg():
    return ModelConfig(
        name="mig-mini", arch_type="dense", n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=128, vocab_size=4096, param_dtype="float32",
        compute_dtype="float32",
    )


@pytest.mark.slow
def test_jax_roam_warm_equals_cold_greedy(jax_cfg):
    """Per-node engines (same seed): the warm roam turn must produce exactly
    the cold path's greedy tokens while prefilling only the prompt suffix."""
    def build(warm):
        return EdgeCluster.build(
            ["a", "b"],
            lambda nid: JaxLLMService.create("mig-mini", jax_cfg, max_len=512),
            warm_start=warm,
        )

    outs = {}
    for warm in ("eager", "off"):
        cluster = build(warm)
        client = LLMClient(cluster, model="mig-mini",
                           mode=ContextMode.TOKENIZED, max_new_tokens=8)
        texts = []
        for i, node in enumerate(["a", "a", "b"]):
            r = client.chat(f"question {i} about robots", node)
            assert r.error is None, r.error
            texts.append(r.text)
            client.think(400)
        outs[warm] = texts
        t3 = client.response_log[2].timing
        if warm == "eager":
            assert t3.migrated and t3.kv_cache_hit and t3.kv_warm_start
            assert t3.prefill_tokens == client.response_log[2].n_prompt_tokens
            assert cluster.node("b").warm_starts >= 1
        else:
            assert t3.migrated and not t3.kv_cache_hit
    assert outs["eager"] == outs["off"]


@pytest.mark.slow
def test_engine_prime_then_generate_suffix_only(jax_cfg):
    """InferenceEngine.prime directly: a primed context makes the next
    generate a warm hit; a diverging prime is dropped safely."""
    svc = JaxLLMService.create("mig-mini", jax_cfg, max_len=512)
    tok = svc.tokenizer
    ctx = tok.encode("a replicated conversation about wheel odometry")
    assert svc.prime("k", ctx)
    assert svc.engine.session_pool.primes == 1
    assert svc.prime("k", ctx)                        # already warm: no-op
    assert svc.engine.session_pool.primes == 1

    p = tok.encode("next question")
    r = svc.completion(ctx, p, 8, cache_key="k")
    assert r.cache_hit and r.warm_start
    assert r.reused_tokens == len(ctx) and r.prefill_tokens == len(p)

    scratch = JaxLLMService.create("mig-mini", jax_cfg, max_len=512, kv_reuse=False)
    assert r.token_ids == scratch.completion(ctx, p, 8).token_ids

    # served turns overwrite provenance: the next hit is not a warm start
    r2 = svc.completion(ctx + p + r.token_ids, tok.encode("more"), 8, cache_key="k")
    assert r2.cache_hit and not r2.warm_start

    # divergent prime: drop + full reprime, still correct
    edited = list(ctx)
    edited[1] = (edited[1] + 1) % jax_cfg.vocab_size
    assert svc.prime("k", edited)
    r3 = svc.completion(edited, p, 8, cache_key="k")
    assert r3.cache_hit and r3.warm_start and r3.reused_tokens == len(edited)


@pytest.mark.slow
def test_prime_extension_of_serve_entry_not_warm(jax_cfg):
    """Regression (Timing counters): a turn served here leaves a "serve"
    entry; when its own context replicates back extended, the prime
    delta-extends it but must keep the provenance — the next local hit is
    NOT a migration warm start."""
    svc = JaxLLMService.create("mig-mini", jax_cfg, max_len=512)
    tok = svc.tokenizer
    p1 = tok.encode("first question about robots")
    r1 = svc.completion([], p1, 8, cache_key="s")
    assert svc.engine.session_pool.peek("s").source == "serve"

    # replication echoes the served history back, extended with a peer turn
    ctx = p1 + r1.token_ids
    extended = ctx + tok.encode("a turn appended elsewhere")
    assert svc.prime("s", extended)
    assert svc.engine.session_pool.peek("s").source == "serve"

    r2 = svc.completion(extended, tok.encode("next"), 8, cache_key="s")
    assert r2.cache_hit and r2.reused_tokens == len(extended)
    assert not r2.warm_start  # would have been True before the fix


@pytest.mark.slow
def test_prime_rejects_overlong_context(jax_cfg):
    svc = JaxLLMService.create("mig-mini", jax_cfg, max_len=64)
    assert not svc.prime("k", list(range(64)))
    assert "k" not in svc.engine.session_pool


# ---------------------------------------------------------------------------
# BatchedServer + session pool (slow: jit compiles)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_batched_server_pool_equivalence(jax_cfg):
    """Pool-backed slots must emit exactly the tokens of a pool-less server
    while reusing the previous turn's KV prefix on admission."""
    params = init_params(jax.random.key(0), jax_cfg)
    tok = get_tokenizer(jax_cfg.vocab_size, seed=0)
    ids1 = tok.encode("first turn about robots and sensors")

    plain = BatchedServer(jax_cfg, params, n_slots=2, max_len=128)
    plain.submit(ids1, max_new=6)
    ref1 = plain.run_to_completion()[0].token_ids

    pool = SessionCachePool(capacity=2)
    srv = BatchedServer(jax_cfg, params, n_slots=2, max_len=128, session_pool=pool)
    srv.submit(ids1, max_new=6, cache_key="s")
    f1 = srv.run_to_completion()[0]
    assert f1.token_ids == ref1 and not f1.cache_hit
    assert "s" in pool

    ids2 = ids1 + f1.token_ids + tok.encode("second turn about mapping")
    plain2 = BatchedServer(jax_cfg, params, n_slots=2, max_len=128)
    plain2.submit(ids2, max_new=6)
    ref2 = plain2.run_to_completion()[0].token_ids

    srv.finished.clear()
    srv.submit(ids2, max_new=6, cache_key="s")
    f2 = srv.run_to_completion()[0]
    assert f2.token_ids == ref2
    assert f2.cache_hit and f2.reused_tokens == len(ids1) + len(f1.token_ids)


@pytest.mark.slow
def test_batched_server_warm_start_from_primed_entry(jax_cfg):
    """A context primed by the migration hook speeds up the batched path:
    admission reuses the primed prefix (the engine and scheduler share one
    pool on a node)."""
    svc = JaxLLMService.create("mig-mini", jax_cfg, max_len=128)
    pool = svc.engine.session_pool
    tok = svc.tokenizer
    ctx = tok.encode("context replicated from a peer node")
    assert svc.prime("roamer", ctx)

    srv = BatchedServer(jax_cfg, svc.engine.params, n_slots=2, max_len=128,
                        session_pool=pool)
    suffix = tok.encode("fresh prompt")
    rid = srv.submit(ctx + suffix, max_new=6, cache_key="roamer")
    fin = {f.request_id: f for f in srv.run_to_completion()}
    assert fin[rid].cache_hit and fin[rid].reused_tokens == len(ctx)

    plain = BatchedServer(jax_cfg, svc.engine.params, n_slots=2, max_len=128)
    plain.submit(ctx + suffix, max_new=6)
    assert fin[rid].token_ids == plain.run_to_completion()[0].token_ids
