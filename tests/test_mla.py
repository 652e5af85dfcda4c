"""Latent attention (MLA) and the dropless DeepSeekMoE layer on the paged
serving path, at small sizes on the CPU in float32 with seeded random
weights: paged prefill and decode against the full forward pass, the
absorbed form against the published one, latent pages shipped and primed,
the expert-parallel share, batch invariance, YaRN against its published
formula, and the MoE counters and latent-page span of the tracer."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import tracing
from repro.models import (
    decode_step_paged,
    forward_full,
    init_params,
    layer_groups,
    prefill_chunk_paged,
)
from repro.models import mla, moe
from repro.models.cache import init_paged_pool, latent_row
from repro.models.layers import mlp_forward, yarn_freqs
from repro.serving import BatchedServer, PagedKVAllocator, SessionCachePool
from repro.serving.chunked_prefill import PagedPrefiller, prime_fill_pages

PS = 8


def tiny(impl="pallas", **kw):
    """DeepSeek-V2-Lite's block at a small size: one dense layer, one MoE
    layer of 4 routed experts (top-2) beside 2 shared, latent rows of
    64 + 16 lanes, YaRN over an original context of 64."""
    return get_config("deepseek-v2-lite").reduced(attn_impl=impl, **kw)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.key(0), tiny())


def _pools(cfg, n_pages=16):
    return [init_paged_pool(cfg, s.n_blocks, n_pages, PS) for s in layer_groups(cfg)]


def _chunk(params, cfg, pools, table, ids, p0, n_skip=0):
    toks = np.zeros((1, 32), np.int32)
    toks[0, : len(ids)] = ids
    return prefill_chunk_paged(
        params, cfg, pools, jnp.asarray(table[None]), jnp.asarray(toks),
        jnp.array([p0], jnp.int32), jnp.array([len(ids)], jnp.int32),
        n_skip=n_skip,
    )


@pytest.mark.parametrize("impl", ["pallas", "reference"])
def test_paged_prefill_and_decode_match_full_forward(params, impl):
    """A prompt prefilled in two chunks (the second after a read-only
    shared page, as a session reuse admits it), then four decode steps:
    every logit row equals the full forward pass's at its position."""
    cfg = tiny(impl)
    toks = np.asarray(jax.random.randint(jax.random.key(1), (30,), 1, cfg.vocab_size))
    full, _ = forward_full(params, cfg, jnp.asarray(toks[None]))
    full = np.asarray(full[0])
    table = np.array([1, 2, 3, 4], np.int32)
    pools = _pools(cfg)
    lg, pools = _chunk(params, cfg, pools, table, toks[:8], 0)
    np.testing.assert_allclose(np.asarray(lg[0]), full[7], atol=2e-5)
    lg, pools = _chunk(params, cfg, pools, table, toks[8:26], 8, n_skip=1)
    np.testing.assert_allclose(np.asarray(lg[0]), full[25], atol=2e-5)
    slot = np.arange(table.size * PS)
    kv_pos = jnp.asarray(np.where(slot < 26, slot, -1)[None].astype(np.int32))
    for i in range(26, 30):
        lg, pools, kv_pos = decode_step_paged(
            params, cfg, pools, jnp.asarray(table[None]), kv_pos,
            jnp.asarray(toks[None, i : i + 1]), jnp.array([i], jnp.int32),
        )
        np.testing.assert_allclose(np.asarray(lg[0, 0]), full[i], atol=2e-5)


def test_absorbed_attention_matches_published_form(params):
    """W_UK folded into the query and W_UV applied after attending the
    latent rows give the published, per-head expanded attention."""
    cfg = tiny("reference")
    p = jax.tree.map(lambda a: a[0], params["groups"][0]["attn"])
    x = jax.random.normal(jax.random.key(2), (2, 12, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(12, dtype=jnp.int32), (2, 12))
    published = mla.mla_forward(p, x, pos, cfg)
    q_nope, q_pe, rows = mla.project(p, x, pos, cfg)
    q = mla.absorb_query(p, q_nope, q_pe, cfg)
    lat = mla._pad_lanes(rows, q.shape[-1])[:, :, None, :]
    o = mla.sdpa(q, lat, lat[..., : cfg.kv_lora_rank], pos, pos,
                 jnp.ones((2, 12), bool), mla.softmax_scale(cfg))
    np.testing.assert_allclose(np.asarray(mla.latent_output(p, o, cfg)),
                               np.asarray(published), atol=2e-5)


def _prefiller(cfg, params):
    alloc = PagedKVAllocator(cfg, page_size=PS, n_pages=16)
    return PagedPrefiller(cfg, params, alloc)


def test_latent_pages_shipped_and_primed_give_the_same_logits(params):
    """Latent pages exported by one node and installed by another, and a
    context primed by recompute, give the next token's logits of the node
    that served the context, bit for bit; both installs are traced as
    ``serving.latent_page_install``."""
    cfg = tiny()
    ids = list(np.asarray(jax.random.randint(jax.random.key(3), (24,), 1, cfg.vocab_size)))
    home = _prefiller(cfg, params)
    pages = home.alloc.alloc(home.alloc.pages_for(len(ids) + 1))
    home.prefill_ids(pages, ids, 0)
    payloads = [home.alloc.export_page_bytes(p) for p in pages[: len(ids) // PS]]
    assert len(payloads[0]) == home.alloc.page_bytes == (
        cfg.n_layers * PS * latent_row(cfg) * 4)
    want = np.asarray(home.run_chunk(pages, [5], len(ids)))

    tracing.start()
    try:
        got = []
        for shipped in (payloads, None):
            node = _prefiller(cfg, params)
            mine = prime_fill_pages(None, node, ids, None, 0, shipped=shipped)
            mine = mine + node.alloc.alloc(1)
            if shipped is not None:
                assert all(node.alloc.export_page_bytes(a) == b
                           for a, b in zip(mine, payloads))
            got.append(np.asarray(node.run_chunk(mine, [5], len(ids))))
    finally:
        trace = tracing.stop()
    for g in got:
        np.testing.assert_array_equal(g, want)
    spans = trace.named(tracing.LATENT_PAGE_INSTALL)
    assert [s.attrs["shipped"] for s in spans] == [len(payloads), 0]


def test_expert_parallel_shares_add_up_to_the_whole_layer():
    """Eight devices each holding 2 of 16 experts: their routed shares,
    with the shared experts counted once, add up to the whole layer, and
    that equals a plain per-token loop over each token's top-6 experts."""
    cfg = tiny("reference").replace(n_experts=16, top_k=6)
    p = moe.init_moe(jax.random.key(4), cfg)
    x = jax.random.normal(jax.random.key(5), (1, 10, cfg.d_model))
    whole, _ = moe.moe_serve(p, x, cfg)
    xf = x[0]
    total = mlp_forward(p["shared"], xf, cfg)
    for i in range(8):
        share = cfg.replace(experts_held=(2 * i, 2))
        held = {k: v[2 * i : 2 * i + 2] for k, v in p.items()
                if k in ("w_up", "w_gate", "w_down")}
        out, _ = moe.held_share({**held, "router": p["router"]}, xf, share)
        total = total + out
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole[0]), atol=1e-5)

    probs = jax.nn.softmax(xf @ p["router"], axis=-1)
    plain = []
    for t in range(xf.shape[0]):
        top = np.argsort(-np.asarray(probs[t]))[:6]
        y = mlp_forward(p["shared"], xf[t], cfg)
        for e in top:
            expert = {k: p[k][e] for k in ("w_up", "w_gate", "w_down")}
            y = y + probs[t, e] * mlp_forward(expert, xf[t], cfg)
        plain.append(y)
    np.testing.assert_allclose(np.asarray(whole[0]), np.stack(plain), atol=1e-5)


def test_decode_logits_do_not_depend_on_the_batch(params):
    """A lane's logits are the same, to the bit, whichever requests share
    its step (the served MoE drops nothing and mixes no tokens)."""
    cfg = tiny()
    rng = np.random.default_rng(6)
    pools = _pools(cfg, 32)
    mp = 3
    tables = np.arange(1, 1 + 4 * mp, dtype=np.int32).reshape(4, mp)
    lens = [13, 5, 20, 9]
    for lane, n in enumerate(lens):
        _, pools = _chunk(params, cfg, pools, tables[lane],
                          rng.integers(1, cfg.vocab_size, n), 0)
    slot = np.arange(mp * PS)
    kv_pos = np.stack([np.where(slot < n, slot, -1) for n in lens]).astype(np.int32)
    toks = rng.integers(1, cfg.vocab_size, (4, 1)).astype(np.int32)

    def lane0(lanes, tokens):
        lg, _, _ = decode_step_paged(
            params, cfg, pools, jnp.asarray(tables[lanes]),
            jnp.asarray(kv_pos[lanes]), jnp.asarray(tokens),
            jnp.asarray(np.array(lens)[lanes], jnp.int32))
        return np.asarray(lg[0, 0])

    alone = lane0([0, 0, 0, 0], np.repeat(toks[:1], 4, 0))
    mixed = lane0([0, 1, 2, 3], toks)
    other = toks.copy()
    other[1:] = (other[1:] + 11) % cfg.vocab_size
    np.testing.assert_array_equal(mixed, alone)
    np.testing.assert_array_equal(lane0([0, 3, 1, 2], other), alone)


def test_yarn_matches_the_published_formula():
    """DeepSeek-V2's ``yarn_find_correction_range``, ``yarn_linear_ramp_
    mask`` and ``yarn_get_mscale``, written out here, against the
    program's frequencies and softmax scale at the published sizes."""
    cfg = get_config("deepseek-v2-lite")
    dim, base, factor = cfg.qk_rope_dim, cfg.rope_theta, cfg.rope_factor

    def corr_dim(rot):
        return (dim * math.log(cfg.rope_original_max / (rot * 2 * math.pi))) / (
            2 * math.log(base))

    low = max(math.floor(corr_dim(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(corr_dim(cfg.yarn_beta_slow)), dim - 1)
    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    inv = freq_inter * (1 - mask) + freq_extra * mask

    def get_mscale(scale, m):
        return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0

    got, m = yarn_freqs(cfg, dim)
    np.testing.assert_allclose(got, inv, rtol=1e-6)
    assert m == get_mscale(factor, 0.707) / get_mscale(factor, 0.707) == 1.0
    want_scale = (128 + 64) ** -0.5 * get_mscale(factor, 0.707) ** 2
    assert mla.softmax_scale(cfg) == pytest.approx(want_scale, rel=1e-12)
    assert mla.softmax_scale(cfg) == pytest.approx(0.1147, abs=1e-4)
    assert (low, high) == (10, 23)


def test_latent_page_format_at_published_sizes():
    """One pool per group of (L, P, 16, 640), no V pool: the 576-lane
    latent row padded to whole 128-lane tiles, so a page of all 27 layers
    is 27 x 16 x 1280 bytes."""
    cfg = get_config("deepseek-v2-lite")
    assert cfg.latent_width == 576 and latent_row(cfg) == 640
    alloc = PagedKVAllocator(cfg, page_size=16, n_pages=2)
    assert [list(p) for p in alloc.pools] == [["kv"], ["kv"]]
    assert [p["kv"].shape for p in alloc.pools] == [(1, 2, 16, 640), (26, 2, 16, 640)]
    assert alloc.page_bytes == 27 * 16 * 1280 == 552_960


def test_served_sessions_count_moe_work_and_reuse_latent_pages(params):
    """The batched server serves the latent MoE model with session reuse
    on the paged Pallas path, token for token as greedy decoding of the
    full forward pass; with the tracer on, the MoE counters add up to
    top-k pairs per MoE layer for every prompt token and decode lane."""
    cfg = tiny().replace(experts_held=(1, 2))
    p = init_params(jax.random.key(7), cfg)
    pool = SessionCachePool(capacity=4)
    srv = BatchedServer(cfg, p, n_slots=2, max_len=64, paged=True, page_size=PS,
                        kv_pages=40, session_pool=pool, stop_tokens=())
    first = [int(t) for t in np.random.default_rng(8).integers(1, cfg.vocab_size, 11)]
    tracing.start()
    try:
        rid = srv.submit(first, max_new=5, cache_key="s")
        srv.submit(first[:4], max_new=3, cache_key="t")
        out1 = {f.request_id: f for f in srv.run_to_completion()}[rid]
        again = first + out1.token_ids + [7, 9]
        rid = srv.submit(again, max_new=4, cache_key="s")
        out2 = {f.request_id: f for f in srv.run_to_completion()}[rid]
    finally:
        trace = tracing.stop()
    assert out2.reused_tokens >= len(first) + 4
    for ids, n, got in ((first, 5, out1.token_ids), (again, 4, out2.token_ids)):
        seq = list(ids)
        for _ in range(n):
            logits, _ = forward_full(p, cfg, jnp.asarray([seq]))
            seq.append(int(jnp.argmax(logits[0, -1])))
        assert got == seq[len(ids):]
    c = trace.counters
    n_moe = cfg.n_layers - cfg.first_dense_layers
    tokens = c["prefill_chunk_tokens"] + c["decode_lanes"]
    assert c["moe_routed_pairs"] == cfg.top_k * n_moe * tokens
    assert 0 < c["moe_held_pairs"] < c["moe_routed_pairs"]
    assert 0 < c["moe_experts_hit"] <= 2 * n_moe * (c["prefill_chunks"] + c["decode_steps"])
