"""Pallas kernel validation: deterministic shape/dtype/feature sweeps
against the pure-jnp oracles (interpret mode on CPU). The hypothesis
property sweeps live in test_kernel_properties.py so this module runs even
where hypothesis isn't installed."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention import decode_attention, decode_attention_ref
from repro.kernels.flash_attention import flash_attention, flash_attention_ref
from repro.kernels.paged_attention import paged_attention, paged_attention_ref
from repro.kernels.ssd import ssd, ssd_ref, ssd_sequential


def _attn_inputs(key, B, S, T, H, KV, Dh, dtype=jnp.float32, qpos_val=None):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, Dh), dtype)
    k = jax.random.normal(ks[1], (B, T, KV, Dh), dtype)
    v = jax.random.normal(ks[2], (B, T, KV, Dh), dtype)
    q_pos = (
        jnp.broadcast_to(jnp.arange(S), (B, S)).astype(jnp.int32)
        if qpos_val is None
        else jnp.full((B, S), qpos_val, jnp.int32)
    )
    kv_pos = jnp.broadcast_to(jnp.arange(T), (B, T)).astype(jnp.int32)
    valid = jnp.ones((B, T), bool)
    return q, k, v, q_pos, kv_pos, valid


# ---------------------------------------------------------------------------
# flash attention — shape/dtype/feature sweep
# ---------------------------------------------------------------------------
SHAPES = [
    (1, 16, 16, 4, 4, 32),    # MHA
    (2, 32, 32, 4, 2, 32),    # GQA g=2
    (2, 64, 64, 8, 1, 16),    # MQA
    (1, 48, 48, 4, 2, 64),    # non-pow2 seq (padding path)
]


@pytest.mark.parametrize("B,S,T,H,KV,Dh", SHAPES)
@pytest.mark.parametrize(
    "dtype",
    [jnp.float32, pytest.param(jnp.bfloat16, marks=pytest.mark.slow)],
)
def test_flash_sweep(B, S, T, H, KV, Dh, dtype):
    args = _attn_inputs(jax.random.key(0), B, S, T, H, KV, Dh, dtype)
    out = flash_attention(*args, block_q=16, block_k=16)
    ref = flash_attention_ref(*args)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=tol, atol=tol
    )


@pytest.mark.parametrize("window", [0, 8, 17])
@pytest.mark.parametrize("softcap", [0.0, 30.0])
def test_flash_window_softcap(window, softcap):
    args = _attn_inputs(jax.random.key(1), 2, 32, 32, 4, 2, 32)
    out = flash_attention(*args, window=window, softcap=softcap, block_q=16, block_k=16)
    ref = flash_attention_ref(*args, window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_flash_padded_kv_masked():
    q, k, v, qp, kp, valid = _attn_inputs(jax.random.key(2), 1, 16, 32, 4, 2, 32)
    valid = valid.at[:, 20:].set(False)
    out = flash_attention(q, k, v, qp, kp, valid, block_q=16, block_k=16)
    ref = flash_attention_ref(q, k, v, qp, kp, valid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("T,H,KV,Dh", [(64, 4, 2, 32), (96, 8, 8, 16), (128, 4, 1, 64)])
@pytest.mark.parametrize(
    "dtype",
    [jnp.float32, pytest.param(jnp.bfloat16, marks=pytest.mark.slow)],
)
def test_decode_sweep(T, H, KV, Dh, dtype):
    key = jax.random.key(0)
    ks = jax.random.split(key, 3)
    B = 2
    q = jax.random.normal(ks[0], (B, 1, H, Dh), dtype)
    k = jax.random.normal(ks[1], (B, T, KV, Dh), dtype)
    v = jax.random.normal(ks[2], (B, T, KV, Dh), dtype)
    q_pos = jnp.full((B, 1), T - 10, jnp.int32)
    kv_pos = jnp.broadcast_to(jnp.arange(T), (B, T)).astype(jnp.int32)
    valid = kv_pos <= T - 10
    out = decode_attention(q, k, v, q_pos, kv_pos, valid, block_k=32)
    ref = decode_attention_ref(q, k, v, q_pos, kv_pos, valid)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=tol, atol=tol
    )


def test_decode_ring_order_independent():
    """Ring caches store positions out of order — masking must be positional."""
    key = jax.random.key(5)
    B, T, H, KV, Dh = 1, 32, 4, 2, 16
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, 1, H, Dh))
    k = jax.random.normal(ks[1], (B, T, KV, Dh))
    v = jax.random.normal(ks[2], (B, T, KV, Dh))
    kv_pos = jnp.asarray(np.random.default_rng(0).permutation(T)[None, :], jnp.int32)
    q_pos = jnp.full((B, 1), T + 5, jnp.int32)
    valid = jnp.ones((B, T), bool)
    out = decode_attention(q, k, v, q_pos, kv_pos, valid, window=16, block_k=8)
    ref = decode_attention_ref(q, k, v, q_pos, kv_pos, valid, window=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# paged attention (decode through a page table)
# ---------------------------------------------------------------------------
def _paged_inputs(key, lens, ps, H, KV, Dh, dtype=jnp.float32, mp=None):
    """One pool + per-lane page tables for ragged session lengths ``lens``
    (0 = empty lane). Each lane owns ceil(n/ps) distinct physical pages;
    page 0 is the scratch page (table padding)."""
    B = len(lens)
    pages_of = lambda n: max(1, -(-n // ps))
    if mp is None:
        mp = max(pages_of(n) for n in lens)
    n_pages = 1 + sum(pages_of(n) for n in lens)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, 1, H, Dh), dtype)
    pool_k = jax.random.normal(ks[1], (n_pages, ps, KV, Dh), dtype)
    pool_v = jax.random.normal(ks[2], (n_pages, ps, KV, Dh), dtype)
    table = np.zeros((B, mp), np.int32)
    kvpos = np.full((B, mp * ps), -1, np.int32)
    used = 1
    for bi, n in enumerate(lens):
        for pj in range(pages_of(n)):
            table[bi, pj] = used
            used += 1
        kvpos[bi, :n] = np.arange(n)
    q_pos = jnp.asarray([[max(n - 1, 0)] for n in lens], jnp.int32)
    return q, pool_k, pool_v, jnp.asarray(table), q_pos, jnp.asarray(kvpos)


# ragged lane lengths: empty, sub-page, exact page boundary, multi-page+tail
RAGGED = (0, 5, 16, 41)


@pytest.mark.parametrize("ps", [8, 16, 64])
def test_paged_page_sizes(ps):
    args = _paged_inputs(jax.random.key(0), RAGGED, ps, 4, 2, 32)
    out = paged_attention(*args)
    ref = paged_attention_ref(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize(
    "H,KV,Dh,dtype",
    [
        (4, 4, 32, jnp.float32),    # MHA
        (8, 2, 32, jnp.float32),    # GQA g=4
        (4, 1, 64, jnp.float32),    # MQA
        (8, 2, 32, jnp.bfloat16),   # GQA in the serving dtype
    ],
)
def test_paged_gqa_sweep(H, KV, Dh, dtype):
    args = _paged_inputs(jax.random.key(1), RAGGED, 16, H, KV, Dh, dtype)
    out = paged_attention(*args)
    ref = paged_attention_ref(*args)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=tol, atol=tol
    )


@pytest.mark.parametrize("window", [0, 17])
@pytest.mark.parametrize("softcap", [0.0, 30.0])
def test_paged_window_softcap(window, softcap):
    args = _paged_inputs(jax.random.key(2), (3, 23, 48), 8, 4, 2, 32)
    out = paged_attention(*args, window=window, softcap=softcap)
    ref = paged_attention_ref(*args, window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


@pytest.mark.slow
def test_paged_empty_lane_is_zero():
    """A lane with no valid key must produce exact zeros — the only answer
    independent of how many pages the bounded grid visits (the gather
    fallback's output there is garbage-by-design and unread)."""
    args = _paged_inputs(jax.random.key(3), (0, 12), 8, 4, 2, 16)
    out = paged_attention(*args)
    assert np.all(np.asarray(out[0]) == 0.0)
    ref = paged_attention_ref(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_paged_max_pages_trim_equivalent():
    """Trimming the static table width (page-width bucketing) must not
    change the output as long as every lane's tokens fit in the trim."""
    args = _paged_inputs(jax.random.key(4), (7, 20), 8, 4, 2, 16, mp=16)
    full = paged_attention(*args)
    trimmed = paged_attention(*args, max_pages=3)   # ceil(20/8) == 3
    np.testing.assert_allclose(np.asarray(full), np.asarray(trimmed), rtol=1e-6, atol=1e-6)


def test_paged_matches_gather_plus_decode_kernel():
    """The paged kernel through the table == the dense decode kernel over
    the gather-materialized view (the two serving decode paths)."""
    from repro.models.cache import gather_pages

    q, pk, pv, table, q_pos, kv_pos = _paged_inputs(
        jax.random.key(5), (9, 33), 8, 4, 2, 32
    )
    out = paged_attention(q, pk, pv, table, q_pos, kv_pos)
    ck = gather_pages(pk, table)
    cv = gather_pages(pv, table)
    dense = decode_attention(q, ck, cv, q_pos, kv_pos, kv_pos >= 0, block_k=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), rtol=1e-5, atol=1e-5)


@pytest.mark.slow
def test_paged_mrope_positions():
    """attention_decode_paged with M-RoPE positions: the kernel consumes the
    rope'd q, so the pallas path must match the gather reference exactly
    under the 3-axis position layout."""
    from repro.models import ModelConfig
    from repro.models.attention import attention_decode_paged, init_attention

    cfg = ModelConfig(
        name="mrope-paged", arch_type="dense", n_layers=1, d_model=64,
        n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
        rope_style="mrope", mrope_sections=(2, 3, 3),  # sums to d_head / 2
        param_dtype="float32", compute_dtype="float32",
    )
    p = init_attention(jax.random.key(6), cfg)
    _, pool_k, pool_v, table, q_pos, kv_pos = _paged_inputs(
        jax.random.key(7), (21,), 8, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    )
    x = jax.random.normal(jax.random.key(8), (1, 1, cfg.d_model))
    positions = jnp.broadcast_to(q_pos[None], (3, 1, 1))
    out_k = attention_decode_paged(
        p, x, positions, pool_k, pool_v, table, kv_pos,
        cfg.replace(attn_impl="pallas"),
    )
    out_r = attention_decode_paged(
        p, x, positions, pool_k, pool_v, table, kv_pos, cfg
    )
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), rtol=1e-5, atol=1e-5)


@pytest.mark.slow
@pytest.mark.parametrize("ps", [8, 16, 64])
@pytest.mark.parametrize("H,KV,Dh", [(4, 4, 16), (8, 2, 32), (4, 1, 32)])
@pytest.mark.parametrize("window", [0, 19])
def test_paged_full_matrix(ps, H, KV, Dh, window):
    """Full deterministic equivalence matrix: every page size x GQA
    grouping x window over ragged lanes (empty, sub-page, exact boundary,
    multi-page) — the exhaustive complement of the fast-gate sweeps."""
    lens = (0, 1, ps - 1, ps, 2 * ps, 2 * ps + 3)
    args = _paged_inputs(jax.random.key(9), lens, ps, H, KV, Dh)
    out = paged_attention(*args, window=window)
    ref = paged_attention_ref(*args, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# shared-prefix (cascade) paged attention
# ---------------------------------------------------------------------------
def _shared_paged_inputs(key, n_shared, suffix_lens, ps, H, KV, Dh,
                         dtype=jnp.float32):
    """Pool + page tables where every lane's first ``n_shared`` pages are
    the SAME physical pages (a cross-session shared prefix) and each lane
    owns fresh pages for its ragged suffix. Lane bi holds
    ``n_shared * ps + suffix_lens[bi]`` tokens. Returns the per-lane kernel
    args plus the shared-page run to hand to the fused cascade path."""
    B = len(suffix_lens)
    pages_of = lambda n: -(-n // ps)
    mp = n_shared + max(pages_of(n) for n in suffix_lens)
    mp = max(mp, n_shared)
    n_pages = 1 + n_shared + sum(pages_of(n) for n in suffix_lens)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, 1, H, Dh), dtype)
    pool_k = jax.random.normal(ks[1], (n_pages, ps, KV, Dh), dtype)
    pool_v = jax.random.normal(ks[2], (n_pages, ps, KV, Dh), dtype)
    shared = list(range(1, 1 + n_shared))
    table = np.zeros((B, mp), np.int32)
    kvpos = np.full((B, mp * ps), -1, np.int32)
    used = 1 + n_shared
    for bi, sfx in enumerate(suffix_lens):
        n = n_shared * ps + sfx
        table[bi, :n_shared] = shared
        for pj in range(pages_of(sfx)):
            table[bi, n_shared + pj] = used
            used += 1
        kvpos[bi, :n] = np.arange(n)
    q_pos = jnp.asarray(
        [[n_shared * ps + sfx - 1] for sfx in suffix_lens], jnp.int32
    )
    return (
        q, pool_k, pool_v, jnp.asarray(table), q_pos, jnp.asarray(kvpos),
        jnp.asarray(shared, jnp.int32),
    )


def _assert_shared_prefix_equiv(args, sp, window=0, softcap=0.0):
    """The cascade path (one shared-prefix pass + per-lane suffix pass
    merged via online-softmax stats) vs the single-pass per-lane kernel vs
    the pure-jnp oracle. The two kernel executions reorder nothing — the
    suffix pass CONTINUES the shared pass's running (acc, m, l) — so they
    must agree bit-for-bit, not just numerically."""
    fused = paged_attention(*args, sp, window=window, softcap=softcap)
    per_lane = paged_attention(*args, window=window, softcap=softcap)
    ref = paged_attention_ref(*args, window=window, softcap=softcap)
    assert jnp.array_equal(fused, per_lane), "cascade != single-pass"
    np.testing.assert_allclose(
        np.asarray(fused), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_shared_prefix_ragged_suffixes():
    """Fast gate: 2 shared pages, suffixes covering zero-length (q inside
    the shared run), single token, page boundary, and multi-page."""
    *args, sp = _shared_paged_inputs(
        jax.random.key(10), 2, (0, 1, 16, 19), 16, 4, 2, 32
    )
    _assert_shared_prefix_equiv(tuple(args), sp)


@pytest.mark.slow
def test_shared_prefix_all_lanes_identical():
    """Every lane is the same sequence (suffix 0, table width == run
    length): start clamps to mp - 1 so the suffix pass still owns the last
    page, and outputs must match lanes that never shared at all."""
    *args, sp = _shared_paged_inputs(
        jax.random.key(11), 3, (0, 0, 0), 8, 4, 2, 16
    )
    _assert_shared_prefix_equiv(tuple(args), sp)


@pytest.mark.slow
def test_shared_prefix_window_cuts_into_run():
    """A sliding window smaller than the shared prefix: the shared pass
    must mask positions outside [q_pos - window, q_pos] even though every
    lane reads the same pages."""
    *args, sp = _shared_paged_inputs(
        jax.random.key(12), 3, (2, 9), 8, 4, 2, 16
    )
    _assert_shared_prefix_equiv(tuple(args), sp, window=11)
    _assert_shared_prefix_equiv(tuple(args), sp, softcap=8.0)


@pytest.mark.slow
@pytest.mark.parametrize("ps", [8, 16, 64])
@pytest.mark.parametrize("H,KV,Dh", [(4, 4, 16), (8, 2, 32), (4, 1, 32)])
@pytest.mark.parametrize("n_shared", [1, 3])
def test_shared_prefix_full_matrix(ps, H, KV, Dh, n_shared):
    """Exhaustive cascade matrix: MHA/GQA/MQA x page size x shared-run
    length over ragged suffixes (zero-length, sub-page, boundary,
    multi-page) — the shared-prefix complement of test_paged_full_matrix."""
    suffixes = (0, 1, ps - 1, ps, 2 * ps + 3)
    *args, sp = _shared_paged_inputs(
        jax.random.key(13), n_shared, suffixes, ps, H, KV, Dh
    )
    _assert_shared_prefix_equiv(tuple(args), sp)
    _assert_shared_prefix_equiv(tuple(args), sp, window=ps + 3)


def test_attention_decode_paged_shared_matches_reference():
    """Model layer: attention_decode_paged with a shared-page run (pallas
    cascade) == without (per-lane kernel) == gather reference — the fallback
    stays bit-compatible whether or not sharing is plumbed through."""
    from repro.models import ModelConfig
    from repro.models.attention import attention_decode_paged, init_attention

    cfg = ModelConfig(
        name="shared-paged", arch_type="dense", n_layers=1, d_model=64,
        n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
        param_dtype="float32", compute_dtype="float32",
    )
    p = init_attention(jax.random.key(14), cfg)
    _, pool_k, pool_v, table, q_pos, kv_pos, sp = _shared_paged_inputs(
        jax.random.key(15), 2, (3, 12), 8, cfg.n_heads, cfg.n_kv_heads,
        cfg.d_head,
    )
    x = jax.random.normal(jax.random.key(16), (2, 1, cfg.d_model))
    kcfg = cfg.replace(attn_impl="pallas")
    out_shared = attention_decode_paged(
        p, x, q_pos, pool_k, pool_v, table, kv_pos, kcfg, shared_pages=sp
    )
    out_kernel = attention_decode_paged(
        p, x, q_pos, pool_k, pool_v, table, kv_pos, kcfg
    )
    out_ref = attention_decode_paged(
        p, x, q_pos, pool_k, pool_v, table, kv_pos, cfg, shared_pages=sp
    )
    assert jnp.array_equal(out_shared, out_kernel)
    np.testing.assert_allclose(
        np.asarray(out_shared), np.asarray(out_ref), rtol=1e-5, atol=1e-5
    )


# ---------------------------------------------------------------------------
# layer stacks: the kernels read one layer of a group's pool in place
# ---------------------------------------------------------------------------
N_LAYERS = 5


def _layer_stack(key, pool):
    """``N_LAYERS`` random layers shaped like the one-layer ``pool``, as
    ``(L, P, ps, KV, Dh)`` and as the flat ``(L, P, ps, KV*Dh)`` stack
    ``init_paged_pool`` lays out. Every layer differs, so a kernel that
    reads the wrong one shows."""
    layers = jax.random.normal(key, (N_LAYERS,) + pool.shape, pool.dtype)
    return layers, layers.reshape(layers.shape[:3] + (-1,))


def _chunk_inputs(key, H, KV, Dh, ps=16, s=24):
    """Two lanes of a bucketed ``s``-row chunk: a ragged first chunk
    (13 rows at 0) and one that starts a page in and crosses the next
    page boundary (20 rows at 16)."""
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (2, s, H, Dh))
    pool_k = jax.random.normal(ks[1], (7, ps, KV, Dh))
    pool_v = jax.random.normal(ks[2], (7, ps, KV, Dh))
    table = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    p0 = jnp.asarray([0, ps], jnp.int32)
    true_len = jnp.asarray([13, 20], jnp.int32)
    return q, pool_k, pool_v, table, p0, true_len


@pytest.mark.parametrize("layer", [0, N_LAYERS // 2, N_LAYERS - 1])
@pytest.mark.parametrize("H,KV,Dh", [(4, 4, 32), (8, 2, 32)], ids=["mha", "gqa"])
@pytest.mark.parametrize("kernel", ["paged", "shared_prefix", "chunked"])
def test_layer_of_stack_matches_one_layer_pool(kernel, H, KV, Dh, layer):
    """Each kernel attending through a group's pool stack at ``layer`` gives
    the same bits as its one-layer entry on that layer's pool: the layer
    index only picks the blocks the index maps fetch. Ragged lanes
    throughout (empty, sub-page, page boundary, multi-page)."""
    from repro.kernels.chunked_prefill import chunked_prefill_attention

    key = jax.random.key(20)
    if kernel == "chunked":
        q, pool_k, pool_v, table, p0, true_len = _chunk_inputs(key, H, KV, Dh)
        lk, sk = _layer_stack(jax.random.key(21), pool_k)
        lv, sv = _layer_stack(jax.random.key(22), pool_v)
        one = chunked_prefill_attention(q, lk[layer], lv[layer], table, p0, true_len)
        stacked = chunked_prefill_attention(q, sk, sv, table, p0, true_len, layer)
    else:
        if kernel == "paged":
            args, sp = _paged_inputs(key, RAGGED, 16, H, KV, Dh), None
        else:
            *args, sp = _shared_paged_inputs(key, 2, (0, 1, 16, 19), 16, H, KV, Dh)
        q, pool_k, pool_v, table, q_pos, kv_pos = args
        lk, sk = _layer_stack(jax.random.key(21), pool_k)
        lv, sv = _layer_stack(jax.random.key(22), pool_v)
        one = paged_attention(q, lk[layer], lv[layer], table, q_pos, kv_pos, sp)
        stacked = paged_attention(q, sk, sv, table, q_pos, kv_pos, sp, layer)
    assert jnp.array_equal(one, stacked)


# ---------------------------------------------------------------------------
# SSD
# ---------------------------------------------------------------------------
def _ssd_inputs(key, B, L, H, P, N):
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bv = jax.random.normal(ks[3], (B, L, 1, N))
    Cv = jax.random.normal(ks[4], (B, L, 1, N))
    return x, dt, A, Bv, Cv


@pytest.mark.parametrize(
    "L,chunk",
    [
        (32, 8),
        pytest.param(64, 16, marks=pytest.mark.slow),
        pytest.param(64, 64, marks=pytest.mark.slow),   # single-chunk limit
        pytest.param(48, 16, marks=pytest.mark.slow),   # ragged tail
    ],
)
@pytest.mark.parametrize(
    "H,P,N",
    [(2, 16, 8), pytest.param(4, 32, 16, marks=pytest.mark.slow)],
)
def test_ssd_sweep(L, chunk, H, P, N):
    x, dt, A, Bv, Cv = _ssd_inputs(jax.random.key(0), 2, L, H, P, N)
    y_seq, f_seq = ssd_sequential(x, dt, A, Bv, Cv)
    y_k, f_k = ssd(x, dt, A, Bv, Cv, chunk)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_seq), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(f_k), np.asarray(f_seq), rtol=1e-4, atol=1e-4)


@pytest.mark.slow
def test_ssd_chunk_invariance():
    """Same result for any chunking — the SSD decomposition's core property."""
    x, dt, A, Bv, Cv = _ssd_inputs(jax.random.key(1), 1, 48, 2, 16, 8)
    y1, f1 = ssd(x, dt, A, Bv, Cv, 8)
    y2, f2 = ssd(x, dt, A, Bv, Cv, 16)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(f1), np.asarray(f2), rtol=1e-4, atol=1e-4)


def test_ssd_initial_state():
    x, dt, A, Bv, Cv = _ssd_inputs(jax.random.key(2), 2, 32, 2, 16, 8)
    h0 = jax.random.normal(jax.random.key(3), (2, 2, 16, 8))
    y_seq, f_seq = ssd_sequential(x, dt, A, Bv, Cv, h0)
    y_k, f_k = ssd(x, dt, A, Bv, Cv, 8, h0)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_seq), rtol=1e-4, atol=1e-4)


@pytest.mark.slow
def test_ssd_state_continuation():
    """Processing [first half] then [second half with carried state] must
    equal processing the whole sequence — the basis of chunked prefill AND
    of DisCEdge state migration for SSM archs."""
    x, dt, A, Bv, Cv = _ssd_inputs(jax.random.key(4), 1, 64, 2, 16, 8)
    y_all, f_all = ssd_sequential(x, dt, A, Bv, Cv)
    half = 32
    y1, f1 = ssd(x[:, :half], dt[:, :half], A, Bv[:, :half], Cv[:, :half], 8)
    y2, f2 = ssd(x[:, half:], dt[:, half:], A, Bv[:, half:], Cv[:, half:], 8, f1)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y_all[:, half:]), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(f2), np.asarray(f_all), rtol=1e-4, atol=1e-4)


@pytest.mark.slow
def test_ssd_gradients_finite_with_large_decay():
    """Regression: exp(seg) at masked (i<j) positions used to overflow to
    inf and poison gradients through the where (NaN after a few train
    steps). Large dt·A products exercise the overflow path."""
    x, dt, A, Bv, Cv = _ssd_inputs(jax.random.key(9), 1, 32, 2, 8, 4)
    dt = dt * 8.0          # big decays -> big positive seg at masked entries
    from repro.models.ssm import ssd_reference

    def loss(args):
        y, f = ssd_reference(*args, chunk=8)
        return jnp.sum(y ** 2) + jnp.sum(f ** 2)

    g = jax.grad(loss)((x, dt, A, Bv, Cv))
    for leaf in g:
        assert bool(jnp.isfinite(leaf).all())
