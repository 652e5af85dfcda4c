"""The DeepSeek-V2 block (``bench/blocks/deepseek_v2.py``) at a small size
on the CPU: its weights are the program's layout, its plain reference
agrees with the program's own forward pass on them, its counts hold
together, and a whole harness run of a small latent-attention MoE cell is
correct, while the fp8 control in the program's place is not."""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, weights
from bench.blocks import block_for, deepseek_v2
from bench.model import load_config
from bench.tests.test_bench_harness import DATA, TINY_LIMIT

FULL = load_config("deepseek-v2-lite-ep8")


def tiny_conf():
    """The published file at a small size: 3 layers (one dense), 8
    experts published, the held share 2 of them from expert 2."""
    conf = json.loads(json.dumps(FULL))
    conf.update(
        num_hidden_layers=3, hidden_size=64, num_attention_heads=4,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32,
        intermediate_size=128, moe_intermediate_size=32, n_routed_experts=2,
        num_experts_per_tok=3, vocab_size=512,
    )
    conf["rope_scaling"] = dict(conf["rope_scaling"], original_max_position_embeddings=64)
    conf["deployment"] = dict(conf["deployment"], experts_held=[2, 2],
                              n_routed_experts_published=8, expert_parallel=4)
    return conf


def tiny_cfg(conf, impl="reference"):
    from repro.configs import get_config

    return get_config(conf["repro_config"]).replace(
        n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, d_head=24, qk_nope_dim=16,
        qk_rope_dim=8, v_head_dim=16, kv_lora_rank=32, d_ff=128, moe_d_ff=32,
        n_experts=8, experts_held=(2, 2), top_k=3, vocab_size=512,
        rope_original_max=64, attn_impl=impl)


def test_full_file_matches_the_program():
    from bench.model import program_config

    d = deepseek_v2.dims_of(FULL)
    deepseek_v2.check_program_config(d, program_config(FULL))
    assert (d.experts, d.held, d.top_k, d.layers) == (64, 8, 6, 27)
    assert deepseek_v2.routed_pairs(d) == 0.75
    assert abs(deepseek_v2.softmax_scale(d) - 0.1147) < 1e-4
    # a page of all 27 layers: 27 x 16 slots x 576 lanes x 2 bytes
    assert d.layers * 16 * deepseek_v2.key_bytes(d) == 497_664


def test_reference_agrees_with_the_program():
    """Independent implementations, float32 both: the block's reference
    on its own weights, and the program's full forward on the same."""
    from repro.models import abstract_params, forward_full

    conf = tiny_conf()
    d = deepseek_v2.dims_of(conf)
    cfg = tiny_cfg(conf).replace(param_dtype="float32", compute_dtype="float32")
    w = dict(conf["weights"], dtype="float32")
    params = weights.make_params(deepseek_v2, d, w, 7, jax.devices()[0])
    weights.check_layout(params, abstract_params(cfg))
    toks = jnp.asarray(np.random.default_rng(0).integers(8, d.vocab, 40), jnp.int32)
    with jax.default_matmul_precision("highest"):
        ref = deepseek_v2.logits(params, d, deepseek_v2.hidden(params, d, toks, "f32"), "f32")
        got, _ = forward_full(params, cfg, toks[None])
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_counts_hold_together():
    d = deepseek_v2.dims_of(FULL)
    one = deepseek_v2.token_flops(d, 1, logits=False)
    assert deepseek_v2.token_flops(d, 101, logits=False) - one == pytest.approx(
        d.layers * deepseek_v2.attention_flops(d, 100))
    assert deepseek_v2.chunk_flops(d, 1, 0) == pytest.approx(
        deepseek_v2.token_flops(d, 1, logits=True))
    calls = deepseek_v2.paged_attention_call(d, [40, 17], 0, 16)
    assert [n for n, _, _ in calls] == [1, 26]
    f, b = calls[0][1:]
    assert f == 2 * 16 * (576 + 512) * 57
    assert b == (3 + 2) * 16 * 1152 + 2 * 16 * (576 + 512) * 2
    [(n0, f0, b0), (n1, f1, b1)] = deepseek_v2.chunked_prefill_call(d, 64, 32, 16)
    assert (n0, n1) == (1, 26) and (f0, b0) == (f1, b1)
    assert f0 == 2 * 16 * 1088 * (64 * 32 + 64 * 65 // 2)


def _tiny_spec():
    """The qwen harness test's small traffic and cell settings, limit
    included, on the small DeepSeek-V2 block."""
    from bench import harness

    conf = tiny_conf()
    mix = json.loads((DATA / "tiny-roam.json").read_text())
    cell = {"nodes": 2, "kv_ship": True, "policy": "strong", "mode": "tokenized",
            "warmup_s": 1.0, "sample_tokens": 40, "gap_limit": TINY_LIMIT}
    return harness.CellSpec("tiny-dsv2", conf, mix, cell, tiny_cfg(conf))


@pytest.mark.parametrize("control", [False, True])
def test_harness_run(control):
    from bench import harness
    from bench.run import ROOT, readers_for

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    out = harness.run(_tiny_spec(), 2 ** 33 + 5, 2.0, False, time.perf_counter(),
                      jax.devices()[0], readers_for(bench, "dsv2lite-roam-chat", False),
                      control=control, log=lambda *a: None)
    assert out["attempted"] > 10 and out["failed"] == 0
    assert out["correct"] != control, out["checks"]
    assert {"itl_p95_ms", "response_p50_ms", "setup_s"} <= set(out["metrics"])
