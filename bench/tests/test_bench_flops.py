"""FLOPs and bytes, against counts made by hand."""

import pytest

from bench import flops
from bench.blocks import block_for, qwen2
from bench.model import load_config

QWEN15 = qwen2.dims_of(load_config("paper-qwen1.5-0.5b"))
# Qwen2-0.5B (huggingface.co/Qwen/Qwen2-0.5B): GQA, 14 heads over 2 KV heads
QWEN2 = qwen2.Dims(layers=24, d_model=896, heads=14, kv_heads=2, d_head=64, d_ff=4864,
                   vocab=151936, rope_theta=1e6, norm_eps=1e-6)
CONFIGS = {"paper-qwen1.5-0.5b": QWEN15, "qwen2-0.5b": QWEN2}


def test_one_decode_token_by_hand():
    # Qwen1.5-0.5B, one token at position 99 (100 keys), with logits:
    # per layer q,k,v,o 4*1024*1024 weights, MLP 3*1024*2816 -> 2 FLOPs each;
    # attention 2*16*64*100 (q.k) + 2*16*64*100 (p.v); unembed 2*1024*151936
    per_layer = 2 * (4 * 1024 * 1024 + 3 * 1024 * 2816) + 4 * 16 * 64 * 100
    want = 24 * per_layer + 2 * 1024 * 151936
    assert qwen2.token_flops(QWEN15, 100, logits=True) == want
    assert qwen2.token_flops(QWEN15, 100, logits=False) == 24 * per_layer


def test_gqa_weights_by_hand():
    # Qwen2-0.5B: q and o 896 x 896, k and v 896 x 128, MLP 3 * 896 * 4864
    assert qwen2.layer_matmul_params(QWEN2) == 2 * 896 * 896 + 2 * 896 * 128 + 3 * 896 * 4864


def test_paged_attention_call_by_hand():
    # two lanes of 40 and 17 keys, pages of 16, a shared run of 1 page:
    # pages 3 and 2, the shared one read once -> 1 + 2 + 1 = 4 pages;
    # a page is K and V: 2 * 16 * 2 heads * 64 * 2 bytes = 8192 bytes
    [(layers, f, b)] = qwen2.paged_attention_call(QWEN2, [40, 17], 1, 16)
    assert layers == 24
    assert f == 4 * 14 * 64 * (40 + 17)
    assert b == 4 * 8192 + 2 * 2 * 14 * 64 * 2


def test_chunked_prefill_call_by_hand():
    # 64 rows at offset 32: rows see 33..96 keys -> 64*32 + 64*65/2 pairs;
    # 96 keys are 6 pages of 16*16*64*2*2 bytes (MHA)
    [(layers, f, b)] = qwen2.chunked_prefill_call(QWEN15, 64, 32, 16)
    assert layers == 24
    assert f == 4 * 16 * 64 * (64 * 32 + 64 * 65 // 2)
    assert b == 6 * (2 * 16 * 16 * 64 * 2) + 2 * 64 * 16 * 64 * 2


def test_bound_and_peaks():
    peaks = flops.peaks_for("TPU v5 lite")
    assert flops.bound_seconds(197e12, 0, peaks) == pytest.approx(1.0)
    assert flops.bound_seconds(0, 819e9, peaks) == pytest.approx(1.0)
    with pytest.raises(KeyError):
        flops.peaks_for("cpu")


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_dims_match_the_program(name):
    from bench.model import program_config

    conf = load_config(name)
    block = block_for(conf)
    assert block is qwen2
    assert block.dims_of(conf) == CONFIGS[name]
    cfg = program_config(conf)
    block.check_program_config(CONFIGS[name], cfg)
    assert (cfg.rope_theta, cfg.norm_eps) == (1e6, 1e-6)
    assert cfg.attn_impl == "pallas"
