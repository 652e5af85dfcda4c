"""A whole run of the harness on the CPU at a small size, on its real
path but without the look for a chip: a sound run is correct, and each
fault planted in the timed path, and the fp8 control put in the program's
place, make ``correct`` come out false."""

import json
import time
from pathlib import Path

import pytest

DATA = Path(__file__).resolve().parent / "data"
# The small cell's limit, set from its own readings like a real cell's:
# over 13 seeds sound runs read at most 0.0057 and the fp8 control at
# least 0.025 (PERF.md); the planted faults read 0.45 and more.
TINY_LIMIT = 0.012


def tiny_spec():
    from bench import harness
    from repro.models import ModelConfig

    conf = json.loads((DATA / "tiny-config.json").read_text())
    mix = json.loads((DATA / "tiny-roam.json").read_text())
    cfg = ModelConfig(
        name="tiny-qwen", arch_type="dense", n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, d_head=16, d_ff=128, vocab_size=512, qkv_bias=True,
        tie_embeddings=True, attn_impl="reference",
    )
    cell = {"nodes": 2, "kv_ship": True, "policy": "strong", "mode": "tokenized",
            "warmup_s": 1.0, "sample_tokens": 40, "gap_limit": TINY_LIMIT}
    return harness.CellSpec("tiny", conf, mix, cell, cfg)


def plant(cluster, fault):
    """Break the timed path underneath: ``token`` alters every 7th
    generated token where the scheduler appends it; ``kv_unchanged`` makes
    each decode step return the KV pool it was given; ``replica`` drops
    every other replicated context write."""
    import jax
    import jax.numpy as jnp

    for node in cluster.nodes.values():
        srv = node.service.server
        if fault == "token":
            def step(step=srv.step, srv=srv):
                step()
                for s in srv.slots:
                    if s is not None and s.generated and len(s.generated) % 7 == 0:
                        s.generated[-1] = (s.generated[-1] + 1) % srv.cfg.vocab_size
            srv.step = step
        if fault == "kv_unchanged":
            def decode(params, pools, *a, decode=srv._decode_paged):
                logits, _, kv_pos = decode(params, jax.tree.map(jnp.copy, pools), *a)
                return logits, pools, kv_pos
            srv._decode_paged = decode
    if fault == "replica":
        deliver, count = cluster.store._on_payload_delivered, [0]

        def deliver_half(*a, **k):
            count[0] += 1
            if count[0] % 2:
                return deliver(*a, **k)
        cluster.store._on_payload_delivered = deliver_half


def run_tiny(seed=12345678901, fault=None, monkeypatch=None, log=None, **kw):
    import jax

    from bench import harness
    from bench.run import ROOT, readers_for

    if fault is not None:
        build = harness.build_cluster

        def build_broken(*a, **k):
            cluster = build(*a, **k)
            plant(cluster, fault)
            return cluster
        monkeypatch.setattr(harness, "build_cluster", build_broken)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return harness.run(tiny_spec(), seed, 2.0, False, time.perf_counter(),
                       jax.devices()[0], readers_for(bench, "qwen15-roam-chat", False),
                       log=log or (lambda *a: None), **kw)


def test_sound_run_is_correct():
    lines = []
    out = run_tiny(log=lines.append)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 10 and out["failed"] == 0
    assert set(out["metrics"]) == {"itl_p95_ms", "response_p50_ms", "setup_s"}
    pages = next(x for x in lines if x.startswith("pages: "))
    assert "edge-0" in pages and "edge-1" in pages


@pytest.mark.parametrize("fault,check", [
    ("token", "served_logit_gap"),          # a token altered where it is produced
    ("kv_unchanged", "served_logit_gap"),   # a decode step that returns its KV unchanged
    ("replica", "replica_mismatches"),      # a replicated context write lost
])
def test_planted_fault_is_not_correct(fault, check, monkeypatch):
    out = run_tiny(fault=fault, monkeypatch=monkeypatch)
    assert not out["correct"]
    c = out["checks"][check]
    assert c["value"] > c["limit"], out["checks"]


@pytest.mark.parametrize("seed", [5, 2 ** 35 + 3, 77])
def test_fp8_control_is_not_correct(seed):
    out = run_tiny(seed=seed, control=True)
    assert not out["correct"]
    assert out["checks"]["served_logit_gap"]["value"] > TINY_LIMIT
