"""Blocks (``bench/blocks/``): the Qwen2 block reads, bit for bit, what the
benchmark read before its code moved there (``data/qwen2-identity.json``),
a ``model_type`` with no module is an error that names the file, and a
block with two kinds of layer enters by a module of its own."""

import hashlib
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops, reference, trace, weights
from bench.blocks import block_for, qwen2
from bench.harness import KERNELS, ChunkRec, Run, StepRec
from bench.metrics import chunked_prefill_roofline, paged_attention_roofline, step_mfu
from bench.model import load_config
from bench.tests import toy_block

DATA = Path(__file__).resolve().parent / "data"
RECORDED = json.loads((DATA / "qwen2-identity.json").read_text())
TINY_CONF = json.loads((DATA / "tiny-config.json").read_text())
DIMS = {"tiny": qwen2.dims_of(TINY_CONF),
        "paper": qwen2.dims_of(load_config("paper-qwen1.5-0.5b"))}
READERS = (step_mfu, paged_attention_roofline, chunked_prefill_roofline)


def _digest(a) -> str:
    return hashlib.sha256(np.asarray(a).tobytes()).hexdigest()[:32]


@pytest.fixture(scope="module")
def tiny_params():
    return weights.make_params(qwen2, DIMS["tiny"], TINY_CONF["weights"], RECORDED["seed"],
                               jax.devices()[0])


def _tokens(n, vocab):
    return [(7 * i + 3) % vocab for i in range(n)]


def test_weights_identical(tiny_params):
    got = {jax.tree_util.keystr(p): _digest(v)
           for p, v in jax.tree_util.tree_flatten_with_path(tiny_params)[0]}
    assert got == RECORDED["leaves"]


@pytest.mark.parametrize("quant", ["f32", "fp8"])
def test_reference_logits_identical(tiny_params, quant):
    d = DIMS["tiny"]
    fn = jax.jit(lambda p, t: qwen2.logits(p, d, qwen2.hidden(p, d, t, quant), quant))
    with jax.default_matmul_precision("highest"):
        got = fn(tiny_params, jnp.asarray(_tokens(40, d.vocab), jnp.int32))
    assert _digest(got) == RECORDED["logits"][quant]


def test_gaps_identical(tiny_params):
    ids = _tokens(40, DIMS["tiny"].vocab)
    turn = SimpleNamespace(input_ids=ids[:30], generated=ids[30:])
    got = reference.gaps_of(tiny_params, qwen2, DIMS["tiny"], [turn], control=True)
    assert got == RECORDED["gaps"]


def _counts(d):
    c = {"layer_matmul_params": qwen2.layer_matmul_params(d),
         "page_bytes": qwen2.page_bytes(d, 16)}
    for n in (1, 17, 100, 2048):
        for lg in (True, False):
            c[f"token_flops {n} {lg}"] = qwen2.token_flops(d, n, logits=lg)
    for cc, p0 in ((64, 0), (64, 32), (17, 128), (1, 0)):
        c[f"chunk_flops {cc} {p0}"] = qwen2.chunk_flops(d, cc, p0)
        [(layers, f, b)] = qwen2.chunked_prefill_call(d, cc, p0, 16)
        assert layers == d.layers
        c[f"chunked_prefill_call {cc} {p0}"] = [f, b]
    for lens, sh in (([40, 17], 1), ([100, 57, 300], 2), ([1], 0)):
        [(layers, f, b)] = qwen2.paged_attention_call(d, lens, sh, 16)
        assert layers == d.layers
        c[f"paged_attention_call {lens} {sh}"] = [f, b]
    return c


@pytest.mark.parametrize("dims", sorted(DIMS))
def test_counts_identical(dims):
    got = _counts(DIMS[dims])
    want = RECORDED["counts"][dims]
    assert got == want
    assert [type(v) for v in got.values()] == [
        type(v) if not isinstance(v, list) else list for v in want.values()]


def _run(block, dims):
    tr = trace.reduce(json.loads((DATA / "trace-decode-step.json").read_text()), 0.05, KERNELS)
    # the recorded step ran no chunk: lend its paged-attention time to that kernel
    tr["kernel_s"]["chunked_prefill"] = tr["kernel_s"]["paged_attention"]
    steps = [StepRec(1.0, [100, 57, 300], 2), StepRec(2.0, [40], 0), StepRec(3.0, [], 0),
             StepRec(9.0, [5], 0)]
    chunks = [ChunkRec(1.5, 64, 0), ChunkRec(2.5, 64, 64), ChunkRec(2.7, 17, 128),
              ChunkRec(9.5, 64, 0)]
    return Run(spec=None, block=block, dims=dims, window_ms=40e3, setup_s=0.0, turns=[],
               steps=steps, chunks=chunks, counters={}, page_size=16, trace=tr,
               peaks=flops.peaks_for("TPU v5 lite"), trace_wall=(0.0, 5.0))


@pytest.mark.parametrize("reader", READERS, ids=lambda m: m.__name__.split(".")[-1])
def test_readers_identical(reader):
    name = reader.__name__.split(".")[-1]
    assert reader.read(_run(qwen2, DIMS["paper"])) == RECORDED["readers"][name]


def test_unknown_model_type_names_the_file():
    with pytest.raises(ModuleNotFoundError, match=r"bench/blocks/no_such_block\.py"):
        block_for({"model_type": "no_such_block"})


# ----------------------------------------------------------------------------
# a block with two kinds of layer, one windowed
# ----------------------------------------------------------------------------

TOY_CONF = {"model_type": "toy_two_kinds", "full_layers": 2, "windowed_layers": 3,
            "window": 8, "hidden_size": 32, "num_attention_heads": 2, "head_dim": 16,
            "intermediate_size": 64, "vocab_size": 128, "rms_norm_eps": 1e-6,
            "weights": dict(TINY_CONF["weights"], dtype="float32")}


@pytest.fixture
def toy(monkeypatch):
    monkeypatch.setitem(sys.modules, "bench.blocks.toy_two_kinds", toy_block)
    block = block_for(TOY_CONF)
    d = block.dims_of(TOY_CONF)
    params = weights.make_params(block, d, TOY_CONF["weights"], 2 ** 34 + 11, jax.devices()[0])
    return block, d, params


def test_toy_block_is_found_by_its_model_type(toy):
    block, d, params = toy
    assert block is toy_block
    assert [g["w_qkv"].shape[0] for g in params["groups"]] == [2, 3]


def test_toy_block_window_masks(toy):
    block, d, params = toy
    toks = jnp.asarray(_tokens(24, d.vocab), jnp.int32)
    wide = toy_block.Dims(**dict(vars(d), window=64))
    a = block.hidden(params, d, toks, "f32")
    b = block.hidden(params, wide, toks, "f32")
    assert np.array_equal(a[: d.window], b[: d.window])
    assert not np.allclose(a[d.window:], b[d.window:])


def test_toy_block_through_the_reference(toy):
    block, d, params = toy
    ids = _tokens(20, d.vocab)
    step = jax.jit(lambda p, t: block.logits(p, d, block.hidden(p, d, t, "f32"), "f32")[-1])
    served = []
    with jax.default_matmul_precision("highest"):
        for _ in range(12):
            served.append(int(jnp.argmax(step(params, jnp.asarray(ids + served, jnp.int32)))))
    greedy = SimpleNamespace(input_ids=ids, generated=served)
    got = reference.gaps_of(params, block, d, [greedy], control=True)
    assert got["tokens"] == 12
    assert got["served_gap"] < 1e-4
    wrong = SimpleNamespace(input_ids=ids, generated=[(t + 1) % d.vocab for t in served])
    assert reference.gaps_of(params, block, d, [wrong])["served_gap"] > 1e-3


def test_toy_block_through_the_readers(toy):
    block, d, _ = toy
    run = _run(block, d)
    peaks, steps, chunks = run.peaks, run.steps[:3], run.chunks[:3]

    def need(calls):
        # full layers attend every key, windowed ones the last 8 alone
        (nf, ff, bf), (nw, fw, bw) = calls
        assert (nf, nw) == (2, 3) and fw <= ff and bw <= bf
        return nf * flops.bound_seconds(ff, bf, peaks) + nw * flops.bound_seconds(fw, bw, peaks)

    paged = sum(need(block.paged_attention_call(d, s.kv_lens, s.shared_pages, 16))
                for s in steps if s.kv_lens)
    chunked = sum(need(block.chunked_prefill_call(d, c.c, c.p0, 16)) for c in chunks)
    spent = run.trace["kernel_s"]["paged_attention"]
    assert paged_attention_roofline.read(run) == pytest.approx(100 * paged / spent, rel=1e-12)
    assert chunked_prefill_roofline.read(run) == pytest.approx(100 * chunked / spent, rel=1e-12)
    total = (sum(block.token_flops(d, n, True) for s in steps for n in s.kv_lens)
             + sum(block.chunk_flops(d, c.c, c.p0) for c in chunks))
    want = 100 * total / (run.trace["window_s"] * peaks["bf16_flops_per_s"])
    assert step_mfu.read(run) == pytest.approx(want, rel=1e-12)
    # a windowed layer of a long lane does less than a full one
    (_, ff, _), (_, fw, _) = block.paged_attention_call(d, [300], 0, 16)
    assert fw == ff * 8 / 300
