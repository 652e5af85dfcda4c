"""Ahead-of-time compiles for a described TPU v5e: no chip is needed, and
nothing runs. Mosaic refuses what interpret mode accepts — blocks whose
last two dims break the (8, 128) tiling rule, kernels over the VMEM limit —
and XLA refuses a step that does not fit the device's memory. These tests
compile the serving kernels at published head widths and one whole paged
decode step of the paper's model, so such a refusal shows up here instead
of on the chip.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and a module that did so while being
collected would give pytest-xdist workers different test lists."""

import functools
import os

import jax
import jax.numpy as jnp
import pytest

# (n_heads, n_kv_heads, d_head) come from the configs themselves
ARCHS = ["paper-qwen1.5-0.5b", "qwen2-0.5b"]
V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def chip_compile(one_chip):
    """``compile(fn, *shape_trees)`` for one v5e chip, with JAX's
    persistent cache off: an entry compiled for a described chip cannot be
    read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def place(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
            tree,
        )

    def compile_(fn, *trees, donate_argnums=()):
        args = [place(t) for t in trees]
        return jax.jit(fn, donate_argnums=donate_argnums).lower(*args).compile()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _widths(arch):
    from repro.configs import get_config

    cfg = get_config(arch)
    return cfg.n_heads, cfg.n_kv_heads, cfg.d_head


def _s(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


B, PS, MP = 4, 16, 128          # 4 lanes, 16-token pages, max_len 2048
N_PAGES = 1 + B * MP


def _paged_shapes(arch, lanes=B):
    h, kv, dh = _widths(arch)
    i32 = jnp.int32
    return (
        _s((lanes, 1, h, dh)), _s((N_PAGES, PS, kv, dh)),
        _s((N_PAGES, PS, kv, dh)), _s((lanes, MP), i32), _s((lanes, 1), i32),
        _s((lanes, MP * PS), i32),
    )


@pytest.mark.parametrize("arch", ARCHS)
def test_paged_decode_compiles(chip_compile, arch):
    from repro.kernels.paged_attention import paged_attention

    fn = functools.partial(paged_attention, interpret=False)
    _assert_kernel(chip_compile(fn, *_paged_shapes(arch)))


@pytest.mark.parametrize("lanes", [B, 32])
@pytest.mark.parametrize("arch", ARCHS)
def test_paged_decode_shared_prefix_compiles(chip_compile, arch, lanes):
    """The cascade pass (one DMA per shared page for every lane) and the
    suffix pass seeded with its softmax stats; at 32 lanes the pass's VMEM,
    which holds every lane's softmax state, must still fit."""
    from repro.kernels.paged_attention import paged_attention

    fn = functools.partial(paged_attention, interpret=False)
    compiled = chip_compile(fn, *_paged_shapes(arch, lanes), _s((8,), jnp.int32))
    assert compiled.as_text().count("tpu_custom_call") >= 2


@pytest.mark.parametrize("arch", ARCHS)
def test_chunked_prefill_compiles(chip_compile, arch):
    """At the 256-token chunk cap, the largest query block prefill uses."""
    from repro.kernels.chunked_prefill import chunked_prefill_attention

    h, kv, dh = _widths(arch)
    i32 = jnp.int32
    fn = functools.partial(chunked_prefill_attention, interpret=False)
    _assert_kernel(chip_compile(
        fn, _s((1, 256, h, dh)), _s((N_PAGES, PS, kv, dh)),
        _s((N_PAGES, PS, kv, dh)), _s((1, MP), i32), _s((1,), i32),
        _s((1,), i32),
    ))


@pytest.mark.parametrize("arch", ARCHS)
def test_flash_decode_compiles(chip_compile, arch):
    from repro.kernels.decode_attention import decode_attention

    h, kv, dh = _widths(arch)
    t = MP * PS
    fn = functools.partial(decode_attention, interpret=False)
    _assert_kernel(chip_compile(
        fn, _s((B, 1, h, dh)), _s((B, t, kv, dh)), _s((B, t, kv, dh)),
        _s((B, 1), jnp.int32), _s((B, t), jnp.int32), _s((B, t), jnp.bool_),
    ))


@pytest.mark.parametrize("arch", ARCHS)
def test_flash_attention_compiles(chip_compile, arch):
    """Two lanes, so the position blocks are a strict part of their
    arrays."""
    from repro.kernels.flash_attention import flash_attention

    h, kv, dh = _widths(arch)
    s = 256
    fn = functools.partial(flash_attention, interpret=False)
    _assert_kernel(chip_compile(
        fn, _s((2, s, h, dh)), _s((2, s, kv, dh)), _s((2, s, kv, dh)),
        _s((2, s), jnp.int32), _s((2, s), jnp.int32), _s((2, s), jnp.bool_),
    ))


def test_paged_decode_step_fits_v5e(chip_compile, monkeypatch):
    """One whole jitted paged decode step of the paper's model at full
    width, against the page pool a default batched service allocates at
    max_len 2048, compiles with the Pallas kernels in it and fits one
    chip's HBM."""
    from repro.configs import get_config
    from repro.kernels.chunked_prefill import ops as chunk_ops
    from repro.kernels.decode_attention import ops as decode_ops
    from repro.kernels.flash_attention import ops as flash_ops
    from repro.kernels.paged_attention import ops as paged_ops
    from repro.models import decode_step_paged, init_params, layer_groups

    # the kernel wrappers pick interpret mode from the backend, which is the
    # CPU here; the step is compiled for the chip, so steer them to Mosaic
    for mod in (paged_ops, chunk_ops, decode_ops, flash_ops):
        monkeypatch.setattr(mod, "_on_cpu", lambda: False)

    cfg = get_config("paper-qwen1.5-0.5b").replace(attn_impl="pallas")
    n_slots, max_len, entries = 4, 2048, 8
    n_pages = 1 + (n_slots + entries) * (max_len // PS)
    mp = max_len // PS
    params = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
    kv, dh = cfg.n_kv_heads, cfg.d_head
    pools = [
        {name: _s((spec.n_blocks, n_pages, PS, kv, dh)) for name in ("k", "v")}
        for spec in layer_groups(cfg)
    ]
    i32 = jnp.int32
    step = functools.partial(decode_step_paged, cfg=cfg)
    compiled = chip_compile(
        lambda p, pl_, t, kp, tok, pos: step(p, pools=pl_, page_table=t,
                                             kv_pos=kp, tokens=tok, pos=pos),
        params, pools, _s((n_slots, mp), i32), _s((n_slots, max_len), i32),
        _s((n_slots, 1), i32), _s((n_slots,), i32),
        donate_argnums=(1, 3),
    )
    _assert_kernel(compiled)
    mem = compiled.memory_analysis()
    total = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes - mem.alias_size_in_bytes
    )
    assert total < V5E_HBM_BYTES, total
