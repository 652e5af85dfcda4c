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


def _pool_shapes(cfg, n_pages):
    """Each layer group's K/V pools as a batched service allocates them."""
    from repro.models import layer_groups
    from repro.models.cache import init_paged_pool

    return [
        jax.eval_shape(lambda: init_paged_pool(cfg, spec.n_blocks, n_pages, PS))
        for spec in layer_groups(cfg)
    ]


def _steer_to_mosaic(monkeypatch):
    """The kernel wrappers pick interpret mode from the backend, which is
    the CPU here; a step compiled for the chip must take the Mosaic
    kernels."""
    from repro.kernels.chunked_prefill import ops as chunk_ops
    from repro.kernels.decode_attention import ops as decode_ops
    from repro.kernels.flash_attention import ops as flash_ops
    from repro.kernels.paged_attention import ops as paged_ops

    for mod in (paged_ops, chunk_ops, decode_ops, flash_ops):
        monkeypatch.setattr(mod, "_on_cpu", lambda: False)


def test_paged_decode_step_fits_v5e(chip_compile, monkeypatch):
    """One whole jitted paged decode step of the paper's model at full
    width, against the page pool a default batched service allocates at
    max_len 2048, compiles with the Pallas kernels in it and fits one
    chip's HBM."""
    from repro.configs import get_config
    from repro.models import decode_step_paged, init_params

    _steer_to_mosaic(monkeypatch)
    cfg = get_config("paper-qwen1.5-0.5b").replace(attn_impl="pallas")
    n_slots, max_len, entries = 4, 2048, 8
    n_pages = 1 + (n_slots + entries) * (max_len // PS)
    mp = max_len // PS
    params = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
    pools = _pool_shapes(cfg, n_pages)
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


def test_paged_steps_update_pool_in_place(chip_compile, monkeypatch):
    """The full-width paged decode step and one chunk step of the paper's
    model, against a pool of 1537 pages of 16 with the pools (and the
    decode's kv_pos) donated, update the pools in place: each program
    aliases the donated pools to its outputs, and its scratch memory is
    below one layer's K pool, so no step copies a layer's pool, let alone
    the group's stack."""
    from repro.configs import get_config
    from repro.models import decode_step_paged, init_params
    from repro.models.prefill import prefill_chunk_paged

    _steer_to_mosaic(monkeypatch)
    cfg = get_config("paper-qwen1.5-0.5b").replace(attn_impl="pallas")
    n_slots, max_len, n_pages = 4, 2048, 1537
    mp = max_len // PS
    params = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
    pools = _pool_shapes(cfg, n_pages)
    pool_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(pools))
    layer_k_bytes = n_pages * PS * cfg.n_kv_heads * cfg.d_head * 2
    assert layer_k_bytes == 50_364_416
    i32 = jnp.int32
    decode = chip_compile(
        lambda p, pl_, t, kp, tok, pos: decode_step_paged(
            p, cfg, pl_, t, kp, tok, pos),
        params, pools, _s((n_slots, mp), i32), _s((n_slots, max_len), i32),
        _s((n_slots, 1), i32), _s((n_slots,), i32),
        donate_argnums=(1, 3),
    )
    chunk = chip_compile(
        lambda p, pl_, t, tok, p0, n: prefill_chunk_paged(p, cfg, pl_, t, tok, p0, n),
        params, pools, _s((1, mp), i32), _s((1, 64), i32), _s((1,), i32),
        _s((1,), i32),
        donate_argnums=(1,),
    )
    for name, compiled in (("decode", decode), ("chunk", chunk)):
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes < layer_k_bytes, (name, mem.temp_size_in_bytes)
        assert mem.alias_size_in_bytes >= pool_bytes, (name, mem.alias_size_in_bytes)


def test_served_decode_program_samples_and_updates_pool_in_place(
        chip_compile, monkeypatch):
    """The batched server's own paged decode program for the paper's
    model, against the same pool of 1537 pages with the pools and kv_pos
    donated, returns the next step's tokens, ``(n_slots, 1)`` int32, from
    the same program, and still aliases the donated pools to its outputs
    with scratch below one layer's K pool."""
    from repro.configs import get_config
    from repro.models import init_params
    from repro.serving.scheduler import _server_fns

    _steer_to_mosaic(monkeypatch)
    cfg = get_config("paper-qwen1.5-0.5b").replace(attn_impl="pallas")
    n_slots, max_len, n_pages = 4, 2048, 1537
    mp = max_len // PS
    params = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
    pools = _pool_shapes(cfg, n_pages)
    pool_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(pools))
    layer_k_bytes = n_pages * PS * cfg.n_kv_heads * cfg.d_head * 2
    decode_paged = _server_fns(cfg, max_len)[3]
    i32 = jnp.int32
    compiled = chip_compile(
        lambda p, pl_, t, kp, tok, pos: decode_paged(p, pl_, t, kp, tok, pos),
        params, pools, _s((n_slots, mp), i32), _s((n_slots, max_len), i32),
        _s((n_slots, 1), i32), _s((n_slots,), i32),
        donate_argnums=(1, 3),
    )
    logits, out_pools, kv_pos, counts, tokens = compiled.out_info
    assert (tokens.shape, tokens.dtype) == ((n_slots, 1), jnp.int32)
    assert logits.shape == (n_slots, 1, cfg.vocab_size)
    assert jax.tree.map(lambda a: a.shape, out_pools) == jax.tree.map(
        lambda a: a.shape, pools)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < layer_k_bytes, mem.temp_size_in_bytes
    assert mem.alias_size_in_bytes >= pool_bytes, mem.alias_size_in_bytes


def test_kernel_names_in_a_decode_step_and_a_chunk(chip_compile, monkeypatch):
    """The paged-attention, shared-prefix and chunked-prefill kernels run
    under names they give themselves, so a device trace finds them by the
    instruction names ``%paged_attention.N``, ``%shared_prefix.N`` and
    ``%chunked_prefill.N`` however the code around them is arranged."""
    import re

    from repro.configs import get_config
    from repro.models import decode_step_paged, init_params
    from repro.models.prefill import prefill_chunk_paged

    _steer_to_mosaic(monkeypatch)
    cfg = get_config("paper-qwen1.5-0.5b").replace(attn_impl="pallas", n_layers=2)
    n_slots, max_len = 4, 256
    n_pages, mp = 1 + 12 * (max_len // PS), max_len // PS
    params = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
    pools = _pool_shapes(cfg, n_pages)
    i32 = jnp.int32

    def names(compiled):
        return set(re.findall(r"%([a-z_]+)\.\d+ = [^\n]*custom-call\(",
                              compiled.as_text()))

    decode = chip_compile(
        lambda p, pl_, t, kp, tok, pos, sp: decode_step_paged(
            p, cfg, pl_, t, kp, tok, pos, sp),
        params, pools, _s((n_slots, mp), i32), _s((n_slots, max_len), i32),
        _s((n_slots, 1), i32), _s((n_slots,), i32), _s((2,), i32),
    )
    assert {"paged_attention", "shared_prefix"} <= names(decode)
    chunk = chip_compile(
        lambda p, pl_, t, tok, p0, n: prefill_chunk_paged(p, cfg, pl_, t, tok, p0, n),
        params, pools, _s((1, mp), i32), _s((1, 64), i32), _s((1,), i32),
        _s((1,), i32),
    )
    assert "chunked_prefill" in names(chunk)


# ---------------------------------------------------------------------------
# latent attention (DeepSeek-V2-Lite): one shared latent head per token
# (576 lanes, 640 in the pool), 16 query heads, the value the row's
# leading 512 lanes
# ---------------------------------------------------------------------------

MLA = "deepseek-v2-lite"


def _mla_dims():
    """The config, its heads, the pool's row (576 lanes padded to 640),
    the value lanes and the softmax scale."""
    from repro.configs import get_config
    from repro.models.cache import latent_row
    from repro.models.mla import softmax_scale

    cfg = get_config(MLA)
    return cfg, cfg.n_heads, latent_row(cfg), cfg.kv_lora_rank, softmax_scale(cfg)


@pytest.mark.parametrize("shared", [0, 8])
def test_mla_paged_decode_compiles(chip_compile, shared):
    """The latent decode kernel, and with a shared run the cascade pass
    beside it, at 4 lanes and at the published widths."""
    from repro.kernels.paged_attention import paged_attention

    _, h, w, r, scale = _mla_dims()
    i32 = jnp.int32
    fn = functools.partial(paged_attention, interpret=False, scale=scale, v_width=r)
    shapes = [_s((B, 1, h, w)), _s((N_PAGES, PS, 1, w)), None, _s((B, MP), i32),
              _s((B, 1), i32), _s((B, MP * PS), i32)]
    if shared:
        shapes.append(_s((shared,), i32))
    compiled = chip_compile(fn, *shapes)
    assert compiled.as_text().count("tpu_custom_call") >= (2 if shared else 1)


@pytest.mark.parametrize("chunk", [64, 256])
def test_mla_chunked_prefill_compiles(chip_compile, chunk):
    """A latent prefill chunk at the step's 64-token budget and at the
    256-token cap, where its 4096 query rows are split into row blocks."""
    from repro.kernels.chunked_prefill import chunked_prefill_attention

    _, h, w, r, scale = _mla_dims()
    i32 = jnp.int32
    fn = functools.partial(chunked_prefill_attention, interpret=False,
                           scale=scale, v_width=r)
    _assert_kernel(chip_compile(
        fn, _s((1, chunk, h, w)), _s((N_PAGES, PS, 1, w)), None,
        _s((1, MP), i32), _s((1,), i32), _s((1,), i32),
    ))


def test_mla_moe_steps_compile_and_fit_v5e(chip_compile, monkeypatch):
    """DeepSeek-V2-Lite's paged decode step (with a shared run) and a
    64-token chunk step at two layers (the dense layer and one MoE layer),
    with the kernels under their names; then the whole 27-layer decode
    step, against one node's pool of 1537 pages, fits one chip and
    updates the latent pool in place, copying none of it."""
    import re

    from repro.configs import get_config
    from repro.models import decode_step_paged, init_params
    from repro.models.prefill import prefill_chunk_paged

    _steer_to_mosaic(monkeypatch)
    i32 = jnp.int32
    n_slots, max_len = 4, 2048
    mp = max_len // PS

    def names(compiled):
        return set(re.findall(r"%([a-z_]+)\.\d+ = [^\n]*custom-call\(",
                              compiled.as_text()))

    for n_layers, n_pages in ((2, 1 + 12 * 8), (27, 1537)):
        cfg = get_config(MLA).replace(attn_impl="pallas", n_layers=n_layers)
        params = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
        pools = _pool_shapes(cfg, n_pages)
        decode = chip_compile(
            lambda p, pl_, t, kp, tok, pos, sp: decode_step_paged(
                p, cfg, pl_, t, kp, tok, pos, sp, stats=True),
            params, pools, _s((n_slots, mp), i32), _s((n_slots, max_len), i32),
            _s((n_slots, 1), i32), _s((n_slots,), i32), _s((2,), i32),
            donate_argnums=(1, 3),
        )
        if n_layers == 2:
            assert {"paged_attention", "shared_prefix"} <= names(decode)
            chunk = chip_compile(
                lambda p, pl_, t, tok, p0, n: prefill_chunk_paged(
                    p, cfg, pl_, t, tok, p0, n, stats=True),
                params, pools, _s((1, mp), i32), _s((1, 64), i32),
                _s((1,), i32), _s((1,), i32),
                donate_argnums=(1,),
            )
            assert "chunked_prefill" in names(chunk)
            continue
        mem = decode.memory_analysis()
        pool_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(pools))
        total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        assert total < V5E_HBM_BYTES, total
        assert mem.alias_size_in_bytes >= pool_bytes, mem.alias_size_in_bytes
        # no copy of a layer's pool: a 576-lane row would be stored with
        # the page axis innermost and copied to row-major every step
        layer_bytes = pools[1]["kv"].size // n_layers * 2
        assert mem.temp_size_in_bytes < layer_bytes, mem.temp_size_in_bytes
