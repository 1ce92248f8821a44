"""Compile the serving Pallas kernels for a described TPU v5e chip.

Interpret mode (every other kernel test) runs a kernel body on the CPU and
cannot see the chip's tiling rules: a block whose last two dims are neither
(8, 128)-aligned nor the full array dims, or a kernel that needs more VMEM
than the scoped limit, passes there and is refused by the TPU compiler.
These tests hand the installed TPU compiler the kernels of the serving path
at ``qwen3-0.6b``'s real widths (d_head 128, 8 KV heads, 16 query heads) for
a chip that is described, not attached, and check that each lowers to a
Mosaic ``tpu_custom_call``.  Nothing runs, so they say nothing about
results or speed.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and under pytest-xdist
every worker imports every test file.  Keep all such tests in this file so
one worker owns the library.
"""

import math
import os

import jax
import jax.numpy as jnp
import pytest

DH, HKV, H = 128, 8, 16          # qwen3-0.6b attention widths
B = 8                            # decode slots


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else logs under /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # a persistent-cache entry written for a described chip cannot be read
    # back without one: keep these compiles out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(lowered):
    hlo = lowered.compile().as_text()
    assert "tpu_custom_call" in hlo


def _paged_pool(sharding, page_size, dtype, stacked):
    """One layer's pool, or the stacked pools of 28 layers and a layer
    index: the two forms the paged kernel takes."""
    pool = (257, page_size, HKV, DH)
    if not stacked:
        return _spec(sharding, pool, dtype), ()
    return (_spec(sharding, (28,) + pool, dtype),
            (_spec(sharding, (), jnp.int32),))


@pytest.mark.parametrize("stacked", [False, True], ids=["layer", "stacked"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("page_size", [16, 64, 128])
def test_paged_decode_compiles(one_chip, page_size, dtype, stacked):
    from repro.kernels.paged_attention import ops

    max_pages = 1024 // page_size
    pool, layer = _paged_pool(one_chip, page_size, dtype, stacked)
    _assert_kernel(ops.paged_decode_attention.lower(
        _spec(one_chip, (B, H, DH), dtype), pool, pool,
        _spec(one_chip, (B, max_pages), jnp.int32),
        _spec(one_chip, (B,), jnp.int32), *layer, interpret=False))


@pytest.mark.parametrize("stacked", [False, True], ids=["layer", "stacked"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("page_size", [16, 64, 128])
def test_paged_verify_compiles(one_chip, page_size, dtype, stacked):
    from repro.kernels.paged_attention import ops

    max_pages, W = 1024 // page_size, 4
    pool, layer = _paged_pool(one_chip, page_size, dtype, stacked)
    _assert_kernel(ops.paged_verify_attention.lower(
        _spec(one_chip, (B, W, H, DH), dtype), pool, pool,
        _spec(one_chip, (B, max_pages), jnp.int32),
        _spec(one_chip, (B,), jnp.int32), *layer, interpret=False))


def test_paged_decode_chunk_keeps_pool_in_place(one_chip, monkeypatch):
    """The paged decode chunk at qwen3-0.6b's widths (28 layers, 16 slots,
    page 16) updates the stacked K/V pool in place: its temporaries stay
    far below the pool (a layer scan that passes the pool as xs/ys holds a
    second pool as a temporary), and no copy, dynamic-slice or
    dynamic-update-slice yields a layer's pool or the whole stack."""
    import re

    from repro.configs import get_config
    from repro.kernels.paged_attention import ops
    from repro.models import init_paged_caches, init_params
    from repro.serving.engine import (
        ServeConfig, init_page_state, init_slot_state,
        paged_decode_chunk_program)

    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    cfg = get_config("qwen3-0.6b")
    # 2048 pages (3.76 GB of K/V): large enough that the chunk's own
    # temporaries (~0.18 GB, which do not grow with the pool) sit well
    # under an eighth of it, small enough to compile in seconds
    slots, ps, n_pages, max_len = 16, 16, 2048, 1024

    def shaped(tree):
        return jax.tree.map(
            lambda a: _spec(one_chip, a.shape, a.dtype), tree)

    caches = shaped(jax.eval_shape(
        lambda: init_paged_caches(cfg, slots, n_pages, ps)))
    compiled = paged_decode_chunk_program(
        cfg, ServeConfig(max_len=max_len, attn_impl="pallas", chunk=8), 8,
        ps,
    ).lower(
        shaped(jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))),
        caches, shaped(init_slot_state(slots)),
        shaped(init_page_state(slots, n_pages, max_len // ps)),
        _spec(one_chip, (2,), jnp.uint32),
    ).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo

    pool = caches.kv["0"].k
    pool_bytes = 2 * pool.size * pool.dtype.itemsize
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < pool_bytes / 8, (temp, pool_bytes)

    pool_sized = {pool.size, pool.size // pool.shape[0]}
    moves = [
        m.group(0) for m in re.finditer(
            r"= \w+\[([\d,]*)\]\S* (copy|dynamic-slice|dynamic-update-slice)\(",
            hlo)
        if math.prod(int(d) for d in m.group(1).split(",") if d)
        in pool_sized]
    assert not moves, moves


@pytest.mark.parametrize("C", [1024, 4096])
def test_decode_attention_compiles(one_chip, C):
    from repro.kernels.decode_attention import ops

    kv = _spec(one_chip, (B, C, HKV, DH))
    _assert_kernel(ops.decode_attention.lower(
        _spec(one_chip, (B, H, DH)), kv, kv,
        _spec(one_chip, (B, C), jnp.int32),
        _spec(one_chip, (B,), jnp.int32), interpret=False))


@pytest.mark.parametrize("Lp,Sk", [(512, 512), (768, 256), (1024, 256), (96, 32)])
def test_prefix_attention_compiles(one_chip, Lp, Sk):
    from repro.kernels.prefix_attention import ops

    pk = _spec(one_chip, (2, Lp, HKV, DH))
    k = _spec(one_chip, (2, Sk, HKV, DH))
    _assert_kernel(ops.prefix_flash_attention.lower(
        _spec(one_chip, (2, Sk, H, DH)), pk, pk, k, k, interpret=False))


@pytest.mark.parametrize("S", [2048, 512])
def test_flash_attention_compiles(one_chip, S):
    from repro.kernels.flash_attention import ops

    kv = _spec(one_chip, (2, S, HKV, DH))
    _assert_kernel(ops.flash_attention.lower(
        _spec(one_chip, (2, S, H, DH)), kv, kv, interpret=False))


# Mellum2-12B-A2.5B's widths: 32 query / 4 KV heads of 128, window 1024,
# 16 of 64 experts of width 896 held per layer, d 2304, 7 layers a position
M_H, M_HKV, M_W = 32, 4, 1024


@pytest.mark.parametrize("n_q", [1, 4])
def test_windowed_paged_compiles(one_chip, n_q):
    """The windowed page walk (the table sliced to the window's pages, a
    fourth scalar-prefetch operand) at 4,608 positions of page 16."""
    from repro.kernels.paged_attention import ops

    pool = _spec(one_chip, (28, 3649, 16, M_HKV, DH))
    _assert_kernel(ops.paged_verify_attention.lower(
        _spec(one_chip, (16, n_q, M_H, DH)), pool, pool,
        _spec(one_chip, (16, 288), jnp.int32),
        _spec(one_chip, (16,), jnp.int32), _spec(one_chip, (), jnp.int32),
        window=M_W, interpret=False))


def test_windowed_prefix_compiles(one_chip):
    from repro.kernels.prefix_attention import ops

    pk = _spec(one_chip, (2, 3840, M_HKV, DH))
    k = _spec(one_chip, (2, 256, M_HKV, DH))
    _assert_kernel(ops.prefix_flash_attention.lower(
        _spec(one_chip, (2, 256, M_H, DH)), pk, pk, k, k, window=M_W,
        interpret=False))


@pytest.mark.parametrize("k,n", [(2304, 1792), (896, 2304)])
def test_expert_gmm_reads_the_stack(one_chip, k, n):
    """The grouped product of a decode step (128 assignments) over the
    stack of 7 layers x 16 held experts compiles to the Pallas grouped
    matmul, and its temporaries are nowhere near one layer's experts: the
    stack is read where it lies, not copied."""
    from repro.models.moe import grouped_matmul

    stack = _spec(one_chip, (7 * 16, k, n))
    compiled = jax.jit(grouped_matmul).lower(
        _spec(one_chip, (128, k)), stack,
        _spec(one_chip, (7 * 16,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    layer_bytes = 16 * k * n * 2
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes / 16
