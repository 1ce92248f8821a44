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


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("page_size", [16, 64, 128])
def test_paged_decode_compiles(one_chip, page_size, dtype):
    from repro.kernels.paged_attention import ops

    n_pages, max_pages = 256, 1024 // page_size
    pool = _spec(one_chip, (n_pages + 1, page_size, HKV, DH), dtype)
    _assert_kernel(ops.paged_decode_attention.lower(
        _spec(one_chip, (B, H, DH), dtype), pool, pool,
        _spec(one_chip, (B, max_pages), jnp.int32),
        _spec(one_chip, (B,), jnp.int32), interpret=False))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("page_size", [16, 64, 128])
def test_paged_verify_compiles(one_chip, page_size, dtype):
    from repro.kernels.paged_attention import ops

    n_pages, max_pages, W = 256, 1024 // page_size, 4
    pool = _spec(one_chip, (n_pages + 1, page_size, HKV, DH), dtype)
    _assert_kernel(ops.paged_verify_attention.lower(
        _spec(one_chip, (B, W, H, DH), dtype), pool, pool,
        _spec(one_chip, (B, max_pages), jnp.int32),
        _spec(one_chip, (B,), jnp.int32), interpret=False))


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
