"""The serving paged-attention kernel compiled for a described TPU v5e.

Interpret mode (every other kernel test) never meets the TPU compiler's
tiling and memory rules; this file asks that compiler, for a chip that is
described, not attached, at qwen2-72b widths (hd 128, ps 16; KV 8 heads on
one chip, 2 per chip under tp=4), at the deepseek-7b cells' widths (32
MHA heads, 32 slots, a 256-entry table) and at the qwen2-72b-pp16 cell's
(8 KV heads, G 8, 32 slots, a 224-entry table). Nothing runs, so these are
compile checks only. The topology is described inside a fixture: only the
worker that runs this file loads the TPU library.
"""
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_kv_attention import paged_kv_attention_chunk

B, G, HD, PS, NP = 8, 8, 128, 16, 36


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(sharding, *, kv, s, bits, num_pages, b=B, g=G, np_=NP):
    hdw = HD * bits // 32 if 0 < bits < 8 else HD
    pdt = {8: jnp.int8, 4: jnp.int32, 0: jnp.bfloat16}[bits]

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    args = (sds((b, s, kv * g, HD), jnp.bfloat16),
            sds((num_pages, PS, kv, hdw), pdt),
            sds((num_pages, PS, kv, hdw), pdt),
            sds((num_pages,), jnp.float32), sds((num_pages,), jnp.float32),
            sds((b, np_), jnp.int32), sds((b,), jnp.int32),
            sds((b,), jnp.int32))
    fn = jax.jit(lambda *a: paged_kv_attention_chunk(*a, bits=bits,
                                                     interpret=False))
    return fn.lower(*args).compile()


@pytest.mark.parametrize("bits", [8, 4, 0], ids=["int8", "int4", "fp"])
@pytest.mark.parametrize("s", [1, 32], ids=["decode", "prefill32"])
@pytest.mark.parametrize("kv", [8, 2], ids=["kv8", "kv2"])
def test_paged_kernel_compiles_for_v5e(one_chip, no_compile_cache, kv, s,
                                       bits):
    compiled = _compile(one_chip, kv=kv, s=s, bits=bits,
                        num_pages=1 + B * NP)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("bits", [8, 4, 0], ids=["int8", "int4", "fp"])
@pytest.mark.parametrize("s", [1, 128], ids=["decode", "prefill128"])
def test_paged_kernel_compiles_for_v5e_kv32(one_chip, no_compile_cache, s,
                                            bits):
    """The deepseek-7b cells' shapes: MHA (32 KV heads, G 1), 32 slots,
    a 256-entry table (max_len 4096), a 5120-page pool, decode and the
    128-token prefill bucket: the page-block copies at the widths the
    cells run."""
    compiled = _compile(one_chip, kv=32, s=s, bits=bits, num_pages=5121,
                        b=32, g=1, np_=256)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("s", [1, 128], ids=["decode", "prefill128"])
def test_paged_kernel_compiles_for_v5e_gqa_pp16(one_chip, no_compile_cache,
                                                s):
    """The qwen2-72b-pp16 cell's shapes: GQA (8 KV heads, G 8, so 8 query
    rows a KV head at decode and 1024 at a 128-token chunk), 32 slots, a
    224-entry table (max_len 3584), a 7169-page int8 pool."""
    compiled = _compile(one_chip, kv=8, s=s, bits=8, num_pages=7169,
                        b=32, g=8, np_=224)
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_kernel_compiles_with_large_pool(one_chip, no_compile_cache):
    """Per-page scales as whole-pool SMEM operands overflowed the 1 MiB of
    SMEM at 131072 pages; the kernel's scalar operands must not grow with
    the pool."""
    compiled = _compile(one_chip, kv=8, s=1, bits=8, num_pages=131072)
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # the pool is an argument, not a copy: temporaries stay far below it
    pool_bytes = 2 * 131072 * PS * 8 * HD
    assert mem.temp_size_in_bytes < pool_bytes // 100, mem
