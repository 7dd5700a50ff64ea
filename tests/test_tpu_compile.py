"""The serving paged-attention kernel compiled for a described TPU v5e.

Interpret mode (every other kernel test) never meets the TPU compiler's
tiling and memory rules; this file asks that compiler, for a chip that is
described, not attached, at qwen2-72b widths (hd 128, ps 16; KV 8 heads on
one chip, 2 per chip under tp=4), at the deepseek-7b cells' widths (32
MHA heads, 32 slots, a 256-entry table) and at the qwen2-72b-pp16 cell's
(8 KV heads, G 8, 32 slots, a 224-entry table), each reading one layer of
a stacked pool as the serving scan carries it. Then the whole decode and
prefill step programs of both cells' configurations, kernel included: no
copy of a pool, and the pools aliased in place.
Nothing runs, so these are compile checks only. The topology is described
inside a fixture: only the worker that runs this file loads the TPU
library.
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


def _compile(sharding, *, kv, s, bits, num_pages, b=B, g=G, np_=NP,
             layers=1):
    hdw = HD * bits // 32 if 0 < bits < 8 else HD
    pdt = {8: jnp.int8, 4: jnp.int32, 0: jnp.bfloat16}[bits]

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    pages = (layers, num_pages, PS, kv, hdw)
    args = (sds((b, s, kv * g, HD), jnp.bfloat16),
            sds(pages, pdt), sds(pages, pdt),
            sds((layers, num_pages), jnp.float32),
            sds((layers, num_pages), jnp.float32),
            sds((b, np_), jnp.int32), sds((b,), jnp.int32),
            sds((b,), jnp.int32), sds((), jnp.int32))
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
    a 256-entry table (max_len 4096), 5120-page pools, decode and the
    128-token prefill bucket: the page-block copies at the widths the
    cells run. The int8 pools are the cells' 7 stacked layers; the other
    containers, which no cell runs, one layer (7 bf16 layers, or 7 int4
    layers as the chip lays out their 16-word rows, outgrow 16 GB)."""
    compiled = _compile(one_chip, kv=32, s=s, bits=bits, num_pages=5120,
                        b=32, g=1, np_=256, layers=7 if bits == 8 else 1)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("s", [1, 128], ids=["decode", "prefill128"])
def test_paged_kernel_compiles_for_v5e_gqa_pp16(one_chip, no_compile_cache,
                                                s):
    """The qwen2-72b-pp16 cell's shapes: GQA (8 KV heads, G 8, so 8 query
    rows a KV head at decode and 1024 at a 128-token chunk), 32 slots, a
    224-entry table (max_len 3584), the 5 layers' stacked 7169-page int8
    pools."""
    compiled = _compile(one_chip, kv=8, s=s, bits=8, num_pages=7169,
                        b=32, g=8, np_=224, layers=5)
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


def _cell_shapes(name):
    """A benchmark configuration's serving state as shapes: the parameters
    and the full stacked pools (nothing is allocated), and the quantizer
    of a ``BatchedServer`` built with its settings on a two-page pool."""
    from types import SimpleNamespace

    from bench import harness
    from repro.core.paged_kv import PagedCacheSpec
    from repro.launch.serve import BatchedServer
    from repro.models.transformer import init_cache, init_model
    conf = harness.load_json(f"bench/configs/{name}.json")
    cfg, sv = harness.model_config(conf), conf["server"]
    quant = BatchedServer(cfg, None, batch_size=1, max_len=sv["page_size"],
                          kv_bits=sv["kv_bits"], page_size=sv["page_size"],
                          num_pages=2, kv_scale=sv["kv_scale"]).quant
    B, ps = sv["batch_size"], sv["page_size"]
    np_max = -(-sv["max_len"] // ps)
    spec = PagedCacheSpec(page_size=ps, num_pages=sv.get(
        "num_pages") or 1 + B * np_max)
    return SimpleNamespace(
        cfg=cfg, quant=quant, attn_impl=sv["attn_impl"], B=B, np_max=np_max,
        params=jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0),
                                                 cfg)),
        caches=jax.eval_shape(lambda: init_cache(cfg, B, sv["max_len"],
                                                 quant, paged=spec)))


@pytest.mark.parametrize("step", ["decode", "prefill128"])
@pytest.mark.parametrize("cell", ["deepseek-7b-pp4", "qwen2-72b-pp16"])
def test_step_updates_stacked_pool_in_place_on_v5e(one_chip, no_compile_cache,
                                                   monkeypatch, cell, step):
    """The serving step programs of the cells' configurations (MHA; GQA G 8
    with QKV bias) at full size, with the compiled kernel (the platform
    check that picks interpret mode sees the CPU here, so the test steers
    it): the kernel and the writes use the carried stacked pool in place,
    so the TPU compiler inserts no copy of a pool or of one layer's pool,
    and the donated pools alias the outputs. At smoke size the compiler
    would stage the tiny pools through on-chip memory instead, so only
    the real size shows what the chip runs."""
    from _stacked_pool import pool_copies, step_program, unaliased_pool_params
    monkeypatch.setattr("repro.kernels.ops._default_interpret",
                        lambda: False)
    shapes = _cell_shapes(cell)
    fn, args = step_program(shapes, step, sharding=one_chip)
    hlo = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    pool = shapes.caches[0][0]["k_pages"]
    assert pool_copies(hlo, pool) == []
    missing, n = unaliased_pool_params(hlo, pool)
    assert n == 2 and missing == []
