"""Tensor-parallel serving mesh + sharding-rule tests (launch.mesh
make_serving_mesh, parallel.sharding paged_pool_shardings /
param_shardings inference mode).

Mesh-shape and sharded-vs-single identity cases run in subprocesses with
``--xla_force_host_platform_device_count`` — the device count must be
fixed before jax initializes. Spec rules are pure and test in-process on
the 1-device mesh.
"""
import os
import subprocess
import sys
import textwrap

import pytest

import jax
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_serving_mesh
from repro.parallel.sharding import (paged_pool_shardings, param_shardings,
                                     plan_for_mesh)

jax.config.update("jax_platform_name", "cpu")

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(body: str, devices: int = 8, timeout=420,
           single_thread=False) -> str:
    flags = f"--xla_force_host_platform_device_count={devices}"
    if single_thread:
        flags += (" --xla_cpu_multi_thread_eigen=false "
                  "intra_op_parallelism_threads=1")
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "{flags}"
        import warnings; warnings.filterwarnings("ignore")
        import jax, jax.numpy as jnp, numpy as np
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=os.path.join(_ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env, cwd=_ROOT)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


# ---------------------------------------------------------------------------
# make_serving_mesh
# ---------------------------------------------------------------------------
def test_serving_mesh_rejects_bad_tp():
    with pytest.raises(ValueError):
        make_serving_mesh(0)
    with pytest.raises(ValueError):
        make_serving_mesh(3)   # 3 does not divide this host's 1 device


def test_serving_mesh_single_device():
    mesh = make_serving_mesh(1)
    assert mesh.axis_names == ("data", "model")
    assert mesh.shape["model"] == 1


def test_serving_mesh_shapes_on_8_devices():
    run_py("""
        from repro.launch.mesh import make_serving_mesh
        for tp, want in ((1, (8, 1)), (2, (4, 2)), (8, (1, 8))):
            mesh = make_serving_mesh(tp)
            assert mesh.axis_names == ("data", "model"), mesh.axis_names
            shape = (mesh.shape["data"], mesh.shape["model"])
            assert shape == want, (tp, shape)
        try:
            make_serving_mesh(3)
        except ValueError:
            pass
        else:
            raise AssertionError("tp=3 must not divide 8 devices")
        print("MESH_OK")
    """)


def test_every_mesh_constructor_has_auto_axes():
    """The installed JAX defaults ``jax.make_mesh`` to Explicit axes, under
    which the embedding gather of a sharded table raises; every launch.mesh
    constructor must hand the partitioner Auto axes."""
    run_py("""
        from jax.sharding import AxisType
        from repro.launch import mesh as m
        meshes = {"production": m.make_production_mesh(),
                  "production_multi": m.make_production_mesh(multi_pod=True),
                  "host": m.make_host_mesh(),
                  "test": m.make_test_mesh(),
                  "serving": m.make_serving_mesh(4)}
        for name, mesh in meshes.items():
            assert set(mesh.axis_types) == {AxisType.Auto}, \\
                (name, mesh.axis_types)
        print("AUTO_OK")
    """, devices=512)


# ---------------------------------------------------------------------------
# Sharding specs (pure rules, 1-device mesh)
# ---------------------------------------------------------------------------
def _plan():
    return plan_for_mesh(make_serving_mesh(1))


def test_paged_pool_specs_shard_head_axis():
    plan = _plan()
    caches = [{"k_pages": jax.ShapeDtypeStruct((10, 8, 2, 16), jax.numpy.int8),
               "v_pages": jax.ShapeDtypeStruct((10, 8, 2, 16), jax.numpy.int8),
               "k_scale": jax.ShapeDtypeStruct((10,), jax.numpy.float32),
               "v_scale": jax.ShapeDtypeStruct((10,), jax.numpy.float32)}]
    sh = paged_pool_shardings(caches, plan)[0]
    # page grids: KV-heads axis (ndim-2) over "model", everything else whole
    assert sh["k_pages"].spec == P(None, None, "model", None)
    assert sh["v_pages"].spec == P(None, None, "model", None)
    # per-page scales replicate (aliased by every shard)
    assert sh["k_scale"].spec == P(None)
    assert sh["v_scale"].spec == P(None)
    # scan-stacked pools: same axis counted from the tail
    stacked = [{"k_pages": jax.ShapeDtypeStruct((3, 10, 8, 2, 16),
                                                jax.numpy.int32)}]
    assert paged_pool_shardings(stacked, plan)[0]["k_pages"].spec \
        == P(None, None, None, "model", None)


def test_paged_pool_specs_divisibility_fallback():
    run_py("""
        from repro.launch.mesh import make_serving_mesh
        from repro.parallel.sharding import paged_pool_shardings, \\
            plan_for_mesh
        from jax.sharding import PartitionSpec as P
        plan = plan_for_mesh(make_serving_mesh(8))   # model axis = 8
        caches = [{"k_pages": jax.ShapeDtypeStruct((10, 8, 2, 16),
                                                   jnp.int8)}]
        # 2 KV heads don't divide tp=8: replicate rather than fail to lower
        assert paged_pool_shardings(caches, plan)[0]["k_pages"].spec \\
            == P(None, None, None, None)
        print("FALLBACK_OK")
    """)


def test_param_specs_inference_strips_fsdp():
    plan = _plan()
    params = {"layers": {"block": {
        "wq": jax.ShapeDtypeStruct((64, 64), jax.numpy.float32)}}}
    train = param_shardings(params, plan)["layers"]["block"]["wq"]
    infer = param_shardings(params, plan,
                            inference=True)["layers"]["block"]["wq"]
    assert train.spec == P("data", "model")
    # serving keeps weights resident: TP-only, no per-token FSDP gathers
    assert infer.spec == P(None, "model")


# ---------------------------------------------------------------------------
# Sharded-vs-single serving identity (the tp=2 replica really is the same
# server — same trace, bitwise-equal token streams)
# ---------------------------------------------------------------------------
def test_tp2_serving_token_identity():
    run_py("""
        jax.config.update("jax_platform_name", "cpu")
        from repro.configs.registry import get_smoke_config
        from repro.launch.mesh import make_serving_mesh
        from repro.launch.serve import BatchedServer, Request
        from repro.models.transformer import init_model

        cfg = get_smoke_config("qwen2-72b")
        params = init_model(jax.random.PRNGKey(0), cfg)

        def mk():
            rng = np.random.default_rng(5)
            return [Request(i, rng.integers(0, cfg.vocab_size,
                                            4 + 3 * i).astype(np.int32),
                            4 + i) for i in range(3)]

        common = dict(batch_size=2, max_len=32, page_size=8, num_pages=10,
                      kv_bits=8)
        single = {r.rid: r for r in
                  BatchedServer(cfg, params, **common).run(mk())}
        mesh = make_serving_mesh(2)
        assert mesh.shape["model"] == 2
        sharded = BatchedServer(cfg, params, mesh=mesh, **common).run(mk())
        for r in sharded:
            assert r.out == single[r.rid].out, (r.rid, r.out,
                                                single[r.rid].out)
            assert r.done
        print("TP_IDENTITY_OK")
    """, devices=2, single_thread=True, timeout=900)
