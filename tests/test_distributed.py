"""Distribution tests on 8 fake host devices (subprocess — the device count
must be fixed before jax initializes, so each case runs its own python)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(body: str, devices: int = 8, timeout=420) -> str:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
        import warnings; warnings.filterwarnings("ignore")
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_test_mesh
    """) + textwrap.dedent(body)
    env = dict(os.environ,
               PYTHONPATH=os.path.join(_ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env, cwd=_ROOT)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_quantized_allreduce_matches_mean():
    run_py("""
        from jax.sharding import PartitionSpec as P
        from repro.optim.compress import quantized_allreduce
        mesh = make_test_mesh((8,), ("d",))
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
        f = jax.shard_map(lambda x: quantized_allreduce(x[0], "d")[None],
                          mesh=mesh, in_specs=P("d"), out_specs=P("d"),
                          check_vma=False)
        got = f(x)                       # every row = approx mean
        want = x.mean(axis=0)
        err = float(jnp.abs(got - want[None]).max())
        rel = err / float(jnp.abs(want).max())
        assert rel < 0.05, (err, rel)    # int8 wire, n-1 requant hops
        # int8 wire really appears in the lowered HLO
        txt = jax.jit(f).lower(x).compile().as_text()
        assert "s8[" in txt and "collective-permute" in txt
        print("OK")
    """)


def test_gpipe_pipeline_matches_sequential():
    run_py("""
        from repro.parallel.pipeline import gpipe_apply, pipeline_bubble
        mesh = make_test_mesh((8,), ("stage",))
        S, M, mb, D = 8, 4, 2, 16
        ks = jax.random.split(jax.random.PRNGKey(0), S)
        Ws = jnp.stack([jax.random.normal(k, (D, D)) * 0.3 for k in ks])
        x = jax.random.normal(jax.random.PRNGKey(1), (M, mb, D))
        def stage_fn(W, h):
            return jnp.tanh(h @ W)
        out = gpipe_apply(stage_fn, Ws, x, mesh=mesh, axis="stage")
        # sequential reference
        ref = x
        for s in range(S):
            ref = jnp.tanh(ref @ Ws[s])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        assert abs(pipeline_bubble(8, 4) - 7/11) < 1e-9
        print("OK")
    """)


def test_sharded_train_step_runs_and_matches_single_device():
    """The REAL train step, jit'd with production sharding rules on a (2,4)
    mesh, must produce the same loss trajectory as the unsharded step."""
    run_py("""
        import dataclasses
        from repro.configs.registry import get_smoke_config
        from repro.launch.steps import TrainHParams, init_train_state, \\
            make_train_step
        from repro.data.lm import LMDataConfig, lm_batch
        from repro.parallel.sharding import (auto_batch_sharding,
                                             plan_for_mesh, state_shardings)
        from repro.parallel.hints import activation_hints

        cfg = get_smoke_config("yi-34b")
        hp = TrainHParams(lr=1e-3)
        dcfg = LMDataConfig(vocab_size=cfg.vocab_size, seq_len=64,
                            batch_size=8, seed=5)
        state0 = init_train_state(jax.random.PRNGKey(0), cfg, hp)
        step = make_train_step(cfg, hp)

        # single device
        s = state0
        losses1 = []
        for i in range(3):
            s, m = jax.jit(step)(s, lm_batch(dcfg, i))
            losses1.append(float(m["loss"]))

        # sharded
        mesh = make_test_mesh((2, 4), ("data", "model"))
        plan = plan_for_mesh(mesh)
        sh = state_shardings(jax.eval_shape(lambda: state0), plan)
        bsh = auto_batch_sharding(jax.eval_shape(lambda: lm_batch(dcfg, 0)),
                                  plan)
        s2 = jax.device_put(state0, sh)
        with activation_hints(plan):
            jstep = jax.jit(step, in_shardings=(sh, bsh),
                            out_shardings=(sh, None))
            losses2 = []
            for i in range(3):
                batch = jax.device_put(lm_batch(dcfg, i), bsh)
                s2, m = jstep(s2, batch)
                losses2.append(float(m["loss"]))
        np.testing.assert_allclose(losses1, losses2, rtol=2e-2)
        print("OK", losses1, losses2)
    """)


def test_elastic_restore_across_meshes(tmp_path):
    """Checkpoint on a (4,2) mesh, restore onto (2,4) and single device —
    the mesh-agnostic checkpoint contract."""
    d = str(tmp_path / "ck")
    run_py(f"""
        from repro.configs.registry import get_smoke_config
        from repro.launch.steps import TrainHParams, init_train_state
        from repro.parallel.sharding import plan_for_mesh, state_shardings
        from repro.checkpoint.ckpt import save_checkpoint
        cfg = get_smoke_config("deepseek-7b")
        hp = TrainHParams()
        state = init_train_state(jax.random.PRNGKey(3), cfg, hp)
        mesh = make_test_mesh((4, 2), ("data", "model"))
        sh = state_shardings(jax.eval_shape(lambda: state),
                             plan_for_mesh(mesh))
        state = jax.device_put(state, sh)
        save_checkpoint({d!r}, 17, state)
        print("saved")
    """)
    run_py(f"""
        from repro.configs.registry import get_smoke_config
        from repro.launch.steps import TrainHParams, init_train_state
        from repro.runtime.elastic import elastic_restore
        cfg = get_smoke_config("deepseek-7b")
        hp = TrainHParams()
        tmpl = jax.eval_shape(
            lambda k: init_train_state(k, cfg, hp), jax.random.PRNGKey(0))
        for shape, axes in [((2, 4), ("data", "model")),
                            ((8,), ("data",))]:
            mesh = make_test_mesh(shape, axes)
            step, state, _ = elastic_restore({d!r}, tmpl, mesh)
            assert step == 17
            leaf = state["params"]["embed"]["table"]
            assert leaf.shape == tmpl["params"]["embed"]["table"].shape
        print("OK")
    """)


def test_dryrun_cells_compile_on_test_mesh():
    """dryrun.lower_cell on a small mesh for one arch of each family kind."""
    run_py("""
        import dataclasses
        from repro.configs.registry import get_smoke_config
        from repro.configs.shapes import ShapeConfig
        from repro.launch import dryrun
        mesh = make_test_mesh((2, 2, 2), ("pod", "data", "model"))
        tr = ShapeConfig("t", 128, 8, "train")
        dc = ShapeConfig("d", 128, 8, "decode")
        for arch in ["qwen1.5-32b", "deepseek-v3-671b", "xlstm-350m",
                     "jamba-v0.1-52b"]:
            cfg = dataclasses.replace(get_smoke_config(arch), loss_chunk=64)
            for shape in (tr, dc):
                c = dryrun.lower_cell(cfg, shape, mesh,
                                      kv_bits=8 if shape.kind == "decode"
                                      else 0).compile()
                assert c is not None
        print("OK")
    """, timeout=560)


def test_moe_all_to_all_visible_in_hlo():
    run_py("""
        import dataclasses
        from repro.configs.registry import get_smoke_config
        from repro.models.moe import init_moe, moe_apply
        from repro.parallel.sharding import plan_for_mesh
        from repro.parallel.hints import activation_hints
        mesh = make_test_mesh((2, 4), ("data", "model"))
        plan = plan_for_mesh(mesh)
        cfg = get_smoke_config("deepseek-v3-671b")
        p = init_moe(jax.random.PRNGKey(0), cfg)
        x = jnp.ones((4, 32, cfg.d_model), jnp.float32)
        with activation_hints(plan):
            txt = jax.jit(lambda p, x: moe_apply(p, x, cfg=cfg,
                                                 mode="scatter")[0]) \\
                .lower(p, x).compile().as_text()
        assert "all-to-all" in txt, "EP dispatch must lower to all-to-all"
        print("OK")
    """)
