"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

``main`` asks XLA for 512 host devices before the backend initializes (jax
locks the device count at first use, and the production meshes need 512);
importing this module leaves the environment alone.

Per cell this produces (results/dryrun/<tag>/<mesh>_<arch>_<shape>.json):
  * compiled.memory_analysis()  -> per-device bytes (proves it fits v5e HBM)
  * compiled.cost_analysis()    -> HLO FLOPs / bytes (roofline terms 1-2)
  * parsed collective bytes     -> roofline term 3 (launch.hlo_analysis)
plus model-analytic params/FLOPs. benchmarks/roofline.py turns these into
the EXPERIMENTS.md tables.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
      --mesh both [--tag baseline] [--set mla_absorbed=True] [--kv-bits 8]
"""
import argparse
import ast
import dataclasses
import json
import os
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs.registry import ARCH_IDS, get_config
from ..configs.shapes import SHAPES, applicable, decode_specs, input_specs
from ..core.fixedpoint import FixedPointFormat
from ..core.policy import PrecisionPolicy
from ..models.transformer import init_model
from ..optim.adamw import AdamWConfig
from ..parallel.hints import activation_hints
from ..parallel.sharding import (auto_batch_sharding, cache_shardings,
                                 param_shardings, plan_for_mesh,
                                 state_shardings)
from ..quant.apply import build_model_quant, transformer_layer_names
from .hlo_analysis import collective_summary, cost_summary, memory_summary
from .hlo_cost import analyze as hlo_loop_analyze
from .mesh import make_production_mesh
from .steps import (TrainHParams, init_train_state, make_decode_step,
                    make_embed_decode_step, make_prefill_step,
                    make_train_step)


def dryrun_config(cfg, shape):
    """Pod-scale numerics: bf16 params (fp32 master lives in the optimizer
    problem domain; see DESIGN.md §8), chunked CE for train/prefill."""
    return dataclasses.replace(
        cfg, param_dtype="bfloat16",
        loss_chunk=2048 if shape.kind == "train" else 0)


def make_quant(cfg, kv_bits: int):
    if kv_bits <= 0:
        return None
    names = transformer_layer_names(cfg)
    pol = PrecisionPolicy.uniform(
        names, None, FixedPointFormat(2, kv_bits - 2))
    return build_model_quant(pol, cfg, quantize_kv=True,
                             quantize_activations=False,
                             kv_container="int8" if kv_bits <= 8 else "int16")


def lower_cell(cfg, shape, mesh, *, kv_bits: int = 0,
               tp_decode: bool = False):
    """Returns (lowered, aux_info)."""
    plan = plan_for_mesh(mesh)
    quant = make_quant(cfg, kv_bits) if shape.kind == "decode" else None

    if shape.kind == "train":
        hp = TrainHParams(adamw=AdamWConfig(quantize_moments=True))
        state_struct = jax.eval_shape(
            lambda k: init_train_state(k, cfg, hp), jax.random.PRNGKey(0))
        state_sh = state_shardings(state_struct, plan)
        batch = input_specs(cfg, shape)
        batch_sh = auto_batch_sharding(batch, plan)
        step = make_train_step(cfg, hp)
        with activation_hints(plan):
            lowered = jax.jit(
                step, in_shardings=(state_sh, batch_sh),
                out_shardings=(state_sh, None),
                donate_argnums=(0,)).lower(state_struct, batch)
        return lowered

    params_struct = jax.eval_shape(
        lambda k: init_model(k, cfg), jax.random.PRNGKey(0))
    param_sh = param_shardings(params_struct, plan,
                               inference=(tp_decode
                                          and shape.kind == "decode"))

    if shape.kind == "prefill":
        batch = input_specs(cfg, shape)
        batch_sh = auto_batch_sharding(batch, plan)
        step = make_prefill_step(cfg, max_len=shape.seq_len)
        with activation_hints(plan):
            lowered = jax.jit(
                step, in_shardings=(param_sh, batch_sh)).lower(
                params_struct, batch)
        return lowered

    # decode
    specs = decode_specs(cfg, shape, quant=quant)
    caches_sh = cache_shardings(specs["caches"], plan, lead=1)
    tok_sh = auto_batch_sharding(
        {"t": specs.get("tokens", specs.get("embeds"))}, plan)["t"]
    pos_sh = NamedSharding(mesh, P())
    if "embeds" in specs:
        step = make_embed_decode_step(cfg, quant=quant)
        first = specs["embeds"]
    else:
        step = make_decode_step(cfg, quant=quant)
        first = specs["tokens"]
    with activation_hints(plan):
        lowered = jax.jit(
            step,
            in_shardings=(param_sh, tok_sh, pos_sh, caches_sh),
            out_shardings=(None, None, caches_sh),
            donate_argnums=(3,)).lower(
            params_struct, first, specs["pos"], specs["caches"])
    return lowered


def run_cell(arch: str, shape_name: str, mesh_kind: str, *,
             kv_bits: int = 0, overrides=None, hlo_dir=None,
             tp_decode: bool = False):
    shape = SHAPES[shape_name]
    cfg = get_config(arch)
    if not applicable(cfg, shape):
        return {"skipped": True, "reason": "not applicable"}
    cfg = dryrun_config(cfg, shape)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))

    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "mesh_shape": dict(mesh.shape), "kv_bits": kv_bits,
           "overrides": overrides or {}, "tp_decode": tp_decode,
           "skipped": False}
    t0 = time.time()
    lowered = lower_cell(cfg, shape, mesh, kv_bits=kv_bits,
                         tp_decode=tp_decode)
    rec["lower_s"] = round(time.time() - t0, 2)
    t0 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t0, 2)

    rec["memory"] = memory_summary(compiled)
    rec["cost"] = cost_summary(compiled)       # XLA aggregate (loop body x1)
    hlo = compiled.as_text()
    rec["collectives"] = collective_summary(hlo)
    # loop-aware per-device costs: while bodies x known_trip_count — the
    # numbers the roofline terms are built from (see launch.hlo_cost)
    rec["loop_cost"] = hlo_loop_analyze(hlo)
    rec["hlo_bytes"] = len(hlo)
    if hlo_dir:
        os.makedirs(hlo_dir, exist_ok=True)
        with open(os.path.join(
                hlo_dir, f"{mesh_kind}_{arch}_{shape_name}.hlo.txt"),
                "w") as f:
            f.write(hlo)

    # analytic model terms for the roofline
    n_params = cfg.param_count()
    n_active = cfg.active_param_count()
    tokens = (shape.global_batch * shape.seq_len
              if shape.kind in ("train", "prefill") else shape.global_batch)
    factor = 6 if shape.kind == "train" else 2
    rec["model"] = {
        "n_params": n_params, "n_active_params": n_active,
        "tokens_per_step": tokens,
        "model_flops": float(factor * n_active * tokens),
        "seq_len": shape.seq_len, "global_batch": shape.global_batch,
        "kind": shape.kind,
    }
    return rec


def main():
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--kv-bits", type=int, default=0)
    ap.add_argument("--tp-decode", action="store_true",
                    help="inference TP sharding for decode cells (no FSDP "
                         "weight gathers per token)")
    ap.add_argument("--set", action="append", default=[],
                    metavar="FIELD=PYVALUE", dest="sets")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--hlo-dir", default=None,
                    help="also dump optimized HLO text per cell")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = list(ARCH_IDS) if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = (["single", "multi"] if args.mesh == "both" else [args.mesh])
    overrides = {}
    for s in args.sets:
        k, v = s.split("=", 1)
        overrides[k] = ast.literal_eval(v)

    outdir = os.path.join(args.out, args.tag)
    os.makedirs(outdir, exist_ok=True)
    failures = []
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                path = os.path.join(outdir,
                                    f"{mesh_kind}_{arch}_{shape}.json")
                if os.path.exists(path) and not args.force:
                    print(f"[skip cached] {mesh_kind:6s} {arch} {shape}")
                    continue
                try:
                    rec = run_cell(arch, shape, mesh_kind,
                                   kv_bits=args.kv_bits, overrides=overrides,
                                   hlo_dir=args.hlo_dir,
                                   tp_decode=args.tp_decode)
                except Exception as e:  # noqa: BLE001 — record and continue
                    traceback.print_exc()
                    failures.append((arch, shape, mesh_kind, repr(e)))
                    continue
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                if rec.get("skipped"):
                    print(f"[n/a ] {mesh_kind:6s} {arch:26s} {shape}")
                else:
                    mem = (rec["memory"].get("argument_size_in_bytes", 0)
                           + rec["memory"].get("temp_size_in_bytes", 0)) \
                        / 2**30
                    lc = rec["loop_cost"]
                    print(f"[ok  ] {mesh_kind:6s} {arch:26s} {shape:12s} "
                          f"dev_mem={mem:7.2f}GiB flops={lc['flops']:.3e} "
                          f"hbm={lc['hbm_bytes']:.3e} "
                          f"wire={lc['wire_bytes'] / 2**20:9.1f}MiB "
                          f"compile={rec['compile_s']:.0f}s")
    if failures:
        print("\nFAILURES:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print("\nall requested cells compiled OK")


if __name__ == "__main__":
    main()
