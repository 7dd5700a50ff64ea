"""End-to-end training launcher.

Composes every substrate layer: synthetic LM pipeline (pure-function-of-step
batches), pjit'd train step with the production sharding rules, AdamW
(optionally int8 moments), per-layer precision (fake-quant via --policy /
--kv-bits), async checkpointing, fault-tolerant supervisor with straggler
log, and elastic restore (checkpoints are mesh-agnostic).

On this container it runs REAL training on the 1-CPU mesh — e.g. the ~100M
LM of examples/train_lm_mixed_precision.py; on a pod the same file drives
the production mesh (--mesh single|multi).

Usage (CPU example):
  PYTHONPATH=src python -m repro.launch.train --arch xlstm-350m --smoke \
      --steps 200 --batch-size 8 --seq-len 256 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint.ckpt import CheckpointManager, latest_step
from ..configs.registry import get_config, get_smoke_config
from ..core.fixedpoint import FixedPointFormat
from ..core.policy import PrecisionPolicy
from ..data.lm import LMDataConfig, lm_batch
from ..data.pipeline import DataPipeline
from ..optim.adamw import AdamWConfig
from ..optim.compress import CompressionConfig
from ..optim.schedule import cosine_warmup
from ..parallel.hints import activation_hints
from ..parallel.sharding import (auto_batch_sharding, plan_for_mesh,
                                 state_shardings)
from ..quant.apply import build_model_quant, transformer_layer_names
from ..runtime.compile_cache import enable_compile_cache
from ..runtime.fault import StragglerMonitor, TrainSupervisor
from .mesh import make_host_mesh, make_production_mesh
from .steps import TrainHParams, init_train_state, make_train_step


def build_quant(cfg, *, weight_bits: int, data_bits: int, kv_bits: int,
                policy_json: str):
    if policy_json:
        with open(policy_json) as f:
            pol = PrecisionPolicy.from_json(f.read())
    elif weight_bits or data_bits:
        names = transformer_layer_names(cfg)
        w = FixedPointFormat(2, weight_bits - 2) if weight_bits else None
        d = FixedPointFormat(4, data_bits - 4) if data_bits else None
        pol = PrecisionPolicy.uniform(names, w, d)
    else:
        pol = None
    if pol is None and not kv_bits:
        return None
    if pol is None:
        names = transformer_layer_names(cfg)
        pol = PrecisionPolicy.uniform(
            names, None, FixedPointFormat(2, kv_bits - 2))
    return build_model_quant(pol, cfg, quantize_kv=kv_bits > 0)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-trainable)")
    ap.add_argument("--mesh", default="host",
                    choices=["host", "single", "multi"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-interval", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--int8-moments", action="store_true")
    ap.add_argument("--grad-compress", action="store_true",
                    help="int8 gradient wire format + error feedback")
    # per-layer precision (the paper's feature, as first-class flags)
    ap.add_argument("--weight-bits", type=int, default=0)
    ap.add_argument("--data-bits", type=int, default=0)
    ap.add_argument("--kv-bits", type=int, default=0)
    ap.add_argument("--policy", default="", help="PrecisionPolicy json file")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--metrics-out", default="")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = (make_host_mesh() if args.mesh == "host"
            else make_production_mesh(multi_pod=args.mesh == "multi"))
    plan = plan_for_mesh(mesh)

    hp = TrainHParams(
        lr=args.lr,
        adamw=AdamWConfig(quantize_moments=args.int8_moments),
        grad_compress=CompressionConfig() if args.grad_compress else None)
    quant = build_quant(cfg, weight_bits=args.weight_bits,
                        data_bits=args.data_bits, kv_bits=args.kv_bits,
                        policy_json=args.policy)

    dcfg = LMDataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                        batch_size=args.batch_size, seed=args.seed + 1)

    state_struct = jax.eval_shape(
        lambda k: init_train_state(k, cfg, hp), jax.random.PRNGKey(args.seed))
    state_sh = state_shardings(state_struct, plan)
    batch_struct = jax.eval_shape(lambda: lm_batch(dcfg, 0))
    batch_sh = auto_batch_sharding(batch_struct, plan)

    lr_fn = cosine_warmup(args.lr, args.warmup, args.steps)

    def step_with_lr(state, batch, step_idx):
        hp_s = dataclasses.replace(hp, lr=lr_fn(step_idx))
        return make_train_step(cfg, hp_s, quant=quant)(state, batch)

    with activation_hints(plan):
        jit_step = jax.jit(step_with_lr,
                           in_shardings=(state_sh, batch_sh, None),
                           out_shardings=(state_sh, None),
                           donate_argnums=(0,))

        start_step = 0
        ckpt = None
        if args.ckpt_dir:
            ckpt = CheckpointManager(args.ckpt_dir,
                                     interval=args.ckpt_interval)
        if args.resume and args.ckpt_dir and \
                latest_step(args.ckpt_dir) is not None:
            start_step, state, _ = ckpt.restore_latest(
                state_struct, shardings=state_sh)
            print(f"[train] resumed from step {start_step}")
        else:
            state = jax.jit(
                lambda k: init_train_state(k, cfg, hp),
                out_shardings=state_sh)(jax.random.PRNGKey(args.seed))

        pipe = DataPipeline(lambda s: lm_batch(dcfg, s),
                            sharding=batch_sh, start_step=start_step)
        monitor = StragglerMonitor()

        def one_step(state, step):
            batch = next(pipe)
            state, metrics = jit_step(state, batch, step)
            return state, metrics

        def save_hook(step, state):
            if ckpt:
                ckpt.maybe_save(step, state,
                                extra={"data": pipe.state})

        def restore_fn():
            step, state, extra = ckpt.restore_latest(state_struct,
                                                     shardings=state_sh)
            pipe.restore(extra.get("data", {"step": step}))
            return step, state

        sup = TrainSupervisor(step_fn=one_step, save_hook=save_hook,
                              restore_fn=restore_fn, monitor=monitor)

        log = []
        t_start = time.time()
        # run in chunks so we can print progress
        step = start_step
        while step < args.steps:
            n = min(args.log_every, args.steps - step)
            state, metrics_list = sup.run(state, step, n)
            step += n
            m = metrics_list[-1]
            loss = float(m["loss"])
            log.append({"step": step, "loss": loss,
                        "grad_norm": float(m["grad_norm"])})
            tok_s = (args.batch_size * args.seq_len * n /
                     max(time.time() - t_start, 1e-9))
            t_start = time.time()
            print(f"[train] step {step:5d} loss {loss:8.4f} "
                  f"grad_norm {float(m['grad_norm']):8.3f} tok/s {tok_s:,.0f}")

    if ckpt:
        ckpt.maybe_save(step, state, extra={"data": pipe.state}, force=True)
        ckpt.wait()
    print("[train] straggler summary:", json.dumps(monitor.summary()))
    if args.metrics_out:
        os.makedirs(os.path.dirname(args.metrics_out) or ".", exist_ok=True)
        with open(args.metrics_out, "w") as f:
            json.dump(log, f, indent=1)
    return log


if __name__ == "__main__":
    main()
