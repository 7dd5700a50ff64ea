"""Pipeline parallelism: GPipe schedule over a "stage" mesh axis.

Optional at the default production mesh (2-axis DP x TP suits v5e's 2-D
torus); provided for clusters where an extra "stage" axis wins — e.g. very
deep dense models on elongated slices — and as the PP building block the
assignment asks for.

Implementation: ``shard_map`` over ("stage",); stage s holds the stacked
params of its layer range. The classic GPipe loop runs T = M + S - 1 ticks;
at tick t, stage s computes microbatch (t - s) if 0 <= t - s < M, then the
activation ring advances one hop via ``lax.ppermute``. Bubble fraction =
(S-1)/(M+S-1), reported by ``pipeline_bubble``.

The loop is a ``lax.scan`` over ticks; per-tick activations are a single
(microbatch, ...) block, so the HLO stays O(1) in both S and M. Collective
cost: (S-1+M-1) hops x activation bytes — priced in the roofline's
collective term when enabled.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_bubble(num_stages: int, num_microbatches: int) -> float:
    return (num_stages - 1) / (num_microbatches + num_stages - 1)


def gpipe_apply(stage_fn: Callable, stage_params, x_mb, *, mesh: Mesh,
                axis: str = "stage"):
    """Run x through S stages of ``stage_fn`` with the GPipe schedule.

    stage_fn(params_s, x) -> y, applied per stage (already vmapped over the
    stage's own layers if it holds several).
    stage_params: pytree with leading (S,) dim (stacked per-stage params).
    x_mb: (M, mb, ...) microbatched input, replicated across stages.
    Returns (M, mb, ...) outputs (as produced by the LAST stage).
    """
    S = mesh.shape[axis]
    M = x_mb.shape[0]
    T = M + S - 1

    def per_stage(params_s, x_all):
        # params_s: this stage's params (lead dim stripped by shard_map);
        # x_all: (M, mb, ...) full input (replicated); only stage 0 uses it.
        sid = jax.lax.axis_index(axis)
        params_s = jax.tree_util.tree_map(lambda a: a[0], params_s)
        mb_shape = x_all.shape[1:]
        buf = jnp.zeros((M,) + mb_shape, x_all.dtype)  # outputs of this stage

        def tick(carry, t):
            buf, inflight = carry
            # stage 0 ingests microbatch t; others take the ring payload
            mb_idx = t - sid
            x_in = jnp.where(
                sid == 0,
                x_all[jnp.clip(t, 0, M - 1)],
                inflight)
            active = (mb_idx >= 0) & (mb_idx < M)
            y = stage_fn(params_s, x_in)
            y = jnp.where(active, y, jnp.zeros_like(y))
            buf = jax.lax.cond(
                active,
                lambda b: jax.lax.dynamic_update_slice_in_dim(
                    b, y[None], jnp.clip(mb_idx, 0, M - 1), axis=0),
                lambda b: b, buf)
            # advance ring: stage s -> s+1 (last stage's output drops off)
            nxt = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % S) for i in range(S)])
            return (buf, nxt), None

        inflight0 = jnp.zeros(mb_shape, x_all.dtype)
        (buf, _), _ = jax.lax.scan(tick, (buf, inflight0), jnp.arange(T))
        # only the LAST stage's buffer is the model output; broadcast it
        out = jax.lax.psum(
            jnp.where(sid == S - 1, buf, jnp.zeros_like(buf)), axis)
        return out

    fn = jax.shard_map(per_stage, mesh=mesh,
                       in_specs=(P(axis), P()),
                       out_specs=P(),
                       check_vma=False)
    return fn(stage_params, x_mb)
