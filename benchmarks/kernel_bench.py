"""Kernel benchmarks: correctness deltas vs oracle + footprint/traffic model.

This container executes Pallas in interpret mode (Python), so WALL TIMES
here characterize the oracle/kernel agreement and the memory model, not TPU
speed. The TPU-side throughput claim is structural: bytes-per-element moved
by each kernel at its BlockSpec tiling, reported as the compression ratio
the paper's formats buy."""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref

from .common import save_json


def _timeit(fn, *args, reps=3):
    fn(*args)  # warm/compile
    t0 = time.time()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / reps


def bench_quant_cast():
    x = jax.random.normal(jax.random.PRNGKey(0), (1024, 1024)) * 4
    out = {}
    for (i, f) in [(2, 6), (4, 4), (2, 14), (8, 8)]:
        y = ops.quant_cast(x, i, f)
        yr = ref.quant_cast_ref(x, i, f)
        out[f"Q{i}.{f}"] = {
            "max_err_vs_ref": float(jnp.abs(y - yr).max()),
            "interpret_s": _timeit(ops.quant_cast, x, i, f),
            "hbm_bytes_fp32": x.size * 4 * 2,
            "container_bits": 8 if i + f <= 8 else 16,
        }
    return out


def bench_pack():
    out = {}
    for bits in (2, 4, 8, 16):
        lo, hi = -(2 ** (bits - 1)), 2 ** (bits - 1) - 1
        q = jax.random.randint(jax.random.PRNGKey(1), (2048, 512), lo, hi + 1,
                               jnp.int32)
        w = ops.pack(q, bits)
        rt = ops.unpack(w, bits)
        out[f"{bits}b"] = {
            "roundtrip_exact": bool(jnp.array_equal(q, rt)),
            "footprint_ratio_vs_int32": w.size / q.size,
            "footprint_ratio_vs_fp32": w.size / q.size,
            "interpret_pack_s": _timeit(ops.pack, q, bits),
        }
    return out


def bench_quant_matmul():
    out = {}
    for (m, k, n) in [(256, 1024, 256), (512, 4096, 512)]:
        a = jax.random.normal(jax.random.PRNGKey(2), (m, k), jnp.float32)
        wq = jax.random.randint(jax.random.PRNGKey(3), (k, n), -128, 128,
                                jnp.int32).astype(jnp.int8)
        s = jax.random.uniform(jax.random.PRNGKey(4), (n,), minval=0.001,
                               maxval=0.02)
        y = ops.qmatmul(a, wq, s)
        yr = ref.quant_matmul_ref(a, wq, s)
        rel = float(jnp.abs(y - yr).max() / (jnp.abs(yr).max() + 1e-9))
        out[f"{m}x{k}x{n}"] = {
            "rel_err_vs_ref": rel,
            "weight_hbm_bytes": int(wq.size + n * 4),
            "weight_hbm_bytes_bf16": int(k * n * 2),
            "weight_traffic_ratio": (wq.size + n * 4) / (k * n * 2),
            "interpret_s": _timeit(ops.qmatmul, a, wq, s),
        }
    return out


def bench_paged_prefill_chunk():
    """Prefill-chunk attention: the variable-length paged chunk kernel
    (interpret mode) vs the jnp gather path, S in {8, 32, 128}, fragmented
    page tables, int4/int8/fp containers, per-row starts that straddle page
    boundaries. Errors are vs the dense-gather oracle; ``gather_s`` times a
    jitted gather-path equivalent (the serving reference mode)."""
    out = {}
    B, kv, g, hd, ps = 2, 2, 2, 32, 16
    for S in (8, 32, 128):
        starts = np.array([3, ps - 1], np.int32)[:B]   # straddle boundaries
        NP = -(-int(starts.max() + S) // ps)
        for bits, cont in ((0, "fp"), (8, "int8"), (4, "int4")):
            rng = np.random.default_rng(S * 10 + bits)
            kq, vq, ks, vs, pt = ref.make_fragmented_pool(rng, B, NP, ps,
                                                          kv, hd, bits)
            q = jnp.asarray(rng.normal(size=(B, S, kv * g, hd)), jnp.float32)
            qs = jnp.asarray(starts)
            lens = jnp.asarray(starts + S)
            y = ops.paged_kv_attention_chunk(q, kq, vq, ks, vs, pt, qs, lens,
                                             bits=bits)
            yr = ref.paged_kv_attention_chunk_ref(q, kq, vq, ks, vs, pt, qs,
                                                  lens, bits=bits)
            gather_fn = jax.jit(functools.partial(
                ref.paged_kv_attention_chunk_ref, bits=bits))
            out[f"S{S}-{cont}"] = {
                "max_err_vs_gather": float(jnp.abs(y - yr).max()),
                "pages": int(NP), "page_size": ps, "fragmented": True,
                "pallas_interpret_s": _timeit(
                    lambda q, *a: ops.paged_kv_attention_chunk(
                        q, *a, bits=bits),
                    q, kq, vq, ks, vs, pt, qs, lens, reps=1),
                "gather_s": _timeit(gather_fn, q, kq, vq, ks, vs, pt, qs,
                                    lens, reps=1),
            }
    return out


_STAGES = {
    "quant_cast": bench_quant_cast,
    "pack": bench_pack,
    "quant_matmul": bench_quant_matmul,
    "paged_prefill_chunk": bench_paged_prefill_chunk,
}


def run(*, verbose=True, only=None):
    res = {name: fn() for name, fn in _STAGES.items()
           if only is None or name in only}
    if verbose:
        print("[kernel_bench]")
        for kname, rows in res.items():
            for cfg, r in rows.items():
                err = r.get("max_err_vs_ref",
                            r.get("max_err_vs_gather",
                                  r.get("rel_err_vs_ref",
                                        r.get("roundtrip_exact"))))
                print(f"  {kname:19s} {cfg:18s} err/ok={err} ")
    save_json("kernel_bench.json" if only is None
              else f"kernel_bench_{'_'.join(sorted(only))}.json", res)
    return res


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help=f"comma list of stages ({','.join(_STAGES)})")
    args = ap.parse_args(argv)
    only = [s for s in args.only.split(",") if s] or None
    if only:
        unknown = set(only) - set(_STAGES)
        if unknown:
            raise SystemExit(f"unknown kernel_bench stages: {unknown}")
    run(only=only)


if __name__ == "__main__":
    main()
