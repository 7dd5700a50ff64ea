"""Smoke run of the serving path on a TPU: qwen2-72b at its published widths
(d_model 8192, 64/8 heads, head_dim 128, d_ff 29568, vocab 152064, QKV
bias), depth cut to fit one v5e chip, bf16 weights drawn from a seed, an
int8 paged KV pool, and the Pallas paged-attention kernel, driven through
``BatchedServer.run``.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the --tp 4 path against tp=1, 4 chips

One chip: the kernel at serving shapes against its jnp oracle, then the
requests served with the kernel and again with the jnp gather path (token
agreement is printed, not gated: random weights flip the argmax on bf16
rounding). Four chips: only the tp=1 run and the tp=4 run it is compared
with, first-step logits within ``TP_LOGIT_TOL`` and no all-gather of the
page pools in the compiled programs.

Each fact is printed on its own line; the last line is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``. Any failed check,
or a platform other than TPU, exits non-zero without that line.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import re
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

LAYERS = 4            # of 80: 6.0 B params, 11.2 GiB of bf16 weights
PAGE_SIZE = 16
BATCH = 8             # slots, and requests (half of each prompt length)
PROMPT_LENS = (128, 512)
MAX_NEW = 32
MAX_LEN = 576         # 512 + 32 new tokens, rounded up to whole pages
PREFILL_BUCKET = 128
KV_BITS = 8
SEED = 0
# kernel vs oracle (float32 oracle at "highest" matmul precision), absolute
# and relative. On a v5e the kernel's float32 dots land within 1.4e-5; dots
# on bf16-rounded operands were 0.1 off, and a wrong page, scale or mask
# moves outputs by O(1)
KERNEL_TOL = 1e-3
# tp=4 vs tp=1 first-step logits: max |diff| over max |logit|
TP_LOGIT_TOL = 5e-2


class SmokeFailure(Exception):
    pass


def say(key, value):
    print(f"[chip_smoke] {key}: {value}", flush=True)


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


class CompileClock:
    """Sums XLA backend compile time from JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds, self.programs = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.programs += 1


def tpu_devices(chips: int):
    import jax
    devs = jax.devices()
    platform = devs[0].platform
    check(platform == "tpu", f"JAX found platform {platform!r}, not 'tpu'")
    check(len(devs) >= chips, f"{chips} chips asked, {len(devs)} found")
    return devs


def model_config():
    from repro.configs.qwen2_72b import CONFIG
    return dataclasses.replace(CONFIG, num_layers=LAYERS,
                               param_dtype="bfloat16")


def make_requests(vocab: int):
    from repro.launch.serve import Request
    rng = np.random.default_rng(SEED)
    return [Request(i, rng.integers(0, vocab, PROMPT_LENS[i % 2])
                    .astype(np.int32), MAX_NEW) for i in range(BATCH)]


def check_kernel():
    """The serving kernel, compiled (not interpreted), against the oracle
    at serving shapes: B=8, KV=8, G=8, hd=128, ps=16, a fragmented table of
    MAX_LEN // ps pages per row, decode (S=1) and one prefill bucket."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref
    from repro.kernels.paged_kv_attention import paged_kv_attention_chunk
    cfg = model_config()
    kv, g, hd = cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads, \
        cfg.head_dim
    npg = MAX_LEN // PAGE_SIZE
    failed = []
    for bits in (8, 4, 0):
        for s in (1, PREFILL_BUCKET):
            rng = np.random.default_rng(bits * 1000 + s)
            pool = ref.make_fragmented_pool(rng, BATCH, npg, PAGE_SIZE, kv,
                                            hd, bits)
            q = jnp.asarray(rng.normal(size=(BATCH, s, kv * g, hd)),
                            jnp.bfloat16)
            starts = rng.integers(0, MAX_LEN - s + 1, BATCH).astype(np.int32)
            args = (q, *pool[:4], jnp.asarray(pool[4]), jnp.asarray(starts),
                    jnp.asarray(starts + s))
            fn = jax.jit(lambda *a: paged_kv_attention_chunk(
                *a, bits=bits, interpret=False))
            hlo = fn.lower(*args).compile().as_text()
            check("tpu_custom_call" in hlo,
                  f"kernel bits={bits} S={s} did not lower to a Mosaic "
                  "custom call")
            out = np.asarray(fn(*args))
            with jax.default_matmul_precision("highest"):
                want = np.asarray(ref.paged_kv_attention_chunk_ref(
                    q, *pool[:4], pool[4], starts, starts + s, bits=bits))
            err = float(np.abs(out - want).max())
            ok = bool(np.allclose(out, want, atol=KERNEL_TOL,
                                  rtol=KERNEL_TOL)
                      and np.isfinite(out).all())
            say(f"kernel bits={bits} S={s}",
                f"compiled (tpu_custom_call), max |kernel - oracle| = {err}"
                f" (tol {KERNEL_TOL}) {'ok' if ok else 'FAIL'}")
            if not ok:
                failed.append(f"bits={bits} S={s}")
    check(not failed, f"kernel off the oracle at {', '.join(failed)}")


def serve(cfg, params, attn_impl: str, mesh=None):
    """Serve the smoke requests; returns (server, requests, first decode
    step's logits as numpy)."""
    from repro.launch.serve import BatchedServer
    srv = BatchedServer(cfg, params, batch_size=BATCH, max_len=MAX_LEN,
                        kv_bits=KV_BITS, page_size=PAGE_SIZE,
                        attn_impl=attn_impl, prefill_bucket=PREFILL_BUCKET,
                        mesh=mesh)
    first = []
    decode = srv.decode

    def decode_keep_first(*args):
        out = decode(*args)
        if not first:
            first.append(np.asarray(out[1], np.float32))
        return out
    srv.decode = decode_keep_first
    reqs = make_requests(cfg.vocab_size)
    t0 = time.perf_counter()
    srv.run(reqs)
    dt = time.perf_counter() - t0
    srv.decode = decode
    answered = sum(r.done and r.error is None and len(r.out) == MAX_NEW
                   for r in reqs)
    tokens = sum(len(r.out) for r in reqs)
    tag = f"serve attn={attn_impl}" + (f" tp={mesh.shape['model']}"
                                       if mesh is not None else "")
    say(tag, f"{answered}/{len(reqs)} requests answered, {tokens} tokens "
        f"generated, {srv.prefill_forwards} prefill forwards, "
        f"{srv.decode_steps} decode steps, {dt:.3f} s wall (compile "
        "included)")
    check(answered == len(reqs), f"{tag}: {len(reqs) - answered} requests "
          "unanswered")
    check(all(0 <= t < cfg.vocab_size for r in reqs for t in r.out),
          f"{tag}: token id out of range")
    check(first and np.isfinite(first[0]).all()
          and first[0].shape == (BATCH, cfg.vocab_size),
          f"{tag}: first-step logits missing, misshapen or not finite")
    return srv, reqs, first[0]


def token_agreement(a, b) -> float:
    return float(np.mean([np.mean(np.asarray(x.out) == np.asarray(y.out))
                          for x, y in zip(a, b)]))


def pool_all_gathers(hlo: str, num_pages: int):
    """All-gather instructions whose result has the pool's page axes."""
    pat = re.compile(rf"\[(\d+,)?{num_pages},{PAGE_SIZE},")
    return [ln.strip() for ln in hlo.splitlines()
            if "all-gather" in ln and "=" in ln
            and pat.search(ln.split("all-gather", 1)[0])]


def kernel_pool_heads(hlo: str, num_pages: int):
    """KV-head counts of the pool operands the paged kernel is handed."""
    pat = re.compile(rf"s8\[{num_pages},{PAGE_SIZE},(\d+),\d+\]")
    heads = set()
    for ln in hlo.splitlines():
        if "tpu_custom_call" in ln and "paged_kv_attention" in ln:
            heads.update(int(h) for h in pat.findall(ln))
    return heads


def one_chip():
    import jax
    from repro.models.transformer import init_model
    check_kernel()
    cfg = model_config()
    t0 = time.perf_counter()
    params = jax.block_until_ready(init_model(jax.random.PRNGKey(SEED), cfg))
    nbytes = sum(a.nbytes for a in jax.tree.leaves(params))
    say("params", f"{cfg.param_count()} ({nbytes} bytes, bf16), init "
        f"{time.perf_counter() - t0:.3f} s")
    srv, reqs, logits = serve(cfg, params, "pallas")
    del srv
    gc.collect()
    _, reqs_g, logits_g = serve(cfg, params, "gather")
    say("pallas vs gather", f"token agreement "
        f"{token_agreement(reqs, reqs_g)}, first-step logits max |diff| "
        f"{float(np.abs(logits - logits_g).max())} (not gated)")


def four_chips():
    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import make_serving_mesh
    from repro.models.transformer import init_model
    from repro.parallel.sharding import param_shardings, plan_for_mesh
    cfg = model_config()
    key = jax.random.PRNGKey(SEED)
    params = jax.block_until_ready(init_model(key, cfg))
    say("params", f"{cfg.param_count()} "
        f"({sum(a.nbytes for a in jax.tree.leaves(params))} bytes, bf16)")
    srv, reqs1, logits1 = serve(cfg, params, "pallas")
    # device 0 cannot hold the tp=1 weights next to its tp=4 shard
    del srv, params
    gc.collect()
    mesh = make_serving_mesh(4)
    check(mesh.shape["model"] == 4, f"mesh {dict(mesh.shape)}")
    shardings = param_shardings(
        jax.eval_shape(lambda k: init_model(k, cfg), key),
        plan_for_mesh(mesh), inference=True)
    params = jax.block_until_ready(jax.jit(
        init_model, static_argnums=1, out_shardings=shardings)(key, cfg))
    srv, reqs4, logits4 = serve(cfg, params, "pallas", mesh=mesh)
    diff = float(np.abs(logits4 - logits1).max())
    scale = float(np.abs(logits1).max())
    say("tp=4 vs tp=1", f"first-step logits max |diff| {diff} over max "
        f"|logit| {scale} = {diff / scale} (tol {TP_LOGIT_TOL}), argmax "
        f"agreement {float(np.mean(logits4.argmax(-1) == logits1.argmax(-1)))}"
        f", token agreement {token_agreement(reqs1, reqs4)}")
    check(diff <= TP_LOGIT_TOL * scale, "tp=4 logits off tp=1")
    # the compiled tp=4 programs: the kernel per shard on KV/4 heads, and
    # no all-gather of a page pool
    num_pages = srv.allocator.num_pages
    zeros = jnp.zeros((BATCH,), jnp.int32)
    pt = srv._page_table_dev()
    programs = {
        "decode": srv.decode.lower(srv.params, zeros, zeros, srv.caches, pt),
        "prefill": srv._chunk_prefill.lower(
            srv.params, jnp.zeros((BATCH, PREFILL_BUCKET), jnp.int32), zeros,
            zeros, srv.caches, pt),
    }
    for name, lowered in programs.items():
        hlo = lowered.compile().as_text()
        gathers = pool_all_gathers(hlo, num_pages)
        heads = kernel_pool_heads(hlo, num_pages)
        say(f"tp=4 {name} HLO", f"kernel pool operands hold KV heads "
            f"{sorted(heads)}, {len(gathers)} all-gathers of a page pool")
        check(heads == {cfg.num_kv_heads // 4},
              f"{name}: kernel pool operands hold heads {sorted(heads)}")
        check(not gathers, f"{name}: pool all-gathered: {gathers[:2]}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the --tp 4 serving path and the tp=1 "
                         "run it is compared with")
    args = ap.parse_args(argv)
    try:
        devs = tpu_devices(args.chips)
        from repro.runtime.compile_cache import enable_compile_cache
        cache_dir = enable_compile_cache()
        clock = CompileClock()
        say("device", f"{devs[0].platform} {devs[0].device_kind} x "
            f"{len(devs)}")
        say("compile cache", cache_dir)
        cfg = model_config()
        say("model", f"{cfg.name} layers {cfg.num_layers} of 80 (depth cut "
            f"to fit one chip), d_model {cfg.d_model}, heads "
            f"{cfg.num_heads}/{cfg.num_kv_heads}, head_dim {cfg.head_dim}, "
            f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, params "
            f"{cfg.param_dtype}, int{KV_BITS} pool ps {PAGE_SIZE}")
        (four_chips if args.chips == 4 else one_chip)()
        stats = devs[0].memory_stats() or {}
        say("peak device memory (device 0)",
            stats.get("peak_bytes_in_use", "not reported"))
        say("compile", f"{clock.seconds:.3f} s over {clock.programs} "
            "programs")
    except SmokeFailure as e:
        print(f"[chip_smoke] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
