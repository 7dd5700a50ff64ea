"""Paged vs dense KV-cache serving bench: tokens/sec, prefill latency, and
HBM bytes per token — the serving hot-path trajectory.

Runs the same mixed-length request trace through several BatchedServer
configurations on a smoke-scale GQA arch:

  dense-fp32        — the seed layout: one (B, max_len) fp32 slab per layer
  paged-int8-step   — page pool, int8 pages, SLOT-GRANULAR prefill (the PR 1
                      hot path: O(prompt_len) whole-batch forwards/request)
  paged-int8        — same pool, BUCKETED chunked prefill (O(prompt/bucket)
                      forwards) — the before/after pair for the prefill work
  paged-int4        — bucketed prefill, 4-bit lane-packed pages
  paged-int8-pallas — bucketed prefill + decode routed through the Pallas
                      paged-attention kernel (interpret-mode on CPU, so CPU
                      tok/s is NOT indicative; the row tracks routing +
                      numerics, the kernel is bench'd on TPU)

and reports, per configuration:

  * decode throughput (generated tokens / wall second),
  * prefill latency (wall seconds of prefill per admitted request) and the
    number of prefill forward-program executions,
  * KV **at-rest bytes per token-slot** — stored cache bytes divided by the
    token capacity they back (~4x smaller for int8, ~8x for int4 vs fp32),
  * total cache HBM actually allocated.

A second, **shared-prefix workload** (``run_prefix`` / ``--workload
prefix``) serves N requests that share a long system prompt and measures
the prefix page cache: request/token hit rates, prefill forwards with
sharing off vs on (the O(prompt/bucket) -> O(suffix/bucket) admission win),
CoW copies and evictions, and at-rest KV bytes under uniform int8 vs a
mixed per-layer precision profile vs int4. It RAISES on a prefix-cache
refcount leak (allocator end-state check) — the CI bench-smoke gate.

A third, **overcommit workload** (``run_overcommit`` / ``--workload
overcommit``) offers ~2.5x the device pool's page capacity through the
tiered page store: --kv-offload host + --sched slo + preemption. It
reports the device/host byte split (per container), preempt/resume and
demote/promote counts, and prefix hit-rate parity after a simulated
restart (snapshot -> fresh server -> restore). It RAISES on any rejected
waitable request, an unresumed preemption victim, an allocator refcount
leak, or a host-tier page leak — the CI overcommit-smoke gate.

A fourth, **adaptation workload** (``run_adapt`` / ``--workload adapt``)
serves a many-tenant overcommitted trace with --kv-adapt off vs on: with
adaptation on, pool pressure REQUANTIZES cold cached prefix pages one
container step narrower (fp -> int8 -> int4) into a bounded device tier
*before* any host round trip. It gates (RAISES) on >=1 requantization
before the first host demotion, >=2x device-held tokens before the first
round trip vs adapt-off, the lm_precision accuracy gate (>=0.9 token
agreement vs the byte-exact adapt-off reference, zero violations), and
pool/host/tier leak checks — the CI adapt-smoke gate.

Results land in results/paged_serve.json (+ results/prefix_serve.json,
results/overcommit_serve.json, results/adapt_serve.json) AND append a trajectory point to the repo-root
BENCH_serve.json so the perf trend is tracked across PRs.

Run:  PYTHONPATH=src python -m benchmarks.paged_serve [--arch qwen2-72b]
      [--page-size 16] [--requests 12] [--fast]
      [--workload all|mixed|prefix|overcommit|adapt]
(--fast = CI smoke: tiny trace, one bench iteration per config.)
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from repro.configs.registry import get_smoke_config
from repro.launch.serve import BatchedServer, Request
from repro.models.transformer import init_model

from .common import RESULTS, save_json

BENCH_TRAJECTORY = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_serve.json")


def _export_trace(srv, path):
    """Chrome-trace export guarded on live tracing: with ``metrics="off"``
    the server carries a NullTracer whose export writes nothing (it warns
    and returns None) — skip it explicitly so the bench never advertises
    a trace artifact it did not produce. Returns the written path, or
    None when tracing is disabled."""
    if not srv.tracer.enabled:
        print(f"  [trace] skipped {os.path.basename(path)}: tracing "
              f"disabled (metrics off)")
        return None
    os.makedirs(RESULTS, exist_ok=True)
    return srv.tracer.export_chrome(path)


def _kv_cache_leaves(caches):
    """Yield (kind, array) for attention-cache storage leaves.

    Handles both the stacked (periods, ...) layout and the per-period LIST
    layout the per-layer precision profiles use (mixed containers cannot
    stack)."""
    for seg in caches:
        for entry in seg:
            layers = entry if isinstance(entry, list) else [entry]
            for layer in layers:
                if isinstance(layer, dict):
                    if "k_pages" in layer:
                        for k in ("k_pages", "v_pages", "k_scale", "v_scale"):
                            yield k, layer[k]
                    elif "k" in layer and "v" in layer:
                        yield "k", layer["k"]
                        yield "v", layer["v"]


def cache_stats(srv):
    """(stored_bytes, token_capacity) of the serving KV cache.

    For paged pools the reserved scratch page backs no tokens; its (single
    page) share is excluded from the per-token figure but still counted in
    the reported total MiB."""
    total = sum(a.size * a.dtype.itemsize
                for _, a in _kv_cache_leaves(srv.caches))
    if srv.paged:
        P = srv.allocator.num_pages
        return total, total * (P - 1) / P, (P - 1) * srv.page_size
    return total, total, srv.B * srv.max_len


MAX_PROMPT = 13


def mk_requests(vocab, n, max_new, seed=0):
    rng = np.random.default_rng(seed)
    lens = rng.integers(3, MAX_PROMPT + 1, n)
    return [Request(i, rng.integers(0, vocab, L).astype(np.int32), max_new)
            for i, L in enumerate(lens)]


def bench_one(cfg, params, *, name, requests, batch, max_len, kv_bits,
              page_size, num_pages, attn_impl="gather", prefill="auto",
              prefill_bucket=16, prefill_batch=0, warmup=True):
    srv = BatchedServer(cfg, params, batch_size=batch, max_len=max_len,
                        kv_bits=kv_bits, page_size=page_size,
                        num_pages=num_pages, attn_impl=attn_impl,
                        prefill=prefill, prefill_bucket=prefill_bucket,
                        prefill_batch=prefill_batch)
    if warmup:
        # compile the decode step AND every power-of-two bucket program the
        # trace can hit (prompt lens 3..MAX_PROMPT -> buckets 2..16), so the
        # measured run is execution only
        rng = np.random.default_rng(99)
        reqs = [Request(1000 + i,
                        rng.integers(0, cfg.vocab_size, L).astype(np.int32),
                        2)
                for i, L in enumerate([2, 3, 5, 9, min(13, max_len // 2)])]
        srv.run(reqs)
        # registry-wide zero at the warmup boundary: every serve counter is
        # registry-backed now, so one reset() replaces the old per-counter
        # hand-zeroing (and can't silently miss a newly added counter)
        srv.metrics.reset()
    reqs = mk_requests(cfg.vocab_size, requests,
                       max_new=srv.max_len // 2, seed=0)
    t0 = time.time()
    srv.run(reqs)
    dt = time.time() - t0
    gen = sum(len(r.out) for r in reqs)
    stored, usable, capacity = cache_stats(srv)
    res = {
        "name": name,
        "kv_bits": kv_bits,
        "page_size": page_size,
        "attn_impl": srv.attn_impl,
        "prefill": srv.prefill_mode,
        "tokens_per_s": gen / max(dt, 1e-9),
        "prefill_forwards": srv.prefill_forwards,
        "prefill_latency_ms": 1e3 * srv.prefill_s / max(len(reqs), 1),
        "prefill_s": srv.prefill_s,
        "kv_bytes_per_token_slot": usable / capacity,
        "kv_cache_mib": stored / 2 ** 20,
        "token_capacity": capacity,
        "wall_s": dt,
    }
    return res


def run_batched_prefill(cfg, params, *, requests=8, batch=4, verbose=True,
                        fast=False):
    """Shared-bucket batched-prefill bench: same-length prompts arriving
    together, so every admission cycle surfaces several same-bucket rows.
    Sequential (--prefill-batch 1) vs batched (auto = batch size) admission
    — the multi-request batched prefill win is FEWER prefill forwards at
    equal tokens, which is what TTFT on a real accelerator tracks.

    GATES (RAISES — the CI mixed bench-smoke step): batched must run
    strictly fewer prefill forwards than sequential, and the generated
    token streams must agree (bitwise identity is asserted separately in
    the single-threaded-XLA subprocess test; multithreaded CPU GEMMs can
    flip argmax ties here, hence agreement)."""
    if fast:
        requests, batch = 4, 2
    plen, max_new, max_len, page_size = 11, 8, 64, 8
    per_slot = -(-(plen + max_new) // page_size)
    num_pages = 1 + batch * per_slot

    def mk():
        rng = np.random.default_rng(3)
        return [Request(i, rng.integers(0, cfg.vocab_size, plen)
                        .astype(np.int32), max_new) for i in range(requests)]

    def serve(pb):
        srv = BatchedServer(cfg, params, batch_size=batch, max_len=max_len,
                            page_size=page_size, num_pages=num_pages,
                            kv_bits=8, prefill="bucketed", prefill_bucket=16,
                            prefill_batch=pb)
        t0 = time.time()
        reqs = srv.run(mk())
        return srv, reqs, time.time() - t0

    seq, reqs_seq, _ = serve(1)
    bat, reqs_bat, _ = serve(batch)
    agree = np.mean([np.mean(np.asarray(a.out) == np.asarray(b.out))
                     for a, b in zip(reqs_seq, reqs_bat)])
    if agree < 0.9:
        raise RuntimeError(f"batched prefill broke decode: only {agree:.1%} "
                           f"token agreement with sequential admission")
    if bat.prefill_forwards >= seq.prefill_forwards:
        raise RuntimeError(
            f"batched prefill failed to reduce forwards on the shared-bucket "
            f"trace: {seq.prefill_forwards} sequential vs "
            f"{bat.prefill_forwards} batched")
    res = {
        "requests": requests, "batch": batch, "prompt_len": plen,
        "prefill_forwards_sequential": seq.prefill_forwards,
        "prefill_forwards_batched": bat.prefill_forwards,
        "prefill_forwards_reduction": (seq.prefill_forwards
                                       / max(bat.prefill_forwards, 1)),
        "ttft_ms_sequential": 1e3 * seq.prefill_s / requests,
        "ttft_ms_batched": 1e3 * bat.prefill_s / requests,
        "token_agreement": float(agree),
    }
    if verbose:
        print(f"[batched_prefill] {requests} same-bucket prompts "
              f"(len {plen}, batch={batch}): "
              f"{res['prefill_forwards_sequential']} -> "
              f"{res['prefill_forwards_batched']} prefill forwards "
              f"({res['prefill_forwards_reduction']:.1f}x fewer), "
              f"TTFT {res['ttft_ms_sequential']:.1f} -> "
              f"{res['ttft_ms_batched']:.1f} ms/req, "
              f"agreement {agree:.1%}")
    return res


def _mixed_profile(cfg):
    """Per-layer KV policy with two distinct bit-widths: even layers int8
    Q(2,6), odd layers int4 Q(2,2) — the shape of a core.search output."""
    from repro.core.fixedpoint import FixedPointFormat
    from repro.core.policy import LayerPolicy, PrecisionPolicy
    return PrecisionPolicy(
        tuple(f"layer_{i:03d}" for i in range(cfg.num_layers)),
        tuple(LayerPolicy(None, FixedPointFormat(2, 6 if i % 2 == 0 else 2))
              for i in range(cfg.num_layers)))


def mk_prefix_requests(vocab, n, sys_len, max_new, seed=0):
    """N requests sharing a common system prompt + a short random suffix —
    the multi-user traffic shape the prefix cache exists for."""
    rng = np.random.default_rng(seed)
    sys_prompt = rng.integers(0, vocab, sys_len).astype(np.int32)
    return [Request(i, np.concatenate(
                [sys_prompt,
                 rng.integers(0, vocab, int(rng.integers(3, 8)))
                 .astype(np.int32)]), max_new)
            for i in range(n)]


def _kv_at_rest_bytes(srv):
    return sum(a.size * a.dtype.itemsize
               for _, a in _kv_cache_leaves(srv.caches))


def run_prefix(*, arch="qwen2-72b", requests=8, batch=4, verbose=True,
               fast=False):
    """Shared-prefix serving workload: prefix cache on vs off, uniform int8
    vs per-layer profile vs int4.

    Reports the prefix hit rate, prefill forwards saved (the O(prompt) ->
    O(suffix) admission win), and at-rest KV bytes per configuration; the
    run RAISES on a prefix-cache refcount leak (allocator end-state check),
    which is what the CI bench-smoke step gates on."""
    cfg = get_smoke_config(arch)
    params = init_model(jax.random.PRNGKey(0), cfg)
    if fast:
        requests, batch = 4, 2
    # 42 = 5 full pages + 2 tokens into page 6: every hit aliases 5 pages
    # AND copies-on-write the partially shared sixth
    sys_len, page_size, max_new, max_len = 42, 8, 8, 64
    # pool sized for the UNSHARED worst case so on/off see identical pools
    per_slot = -(-(sys_len + 7 + max_new) // page_size)
    num_pages = 1 + batch * per_slot + 2
    mk = lambda: mk_prefix_requests(cfg.vocab_size, requests, sys_len,
                                    max_new, seed=0)
    # prefill_batch pinned to 1: the off-vs-on comparison measures PREFIX
    # SHARING alone (batched admission is the other forward-count axis,
    # measured by run_batched_prefill; auto would batch only the off side)
    common = dict(batch_size=batch, max_len=max_len, page_size=page_size,
                  num_pages=num_pages, prefill_bucket=16, prefill_batch=1)

    def serve(**kw):
        srv = BatchedServer(cfg, params, **common, **kw)
        t0 = time.time()
        reqs = srv.run(mk())
        return srv, reqs, time.time() - t0

    off, reqs_off, dt_off = serve(kv_bits=8, prefix_cache="off")
    on, reqs_on, dt_on = serve(kv_bits=8, prefix_cache="on")
    prof, _, _ = serve(kv_profile=_mixed_profile(cfg), prefix_cache="on")
    pscale, _, _ = serve(kv_bits=8, kv_scale="page", prefix_cache="on")
    int4, _, _ = serve(kv_bits=4, prefix_cache="on")

    agree = np.mean([np.mean(np.asarray(a.out) == np.asarray(b.out))
                     for a, b in zip(reqs_off, reqs_on)])
    if agree < 0.9:
        raise RuntimeError(f"prefix sharing broke decode: only {agree:.1%} "
                           f"token agreement with sharing off")
    stats = on.prefix_cache.stats()
    for tag, srv in [("on", on), ("profile", prof), ("pscale", pscale),
                     ("int4", int4)]:
        leaked = srv.release_prefix_cache()
        if leaked or srv.allocator.num_free != srv.allocator.num_usable:
            raise RuntimeError(
                f"prefix-cache refcount leak in config {tag!r}: {leaked} "
                f"pages still cache-referenced, "
                f"{srv.allocator.num_usable - srv.allocator.num_free} "
                f"pages unreturned after all requests completed")
    bytes_int8 = _kv_at_rest_bytes(on)
    bytes_prof = _kv_at_rest_bytes(prof)
    bytes_int4 = _kv_at_rest_bytes(int4)
    res = {
        "arch": arch, "requests": requests, "batch": batch,
        "sys_prompt_len": sys_len, "page_size": page_size,
        "prefix_hit_rate": stats["hit_rate"],
        "prefix_token_hit_rate": stats["token_hit_rate"],
        "prefix_hit_tokens": stats["hit_tokens"],
        "cow_copies": stats["cow_copies"],
        "evictions": stats["evictions"],
        "prefill_forwards_off": off.prefill_forwards,
        "prefill_forwards_on": on.prefill_forwards,
        "prefill_forwards_saved": on.prefill_forwards_saved,
        "prefill_forwards_reduction": (
            off.prefill_forwards / max(on.prefill_forwards, 1)),
        "prefill_s_off": off.prefill_s,
        "prefill_s_on": on.prefill_s,
        "token_agreement_on_vs_off": float(agree),
        "kv_at_rest_bytes": {"uniform-int8": bytes_int8,
                             "profile-int8/int4": bytes_prof,
                             "uniform-int4": bytes_int4},
        "profile_bytes_vs_int8": bytes_prof / bytes_int8,
        "tokens_per_s_on": sum(len(r.out) for r in reqs_on) / max(dt_on,
                                                                  1e-9),
        "tokens_per_s_off": sum(len(r.out) for r in reqs_off) / max(dt_off,
                                                                    1e-9),
    }
    if verbose:
        print(f"[prefix_serve] arch={arch} {requests} reqs sharing a "
              f"{sys_len}-token system prompt (batch={batch})")
        print(f"  hit rate {res['prefix_hit_rate']:.0%} requests / "
              f"{res['prefix_token_hit_rate']:.0%} prompt tokens; "
              f"{res['cow_copies']} CoW copies, {res['evictions']} evictions")
        print(f"  prefill forwards {res['prefill_forwards_off']} (off) -> "
              f"{res['prefill_forwards_on']} (on), "
              f"{res['prefill_forwards_reduction']:.1f}x fewer "
              f"({res['prefill_forwards_saved']} saved)")
        print(f"  at-rest KV: int8 {bytes_int8 / 2**10:.1f} KiB, "
              f"profile {bytes_prof / 2**10:.1f} KiB "
              f"({res['profile_bytes_vs_int8']:.2f}x), "
              f"int4 {bytes_int4 / 2**10:.1f} KiB")
        print(f"  token agreement on/off {agree:.1%}; no refcount leaks")
    save_json("prefix_serve.json", res)
    return res


def mk_overcommit_requests(vocab, sys_len, *, waves, seed=0):
    """Overcommitted trace in three deterministic waves (decode-step
    arrivals): (1) low-priority long decodes that oversubscribe the pool,
    (2) later high-priority short SLO requests that must PREEMPT, (3) a
    tail re-using the shared system prompt (hits demoted/promoted prefix
    pages). ``waves = (n_long, n_urgent, n_tail)``."""
    from repro.launch.serve import Request
    rng = np.random.default_rng(seed)
    sys_prompt = rng.integers(0, vocab, sys_len).astype(np.int32)
    n_long, n_urgent, n_tail = waves
    reqs, rid = [], 0

    def add(n, sfx_len, max_new, priority, arrive, deadline=None):
        nonlocal rid
        for _ in range(n):
            prompt = np.concatenate(
                [sys_prompt, rng.integers(0, vocab, sfx_len)
                 .astype(np.int32)])
            reqs.append(Request(rid, prompt, max_new, priority=priority,
                                arrive_step=arrive, deadline_step=deadline))
            rid += 1
    add(n_long, 3, 16, priority=0, arrive=0)
    add(n_urgent, 2, 6, priority=5, arrive=6, deadline=30)
    add(n_tail, 4, 8, priority=1, arrive=18)
    return reqs


def run_overcommit(*, arch="qwen2-72b", verbose=True, fast=False):
    """Overcommit workload: offered page demand ~2.5x the device pool,
    served through the tiered page store (--kv-offload host) with SLO
    scheduling + preemption and a simulated restart.

    Gates (RAISES — the CI bench-smoke step): zero rejected waitable
    requests, every preempted request resumed and completed, prefix
    hit-rate parity after snapshot restore, no allocator refcount leaks,
    and no host-tier page leaks after release."""
    cfg = get_smoke_config(arch)
    params = init_model(jax.random.PRNGKey(0), cfg)
    # fast needs 4 long decodes: 3 no longer oversubscribe the pool since
    # re-aliasing + requant relief landed, so preemption would never fire
    waves = (4, 1, 2) if fast else (4, 2, 3)
    sys_len, page_size, max_len, batch = 21, 8, 64, 3
    # pool sized to ~2 concurrent long requests; the OFFERED demand
    # (waves[0] alone needs waves[0]*5 pages) oversubscribes it ~2.5x
    num_pages = 1 + 11
    mk = lambda: mk_overcommit_requests(cfg.vocab_size, sys_len,
                                        waves=waves, seed=0)
    common = dict(batch_size=batch, max_len=max_len, page_size=page_size,
                  num_pages=num_pages, kv_bits=8, prefix_cache="on",
                  kv_offload="host", sched="slo", metrics="on")

    srv = BatchedServer(cfg, params, **common)
    t0 = time.time()
    reqs = srv.run(mk())
    dt = time.time() - t0
    offered_pages = sum(srv._pages_needed(r) for r in reqs)
    # SLO fields + trace artifact come from the COLD run only: the warm
    # restart pass below re-issues the same rids, which would fold a second
    # incarnation of every request into the goodput denominator
    slo = srv.tracer.slo_summary()
    trace_path = _export_trace(srv,
                               os.path.join(RESULTS,
                                            "trace_overcommit.json"))
    n_events = len(srv.tracer.events)

    # --- gate: a bounded pool served an overcommitted offered load ---
    rejected = [r for r in reqs if r.error is not None]
    if rejected:
        raise RuntimeError(f"overcommit: {len(rejected)} waitable requests "
                           f"rejected with --kv-offload host (expected 0): "
                           f"{[r.rid for r in rejected]}")
    if not all(r.done and len(r.out) > 0 for r in reqs):
        raise RuntimeError("overcommit: not every request completed")
    if srv.preempt_count < 1:
        raise RuntimeError("overcommit trace failed to trigger preemption")
    if srv.resume_count != srv.preempt_count:
        raise RuntimeError(f"preempted {srv.preempt_count} but resumed "
                           f"{srv.resume_count} — a victim never came back")

    # --- preempted streams match an uninterrupted run (agreement: argmax
    # can flip on float ties under multithreaded XLA; the subprocess test
    # in tests/test_scheduler.py asserts bitwise identity) ---
    big = BatchedServer(cfg, params, batch_size=batch, max_len=max_len,
                        page_size=page_size, kv_bits=8)
    reqs_ref = big.run(mk())
    by_rid = {r.rid: r for r in reqs_ref}
    agree = np.mean([np.mean(np.asarray(r.out)
                             == np.asarray(by_rid[r.rid].out))
                     for r in reqs])
    if agree < 0.9:
        raise RuntimeError(f"overcommit decode disagrees with the "
                           f"uninterrupted reference: {agree:.1%}")

    inv = srv.kv_inventory()
    stats = srv.prefix_cache.stats()

    # --- simulated restart: snapshot -> fresh server -> restore ---
    import tempfile
    snap = os.path.join(tempfile.mkdtemp(prefix="kv_snapshot_"),
                        "prefix_pages.npz")
    snap_pages = srv.snapshot_prefix_cache(snap)
    # warm reference: second pass on the ORIGINAL server
    l0, h0 = srv.prefix_cache.lookups, srv.prefix_cache.hits
    srv.run(mk())
    warm_rate = ((srv.prefix_cache.hits - h0)
                 / max(srv.prefix_cache.lookups - l0, 1))
    srv2 = BatchedServer(cfg, params, **common)
    restored = srv2.restore_prefix_cache(snap)
    reqs2 = srv2.run(mk())
    s2 = srv2.prefix_cache.stats()
    if not all(r.done and r.error is None for r in reqs2):
        raise RuntimeError("restored server failed the overcommit trace")
    if s2["hit_rate"] < warm_rate - 0.05:
        raise RuntimeError(
            f"restart hit-rate parity broken: restored {s2['hit_rate']:.0%}"
            f" vs warm {warm_rate:.0%}")

    # --- leak gates: refcounts AND host tier drain to zero ---
    for tag, s in [("primary", srv), ("restored", srv2)]:
        leaked = s.release_prefix_cache()
        if leaked or s.allocator.num_free != s.allocator.num_usable:
            raise RuntimeError(
                f"allocator refcount leak ({tag}): {leaked} cache pages, "
                f"{s.allocator.num_usable - s.allocator.num_free} "
                f"unreturned")
        if s.host_store.num_pages != 0:
            raise RuntimeError(
                f"host-tier page leak ({tag}): {s.host_store.num_pages} "
                f"pages still parked after release")

    res = {
        "arch": arch, "requests": len(reqs), "batch": batch,
        "page_size": page_size, "device_pages": num_pages - 1,
        "offered_pages": offered_pages,
        "overcommit_ratio": offered_pages / (num_pages - 1),
        "completed": len(reqs), "rejected": 0,
        "preemptions": srv.preempt_count, "resumes": srv.resume_count,
        "realias_skipped_demotions": srv.realias_skipped,
        "ooo_admissions": srv.scheduler.ooo_admissions,
        "demotions": stats["demotions"], "promotions": stats["promotions"],
        "host_peak_pages": srv.host_store.peak_pages,
        "host_peak_bytes": srv.host_store.peak_bytes,
        "kv_inventory": inv,
        "prefix_hit_rate_cold": stats["hit_rate"],
        "prefix_hit_rate_warm": warm_rate,
        "prefix_hit_rate_restored": s2["hit_rate"],
        "snapshot_pages": snap_pages, "restored_pages": restored,
        "token_agreement_vs_uninterrupted": float(agree),
        "tokens_per_s": sum(len(r.out) for r in reqs) / max(dt, 1e-9),
        "wall_s": dt,
        # SLO fields computed from the request-lifecycle trace (cold run)
        "goodput": slo["goodput"],
        "deadline_misses": slo["deadline_misses"],
        "ttft_p50_s": slo["ttft_p50_s"], "ttft_p99_s": slo["ttft_p99_s"],
        "tpot_p50_s": slo["tpot_p50_s"], "tpot_p99_s": slo["tpot_p99_s"],
        "trace_path": trace_path, "trace_events": n_events,
    }
    if verbose:
        print(f"[overcommit_serve] arch={arch} offered "
              f"{offered_pages} pages onto a {num_pages - 1}-page pool "
              f"({res['overcommit_ratio']:.1f}x overcommit, batch={batch})")
        print(f"  {len(reqs)} completed / 0 rejected; "
              f"{srv.preempt_count} preemptions (all resumed, "
              f"{res['realias_skipped_demotions']} victim-page demotions "
              f"skipped by re-aliasing), "
              f"{res['ooo_admissions']} out-of-order admissions")
        print(f"  tiers: device {inv['device_bytes'] / 2**10:.1f} KiB "
              f"{inv['device_by_container']} | host peak "
              f"{res['host_peak_pages']} pages "
              f"{res['host_peak_bytes'] / 2**10:.1f} KiB "
              f"({stats['demotions']} demotions, {stats['promotions']} "
              f"promotions)")
        print(f"  restart: {snap_pages} pages snapshotted, {restored} "
              f"restored; hit rate cold {res['prefix_hit_rate_cold']:.0%} "
              f"-> warm {warm_rate:.0%} -> restored {s2['hit_rate']:.0%}")
        print(f"  agreement vs uninterrupted run {agree:.1%}; no leaks")
        print(f"  slo: goodput {res['goodput']:.2f} "
              f"({res['deadline_misses']} deadline misses), ttft p50 "
              f"{1e3 * (res['ttft_p50_s'] or 0):.1f} ms / p99 "
              f"{1e3 * (res['ttft_p99_s'] or 0):.1f} ms, tpot p50 "
              f"{1e3 * (res['tpot_p50_s'] or 0):.2f} ms; {n_events} trace "
              f"events -> {os.path.basename(trace_path)}")
    save_json("overcommit_serve.json", res)
    return res


def mk_adapt_requests(vocab, sys_len, *, groups, per_group, reuse_groups,
                      seed=0):
    """Adaptation trace: ``groups`` tenants, each sharing its OWN system
    prompt across ``per_group`` requests — many distinct cached chains, so
    pool pressure must park cold ones — plus a late second wave re-issuing
    the first ``reuse_groups`` tenants' prompts verbatim (their pages are
    parked in the quant tier by then: the re-hits exercise the LOSSY
    promotion path, which is where the accuracy gate earns its keep)."""
    from repro.launch.serve import Request
    rng = np.random.default_rng(seed)
    prefixes = [rng.integers(0, vocab, sys_len).astype(np.int32)
                for _ in range(groups)]
    reqs, rid = [], 0
    wave2 = []
    for g in range(groups):
        for _ in range(per_group):
            prompt = np.concatenate(
                [prefixes[g], rng.integers(0, vocab, 5).astype(np.int32)])
            reqs.append(Request(rid, prompt, 4))
            rid += 1
            if g < reuse_groups:
                wave2.append(prompt)
    for prompt in wave2:
        reqs.append(Request(rid, prompt.copy(), 4, arrive_step=30))
        rid += 1
    return reqs


def run_adapt(*, arch="qwen2-72b", verbose=True, fast=False):
    """Online-precision-adaptation workload (--kv-adapt): the same
    many-tenant overcommitted trace served twice through an identical
    small pool + host tier, adapt OFF (byte-exact demote/drop relief
    only) vs adapt ON (cold cached pages REQUANTIZE one container step
    narrower into the bounded device tier before any host round trip).

    Gates (RAISES — the CI adapt-smoke step):
      * the off run must actually pressure the pool into host demotions
        (otherwise the comparison is vacuous);
      * the adapt run must requantize >= 1 page BEFORE its first host
        demotion (here: absorb the whole trace with ZERO demotions);
      * device-held tokens before the first host round trip must be
        >= 2x the off run's (pool capacity + peak parked tier pages);
      * the lm_precision accuracy gate must pass with ZERO violations:
        >= 0.9 overall token agreement vs the adapt-off reference and no
        single request below the per-request floor (requant error is
        bounded; a garbled request would hide inside a high average)."""
    from .lm_precision import accuracy_gate
    cfg = get_smoke_config(arch)
    params = init_model(jax.random.PRNGKey(0), cfg)
    groups, per_group, reuse = (5, 2, 2) if fast else (7, 2, 2)
    sys_len, page_size, max_len, batch = 8, 4, 64, 2
    num_pages = 1 + 9
    usable = num_pages - 1
    mk = lambda: mk_adapt_requests(cfg.vocab_size, sys_len, groups=groups,
                                   per_group=per_group, reuse_groups=reuse,
                                   seed=0)
    # fp pool: the requant ladder starts at fp -> int8 (deepen reaches
    # int4), so the one-step promotion error stays small; the tier byte
    # budget is 4x the pool, quoted in int4-page equivalents (= 2x the
    # pool in int8-parked pages)
    common = dict(batch_size=batch, max_len=max_len, page_size=page_size,
                  num_pages=num_pages, prefix_cache="on",
                  kv_offload="host", prefill_batch=1)

    def serve(**kw):
        srv = BatchedServer(cfg, params, **common, **kw)
        t0 = time.time()
        reqs = srv.run(mk())
        return srv, reqs, time.time() - t0

    off, reqs_off, dt_off = serve(kv_adapt="off")
    on, reqs_on, dt_on = serve(kv_adapt="on", adapt_pages=4 * usable)
    s_off = off.prefix_cache.stats()
    s_on = on.prefix_cache.stats()

    # --- gate: the trace genuinely overcommits the pool ---
    if s_off["demotions"] < 1:
        raise RuntimeError(
            f"adapt trace failed to pressure the pool: adapt-off run paid "
            f"{s_off['demotions']} host demotions (expected >= 1)")
    # --- gate: requantization relieves pressure BEFORE the host tier ---
    if s_on["requants"] < 1:
        raise RuntimeError("adapt run performed no requantizations")
    if s_on["demotions"] > 0 and not (s_on["requants_at_first_demotion"]
                                      or 0) >= 1:
        raise RuntimeError(
            f"adapt run demoted to host before its first requantization "
            f"(requants_at_first_demotion="
            f"{s_on['requants_at_first_demotion']})")
    # --- gate: >= 2x tokens held on device before the first round trip ---
    tier_peak = on.quant_tier.peak_pages
    tokens_off = usable * page_size
    tokens_on = ((usable + tier_peak) * page_size
                 if s_on["demotions"] == 0 else tokens_off)
    token_ratio = tokens_on / tokens_off
    if token_ratio < 2.0:
        raise RuntimeError(
            f"adaptation held only {token_ratio:.2f}x the off run's tokens "
            f"before the first host round trip (expected >= 2x: pool "
            f"{usable} pages + tier peak {tier_peak}, "
            f"{s_on['demotions']} demotions)")
    # --- gate: accuracy within tolerance (the off run round-trips bytes
    # exactly, so it IS the faithful reference) ---
    # allowed_below_floor: on a random-init smoke model one argmax tie
    # flip fully diverges a 4-token request — a bounded fraction of those
    # is tie chaos (see lm_precision.accuracy_gate), systematic garbling
    # still trips the overall floor
    gate = accuracy_gate([r.out for r in reqs_off],
                         [r.out for r in reqs_on],
                         min_agreement=0.9, request_floor=0.5,
                         allowed_below_floor=0.15)
    if not gate["passed"]:
        raise RuntimeError(
            f"accuracy gate: {gate['violations']} violations "
            f"(overall agreement {gate['agreement']:.1%}, "
            f"per-request min {min(gate['per_request']):.1%})")

    inv = on.kv_inventory()
    # --- leak gates: pool, host tier AND quant tier drain to zero ---
    for tag, s in [("off", off), ("on", on)]:
        leaked = s.release_prefix_cache()
        if leaked or s.allocator.num_free != s.allocator.num_usable:
            raise RuntimeError(
                f"refcount leak (adapt {tag}): {leaked} cache pages, "
                f"{s.allocator.num_usable - s.allocator.num_free} "
                f"unreturned")
        if s.host_store.num_pages != 0:
            raise RuntimeError(f"host-tier leak (adapt {tag}): "
                               f"{s.host_store.num_pages} pages parked")
    if on.quant_tier.num_pages != 0 or on.quant_tier.nbytes != 0:
        raise RuntimeError(
            f"quant-tier leak: {on.quant_tier.num_pages} pages / "
            f"{on.quant_tier.nbytes} bytes still parked after release")

    res = {
        "arch": arch, "requests": len(reqs_on), "batch": batch,
        "page_size": page_size, "device_pages": usable,
        "tenant_groups": groups,
        "requants": s_on["requants"], "deepens": s_on["deepens"],
        "tier_promotions": s_on["tier_promotions"],
        "tier_peak_pages": tier_peak,
        "tier_peak_bytes": on.quant_tier.peak_bytes,
        "requants_at_first_demotion": s_on["requants_at_first_demotion"],
        "demotions_off": s_off["demotions"],
        "demotions_on": s_on["demotions"],
        "evictions_off": s_off["evictions"],
        "evictions_on": s_on["evictions"],
        "tokens_before_host_off": tokens_off,
        "tokens_before_host_on": tokens_on,
        "token_ratio_vs_off": token_ratio,
        "accuracy_gate": {k: gate[k] for k in
                          ("agreement", "violations", "passed")},
        "kv_inventory": inv,
        "tokens_per_s_on": sum(len(r.out) for r in reqs_on) / max(dt_on,
                                                                  1e-9),
        "tokens_per_s_off": sum(len(r.out) for r in reqs_off) / max(dt_off,
                                                                    1e-9),
    }
    if verbose:
        print(f"[adapt_serve] arch={arch} {groups} tenants x {per_group} "
              f"reqs + {reuse * per_group} re-hits onto a {usable}-page "
              f"pool (batch={batch})")
        print(f"  adapt off: {s_off['demotions']} host demotions, "
              f"{s_off['evictions']} destructive evictions")
        print(f"  adapt on: {s_on['requants']} requants "
              f"({s_on['deepens']} deepens, {s_on['tier_promotions']} lossy "
              f"promotions), {s_on['demotions']} host demotions; tier peak "
              f"{tier_peak} pages / {on.quant_tier.peak_bytes / 2**10:.1f} "
              f"KiB {inv['tier_by_container']}")
        print(f"  tokens before first host round trip: {tokens_off} -> "
              f"{tokens_on} ({token_ratio:.1f}x)")
        print(f"  accuracy gate: agreement {gate['agreement']:.1%}, "
              f"{gate['violations']} violations; no leaks")
    save_json("adapt_serve.json", res)
    return res


def _append_trajectory(point):
    """BENCH_serve.json accumulates one point per bench run, so the serving
    perf trend is visible across PRs (the driver diffs it)."""
    traj = {"bench": "paged_serve", "trajectory": []}
    if os.path.exists(BENCH_TRAJECTORY):
        try:
            with open(BENCH_TRAJECTORY) as f:
                traj = json.load(f)
        except (OSError, json.JSONDecodeError):
            pass
    traj.setdefault("trajectory", []).append(point)
    with open(BENCH_TRAJECTORY, "w") as f:
        json.dump(traj, f, indent=1)
    return BENCH_TRAJECTORY


def run(*, arch="qwen2-72b", requests=10, batch=4, max_len=64, page_size=16,
        verbose=True, fast=False, workload="all"):
    if workload in ("prefix", "overcommit", "adapt"):
        fn = {"prefix": run_prefix, "overcommit": run_overcommit,
              "adapt": run_adapt}[workload]
        res = fn(arch=arch, verbose=verbose, fast=fast)
        point = {"when": time.strftime("%Y-%m-%d %H:%M:%S"), "arch": arch,
                 "fast": fast, "summary": {workload: res}}
        path = _append_trajectory(point)
        if verbose:
            print(f"  trajectory point appended to {os.path.basename(path)}")
        return res
    cfg = get_smoke_config(arch)
    params = init_model(jax.random.PRNGKey(0), cfg)
    if fast:   # CI smoke: one tiny iteration per config, no warmup pass
        requests, batch, max_len, page_size = 2, 2, 32, 8
    # pool sized to the traffic's worst concurrent demand, not batch*max_len:
    # this is the allocation the dense layout cannot shrink
    per_slot = -(-(MAX_PROMPT + max_len // 2) // page_size)
    num_pages = 1 + batch * per_slot
    common = dict(requests=requests, batch=batch, max_len=max_len,
                  warmup=not fast)
    rows = [
        bench_one(cfg, params, name="dense-fp32", kv_bits=0, page_size=0,
                  num_pages=None, **common),
        bench_one(cfg, params, name="paged-int8-step", kv_bits=8,
                  page_size=page_size, num_pages=num_pages,
                  prefill="stepwise", **common),
        bench_one(cfg, params, name="paged-int8", kv_bits=8,
                  page_size=page_size, num_pages=num_pages, **common),
        bench_one(cfg, params, name="paged-int4", kv_bits=4,
                  page_size=page_size, num_pages=num_pages, **common),
        bench_one(cfg, params, name="paged-int8-pallas", kv_bits=8,
                  page_size=page_size, num_pages=num_pages,
                  attn_impl="pallas", **common),
    ]
    base = rows[0]["kv_bytes_per_token_slot"]
    for r in rows:
        r["footprint_reduction_vs_fp32"] = base / r["kv_bytes_per_token_slot"]
    step, bucketed = rows[1], rows[2]
    summary = {
        "prefill_forwards_stepwise": step["prefill_forwards"],
        "prefill_forwards_bucketed": bucketed["prefill_forwards"],
        "prefill_forwards_reduction": (
            step["prefill_forwards"] / max(bucketed["prefill_forwards"], 1)),
        "prefill_latency_ms_stepwise": step["prefill_latency_ms"],
        "prefill_latency_ms_bucketed": bucketed["prefill_latency_ms"],
        "tokens_per_s": {r["name"]: r["tokens_per_s"] for r in rows},
        "kv_bytes_per_token_slot": {r["name"]: r["kv_bytes_per_token_slot"]
                                    for r in rows},
    }
    # shared-bucket batched-prefill stage: forward counts + TTFT sequential
    # vs batched (RAISES unless batching reduces forwards — the CI gate)
    summary["batched_prefill"] = run_batched_prefill(
        cfg, params, verbose=verbose, fast=fast)
    if verbose:
        print(f"[paged_serve] arch={arch} batch={batch} max_len={max_len} "
              f"page_size={page_size}")
        for r in rows:
            print(f"  {r['name']:17s} {r['tokens_per_s']:8.1f} tok/s  "
                  f"prefill {r['prefill_forwards']:3d} fwd "
                  f"{r['prefill_latency_ms']:7.1f} ms/req  "
                  f"{r['kv_bytes_per_token_slot']:7.1f} B/token-slot "
                  f"({r['footprint_reduction_vs_fp32']:4.1f}x vs fp32)  "
                  f"cache {r['kv_cache_mib']:6.2f} MiB")
        print(f"  prefill forwards: {summary['prefill_forwards_stepwise']} "
              f"(stepwise) -> {summary['prefill_forwards_bucketed']} "
              f"(bucketed), "
              f"{summary['prefill_forwards_reduction']:.1f}x fewer")
    if workload == "all":
        prefix = run_prefix(arch=arch, verbose=verbose, fast=fast)
        summary["prefix"] = {
            k: prefix[k] for k in
            ("prefix_hit_rate", "prefix_token_hit_rate",
             "prefill_forwards_off", "prefill_forwards_on",
             "prefill_forwards_saved", "prefill_forwards_reduction",
             "cow_copies", "evictions", "kv_at_rest_bytes",
             "profile_bytes_vs_int8", "token_agreement_on_vs_off")}
        over = run_overcommit(arch=arch, verbose=verbose, fast=fast)
        summary["overcommit"] = {
            k: over[k] for k in
            ("overcommit_ratio", "completed", "rejected", "preemptions",
             "resumes", "realias_skipped_demotions", "ooo_admissions",
             "demotions", "promotions",
             "host_peak_pages", "kv_inventory",
             "prefix_hit_rate_restored", "prefix_hit_rate_warm",
             "token_agreement_vs_uninterrupted",
             "goodput", "deadline_misses", "ttft_p50_s", "ttft_p99_s",
             "tpot_p50_s", "tpot_p99_s")}
    out = {"arch": arch, "batch": batch, "max_len": max_len,
           "page_size": page_size, "rows": rows, "summary": summary}
    save_json("paged_serve.json", out)
    point = {"when": time.strftime("%Y-%m-%d %H:%M:%S"), "arch": arch,
             "fast": fast, "summary": summary}
    path = _append_trajectory(point)
    if verbose:
        print(f"  trajectory point appended to {os.path.basename(path)}")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-72b")
    ap.add_argument("--requests", type=int, default=10)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--fast", action="store_true",
                    help="CI smoke: tiny trace, single iteration per config")
    ap.add_argument("--workload",
                    choices=["all", "mixed", "prefix", "overcommit",
                             "adapt"],
                    default="all",
                    help="mixed = the PR-2 mixed-length trace; prefix = the "
                         "shared-system-prompt trace (prefix cache on/off, "
                         "per-layer profile, refcount-leak gate); "
                         "overcommit = offered pages >> device pool through "
                         "the tiered store (offload + preemption + restart "
                         "parity; refcount/host-leak gates); adapt = the "
                         "online-requantization trace (--kv-adapt on vs "
                         "off: requant-before-demote ordering, >=2x tokens "
                         "before the first host round trip, lm_precision "
                         "accuracy gate)")
    args = ap.parse_args(argv)
    run(arch=args.arch, requests=args.requests, batch=args.batch,
        max_len=args.max_len, page_size=args.page_size, fast=args.fast,
        workload=args.workload)


if __name__ == "__main__":
    main()
