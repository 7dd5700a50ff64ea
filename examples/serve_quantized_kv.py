"""Serving example: continuous-batching decode with quantized + paged KV.

The KV cache is the dominant decode traffic (paper §2.4's "data" at batch
scale). Three levers stack here:

* per-layer data bits (int8 Q(2,6) / int4 Q(2,2)) shrink every stored token,
* the paged layout (--page-size in launch.serve) allocates cache by pages
  actually used instead of batch * max_len slabs, and frees them per
  request,
* the serving hot path: **bucketed prefill** admits prompts in power-of-two
  chunks written straight into the paged pool (O(prompt/bucket) forwards
  instead of O(prompt) whole-batch steps; `prefill_bucket` caps the chunk,
  so at most log2(bucket)+1 prefill programs ever compile per row count);
  **multi-request batched prefill** (`prefill_batch` / ``--prefill-batch``)
  stacks same-bucket prompts admitted in one scheduler cycle into single
  ``[n_reqs, bucket]`` forwards with per-row page tables and valid lengths
  — fewer forwards and fewer compilations when traffic arrives in waves
  (0 = auto: the batch size; with the prefix cache on, same-wave prompts
  sharing a prefix are deduplicated by the prefix-aware wave dedupe below
  instead of falling back to sequential admission); and **unified
  attention routing** (`attn_impl="pallas"`): ONE variable-length Pallas chunk
  kernel (`kernels.paged_kv_attention`, scalar-prefetch page tables,
  per-row causal masking against cache positions) serves BOTH chunked
  prefill (S > 1) and decode (S = 1 — the kernel's single-row special
  case); interpret-mode on CPU, compiled on TPU.

Which modes remain **bitwise-reference**: `attn_impl="gather"` (jnp pool
reads, identical accumulation order to the dense cache) for every chunk
shape, and `prefill="stepwise"` (slot-granular whole-batch steps). Batched
prefill is bitwise-identical to sequential bucketed prefill (rows are
independent sequences writing disjoint pages — asserted in
tests/test_serve_fast.py), so it is NOT a reference/fast split; the pallas
kernel's per-page online softmax reorders accumulation, so pallas ==
gather only within float tolerance.

Two further levers ride the same paged pool:

* **shared-prefix page cache** (``prefix_cache="on"`` / ``--prefix-cache
  on``): requests sharing a system prompt alias its full pages (refcounted;
  freed only at refcount zero), copy-on-write the page where they diverge
  mid-page, and prefill only their suffix. Unreferenced cached prefixes are
  LRU-evicted under pool pressure; ``release_prefix_cache()`` drops them
  all and returns the leak count (0 = clean).
* **per-layer precision profiles** (``--kv-profile policy.json``, see
  examples/serve_policy_profile.py) store each layer's pages in the
  container its policy data format needs — the paper's per-layer result
  applied to serving HBM; ``--kv-scale page`` swaps the static Q(I,F) grid
  for dynamic per-page max-abs calibration.

Since PR 4 the bounded device pool is backed by a **tiered page store**:

* ``kv_offload="host"`` (``--kv-offload host``) adds a host-memory tier
  (``core.page_store``): pool pressure *demotes* unreferenced cached
  prefix pages to host numpy instead of destroying them, and admission
  *promotes* matched host pages back before aliasing. Demoted bytes stay
  in their packed int4/int8/fp containers, so offload traffic scales with
  the precision policy. ``host_pages=N`` (``--host-pages``) bounds the
  tier; when it fills, cold host pages are dropped LRU and eviction falls
  back to the PR-3 destructive path.
* ``sched="slo"`` (``--sched``) replaces FIFO admission: the queue is
  ordered by (priority, deadline_step, arrival), up to ``admit_window``
  requests may be admitted past a deferred head (no more head-of-line
  block), and a strictly more urgent request may PREEMPT a running one —
  the victim's written pages demote to the host tier, it re-queues, and
  resume promotes the pages back and continues decoding
  bitwise-identically (no re-prefill).
* ``snapshot_prefix_cache(path)`` / ``restore_prefix_cache(path)``
  (``--prefix-snapshot``) persist cached prefix chains across server
  restarts. The snapshot format is **profile-key-namespaced like the
  trie** — every chain carries its KV quantization key, so an int8
  snapshot can never back an int4 server — and a pool-geometry signature
  rejects arch mismatches. Restored pages land in the HOST tier (zero
  device pages until a hit promotes them).

Since PR 6 pool pressure can *narrow* pages before it evicts them —
**online precision adaptation** (``kv_adapt="on"`` / ``--kv-adapt on``;
needs the paged pool + prefix cache):

* the eviction chain becomes requantize -> host-demote -> drop: a cold,
  unreferenced cached prefix page is re-quantized one container step
  (fp -> int8 -> int4; fresh per-page max-abs scales, stale tail slots
  masked out of calibration) and PARKED in a device-resident quant tier
  instead of paying a host round trip. ``--kv-adapt-pages`` bounds the
  tier in int4-floor page-byte units (0 = auto: the pool size);
  ``--kv-adapt-floor {4,8}`` sets the narrowing floor (8 stops at int8,
  e.g. when accuracy headroom is thin).
* under continued pressure parked pages *deepen* toward the floor
  (int8 -> int4) to make byte room; only when the tier is genuinely full
  does eviction fall back to the PR-4 host tier and then the PR-3
  destructive drop.
* a later hit promotes a parked page back: the narrowed grid widens
  exactly into the pool's native container. The narrowing rounding loss
  is permanent — ``benchmarks.lm_precision.accuracy_gate`` prices it, and
  the ``--workload adapt`` bench gates >= 0.9 token agreement against the
  byte-exact adapt-off reference.
* ``OutOfPagesError.requantizable`` reports how many cold cached pages
  could still be narrowed right now (the operator hint that --kv-adapt
  headroom exists). With ``--kv-adapt off`` all of the above is bitwise
  inert (asserted in tests/test_serve_fast.py).

**Prefix-aware wave dedupe** makes ``--prefill-batch`` compose with
``--prefix-cache``: prompts admitted in the same wave that share a page-
aligned prefix elect a leader; followers wait, then alias the leader's
freshly written pages (refcounted, like a cache hit) and prefill only their
tail — fewer prefill forwards than sequential admission even when the
shared prefix was never cached before.

Since PR 8 the whole serving path is **observable** (``--metrics on``;
``runtime.telemetry``):

* one injectable :class:`~repro.runtime.telemetry.MetricsRegistry` per
  server threads through allocator, scheduler, prefix cache and host/quant
  tiers — every legacy counter attribute (``prefill_forwards``,
  ``preempt_count``, ``prefix_cache.hits``, …) is now registry-backed
  (``serve.*`` / ``sched.*`` / ``prefix.*`` / ``alloc.*`` / ``kv.*``
  names), with live ``kv.*`` gauges mirroring ``kv_inventory()`` exactly.
  ``registry.reset()`` / ``checkpoint()`` / ``since()`` are the sanctioned
  warmup/measurement boundary (benchmarks no longer hand-zero attributes).
* a span :class:`~repro.runtime.telemetry.Tracer` records the request
  lifecycle — arrive -> admit/defer/reject -> prefill chunks -> decode
  spans -> preempt/offload/resume -> finish — on a monotonic clock and
  exports **Chrome trace-event JSON** via ``--trace-out trace.json``: load
  it at https://ui.perfetto.dev (drag-and-drop) or chrome://tracing; tid 0
  is the engine track, tid 1+rid one track per request. The same records
  reduce to SLO metrics (``tracer.slo_summary()``): exact p50/p99 **TTFT**
  and **TPOT**, and **goodput** — the fraction of offered requests that
  finished by their ``deadline_step`` (printed after every ``--metrics
  on`` run; the overcommit bench appends them to BENCH_serve.json).
* ``--metrics-out metrics.jsonl`` streams a ``registry.snapshot()`` JSONL
  line every ``--metrics-every`` decode steps (counters + gauges +
  histogram summaries) for dashboard scraping.
* ``--metrics off`` (default) is the NullTracer path: telemetry lives
  entirely outside jitted code, so off is bitwise-identical to the
  pre-telemetry server (asserted in tests/test_telemetry.py).

Since PR 9 the telemetry closes the loop — **traffic at scale**
(``core.traffic`` + ``--predictor on`` / ``--pager-async on``):

* ``core.traffic.generate_trace`` expands a seeded :class:`TraceConfig`
  into a deterministic open-loop arrival stream — Poisson or bursty
  (2-state MMPP) arrivals, heavy-tailed lognormal prompt/output lengths,
  multi-tenant mixes with per-tenant priority, deadline slack, and
  Zipf-weighted shared-prefix pools. Equal configs yield byte-identical
  traces across processes (``trace_fingerprint``), so both arms of an
  A/B replay exactly the same offered load.
* ``SLOMonitor`` (``runtime.telemetry``) reduces the live run to rolling
  ``slo.*`` gauges — windowed goodput, TTFT/TPOT p50/p99 over the last N
  finished requests, queue-depth / arrival-rate / TPOT EWMAs — streamed
  with every ``--metrics-out`` snapshot line.
* ``--predictor on`` (needs ``--sched slo``) consults an online logistic
  **deadline-miss predictor** every admission cycle: features are queue
  depth, arrival-rate EWMA, free-page headroom, prefill debt, occupancy,
  and TPOT slowdown; the risk feeds a peak-hold hazard that resizes the
  SPECULATIVE share of the batch (no-deadline admissions throttle to 1
  then 0 as hazard crosses the gate) — deadlined requests are never
  gated. Retired deadlined requests SGD-update the weights online. On
  the bursty overload bench (``benchmarks.traffic --mode serve``) the
  gate lifts goodput 0.79 -> 0.91 at 100% token agreement.
* ``--pager-async on`` (needs ``--kv-offload host``) double-buffers
  demote/offload transfers: ``copy_to_host_async`` slices are enqueued
  at eviction time and drained at decode-span boundaries, so transfer
  time hides behind decode — ``pager.demote``/``pager.offload`` spans
  overlap ``decode_span`` on the Chrome trace's pager track.

Since PR 10 serving scales UP and OUT — **sharded multi-replica paged
serving** (``launch.frontend`` + ``--tp``):

* ``--tp N`` (``launch.mesh.make_serving_mesh``) makes one server a
  tensor-parallel replica over a ``(devices//N, N)`` data x model mesh:
  weights land TP-only via ``parallel.sharding.param_shardings(...,
  inference=True)`` (no per-token FSDP gathers) and the paged KV pool
  becomes a sharded pytree via ``paged_pool_shardings`` — page grids
  ``(NP, ps, KV, hdw)`` shard their KV-heads axis over "model" (int4
  lane-packing runs along head_dim, so packed lanes stay whole per
  shard), per-page scales replicate. Token streams are identical to the
  single-device server (CI asserts this on virtual host devices —
  tests/test_serving_mesh.py).
* :class:`~repro.launch.frontend.ReplicaFrontend` scales OUT: it consumes
  a ``core.traffic`` arrival stream and routes each request to one of N
  replica servers on ONE shared decode-step clock. Routing is
  prefix-affinity first — requests carrying a shared system prompt stick
  to the replica that prefilled it (pages keep being re-aliased instead
  of re-prefilled N times) — and yields to the least-loaded replica only
  past a load margin, where load = the replica's own ``slo.*`` gauges
  (queue-depth EWMA) + slot occupancy - paged-pool headroom.
* the :class:`~repro.launch.frontend.SharedPrefixStore` closes the pool:
  after each global round, every replica's cached chains publish into a
  cross-replica store on the PR-4 snapshot wire format (profile-key +
  pool-geometry namespaced) and install into the other replicas' HOST
  tiers — a hot system prompt prefilled once is aliasable by all, at
  zero device pages until a hit promotes it.
* the identity contract: a 1-replica frontend IS the plain server —
  bitwise-identical token streams at kv-bits {0, 8, 4} (asserted in
  tests/test_frontend.py; delivering arrivals at the shared clock caps
  decode spans exactly like a pending request does). On the bursty
  4x-overload trace (``benchmarks.traffic --mode replicas``) 2 replicas
  lift aggregate goodput 0.79 -> 1.00 at 100% token agreement, with the
  affinity map absorbing the shared-prefix tenants and the store moving
  the hot chains across the pool.
* the paged attention kernel fetches whole ``(ps, KV, hdw)`` pages per
  grid step (``ops.paged_kv_attention_chunk``), the block shape the TPU
  compiler accepts at every head count.

Error/failure semantics: paged admission preflights a request's WORST-CASE
page demand (prompt + max_new; with prefix sharing, only the non-shared
suffix plus one promotion page per matched host page is charged). A
request that can never fit the pool is rejected with
``core.paged_kv.OutOfPagesError`` carrying the full inventory
(needed/free/usable plus written vs reserved-but-unwritten vs
evictable-cached vs host-tier pages): FIFO mode records it on
``request.error``, SKIPS it (the queue behind it keeps being served — the
old behavior stalled), and re-raises after the run drains; SLO mode only
records it. A request that merely has to wait is deferred. Preemption
requires the host tier (victim pages must survive); with the tier full
and nothing droppable, preemption simply does not fire and the request
waits like before. The free list can never empty mid-prefill.

Prints token agreement between the runs and the cache footprint ratios.

Run:  PYTHONPATH=src python examples/serve_quantized_kv.py
"""
import jax
import numpy as np

from repro.configs.registry import get_smoke_config
from repro.core.paged_kv import OutOfPagesError
from repro.launch.serve import BatchedServer, Request
from repro.models.transformer import init_model


def cache_bytes(caches):
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(caches))


def agreement(a_reqs, b_reqs):
    return np.mean([np.mean(np.asarray(a.out) == np.asarray(b.out))
                    for a, b in zip(a_reqs, b_reqs)])


def main():
    cfg = get_smoke_config("qwen2-72b")
    params = init_model(jax.random.PRNGKey(0), cfg)

    def mk():
        rng = np.random.default_rng(0)
        return [Request(i, rng.integers(0, cfg.vocab_size, 10)
                        .astype(np.int32), 12) for i in range(8)]

    print("=== fp32 dense KV cache ===")
    srv_fp = BatchedServer(cfg, params, batch_size=4, max_len=96)
    reqs_fp = srv_fp.run(mk(), verbose=True)

    print("=== int8 Q(2,6) dense KV cache ===")
    srv_q8 = BatchedServer(cfg, params, batch_size=4, max_len=96, kv_bits=8)
    reqs_q8 = srv_q8.run(mk(), verbose=True)

    print("=== int4 Q(2,2) paged KV cache (page_size=16, bucketed "
          "prefill) ===")
    srv_p4 = BatchedServer(cfg, params, batch_size=4, max_len=96, kv_bits=4,
                           page_size=16, num_pages=1 + 4 * 2,
                           prefill_bucket=16)
    reqs_p4 = srv_p4.run(mk(), verbose=True)
    print(f"  bucketed prefill: {srv_p4.prefill_forwards} chunk forwards for "
          f"{srv_p4.prefill_tokens} prompt tokens "
          f"(stepwise would take {srv_p4.prefill_tokens - 8} whole-batch "
          f"steps)")

    print("=== int8 paged + unified Pallas attention (prefill + decode "
          "through one chunk kernel; interpret on CPU) ===")
    srv_pl = BatchedServer(cfg, params, batch_size=4, max_len=96, kv_bits=8,
                           page_size=16, attn_impl="pallas")
    reqs_pl = srv_pl.run(mk(), verbose=True)

    print("=== int8 paged + batched prefill (same-bucket prompts stacked "
          "into one [n, bucket] forward) ===")
    srv_bp = BatchedServer(cfg, params, batch_size=4, max_len=96, kv_bits=8,
                           page_size=16, prefill_batch=4)
    srv_sp = BatchedServer(cfg, params, batch_size=4, max_len=96, kv_bits=8,
                           page_size=16, prefill_batch=1)
    reqs_bp = srv_bp.run(mk(), verbose=True)
    reqs_sp = srv_sp.run(mk())
    print(f"  prefill forwards: {srv_sp.prefill_forwards} sequential -> "
          f"{srv_bp.prefill_forwards} batched "
          f"(token agreement {agreement(reqs_sp, reqs_bp):.1%}; "
          f"bitwise-identical under single-threaded XLA)")

    fp_b, q8_b = cache_bytes(srv_fp.caches), cache_bytes(srv_q8.caches)
    p4_b = cache_bytes(srv_p4.caches)
    print(f"\ncache footprint: fp32={fp_b / 2**20:.2f} MiB  "
          f"int8={q8_b / 2**20:.2f} MiB ({q8_b / fp_b:.2f}x)  "
          f"paged-int4={p4_b / 2**20:.2f} MiB ({p4_b / fp_b:.2f}x; "
          f"pool sized to live pages, not max_len)")
    print(f"token agreement fp vs int8-KV:        "
          f"{agreement(reqs_fp, reqs_q8):.1%}")
    print(f"token agreement fp vs paged-int4-KV:  "
          f"{agreement(reqs_fp, reqs_p4):.1%}")
    print(f"token agreement fp vs pallas-decode:  "
          f"{agreement(reqs_fp, reqs_pl):.1%}")
    print(f"pages free after run: {srv_p4.allocator.num_free}/"
          f"{srv_p4.allocator.num_pages - 1} (all requests released)")

    print("=== int8 paged + shared-prefix page cache ===")
    rng = np.random.default_rng(3)
    sys_prompt = rng.integers(0, cfg.vocab_size, 22).astype(np.int32)
    mk_shared = lambda: [
        Request(i, np.concatenate([sys_prompt,
                                   np.random.default_rng(i).integers(
                                       0, cfg.vocab_size, 4)
                                   .astype(np.int32)]), 10)
        for i in range(8)]
    srv_px = BatchedServer(cfg, params, batch_size=4, max_len=96, kv_bits=8,
                           page_size=16, prefix_cache="on")
    srv_px.run(mk_shared(), verbose=True)
    st = srv_px.prefix_cache.stats()
    print(f"  {st['hits']}/{st['lookups']} prompts hit the cache "
          f"({st['hit_tokens']} tokens aliased, {st['cow_copies']} CoW "
          f"copies); {srv_px.prefill_forwards_saved} prefill forwards saved")
    print(f"  release_prefix_cache() -> {srv_px.release_prefix_cache()} "
          f"leaked pages (0 = every refcount balanced)")

    print("=== tiered page store: host offload + SLO preemption + "
          "restart ===")
    import os
    import tempfile
    mk_tiered = lambda: [
        Request(0, np.concatenate([sys_prompt, np.arange(3, dtype=np.int32)]),
                16, priority=0),                       # long, low priority
        Request(1, np.concatenate([sys_prompt, np.arange(2, dtype=np.int32)]),
                6, priority=5, arrive_step=4, deadline_step=24),  # urgent
        Request(2, np.concatenate([sys_prompt, np.arange(4, dtype=np.int32)]),
                8, priority=1, arrive_step=10),
    ]
    tiered_kw = dict(batch_size=1, max_len=96, kv_bits=8, page_size=16,
                     num_pages=5,                      # 4 usable: too small
                     prefix_cache="on", kv_offload="host", sched="slo")
    srv_t = BatchedServer(cfg, params, **tiered_kw)
    reqs_t = srv_t.run(mk_tiered(), verbose=True)
    print(f"  {srv_t.preempt_count} preemption(s), {srv_t.resume_count} "
          f"resume(s), {srv_t.realias_skipped} victim-page demotion(s) "
          f"skipped by re-aliasing still-resident prefix nodes; "
          f"every request completed: "
          f"{all(r.done and r.error is None for r in reqs_t)}")
    print(f"  kv inventory (device/host split): {srv_t.kv_inventory()}")
    snap = os.path.join(tempfile.mkdtemp(), "prefix_pages.npz")
    n = srv_t.snapshot_prefix_cache(snap)
    srv_t2 = BatchedServer(cfg, params, **tiered_kw)
    m = srv_t2.restore_prefix_cache(snap)
    srv_t2.run(mk_tiered())
    s2 = srv_t2.prefix_cache.stats()
    print(f"  restart: {n} pages snapshotted -> {m} restored to the host "
          f"tier; hit rate after restore {s2['hit_rate']:.0%} "
          f"({s2['promotions']} host pages promoted on demand)")
    for s in (srv_t, srv_t2):
        assert s.release_prefix_cache() == 0 and s.host_store.num_pages == 0

    print("=== online precision adaptation: requantize before demote ===")
    rng = np.random.default_rng(9)
    mk_adapt = lambda: [
        Request(i, np.concatenate([
            np.asarray(tenant, np.int32),
            rng.integers(0, cfg.vocab_size, 3).astype(np.int32)]), 8)
        for i, tenant in enumerate(
            rng.integers(0, cfg.vocab_size, (4, 18)))]
    srv_ad = BatchedServer(cfg, params, batch_size=2, max_len=96, kv_bits=8,
                           page_size=16, num_pages=6,   # 5 usable: too small
                           prefix_cache="on", kv_offload="host",
                           kv_adapt="on")
    reqs_ad = srv_ad.run(mk_adapt(), verbose=True)
    st = srv_ad.prefix_cache.stats()
    print(f"  eviction chain requant->demote->drop: {st['requants']} "
          f"page(s) narrowed in place, {st['deepens']} deepened toward the "
          f"int4 floor, {st['demotions']} host demotion(s) "
          f"(requants before the first: {st['requants_at_first_demotion']}), "
          f"{st['tier_promotions']} parked page(s) promoted on a later hit")
    print(f"  kv inventory (device/host/tier): {srv_ad.kv_inventory()}")
    print(f"  every request completed: "
          f"{all(r.done and r.error is None for r in reqs_ad)}")
    assert srv_ad.release_prefix_cache() == 0
    assert srv_ad.quant_tier.num_pages == 0
    assert srv_ad.host_store.num_pages == 0

    print("=== telemetry: lifecycle trace + SLO goodput (--metrics on) ===")
    srv_tm = BatchedServer(cfg, params, batch_size=1, max_len=96, kv_bits=8,
                           page_size=16, num_pages=5, prefix_cache="on",
                           kv_offload="host", sched="slo", metrics="on")
    srv_tm.run(mk_tiered(), verbose=True)
    slo = srv_tm.tracer.slo_summary()
    print(f"  slo_summary: goodput {slo['goodput']:.2f} "
          f"({slo['finished']}/{slo['requests']} finished, "
          f"{slo['deadline_misses']} deadline misses, "
          f"{slo['preemptions']} preemptions)")
    print(f"  ttft p50 {1e3 * slo['ttft_p50_s']:.1f} ms / p99 "
          f"{1e3 * slo['ttft_p99_s']:.1f} ms; "
          f"tpot p50 {1e3 * (slo['tpot_p50_s'] or 0):.2f} ms")
    m = srv_tm.metrics
    print(f"  registry: serve.decode_steps="
          f"{m.counter('serve.decode_steps').value} "
          f"serve.preempt_count={m.counter('serve.preempt_count').value} "
          f"prefix.hits={m.counter('prefix.hits').value} "
          f"kv.device_bytes={m.gauge('kv.device_bytes').value} "
          f"kv.host_pages={m.gauge('kv.host_pages').value}")
    trace_out = os.path.join(tempfile.mkdtemp(), "serve_trace.json")
    srv_tm.tracer.export_chrome(trace_out)
    print(f"  {len(srv_tm.tracer.events)} trace events -> {trace_out} "
          f"(load at https://ui.perfetto.dev or chrome://tracing)")
    assert srv_tm.release_prefix_cache() == 0

    print("=== traffic harness: seeded bursty trace -> SLO gauges + "
          "deadline-miss predictor ===")
    from repro.core.traffic import TenantSpec, TraceConfig, generate_trace, \
        trace_fingerprint
    trace = generate_trace(TraceConfig(
        seed=5, horizon=24, rate=0.1, process="bursty", burst_rate=1.2,
        p_enter_burst=0.2, p_exit_burst=0.3, vocab_size=cfg.vocab_size,
        tenants=(
            TenantSpec("chat", weight=0.7, priority=5, deadline_slack=6,
                       prompt_mean=8, prompt_cap=14, max_new_mean=3,
                       max_new_cap=5, shared_prefix_len=8, prefix_pool=2),
            TenantSpec("batch", weight=0.3, max_new_mean=8, max_new_cap=12),
        )))
    print(f"  trace: {len(trace.requests)} arrivals over "
          f"{trace.config.horizon} steps, burst overload "
          f"{trace.overload_ratio(batch_size=2):.1f}x sustainable, "
          f"fingerprint {trace_fingerprint(trace)[:12]}... "
          f"(same seed = same stream, any process)")
    srv_tr = BatchedServer(cfg, params, batch_size=2, max_len=96, kv_bits=8,
                           page_size=16, num_pages=9, prefix_cache="on",
                           kv_offload="host", sched="slo", preempt=False,
                           metrics="on", predictor="on", pager_async="on")
    srv_tr.run([Request(r.rid, np.array(r.prompt), r.max_new,
                        priority=r.priority, deadline_step=r.deadline_step,
                        arrive_step=r.arrive_step)
                for r in trace.requests], verbose=True)
    slo = srv_tr.tracer.slo_summary()
    gauges = {k: v for k, v in srv_tr.metrics.snapshot()["gauges"].items()
              if k.startswith("slo.")}
    print(f"  windowed slo.* gauges (live during the run, snapshot-streamed "
          f"via --metrics-out): goodput "
          f"{gauges['slo.window_goodput']:.2f} over "
          f"{gauges['slo.window_requests']:.0f} reqs, queue EWMA "
          f"{gauges['slo.queue_depth_ewma']:.1f}, arrival EWMA "
          f"{gauges['slo.arrival_rate_ewma']:.2f}/step")
    print(f"  predictor: {srv_tr.predictor.gated} speculative admission(s) "
          f"gated, {srv_tr.predictor.updates} online SGD update(s), final "
          f"hazard {srv_tr.predictor.hazard:.2f}; async pager "
          f"{srv_tr.pager.demotions} demotion(s) overlapped with decode")
    print(f"  exact post-hoc goodput {slo['goodput']:.2f} "
          f"({slo['deadline_misses']} deadline misses / {slo['requests']} "
          f"offered)")
    assert srv_tr.release_prefix_cache() == 0

    print("=== multi-replica frontend: prefix-affinity routing + shared "
          "prefix store ===")
    from repro.launch.frontend import (ReplicaFrontend, aggregate_goodput,
                                       make_replicas, requests_from_trace)
    common = dict(batch_size=2, max_len=96, kv_bits=8, page_size=16,
                  num_pages=9, prefix_cache="on", kv_offload="host",
                  sched="slo", preempt=False, metrics="on",
                  pager_async="on")
    goodput = {}
    for n in (1, 2):
        fe = ReplicaFrontend(make_replicas(n, cfg, params, **common))
        reqs, keys = requests_from_trace(trace)   # same offered stream
        fe.run(reqs, keys)
        goodput[n] = aggregate_goodput(reqs)
        if n == 2:
            c = fe.metrics.snapshot()["counters"]
            print(f"  2 replicas: routed "
                  f"[{c.get('frontend.routed_replica0', 0)}, "
                  f"{c.get('frontend.routed_replica1', 0)}], "
                  f"{c.get('frontend.affinity_hits', 0)} affinity hits, "
                  f"{c.get('frontend.rebalanced', 0)} rebalances, "
                  f"{c.get('frontend.shared_prefix_pages', 0)} prefix "
                  f"pages exchanged through the shared store")
    print(f"  aggregate goodput on the same trace: 1 replica "
          f"{goodput[1]:.2f} (== the plain server, bitwise) -> 2 replicas "
          f"{goodput[2]:.2f}")

    # admission preflight: a request whose prompt + max_new can never be
    # backed by the pool is rejected with counts — recorded on the request
    # and (FIFO mode) re-raised AFTER serviceable traffic drains, so a
    # too-large head no longer starves the queue behind it
    tiny = BatchedServer(cfg, params, batch_size=2, max_len=96, kv_bits=8,
                         page_size=16, num_pages=4)   # 3 usable pages
    ok_req = Request(100, np.arange(8, dtype=np.int32), 8)
    try:
        tiny.run([Request(99, np.arange(40, dtype=np.int32), 50), ok_req])
    except OutOfPagesError as e:
        print(f"\nOutOfPagesError (expected): {e}")
    print(f"request behind the rejected head still served: {ok_req.done} "
          f"({len(ok_req.out)} tokens)")


if __name__ == "__main__":
    main()
