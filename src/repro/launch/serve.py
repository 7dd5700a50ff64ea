"""Batched serving launcher: continuous-batching prefill + decode with an
optionally quantized, optionally **paged** KV cache.

A REQUEST = (prompt token ids, max_new_tokens). The server packs up to
--batch-size requests into fixed slots and decodes step-by-step with
**per-slot positions**: each slot tracks its own length, finished slots are
refilled from the queue (continuous batching at step granularity), and idle
slots harmlessly rewrite a scratch location.

Two cache layouts:

* dense (default): one (batch, max_len, ...) slab per layer — HBM scales
  with the worst-case request even for short traffic.
* paged (--page-size N): per-layer page pools + a per-slot page table
  (core.paged_kv). Pages are allocated as a request grows and freed when it
  completes, so cache HBM scales with live tokens, not max_len. KV bits
  apply inside the page container: --kv-bits 8 stores int8 pages, --kv-bits
  4 lane-packs a 4-bit grid into int32 words (~8x smaller at rest than
  fp32). --num-pages sizes the shared pool (default: full capacity).
  Admission preflights the pool: a request whose worst-case page demand can
  never fit raises ``OutOfPagesError`` (with counts) up front; one that
  merely has to wait for live requests to finish is deferred in the queue.

The serving **hot path** is built around three ideas:

* **Bucketed chunked prefill** (paged, attention-only archs): prompts are
  admitted through ``launch.steps.make_chunk_prefill_step`` — one forward
  per power-of-two prompt chunk (--prefill-bucket caps the bucket), padded
  and masked, writing straight into the paged pool — instead of O(prompt)
  whole-batch decode steps. ``--prefill stepwise`` keeps the slot-granular
  reference path (bitwise-identical results; see tests/test_serve_fast.py).
* **Multi-request batched prefill** (--prefill-batch): one admission cycle
  may admit several waiting prompts (the scheduler's admit window surfaces
  them), and their same-bucket chunks STACK into single [n_reqs, bucket]
  prefill forwards with per-row page tables, start positions, and valid
  lengths — amortizing both forward count and per-bucket compilations
  across concurrent admissions. Rows are independent sequences writing
  disjoint pages, so batched == sequential bitwise (tests assert it).
  An ``OutOfPagesError`` mid-batch rolls back every partially admitted
  row before surfacing.
* **Prefix-aware batched prefill** (wave dedupe): inside one admission
  wave, a prompt sharing page-aligned full chunks with an earlier same-wave
  prompt WAITS until that leader has written through the shared span, then
  increfs the leader's pages into its own table instead of re-running their
  forwards — so --prefill-batch composes with --prefix-cache (auto batching
  no longer falls back to sequential admission under the prefix cache).
* **Unified kernel-routed attention** (--attn-impl pallas): decode AND
  chunked prefill attention run through ONE variable-length
  ``kernels.paged_kv_attention`` chunk kernel (scalar-prefetch DMA over
  the page table, dequant in VMEM, per-row causal masking against cache
  positions; interpret-mode on CPU, compiled on TPU) — there is no jnp
  fallback on the S>1 path. The default ``gather`` impl stays the
  bitwise-reference mode for every chunk shape.
* **Batched host<->device traffic**: decode advances in "runs" between slot
  events (admission/completion, both predictable from token counts), feeding
  next-token ids device-to-device and fetching generated tokens
  asynchronously at run boundaries — no per-token ``.at[slot].set`` and no
  blocking per-step ``np.array`` round-trips.
* **Shared-prefix page cache** (--prefix-cache on): a page-granular radix
  index over prompt tokens (``core.prefix_cache``). Admission aliases fully
  matched pages into the new slot's page table (refcounted — sharing is
  pure indirection, the kernels never know), copies-on-write the page where
  the prompt diverges mid-page, charges reservation accounting only for the
  non-shared suffix, and prefills only that suffix: prefix hits turn
  O(prompt/bucket) admission forwards into O(suffix/bucket). Unreferenced
  cached prefixes are LRU-evicted under pool pressure.
* **Per-layer KV precision profiles** (--kv-profile policy.json): the
  paper's central result — precision tolerance varies per layer — applied
  to the serving pool. Each layer's pages live in the container its policy
  data format needs (int4 / int8 / float), so a ``core.search`` policy
  drives the at-rest KV footprint directly; uniform --kv-bits stays the
  degenerate profile. Contiguous same-container layer runs still ride
  ``lax.scan`` (--kv-profile-scan unroll forces the unrolled reference).
  --kv-scale page additionally calibrates per-page max-abs dequant scales
  at write time instead of the static Q(I,F) grid.
* **Online precision adaptation** (--kv-adapt on): under pool pressure,
  cold cached prefix pages are REQUANTIZED one container step narrower
  (fp -> int8 -> int4, freshly calibrated per-page max-abs scales) into a
  bounded device-byte tier (``core.page_store.QuantTierStore``) *before*
  any host demotion — the paper's within-network precision-tolerance
  result applied temporally: a page's precision decays with coldness
  instead of being fixed at admission. Eviction order becomes
  requant -> host demote -> destructive drop; a re-hit widens the page
  back into the pool (the one-step quantization error is the price of
  having kept it on device). --kv-adapt-floor bounds the ladder (4 or 8
  data bits; per-layer --kv-profile containers are the starting rungs),
  --kv-adapt-pages bounds the tier's byte budget.
* **Tiered page store** (--kv-offload host): a host-memory page tier
  (``core.page_store``) behind the bounded device pool. Pool pressure
  *demotes* unreferenced cached prefixes to host numpy (bytes stay in their
  packed int4/int8/fp containers, so offload traffic scales with the
  precision policy) instead of destroying them; admission *promotes*
  matched host pages back before aliasing. --host-pages bounds the tier.
  ``snapshot_prefix_cache``/``restore_prefix_cache`` (--prefix-snapshot)
  persist the cached chains across server restarts — the snapshot is
  profile-key-namespaced like the trie, so an int8 snapshot never backs an
  int4 server.
* **SLO scheduling + preemption** (--sched slo): admission orders the queue
  by (priority, deadline, arrival) and may admit up to --admit-window
  requests past a deferred head (killing the FIFO head-of-line block). A
  queued request strictly more urgent than a running one may PREEMPT it:
  the victim's written pages demote to the host tier, the request
  re-queues, and on re-admission its pages promote back and decoding
  resumes bitwise-identically — no re-prefill (gather mode; see
  tests/test_scheduler.py). Preemption requires --kv-offload host.

CPU demos:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-72b --smoke \
      --requests 12 --batch-size 4 --max-new 24 --kv-bits 8
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-72b --smoke \
      --requests 12 --batch-size 4 --max-new 24 --kv-bits 4 --page-size 16 \
      --attn-impl pallas

Bench (tok/s, prefill latency, HBM bytes/token; dense vs paged, gather vs
pallas, stepwise vs bucketed):
  PYTHONPATH=src python -m benchmarks.run paged_serve
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.registry import get_config, get_smoke_config
from ..core.fixedpoint import FixedPointFormat
from ..core.page_store import (HostPageStore, QuantTierStore, TieredPager,
                               cache_geometry, extract_page, inject_page,
                               load_prefix_snapshot, save_prefix_snapshot,
                               snapshot_path)
from ..core.paged_kv import (SCRATCH_PAGE, OutOfPagesError, PageAllocator,
                             PagedCacheSpec, caches_kv_bytes, copy_pool_pages,
                             map_kv_pools, max_pages_per_seq)
from ..core.policy import LayerPolicy, PrecisionPolicy
from ..core.prefix_cache import PrefixCache
from ..models.transformer import init_cache, init_model
from ..parallel.sharding import (paged_pool_shardings, param_shardings,
                                 plan_for_mesh)
from ..quant.apply import (build_model_quant, kv_profile_key,
                           transformer_layer_names)
from ..runtime.compile_cache import enable_compile_cache
from ..runtime.telemetry import (MetricsRegistry, MetricsSnapshotter,
                                 SLOMonitor, make_tracer, metric_attr)
from .mesh import make_serving_mesh
from .scheduler import DeadlineMissPredictor, SchedPolicy, SLOScheduler
from .steps import make_chunk_prefill_step, make_decode_step


@dataclasses.dataclass
class PreemptedState:
    """Slot state captured at a span boundary when a request is preempted:
    everything resume needs to continue decoding bitwise-identically —
    the cache position, the next token to consume, the generated count,
    and one entry per slot page (in page-table order). An entry is either
    ``("host", handle)`` — the page's bytes were demoted to the host tier —
    or ``("alias", node)`` — the page aliases a still-resident prefix-cache
    node (refcount > 1, so demoting it frees nothing): the victim's slot
    reference was dropped, the node PINNED against eviction, and resume
    re-aliases it with a fresh incref instead of paying the host round
    trip (preemption re-aliasing)."""

    pos: int
    token: int
    gen: int
    entries: List[tuple]


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray          # (P,) int32
    max_new: int
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # --- scheduling metadata (core.launch.scheduler orders on these) ---
    priority: int = 0           # higher = more urgent
    deadline_step: Optional[int] = None  # SLO: finish by this decode step
    arrive_step: int = 0        # becomes visible to admission at this step
    # --- outcome / preemption state ---
    error: Optional[Exception] = None    # set when admission rejects
    finish_step: Optional[int] = None    # decode-step clock at retirement
    preemptions: int = 0
    _paused: Optional[PreemptedState] = None
    # admission-cycle feature vector (predictor on, deadlined requests
    # only): the training example paired with the miss/met label at retire
    _risk_feat: Optional[list] = None


def _pow2_bucket(n: int, cap: int) -> int:
    """Smallest power-of-two >= n, clipped to cap (the max bucket)."""
    return min(cap, 1 << max(0, n - 1).bit_length())


def _shared_page_tokens(a: np.ndarray, b: np.ndarray, ps: int) -> int:
    """Length of the common prompt prefix of ``a`` and ``b`` that both
    requests actually CACHE (each writes prompt[:-1]; the last token is
    consumed by decode), rounded down to full ``ps``-token pages — the span
    one admission-wave prompt can alias off another's freshly written
    pages."""
    n = min(len(a), len(b)) - 1
    if n <= 0:
        return 0
    eq = a[:n] == b[:n]
    common = n if eq.all() else int(np.argmin(eq))
    return (common // ps) * ps


@dataclasses.dataclass
class _PrefillJob:
    """One planned bucketed prefill (slot already reserved/aliased): feed
    ``req.prompt[start:-1]`` into the pool. ``done`` tracks written tokens
    across the batched rounds; ``finished`` flips once the slot's clock and
    token state are final (rollback on a failed batch skips finished
    jobs). ``wait_for = (leader_job, shared)`` marks a wave-dedupe
    follower: it sits out prefill rounds until ``leader_job`` has written
    through token ``shared``, then aliases the leader's pages for
    [start, shared) instead of re-running their forwards (see
    ``_plan_wave_dedupe``). ``start`` is mutable for exactly that jump."""

    slot: int
    req: Request
    start: int
    done: int = 0
    finished: bool = False
    wait_for: Optional[tuple] = None

    @property
    def total(self) -> int:
        return max(0, len(self.req.prompt) - 1 - self.start)


def _upload(x):
    """Device-put a host-MUTABLE numpy buffer via a host-side snapshot.

    jax may zero-copy alias numpy memory on CPU, and even copying uploads
    can complete asynchronously — so handing jax a buffer the serving loop
    later mutates in place (pos, tokens, page_table) is a data race. The
    snapshot is synchronous host work and nobody ever mutates it."""
    return jnp.asarray(np.array(x))


def _jit_on_mesh(step, mesh, caches_arg: int):
    """``jax.jit(step)`` with the caches (positional argument
    ``caches_arg``) donated: the step writes the KV pool in place, so the
    input and output pools never coexist. The caller's array is deleted by
    the call; every holder of the caches re-reads ``BatchedServer.caches``
    after a step. With a serving mesh, ``step`` is traced under the mesh's
    abstract context, which is how the paged attention kernel finds the
    "model" axis to ``shard_map`` over (models.attention). The wrapper
    keeps ``step``'s name, so the module is ``jit_<name>`` either way."""
    if mesh is None:
        return jax.jit(step, donate_argnums=caches_arg)

    @functools.wraps(step)
    def on_mesh(*args):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return step(*args)
    return jax.jit(on_mesh, donate_argnums=caches_arg)


class BatchedServer:
    """Fixed-slot continuous batching with per-slot positions.

    Invariant per occupied slot i: cache positions [0, pos[i]) hold the KV
    of the request's consumed tokens and ``tokens[i]`` is the next token to
    consume (last prompt token after prefill, last generated token after).
    Free slots sit at pos 0 with their page-table row parked on the scratch
    page, so the shared decode step can run them without corrupting live
    data.

    ``prefill``: "auto" picks the bucketed chunked prefill whenever the
    layout supports it (paged + attention-only arch), "bucketed" demands it,
    "stepwise" forces the slot-granular reference path. ``prefill_batch``
    caps how many same-bucket prompts one admission cycle stacks into a
    single batched prefill forward (0 = auto, 1 = sequential).
    ``attn_impl``: "gather" (jnp reference) or "pallas" (the unified
    variable-length paged chunk kernel, decode AND prefill; paged only).

    ``metrics``: "on" records request-lifecycle spans on ``self.tracer``
    (Chrome-trace exportable) and enables the JSONL snapshot stream;
    "off" (default) installs the no-op ``NullTracer``. In both modes the
    serving loop's spans (``serve.tick``, ``serve.admission``,
    ``serve.admit``, ``serve.prefill_chunk``, ``serve.decode_span``,
    ``serve.decode_step``, ``serve.span_fetch``, ``serve.dispatch``,
    ``serve.retire``, ...) are profiler annotations, so a profiler session
    sees them on the device trace's clock. The
    ``MetricsRegistry`` itself is ALWAYS live — counters are pure host
    bookkeeping outside every jitted program, so tokens are identical
    either way (subprocess-asserted, like ``--kv-adapt off``).
    ``registry`` injects a shared registry; the default is per-server so
    A/B benches comparing two servers in one process never mix counters.
    """

    # Legacy counter attributes, registry-backed via telemetry.metric_attr:
    # every historical call site (`srv.prefill_forwards += 1`, test/bench
    # reads, hand-zeroing) works unchanged, but the value lives in
    # `self.metrics` — serve, tests and benches read one source of truth.
    prefill_forwards = metric_attr("serve.prefill_forwards")
    prefill_tokens = metric_attr("serve.prefill_tokens")
    prefill_s = metric_attr("serve.prefill_s")
    decode_steps = metric_attr("serve.decode_steps")
    program_launches = metric_attr("serve.program_launches")
    wave_dedup_pages = metric_attr("serve.wave_dedup_pages")
    _gen_tokens = metric_attr("serve.gen_tokens")
    prefix_hit_tokens = metric_attr("serve.prefix_hit_tokens")
    prefill_forwards_saved = metric_attr("serve.prefill_forwards_saved")
    preempt_count = metric_attr("serve.preempt_count")
    resume_count = metric_attr("serve.resume_count")
    realias_skipped = metric_attr("serve.realias_skipped")

    def __init__(self, cfg, params, *, batch_size: int, max_len: int,
                 kv_bits: int = 0, page_size: int = 0,
                 num_pages: Optional[int] = None, seed: int = 0,
                 attn_impl: str = "gather", prefill: str = "auto",
                 prefill_bucket: int = 32, prefill_batch: int = 0,
                 kv_profile: Optional[PrecisionPolicy] = None,
                 kv_scale: str = "static", prefix_cache: str = "off",
                 kv_profile_scan: str = "group",
                 kv_offload: str = "none",
                 host_pages: Optional[int] = None,
                 sched: str = "fifo", admit_window: int = 4,
                 preempt: Optional[bool] = None,
                 kv_adapt: str = "off", adapt_pages: int = 0,
                 adapt_floor_bits: int = 4,
                 metrics: str = "off",
                 registry: Optional[MetricsRegistry] = None,
                 snapshot_out: Optional[str] = None,
                 snapshot_every: int = 50,
                 predictor: str = "off", pager_async: str = "off",
                 mesh=None):
        # telemetry first: counter attributes below are registry-backed
        # descriptors, so `self.metrics` must exist before any assignment
        self.metrics = registry if registry is not None else MetricsRegistry()
        self.tracer = make_tracer(metrics)
        self._snapshotter = (MetricsSnapshotter(self.metrics, snapshot_out,
                                                every=snapshot_every)
                             if snapshot_out else None)
        self._clock = 0         # decode-step clock of the current run()
        self.cfg = cfg
        self.params = params
        self.B = batch_size
        self.max_len = max_len
        self.paged = page_size > 0
        if self.paged and cfg.attention_type == "mla":
            raise NotImplementedError("paged KV serving supports GQA archs")
        if attn_impl not in ("gather", "pallas"):
            raise ValueError(f"attn_impl must be 'gather' or 'pallas', "
                             f"got {attn_impl!r}")
        if attn_impl == "pallas" and not self.paged:
            raise ValueError("--attn-impl pallas routes the paged decode "
                             "kernel; it needs --page-size > 0")
        self.attn_impl = attn_impl
        if prefill not in ("auto", "bucketed", "stepwise"):
            raise ValueError(f"prefill must be auto|bucketed|stepwise, "
                             f"got {prefill!r}")
        attn_only = all(k == "attn" for k in cfg.layer_kinds)
        # bucketed prefill is only offered where it is output-equivalent to
        # the stepwise reference: SSM states are per-slot dense, and
        # capacity-bounded scatter MoE routes differently at chunk batch
        # shapes (capacity scales with tokens-per-forward)
        bucketed_ok = (self.paged and attn_only
                       and (cfg.num_experts == 0
                            or cfg.moe_mode == "eval_all"))
        if prefill == "bucketed" and not bucketed_ok:
            raise ValueError("bucketed prefill needs a paged cache, an "
                             "attention-only arch (SSM states are per-slot "
                             "dense), and exact MoE routing (scatter-mode "
                             "expert capacity depends on the forward's "
                             "token count); use prefill='stepwise'")
        self.prefill_mode = ("bucketed" if prefill in ("auto", "bucketed")
                             and bucketed_ok else "stepwise")
        if prefill_bucket < 1:
            raise ValueError("prefill_bucket must be >= 1")
        self.prefill_bucket = prefill_bucket
        if prefill_batch < 0:
            raise ValueError("prefill_batch must be >= 0 (0 = auto)")
        self.prefill_batch = prefill_batch
        if kv_scale not in ("static", "page"):
            raise ValueError(f"kv_scale must be 'static' or 'page', "
                             f"got {kv_scale!r}")
        if kv_scale == "page" and not (self.paged
                                       and (kv_bits or kv_profile)):
            raise ValueError("--kv-scale page calibrates per-page dequant "
                             "scales; it needs a quantized paged cache "
                             "(--page-size > 0 and --kv-bits/--kv-profile)")
        self.kv_scale = kv_scale
        if prefix_cache not in ("on", "off"):
            raise ValueError(f"prefix_cache must be 'on' or 'off', "
                             f"got {prefix_cache!r}")
        if prefix_cache == "on" and not self.paged:
            raise ValueError("--prefix-cache on shares pool pages; it needs "
                             "--page-size > 0")
        if prefix_cache == "on" and not attn_only:
            raise ValueError("prefix sharing needs an attention-only arch: "
                             "an SSM state folds the whole prefix, so "
                             "cached KV pages cannot stand in for skipped "
                             "prefill forwards")
        if kv_offload not in ("none", "host"):
            raise ValueError(f"kv_offload must be 'none' or 'host', "
                             f"got {kv_offload!r}")
        if kv_offload == "host" and not self.paged:
            raise ValueError("--kv-offload host demotes pool pages; it "
                             "needs --page-size > 0")
        if kv_adapt not in ("off", "on"):
            raise ValueError(f"kv_adapt must be 'off' or 'on', "
                             f"got {kv_adapt!r}")
        if kv_adapt == "on" and not (self.paged and prefix_cache == "on"):
            raise ValueError("--kv-adapt on requantizes cold CACHED prefix "
                             "pages under pool pressure; it needs "
                             "--page-size > 0 and --prefix-cache on")
        if adapt_floor_bits not in (4, 8):
            raise ValueError(f"adapt_floor_bits must be 4 or 8, "
                             f"got {adapt_floor_bits}")
        if sched not in ("fifo", "slo"):
            raise ValueError(f"sched must be 'fifo' or 'slo', got {sched!r}")
        if sched == "slo" and not self.paged:
            raise ValueError("--sched slo schedules page-pool admission; "
                             "it needs --page-size > 0")
        if preempt is None:
            preempt = sched == "slo" and kv_offload == "host"
        if preempt and kv_offload != "host":
            raise ValueError("preemption parks victim pages in the host "
                             "tier; it needs --kv-offload host")
        if preempt and sched != "slo":
            raise ValueError("preemption is driven by the SLO scheduler; "
                             "it needs --sched slo")
        self.sched = sched
        self.scheduler = (SLOScheduler(SchedPolicy(admit_window=admit_window,
                                                   preempt=preempt),
                                       metrics=self.metrics)
                          if sched == "slo" else None)
        if predictor not in ("off", "on"):
            raise ValueError(f"predictor must be 'off' or 'on', "
                             f"got {predictor!r}")
        if predictor == "on" and sched != "slo":
            raise ValueError("--predictor on gates speculative admissions "
                             "inside the SLO admission loop; it needs "
                             "--sched slo")
        if pager_async not in ("off", "on"):
            raise ValueError(f"pager_async must be 'off' or 'on', "
                             f"got {pager_async!r}")
        if pager_async == "on" and kv_offload != "host":
            raise ValueError("--pager-async on overlaps host-tier page "
                             "transfers with decode; it needs "
                             "--kv-offload host")
        # rolling-window SLO reductions are always live (pure host-side
        # bookkeeping, like the registry itself); the predictor that ACTS
        # on them is strictly opt-in so default serving stays bitwise
        # identical
        self.slo_monitor = SLOMonitor(self.metrics)
        self.predictor = (DeadlineMissPredictor(metrics=self.metrics)
                          if predictor == "on" else None)
        self._risk_feat_last: Optional[list] = None
        if kv_profile_scan not in ("group", "unroll"):
            raise ValueError(f"kv_profile_scan must be 'group' or 'unroll', "
                             f"got {kv_profile_scan!r}")
        self.quant = None
        if kv_profile is not None:
            if kv_bits:
                raise ValueError("--kv-profile supersedes --kv-bits; "
                                 "pass only one")
            if not (self.paged and attn_only):
                raise ValueError("--kv-profile (per-layer KV containers) "
                                 "needs a paged cache and an attention-only "
                                 "arch")
            # serving quantizes the CACHE only: data formats drive the KV
            # containers, weight formats (if the policy has them, e.g. from
            # core.search output) are dropped
            kv_profile = PrecisionPolicy(
                kv_profile.names,
                tuple(LayerPolicy(None, lp.data) for lp in kv_profile.layers))
            self.quant = build_model_quant(kv_profile, cfg, quantize_kv=True,
                                           quantize_activations=False,
                                           per_layer_kv=True,
                                           kv_scale_mode=kv_scale,
                                           kv_unroll=(kv_profile_scan
                                                      == "unroll"))
        elif kv_bits:
            container = "int4" if (self.paged and kv_bits <= 4) else "int8"
            names = transformer_layer_names(cfg)
            pol = PrecisionPolicy.uniform(
                names, None, FixedPointFormat(2, kv_bits - 2))
            self.quant = build_model_quant(pol, cfg, quantize_kv=True,
                                           quantize_activations=False,
                                           kv_container=container,
                                           kv_scale_mode=kv_scale)
        # pages may only be shared between identically-quantized configs:
        # the prefix cache namespaces its trie by this key
        self.profile_key = kv_profile_key(kv_profile, kv_bits=kv_bits,
                                          kv_scale_mode=kv_scale)
        self.decode = _jit_on_mesh(make_decode_step(cfg, quant=self.quant,
                                                    attn_impl=attn_impl),
                                   mesh, 3)
        self._chunk_prefill = (
            _jit_on_mesh(make_chunk_prefill_step(cfg, quant=self.quant,
                                                 attn_impl=attn_impl),
                         mesh, 4)
            if self.prefill_mode == "bucketed" else None)

        paged_spec = None
        self.prefix_cache: Optional[PrefixCache] = None
        self.host_store: Optional[HostPageStore] = None
        self.pager: Optional[TieredPager] = None
        if self.paged:
            self.np_max = max_pages_per_seq(max_len, page_size)
            if num_pages is None:
                num_pages = 1 + batch_size * self.np_max  # full capacity
            paged_spec = PagedCacheSpec(page_size=page_size,
                                        num_pages=num_pages)
            self.allocator = PageAllocator(num_pages, metrics=self.metrics)
            self.page_size = page_size
            self.page_table = np.full((batch_size, self.np_max),
                                      SCRATCH_PAGE, np.int32)
            self.slot_pages: List[List[int]] = [[] for _ in range(batch_size)]
            self.slot_reserved = [0] * batch_size  # worst-case page demand
            self._pt_dev = _upload(self.page_table)
            self._pt_dirty = False
            if kv_offload == "host":
                self.host_store = HostPageStore(max_pages=host_pages,
                                                metrics=self.metrics)
                self.pager = TieredPager(
                    self.allocator, self.host_store,
                    lambda: self.caches,
                    lambda c: setattr(self, "caches", c),
                    metrics=self.metrics,
                    async_mode=(pager_async == "on"),
                    tracer=self.tracer)
                self.allocator.host_inventory = \
                    lambda: self.host_store.num_pages
            if prefix_cache == "on":
                self.prefix_cache = PrefixCache(self.allocator, page_size,
                                                self.profile_key,
                                                pager=self.pager,
                                                metrics=self.metrics,
                                                tracer=self.tracer)
                # pool pressure demotes (host tier) or evicts cold cached
                # prefixes before failing the allocation
                self.allocator.reclaim = self.prefix_cache.evict
        self.caches = init_cache(cfg, batch_size, max_len, self.quant,
                                 paged=paged_spec)
        # --- tensor-parallel placement (ROADMAP item 1) --------------------
        # mesh= shards ONE replica across devices: weights TP-only over
        # "model" (plan_for_mesh + inference=True strips the FSDP axis) and
        # the paged KV pool over the attention KV-head axis
        # (parallel.sharding.paged_pool_shardings — per-page scales
        # replicate, int4 lane-packing is along head_dim so head shards
        # stay whole). GSPMD propagates the layout through the existing
        # jitted decode/prefill programs unchanged; host-side page ops
        # (extract/inject, np.asarray reads) force gathers and stay exact.
        self.mesh = mesh
        self.mesh_plan = None
        if mesh is not None:
            if not self.paged:
                raise ValueError("mesh-sharded serving shards the paged KV "
                                 "pool; it needs --page-size > 0")
            self.mesh_plan = plan_for_mesh(mesh)
            self.params = params = jax.device_put(
                params, param_shardings(params, self.mesh_plan,
                                        inference=True))
            self.caches = jax.device_put(
                self.caches,
                paged_pool_shardings(self.caches, self.mesh_plan))
        # online precision adaptation (--kv-adapt): a bounded device-byte
        # tier that REQUANTIZES cold cached prefix pages one container step
        # narrower (fp -> int8 -> int4) before any host round trip; built
        # after the caches because it probes the pool geometry for its
        # per-page byte quotes
        self.quant_tier: Optional[QuantTierStore] = None
        if kv_adapt == "on":
            self.quant_tier = QuantTierStore(
                lambda: self.caches,
                lambda c: setattr(self, "caches", c),
                pages=adapt_pages or self.allocator.num_usable,
                floor_bits=adapt_floor_bits, metrics=self.metrics)
            self.prefix_cache.tier = self.quant_tier
            # admission preflight / OutOfPagesError inventory hook
            self.allocator.requant_inventory = \
                self.prefix_cache.requantizable_pages
        self.slots: List[Optional[Request]] = [None] * batch_size
        self.pos = np.zeros((batch_size,), np.int32)    # host-side lengths
        self.tokens = np.zeros((batch_size,), np.int32)  # host-side tokens
        self.slot_gen = [0] * batch_size                 # generated counts
        # hot-path instrumentation: registry-backed class descriptors (see
        # above); zeroing them here just initializes the "serve.*" counters
        self.prefill_forwards = 0   # forward-program executions in prefill
        self.prefill_tokens = 0     # prompt tokens consumed by prefill
        self.prefill_s = 0.0
        self.decode_steps = 0
        self.program_launches = 0   # every jitted forward executed
        self.wave_dedup_pages = 0   # pages aliased off a same-wave leader
        self._gen_tokens = 0        # generated tokens (all run() calls)
        self.prefix_hit_tokens = 0        # prompt tokens served from cache
        self.prefill_forwards_saved = 0   # forwards prefix hits avoided
        self.preempt_count = 0            # victim slots demoted + re-queued
        self.resume_count = 0             # preempted requests resumed
        self.realias_skipped = 0          # preempt host-copies skipped by
        #                                   re-aliasing resident cache nodes
        self.rejected: List[Request] = []  # never-fit requests (error set)
        # one shared KV-inventory gauge schema (``kv_inventory`` and the
        # snapshot stream read the SAME callbacks; satellite of ISSUE 8)
        if self.paged:
            reg = self.metrics.register_gauge
            reg("kv.device_bytes",
                lambda: sum(caches_kv_bytes(self.caches).values()))
            reg("kv.device_pages_free", lambda: self.allocator.num_free)
            reg("kv.device_pages_usable", lambda: self.allocator.num_usable)
            reg("kv.host_bytes",
                lambda: self.host_store.nbytes if self.host_store else 0)
            reg("kv.host_pages",
                lambda: self.host_store.num_pages if self.host_store else 0)
            reg("kv.tier_bytes",
                lambda: self.quant_tier.nbytes if self.quant_tier else 0)
            reg("kv.tier_pages",
                lambda: self.quant_tier.num_pages if self.quant_tier else 0)

    # -- page bookkeeping ---------------------------------------------------
    def _ensure_page(self, slot: int, position: int):
        """Allocate pages so logical ``position`` of ``slot`` is backed."""
        blk = position // self.page_size
        if self.kv_scale == "page" and blk < len(self.slot_pages[slot]):
            # SHARING CONTRACT (core.paged_kv._paged_update_page_scale): a
            # per-page scale raise rewrites the whole page's grid in place,
            # so a write target must be exclusively owned. _cache_insert
            # never shares a page the owner keeps writing (page mode skips
            # the partial tail), so any violation here is a refcount bug.
            assert self.allocator.refcount(self.slot_pages[slot][blk]) == 1, \
                "page-scale write into a CoW-shared page"
        while len(self.slot_pages[slot]) <= blk:
            page = self.allocator.alloc()
            self.page_table[slot, len(self.slot_pages[slot])] = page
            self.slot_pages[slot].append(page)
            self._pt_dirty = True

    def _release_slot(self, slot: int):
        if self.paged:
            if self.slot_pages[slot]:
                self.allocator.free(self.slot_pages[slot])
                self.slot_pages[slot] = []
                self.page_table[slot, :] = SCRATCH_PAGE
                self._pt_dirty = True
            self.slot_reserved[slot] = 0
        self.pos[slot] = 0
        self.slot_gen[slot] = 0

    def _page_table_dev(self):
        if self._pt_dirty:
            self._pt_dev = _upload(self.page_table)
            self._pt_dirty = False
        return self._pt_dev

    def _pages_needed(self, req: Request) -> int:
        """Worst-case pages ``req`` can ever occupy: prompt + generation,
        clipped by the max_len-1 position ceiling of the decode loop. The
        loop always generates at least one token (``_run_span`` >= 1), so
        max_new counts as >= 1 here or the preflight would under-reserve."""
        tokens = min(len(req.prompt) - 1 + max(req.max_new, 1),
                     self.max_len - 1)
        return -(-max(tokens, 1) // self.page_size)

    def _outstanding_reservation(self) -> int:
        """Pages promised to live requests but not yet allocated."""
        return sum(max(0, self.slot_reserved[i] - len(self.slot_pages[i]))
                   for i in range(self.B) if self.slots[i] is not None)

    # -- prefill ------------------------------------------------------------
    def _sync_step(self):
        """One whole-batch decode step driven from the host-side state
        (the slot-granular prefill path; output tokens are discarded)."""
        pt = self._page_table_dev() if self.paged else None
        _, _, self.caches = self.decode(
            self.params, _upload(self.tokens), _upload(self.pos),
            self.caches, pt)
        self.prefill_forwards += 1
        self.program_launches += 1

    def _prefill_stepwise(self, slot: int, req: Request, start: int = 0):
        """Feed prompt[start:-1] through shared decode steps, leaving the
        last prompt token in ``tokens`` for the run loop to consume
        (``start`` > 0 = prefix-cache hit: positions [0, start) are already
        backed by shared/copied pages). Other slots do not advance: they
        rewrite their current position with identical values. This is the
        bitwise-reference prefill (one compiled program, O(prompt_len)
        whole-batch forwards)."""
        self.pos[slot] = start
        for t in req.prompt[start:-1]:
            if self.paged:
                self._ensure_page(slot, int(self.pos[slot]))
            self.tokens[slot] = int(t)
            self._sync_step()
            self.pos[slot] += 1
        self.tokens[slot] = int(req.prompt[-1])

    def _n_chunks(self, n: int) -> int:
        """Bucketed-prefill forwards needed for ``n`` prompt tokens."""
        c, done = 0, 0
        while done < n:
            done += min(_pow2_bucket(n - done, self.prefill_bucket), n - done)
            c += 1
        return c

    def _prefill_slot(self, slot: int, req: Request, start: int = 0):
        """Slot-granular reference prefill (stepwise mode / dense caches);
        bucketed admissions go through ``_run_prefills`` instead."""
        t0 = time.perf_counter()
        self._prefill_stepwise(slot, req, start)
        self.prefill_forwards_saved += start
        self.prefill_s += time.perf_counter() - t0
        self.prefill_tokens += len(req.prompt)
        self.slot_gen[slot] = 0

    # -- batched bucketed prefill -------------------------------------------
    def _prefill_group_cap(self) -> int:
        """Max prompt rows stacked into one batched prefill forward.
        ``prefill_batch=0`` is auto: the batch size. Intra-wave prefix
        sharing no longer forces sequential admission — same-wave prompts
        sharing page-aligned chunks are deduped inside the batched wave by
        ``_plan_wave_dedupe`` (followers alias the leader's fresh pages),
        so --prefill-batch composes with --prefix-cache."""
        if self.prefill_batch:
            return self.prefill_batch
        return self.B

    def _plan_wave_dedupe(self, pending: List[_PrefillJob]) -> None:
        """Prefix-AWARE batched prefill: pair each wave job with the
        earlier same-wave job (leader) whose prompt shares the most
        page-aligned full chunks beyond the follower's own prefix-cache
        hit. The follower sits out rounds (``wait_for``) until the leader
        has written through the shared span, then ``_apply_wave_aliases``
        increfs the leader's pages into its table — the forwards for those
        chunks run ONCE per wave instead of once per request.

        A follower must start page-aligned (a CoW divergence means its own
        content already differs mid-page) with a leader starting at or
        below it (so the leader's slot holds every needed page), and only
        FULLY-written pages are ever aliased — the leader's later writes
        (including page-scale rescales, which touch only blocks of the
        chunk being written) never revisit them, honoring the page-scale
        sharing contract."""
        ps = self.page_size
        leaders: List[_PrefillJob] = []
        for job in pending:
            best, best_shared = None, job.start
            if job.done == 0 and job.start % ps == 0:
                for lead in leaders:
                    if lead.start > job.start:
                        continue
                    shared = _shared_page_tokens(lead.req.prompt,
                                                 job.req.prompt, ps)
                    if shared > best_shared:
                        best, best_shared = lead, shared
            if best is not None:
                job.wait_for = (best, best_shared)
            else:
                leaders.append(job)

    def _apply_wave_aliases(self, pending: List[_PrefillJob]) -> None:
        """Unblock wave-dedupe followers whose leader has written through
        the shared span: alias the leader's pages for [start, shared) into
        the follower's table (one incref per page, no forwards) and jump
        the follower's start to ``shared``. Runs at every round boundary —
        a leader that just finished its prefill unblocks its followers
        BEFORE it can retire from decode, so the increfs always land on
        live pages."""
        for job in pending:
            if job.wait_for is None or job.finished:
                continue
            lead, shared = job.wait_for
            if lead.start + lead.done < shared:
                continue
            ps = self.page_size
            b0, b1 = job.start // ps, shared // ps
            assert len(self.slot_pages[job.slot]) == b0, \
                "wave-dedupe follower holds pages past its start"
            chunks_before = self._n_chunks(job.total)
            for b in range(b0, b1):
                page = self.slot_pages[lead.slot][b]
                self.allocator.incref(page)   # the follower's reference
                self.page_table[job.slot, b] = page
                self.slot_pages[job.slot].append(page)
            self._pt_dirty = True
            job.start = shared
            job.wait_for = None
            self.pos[job.slot] = shared
            self.wave_dedup_pages += b1 - b0
            self.prefill_forwards_saved += (chunks_before
                                            - self._n_chunks(job.total))
            if job.total == 0:
                self._finish_job(job)

    def _prefill_group(self, rows: List[_PrefillJob], bucket: int):
        """ONE batched prefill forward: each row's next ``bucket``-sized
        chunk, stacked into a [n_rows, bucket] program with per-row page
        tables, start positions, and valid lengths. Rows are independent
        sequences writing disjoint pages, so stacking is bitwise-neutral
        per row (asserted in tests/test_serve_fast.py)."""
        n = len(rows)
        with self.tracer.span("prefill_chunk",
                              args={"rows": n, "bucket": bucket,
                                    "step": self._clock}):
            chunk = np.zeros((n, bucket), np.int32)
            starts = np.zeros((n,), np.int32)
            valids = np.zeros((n,), np.int32)
            pts = np.empty((n, self.np_max), np.int32)
            for r, job in enumerate(rows):
                off = job.start + job.done
                toks = job.req.prompt[off:len(job.req.prompt) - 1]
                valid = min(bucket, len(toks))
                self._ensure_page(job.slot, off + valid - 1)
                chunk[r, :valid] = toks[:valid]
                starts[r], valids[r] = off, valid
                pts[r] = self.page_table[job.slot]
            # chunk/starts/valids/pts are private copies nobody mutates
            # later, so plain asarray uploads are race-free (cf. _upload)
            chunk_dev, starts_dev, valids_dev, pts_dev = (
                jnp.asarray(chunk), jnp.asarray(starts),
                jnp.asarray(valids), jnp.asarray(pts))
            with self.tracer.span("dispatch"):
                self.caches = self._chunk_prefill(
                    self.params, chunk_dev, starts_dev, valids_dev,
                    self.caches, pts_dev)
            self.prefill_forwards += 1
            self.program_launches += 1
            self._count_attn_pages(starts, starts + bucket, bucket)
            for r, job in enumerate(rows):
                job.done += int(valids[r])
                self.pos[job.slot] = job.start + job.done

    def _count_attn_pages(self, q_start, kv_len, seq_len: int):
        """One program call's paged-kernel walk on the registry (each of
        its layers walks the same table): ``serve.attn_pages_live``, the
        table entries the kernel fetches, and ``serve.attn_pages_table``,
        those a walk of every entry would visit; ``serve.attn_grid_steps``,
        the grid steps the kernel runs (its work list's items), and
        ``serve.attn_grid_box``, those of a grid over every row, query
        block and page block."""
        if self.attn_impl != "pallas":
            return
        from ..kernels.paged_kv_attention import page_walk
        counts = page_walk(q_start, kv_len, seq_len=seq_len,
                           num_table_pages=self.np_max,
                           page_size=self.page_size, ppb=self._attn_ppb)
        for name, n in zip(("attn_pages_live", "attn_pages_table",
                            "attn_grid_steps", "attn_grid_box"), counts):
            self.metrics.counter(f"serve.{name}").inc(n)

    @functools.cached_property
    def _attn_ppb(self) -> int:
        """Table entries a grid step of the paged kernel walks, as the
        kernel derives them from the first paged layer's pool (a shard of
        it on the server's mesh)."""
        from ..kernels.paged_kv_attention import pages_per_block
        from ..models.attention import paged_kernel_pages
        pool = next(c for c in jax.tree_util.tree_leaves(
            self.caches, is_leaf=lambda c: isinstance(c, dict)
            and "k_pages" in c) if isinstance(c, dict))
        return pages_per_block(num_table_pages=self.np_max,
                               **paged_kernel_pages(pool, self.mesh))

    def _finish_job(self, job: _PrefillJob):
        """Seal a prefilled slot: clock at the last prompt token (which the
        decode loop consumes) and the fresh pages indexed into the prefix
        cache."""
        self.pos[job.slot] = len(job.req.prompt) - 1
        self.tokens[job.slot] = int(job.req.prompt[-1])
        if self.prefix_cache is not None:
            self._cache_insert(job.slot, job.req)
        job.finished = True

    def _rollback_admission(self, job: _PrefillJob, err) -> None:
        """Undo one partially executed admission after a failed batch:
        release every page the row holds (aliased prefix pages just drop
        the slot's reference), clear the reservation, and vacate the slot —
        so an OutOfPagesError mid-batch leaves the accounting exactly as if
        the row was never admitted."""
        i = job.slot
        self.slots[i] = None
        if self.slot_pages[i]:
            self.allocator.free(self.slot_pages[i])
            self.slot_pages[i] = []
        self.page_table[i, :] = SCRATCH_PAGE
        self._pt_dirty = True
        self.slot_reserved[i] = 0
        self.pos[i] = 0
        self.tokens[i] = 0
        self.slot_gen[i] = 0
        self._discard_paused(job.req)
        job.req.error = err

    def _run_prefills(self, jobs: List[_PrefillJob]):
        """Execute one admission cycle's bucketed prefills, stacking
        same-bucket rows of different requests into single [n, bucket]
        forwards (capped at ``_prefill_group_cap`` rows): the scheduler's
        admit window surfaces several admissible prompts per cycle, and
        stacking amortizes both the forward count and the per-bucket
        compilations across them. Round-robin: every round, each unfinished
        row contributes its next power-of-two chunk; rows sharing a bucket
        share a forward. An ``OutOfPagesError`` mid-batch (the preflight
        makes this unreachable; defense against accounting bugs) rolls back
        every not-yet-finished row before re-raising."""
        t0 = time.perf_counter()
        cap = self._prefill_group_cap()
        try:
            pending = []
            for job in jobs:
                self.prefill_tokens += len(job.req.prompt)
                self.prefill_forwards_saved += (
                    self._n_chunks(len(job.req.prompt) - 1)
                    - self._n_chunks(job.total))
                if job.total == 0:
                    self._finish_job(job)   # full-chain hit / 1-token prompt
                else:
                    pending.append(job)
            if self.prefix_cache is not None and cap > 1:
                # intra-wave sharing: followers alias a leader's fresh
                # pages instead of forcing sequential admission
                self._plan_wave_dedupe(pending)
            while pending:
                groups = {}
                for job in pending:
                    if job.wait_for is not None:
                        continue        # follower: leader still writing
                    b = _pow2_bucket(job.total - job.done,
                                     self.prefill_bucket)
                    groups.setdefault(b, []).append(job)
                for bucket in sorted(groups):
                    grp = groups[bucket]
                    for k in range(0, len(grp), cap):
                        self._prefill_group(grp[k:k + cap], bucket)
                self._apply_wave_aliases(pending)
                nxt = []
                for job in pending:
                    if job.finished:
                        continue        # alias jump covered the whole job
                    if job.wait_for is None and job.done >= job.total:
                        self._finish_job(job)
                    else:
                        nxt.append(job)
                pending = nxt
        except OutOfPagesError as err:
            for job in jobs:
                if not job.finished:
                    self._rollback_admission(job, err)
            raise
        finally:
            self.prefill_s += time.perf_counter() - t0

    # -- prefix sharing -----------------------------------------------------
    def _copy_pool_pages(self, src: int, dst: int):
        """Copy page ``src`` -> ``dst`` in EVERY attention layer's pool
        (copy-on-write: one host-side allocator, one page-id space, all
        layers alias the same table)."""
        self.caches = map_kv_pools(
            self.caches,
            lambda pool, axis: copy_pool_pages(pool, src, dst,
                                               page_axis=axis))

    def _cache_insert(self, slot: int, req: Request):
        """Index the request's freshly prefilled prompt pages (tokens
        [0, P-1)) into the prefix cache; chunks already cached dedupe.

        In --kv-scale page mode the PARTIAL tail page is not inserted: the
        owner slot keeps decoding into it, and a per-page scale raise
        rewrites the page's grid in place — sharing it would silently
        change dequant values under aliased readers (the page-scale
        sharing contract; see core.paged_kv._paged_update_page_scale).
        Static-grid mode shares the tail safely (writes touch only
        offsets past every sharer's valid length)."""
        n_tok = len(req.prompt) - 1
        if self.kv_scale == "page":
            n_tok = (n_tok // self.page_size) * self.page_size
        if n_tok <= 0:
            return
        n_pages = -(-n_tok // self.page_size)
        self.prefix_cache.insert(req.prompt[:n_tok],
                                 self.slot_pages[slot][:n_pages])

    # -- admission ----------------------------------------------------------
    def _admission_plan(self, req: Request):
        """Preflight one request against the pool. Returns
        ``(verdict, info)`` with verdict in {"admit", "defer", "reject"}.

        Paged admission preflights the request's WORST-CASE page demand
        (prompt + max_new, minus fully-matched RESIDENT prefix pages, plus
        one promotion page per matched HOST page) against the free list
        less outstanding reservations, counting reclaimable cached pages —
        so ``_ensure_page`` can never hit an empty free list mid-run.

        On "admit" the hit's chain is PINNED in the trie (``info["hit"]``);
        the caller must either complete the admission (``_do_admit``
        unpins) or unpin itself. "defer" means the request must wait for
        live requests' pages; "reject" means it can NEVER fit (its error
        carries the full device/host/evictable inventory).

        Malformed requests raise here, BEFORE any pin/reservation is
        taken, so the error cannot leak cache state."""
        if len(req.prompt) == 0:
            raise ValueError(f"request {req.rid} has an empty prompt")
        if len(req.prompt) >= self.max_len:
            raise ValueError(f"request {req.rid} prompt length "
                             f"{len(req.prompt)} >= max_len {self.max_len}")
        if not self.paged:
            return "admit", {"hit": None, "total": 0}
        total = self._pages_needed(req)
        hit = None
        if req._paused is not None:
            # resume allocates only the host-demoted pages; re-aliased
            # (pinned) cache nodes cost nothing
            need_new = total - sum(1 for kind, _ in req._paused.entries
                                   if kind == "alias")
        elif self.prefix_cache is not None:
            # record=False: a deferred request retries this lookup every
            # span; hit-rate stats count once, on admission
            hit = self.prefix_cache.lookup(req.prompt[:-1], record=False)
            # pin the chain so preflight/admission eviction can't touch it
            self.prefix_cache.pin(hit)
            need_new = (total - len(hit.nodes)
                        + self.prefix_cache.host_nodes_in(hit))
        else:
            need_new = total
        avail = self.allocator.num_free - self._outstanding_reservation()
        evictable = 0
        if need_new > avail and self.prefix_cache is not None:
            # only walk the trie when the free list alone won't do
            evictable = self.prefix_cache.evictable_pages()
            avail += evictable
        if need_new <= avail:
            return "admit", {"hit": hit, "total": total,
                             "need_new": need_new}
        if hit is not None:
            self.prefix_cache.unpin(hit)
        if (need_new > self.allocator.num_usable
                or not any(s is not None for s in self.slots)):
            written = len(set().union(*map(set, self.slot_pages)))
            err = OutOfPagesError(
                needed=need_new, free=self.allocator.num_free,
                total=self.allocator.num_usable, rid=req.rid,
                reserved=self._outstanding_reservation(),
                written=written, evictable=evictable,
                requantizable=self.allocator.requant_pages(),
                host_pages=self.allocator.host_pages())
            return "reject", {"err": err}
        return "defer", {"total": total, "need_new": need_new,
                         "shortfall": need_new - avail}

    def _do_admit(self, i: int, req: Request, info: dict,
                  jobs: List[_PrefillJob]):
        """Execute a planned admission into free slot ``i``: alias/promote
        the pinned prefix chain, CoW-copy a mid-page divergence, and stage
        the non-shared suffix's prefill (or promote+resume a preempted
        request). Bucketed-mode prefills are only PLANNED here (appended to
        ``jobs``); the admission cycle runs them batched at the end
        (``_run_prefills``), so several same-cycle admissions share
        forwards. The slot is claimed immediately — reservation accounting
        for the rest of the cycle sees it. (Prompt validation happened in
        ``_admission_plan``, before the hit chain was pinned.)"""
        if (self.predictor is not None and req.deadline_step is not None
                and req._risk_feat is None):
            # pair this cycle's consulted features with the request: its
            # met/missed outcome at retirement is the training label
            req._risk_feat = self._risk_feat_last
        if not self.paged:
            self.tracer.req_admit(req.rid, self._clock)
            self._prefill_slot(i, req, 0)
            self.slots[i] = req
            return
        if req._paused is not None:
            self._resume_slot(i, req, info["total"])
            return
        self.tracer.req_admit(req.rid, self._clock)
        hit = info["hit"]
        self.slot_reserved[i] = info["total"]
        start = 0
        if hit is not None:
            for j, node in enumerate(hit.nodes):
                # host-state nodes promote back to device pages first
                page = self.prefix_cache.ensure_resident(node)
                self.allocator.incref(page)   # the slot's alias reference
                self.page_table[i, j] = page
                self.slot_pages[i].append(page)
                self._pt_dirty = True
            start = len(hit.nodes) * self.page_size
            if hit.cow_node is not None and hit.cow_valid > 0:
                # divergence inside a partially shared page: private copy
                src = self.prefix_cache.ensure_resident(hit.cow_node)
                dst = self.allocator.alloc()   # reclaim hook may evict
                self.page_table[i, len(hit.nodes)] = dst
                self.slot_pages[i].append(dst)
                self._pt_dirty = True
                self._copy_pool_pages(int(src), int(dst))
                self.prefix_cache.cow_copies += 1
                start += hit.cow_valid
            self.prefix_cache.unpin(hit)
            self.prefix_cache.note_lookup(len(req.prompt) - 1, start)
            self.prefix_hit_tokens += start
        self.slots[i] = req
        self.pos[i] = start
        self.slot_gen[i] = 0
        if self.prefill_mode == "bucketed":
            job = _PrefillJob(i, req, start)
            if self._prefill_group_cap() > 1:
                jobs.append(job)     # cycle runs these batched at the end
            else:
                # sequential discipline (explicit --prefill-batch 1):
                # prefill AND cache-insert complete before the next
                # admission plans, so a same-wave prompt can still alias
                # this request's fresh pages through the trie
                self._run_prefills([job])
        else:
            self._prefill_slot(i, req, start)
            if self.prefix_cache is not None:
                self._cache_insert(i, req)

    def _discard_paused(self, req: Request) -> None:
        """Release a preempted request's parked resources once it will
        NEVER resume (admission reject / rollback): unpin every re-aliased
        prefix node and drop every host-tier page its resume state holds.
        Without this, rejecting a preempted request leaks PINNED trie
        nodes — they survive ``clear()``, so the leak gate reports phantom
        retained pages — and orphaned host blobs that count against
        --host-pages forever."""
        st = req._paused
        if st is None:
            return
        for kind, val in st.entries:
            if kind == "alias":
                self.prefix_cache.unpin_node(val)
            else:
                self.host_store.drop(val)
        req._paused = None

    def _reject(self, queue: List[Request], idx: int, err) -> None:
        """Drop a never-fit request from the queue WITHOUT killing the run
        (the legacy behavior stalled everything behind a too-large head):
        the error is recorded on the request; FIFO mode re-raises it after
        the serviceable traffic drained. A preempted request rejected
        before resume releases its parked pages/pins first."""
        req = queue.pop(idx)
        self._discard_paused(req)
        req.error = err
        req.done = True
        self.rejected.append(req)
        self.metrics.counter("sched.rejects").inc()
        self.slo_monitor.note_finish(req.rid, False, 0)
        self.tracer.req_reject(req.rid, self._clock,
                               reason=type(err).__name__)

    def _admit_fifo(self, queue: List[Request], jobs: List[_PrefillJob]):
        """Legacy FIFO admission: strict queue order, but a permanently
        -too-large head is SKIPPED (recorded + surfaced at end of run)
        instead of stalling the queue forever behind it."""
        for i in range(self.B):
            if self.slots[i] is not None:
                continue
            while queue:
                verdict, info = self._admission_plan(queue[0])
                if verdict == "reject":
                    self._reject(queue, 0, info["err"])
                    continue              # next head, same free slot
                if verdict == "defer":
                    self.metrics.counter("sched.defers").inc()
                    self.tracer.req_defer(queue[0].rid, self._clock)
                    return                # wait for live requests' pages
                req = queue.pop(0)
                with self.tracer.span("admit", args={"rid": req.rid}):
                    self._do_admit(i, req, info, jobs)
                break

    def _admit_slo(self, queue: List[Request], jobs: List[_PrefillJob],
                   spec_budget: Optional[int] = None):
        """Priority/EDF admission with bounded out-of-order admission past
        a deferred head, and preemption of strictly less urgent running
        requests when a candidate's page shortfall can be met by demoting
        a victim to the host tier. ``spec_budget`` (predictor on) caps NEW
        speculative admissions this cycle — no-deadline, non-resumed
        requests past the budget are passed over (they stay queued and are
        re-examined next cycle); deadlined and preempted-resume requests
        are never gated."""
        pol = self.scheduler.policy
        self.scheduler.sort_queue(queue)
        preempts_left = pol.max_preempt_per_admit
        deferred = False
        examined = 0          # requests examined past the deferred head
        idx = 0
        while idx < len(queue):
            if deferred:
                examined += 1
                if examined > pol.admit_window:
                    break
            req = queue[idx]
            speculative = (req.deadline_step is None
                           and req._paused is None)
            if speculative and spec_budget is not None and spec_budget <= 0:
                # gate BEFORE planning: an admit plan pins the prefix hit
                # chain, so skipping after planning would leak pins
                self.predictor.gated += 1
                self.tracer.req_defer(req.rid, self._clock)
                idx += 1
                continue
            free = [i for i in range(self.B) if self.slots[i] is None]
            if not free:
                # batch full: the most urgent queued request may claim a
                # slot by preempting a strictly less urgent running one
                n = self._preempt_for(req, queue, 0, preempts_left)
                if n:
                    preempts_left -= n
                    continue
                break
            verdict, info = self._admission_plan(req)
            if verdict == "reject":
                self._reject(queue, idx, info["err"])
                continue
            if verdict == "admit":
                queue.pop(idx)
                with self.tracer.span("admit", args={"rid": req.rid}):
                    self._do_admit(free[0], req, info, jobs)
                if speculative and spec_budget is not None:
                    spec_budget -= 1
                if deferred:
                    self.scheduler.ooo_admissions += 1
                continue
            # defer: try preemption before stepping past this request
            n = self._preempt_for(req, queue, info["shortfall"],
                                  preempts_left)
            if n:
                preempts_left -= n
                continue                  # re-plan the same request
            self.metrics.counter("sched.defers").inc()
            self.tracer.req_defer(req.rid, self._clock)
            deferred = True
            idx += 1

    def _risk_features(self, queue: List[Request]) -> list:
        """Assemble the predictor's per-cycle feature vector from live
        telemetry. Queue depth and prefill debt count DEADLINED requests
        only — a gated backlog of speculative work must not feed back into
        the very gate holding it, or the gate would never reopen."""
        deadlined = [r for r in queue if r.deadline_step is not None]
        live = sum(1 for s in self.slots if s is not None)
        if self.paged:
            usable = max(1, self.allocator.num_usable)
            free = max(0, self.allocator.num_free
                       - self._outstanding_reservation())
            free_frac = free / usable
        else:
            free_frac = 1.0 - live / self.B
        return self.predictor.features(
            queue_deadlined=len(deadlined), batch=self.B,
            free_frac=free_frac,
            prefill_debt=sum(len(r.prompt) for r in deadlined),
            debt_cap=self.B * self.prefill_bucket,
            live_frac=live / self.B,
            arrival_ewma=self.slo_monitor.arrival_rate.get(),
            tpot_slowdown=self.slo_monitor.tpot_slowdown())

    def _admit(self, queue: List[Request]):
        """One admission cycle: plan/claim as many queued requests as slots
        and pages allow, then execute their prefills BATCHED (same-bucket
        rows of different requests stack into one forward)."""
        if not queue:
            return
        self.slo_monitor.note_queue_depth(len(queue))
        spec_budget: Optional[int] = None
        if self.predictor is not None:
            feat = self._risk_features(queue)
            self._risk_feat_last = feat
            self.predictor.consult(feat)
            spec_budget = self.predictor.spec_budget(self.B)
            if (spec_budget <= 0
                    and all(s is None for s in self.slots)
                    and not any(r.deadline_step is not None
                                or r._paused is not None for r in queue)):
                # progress valve: nothing live and nothing the gate would
                # ever let through — admit one row so purely speculative
                # traffic still drains instead of stranding the run
                spec_budget = 1
        with self.tracer.span("admission", args={"queued": len(queue),
                                                 "step": self._clock}):
            jobs: List[_PrefillJob] = []
            if self.scheduler is not None:
                self._admit_slo(queue, jobs, spec_budget)
            else:
                self._admit_fifo(queue, jobs)
            if jobs:
                self._run_prefills(jobs)

    # -- preemption ---------------------------------------------------------
    def _preempt_gain(self, i: int) -> int:
        """Device pages preempting slot ``i`` recovers: its exclusively
        held pages (shared/aliased pages only drop a refcount) plus its
        not-yet-allocated reservation."""
        freed = sum(1 for p in self.slot_pages[i]
                    if self.allocator.refcount(p) == 1)
        return freed + max(0, self.slot_reserved[i] - len(self.slot_pages[i]))

    def _realias_plan(self, i: int) -> dict:
        """Slot pages of ``i`` that alias STILL-RESIDENT prefix-cache nodes
        (page-table index -> node). Demoting such a page at preemption
        frees nothing (the cache's reference keeps it alive) and pays a
        host copy + a resume promotion for bytes that never leave the
        device — so ``_preempt_slot`` pins the node and drops only the
        slot's reference, and resume re-aliases it (preemption
        re-aliasing). A victim's own freshly inserted prompt pages match
        here too (``_cache_insert`` made them chain nodes), so typically
        only decode-tail pages take the host round trip."""
        req = self.slots[i]
        if self.prefix_cache is None or req is None:
            return {}
        hit = self.prefix_cache.lookup(req.prompt[:-1], record=False)
        plan = {}
        for j, node in enumerate(hit.nodes):
            if (j < len(self.slot_pages[i]) and node.resident
                    and node.page == self.slot_pages[i][j]):
                plan[j] = node
            else:
                return plan     # private page (e.g. CoW): chain ends here
        j = len(hit.nodes)
        if (hit.cow_node is not None and j < len(self.slot_pages[i])
                and hit.cow_node.resident
                and hit.cow_node.page == self.slot_pages[i][j]):
            plan[j] = hit.cow_node   # the victim's own partial leaf page
        return plan

    def _preempt_for(self, req: Request, queue: List[Request],
                     shortfall: int, budget: int) -> int:
        """Preempt strictly-less-urgent running slots so ``req`` becomes
        admissible (``shortfall`` pages short; 0 = needs only a slot),
        spending at most ``budget`` victims (the admission cycle's
        remaining max_preempt_per_admit allowance). Victims demote to the
        host tier (cache-aliased pages are re-alias-pinned instead — they
        need no host room) and re-queue. Returns the number of slots
        preempted."""
        if self.scheduler is None or self.host_store is None or budget <= 0:
            return 0
        running = [(i, self.slots[i], 0) for i in range(self.B)
                   if self.slots[i] is not None]
        victims = self.scheduler.choose_victims(
            req, running, max(0, shortfall), self._preempt_gain,
            limit=budget)
        preempted = 0
        for i in victims:
            plan = self._realias_plan(i)
            need_room = len(self.slot_pages[i]) - len(plan)
            while not self.host_store.has_room(need_room):
                # make host room by dropping cold demoted prefixes
                if (self.prefix_cache is None
                        or not self.prefix_cache.drop_host_lru()):
                    return preempted      # host tier genuinely full
            queue.append(self._preempt_slot(i, plan))
            preempted += 1
        return preempted

    def _preempt_slot(self, i: int, plan: Optional[dict] = None) -> Request:
        """Evict the request in slot ``i`` mid-decode (at a span boundary,
        where host-side slot state is consistent): every written page
        either demotes to the host tier (private pages) or stays resident
        as a PINNED prefix-cache node with the slot's reference dropped
        (cache-aliased pages — host-copying a refcount>1 page frees
        nothing). Device pages + reservation are released and the resume
        state captured. The request re-queues; resume promotes the host
        pages back / re-increfs the pinned nodes and continues decoding
        bitwise-identically (no re-prefill)."""
        if plan is None:
            plan = self._realias_plan(i)
        req = self.slots[i]
        self.tracer.req_preempt(req.rid, self._clock)
        entries = []
        with self.tracer.req_span(req.rid, "offload",
                                  args={"pages": len(self.slot_pages[i]),
                                        "step": self._clock}):
            for j, p in enumerate(self.slot_pages[i]):
                node = plan.get(j)
                if node is not None:
                    # page survives via the cache's reference; pin the node
                    # so eviction (demote AND drop) cannot touch it before
                    # resume
                    self.prefix_cache.pin_node(node)
                    entries.append(("alias", node))
                    self.realias_skipped += 1
                else:
                    # pager.offload: sync mode is byte-for-byte the old
                    # host_store.put(extract_page(...)); async mode issues
                    # the D2H copy and resolves it at the next span
                    # boundary drain
                    entries.append(("host", self.pager.offload(p)))
                self.allocator.free([p])
        self.slot_pages[i] = []
        self.page_table[i, :] = SCRATCH_PAGE
        self._pt_dirty = True
        self.slot_reserved[i] = 0
        req._paused = PreemptedState(pos=int(self.pos[i]),
                                     token=int(self.tokens[i]),
                                     gen=int(self.slot_gen[i]),
                                     entries=entries)
        req.preemptions += 1
        self.preempt_count += 1
        self.pos[i] = 0
        self.slot_gen[i] = 0
        self.tokens[i] = 0
        self.slots[i] = None
        return req

    def _resume_slot(self, i: int, req: Request, total: int):
        """Re-admit a preempted request: promote its demoted pages back
        into freshly allocated device pages (byte-identical — see
        core.page_store) and re-alias its pinned cache nodes (an incref,
        no byte movement), restore the slot clock/token state, and
        continue decoding where it left off. No prefill runs."""
        st = req._paused
        self.slot_reserved[i] = total
        with self.tracer.req_span(req.rid, "resume",
                                  args={"pages": len(st.entries),
                                        "step": self._clock}):
            for j, (kind, val) in enumerate(st.entries):
                if kind == "alias":
                    assert val.resident, "pinned prefix node lost residency"
                    page = val.page
                    self.allocator.incref(page)  # the slot's alias reference
                    self.prefix_cache.unpin_node(val)
                else:
                    page = self.allocator.alloc()  # reclaim may evict/demote
                    self.caches = inject_page(self.caches,
                                              self.host_store.pop(val), page)
                self.page_table[i, j] = page
                self.slot_pages[i].append(page)
                self._pt_dirty = True
        self.pos[i] = st.pos
        self.tokens[i] = st.token
        self.slot_gen[i] = st.gen
        req._paused = None
        self.resume_count += 1
        self.tracer.req_admit(req.rid, self._clock, resumed=True)
        self.slots[i] = req

    # -- decode -------------------------------------------------------------
    def _run_span(self) -> int:
        """Decode steps until the next slot event (a completion), computable
        purely from counts — the span the hot loop can run without any
        host<->device synchronization."""
        spans = []
        for i in range(self.B):
            req = self.slots[i]
            if req is None:
                continue
            spans.append(min(req.max_new - self.slot_gen[i],
                             (self.max_len - 1) - int(self.pos[i])))
        return max(1, min(spans))

    def _note_finish(self, req: Request, step: int) -> None:
        """Retirement bookkeeping at a span boundary: the deadline-miss
        counter is measured on the decode-step clock (deterministic), the
        tracer closes the request's record,
        the rolling SLO window absorbs the outcome, and (predictor on)
        the retired request's admission-time features become one SGD
        example with the miss as its label."""
        req.finish_step = step
        missed = (req.deadline_step is not None
                  and step > req.deadline_step)
        if missed:
            self.metrics.counter("sched.deadline_misses").inc()
        if self.predictor is not None and req._risk_feat is not None:
            self.predictor.observe(req._risk_feat, missed)
        self.slo_monitor.note_finish(req.rid, not missed, len(req.out))
        self.tracer.req_finish(req.rid, step, len(req.out))

    def start_loop(self, requests: List[Request]) -> "ServeLoop":
        """Begin a steppable serving loop over ``requests``.

        The multi-replica admission front (``launch.frontend``) drives N
        of these on one shared decode-step clock; :meth:`run` is exactly
        ``start_loop`` + tick-until-drained, so the single-server token
        streams are the refactor's bitwise identity baseline."""
        return ServeLoop(self, requests)

    def _prefetch_promotes(self, queue: List[Request]) -> None:
        """Promote-path prefetch: requests still queued after an admission
        pass are next cycle's admission candidates, so any prefix-chain
        (or preemption-state) page parked on the host tier is likely to be
        promoted then. Stage its host->device copy NOW — the transfer
        dispatches asynchronously and rides behind the decode span about
        to run (the promote-direction mirror of the async demote double
        buffer); the synchronous promote that follows consumes the staged
        device arrays instead of paying the H2D copy inside admission.
        Pure staging: no page allocation, no trie stamps (``peek_chain``),
        so token streams are bit-identical with prefetch on or off."""
        pager = self.pager
        if pager is None or not pager.async_mode or not queue:
            return
        budget = pager.stage_room()
        # only the queue head region can be admitted next cycle — scanning
        # deeper would stage copies that expire before their promote
        for req in queue[:max(2, self.B)]:
            if budget <= 0:
                return
            if req._paused is not None:
                for kind, val in req._paused.entries:
                    if kind == "host" and budget > 0:
                        budget -= pager.prefetch(val)
                continue
            if self.prefix_cache is None:
                continue
            for node in self.prefix_cache.peek_chain(req.prompt[:-1]):
                if node.host is not None and budget > 0:
                    budget -= pager.prefetch(node.host)

    def run(self, requests: List[Request], *, verbose: bool = False):
        t0 = time.time()
        gen0 = self._gen_tokens
        # instance counters are cumulative across run() calls (benchmarks
        # zero them between warmup and measurement); the verbose print
        # reports THIS run's deltas
        steps0, pf0 = self.decode_steps, self.prefill_forwards
        rejected0 = len(self.rejected)
        loop = self.start_loop(requests)
        while not loop.finished:
            loop.tick()
        loop.close()
        dt = time.time() - t0
        gen_tokens = self._gen_tokens - gen0
        if verbose:
            layout = (f"paged ps={self.page_size} "
                      f"free={self.allocator.num_free}"
                      if self.paged else "dense")
            steps = self.decode_steps - steps0
            print(f"[serve] {steps} decode steps, "
                  f"{self.prefill_forwards - pf0} prefill forwards "
                  f"({self.prefill_mode}), {len(requests)} requests, "
                  f"{gen_tokens / max(dt, 1e-9):,.1f} tok/s "
                  f"({steps * self.B / max(dt, 1e-9):,.1f} "
                  f"tok-slots/s, {layout}, attn={self.attn_impl}, "
                  f"{self.program_launches} programs)")
            if self.prefix_cache is not None:
                s = self.prefix_cache.stats()
                print(f"[serve] prefix cache: {s['hits']}/{s['lookups']} "
                      f"hits, {s['hit_tokens']} tokens reused, "
                      f"{self.prefill_forwards_saved} prefill forwards "
                      f"saved, {s['cow_copies']} CoW copies, "
                      f"{s['cached_pages']} pages cached + "
                      f"{s['host_pages']} host "
                      f"({s['evictions']} evicted, {s['demotions']} demoted, "
                      f"{s['promotions']} promoted)")
            if self.quant_tier is not None:
                s = self.prefix_cache.stats()
                print(f"[serve] quant tier: {self.quant_tier.num_pages} "
                      f"pages / {self.quant_tier.nbytes / 2**20:.2f} MiB "
                      f"parked (peak {self.quant_tier.peak_pages}), "
                      f"{s['requants']} requants, {s['deepens']} deepens, "
                      f"{s['tier_promotions']} promotions")
            if self.host_store is not None:
                print(f"[serve] host tier: {self.host_store.num_pages} "
                      f"pages / {self.host_store.nbytes / 2**20:.2f} MiB "
                      f"(peak {self.host_store.peak_pages}), "
                      f"{self.preempt_count} preemptions, "
                      f"{self.resume_count} resumes, "
                      f"{self.realias_skipped} demotions skipped "
                      f"(re-aliased)")
        new_rejects = self.rejected[rejected0:]
        if new_rejects and self.scheduler is None:
            # legacy strict semantics: surface the first impossible request
            # — but only AFTER the serviceable traffic drained (the old
            # code raised immediately, stalling everything queued behind a
            # too-large head). SLO mode records errors on the requests.
            raise new_rejects[0].error
        return requests

    def release_prefix_cache(self) -> int:
        """Drop every unreferenced cached prefix page back to the free
        list (and every demoted page out of the host tier). Returns the
        DEVICE page count the cache STILL holds — with all requests
        completed that must be 0, anything else is a refcount leak (the
        bench-smoke CI gate checks exactly this)."""
        if self.prefix_cache is None:
            return 0
        return self.prefix_cache.clear()

    # -- tiered-store introspection / persistence ---------------------------
    def kv_inventory(self) -> dict:
        """Device/host split of the KV store (bytes per container, page
        counts) — the two-tier generalization of ``pool_bytes``. Scalar
        fields read the registered ``kv.*`` gauges, so this dict, the
        snapshot stream, and any direct ``metrics.gauge("kv.…")`` reader
        share one schema (tests assert the byte reconciliation)."""
        if not self.paged:
            return {"device_bytes": 0, "device_by_container": {},
                    "device_pages_free": 0, "device_pages_usable": 0,
                    "host_bytes": 0, "host_pages": 0,
                    "host_by_container": {},
                    "tier_bytes": 0, "tier_pages": 0,
                    "tier_by_container": {}}
        g = self.metrics.gauge
        hs = self.host_store
        qt = self.quant_tier
        return {
            "device_bytes": g("kv.device_bytes").value,
            "device_by_container": caches_kv_bytes(self.caches),
            "device_pages_free": g("kv.device_pages_free").value,
            "device_pages_usable": g("kv.device_pages_usable").value,
            "host_bytes": g("kv.host_bytes").value,
            "host_pages": g("kv.host_pages").value,
            "host_by_container": hs.bytes_by_container() if hs else {},
            "tier_bytes": g("kv.tier_bytes").value,
            "tier_pages": g("kv.tier_pages").value,
            "tier_by_container": qt.bytes_by_container() if qt else {},
        }

    def snapshot_prefix_cache(self, path: str) -> int:
        """Serialize every cached prefix page (resident pages read straight
        off the device pools, demoted ones from the host tier) to ``path``.
        The snapshot is profile-key-namespaced like the trie and carries a
        pool-geometry signature; returns the number of pages written."""
        if self.prefix_cache is None:
            raise ValueError("snapshot needs --prefix-cache on")
        entries = []
        for key, tokens, node in self.prefix_cache.iter_chain_nodes():
            if node.host is not None:
                blob = self.host_store.get(node.host)
            elif node.tier is not None:
                # widened back to pool-native containers so the snapshot
                # geometry signature matches (the requant cost is already
                # baked into the grid values)
                blob = self.quant_tier.export(node.tier)
            else:
                blob = extract_page(self.caches, node.page)
            entries.append((key, tokens, blob))
        return save_prefix_snapshot(path, entries, page_size=self.page_size,
                                    geometry=cache_geometry(self.caches))

    def restore_prefix_cache(self, path: str) -> int:
        """Load a snapshot into the HOST tier: every chain page becomes a
        host-state trie node (zero device pages consumed until a hit
        promotes it). Chains whose profile key differs from this server's
        stay in their own namespace — harmless, never matched. Returns the
        pages restored (stops early when the host tier fills)."""
        if self.prefix_cache is None:
            raise ValueError("restore needs --prefix-cache on")
        if self.host_store is None:
            raise ValueError("restore lands pages in the host tier; it "
                             "needs --kv-offload host")
        meta, entries = load_prefix_snapshot(path)
        if meta["page_size"] != self.page_size:
            raise ValueError(f"snapshot page_size {meta['page_size']} != "
                             f"server page_size {self.page_size}")
        geo = cache_geometry(self.caches)
        if meta["geometry"] != geo:
            raise ValueError("snapshot pool geometry does not match this "
                             "server's architecture/profile")
        n = 0
        for key, tokens, blob in entries:
            if not self.host_store.has_room(1):
                break
            h = self.host_store.put(blob)
            if self.prefix_cache.insert_host(tokens, h, key):
                n += 1
            else:
                self.host_store.drop(h)   # duplicate / orphaned chain
        return n


class ServeLoop:
    """One in-flight :meth:`BatchedServer.run`, steppable one scheduler
    cycle at a time.

    Extracted from ``run()`` so a multi-replica admission front
    (``launch.frontend.ReplicaFrontend``) can interleave N servers on one
    shared decode-step clock: each :meth:`tick` executes exactly one
    iteration of the serving loop — arrivals, admission, promote
    prefetch, one decode span — and ``limit_step`` caps how far the
    replica clock may advance, behaving exactly like a pending arrival at
    that step (span cap while busy, clock jump while idle). With
    ``limit_step=None`` the tick sequence is the pre-refactor ``run()``
    body line for line, which is what keeps the single-server token
    streams bitwise identical.

    Arrivals are measured on a per-run decode-step clock (deterministic,
    unlike wall time): a request joins the queue once
    ``clock >= arrive_step``; requests with the default ``arrive_step=0``
    reproduce the all-at-once legacy behavior exactly.
    """

    def __init__(self, srv: "BatchedServer", requests: List[Request]):
        self.srv = srv
        self.pending = sorted(requests, key=lambda r: r.arrive_step)
        self.queue: List[Request] = []
        self.clock = 0
        self.finished = False

    @property
    def live(self) -> bool:
        return any(s is not None for s in self.srv.slots)

    def add(self, req: Request) -> None:
        """Deliver one more request mid-run (frontend routing). Stable
        insert: same-step arrivals keep their delivery order, matching
        the sort in ``__init__``."""
        i = len(self.pending)
        while i > 0 and self.pending[i - 1].arrive_step > req.arrive_step:
            i -= 1
        self.pending.insert(i, req)
        self.finished = False

    def tick(self, limit_step: Optional[int] = None) -> bool:
        """One scheduler cycle (span ``tick``). Never advances ``clock``
        past ``limit_step`` (when given). Returns True while the loop is
        doing work or moving its clock; False once fully drained (also
        sets ``finished``)."""
        with self.srv.tracer.span("tick"):
            return self._tick(limit_step)

    def _tick(self, limit_step: Optional[int]) -> bool:
        srv = self.srv
        pending, queue = self.pending, self.queue
        if not (pending or queue or self.live):
            if limit_step is not None and limit_step > self.clock:
                # empty but clock-limited: the frontend may still route
                # arrivals here — follow the shared clock, don't drain
                self.clock = limit_step
                return True
            self.finished = True
            return False
        clock = self.clock
        srv._clock = clock
        while pending and pending[0].arrive_step <= clock:
            req = pending.pop(0)
            srv.tracer.req_arrive(req.rid, req.arrive_step,
                                  req.deadline_step)
            srv.slo_monitor.note_arrive(req.rid)
            queue.append(req)
        srv._admit(queue)
        srv._prefetch_promotes(queue)
        live = [i for i in range(srv.B) if srv.slots[i] is not None]
        if not live:
            # nothing runnable: everything admissible was admitted (or
            # rejected), so only a future arrival can change the state
            if pending:
                nxt = pending[0].arrive_step
                if limit_step is not None:
                    nxt = min(nxt, limit_step)
                self.clock = max(clock, nxt)
                return True
            if limit_step is not None and limit_step > clock:
                # idle but the frontend may still route arrivals here:
                # follow the shared clock instead of draining
                self.clock = limit_step
                return True
            self.finished = True
            return False
        span = srv._run_span()
        if pending:
            # cap the span at the next arrival so urgent latecomers
            # get an admission (and preemption) opportunity promptly
            span = max(1, min(span, pending[0].arrive_step - clock))
        if limit_step is not None:
            span = max(1, min(span, limit_step - clock))
        # device-resident state for the span: tokens advance
        # device-to-device; generated ids are fetched asynchronously and
        # materialized only at the span boundary
        tokens_dev = _upload(srv.tokens)
        pos_dev = _upload(srv.pos)
        live_mask = np.zeros((srv.B,), bool)
        live_mask[live] = True
        all_live = bool(live_mask.all())
        live_mask_dev = jnp.asarray(live_mask)
        live_inc = jnp.asarray(live_mask.astype(np.int32))
        fetches = []                       # (nxt_dev, owner snapshot)
        with srv.tracer.span("decode_span",
                             args={"steps": span, "rows": len(live),
                                   "step": clock}):
            for _ in range(span):
                with srv.tracer.span("decode_step"):
                    if srv.paged:
                        for i in live:
                            srv._ensure_page(i, int(srv.pos[i]))
                    pt = srv._page_table_dev() if srv.paged else None
                    with srv.tracer.span("dispatch"):
                        nxt, _, srv.caches = srv.decode(
                            srv.params, tokens_dev, pos_dev, srv.caches, pt)
                    srv.program_launches += 1
                    srv._count_attn_pages(srv.pos, srv.pos + 1, 1)
                    nxt.copy_to_host_async()
                    fetches.append((nxt, tuple(srv.slots)))
                    # idle slots hold their token (keeps runs reproducible
                    # across layouts even when idle rows share MoE
                    # capacity)
                    tokens_dev = (nxt if all_live
                                  else jnp.where(live_mask_dev, nxt,
                                                 tokens_dev))
                    pos_dev = pos_dev + live_inc
                    for i in live:
                        srv.pos[i] += 1
                        srv.slot_gen[i] += 1
                    srv.decode_steps += 1
                    srv._gen_tokens += len(live)
            # span boundary: materialize tokens (the host waits on the
            # device here)
            with srv.tracer.span("span_fetch"):
                last_np = None
                for nxt_dev, owners in fetches:
                    arr = np.asarray(nxt_dev)
                    last_np = arr
                    for i, req in enumerate(owners):
                        if req is not None:
                            if not req.out:
                                srv.tracer.req_first_token(req.rid)
                                srv.slo_monitor.note_first_token(req.rid)
                            req.out.append(int(arr[i]))
        if srv.pager is not None:
            # span boundary: resolve in-flight async page transfers —
            # their D2H copies ran concurrently with the decode span
            # above (the Chrome trace's pager track shows the overlap)
            srv.pager.drain()
        with srv.tracer.span("retire"):
            for i in live:
                srv.tokens[i] = int(last_np[i])
                req = srv.slots[i]
                if (srv.slot_gen[i] >= req.max_new
                        or srv.pos[i] >= srv.max_len - 1):
                    req.done = True
                    srv.slots[i] = None
                    srv._release_slot(i)
                    # everyone retiring here hit exactly span's end: span
                    # is the min remaining capacity over live slots
                    srv._note_finish(req, clock + span)
        self.clock = clock + span
        srv.slo_monitor.advance(span)
        if srv._snapshotter is not None:
            srv._snapshotter.maybe_emit(srv.decode_steps)
        return True

    def close(self) -> None:
        """Final pager drain (the epilogue ``run()`` always executed)."""
        if self.srv.pager is not None:
            self.srv.pager.drain()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--kv-bits", type=int, default=0,
                    help="0=fp cache, 8=int8 pages/grid, 4=int4 "
                         "(lane-packed when --page-size > 0)")
    ap.add_argument("--page-size", type=int, default=0,
                    help="tokens per KV page; 0 = dense max_len cache")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="shared pool pages (0 = full capacity)")
    ap.add_argument("--attn-impl", choices=["gather", "pallas"],
                    default="gather",
                    help="paged decode backend: jnp gather (bitwise "
                         "reference) or the Pallas paged-attention kernel "
                         "(interpret-mode on CPU)")
    ap.add_argument("--prefill", choices=["auto", "bucketed", "stepwise"],
                    default="auto",
                    help="bucketed = chunked prefill jit straight into the "
                         "paged pool; stepwise = slot-granular reference")
    ap.add_argument("--prefill-bucket", type=int, default=32,
                    help="max power-of-two prompt chunk for bucketed prefill")
    ap.add_argument("--prefill-batch", type=int, default=0,
                    help="max same-bucket prompts stacked into ONE batched "
                         "prefill forward per admission cycle (0 = auto: "
                         "the batch size — intra-wave prefix sharing is "
                         "handled by wave dedupe, so this composes with "
                         "--prefix-cache; 1 = sequential reference)")
    ap.add_argument("--kv-profile", default="",
                    help="path to a core.policy.PrecisionPolicy JSON (e.g. "
                         "core.search output): per-layer KV containers — "
                         "int4 pages for <=4 data bits, int8 for <=8, float "
                         "pages for fp32 layers (paged, attention-only "
                         "archs; supersedes --kv-bits)")
    ap.add_argument("--kv-scale", choices=["static", "page"],
                    default="static",
                    help="paged dequant scales: static = the layer's Q(I,F) "
                         "grid (bitwise-reproducible reference); page = "
                         "dynamic per-page max-abs calibration")
    ap.add_argument("--prefix-cache", choices=["on", "off"], default="off",
                    help="share page-aligned common prompt prefixes across "
                         "requests (refcounted aliasing + copy-on-write; "
                         "LRU eviction of unreferenced prefixes under pool "
                         "pressure)")
    ap.add_argument("--kv-profile-scan", choices=["group", "unroll"],
                    default="group",
                    help="per-layer profile forward: group contiguous "
                         "same-container layer runs into lax.scan segments "
                         "(default) or force the fully unrolled reference")
    ap.add_argument("--kv-offload", choices=["none", "host"], default="none",
                    help="host = add a host-memory page tier: pool pressure "
                         "DEMOTES cached prefixes (packed containers ride "
                         "along) instead of destroying them; enables "
                         "preemption and snapshot persistence")
    ap.add_argument("--host-pages", type=int, default=0,
                    help="host-tier capacity in pages (0 = unbounded)")
    ap.add_argument("--kv-adapt", choices=["off", "on"], default="off",
                    help="on = online precision adaptation: pool pressure "
                         "REQUANTIZES cold cached prefix pages one "
                         "container step narrower (fp->int8->int4, fresh "
                         "per-page max-abs scales) into a bounded device "
                         "tier BEFORE any host demotion or drop; needs "
                         "--prefix-cache on")
    ap.add_argument("--kv-adapt-pages", type=int, default=0,
                    help="adaptation-tier byte budget, quoted in "
                         "floor-container page equivalents (0 = auto: the "
                         "pool's usable page count)")
    ap.add_argument("--kv-adapt-floor", type=int, choices=[4, 8], default=4,
                    help="narrowest container requantization may reach "
                         "(per-pool: a layer whose head_dim cannot "
                         "lane-pack floors at int8 regardless)")
    ap.add_argument("--sched", choices=["fifo", "slo"], default="fifo",
                    help="admission order: fifo = legacy arrival order "
                         "(too-large heads are skipped, not stalled "
                         "behind); slo = priority + earliest-deadline with "
                         "bounded out-of-order admission and preemption")
    ap.add_argument("--admit-window", type=int, default=4,
                    help="SLO sched: max requests admitted past a deferred "
                         "head per cycle")
    ap.add_argument("--no-preempt", action="store_true",
                    help="SLO sched: disable preemption of running "
                         "requests")
    ap.add_argument("--predictor", choices=["off", "on"], default="off",
                    help="on = consult the online deadline-miss predictor "
                         "every admission cycle: gates NEW speculative "
                         "(no-deadline) admissions while the hazard says "
                         "an overload is in progress; trains on retired "
                         "deadlined requests' outcomes; needs --sched slo")
    ap.add_argument("--pager-async", choices=["off", "on"], default="off",
                    help="on = double-buffered async host-tier transfers: "
                         "demote/offload D2H copies are issued immediately "
                         "and resolved at the next decode-span boundary, "
                         "overlapping decode compute; needs --kv-offload "
                         "host")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree for ONE serving replica: "
                         "builds a (n_devices//tp, tp) data x model mesh "
                         "(launch.mesh.make_serving_mesh) and shards the "
                         "attention-head axis of weights AND the paged KV "
                         "pool over 'model' (per-page scales replicate; "
                         "int4 lane-packed words shard along heads). 1 = "
                         "single-device reference. CI exercises tp>1 on "
                         "virtual host devices")
    ap.add_argument("--prefix-snapshot", default="",
                    help="path: restore the prefix cache from it at start "
                         "(if the file exists) and snapshot back at exit — "
                         "cached prefixes survive server restarts")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics", choices=["off", "on"], default="off",
                    help="on = record request-lifecycle spans (arrival/"
                         "admit/defer/reject/prefill/decode/preempt/"
                         "resume/finish) on a monotonic clock and report "
                         "an SLO summary (p50/p99 TTFT+TPOT, goodput). "
                         "The metrics registry itself is always live; "
                         "tokens are identical either way")
    ap.add_argument("--trace-out", default="",
                    help="path: export the request-lifecycle trace as "
                         "Chrome trace-event JSON (load in chrome://"
                         "tracing or https://ui.perfetto.dev). Implies "
                         "--metrics on")
    ap.add_argument("--metrics-out", default="",
                    help="path: append a JSONL registry snapshot every "
                         "--metrics-every decode steps. Implies "
                         "--metrics on")
    ap.add_argument("--metrics-every", type=int, default=50,
                    help="decode steps between JSONL snapshots "
                         "(with --metrics-out)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.trace_out or args.metrics_out:
        args.metrics = "on"

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.family == "encoder":
        raise SystemExit("encoder-only archs have no decode path")
    params = init_model(jax.random.PRNGKey(args.seed), cfg)
    rng = np.random.default_rng(args.seed)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size,
                                    args.prompt_len).astype(np.int32),
                    args.max_new)
            for i in range(args.requests)]
    kv_profile = None
    if args.kv_profile:
        with open(args.kv_profile) as f:
            kv_profile = PrecisionPolicy.from_json(f.read())
    srv = BatchedServer(cfg, params, batch_size=args.batch_size,
                        max_len=args.max_len, kv_bits=args.kv_bits,
                        page_size=args.page_size,
                        num_pages=args.num_pages or None,
                        attn_impl=args.attn_impl, prefill=args.prefill,
                        prefill_bucket=args.prefill_bucket,
                        prefill_batch=args.prefill_batch,
                        kv_profile=kv_profile, kv_scale=args.kv_scale,
                        prefix_cache=args.prefix_cache,
                        kv_profile_scan=args.kv_profile_scan,
                        kv_offload=args.kv_offload,
                        host_pages=args.host_pages or None,
                        sched=args.sched, admit_window=args.admit_window,
                        preempt=False if args.no_preempt else None,
                        kv_adapt=args.kv_adapt,
                        adapt_pages=args.kv_adapt_pages,
                        adapt_floor_bits=args.kv_adapt_floor,
                        metrics=args.metrics,
                        snapshot_out=args.metrics_out or None,
                        snapshot_every=args.metrics_every,
                        predictor=args.predictor,
                        pager_async=args.pager_async,
                        mesh=make_serving_mesh(args.tp)
                        if args.tp > 1 else None)
    import os
    if args.prefix_snapshot and os.path.exists(
            snapshot_path(args.prefix_snapshot)):
        n = srv.restore_prefix_cache(args.prefix_snapshot)
        print(f"[serve] restored {n} prefix pages from "
              f"{args.prefix_snapshot} (host tier)")
    srv.run(reqs, verbose=True)
    if args.prefix_snapshot:
        n = srv.snapshot_prefix_cache(args.prefix_snapshot)
        print(f"[serve] snapshotted {n} prefix pages to "
              f"{args.prefix_snapshot}")
    if args.metrics == "on":
        slo = srv.tracer.slo_summary()
        ttft = slo.get("ttft_p50_s")
        tpot = slo.get("tpot_p50_s")
        goodput = slo.get("goodput")
        print(f"[serve] slo: "
              f"goodput={'n/a' if goodput is None else format(goodput, '.3f')} "
              f"({slo['finished']}/{slo['requests']} finished, "
              f"{slo['deadline_misses']} deadline misses), "
              f"ttft p50={0.0 if ttft is None else ttft * 1e3:.1f}ms "
              f"p99={0.0 if slo['ttft_p99_s'] is None else slo['ttft_p99_s'] * 1e3:.1f}ms, "
              f"tpot p50={0.0 if tpot is None else tpot * 1e3:.2f}ms")
    if args.trace_out:
        srv.tracer.export_chrome(args.trace_out)
        print(f"[serve] wrote {len(srv.tracer.events)} trace events to "
              f"{args.trace_out}")
    for r in reqs[:4]:
        print(f"  req {r.rid}: {len(r.out)} tokens -> {r.out[:8]}...")
    return reqs


if __name__ == "__main__":
    main()
