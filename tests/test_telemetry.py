"""Telemetry-layer tests: registry semantics, the metric_attr bridge,
kv-inventory/gauge reconciliation, trace integrity over a preempt/resume
run, and the metrics on/off bitwise-identity contract.

* MetricsRegistry: exact nearest-rank percentiles over raw observations,
  reset()/checkpoint()/since() warmup-boundary semantics, gauge callbacks.
* metric_attr: legacy instance-attribute reads/writes (``srv.x += 1``,
  hand-zeroing) land on the owning registry's counters.
* kv_inventory() scalars must reconcile byte-for-byte with the registered
  ``kv.*`` gauges AND with caches_kv_bytes over the live pools — one
  schema shared by the dict, the snapshot stream, and direct gauge reads.
* A preempt/resume trace must export valid Chrome trace-event JSON:
  non-negative monotonic-clock timestamps, spans on one track disjoint or
  properly nested, lifecycle instants ordered arrive <= admit <=
  first_token <= finish, and the victim's track showing offload + resume
  spans that do not overlap.
* ``--metrics off`` must be token-identical to a server built without the
  flag, and ``--metrics on`` must change tokens nowhere, at kv-bits
  {0, 8, 4} (subprocess, single-threaded XLA — same pattern as the other
  bitwise-identity suites).
* Under a profiler session the serving loop's spans appear as
  ``serve.*`` host events, nested tick > admission > admit and
  tick > decode_span > decode_step > dispatch, whatever the metrics mode;
  the device programs lower as ``jit_decode_step`` and
  ``jit_chunk_prefill_step``, on a mesh too.
"""
import glob
import json
import os

import jax
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.launch.serve import BatchedServer, Request
from repro.models.transformer import init_model
from repro.runtime.telemetry import (Ewma, MetricsRegistry,
                                     MetricsSnapshotter, NullTracer,
                                     SLOMonitor, Tracer, make_tracer,
                                     metric_attr, percentile)

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(scope="module")
def smoke_model():
    cfg = get_smoke_config("qwen2-72b")
    params = init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


# ---------------------------------------------------------------------------
# Registry primitives
# ---------------------------------------------------------------------------
def test_percentile_exact_nearest_rank():
    xs = list(range(1, 101))        # 1..100
    assert percentile(xs, 50) == 50
    assert percentile(xs, 99) == 99
    assert percentile(xs, 100) == 100
    assert percentile(xs, 0) == 1   # nearest-rank floors at the minimum
    assert percentile([7.5], 50) == 7.5
    assert percentile([], 50) is None
    # unsorted input is sorted internally
    assert percentile([3, 1, 2], 50) == 2


def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    c = reg.counter("c")
    c.inc()
    c.inc(2.5)
    assert reg.counter("c") is c            # stable object per name
    assert c.value == 3.5
    c.value = 4                             # hand-assignment (bench idiom)
    assert c.value == 4 and isinstance(c.value, int)

    reg.gauge("g").set(7)
    assert reg.gauge("g").value == 7
    backing = {"v": 11}
    reg.register_gauge("live", lambda: backing["v"])
    assert reg.gauge("live").value == 11
    backing["v"] = 13                       # callback gauges read live state
    assert reg.gauge("live").value == 13

    h = reg.histogram("h")
    for v in range(1, 101):
        h.observe(v)
    assert h.count == 100
    assert h.percentile(50) == 50 and h.percentile(99) == 99
    s = h.summary()
    assert s["min"] == 1 and s["max"] == 100 and s["p50"] == 50

    assert reg.value("c") == 4
    assert reg.value("live") == 13
    assert reg.value("h") == 100
    with pytest.raises(KeyError):
        reg.value("nope")


def test_registry_reset_checkpoint_since():
    reg = MetricsRegistry()
    c = reg.counter("n")
    c.inc(5)
    reg.histogram("h").observe(1.0)
    reg.gauge("g").set(9)

    mark = reg.checkpoint()
    c.inc(3)
    reg.counter("new_after_mark").inc(2)
    delta = reg.since(mark)
    assert delta["n"] == 3 and delta["new_after_mark"] == 2

    reg.reset()
    assert c.value == 0                     # the held object was zeroed...
    assert reg.counter("n") is c            # ...not replaced
    assert reg.histogram("h").count == 0
    assert reg.gauge("g").value == 9        # gauges are state, not counts


def test_metric_attr_routes_through_registry():
    class Thing:
        hits = metric_attr("thing.hits")

        def __init__(self):
            self.metrics = MetricsRegistry()

    a, b = Thing(), Thing()
    a.hits += 1
    a.hits += 1
    b.hits = 40
    assert a.hits == 2 and b.hits == 40     # per-instance registries
    assert a.metrics.counter("thing.hits").value == 2
    a.metrics.reset()
    assert a.hits == 0 and b.hits == 40


def test_make_tracer_and_null_surface(tmp_path, caplog):
    assert isinstance(make_tracer("on"), Tracer)
    null = make_tracer("off")
    assert isinstance(null, NullTracer) and not null.enabled
    with pytest.raises(ValueError, match="metrics"):
        make_tracer("maybe")
    # the disabled surface: spans are reusable null contexts, reductions
    # are empty, exporting is a warned no-op (returns None, writes no
    # file) instead of the PR 8 RuntimeError footgun
    with null.span("x"):
        with null.req_span(0, "y"):
            null.req_arrive(0, 0)
            null.req_finish(0, 1, 1)
    null.pager_span("pager.demote", 0.0, 1.0)
    assert null.request_stats() == [] and null.slo_summary() == {}
    assert null.chrome_trace()["traceEvents"] == []
    path = tmp_path / "t.json"
    import logging
    with caplog.at_level(logging.WARNING, "repro.runtime.telemetry"):
        assert null.export_chrome(str(path)) is None
    assert not path.exists(), "NullTracer export must not write a file"
    assert any("disabled" in r.getMessage() for r in caplog.records)


def test_snapshotter_jsonl_stream(tmp_path):
    reg = MetricsRegistry()
    reg.counter("c").inc(4)
    path = str(tmp_path / "metrics.jsonl")
    snap = MetricsSnapshotter(reg, path, every=10)
    assert snap.maybe_emit(0) is True       # first window
    assert snap.maybe_emit(5) is False      # same window
    reg.counter("c").inc(1)
    assert snap.maybe_emit(10) is True      # next window
    lines = [json.loads(ln) for ln in open(path)]
    assert [ln["cycle"] for ln in lines] == [0, 10]
    assert lines[0]["counters"]["c"] == 4
    assert lines[1]["counters"]["c"] == 5
    assert all(ln["elapsed_s"] >= 0 for ln in lines)
    with pytest.raises(ValueError, match="interval"):
        MetricsSnapshotter(reg, path, every=0)


# ---------------------------------------------------------------------------
# kv_inventory == registry gauges == live pool bytes
# ---------------------------------------------------------------------------
def test_kv_inventory_reconciles_with_gauges(smoke_model):
    from repro.core.paged_kv import caches_kv_bytes
    cfg, params = smoke_model
    srv = BatchedServer(cfg, params, batch_size=2, max_len=32, kv_bits=8,
                        page_size=8, prefix_cache="on", kv_offload="host",
                        sched="slo", metrics="on")
    rng = np.random.default_rng(3)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, 7 + i)
                    .astype(np.int32), 4) for i in range(3)]
    srv.run(reqs)
    inv = srv.kv_inventory()
    # one schema: the dict's scalars ARE the registered kv.* gauges
    g = srv.metrics.gauge
    assert inv["device_bytes"] == g("kv.device_bytes").value
    assert inv["device_pages_free"] == g("kv.device_pages_free").value
    assert inv["device_pages_usable"] == g("kv.device_pages_usable").value
    assert inv["host_bytes"] == g("kv.host_bytes").value
    assert inv["host_pages"] == g("kv.host_pages").value
    assert inv["tier_bytes"] == g("kv.tier_bytes").value
    assert inv["tier_pages"] == g("kv.tier_pages").value
    # ...and the gauges reconcile with the live pools
    assert inv["device_bytes"] == sum(caches_kv_bytes(srv.caches).values())
    assert inv["device_bytes"] == sum(inv["device_by_container"].values())
    assert inv["device_pages_free"] == srv.allocator.num_free
    assert inv["device_pages_usable"] == srv.allocator.num_usable
    assert inv["host_bytes"] == srv.host_store.nbytes
    assert inv["host_pages"] == srv.host_store.num_pages
    # the registry path is live, not a construction-time copy: park a page
    # on the host tier and re-read
    before = inv["host_pages"]
    from repro.core.page_store import extract_page
    blob = extract_page(srv.caches, 1)
    h = srv.host_store.put(blob)
    inv2 = srv.kv_inventory()
    assert inv2["host_pages"] == before + 1
    assert inv2["host_bytes"] == srv.host_store.nbytes > 0
    srv.host_store.pop(h)


def test_kv_inventory_unpaged_is_zero(smoke_model):
    cfg, params = smoke_model
    srv = BatchedServer(cfg, params, batch_size=2, max_len=32)
    inv = srv.kv_inventory()
    assert inv["device_bytes"] == 0 and inv["device_by_container"] == {}


# ---------------------------------------------------------------------------
# Trace integrity over a preempt/resume run
# ---------------------------------------------------------------------------
def _spans_disjoint_or_nested(spans, eps=0.5):
    """Every pair of X intervals on one track must be disjoint or properly
    nested (eps in us absorbs float jitter at shared boundaries)."""
    ivs = [(e["ts"], e["ts"] + e["dur"], e["name"]) for e in spans]
    for i in range(len(ivs)):
        for j in range(i + 1, len(ivs)):
            a0, a1, an = ivs[i]
            b0, b1, bn = ivs[j]
            disjoint = a1 <= b0 + eps or b1 <= a0 + eps
            a_in_b = b0 <= a0 + eps and a1 <= b1 + eps
            b_in_a = a0 <= b0 + eps and b1 <= a1 + eps
            assert disjoint or a_in_b or b_in_a, (
                f"overlapping spans on one track: {an} [{a0},{a1}] vs "
                f"{bn} [{b0},{b1}]")


def test_trace_integrity_preempt_resume(smoke_model):
    cfg, params = smoke_model
    srv = BatchedServer(cfg, params, batch_size=1, max_len=48, kv_bits=4,
                        page_size=8, num_pages=4, kv_offload="host",
                        sched="slo", metrics="on")
    rng = np.random.default_rng(2)
    low = Request(0, rng.integers(0, cfg.vocab_size, 9).astype(np.int32),
                  16, priority=0)
    hi = Request(1, rng.integers(0, cfg.vocab_size, 9).astype(np.int32),
                 6, priority=5, arrive_step=4, deadline_step=20)
    srv.run([low, hi])
    assert low.done and hi.done and srv.preempt_count >= 1

    trace = srv.tracer.chrome_trace()
    # Chrome trace-event JSON: round-trips, only known phases, sane fields
    trace = json.loads(json.dumps(trace))
    events = trace["traceEvents"]
    assert trace["displayTimeUnit"] == "ms"
    assert {e["ph"] for e in events} <= {"X", "i", "M"}
    xs = [e for e in events if e["ph"] == "X"]
    assert xs, "no complete spans recorded"
    for e in events:
        assert e["pid"] == 0
        if e["ph"] == "M":
            continue
        assert e["ts"] >= 0, e
        if e["ph"] == "X":
            assert e["dur"] >= 0, e
    # the engine track saw decode spans and admission waves
    engine_names = {e["name"] for e in xs if e["tid"] == 0}
    assert "decode_span" in engine_names and "admission" in engine_names

    # spans on any one track are disjoint or properly nested
    for tid in {e["tid"] for e in xs}:
        _spans_disjoint_or_nested([e for e in xs if e["tid"] == tid])

    # per-request lifecycle instants are causally ordered
    for rid in (0, 1):
        tid = 1 + rid
        inst = {e["name"]: e["ts"] for e in events
                if e["ph"] == "i" and e["tid"] == tid}
        assert inst["arrive"] <= inst["admit"] \
            <= inst["first_token"] <= inst["finish"]
    # track names were emitted for both request tracks
    names = {e["args"]["name"] for e in events if e["ph"] == "M"
             and e["name"] == "thread_name"}
    assert {"engine", "req 0", "req 1"} <= names

    # the victim's track shows the offload and resume spans, not overlapping
    victim = [e for e in xs if e["tid"] == 1 + low.rid]
    offloads = [e for e in victim if e["name"] == "offload"]
    resumes = [e for e in victim if e["name"] == "resume"]
    assert offloads and resumes, "victim track missing offload/resume spans"
    for o in offloads:
        for r in resumes:
            assert (o["ts"] + o["dur"] <= r["ts"]
                    or r["ts"] + r["dur"] <= o["ts"]), \
                "offload and resume spans overlap"
    # preempt instant precedes the resume span
    pre = [e["ts"] for e in events if e["ph"] == "i"
           and e["tid"] == 1 + low.rid and e["name"] == "preempt"]
    assert pre and min(pre) <= resumes[0]["ts"]

    # the lifecycle records reduce correctly
    stats = {s["rid"]: s for s in srv.tracer.request_stats()}
    assert stats[0]["preemptions"] >= 1 and stats[0]["resumed"] >= 1
    assert stats[0]["finished"] and stats[1]["finished"]
    assert stats[1]["met_deadline"], stats[1]
    assert stats[0]["tokens"] == 16 and stats[1]["tokens"] == 6
    slo = srv.tracer.slo_summary()
    assert slo["requests"] == 2 and slo["finished"] == 2
    assert slo["preemptions"] == srv.preempt_count
    assert slo["deadlined"] == 1
    assert slo["ttft_p50_s"] is not None and slo["ttft_p50_s"] >= 0
    assert slo["tpot_p50_s"] is not None and slo["tpot_p50_s"] >= 0

    # export writes loadable JSON
    import tempfile, os
    path = os.path.join(tempfile.mkdtemp(prefix="trace_"), "t.json")
    srv.tracer.export_chrome(path)
    with open(path) as f:
        assert json.load(f)["traceEvents"] == events


def test_rid_reuse_opens_fresh_incarnation(smoke_model):
    """Warm bench passes re-offer the same rids; each arrival must open a
    fresh lifecycle record instead of merging into (or corrupting) the
    finished one."""
    cfg, params = smoke_model
    srv = BatchedServer(cfg, params, batch_size=1, max_len=32, kv_bits=8,
                        page_size=8, metrics="on")
    rng = np.random.default_rng(4)
    mk = lambda: [Request(0, rng.integers(0, cfg.vocab_size, 5)
                          .astype(np.int32), 3)]
    srv.run(mk())
    srv.run(mk())
    stats = srv.tracer.request_stats()
    assert len(stats) == 2
    assert all(s["rid"] == 0 and s["finished"] for s in stats)
    assert srv.tracer.slo_summary()["requests"] == 2


# ---------------------------------------------------------------------------
# --metrics off == seed, --metrics on changes tokens nowhere (subprocess)
# ---------------------------------------------------------------------------
_METRICS_IDENTITY_SCRIPT = r"""
import jax, numpy as np
jax.config.update("jax_platform_name", "cpu")
from repro.configs.registry import get_smoke_config
from repro.launch.serve import BatchedServer, Request
from repro.models.transformer import init_model

cfg = get_smoke_config("qwen2-72b")
params = init_model(jax.random.PRNGKey(0), cfg)

def mk():
    rng = np.random.default_rng(7)
    lens = [1, 7, 9, 3, 21]
    return [Request(i, rng.integers(0, cfg.vocab_size, L).astype(np.int32),
                    5 + (i % 3)) for i, L in enumerate(lens)]

for kv_bits in (0, 8, 4):
    base = dict(batch_size=3, max_len=32, kv_bits=kv_bits, page_size=8,
                prefill="bucketed", prefill_bucket=8)
    seed = BatchedServer(cfg, params, **base)          # no metrics kwarg
    out_seed = seed.run(mk())
    off = BatchedServer(cfg, params, metrics="off", **base)
    out_off = off.run(mk())
    on = BatchedServer(cfg, params, metrics="on", **base)
    out_on = on.run(mk())
    for a, b, c in zip(out_seed, out_off, out_on):
        assert a.out == b.out, ("off", kv_bits, a.rid, a.out, b.out)
        assert a.out == c.out, ("on", kv_bits, a.rid, a.out, c.out)
    assert all(r.done for r in out_on)
    assert len(on.tracer.events) > 0 and len(off.tracer.events) == 0
    assert on.tracer.slo_summary()["finished"] == len(out_on)
    print(f"kv_bits={kv_bits} tokens identical across seed/off/on")
print("METRICS_IDENTITY_OK")
"""


def test_metrics_modes_are_token_neutral():
    """--metrics off is token-identical to a server built without the flag,
    and --metrics on changes tokens nowhere, at kv-bits {0, 8, 4}.

    Runs in a subprocess with single-threaded XLA: multi-threaded XLA:CPU
    GEMMs are not bitwise deterministic under thread contention, and exact
    argmax token identity needs bitwise-equal logits."""
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["XLA_FLAGS"] = ("--xla_cpu_multi_thread_eigen=false "
                        "intra_op_parallelism_threads=1 "
                        + env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = os.pathsep.join(
        [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
        + [os.path.join(os.path.dirname(__file__), "..", "src")])
    res = subprocess.run([sys.executable, "-c", _METRICS_IDENTITY_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=1200)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "METRICS_IDENTITY_OK" in res.stdout


def test_scattered_counters_share_one_registry(smoke_model):
    """The server threads ONE registry through allocator, scheduler, prefix
    cache and tiers: the migrated legacy attributes and the registry read
    the same storage."""
    cfg, params = smoke_model
    srv = BatchedServer(cfg, params, batch_size=2, max_len=32, kv_bits=8,
                        page_size=8, prefix_cache="on", sched="slo")
    rng = np.random.default_rng(9)
    sys_p = rng.integers(0, cfg.vocab_size, 9).astype(np.int32)
    reqs = [Request(i, np.concatenate(
                [sys_p, rng.integers(0, cfg.vocab_size, 2 + i)
                 .astype(np.int32)]), 3) for i in range(3)]
    srv.run(reqs)
    m = srv.metrics
    assert srv.prefill_forwards == m.counter("serve.prefill_forwards").value
    assert srv.decode_steps == m.counter("serve.decode_steps").value > 0
    assert srv.prefix_cache.lookups == m.counter("prefix.lookups").value > 0
    assert srv.prefix_cache.hits == m.counter("prefix.hits").value
    assert (srv.scheduler.ooo_admissions
            == m.counter("sched.ooo_admissions").value)
    assert m.counter("alloc.allocs").value > 0
    assert m.gauge("alloc.free_pages").value == srv.allocator.num_free
    # two servers never share counters (per-server registries)
    other = BatchedServer(cfg, params, batch_size=2, max_len=32, kv_bits=8,
                          page_size=8)
    assert other.metrics is not m
    assert other.metrics.counter("serve.decode_steps").value == 0


# ---------------------------------------------------------------------------
# slo_summary edge cases + SLOMonitor rolling windows
# ---------------------------------------------------------------------------
def test_slo_summary_zero_requests():
    """An untouched tracer summarises to zeros/None, never NaN or a raise."""
    tr = Tracer()
    s = tr.slo_summary()
    assert s["requests"] == 0 and s["finished"] == 0
    assert s["goodput"] is None
    for k in ("ttft_p50_s", "ttft_p99_s", "tpot_p50_s", "tpot_p99_s"):
        assert s[k] is None


def test_slo_summary_all_deferred():
    """Requests that arrive but never admit (gate closed all run) count as
    offered-but-not-good: goodput 0.0 with None percentiles."""
    tr = Tracer()
    for rid in range(3):
        tr.req_arrive(rid, step=0, deadline_step=10)
        tr.req_defer(rid, step=1)
        tr.req_defer(rid, step=2)
    s = tr.slo_summary()
    assert s["requests"] == 3 and s["finished"] == 0
    assert s["goodput"] == 0.0
    assert s["deadline_misses"] == 3
    assert s["ttft_p50_s"] is None and s["tpot_p50_s"] is None
    assert all(st["defers"] == 2 for st in tr.request_stats())


def test_slo_summary_never_first_token():
    """A request that finishes without ever emitting a token (e.g. rejected
    after admit, or zero-token cap) has None TTFT/TPOT; percentiles are
    drawn only from requests that actually emitted tokens."""
    tr = Tracer()
    tr.req_arrive(0, step=0)
    tr.req_admit(0, step=0)
    tr.req_finish(0, step=3, tokens=0)       # no req_first_token ever
    tr.req_arrive(1, step=0)
    tr.req_admit(1, step=0)
    tr.req_first_token(1)
    tr.req_finish(1, step=4, tokens=5)
    stats = {s["rid"]: s for s in tr.request_stats()}
    assert stats[0]["ttft_s"] is None and stats[0]["tpot_s"] is None
    assert stats[0]["finished"] and stats[0]["met_deadline"]
    assert stats[1]["ttft_s"] is not None and stats[1]["tpot_s"] is not None
    s = tr.slo_summary()
    assert s["goodput"] == 1.0               # both no-deadline + finished
    assert s["ttft_p50_s"] == stats[1]["ttft_s"]
    assert s["tpot_p50_s"] == stats[1]["tpot_s"]


def test_slo_monitor_window_reductions_and_gauges():
    reg = MetricsRegistry()
    mon = SLOMonitor(reg, window=4)
    g = reg.snapshot()["gauges"]
    # empty window: gauges read 0.0, window_requests disambiguates
    assert g["slo.window_requests"] == 0
    assert g["slo.window_goodput"] == 0.0
    assert mon.window_goodput() is None      # the method keeps the None
    assert g["slo.window_ttft_p50_s"] == 0.0
    # feed 6 finishes through a window of 4: only the last 4 count
    for rid in range(6):
        mon.note_arrive(rid)
        mon.note_first_token(rid)
        mon.note_finish(rid, met=(rid >= 2), tokens=8)
    g = reg.snapshot()["gauges"]
    assert g["slo.window_requests"] == 4
    assert mon.window_goodput() == 1.0       # rids 2..5 all met
    assert g["slo.window_goodput"] == 1.0
    assert mon.window_ttft(50) is not None and mon.window_ttft(50) >= 0.0
    assert mon.window_tpot(99) is not None and mon.window_tpot(99) >= 0.0
    # a rejection is one window sample with met=False, no TPOT
    mon.note_arrive(99)
    mon.note_finish(99, met=False, tokens=0)
    assert mon.window_goodput() == 0.75


def test_slo_monitor_advance_and_slowdown_clipping():
    reg = MetricsRegistry()
    mon = SLOMonitor(reg, window=4)
    # advance folds pending arrivals into a per-step rate EWMA
    for rid in range(6):
        mon.note_arrive(rid)
    mon.advance(steps=3)
    assert mon.arrival_rate.get() == pytest.approx(2.0)
    mon.advance(steps=5)                     # no new arrivals -> decays
    assert 0.0 < mon.arrival_rate.get() < 2.0
    mon.note_queue_depth(10)
    assert mon.queue_depth.get() == 10.0
    # slowdown: 0 before any TPOT sample, then clipped to +/-0.25
    assert mon.tpot_slowdown() == 0.0
    mon.tpot.value, mon.tpot_ref.value = 10.0, 1.0
    assert mon.tpot_slowdown() == 0.25
    mon.tpot.value = 0.1
    assert mon.tpot_slowdown() == -0.25
    mon.tpot.value = 1.05
    assert mon.tpot_slowdown() == pytest.approx(0.05)
    with pytest.raises(ValueError):
        SLOMonitor(MetricsRegistry(), window=0)
    with pytest.raises(ValueError):
        Ewma(alpha=0.0)


# ---------------------------------------------------------------------------
# Spans on the profiler clock, and the device programs' names
# ---------------------------------------------------------------------------
def _profiled(fn, trace_dir):
    """Run ``fn`` under a profiler session; return its result and the
    ``serve.*`` host events as dicts (name, t0, t1, stats)."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(trace_dir))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    events.append({"name": ev.name[len("serve."):],
                                   "t0": ev.start_ns,
                                   "t1": ev.start_ns + ev.duration_ns,
                                   "stats": dict(ev.stats)})
    return out, events


def _parent(ev, events):
    """Name of the innermost other event that covers ``ev`` (None if
    none)."""
    best = None
    for o in events:
        if o is ev or not (o["t0"] <= ev["t0"] and ev["t1"] <= o["t1"]):
            continue
        if best is None or o["t1"] - o["t0"] < best["t1"] - best["t0"]:
            best = o
    return None if best is None else best["name"]


def test_span_primitive_writes_profiler_events(tmp_path):
    """Both tracers' spans (request-track ones too) enter the profiler
    annotation ``serve.<name>`` with their args as stats."""
    on, off = Tracer(), NullTracer()

    def body():
        with on.span("outer", args={"steps": 3}):
            with off.span("inner", args={"rows": 2}):
                with off.req_span(0, "offload"):
                    pass
            with on.req_span(1, "resume"):
                pass
    _, events = _profiled(body, tmp_path)
    by = {e["name"]: e for e in events}
    assert set(by) == {"outer", "inner", "offload", "resume"}
    assert by["outer"]["stats"] == {"steps": 3}
    assert by["inner"]["stats"] == {"rows": 2}
    assert _parent(by["inner"], events) == "outer"
    assert _parent(by["offload"], events) == "inner"
    assert _parent(by["resume"], events) == "outer"
    # the live tracer still keeps its Chrome events under the bare names
    assert {e["name"] for e in on.events if e["ph"] == "X"} \
        == {"outer", "resume"}


# where each serving-loop span sits: the innermost span around it
_SPAN_PARENTS = {
    "tick": {None},
    "admission": {"tick"},
    "admit": {"admission"},
    "prefill_chunk": {"admission"},
    "decode_span": {"tick"},
    "decode_step": {"decode_span"},
    "span_fetch": {"decode_span"},
    "dispatch": {"decode_step", "prefill_chunk"},
    "retire": {"tick"},
}


def test_profiler_trace_holds_serving_spans(smoke_model, tmp_path):
    """``metrics="off"``: a profiler session over a ServeLoop run still
    holds every serving span, nested as the loop runs them, with the
    host values the loop had at hand as args."""
    cfg, params = smoke_model
    srv = BatchedServer(cfg, params, batch_size=2, max_len=32, kv_bits=8,
                        page_size=8, prefill="bucketed", prefill_bucket=8,
                        metrics="off")
    rng = np.random.default_rng(5)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, 6 + 3 * i)
                    .astype(np.int32), 3 + i) for i in range(3)]
    srv.run([Request(9, reqs[0].prompt, 2)])        # compile outside
    steps0 = srv.decode_steps

    def serve():
        loop = srv.start_loop(reqs)
        ticks = 0
        while loop.tick():
            ticks += 1
        return ticks
    ticks, events = _profiled(serve, tmp_path)
    assert all(r.done for r in reqs)
    assert srv.tracer.events == []                 # off records nothing
    names = {e["name"] for e in events}
    assert names == set(_SPAN_PARENTS), names
    for ev in events:
        assert _parent(ev, events) in _SPAN_PARENTS[ev["name"]], ev
    count = lambda n: sum(1 for e in events if e["name"] == n)
    assert count("tick") == ticks + 1              # the draining tick too
    assert sorted(e["stats"]["rid"] for e in events
                  if e["name"] == "admit") == [0, 1, 2]
    spans = [e for e in events if e["name"] == "decode_span"]
    assert sum(e["stats"]["steps"] for e in spans) == count("decode_step")
    assert all(e["stats"]["rows"] >= 1 for e in spans)
    assert count("span_fetch") == len(spans)
    assert count("dispatch") == count("decode_step") + count("prefill_chunk")
    assert count("decode_step") == srv.decode_steps - steps0
    assert all(e["stats"]["bucket"] <= 8 for e in events
               if e["name"] == "prefill_chunk")


def test_chrome_export_keeps_span_names(smoke_model):
    """``metrics="on"``: the Chrome export keeps the span names it always
    had, bare (no ``serve.`` prefix), beside the new ones."""
    cfg, params = smoke_model
    srv = BatchedServer(cfg, params, batch_size=2, max_len=32, kv_bits=8,
                        page_size=8, prefill="bucketed", prefill_bucket=8,
                        metrics="on")
    rng = np.random.default_rng(6)
    srv.run([Request(i, rng.integers(0, cfg.vocab_size, 9)
                     .astype(np.int32), 3) for i in range(2)])
    xs = [e for e in srv.tracer.chrome_trace()["traceEvents"]
          if e["ph"] == "X"]
    names = {e["name"] for e in xs}
    assert {"admission", "prefill_chunk", "decode_span"} <= names
    assert {"tick", "admit", "decode_step", "span_fetch", "dispatch",
            "retire"} <= names
    assert not any(n.startswith("serve.") for n in names)
    _spans_disjoint_or_nested([e for e in xs if e["tid"] == 0])


def test_device_programs_have_stable_names(smoke_model):
    """The serving programs lower as ``jit_decode_step`` and
    ``jit_chunk_prefill_step`` — jitted directly and through
    ``_jit_on_mesh`` on a 1-device mesh — never ``jit_step``."""
    from repro.launch.mesh import make_serving_mesh
    from repro.launch.serve import _jit_on_mesh
    from repro.launch.steps import make_chunk_prefill_step, make_decode_step
    cfg, params = smoke_model
    srv = BatchedServer(cfg, params, batch_size=2, max_len=32, kv_bits=8,
                        page_size=8, prefill="bucketed", prefill_bucket=8)
    B, NP = srv.B, srv.np_max
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, np.int32)
    pt = i32(B, NP)
    programs = {
        "decode_step": (make_decode_step,
                        (i32(B), i32(B), srv.caches, pt), 3),
        "chunk_prefill_step": (make_chunk_prefill_step,
                               (i32(B, 8), i32(B), i32(B), srv.caches,
                                pt), 4),
    }
    for mesh in (None, make_serving_mesh(1)):
        for name, (make, args, caches_arg) in programs.items():
            fn = _jit_on_mesh(make(cfg, quant=srv.quant), mesh, caches_arg)
            head = fn.lower(srv.params, *args).as_text().split("\n", 1)[0]
            assert head.startswith(f"module @jit_{name} "), (mesh, head)
