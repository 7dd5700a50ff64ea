"""One run of one cell: build the cell's server from its files, warm it up,
offer its traffic open loop on the wall clock, and reduce what happened to
the cell's metrics and the correctness check.

Everything that belongs to one configuration, traffic mix or metric is
found by name: ``bench/configs/<config>.json`` (sizes, deployment, server
settings, the reference's name and the limit of the check),
``bench/traffic/<mix>.json`` and ``bench/metrics/<metric>.py``.

The run, in order:

1. Weights from the seed on the device in one jitted call
   (``bench/weights.py``, in the tree the program's ``init_model`` lays
   out); a ``BatchedServer`` built with the settings of the configuration
   file that its signature still takes.
2. Warm-up: one request per prefill bucket (and, with the prefix cache on,
   a pair that shares a prefix across a page boundary, so the aliasing and
   copy-on-write paths run), then the prefix cache is emptied and
   ``lead_in_s`` of the mix's own traffic, from streams of its own, runs
   straight into the window. Compiles are counted from the warm-up's end.
3. The window: requests are handed to ``ServeLoop.add`` once due, with
   ``arrive_step`` at the loop's clock, and ``ServeLoop.tick`` runs between
   handoffs; after each tick new tokens in ``req.out`` are stamped with the
   host clock. After ``--seconds`` no more requests arrive, and the loop
   runs on until every request due in the window has its first token and
   enough have finished for the check (at most ``DRAIN_S`` more seconds).
4. The metrics, the peak device memory, then the server is freed and the
   reference runs over a sample of finished requests (``correct``).
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import importlib
import inspect
import json
import os
import shutil
import sys
import time
from typing import Dict, List, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
DRAIN_S = 60.0
TRACE_DIR = os.path.join(ROOT, ".bench_out", "trace")
if os.path.join(ROOT, "src") not in sys.path:   # the system under test
    sys.path.append(os.path.join(ROOT, "src"))

from bench import costs, peaks, reduce, traffic, weights  # noqa: E402


class Refusal(Exception):
    """The run cannot measure what was asked: wrong platform, too few
    chips, an unknown device kind, or a cell that is not defined."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Cell files
# ---------------------------------------------------------------------------
def load_json(rel: str) -> dict:
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: dict          # the configuration file, smoke overrides applied
    mix: dict
    metrics: List[dict]  # BENCHMARK.json entries this run reports


def load_cell(workload: str, trace: bool, smoke: bool = False) -> Cell:
    bm = load_json("BENCHMARK.json")
    cells = {w["name"]: w for w in bm["workloads"]}
    if workload not in cells:
        raise Refusal(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    entry = {c["name"]: c for c in bm["configs"]}[w["config"]]
    conf = load_json(entry["file"])
    if smoke:
        conf = traffic.merge(conf, conf.get("smoke", {}))
    mix = traffic.load_mix(w["traffic"], smoke=smoke)
    kind = "per_layer" if trace else "end_to_end"
    wanted = [m for m in bm[kind]
              if workload in m.get("workloads", [workload])]
    return Cell(workload, w["chips"], conf, mix, wanted)


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig
    heads = conf["num_attention_heads"]
    return ModelConfig(
        name=conf["name"], family="dense",
        num_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        num_heads=heads, num_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        head_dim=conf.get("head_dim") or conf["hidden_size"] // heads,
        attention_bias=bool(conf.get("attention_bias", False)),
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=bool(conf.get("tie_word_embeddings", False)),
        dtype=conf["dtype"]["activations"],
        param_dtype=conf["dtype"]["weights"])


def cost_shape(conf: dict) -> costs.Shape:
    heads = conf["num_attention_heads"]
    srv = conf["server"]
    return costs.Shape(
        layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        heads=heads, kv_heads=conf["num_key_value_heads"],
        head_dim=conf.get("head_dim") or conf["hidden_size"] // heads,
        d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
        page_size=srv["page_size"], kv_bytes=srv["kv_bits"] / 8)


def weights_key(seed: int):
    """The raw threefry key of a run's weights, from any whole seed."""
    import jax.numpy as jnp
    words = traffic.stream(seed, "weights").integers(
        0, 2**32, 2, dtype=np.uint64).astype(np.uint32)
    return jnp.asarray(words)


# ---------------------------------------------------------------------------
# Device
# ---------------------------------------------------------------------------
def devices(chips: int, smoke: bool):
    """The devices a run uses. Only a TPU with a peaks entry is measured;
    the CPU rehearsal (``smoke``) is the one exception, and says so."""
    import jax
    devs = jax.devices()
    platform = devs[0].platform
    if smoke:
        if platform != "cpu":
            raise Refusal("the rehearsal runs on the CPU only")
        return devs[:1], None
    if platform != "tpu":
        raise Refusal(f"JAX found platform {platform!r}, not 'tpu'")
    if len(devs) < chips:
        raise Refusal(f"the cell needs {chips} chips, JAX found {len(devs)}")
    try:
        pk = peaks.peaks_for(devs[0].device_kind)
    except peaks.UnknownDevice as e:
        raise Refusal(str(e)) from None
    return devs[:chips], pk


def device_info(devs) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


class CompileClock:
    """Counts XLA backend compiles from JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds, self.programs = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.programs += 1


def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


# ---------------------------------------------------------------------------
# The system under test, and what the harness records around it
# ---------------------------------------------------------------------------
def serving_mesh(server: dict):
    """The tensor-parallel mesh a configuration's ``"tp"`` setting asks
    for (``None`` for one chip)."""
    if server.get("tp", 1) <= 1:
        return None
    from repro.launch.mesh import make_serving_mesh
    return make_serving_mesh(server["tp"])


def init_params(key, mcfg, mesh):
    """Weights from the seed (``bench/weights.py``) in one jitted program,
    in the program's tree and dtypes, each leaf made on the devices that
    hold it."""
    import jax
    from repro.models.transformer import init_model
    shapes = jax.eval_shape(lambda k: init_model(k, mcfg), key)
    make = functools.partial(weights.program_params, shapes=shapes)
    if mesh is None:
        return jax.block_until_ready(jax.jit(make)(key))
    from repro.parallel.sharding import param_shardings, plan_for_mesh
    shardings = param_shardings(shapes, plan_for_mesh(mesh), inference=True)
    return jax.block_until_ready(
        jax.jit(make, out_shardings=shardings)(key))


def build_server(mcfg, params, server: dict, mesh=None):
    """``BatchedServer`` with the configuration's settings, each passed
    only where the constructor still takes it."""
    from repro.launch.serve import BatchedServer
    sig = inspect.signature(BatchedServer).parameters
    kw = {k: v for k, v in server.items() if k in sig}
    dropped = sorted(set(server) - set(kw) - {"tp"})
    if dropped:
        log(f"server settings the program no longer takes: {dropped}")
    if mesh is not None:
        kw["mesh"] = mesh
    return BatchedServer(mcfg, params, **kw)


class Probe:
    """Host-side records of the served work, and the harness's trace
    spans around the calls into each layer: admission, prefill chunks,
    decode steps. ``fault`` breaks the timed path on purpose (tests)."""

    def __init__(self, srv, fault: Optional[str] = None):
        self.srv = srv
        self.decode_calls: List[tuple] = []   # (t, [(1, kv_len), ...])
        self.prefill_calls: List[tuple] = []  # (t, [(n, end), ...])
        self.admit_t: Dict[int, float] = {}
        decode, group = srv.decode, srv._prefill_group
        admit, do_admit = srv._admit, srv._do_admit
        vocab = srv.cfg.vocab_size

        def decode_rec(*args):
            rows = [(1, int(srv.pos[i]) + 1) for i in range(srv.B)
                    if srv.slots[i] is not None]
            self.decode_calls.append((time.perf_counter(), rows))
            with annotate("bench.decode"):
                out = decode(*args)
            if fault == "token":
                out = ((out[0] + 1) % vocab,) + tuple(out[1:])
            return out

        def group_rec(rows, bucket):
            recs = []
            for job in rows:
                off = job.start + job.done
                n = min(bucket, len(job.req.prompt) - 1 - off)
                recs.append((n, off + n))
            self.prefill_calls.append((time.perf_counter(), recs))
            with annotate("bench.prefill_chunk"):
                return group(rows, bucket)

        def admit_rec(queue):
            with annotate("bench.admit"):
                return admit(queue)

        def do_admit_rec(i, req, info, jobs):
            self.admit_t.setdefault(req.rid, time.perf_counter())
            return do_admit(i, req, info, jobs)

        srv.decode, srv._prefill_group = decode_rec, group_rec
        srv._admit, srv._do_admit = admit_rec, do_admit_rec


@dataclasses.dataclass
class Record:
    arrival: traffic.Arrival
    due: float                 # absolute host time
    window: bool
    req: object = None         # the program's Request, once handed over
    handoff: Optional[float] = None
    first: Optional[float] = None
    stamps: List[tuple] = dataclasses.field(default_factory=list)
    seen: int = 0


def warm_up(srv, conf: dict, vocab: int, seed: int) -> None:
    """Every program the traffic can reach, compiled before the window:
    the decode step, one prefill chunk per power-of-two bucket, and (with
    the prefix cache) an aliased prefix with a copy-on-write page."""
    from repro.launch.serve import Request
    rng = traffic.stream(seed, "warm-up")
    srvc = conf["server"]
    buckets, b = [], 1
    while b <= srvc["prefill_bucket"]:
        buckets.append(b)
        b *= 2
    reqs = [Request(i, rng.integers(0, vocab, b + 1).astype(np.int32), 2)
            for i, b in enumerate(buckets)]
    if srvc.get("prefix_cache") == "on":
        ps = srvc["page_size"]
        base = rng.integers(0, vocab, 2 * ps + ps // 2 + 1).astype(np.int32)
        tail = rng.integers(0, vocab, ps).astype(np.int32)
        reqs.append(Request(len(reqs), base, 2))
        reqs.append(Request(len(reqs),
                            np.concatenate([base[:ps + ps // 2], tail]), 2))
    srv.run(reqs)
    srv.release_prefix_cache()


@dataclasses.dataclass
class Window:
    """What the open loop saw. ``t0``/``t_close`` are the window's nominal
    edges; ``t_open``/``t_shut`` the host times at which the harness's
    window span opened and shut (a scheduler tick under way at an edge
    delays them), and the trace's window; ``t_end`` the drain's end."""
    records: List[Record]
    t0: float
    t_close: float
    t_open: float
    t_shut: float
    t_end: float
    counters_open: dict
    counters_close: dict
    loop: object


def drive(srv, clock: CompileClock, lead, window, seconds: float,
          need_done: int, kv_samples: list) -> Window:
    """The open loop: lead-in, window, drain."""
    from repro.launch.serve import Request
    loop = srv.start_loop([])
    sched = sorted(lead + window, key=lambda a: a.due_s)
    t0 = time.perf_counter() + max(
        0.0, -min((a.due_s for a in sched), default=0.0))
    t_close = t0 + seconds
    recs = [Record(a, t0 + a.due_s, a.due_s >= 0) for a in sched]
    inflight: List[Record] = []
    nxt = 0
    c_open = c_close = t_open = t_shut = None
    span = annotate("bench.window")
    names = ("serve.gen_tokens", "serve.decode_steps",
             "serve.prefix_hit_tokens", "serve.prefill_tokens")

    def counters():
        out = {n: float(srv.metrics.value(n)) for n in names}
        out["compiles"] = clock.programs
        return out

    while True:
        now = time.perf_counter()
        if c_open is None and now >= t0:
            span.__enter__()
            c_open, t_open = counters(), now
        if c_close is None and now >= t_close:
            span.__exit__(None, None, None)
            c_close, t_shut = counters(), now
        if c_close is not None:
            waiting = any(r.window and r.first is None for r in recs)
            done = sum(1 for r in recs if r.window and r.req is not None
                       and r.req.done)
            if (not waiting and done >= need_done) or now > t_close + DRAIN_S:
                return Window(recs, t0, t_close, t_open, t_shut, now, c_open,
                              c_close, loop)
        while nxt < len(recs) and recs[nxt].due <= now:
            r = recs[nxt]
            r.req = Request(nxt, r.arrival.prompt, r.arrival.max_new,
                            arrive_step=loop.clock)
            r.handoff = now
            loop.add(r.req)
            inflight.append(r)
            nxt += 1
        if loop.pending or loop.queue or loop.live:
            with annotate("bench.tick"):
                loop.tick()
            now = time.perf_counter()
            for r in inflight:
                n = len(r.req.out)
                if n > r.seen:
                    r.stamps.append((now, n - r.seen))
                    r.seen = n
                    if r.first is None:
                        r.first = now
            inflight = [r for r in inflight if not r.req.done]
            if c_open is not None and c_close is None:
                g = srv.metrics
                usable = g.value("kv.device_pages_usable")
                if usable:
                    kv_samples.append(
                        1.0 - g.value("kv.device_pages_free") / usable)
        else:
            until = [recs[nxt].due] if nxt < len(recs) else []
            until.append(t0 if c_open is None else
                         t_close if c_close is None else now + 0.05)
            with annotate("bench.wait_arrival"):
                time.sleep(max(0.0, min(min(until) - now, 0.05)))


# ---------------------------------------------------------------------------
# What a metric reader sees
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Run:
    cell: Cell
    seconds: float
    setup_s: float
    win: Window
    kv_used: List[float]
    probe: Optional[Probe]
    shape: costs.Shape
    peaks: Optional[dict]
    chips: int
    trace: Optional[dict] = None

    @property
    def records(self) -> List[Record]:
        return self.win.records

    @property
    def window_records(self) -> List[Record]:
        return [r for r in self.win.records if r.window]

    @property
    def t0(self) -> float:
        return self.win.t0

    @property
    def t_close(self) -> float:
        return self.win.t_close

    @property
    def t_drain_end(self) -> float:
        return self.win.t_end

    def counter_delta(self, name: str) -> float:
        return self.win.counters_close[name] - self.win.counters_open[name]

    def in_window(self, calls):
        """Recorded calls made while the window's span was open (the
        traced window)."""
        return [rows for t, rows in calls
                if self.win.t_open <= t <= self.win.t_shut]


def read_metric(name: str, run: Run):
    mod = importlib.import_module(f"bench.metrics.{name}")
    return mod.read(run)


def lateness(recs: List[Record]) -> dict:
    late = sorted(r.handoff - r.due for r in recs if r.handoff is not None)
    if not late:
        return {}
    return {"p50_s": late[len(late) // 2], "max_s": late[-1]}


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------
def run(workload: str, seed: int, seconds: float, trace: bool, t_start: float,
        smoke: bool = False, fault: Optional[str] = None,
        control_bits: int = 0) -> dict:
    cell = load_cell(workload, trace, smoke)
    import jax
    devs, pk = devices(cell.chips, smoke)
    from bench import correct
    clock = CompileClock()
    conf, mix = cell.conf, cell.mix
    mcfg = model_config(conf)
    key = weights_key(seed)
    mesh = serving_mesh(conf["server"])
    params = init_params(key, mcfg, mesh)
    t_init = time.perf_counter()
    srv = build_server(mcfg, params, conf["server"], mesh)
    warm_up(srv, conf, mcfg.vocab_size, seed)
    probe = Probe(srv, fault)
    t_warm = time.perf_counter()
    compiles_warm = (clock.programs, clock.seconds)
    lead, window = traffic.schedule(mix, seed, seconds, mcfg.vocab_size)
    too_long = [a for a in lead + window
                if len(a.prompt) >= conf["server"]["max_len"]]
    if too_long:
        raise Refusal(f"{len(too_long)} prompts reach max_len")
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    kv_used: list = []
    check = mix["check"]
    win = drive(srv, clock, lead, window, seconds, check["requests"],
                kv_used)
    if trace:
        jax.profiler.stop_trace()
    setup_s = win.t0 - t_start
    rn = Run(cell, seconds, setup_s, win, kv_used, probe, cost_shape(conf),
             pk, len(devs))
    recs, c_open, c_close = win.records, win.counters_open, win.counters_close
    if trace:
        events = reduce.load_events(TRACE_DIR)
        rn.trace = reduce.reduce(events, kernel="paged_kv_attention")
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    metrics = {}
    for m in cell.metrics:
        v = read_metric(m["name"], rn)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    dev = device_info(devs)
    log(f"device {dev}")
    log(f"set-up {setup_s:.3f} s: init {t_init - t_start:.3f} s, server and "
        f"warm-up {t_warm - t_init:.3f} s, lead-in {win.t0 - t_warm:.3f} s; "
        f"compiles before the warm-up's end {compiles_warm[0]} "
        f"({compiles_warm[1]:.3f} s)")
    log(f"compiles in the lead-in: {c_open['compiles'] - compiles_warm[0]}, "
        f"in the window: {c_close['compiles'] - c_open['compiles']}, in the "
        f"drain: {clock.programs - c_close['compiles']}")
    log(f"generator lateness (handoff - due): {lateness(recs)}")
    wrecs = rn.window_records
    attempted = len(wrecs)
    no_token = sum(1 for r in wrecs if r.first is None)
    errors = sum(1 for r in wrecs if r.req is not None
                 and r.req.error is not None)
    failed = sum(1 for r in wrecs if r.first is None or r.req is None
                 or r.req.error is not None)
    log(f"window: {attempted} requests due, {no_token} without a first "
        f"token by the drain's end ({win.t_end - win.t_close:.3f} s past "
        f"the close), {errors} rejected; lead-in {len(lead)} requests")
    # the program's state is freed before the reference runs
    rn.probe = win.loop = None
    del srv, probe, params, mesh
    gc.collect()
    t_ref = time.perf_counter()
    verdict = correct.check(conf, key, seed, recs, check, mcfg.vocab_size,
                            control_bits)
    log(f"reference check {time.perf_counter() - t_ref:.3f} s; run "
        f"{time.perf_counter() - t_start:.3f} s in all")
    checks = dict(verdict["checks"])
    checks["requests_failed"] = {"value": failed, "limit": 0}
    ok = verdict["ok"] and failed == 0
    result = {"correct": bool(ok), "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": dev}
    if "control" in verdict:
        result["control"] = verdict["control"]
        log(f"control run: int{control_bits} KV in the program's place; "
            f"the program's own gap {verdict['control']['served_max_logit_gap']}")
    if trace and rn.trace:
        result["device"]["busy_s"] = rn.trace["busy_s"]
        result["device"]["window_s"] = rn.trace["window_s"]
        result["breakdown"] = {"device_ops": rn.trace["device_ops"],
                               "idle_gaps": rn.trace["idle_gaps"]}
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    return result
