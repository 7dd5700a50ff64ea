"""The program's own spans and device programs in a traced run.

``bench/reduce.py`` keeps, of a profiler trace, the device ops and the
harness's ``bench.*`` spans. The program writes more there: its serving
loop's phases as ``serve.*`` host spans (``runtime/telemetry.py``), and on
each device plane an ``XLA Modules`` line with one event per execution of a
jitted program, named after it (``jit_decode_step``,
``jit_chunk_prefill_step``, ``jit_fused_step``). This module reads them:

* :func:`load_events` keeps what ``reduce.load_events`` keeps, plus the
  ``serve.*`` host spans and the module line; :func:`ops_events` gives back
  exactly the part ``reduce.reduce`` reads, so its numbers do not move;
* :func:`reduce_program` gives, within the window, the program spans'
  durations by name, the module executions' device durations by program,
  and the longest idle gaps, each labelled by the innermost program span
  over it (by the harness's span where no program span covers it);
* :data:`READINGS`: ``decode_span_p90_s``, ``decode_step_ms`` and
  ``prefill_chunk_ms`` from that.

The harness deletes the trace before its metric readers run, and hands
them only what ``reduce.reduce`` returns, so these readings are not yet
metrics of the benchmark. From the command line this module makes one
traced run of a cell, as ``bench/run.py --trace 1`` does, and prints its
result with the readings under ``"program"``, and the cell's end-to-end
metrics beside its per-layer ones (what tracing costs, on the same seed as
a ``--trace 0`` run):

    python3 bench/program_trace.py --workload <cell> --seed <n> --seconds <s>

``--rehearse`` takes the harness's CPU path (smoke sizes, no device plane,
so no module readings).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:       # run as a script: the bench package
    sys.path.insert(0, ROOT)

from bench import reduce, stats  # noqa: E402

PROGRAM_PREFIX = "serve."
MODULES_LINE = "XLA Modules"
DECODE_PROGRAM = "jit_decode_step"
PREFILL_PROGRAM = "jit_chunk_prefill_step"


def _is_device(e: dict) -> bool:
    return e["plane"].startswith(reduce.DEVICE_PREFIX)


def load_events(trace_dir: str) -> List[dict]:
    """Device ops and module executions, and the ``bench.*`` and
    ``serve.*`` host spans, from the newest ``.xplane.pb`` under
    ``trace_dir`` (events as ``reduce.load_events`` makes them)."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return []
    host = (reduce.HOST_PREFIX, PROGRAM_PREFIX)
    events = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        device = plane.name.startswith(reduce.DEVICE_PREFIX)
        for line in plane.lines:
            if device and line.name not in (reduce.OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(host):
                    continue
                events.append({"plane": plane.name, "line": line.name,
                               "name": ev.name,
                               "start_ns": float(ev.start_ns),
                               "dur_ns": float(ev.duration_ns)})
    return events


def ops_events(events: List[dict]) -> List[dict]:
    """The events ``reduce.load_events`` would have kept: device ops and
    the harness's spans."""
    return [e for e in events
            if (e["line"] == reduce.OPS_LINE if _is_device(e)
                else e["name"].startswith(reduce.HOST_PREFIX))]


def program_of(name: str) -> str:
    """A module event's program: ``jit_decode_step(1234)`` ->
    ``jit_decode_step``."""
    return name.split("(", 1)[0].strip()


def _label(program_spans, harness_spans, t: float) -> str:
    """The innermost program span covering host time ``t``, by its
    ``serve.`` name; else the harness's label (``reduce``'s rule)."""
    best = None
    for s, e, name in program_spans:
        if s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    if best is not None:
        return best[2]
    return reduce._host_label(harness_spans, t)


def reduce_program(events: List[dict], top: int = 10) -> Optional[Dict]:
    """Within the harness's window: ``spans``, the durations (s) of the
    program spans that end in it, by name; ``modules``, the device
    durations (s) of the module executions that start in it, by program
    (every device's); ``module_s``, each program's device time clipped to
    the window, averaged over devices; ``idle_gaps``, the ``top`` longest
    gaps between device ops (first device), labelled. ``None`` where the
    trace holds no window."""
    win = [e for e in events if e["name"] == reduce.WINDOW
           and not _is_device(e)]
    if not win:
        return None
    w0 = win[0]["start_ns"]
    w1 = w0 + win[0]["dur_ns"]
    prog, harness = [], []
    spans: Dict[str, List[float]] = {}
    for e in events:
        if _is_device(e) or e["name"] == reduce.WINDOW:
            continue
        iv = (e["start_ns"], e["start_ns"] + e["dur_ns"], e["name"])
        if e["name"].startswith(PROGRAM_PREFIX):
            prog.append(iv)
            if w0 <= iv[1] <= w1:
                spans.setdefault(e["name"], []).append(e["dur_ns"] / 1e9)
        else:
            harness.append(iv)
    modules: Dict[str, List[float]] = {}
    module_s: Dict[str, float] = {}
    mod = [e for e in events if _is_device(e) and e["line"] == MODULES_LINE]
    planes = sorted({e["plane"] for e in events if _is_device(e)})
    for e in mod:
        name = program_of(e["name"])
        if w0 <= e["start_ns"] <= w1:
            modules.setdefault(name, []).append(e["dur_ns"] / 1e9)
        clipped = min(e["start_ns"] + e["dur_ns"], w1) - max(e["start_ns"],
                                                              w0)
        if clipped > 0:
            module_s[name] = (module_s.get(name, 0.0)
                              + clipped / len(planes) / 1e9)
    gaps = []
    if planes:
        iv = []
        for e in events:
            if (e["plane"] != planes[0] or e["line"] != reduce.OPS_LINE
                    or reduce.is_container(e["name"])):
                continue
            s = max(e["start_ns"], w0)
            t = min(e["start_ns"] + e["dur_ns"], w1)
            if t > s:
                iv.append((s, t))
        edges = [w0] + [x for s, t in reduce._union(iv) for x in (s, t)]
        edges.append(w1)
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "spans": spans,
        "modules": modules,
        "module_s": module_s,
        "idle_gaps": [[_label(prog, harness, (s + e) / 2), (e - s) / 1e9]
                      for s, e in gaps[:top]],
    }


def decode_span_p90_s(prog: Dict) -> Optional[float]:
    """Scheduler: p90 of the decode spans' lengths (``serve.decode_span``
    host events that end in the window)."""
    return stats.percentile(prog["spans"].get("serve.decode_span", []), 90)


def _median_ms(prog: Dict, program: str) -> Optional[float]:
    v = stats.percentile(prog["modules"].get(program, []), 50)
    return None if v is None else 1e3 * v


def decode_step_ms(prog: Dict) -> Optional[float]:
    """Whole step: median device time of a ``jit_decode_step`` execution
    that starts in the window."""
    return _median_ms(prog, DECODE_PROGRAM)


def prefill_chunk_ms(prog: Dict) -> Optional[float]:
    """Whole step: median device time of a ``jit_chunk_prefill_step``
    execution that starts in the window."""
    return _median_ms(prog, PREFILL_PROGRAM)


READINGS = {"decode_span_p90_s": decode_span_p90_s,
            "decode_step_ms": decode_step_ms,
            "prefill_chunk_ms": prefill_chunk_ms}


def summary(prog: Optional[Dict], busy_s: Optional[float]) -> Dict:
    """What the command prints under ``"program"``: the readings (``None``
    where the trace holds nothing to read), each program's device
    seconds and their share of the device's busy time, each span's count
    and seconds, and the labelled idle gaps."""
    if prog is None:
        return {name: None for name in READINGS}
    out = {name: read(prog) for name, read in READINGS.items()}
    out["module_s"] = prog["module_s"]
    steps = sum(prog["module_s"].get(p, 0.0)
                for p in (DECODE_PROGRAM, PREFILL_PROGRAM))
    out["steps_share_of_busy"] = (100.0 * steps / busy_s if busy_s
                                  else None)
    out["span_s"] = {name: [len(d), sum(d)]
                     for name, d in sorted(prog["spans"].items())}
    out["idle_gaps"] = prog["idle_gaps"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, smoke sizes, interpret-mode kernel")
    args = ap.parse_args(argv)
    sys.path[:] = [ROOT, os.path.join(ROOT, "src")] + [
        p for p in sys.path if os.path.abspath(p or ".")
        != os.path.dirname(os.path.abspath(__file__))]
    if not args.rehearse:
        from bench import cache
        cache.enable()
    from bench import harness
    kept: List[dict] = []

    def load(trace_dir):
        kept.extend(load_events(trace_dir))
        return ops_events(kept)
    # the harness's own traced run, read wider, in a trace directory of
    # this process's own (a benchmark run beside it keeps its own)
    load_cell = harness.load_cell

    def every_metric(workload, trace, smoke=False):
        cell = load_cell(workload, trace, smoke)
        cell.metrics += load_cell(workload, False, smoke).metrics
        return cell
    harness.load_cell = every_metric
    reduce.load_events = load
    harness.TRACE_DIR = os.path.join(ROOT, ".bench_out",
                                     f"program-trace-{os.getpid()}")
    try:
        result = harness.run(args.workload, args.seed, args.seconds, True,
                             T_START, smoke=args.rehearse)
    except harness.Refusal as e:
        print(f"[bench] refused: {e}", file=sys.stderr, flush=True)
        return 2
    checks = result.pop("checks")
    result["program"] = summary(reduce_program(kept),
                                result["device"].get("busy_s"))
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
