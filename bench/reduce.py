"""From a profiler trace to the numbers the benchmark reports.

A trace is first flattened to a list of events, each a dict with ``plane``,
``line``, ``name``, ``start_ns`` and ``dur_ns``; :func:`reduce` works on
that list alone, so it is checked on a small recorded trace
(``bench/fixtures``) without a chip. Device operations are the events on
the ``XLA Ops`` line of each ``/device:`` plane, less the control-flow ops
(``while``, ...) whose events enclose the ops of their bodies: busy time is
the union of the ops that do work. The measured window is the host span
named :data:`WINDOW` that the harness puts around it; device events are
clipped to it.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional

WINDOW = "bench.window"
HOST_PREFIX = "bench."
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:"
# control-flow ops whose events enclose the ops they run
CONTAINERS = ("while", "conditional", "call")


def op_label(name: str) -> str:
    """``%paged_kv_attention.11 = f32[32,1,8,8,128]{...} custom-call(...)``
    -> ``paged_kv_attention.11 f32[32,1,8,8,128]``: the instruction and its
    result's shape, without layouts."""
    head, _, rest = name.partition(" = ")
    shape = re.sub(r"\{[^}]*\}", "", rest.split(" ", 1)[0]) if rest else ""
    return f"{head.lstrip('%')} {shape}".strip()


def is_container(name: str) -> bool:
    op = name.lstrip("%").split(" ", 1)[0].split(".", 1)[0]
    return op in CONTAINERS


def load_events(trace_dir: str) -> List[dict]:
    """Device-op events and the harness's own host spans from the newest
    ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return []
    events = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(HOST_PREFIX):
                    continue
                events.append({"plane": plane.name, "line": line.name,
                               "name": ev.name,
                               "start_ns": float(ev.start_ns),
                               "dur_ns": float(ev.duration_ns)})
    return events


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _host_label(spans, t: float) -> str:
    """The innermost harness span covering host time ``t``."""
    best = None
    for s, e, name in spans:
        if s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2][len(HOST_PREFIX):] if best else "outside any harness span"


def reduce(events: List[dict], kernel: str,
           top: int = 10) -> Optional[Dict]:
    """Busy and window seconds (averaged over devices), the device time of
    ops whose name contains ``kernel``, the ``top`` device ops by time, and
    the ``top`` longest idle gaps labelled by the host span they fell in.
    ``None`` when the trace holds no window or no device op."""
    win = [e for e in events if e["name"] == WINDOW
           and not e["plane"].startswith(DEVICE_PREFIX)]
    dev = [e for e in events if e["plane"].startswith(DEVICE_PREFIX)
           and not is_container(e["name"])]
    if not win or not dev:
        return None
    w0 = win[0]["start_ns"]
    w1 = w0 + win[0]["dur_ns"]
    spans = [(e["start_ns"], e["start_ns"] + e["dur_ns"], e["name"])
             for e in events if e["name"].startswith(HOST_PREFIX)
             and e["name"] != WINDOW
             and not e["plane"].startswith(DEVICE_PREFIX)]
    planes = sorted({e["plane"] for e in dev})
    busy, kern, by_op, gaps = 0.0, 0.0, {}, []
    for k, plane in enumerate(planes):
        iv = []
        for e in dev:
            if e["plane"] != plane:
                continue
            s = max(e["start_ns"], w0)
            t = min(e["start_ns"] + e["dur_ns"], w1)
            if t <= s:
                continue
            iv.append((s, t))
            label = op_label(e["name"])
            by_op[label] = by_op.get(label, 0.0) + (t - s)
            if kernel in e["name"]:
                kern += t - s
        merged = _union(iv)
        busy += sum(e - s for s, e in merged)
        if k == 0:
            edges = [w0] + [x for s, e in merged for x in (s, e)] + [w1]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    n = len(planes)
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": busy / n / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "kernel_s": kern / n / 1e9,
        "devices": n,
        "device_ops": [[name, t / n / 1e9] for name, t in
                       sorted(by_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_host_label(spans, (s + e) / 2), (e - s) / 1e9]
                      for s, e in gaps[:top]],
    }
