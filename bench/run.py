"""Run one cell of the benchmark once, in this process, on this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cells are the ``workloads`` of ``BENCHMARK.json`` at the root of the
checkout. With ``--trace 0`` the result holds the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics, read from a profiler
trace of the window and the program's counters. The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (platform, kind, count, peak memory; with a trace
also ``busy_s`` and ``window_s``), with a trace ``breakdown``, and last
``checks``, each number compared beside its limit. The same checks close
standard error.

It measures only on a TPU whose ``device_kind`` is in ``bench/peaks.py``:
any other platform, or fewer chips than the cell needs, exits 2 and prints
no result. ``--rehearse`` is the one other path: the CPU, smoke-size
copies of the configuration and mix, the kernel in interpret mode, for
checking the harness without a chip; it is never taken unless asked for.
The correctness control is read by ``bench/calibrate.py``, never here.
The knee sweep is ``bench/sweep.py``.

JAX's compilation cache is kept in ``.jax_cache`` at the root of the
checkout, so that only the first run of a cell in a checkout compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, smoke sizes, interpret-mode kernel")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path[:] = [ROOT, os.path.join(ROOT, "src")] + [
        p for p in sys.path if os.path.abspath(p or ".") != here]
    if not args.rehearse:
        from bench import cache
        cache.enable()
    from bench import harness
    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), T_START, smoke=args.rehearse)
    except harness.Refusal as e:
        print(f"[bench] refused: {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
