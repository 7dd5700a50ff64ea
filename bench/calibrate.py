"""The readings a configuration's correctness limit is set from: runs of
cells on many seeds in one process, each with the control judged in the
program's place (``harness.run(..., control_bits=BITS)``). Each run gives
the program's own widest gap (the lower reading comes from the largest
over the seeds) and the control's (the upper reading from the smallest).
One JSON line a run, also appended to ``--out``.

    python3 bench/calibrate.py --workloads <cell>[,<cell>] \\
        --seeds 3000000101-3000000112 --seconds 51 --control 4 \\
        --out chiprun_out/calibrate.jsonl

Seeds are dealt to the cells in turn. The benchmark's own runs never
take this path.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def seed_list(text: str):
    lo, _, hi = text.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workloads", required=True)
    ap.add_argument("--seeds", required=True, help="LO-HI, inclusive")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, default=4, metavar="BITS")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path[:] = [ROOT, os.path.join(ROOT, "src")] + [
        p for p in sys.path if os.path.abspath(p or ".") != here]
    from bench import cache
    cache.enable()
    from bench import harness
    cells = args.workloads.split(",")
    for k, seed in enumerate(seed_list(args.seeds)):
        cell = cells[k % len(cells)]
        r = harness.run(cell, seed, args.seconds, False, time.perf_counter(),
                        control_bits=args.control)
        c = r["checks"]
        line = {"workload": cell, "seed": seed, "control_bits": args.control,
                "served_max_logit_gap": r["control"]["served_max_logit_gap"],
                "control_max_logit_gap": c["max_logit_gap"]["value"],
                "limit": c["max_logit_gap"]["limit"],
                "control_correct": r["correct"],
                "sampled_tokens": c["sampled_tokens_at_least"]["value"],
                "attempted": r["attempted"], "failed": r["failed"],
                "metrics": {n: m["value"] for n, m in r["metrics"].items()}}
        print(json.dumps(line), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
        del r
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
