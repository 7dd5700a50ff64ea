"""The knee sweep: one cell's traffic offered at several fixed rates in
one process (weights made once), each rate on a fresh server: a lead-in, a
window and a drain. It prints, per rate, what the window's
requests saw; the rate a cell's mix states is set from it by hand (about
four fifths of the highest rate the server sustains).

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 0.5,1,2
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path[:] = [ROOT] + [p for p in sys.path
                            if os.path.abspath(p or ".") != here]
    from bench import cache
    cache.enable()
    from bench import harness, latency, stats, traffic
    cell = harness.load_cell(args.workload, False)
    devs, pk = harness.devices(cell.chips, False)
    clock = harness.CompileClock()
    mcfg = harness.model_config(cell.conf)
    mesh = harness.serving_mesh(cell.conf["server"])
    params = harness.init_params(harness.weights_key(args.seed), mcfg, mesh)
    harness.log(f"weights {time.perf_counter() - T_START:.3f} s")
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        # a fresh server per rate: what an overloaded rate left queued
        # does not spill into the next
        srv = harness.build_server(mcfg, params, cell.conf["server"], mesh)
        harness.warm_up(srv, cell.conf, mcfg.vocab_size, args.seed)
        probe = harness.Probe(srv)
        lead, window = traffic.schedule(cell.mix, args.seed + k, args.seconds,
                                        mcfg.vocab_size, rate)
        kv = []
        win = harness.drive(srv, clock, lead, window, args.seconds, 0, kv)
        run = harness.Run(cell, args.seconds, 0.0, win, kv, probe,
                          harness.cost_shape(cell.conf), pk, len(devs))
        wr = run.window_records
        queued = sum(1 for r in wr if r.req.rid not in probe.admit_t
                     or probe.admit_t[r.req.rid] > win.t_close)
        tt = latency.ttfts(run)
        out = {"rate": rate, "requests": len(wr),
               "output_tok_s": harness.read_metric("output_tok_s", run),
               "ttft_p50_s": stats.percentile(tt, 50),
               "ttft_p90_s": stats.percentile(tt, 90),
               "tpot_p90_ms": harness.read_metric("tpot_p90_ms", run),
               "decode_rows_mean": harness.read_metric("decode_rows_mean",
                                                       run),
               "not_admitted_at_close": queued,
               "no_token_at_drain_end": sum(r.first is None for r in wr),
               "drain_s": win.t_end - win.t_close,
               "compiles_in_window": win.counters_close["compiles"]
               - win.counters_open["compiles"]}
        print(json.dumps(out), flush=True)
        del srv, probe, win, run
        gc.collect()
    print(json.dumps({"device": harness.device_info(devs)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
