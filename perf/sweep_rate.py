#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell: the highest arrival rate
the system sustains without a growing backlog.

  python3 perf/sweep_rate.py --workload serve-chat-openloop \
      --rates 3,4,5,6,7,8,9,10 --seconds 20 --seed 1

Run ONCE, on the chip, when a cell is defined (or re-defined by a later
benchmark PR); a benchmark run never searches for a rate. One process and
one set-up: the cell's own configuration and traffic parameters, then
each rate in turn for `--seconds`, the engine drained between rates. For
each rate one JSON line: what was sent and completed, the requests in
flight early and late in the stretch and their slope, time to first token
and the gap between tokens. A backlog that grows shows as a slope well
above zero and as first-token times that grow with the rate faster than
linearly; the knee is the last rate before that, read off the table by
whoever freezes it into the traffic file (perf/README.md says what was
read and frozen).
"""
import argparse
import copy
import json
import os
import sys
import time

PERF_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(PERF_DIR), PERF_DIR]

import numpy as np  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", nargs="?", const="perf/rehearse.json",
                    default=None)
    args = ap.parse_args()

    from harness import manifest, serving_times, stats
    import run as bench

    cell = manifest.Cell(
        manifest.load_json(args.rehearse or "BENCHMARK.json"), args.workload)
    if not args.rehearse:
        bench.keep_every_executable()
    device = bench.require_device(cell, args.rehearse is not None)
    tracer = bench.Tracer(False, None)
    ctx = bench.Context(cell, args, tracer)
    ctx.log(f"sweep of {cell.name} on {device}")
    runner = manifest.load_plugin("systems", cell.config["system"])
    gen = manifest.load_plugin("generators", cell.traffic["generator"])
    eng, _ = runner.build(ctx)
    runner.cover_shapes(eng)

    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        params = copy.deepcopy(cell.traffic["params"])
        params["arrival"]["rate_per_s"] = rate
        stream = gen.make(params, args.seed + k, cell.config["vocab_size"])
        drv = runner.Driver(eng, stream, tracer)
        in_flight = []
        now = drv.t0
        while now - drv.t0 < args.seconds:
            now = drv.turn()
            in_flight.append((now - drv.t0, len(drv.live)))
        t_end = now
        drv.injecting = False
        while drv.live and now - t_end < 30.0:
            now = drv.turn()
        left = len(drv.live)
        drv.abandon(now)
        while eng.step():
            pass
        t, n = np.array(in_flight).T
        late = t >= args.seconds / 4
        slope = float(np.polyfit(t[late], n[late], 1)[0])
        rec = {"requests": drv.requests, "t_give_up": now}
        done = [r for r in drv.requests if r["state"] == "done"]
        print(json.dumps({
            "rate_per_s": rate, "sent": len(drv.requests),
            "done": len(done), "not_done_30s_after": left,
            "completed_per_s": sum(r["t_done"] <= t_end for r in done)
            / args.seconds,
            "in_flight_mean_2nd_quarter": float(np.mean(
                n[(t >= args.seconds / 4) & (t < args.seconds / 2)])),
            "in_flight_mean_last_quarter": float(np.mean(
                n[t >= 3 * args.seconds / 4])),
            "in_flight_slope_per_s": slope,
            "drain_s": now - t_end,
            "ttft_ms_p50": stats.percentile(serving_times.ttft_ms(rec), 50),
            "ttft_ms_p90": stats.percentile(serving_times.ttft_ms(rec), 90),
            "tpot_ms_p50": stats.percentile(serving_times.tpot_ms(rec), 50),
            "out_tokens_per_s": sum(r["n_out"] for r in done)
            / (now - drv.t0),
        }), flush=True)
    ctx.log(f"sweep done in {time.monotonic() - bench.T_PROCESS_START:.0f} s")


if __name__ == "__main__":
    main()
