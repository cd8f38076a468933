#!/usr/bin/env python3
"""A model of the continuous-batching scheduler on a cell's OWN request
stream: what `serve_out_tokens_per_s` and `tpot_ms_p50` would read at given
program times, and how far 40 s windows of them spread from seed to seed.

  here:  python3 docs/probes/cb_schedule_model.py serve-mla-sparse-longdoc \
             --chunk-ms 48.5 --decode-ms 21.5 [--warm 60 150 300] \
             [--policy slot fcfs shortest] [--seeds 1 2 3 4 5 6]

A probe, run by hand; it needs no chip and no JAX. No benchmark cell runs
it. It is NOT a measurement: its numbers are written in PERF.md as "(model,
PR n)" and never under a device metric's name.

What it copies from the program (`scheduler._step_impl`, decode_block 1,
and `perf/systems/serve_engine.Driver`): the traffic file's generator and
parameters, seat by seat admission into the lowest free slot, ONE program a
step — a prefill chunk of the lowest seated slot that still has prompt
left (`--policy slot`, the engine's; `fcfs` = the seat admitted first,
`shortest` = the fewest chunks left, two orders a scheduling PR might
weigh), alternating with a decode step of every seat that has its first
token while both have work — the first token out of a prompt's last
chunk, a caller's next request due when its last one completes, warm-up
then a window, tokens counted as the engine's counter counts them, the
median over requests completed in the window of (t_done - t_first) /
(tokens - 1). What it leaves out: program times that grow with context
(one time a chunk, one a decode step, plus the host's gap after each),
page pressure (the cell's pools hold every seat at full length).
"""
import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "perf")]
from generators import requests as generator  # noqa: E402


def simulate(traffic, seed, slots, chunk, chunk_ms, decode_ms, *,
             policy="slot", gap_prefill_ms=1.0, gap_decode_ms=3.0,
             warm_s=None, window_s=40.0):
    """{"tokens_per_s", "tpot_ms_p50", "completed", "rows_per_step"} of
    one seed's window."""
    stream = generator.make(traffic["params"], seed, 1000)
    warm_s = float(traffic["warmup_s"] if warm_s is None else warm_s)
    end = warm_s + window_s
    pick = {"slot": lambda pre: pre[0],
            "fcfs": lambda pre: min(pre, key=lambda r: r["seat"]),
            "shortest": lambda pre: min(
                pre, key=lambda r: (r["left"], r["seat"]))}[policy]
    t, seats, queue, admitted = 0.0, [None] * slots, [], 0
    prefer_decode = False
    tokens = rows = steps = 0
    tpot = []
    while t < end:
        for req in stream.due(t):
            queue.append({"req": req, "left": -(-req.prompt.size // chunk),
                          "out": 0, "t_first": None})
        while queue and None in seats:
            r = queue.pop(0)
            r["seat"], admitted = admitted, admitted + 1
            seats[seats.index(None)] = r
        pre = [r for r in seats if r and r["left"]]
        dec = [r for r in seats if r and not r["left"]]
        if not pre and not dec:
            t += 0.001
            continue
        if pre and (not dec or not prefer_decode):
            r = pick(pre)
            t += (chunk_ms + gap_prefill_ms) / 1e3
            r["left"] -= 1
            emitted = []
            if not r["left"]:                   # the prompt's last chunk
                r["out"], r["t_first"] = 1, t
                emitted = [r]
            prefer_decode = True
        else:
            t += (decode_ms + gap_decode_ms) / 1e3
            for r in dec:
                r["out"] += 1
            emitted = dec
            prefer_decode = False
            if warm_s <= t < end:
                rows, steps = rows + len(dec), steps + 1
        if warm_s <= t < end:
            tokens += len(emitted)
        for r in emitted:
            if r["out"] >= r["req"].max_new:
                seats[seats.index(r)] = None
                stream.done(r["req"], t)
                if warm_s <= t < end and r["req"].max_new > 1:
                    tpot.append((t - r["t_first"])
                                / (r["req"].max_new - 1) * 1e3)
    return {"tokens_per_s": tokens / window_s,
            "tpot_ms_p50": statistics.median(tpot) if tpot else None,
            "completed": len(tpot),
            "rows_per_step": rows / steps if steps else None}


def spread(values):
    """Distance between the quartiles over the median, as the driver's
    admission check takes it."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--chunk-ms", type=float, required=True)
    ap.add_argument("--decode-ms", type=float, required=True)
    ap.add_argument("--warm", type=float, nargs="+", default=[None])
    ap.add_argument("--policy", nargs="+", default=["slot"])
    ap.add_argument("--seeds", type=int, nargs="+",
                    default=[3000000083, 3000000089, 3000000097,
                             3000000101, 3000000103, 3000000109])
    args = ap.parse_args(argv)
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w for w in bench["workloads"] if w["name"] == args.cell)
    cfg = json.load(open(os.path.join(ROOT, next(
        c["file"] for c in bench["configs"] if c["name"] == cell["config"]))))
    traffic = json.load(open(os.path.join(
        ROOT, "perf", "traffic", cell["traffic"] + ".json")))
    eng = cfg["serving"]["engine"]
    for policy in args.policy:
        for warm in args.warm:
            runs = [simulate(traffic, s, eng["max_batch"],
                             eng["prefill_chunk"], args.chunk_ms,
                             args.decode_ms, policy=policy, warm_s=warm)
                    for s in args.seeds]
            line = {"cell": args.cell, "policy": policy,
                    "warm_s": traffic["warmup_s"] if warm is None else warm}
            for key in ("tokens_per_s", "tpot_ms_p50", "rows_per_step",
                        "completed"):
                line[key] = [round(r[key], 2) for r in runs]
            for key in ("tokens_per_s", "tpot_ms_p50"):
                if len(runs) > 1:
                    line[key + "_spread"] = round(
                        spread([r[key] for r in runs]), 4)
            print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
