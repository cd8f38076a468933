"""Device time of NAMED kernels, by the step program they ran in.

A Pallas kernel built with `name=` runs as a custom-call whose HLO name
is that name (`%moe_grouped_matmul.3 = ...`), so `trace_reduce.op_name`
+ `op_family` find it. This reads the run's newest `.xplane.pb` (as
`span_reduce.of` does), keeps device 0's operations inside the traced
window, and sums each family's SELF time under the executable (`XLA
Modules` event: `jit_step`, `jit_prefill`) that was running. None
without a device plane.
"""
import bisect
import collections
import json
import sys

from harness import span_reduce, trace_reduce


def of(rec):
    """{"runs": {module: executions}, "seconds": {module: {family:
    self seconds}}} for this run's record, made once and kept in it."""
    tr = rec.get("trace")
    if not tr:
        return None
    if "kernel_times" not in tr:
        path = span_reduce.newest_trace()
        tr["kernel_times"] = reduce_file(path) if path else None
        _log(tr["kernel_times"])
    return tr["kernel_times"]


def _log(kt):
    """To stderr, like every reader's raw material: executions of each
    step program and the ten largest families inside each, ms a run."""
    if kt is None:
        return
    top = {m: {f: round(s / kt["runs"][m] * 1e3, 3) for f, s in sorted(
        c.items(), key=lambda fs: -fs[1])[:10]}
        for m, c in kt["seconds"].items() if kt["runs"].get(m)}
    print("[perf] kernel_times: " + json.dumps(
        {"runs": kt["runs"], "ms_per_run": top}), file=sys.stderr,
        flush=True)


def reduce_file(path):
    devices, bench = trace_reduce.read_planes(path)
    if not devices or not bench:
        return None
    lo, hi = bench[0][1], max(e for _, _, e in bench)
    lines = devices[min(devices)]
    mods = sorted((s, e, trace_reduce.module_name(n))
                  for n, s, e in lines.get(trace_reduce.MODULES_LINE, [])
                  if s >= lo and e <= hi)
    starts = [m[0] for m in mods]
    runs = collections.Counter(m[2] for m in mods)
    seconds = collections.defaultdict(collections.Counter)
    ops = [ev for ev in lines.get(trace_reduce.OPS_LINE, [])
           if ev[1] >= lo and ev[2] <= hi]
    # self time needs the nesting, which lives on one line: attribute
    # each event's self time to the module running at its start
    by_start = collections.defaultdict(list)
    for name, own in _self_times_with_start(ops):
        by_start[name[1]].append((name[0], own))
    for start, items in by_start.items():
        i = bisect.bisect_right(starts, start) - 1
        if i < 0 or start > mods[i][1]:
            continue
        for name, own in items:
            seconds[mods[i][2]][trace_reduce.op_family(name)] += own
    return {"runs": dict(runs),
            "seconds": {m: dict(c) for m, c in seconds.items()}}


def _self_times_with_start(events):
    """trace_reduce.self_times, each name carried with its start."""
    tagged = [((name, s), s, e) for name, s, e in events]
    return trace_reduce.self_times(tagged)


def per_run(rec, module, family):
    """Mean self seconds of `family` per execution of `module`; None when
    either is absent from the trace."""
    kt = of(rec)
    if not kt or not kt["runs"].get(module):
        return None
    total = kt["seconds"].get(module, {}).get(family)
    return None if not total else total / kt["runs"][module]
