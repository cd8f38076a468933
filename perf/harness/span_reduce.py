"""Device-0 idle time attributed to the serving engine's own host spans.

The engine emits `cb.*` spans (`paddle_tpu.profiler.RecordEvent`, always
on; names and extents in docs/observability.md) that land on the host
plane of the benchmark's `jax.profiler` session beside the harness's
`bench.*` spans, on the device lines' clock. This reads them from the
same `.xplane.pb` as `trace_reduce` and answers what its `idle_gaps`
cannot: which PART of `eng.step()` the chip waited for.

  - The window is `trace_reduce`'s: first `bench.*` start to last
    `bench.*` end. Idle is the complement of the union of device 0's
    `XLA Ops` events in it.
  - Every idle gap is cut at span boundaries and each piece credited to
    ONE owner: the innermost `cb.*` span that covers it, else the
    innermost `bench.*` span, else `(no span)`. Pieces sum to the idle
    time exactly; nothing is counted twice.
  - A step is a `cb.step` span; its interval runs from its start to the
    next `cb.step`'s start (the last one's to the window's end), so the
    caller's time between two steps belongs to the earlier one. A step
    is classed `decode` or `prefill` by the `cb.decode_step` /
    `cb.prefill_chunk` span inside it; one with neither (nothing to do)
    has no class and is left out of every per-step number.

Only the host thread that carries the `bench.*` spans is read: spans of
one thread nest, which "innermost" relies on.

What it cannot do better than the profiler: host spans and device events
are on one clock only as well as the profiler aligned the two. In the
recorded fixture (tests/perf_bench/data/small_tpu_spans.xplane.pb) every
program starts on the device's clock 0.7-0.8 ms BEFORE the host's clock
enters the span that dispatches it. An offset moves idle time between
NEIGHBOURING spans (dispatch against fetch); a step's total does not care.
"""
import bisect
import collections
import glob
import json
import os
import sys

from harness import manifest, stats, trace_reduce

STEP = "cb.step"
CB, NO_SPAN = "cb.", "(no span)"
CLASS_OF = {"cb.decode_step": "decode", "cb.prefill_chunk": "prefill"}
# the host is blocked on the device inside these: not its own work
BLOCKED = ("cb.decode.fetch", "cb.prefill.first_token")
# who an idle piece is charged to, by its owner's name: the chip has
# finished and the result is on its way to the host (fetch), the token
# bookkeeping (push), outside the engine (caller), and everything the
# engine does before the next program starts (prepare)
FETCH = ("cb.decode.fetch", "cb.prefill.first_token", "cb.decode_step")
PUSH = ("cb.decode.push",)
GROUPS = ("prepare", "fetch", "push", "caller")


def group_of(owner):
    if not owner.startswith(CB):
        return "caller"
    if owner in FETCH:
        return "fetch"
    if owner in PUSH:
        return "push"
    return "prepare"


def read_host_spans(path):
    """[(name, start_s, end_s)] of the `bench.*` and `cb.*` spans on the
    host thread(s) that carry `bench.*` spans."""
    from jax.profiler import ProfileData
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            mine = [(ev.name, ev.start_ns * 1e-9,
                     (ev.start_ns + ev.duration_ns) * 1e-9)
                    for ev in line.events
                    if ev.name.startswith((trace_reduce.SPAN_PREFIX, CB))]
            if any(n.startswith(trace_reduce.SPAN_PREFIX)
                   for n, _, _ in mine):
                spans.extend(mine)
    return spans


def _owner(stack):
    """Innermost cb.* span open, else innermost bench.* span, else none."""
    for name, _, _ in reversed(stack):
        if name.startswith(CB):
            return name
    return stack[-1][0] if stack else NO_SPAN


def owners(spans, lo, hi):
    """[(t0, t1, owner)]: [lo, hi] cut at every span boundary, each piece
    with the one span it is charged to. `spans` nest (one thread) and
    come sorted by start, outer before inner."""
    out, stack, t = [], [], lo

    def upto(until):
        nonlocal t
        until = min(until, hi)
        if until > t:
            out.append((t, until, _owner(stack)))
            t = until

    for sp in spans:
        while stack and stack[-1][2] <= sp[1]:
            upto(stack[-1][2])
            stack.pop()
        upto(sp[1])
        stack.append(sp)
    while stack:
        upto(stack[-1][2])
        stack.pop()
    upto(hi)
    return out


def idle_pieces(busy, segments, lo, hi):
    """[(t0, t1, owner)]: the complement of `busy` (disjoint, sorted,
    inside [lo, hi]) cut by `segments` (as `owners` returns them)."""
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    pieces, k = [], 0
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 <= g0:
            continue
        while k < len(segments) and segments[k][1] <= g0:
            k += 1
        j = k
        while j < len(segments) and segments[j][0] < g1:
            s0, s1, owner = segments[j]
            pieces.append((max(s0, g0), min(s1, g1), owner))
            j += 1
    return pieces


def reduce_spans(spans, busy, lo, hi):
    """The attribution, from intervals alone. None without a `cb.step`
    span in the window (the parent commit's trace)."""
    spans = sorted((sp for sp in spans if sp[1] >= lo and sp[2] <= hi),
                   key=lambda sp: (sp[1], -sp[2]))
    steps = [sp for sp in spans if sp[0] == STEP]
    if not steps:
        return None
    starts = [s for _, s, _ in steps]
    per_step = [{"kind": None, "dur_s": e - s, "blocked_s": 0.0,
                 "idle_s": 0.0, "idle_by": dict.fromkeys(GROUPS, 0.0)}
                for _, s, e in steps]
    for name, s, e in spans:
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or e > steps[i][2]:
            continue                        # not inside a step
        if name in CLASS_OF:
            per_step[i]["kind"] = CLASS_OF[name]
        if name in BLOCKED:
            per_step[i]["blocked_s"] += e - s
    by_span = collections.Counter()
    for t0, t1, owner in idle_pieces(busy, owners(spans, lo, hi), lo, hi):
        by_span[owner] += t1 - t0
        i = bisect.bisect_right(starts, t0) - 1
        if i >= 0:
            per_step[i]["idle_s"] += t1 - t0
            per_step[i]["idle_by"][group_of(owner)] += t1 - t0
    return {"window_s": hi - lo, "idle_s": sum(by_span.values()),
            "idle_by_span": dict(by_span.most_common()),
            "steps": per_step}


def reduce_file(path):
    """`reduce_spans` of one `.xplane.pb`; None without a device plane
    (a CPU rehearsal), without `bench.*` spans, or without `cb.step`."""
    devices, bench = trace_reduce.read_planes(path)
    if not devices or not bench:
        return None
    lo, hi = bench[0][1], max(e for _, _, e in bench)
    lines = devices[min(devices)]
    ops = lines.get(trace_reduce.OPS_LINE) or \
        lines.get(trace_reduce.MODULES_LINE, [])
    busy = trace_reduce.union([(s, e) for _, s, e in ops], lo, hi)
    return reduce_spans(read_host_spans(path), busy, lo, hi)


def newest_trace():
    found = glob.glob(os.path.join(
        manifest.ROOT, ".perf_trace", "*", "plugins", "profile", "*",
        "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def of(rec):
    """The reduction for this run's record, made once and kept in it
    (under rec["trace"]["host_spans"]). A reader gets only the record,
    which holds no path: when rec["trace"] is there this process has
    just written its trace, and it is the newest under .perf_trace/."""
    tr = rec.get("trace")
    if not tr:
        return None
    if "host_spans" not in tr:
        path = newest_trace()
        tr["host_spans"] = reduce_file(path) if path else None
        _log(tr["host_spans"])
    return tr["host_spans"]


def classed(red, kind=None):
    """The steps that ran a program (of class `kind`, if given)."""
    return [st for st in red["steps"]
            if st["kind"] is not None and kind in (None, st["kind"])]


def steps_of(rec, kind=None):
    """`classed` steps of this run's record; [] with nothing to read."""
    red = of(rec)
    return classed(red, kind) if red else []


def gap_ms_per_step(rec, group):
    """Mean over classed steps of the idle time charged to `group`, ms.
    The four groups sum to the mean idle time of a step."""
    return stats.mean(st["idle_by"][group] * 1e3 for st in steps_of(rec))


def _log(red):
    if red is None:
        return
    steps = classed(red)
    print("[perf] span_reduce: " + json.dumps({
        "window_s": red["window_s"], "idle_s": red["idle_s"],
        "idle_in_classed_steps_s": sum(st["idle_s"] for st in steps),
        "steps": collections.Counter(
            str(st["kind"]) for st in red["steps"]),
        "idle_by_span": red["idle_by_span"]}), file=sys.stderr, flush=True)


def program_totals():
    """`paddle_tpu.profiler.span_totals()` of this process: {name:
    (count, seconds)}; None where the program has none (the parent)."""
    try:
        from paddle_tpu.profiler import span_totals
    except ImportError:
        return None
    return span_totals()
