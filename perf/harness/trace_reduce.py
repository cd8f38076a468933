"""From the profiler's `.xplane.pb` to device numbers.

Read with `jax.profiler.ProfileData` and nothing else. What the reduction
relies on, as seen in traces of this stack on the v5e (PERF.md, PR 22):

  - one plane per chip, named `/device:TPU:<n>`; its line `XLA Ops` has
    one event per HLO operation run, named by the operation's whole HLO
    text (`%fusion.12 = bf16[...] fusion(...)`: the name is what stands
    before ` = `). A `while` spans the operations of its body, which
    follow on the same line, so time is attributed to the innermost
    event: self time. Asynchronous copies sit on a line of their own
    (`Async XLA Ops`) and are not counted as busy time. Its line
    `XLA Modules` has one event per executable run, named
    `<module>(<fingerprint>)`;
  - host threads under `/host:CPU`, where the harness's own
    `jax.profiler.TraceAnnotation`s (`bench.*`) appear by name on the
    python thread's line, on the same clock as the device lines;
  - a Pallas kernel appears as a `custom-call` whose HLO name comes from
    jax's name stack (`closed_call`, `checkpoint`, ...), not from the
    kernel: naming kernels is the next tracing issue's.

The traced window runs from the first `bench.*` span's start to the last
one's end. Busy time of a chip is the union of its operations' intervals
inside that window; an idle gap is attributed to the `bench.*` spans it
overlaps.
"""
import collections
import re

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")
_COLLECTIVE_OP = re.compile(
    r" (all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(?:-start|-done)?\(")
_NUMBERED = re.compile(r"[.\d]+$")
_PLAIN_FUSION = re.compile(r"^fusion(\.\d+)*$")
_FUSION_KIND = re.compile(r"kind=(k\w+)")
SPAN_PREFIX = "bench."
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def op_name(text):
    """`%negate_add_fusion.2 = bf16[...] fusion(...)` ->
    `negate_add_fusion.2`. A fusion XLA left unnamed gets its kind from
    the text: `%fusion.12 = ... kind=kOutput, ...` -> `fusion.kOutput.12`
    (kOutput and kConvolution fusions are the matrix multiplications)."""
    name = text.split(" = ", 1)[0].lstrip("%")
    if _PLAIN_FUSION.match(name):
        kind = _FUSION_KIND.search(text)
        if kind:
            return f"fusion.{kind.group(1)}{name[len('fusion'):]}"
    # a collective that jax named after its own primitive
    # (`%psum.3 = ... all-reduce(...)`) is told by its opcode
    op = _COLLECTIVE_OP.search(text)
    if op and not _COLLECTIVE.search(name):
        return f"{op.group(1)}.{name}"
    return name


def _events(line):
    return [(op_name(ev.name), ev.start_ns * 1e-9,
             (ev.start_ns + ev.duration_ns) * 1e-9) for ev in line.events]


def read_planes(path):
    """(device ordinal -> {line name -> [(name, start_s, end_s)]},
    [bench.* host spans])."""
    from jax.profiler import ProfileData
    devices, spans = {}, []
    for plane in ProfileData.from_file(path).planes:
        m = _DEVICE.match(plane.name)
        if m:
            devices[int(m.group(1))] = {
                line.name: _events(line) for line in plane.lines
                if line.name in (OPS_LINE, MODULES_LINE)}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(e for e in _events(line)
                             if e[0].startswith(SPAN_PREFIX))
    return devices, sorted(spans, key=lambda e: e[1])


def union(intervals, lo, hi):
    """Disjoint sorted union of [start, end) intervals clipped to [lo, hi]."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def self_times(events):
    """[(name, self seconds)] for the events of ONE line: an event's own
    duration less that of the events nested inside it."""
    out, stack = [], []             # stack of [name, end, self]
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and s >= stack[-1][1]:
            top = stack.pop()
            out.append((top[0], top[2]))
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    out.extend((name, own) for name, _, own in stack)
    return out


def op_family(name):
    """`fusion.1234` -> `fusion`: an unrolled program has one numbered
    instance of an operation per layer, and the table is by kind."""
    return _NUMBERED.sub("", name) or name


def module_name(name):
    return name.split("(", 1)[0]


def reduce_trace(path, n_chips):
    """The numbers the result line and the per-layer readers need; None if
    the trace holds no device plane (a CPU rehearsal)."""
    devices, spans = read_planes(path)
    if not devices:
        return None
    used = sorted(devices)[:n_chips]
    ops = {d: devices[d].get(OPS_LINE) or devices[d].get(MODULES_LINE, [])
           for d in used}
    if spans:
        lo, hi = spans[0][1], max(e for _, _, e in spans)
    else:
        every = [ev for d in used for ev in ops[d]]
        lo, hi = min(s for _, s, _ in every), max(e for _, _, e in every)
    busy = {d: union([(s, e) for _, s, e in ops[d]], lo, hi) for d in used}
    busy_s = {d: sum(e - s for s, e in busy[d]) for d in used}

    d0 = used[0]
    inside = [ev for ev in ops[d0] if ev[2] > lo and ev[1] < hi]
    by_family = collections.Counter()
    collective_s = 0.0
    for name, own in self_times(inside):
        by_family[op_family(name)] += own
        if _COLLECTIVE.search(name):
            collective_s += own

    idle = collections.Counter()
    edges = [lo] + [t for iv in busy[d0] for t in iv] + [hi]
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        left = g1 - g0
        for name, s, e in spans:
            over = min(e, g1) - max(s, g0)
            if over > 0:
                idle[name] += over
                left -= over
        if left > 1e-9:
            idle["(no bench span)"] += left

    modules = collections.defaultdict(list)
    for name, s, e in devices[d0].get(MODULES_LINE, []):
        if s >= lo and e <= hi:
            modules[module_name(name)].append(e - s)
    return {
        "window_s": hi - lo,
        "busy_s": sum(busy_s.values()) / len(used),
        "busy_s_per_chip": [busy_s[d] for d in used],
        "collective_s": collective_s,
        "device_ops": [[n, s] for n, s in by_family.most_common(10)],
        "idle_gaps": [[n, s] for n, s in idle.most_common(10)],
        "modules": dict(modules),
    }
