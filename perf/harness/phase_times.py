"""Device time of a step program by MODEL PHASE and, in training, by pass.

The program opens its phases as `jax.named_scope`s from one closed
vocabulary (`paddle_tpu.profiler.PHASES`, imported here: one source), so
every device operation's name stack says which part of the model issued
it. This reads the stacks with `scope_times.read_device_ops` (the one
wire parser), takes self times with `trace_reduce.self_times` and the
executables from the `XLA Modules` line as `kernel_times` does: device 0,
whole executables inside the traced window only (`whole_runs`).

  phase_of(stack)  the first path part, from the left, that is a phase,
                   else "(unphased)". jax renders a transform around the
                   scope that follows it (`transpose(jvp(loss))/mul`), so
                   a part is read with its wrappers off (all but `jit(`:
                   `jit(loss)` is a function's name, no scope). Outermost
                   wins: `attend/sparse_select/top_k` is `attend`.
  pass_of(stack)   "recompute" if a part is `rematted_computation`, else
                   "backward" if a part starts with `transpose(`, else
                   "forward" (as jax 0.9.0 names `jax.grad(jax.checkpoint(
                   f))`: `transpose(jvp())/checkpoint/rematted_computation
                   /attn_proj/dot_general` is the recomputed forward,
                   `transpose(jvp())/checkpoint/attn_proj/transpose` the
                   backward). The `optimizer` phase is a pass of its own,
                   `grad_sync` counts as backward.

A FUSION goes where its own metadata says. On this compiler that is the
stack of the product inside it: the weight-gradient matmuls that XLA
fuses into the layer scan's `dynamic-update-slice` read `attn_proj` /
`ffn` / `loss`, backward (chip traces, PR 34).

An operation with NO name stack at all is the compiler's own (a layout
`copy`, a `copy-done` / `slice-done`) or one whose lowering drops the
stack (jax's cumsum becomes a `reduce-window` product named only
`reduce_window_sum`). It is counted with its NEIGHBOURS IN TIME where
they agree: the nearest operation with a stack before it and the nearest
after it, in the same run of the same executable, under one phase and
pass (`_inherit`; checked against the trace's HLO for the largest case,
AdamW's float32 relayout copies, whose operand and user are both
`optimizer/convert_element_type`). Between two phases, at a program's
edge, or beside an operation whose stack holds no phase (the layer
scan's own slicing, the megakernel) it stays "(unphased)", which is
reported and never spread; what was inherited is logged beside it. A
stack-less COLLECTIVE never inherits: XLA's combiner merges the ZeRO
gradient all-reduces into operations without metadata that run between
the optimizer's updates, a tenth of the four-chip step, and calling them
`optimizer` because of where they run would be a guess with a name.

In every program the phases and the unphased remainder sum to the
program's device self time, held here against the union of its
operations' intervals to 1e-6 of the total. `of(rec)` is made once, kept
in `rec["trace"]` and logged to stderr: per step program, milliseconds a
run by phase x pass and under each the three largest operation families.
None without a device plane.
"""
import bisect
import collections
import json
import re
import sys

from harness import scope_times, span_reduce, trace_reduce

try:
    from paddle_tpu.profiler import PHASES
except ImportError:          # a program from before the vocabulary
    PHASES = ()

UNPHASED = "(unphased)"
_WRAPPED = re.compile(r"^(?:(?!p?jit\()\w+\()+(\w+)\)+$")
_PASS_OF_PHASE = {"optimizer": "optimizer", "grad_sync": "backward"}
LOG_SHARE = 0.01        # programs under this share of device time: no log


def phase_of(stack):
    for part in stack.rstrip(":").split("/"):
        m = _WRAPPED.match(part)
        name = m.group(1) if m else part
        if name in PHASES:
            return name
    return UNPHASED


def pass_of(stack):
    parts = stack.split("/")
    if "rematted_computation" in parts:
        return "recompute"
    if any(p.startswith("transpose(") for p in parts):
        return "backward"
    return "forward"


def _inherit(placed):
    """An operation the compiler left with NO name stack (a layout `copy`,
    a `copy-done`, the product jax's cumsum becomes) is counted with its
    neighbours in time where they agree: the nearest operation with a
    stack before it and the nearest after it, in the same run of the same
    executable, under one phase and pass. Anywhere else (between two
    phases, at a program's edge, beside the scan's own unphased slicing)
    it stays "(unphased)", and so does a collective whatever surrounds
    it. `placed` is sorted by start and changed in place; returns the
    indices that took a neighbour's phase."""
    def nearest(order):
        seen, out = None, {}
        for k in order:
            _, run, phase, pas = placed[k][:4]
            if phase is None:
                out[k] = seen[1] if seen and seen[0] == run else None
            else:
                seen = (run, (phase, pas))
        return out

    before = nearest(range(len(placed)))
    after = nearest(range(len(placed) - 1, -1, -1))
    took = []
    for k, near in before.items():
        if near and near == after[k] and near[0] != UNPHASED \
                and not trace_reduce._COLLECTIVE.search(placed[k][4]):
            placed[k][2:4] = near
            took.append(k)
        else:
            placed[k][2:4] = UNPHASED, "forward"
    return took


def reduce_ops(ops, mods):
    """ops [(stack, hlo name, start_s, end_s)] and mods [(start_s, end_s,
    module)] sorted -> {"runs": {module: executions}, "seconds": {module:
    {phase: {pass: self seconds}}}, "families": {module: {phase: {pass:
    {family: self seconds}}}}, "inherited": {module: self seconds placed
    by `_inherit`}}."""
    starts = [m[0] for m in mods]
    placed = []     # [start, run index, phase, pass, family, self seconds]
    for (stack, name, start, end), own in trace_reduce.self_times(
            [((stack, name, s, e), s, e) for stack, name, s, e in ops]):
        i = bisect.bisect_right(starts, start) - 1
        if i < 0 or start > mods[i][1]:
            continue
        phase = phase_of(stack) if stack else None      # None: no stack
        pas = _PASS_OF_PHASE.get(phase) or pass_of(stack)
        placed.append([start, i, phase, pas,
                       trace_reduce.op_family(name), own, end])
    placed.sort(key=lambda op: op[0])
    inherited = collections.Counter()
    for k in _inherit(placed):
        inherited[mods[placed[k][1]][2]] += placed[k][5]
    seconds, spans = {}, collections.defaultdict(list)
    for start, i, phase, pas, family, own, end in placed:
        mod = mods[i][2]
        spans[mod].append((start, end))
        fam = seconds.setdefault(mod, {}).setdefault(phase, {}).setdefault(
            pas, collections.Counter())
        fam[family] += own
    for mod, by_phase in seconds.items():
        total = sum(sum(f.values()) for p in by_phase.values()
                    for f in p.values())
        busy = sum(e - s for s, e in trace_reduce.union(
            spans[mod], float("-inf"), float("inf")))
        assert abs(total - busy) <= 1e-6 * busy, (
            f"{mod}: phases and the unphased remainder sum to {total} s, "
            f"its operations cover {busy} s")
    return {
        "runs": dict(collections.Counter(m[2] for m in mods)),
        "seconds": {m: {ph: {pa: sum(f.values()) for pa, f in p.items()}
                        for ph, p in by.items()}
                    for m, by in seconds.items()},
        "families": {m: {ph: {pa: dict(f) for pa, f in p.items()}
                         for ph, p in by.items()}
                     for m, by in seconds.items()},
        "inherited": dict(inherited)}


def whole_runs(modules, first_op_start, lo, hi):
    """[(start_s, end_s, module)] sorted: the executions whole inside the
    traced window [lo, hi]. One that was already running when the device
    trace began is recorded from that instant, as if it started there (a
    training step cut to 0.61 of its 0.77 s, chip trace, PR 34): an
    execution that starts with the trace's first device operation is left
    out, cut or not."""
    return sorted((s, e, trace_reduce.module_name(n)) for n, s, e in modules
                  if s >= lo and e <= hi and s > first_op_start + 1e-6)


def reduce_file(path):
    ops = scope_times.read_device_ops(path)
    devices, bench = trace_reduce.read_planes(path)
    if not ops or not devices or not bench:
        return None
    lo, hi = bench[0][1], max(e for _, _, e in bench)
    mods = whole_runs(
        devices[min(devices)].get(trace_reduce.MODULES_LINE, []),
        min(op[2] for op in ops), lo, hi)
    return reduce_ops([op for op in ops if op[2] >= lo and op[3] <= hi],
                      mods)


def of(rec):
    tr = rec.get("trace")
    if not tr:
        return None
    if "phase_times" not in tr:
        path = span_reduce.newest_trace()
        tr["phase_times"] = reduce_file(path) if path else None
        _log(tr["phase_times"])
    return tr["phase_times"]


def _total(by_phase):
    return sum(s for p in by_phase.values() for s in p.values())


def _log(pt):
    if pt is None:
        return
    everything = sum(_total(by) for by in pt["seconds"].values())
    out = {}
    for mod, by_phase in pt["seconds"].items():
        runs = pt["runs"].get(mod)
        if not runs or _total(by_phase) < LOG_SHARE * everything:
            continue

        def ms(s):
            return round(s / runs * 1e3, 3)

        phases = {}
        for ph, by_pass in by_phase.items():
            for pa, s in by_pass.items():
                fams = sorted(pt["families"][mod][ph][pa].items(),
                              key=lambda fv: -fv[1])[:3]
                phases.setdefault(ph, {})[pa] = {
                    "ms": ms(s), "top": {f: ms(v) for f, v in fams}}
        out[mod] = {"runs": runs, "ms_per_run": ms(_total(by_phase)),
                    "inherited_ms": ms(pt["inherited"].get(mod, 0.0)),
                    "phases": phases}
    print("[perf] phase_times: " + json.dumps(out), file=sys.stderr,
          flush=True)


def per_run(rec, module, phases, passes=None):
    """Mean self seconds of the operations under `phases` (in `passes`;
    None: every pass) per execution of `module`. None when the module did
    not run in the trace or nothing ran under them. A name outside the
    vocabulary is a reader's typo, and would read None for ever."""
    unknown = set(phases) - set(PHASES) if PHASES else None
    if unknown:
        raise ValueError(f"no model phase {sorted(unknown)} (have {PHASES})")
    pt = of(rec)
    if not pt or not pt["runs"].get(module):
        return None
    by_phase = pt["seconds"].get(module, {})
    total = sum(s for ph in phases for pa, s in by_phase.get(ph, {}).items()
                if passes is None or pa in passes)
    return total / pt["runs"][module] if total else None


def ms(rec, module, phases, passes=None):
    s = per_run(rec, module, phases, passes)
    return None if s is None else s * 1e3


def unphased_share(rec, module):
    """Self time of `module` under no phase / all of it."""
    pt = of(rec)
    if not pt or not pt["seconds"].get(module):
        return None
    by_phase = pt["seconds"][module]
    return sum(by_phase.get(UNPHASED, {}).values()) / _total(by_phase)


def train_module(rec):
    """The training executable: the module with most device time in a
    `kind == "train"` record."""
    pt = of(rec) if rec.get("kind") == "train" else None
    if not pt or not pt["seconds"]:
        return None
    return max(pt["seconds"], key=lambda m: _total(pt["seconds"][m]))
