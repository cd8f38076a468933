"""`perf/harness/phase_times.py` and the twenty per-phase readers (PR 34):
the rules that place a name stack, the sum rule, `per_run` on a made-up
record, every reader silent where there is nothing to read (no trace, a
CPU rehearsal, the PARENT's trace with no phase in it), the manifest's
entries found by name, and the recorded TPU trace of a small program
that opens its phases through `paddle_tpu.profiler.phase` under
`jax.checkpoint` (tests/perf_bench/record_phase_fixture.py, run on the
chip by this PR) reduced to the seconds the recorder printed.
"""
import importlib.util
import io
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PERF = os.path.join(ROOT, "perf")
if PERF not in sys.path:
    sys.path.insert(0, PERF)

from harness import kernel_times, manifest, phase_times, span_reduce  # noqa: E402,E501

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
FIXTURE = os.path.join(DATA, "small_tpu_phases.xplane.pb")
PARENT_FIXTURE = os.path.join(DATA, "small_tpu_spans.xplane.pb")
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
U = phase_times.UNPHASED

SPARSE3 = ["serve-moe-window-mixedlen", "serve-mla-sparse-longdoc",
           "serve-sparse-gqa-longctx"]
SERVE4 = ["serve-decode-saturated"] + SPARSE3
TRAIN = ["train-dense-1chip", "train-hybrid-4chip"]
_PHASE_MS = ("attn_proj", "kv_write", "attend", "ffn", "head")
# name -> (unit, layer, moves, workloads)
ENTRIES = {}
for _prog, _cells in (("decode", SPARSE3), ("prefill", SERVE4)):
    for _ph in _PHASE_MS:
        ENTRIES[f"{_prog}_{_ph}_ms"] = (
            "ms", "step_programs", "tpot_ms_p50", _cells)
    ENTRIES[f"{_prog}_unphased_share"] = (
        "fraction", "step_programs", "tpot_ms_p50", _cells)
for _n in ("fwd", "recompute", "bwd", "optimizer", "attn", "ffn", "loss"):
    ENTRIES[f"train_{_n}_ms"] = (
        "ms", "training_step", "train_tokens_per_s_per_chip", TRAIN)
ENTRIES["train_unphased_share"] = (
    "fraction", "training_step", "train_tokens_per_s_per_chip", TRAIN)


def _read(name, rec):
    return manifest.load_plugin("layer_metrics", name).read(rec)


STACKS = {
    # jax 0.9.0, a scope under jax.grad(jax.checkpoint(f))
    "jit(f)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
    "attn_proj/dot_general": ("attn_proj", "recompute"),
    "jit(f)/transpose(jvp(jvp()))/checkpoint/attn_proj/transpose":
        ("attn_proj", "backward"),
    "jit(f)/jvp(jvp())/checkpoint/attn_proj/dot_general":
        ("attn_proj", "forward"),
    # outermost wins: the sparse scopes are nested inside `attend`
    "jit(step)/jit(main)/attend/sparse_select/top_k":
        ("attend", "forward"),
    # a transform renders AROUND the scope that follows it
    "jit(step_fn)/transpose(jvp(loss))/while/body/mul":
        ("loss", "backward"),
    "jit(step_fn)/jvp(embed)/gather": ("embed", "forward"),
    "jit(step_fn)/optimizer/sub": ("optimizer", "forward"),
    "jit(f)/vmap(transpose(jvp(ffn)))/mul": ("ffn", "forward"),
    # no phase: a jit that happens to be called like one is no scope
    "jit(loss)/mul": (U, "forward"),
    "jit(step_fn)/transpose(jvp())/while/body/dynamic_update_slice":
        (U, "backward"),
    "jit(step)/jit(main)/ffnx/dot_general:": (U, "forward"),
    "": (U, "forward"),
}


@pytest.mark.parametrize("stack", STACKS)
def test_phase_and_pass_of_a_name_stack(stack):
    assert (phase_times.phase_of(stack),
            phase_times.pass_of(stack)) == STACKS[stack]


def _ops():
    """Two runs of `jit_step` and one of `jit_other`: a `while` spans two
    nested operations, so its own time is what they leave."""
    mods = [(0.0, 1.0, "jit_step"), (2.0, 3.0, "jit_step"),
            (4.0, 4.5, "jit_other")]
    ops = [
        ("jit(step)/attn_proj/dot_general", "fusion.kOutput.1", 0.0, 0.2),
        ("jit(step)/attend/while", "while.3", 0.2, 0.8),
        ("jit(step)/attend/while/body/dot_general", "fusion.2", 0.2, 0.4),
        ("jit(step)/attend/sparse_select/top_k", "sort.5", 0.5, 0.7),
        ("jit(step)/copy", "copy.9", 0.8, 0.9),
        ("jit(step)/ffn/experts/custom_call", "moe_grouped_matmul.4",
         2.0, 2.5),
        ("jit(step)/transpose(jvp(ffn))/mul", "fusion.7", 2.5, 2.75),
        ("jit(step)/optimizer/sub", "subtract_convert_fusion", 2.75, 3.0),
        ("", "copy.1", 4.0, 4.5),
        ("jit(step)/ffn/mul", "fusion.8", 9.0, 9.5),    # in no module
    ]
    return ops, mods


def test_reduce_holds_the_sum_rule_and_splits_by_phase_and_pass():
    ops, mods = _ops()
    pt = phase_times.reduce_ops(ops, mods)
    assert pt["runs"] == {"jit_step": 2, "jit_other": 1}
    step = pt["seconds"]["jit_step"]
    assert step["attn_proj"] == {"forward": pytest.approx(0.2)}
    # the while's own 0.2 s and its two children's 0.4 s
    assert step["attend"] == {"forward": pytest.approx(0.6)}
    assert step["ffn"] == {"forward": pytest.approx(0.5),
                           "backward": pytest.approx(0.25)}
    assert step["optimizer"] == {"optimizer": pytest.approx(0.25)}
    assert step[U] == {"forward": pytest.approx(0.1)}
    assert pt["seconds"]["jit_other"] == {U: {"forward": 0.5}}
    # phases + unphased = the program's device self time
    assert sum(s for p in step.values() for s in p.values()) \
        == pytest.approx(1.9)
    assert pt["families"]["jit_step"]["attend"]["forward"] == {
        "while": pytest.approx(0.2), "fusion": pytest.approx(0.2),
        "sort": pytest.approx(0.2)}


def test_an_operation_without_a_stack_is_counted_with_agreeing_neighbours():
    mods = [(0.0, 1.0, "jit_step"), (1.0, 2.0, "jit_step")]
    ffn, att = "jit(step)/ffn/mul", "jit(step)/attend/dot"
    ops = [
        ("", "copy.1", 0.0, 0.1),               # a program's edge
        (ffn, "fusion.1", 0.1, 0.2),
        ("", "copy.2", 0.2, 0.3),               # between ffn and ffn
        ("", "copy-done.3", 0.3, 0.4),          # the same, two in a row
        (ffn, "fusion.2", 0.4, 0.5),
        ("", "copy.4", 0.5, 0.6),               # between ffn and attend
        (att, "fusion.3", 0.6, 0.7),
        ("", "copy.5", 0.7, 0.8),               # beside a phase-less stack
        ("jit(step)/while/body/squeeze", "fusion.4", 0.8, 0.9),
        (att, "fusion.5", 0.9, 1.0),
        ("", "copy.6", 1.0, 1.1),               # the next run's edge
        (att, "fusion.6", 1.1, 1.2),
        ("", "copy.7", 1.2, 1.3),
        ("jit(step)/transpose(jvp(attend))/dot", "fusion.7", 1.3, 1.4),
        (ffn, "fusion.8", 1.4, 1.5),
        ("", "all-reduce.9", 1.5, 1.7),         # a collective never does
        (ffn, "fusion.9", 1.7, 1.8),
    ]
    pt = phase_times.reduce_ops(ops, mods)
    step = pt["seconds"]["jit_step"]
    assert step["ffn"] == {"forward": pytest.approx(0.6)}
    assert pt["families"]["jit_step"]["ffn"]["forward"] == {
        "fusion": pytest.approx(0.4), "copy": pytest.approx(0.1),
        "copy-done": pytest.approx(0.1)}
    # forward and backward of one phase do not agree either
    assert step["attend"] == {"forward": pytest.approx(0.3),
                              "backward": pytest.approx(0.1)}
    assert step[U] == {"forward": pytest.approx(0.8)}
    assert pt["families"]["jit_step"][U]["forward"]["all-reduce"] \
        == pytest.approx(0.2)
    assert pt["inherited"] == {"jit_step": pytest.approx(0.2)}


def test_reduce_refuses_operations_it_would_count_twice():
    """Two operations that overlap without nesting inside a third: self
    times no longer partition the covered time, and the reader says so
    rather than report phases that sum to more than the program."""
    mods = [(0.0, 1.0, "jit_step")]
    ops = [("jit(step)/attend/while", "while.1", 0.0, 1.0),
           ("jit(step)/attend/a", "fusion.1", 0.1, 0.5),
           ("jit(step)/ffn/b", "fusion.2", 0.4, 0.9)]
    with pytest.raises(AssertionError, match="sum to"):
        phase_times.reduce_ops(ops, mods)


def test_an_execution_cut_by_the_trace_start_is_no_whole_run():
    """The device trace records a running executable from the instant
    tracing began: its event starts WITH the first device operation."""
    modules = [("jit_step_fn(123)", 10.003, 10.614),   # cut: 0.61 of 0.77
               ("jit_step_fn(123)", 10.614, 11.381),
               ("jit__unstack(9)", 11.381, 11.381),
               ("jit_step_fn(123)", 11.381, 12.148),
               ("jit_step_fn(123)", 12.148, 12.915)]    # past the window
    got = phase_times.whole_runs(modules, 10.003, 10.0, 12.5)
    assert got == [(10.614, 11.381, "jit_step_fn"),
                   (11.381, 11.381, "jit__unstack"),
                   (11.381, 12.148, "jit_step_fn")]
    # a trace that began on an idle device loses one whole run, no more
    assert len(phase_times.whole_runs(modules[1:], 10.614, 10.0, 12.5)) == 2


def _rec(seconds, runs=3, kind="serve"):
    """A record as perf/run.py hands it to a reader after a traced run,
    the table already made (`phase_times.of` keeps it)."""
    return {"kind": kind, "trace": {"phase_times": None if seconds is None
                                    else {"runs": {m: runs for m in seconds},
                                          "seconds": seconds}}}


def test_per_run_on_a_made_up_record():
    rec = _rec({"jit_step": {
        "attn_proj": {"forward": 0.003}, "attend": {"forward": 0.012},
        "embed": {"forward": 0.0003}, "head": {"forward": 0.0012},
        U: {"forward": 0.0015}}})
    assert phase_times.per_run(rec, "jit_step", ("attend",)) \
        == pytest.approx(0.004)
    assert phase_times.per_run(rec, "jit_step", ("attend", "attn_proj"),
                               passes=("forward",)) == pytest.approx(0.005)
    assert phase_times.per_run(rec, "jit_step", ("attend",),
                               passes=("backward",)) is None
    assert phase_times.per_run(rec, "jit_step", ("ffn",)) is None
    assert phase_times.per_run(rec, "jit_prefill", ("attend",)) is None
    assert _read("decode_attend_ms", rec) == pytest.approx(4.0)
    assert _read("decode_head_ms", rec) == pytest.approx(0.5)
    assert _read("decode_ffn_ms", rec) is None
    assert _read("decode_unphased_share", rec) == pytest.approx(1.5 / 18)
    assert _read("prefill_attend_ms", rec) is None
    assert _read("prefill_unphased_share", rec) is None
    # a reader's typo would read None for ever: refused instead
    with pytest.raises(ValueError, match="atend"):
        phase_times.per_run(rec, "jit_step", ("atend",))


def test_training_readers_split_the_step_by_pass_and_by_phase():
    table = {"jit_step_fn": {
        "embed": {"forward": 0.003, "backward": 0.006},
        "attn_proj": {"forward": 0.06, "recompute": 0.06, "backward": 0.12},
        "attend": {"forward": 0.075, "recompute": 0.075, "backward": 0.15},
        "ffn": {"forward": 0.21, "recompute": 0.21, "backward": 0.42},
        "loss": {"forward": 0.03, "recompute": 0.03, "backward": 0.06},
        "grad_sync": {"backward": 0.03},
        "optimizer": {"optimizer": 0.18},
        U: {"forward": 0.03, "backward": 0.06}},
        "jit_convert": {U: {"forward": 0.001}}}
    rec = _rec(table, kind="train")
    assert phase_times.train_module(rec) == "jit_step_fn"
    got = {n: _read(n, rec) for n in ENTRIES if n.startswith("train_")}
    assert got == {
        "train_fwd_ms": pytest.approx(126.0),
        "train_recompute_ms": pytest.approx(125.0),
        "train_bwd_ms": pytest.approx(262.0),   # grad_sync counts here
        "train_optimizer_ms": pytest.approx(60.0),
        "train_attn_ms": pytest.approx(180.0),
        "train_ffn_ms": pytest.approx(280.0),
        "train_loss_ms": pytest.approx(43.0),
        "train_unphased_share": pytest.approx(0.09 / 1.809)}
    # the four passes and the unphased remainder are the step
    step_ms = 1.809 / 3 * 1e3
    assert got["train_fwd_ms"] + got["train_recompute_ms"] \
        + got["train_bwd_ms"] + got["train_optimizer_ms"] \
        + got["train_unphased_share"] * step_ms == pytest.approx(step_ms)
    # a serving record has no training executable
    assert phase_times.train_module(dict(rec, kind="serve")) is None


@pytest.mark.parametrize("name", ENTRIES)
def test_reader_is_silent_where_there_is_nothing_to_read(name):
    for kind in ("serve", "train"):
        assert _read(name, {"kind": kind}) is None
        assert _read(name, {"kind": kind, "trace": None}) is None   # CPU
        assert _read(name, _rec(None, kind=kind)) is None
    # the PARENT's trace: the programs run, no operation carries a phase
    module = {"decode": "jit_step", "prefill": "jit_prefill",
              "train": "jit_step_fn"}[name.split("_")[0]]
    parent = _rec({module: {U: {"forward": 0.02, "backward": 0.01}}},
                  kind="train" if module == "jit_step_fn" else "serve")
    want = 1.0 if name.endswith("_unphased_share") else None
    assert _read(name, parent) == want


def test_the_parents_recorded_trace_reads_as_unphased(monkeypatch):
    """A real TPU trace from before the phases (PR 24's fixture), through
    `of(rec)` as a run does it: everything under no phase, the table
    agrees with `kernel_times` on what the program took."""
    monkeypatch.setattr(span_reduce, "newest_trace", lambda: PARENT_FIXTURE)
    rec = {"kind": "train", "trace": {"window_s": 1.0}}
    pt = phase_times.of(rec)
    assert rec["trace"]["phase_times"] is pt        # made once, kept
    assert list(pt["seconds"]["jit_small_step"]) == [U]
    assert _read("train_unphased_share", rec) == 1.0
    assert _read("train_fwd_ms", rec) is None
    # `kernel_times` counts the first execution too, which here starts
    # with the trace's first device operation (`whole_runs` leaves it out)
    kt = kernel_times.reduce_file(PARENT_FIXTURE)
    assert pt["runs"] == {"jit_small_step": 3}
    assert kt["runs"] == {"jit_small_step": 4}
    assert sum(pt["seconds"]["jit_small_step"][U].values()) / 3 \
        == pytest.approx(
            sum(kt["seconds"]["jit_small_step"].values()) / 4, rel=0.02)


@pytest.mark.parametrize("name", ENTRIES)
def test_manifest_entry_is_found_by_name(name):
    found = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert len(found) == 1
    unit, layer, moves, cells = ENTRIES[name]
    assert found[0] == {
        "name": name, "unit": unit, "better": "lower",
        "source": "device_trace", "layer": layer, "moves": moves,
        "workloads": cells}
    assert os.path.isfile(os.path.join(PERF, "layer_metrics", name + ".py"))
    known = {w["name"] for w in BENCH["workloads"]}
    assert set(cells) <= known
    # only cells that report the end-to-end metric it moves
    moved = next(m for m in BENCH["end_to_end"] if m["name"] == moves)
    assert set(cells) <= set(moved["workloads"])


def test_the_manifest_grew_by_twenty_entries_and_nothing_else():
    assert len(ENTRIES) == 20
    names = [m["name"] for m in BENCH["per_layer"]]
    assert len(names) == len(set(names))
    assert set(ENTRIES) <= set(names)


# what tests/perf_bench/record_phase_fixture.py writes beside the fixture:
# the reader's table of the kept file (PR 34, one TPU v5e; made again from
# the same file when the reader learned to place stack-less operations)
RECORDED = json.load(open(os.path.join(DATA, "small_tpu_phases.json")))


def test_recorded_tpu_trace_reduces_to_what_the_recorder_printed():
    pt = phase_times.reduce_file(FIXTURE)
    assert pt["runs"] == RECORDED["runs"] == {"jit_small_train": 4}  # of 5
    got, want = pt["seconds"], RECORDED["seconds"]
    assert got.keys() == want.keys()
    for mod in want:
        assert got[mod].keys() == want[mod].keys()
        for ph in want[mod]:
            assert got[mod][ph] == pytest.approx(want[mod][ph], rel=1e-9)
    step = got["jit_small_train"]
    # every phase the program opens, and all three passes of the layer
    assert {"embed", "attn_proj", "attend", "ffn", "loss",
            "optimizer"} <= set(step)
    for ph in ("attn_proj", "attend", "ffn"):
        assert set(step[ph]) == {"forward", "recompute", "backward"}, ph
    assert set(step["optimizer"]) == {"optimizer"}
    total = sum(s for p in step.values() for s in p.values())
    assert sum(step.get(U, {}).values()) / total < 0.05
    # the same trace by the kernel reader: the same program time
    kt = kernel_times.reduce_file(FIXTURE)
    assert total == pytest.approx(
        sum(kt["seconds"]["jit_small_train"].values()), rel=1e-3)
    assert os.path.getsize(FIXTURE) < 100_000


def test_readers_on_the_recorded_tpu_trace(monkeypatch):
    monkeypatch.setattr(span_reduce, "newest_trace", lambda: FIXTURE)
    rec = {"kind": "train", "trace": {"window_s": 1.0}}
    got = {n: _read(n, rec) for n in ENTRIES}
    for n, v in got.items():
        if n.startswith("train_") and n != "train_ffn_ms":
            assert v is not None and v >= 0, n
        elif not n.startswith("train_"):
            assert v is None, n
    step_ms = sum(
        s for p in rec["trace"]["phase_times"]["seconds"][
            "jit_small_train"].values() for s in p.values()) / 4 * 1e3
    assert got["train_fwd_ms"] + got["train_recompute_ms"] \
        + got["train_bwd_ms"] + got["train_optimizer_ms"] \
        + got["train_unphased_share"] * step_ms == pytest.approx(step_ms)


def test_rehearsal_without_a_device_plane_leaves_the_metrics_out(tmp_path):
    """`tiny-train --trace 1` through perf/run.py in this process (the
    conftest holds jax to the CPU), the rehearsal's manifest made here
    (perf/rehearse.json is the benchmark's own file): the readers run,
    find no device plane and the line leaves all eight out."""
    with open(os.path.join(PERF, "rehearse.json")) as f:
        rehearse = json.load(f)
    mine = [n for n in ENTRIES if n.startswith("train_")]
    for m in BENCH["per_layer"]:
        if m["name"] in mine:
            rehearse["per_layer"].append(dict(
                m, workloads=["tiny-train", "tiny-train-hybrid"]))
    path = tmp_path / "rehearse-phases.json"
    path.write_text(json.dumps(rehearse))
    spec = importlib.util.spec_from_file_location(
        "perf_run_phase_times_test", os.path.join(PERF, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    out, err, old = io.StringIO(), io.StringIO(), sys.stderr
    sys.stderr = err
    try:
        rc = run.main(["--rehearse", str(path), "--workload", "tiny-train",
                       "--seed", "3400000019", "--seconds", "0.5",
                       "--trace", "1"], out=out)
    finally:
        sys.stderr = old
    assert rc == 0, err.getvalue()[-2000:]
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert not set(mine) & set(line["metrics"])
