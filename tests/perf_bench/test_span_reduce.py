"""The attribution of device idle time to the engine's own host spans
(perf/harness/span_reduce.py) and its eight readers: on interval lists
made by hand, on the parent commit's kind of trace (`bench.*` spans and no
`cb.*`), on a record made by hand, on a trace recorded on the chip, and in
the CPU rehearsal (no device plane).
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
for _p in (ROOT, PERF):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from harness import manifest, span_reduce  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PARENT_TRACE = os.path.join(DATA, "small_tpu.xplane.pb")
SPAN_TRACE = os.path.join(DATA, "small_tpu_spans.xplane.pb")
HOST_READERS = ("step_gap_ms_p50", "step_host_ms_p50",
                "gap_prepare_ms_per_step", "gap_fetch_ms_per_step",
                "gap_push_ms_per_step", "gap_caller_ms_per_step")
ENTRY_READERS = ("setup_engine_build_s", "setup_first_calls_s")


def _read(name, rec):
    return manifest.load_plugin("layer_metrics", name).read(rec)


# One decode step as the engine emits it, seconds made up: the harness's
# span around the engine's, the phases inside, the harness's poll after.
ONE_STEP = [
    ("bench.step", 0.0, 10.0), ("cb.step", 1.0, 9.0),
    ("cb.admit", 1.0, 2.0), ("cb.decode.prepare", 2.0, 3.0),
    ("cb.decode_step", 3.0, 7.0), ("cb.decode.dispatch", 3.5, 4.5),
    ("cb.decode.fetch", 5.0, 7.0), ("cb.decode.push", 7.0, 8.0),
    ("bench.poll", 10.0, 12.0),
]


def test_a_moment_belongs_to_the_innermost_program_span():
    assert span_reduce.owners(ONE_STEP, 0.0, 13.0) == [
        (0.0, 1.0, "bench.step"), (1.0, 2.0, "cb.admit"),
        (2.0, 3.0, "cb.decode.prepare"), (3.0, 3.5, "cb.decode_step"),
        (3.5, 4.5, "cb.decode.dispatch"), (4.5, 5.0, "cb.decode_step"),
        (5.0, 7.0, "cb.decode.fetch"), (7.0, 8.0, "cb.decode.push"),
        (8.0, 9.0, "cb.step"), (9.0, 10.0, "bench.step"),
        (10.0, 12.0, "bench.poll"), (12.0, 13.0, "(no span)")]


def test_idle_gaps_are_cut_at_span_boundaries_and_sum_to_the_idle_time():
    busy = [[4.0, 6.0]]                 # the device ran from 4 to 6
    segments = span_reduce.owners(ONE_STEP, 0.0, 12.0)
    pieces = span_reduce.idle_pieces(busy, segments, 0.0, 12.0)
    assert pieces[:5] == [
        (0.0, 1.0, "bench.step"), (1.0, 2.0, "cb.admit"),
        (2.0, 3.0, "cb.decode.prepare"), (3.0, 3.5, "cb.decode_step"),
        (3.5, 4.0, "cb.decode.dispatch")]
    assert pieces[5] == (6.0, 7.0, "cb.decode.fetch")
    assert sum(t1 - t0 for t0, t1, _ in pieces) == 12.0 - 2.0
    assert all(t0 < t1 for t0, t1, _ in pieces)


def test_one_step_reduces_to_its_groups():
    red = span_reduce.reduce_spans(ONE_STEP, [[4.0, 6.0]], 0.0, 12.0)
    assert red["window_s"] == 12.0 and red["idle_s"] == 10.0
    assert sum(red["idle_by_span"].values()) == 10.0
    assert red["idle_by_span"]["cb.decode.dispatch"] == 0.5
    st, = red["steps"]
    assert (st["kind"], st["dur_s"], st["blocked_s"]) == ("decode", 8.0, 2.0)
    # admit 1 + prepare 1 + dispatch 0.5 + cb.step's own second (8-9);
    # decode_step's own 0.5 + fetch 1; push 1; bench.step 9-10 + poll 2
    # (the second of bench.step BEFORE the step belongs to no step)
    assert st["idle_by"] == {"prepare": 3.5, "fetch": 1.5, "push": 1.0,
                             "caller": 3.0}
    assert st["idle_s"] == 9.0


TWO_STEPS = [
    ("bench.step", 0.0, 4.0), ("cb.step", 0.0, 4.0),
    ("cb.decode_step", 0.5, 3.5),
    ("bench.step", 5.0, 9.0), ("cb.step", 5.0, 9.0),
    ("cb.prefill_chunk", 5.5, 6.5),
    ("bench.step", 9.0, 10.0), ("cb.step", 9.0, 10.0),
    ("cb.admit", 9.0, 9.5),
]


def test_a_gap_that_straddles_two_steps_is_cut_at_the_boundary():
    """The device is busy 1-3 and 6-8: the gap 3-6 starts inside the
    first step, crosses the caller's second between the steps (which
    belongs to the EARLIER step) and ends inside the second step."""
    red = span_reduce.reduce_spans(TWO_STEPS, [[1.0, 3.0], [6.0, 8.0]],
                                   0.0, 10.0)
    first, second, third = red["steps"]
    assert [st["kind"] for st in red["steps"]] == ["decode", "prefill",
                                                   None]
    # 0-0.5 cb.step, 0.5-1 decode_step | 3-3.5 decode_step, 3.5-4 cb.step,
    # 4-5 nobody's
    assert first["idle_by"] == {"prepare": 1.0, "fetch": 1.0, "push": 0.0,
                                "caller": 1.0}
    # 5-5.5 cb.step, 5.5-6 prefill_chunk | 8-9 cb.step
    assert second["idle_by"] == {"prepare": 2.0, "fetch": 0.0,
                                 "push": 0.0, "caller": 0.0}
    assert third["idle_s"] == 1.0
    assert sum(st["idle_s"] for st in red["steps"]) == red["idle_s"] == 6.0
    assert red["idle_by_span"] == {"cb.step": 3.0, "cb.decode_step": 1.0,
                                   "(no span)": 1.0, "cb.admit": 0.5,
                                   "cb.prefill_chunk": 0.5}
    # a step that ran no program is in no per-step number
    assert span_reduce.classed(red) == [first, second]
    assert span_reduce.classed(red, "decode") == [first]


def test_spans_without_a_step_reduce_to_nothing():
    bench = [sp for sp in ONE_STEP if sp[0].startswith("bench.")]
    assert span_reduce.reduce_spans(bench, [[4.0, 6.0]], 0.0, 12.0) is None


# ----------------------------------------------------------- the readers --
def _made_up_record():
    def step(kind, dur, blocked, **idle_by):
        by = dict.fromkeys(span_reduce.GROUPS, 0.0)
        by.update(idle_by)
        return {"kind": kind, "dur_s": dur, "blocked_s": blocked,
                "idle_s": sum(by.values()), "idle_by": by}
    steps = [step("decode", 0.060, 0.056, prepare=0.002, fetch=0.001),
             step("decode", 0.062, 0.057, prepare=0.003, push=0.001),
             step("prefill", 0.010, 0.0, prepare=0.004, caller=0.002),
             step(None, 0.001, 0.0, caller=0.5),
             step("decode", 0.064, 0.061, prepare=0.001, fetch=0.001)]
    return {"kind": "serve", "trace": {"window_s": 1.0, "host_spans": {
        "window_s": 1.0, "idle_s": 0.515, "idle_by_span": {},
        "steps": steps}}}


@pytest.mark.parametrize("name,value", [
    ("step_gap_ms_p50", 3.0),           # decode steps idle 3, 4, 2 ms
    ("step_host_ms_p50", 4.0),          # 60-56, 62-57, 64-61
    ("gap_prepare_ms_per_step", 2.5),   # (2 + 3 + 4 + 1) / 4 classed steps
    ("gap_fetch_ms_per_step", 0.5),
    ("gap_push_ms_per_step", 0.25),
    ("gap_caller_ms_per_step", 0.5),    # the unclassed step's is left out
])
def test_host_reader_on_a_made_up_record(name, value):
    assert _read(name, _made_up_record()) == pytest.approx(value)


def test_the_four_gaps_sum_to_the_mean_idle_time_of_a_step():
    rec = _made_up_record()
    steps = span_reduce.classed(rec["trace"]["host_spans"])
    mean_ms = sum(st["idle_s"] for st in steps) / len(steps) * 1e3
    assert sum(_read(f"gap_{g}_ms_per_step", rec)
               for g in span_reduce.GROUPS) == pytest.approx(mean_ms)


@pytest.mark.parametrize("name", ENTRY_READERS)
def test_entry_reader_reads_the_programs_totals(name, monkeypatch):
    from paddle_tpu import profiler
    monkeypatch.setattr(profiler, "_TOTALS", {
        "setup.engine": [1, 7.5], "setup.first_call": [7, 70.0]})
    want = {"setup_engine_build_s": 7.5, "setup_first_calls_s": 70.0}
    assert _read(name, {"kind": "serve"}) == want[name]
    monkeypatch.setattr(profiler, "_TOTALS", {})
    assert _read(name, {"kind": "serve"}) is None


@pytest.mark.parametrize("name", HOST_READERS + ENTRY_READERS)
def test_on_the_parents_trace_every_reader_returns_nothing(
        name, monkeypatch):
    """The parent commit: `bench.*` spans and a device plane, no `cb.*`
    span, and a profiler module without span_totals()."""
    from paddle_tpu import profiler
    monkeypatch.delattr(profiler, "span_totals")
    monkeypatch.setattr(span_reduce, "newest_trace", lambda: PARENT_TRACE)
    rec = {"kind": "serve", "trace": {"window_s": 0.013}}
    assert _read(name, rec) is None
    if name in HOST_READERS:
        assert rec["trace"]["host_spans"] is None   # reduced once, kept


@pytest.mark.parametrize("name", HOST_READERS)
def test_host_reader_without_a_trace_returns_nothing(name, monkeypatch):
    monkeypatch.setattr(span_reduce, "newest_trace", lambda: None)
    assert _read(name, {"kind": "serve", "trace": None}) is None
    assert _read(name, {"kind": "serve", "trace": {"window_s": 1.0}}) is None


def test_the_newest_trace_under_the_checkout_is_the_one_read(
        tmp_path, monkeypatch):
    monkeypatch.setattr(manifest, "ROOT", str(tmp_path))
    assert span_reduce.newest_trace() is None
    for cell, stamp, mtime in (("a", "t1", 100), ("b", "t2", 200)):
        d = tmp_path / ".perf_trace" / cell / "plugins" / "profile" / stamp
        d.mkdir(parents=True)
        (d / "vm.xplane.pb").write_bytes(b"")
        os.utime(d / "vm.xplane.pb", (mtime, mtime))
    assert span_reduce.newest_trace().endswith(
        os.path.join("b", "plugins", "profile", "t2", "vm.xplane.pb"))


# ------------------------------------------------ in the CPU rehearsal --
@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """`tiny-serve-closed --trace 1` through perf/run.py in this process
    (the conftest holds jax to the CPU): (the result line, the stderr
    log). perf/rehearse.json is a file the benchmark already had, which
    only a benchmark PR may touch, so the rehearsal's manifest is made
    here: rehearse.json plus BENCHMARK.json's entries of the new readers,
    in the rehearsal's own serving cells."""
    with open(os.path.join(PERF, "rehearse.json")) as f:
        rehearse = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = ["tiny-serve-closed", "tiny-serve-open"]
    rehearse["per_layer"] += [
        dict(m, workloads=cells,
             moves="setup_s" if m["moves"] == "setup_s" else "tpot_ms_p50")
        for m in bench["per_layer"]
        if m["name"] in HOST_READERS + ENTRY_READERS]
    path = tmp_path_factory.mktemp("rehearse") / "rehearse-spans.json"
    path.write_text(json.dumps(rehearse))
    spec = importlib.util.spec_from_file_location(
        "perf_run_span_test", os.path.join(PERF, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    out, err, old = io.StringIO(), io.StringIO(), sys.stderr
    sys.stderr = err
    try:
        rc = run.main(["--rehearse", str(path), "--workload",
                       "tiny-serve-closed", "--seed", "3", "--seconds",
                       "0.5", "--trace", "1"], out=out)
    finally:
        sys.stderr = old
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1]), \
        err.getvalue()


def test_rehearsal_reports_set_up_phases_and_no_device_gaps(rehearsal):
    line, log = rehearsal
    got = line["metrics"]
    for name in ENTRY_READERS:
        assert got[name]["unit"] == "s" and got[name]["value"] > 0
    assert not set(HOST_READERS) & set(got)     # no device plane on a CPU
    assert "[perf] setup.first_call: " in log   # the count, to stderr


# ------------------------------------------- a trace recorded on the chip --
# tests/perf_bench/record_span_fixture.py on one TPU v5e (PR 24): five
# made-up steps (decode, decode, a prompt's last chunk, nothing to do,
# decode) around a 1.133 ms program, with sleeps of 2 ms in *.prepare, 1 ms
# in cb.decode.push (as recorded: 1.6-2.0 ms) and 1.5 ms in bench.poll (as
# recorded: 2.0-2.2 ms). Numbers below are read from the recorder's print
# of the spans and of the device's busy intervals.
@pytest.fixture(scope="module")
def chip_reduction():
    return span_reduce.reduce_file(SPAN_TRACE)


def test_recorded_spans_reduce_to_the_numbers_read_by_hand(chip_reduction):
    red = chip_reduction
    assert [st["kind"] for st in red["steps"]] == [
        "decode", "decode", "prefill", None, "decode"]
    assert red["window_s"] == pytest.approx(0.035138811, abs=1e-9)
    # four runs of the program, 1.133 ms each, and nothing else
    assert red["window_s"] - red["idle_s"] == pytest.approx(
        4 * 0.001133, abs=2e-6)
    by = red["idle_by_span"]
    assert sum(by.values()) == pytest.approx(red["idle_s"], abs=1e-12)
    # the device sat idle all through the five polls and the three pushes:
    # their idle time is the spans' own length
    assert by["bench.poll"] == pytest.approx(
        (2.11609 + 2.180469 + 2.04402 + 2.164731 + 2.153951) * 1e-3,
        abs=1e-8)
    assert by["cb.decode.push"] == pytest.approx(
        (1.62918 + 1.92114 + 2.01966) * 1e-3, abs=1e-8)
    first = red["steps"][0]
    assert first["dur_s"] == pytest.approx(6.71752e-3, abs=1e-9)
    assert first["blocked_s"] == pytest.approx(2.30907e-3, abs=1e-9)
    assert first["idle_by"]["push"] == pytest.approx(1.62918e-3, abs=1e-9)
    # the step that ran nothing: cb.step's own 7.5 us, then the poll
    nothing = red["steps"][3]
    assert nothing["idle_by"]["prepare"] == pytest.approx(7.511e-6, abs=1e-9)
    assert nothing["idle_by"]["caller"] == pytest.approx(2.181769e-3,
                                                         abs=1e-9)
    in_steps = sum(st["idle_s"] for st in red["steps"])
    assert 0 <= red["idle_s"] - in_steps < 30e-6    # before the first step


def test_recorded_readers_and_what_the_clocks_allow(chip_reduction):
    """The readers on the recording, and a limit of the method that the
    recording shows: on the device's clock each program STARTS 0.69-0.83
    ms before the host's clock enters the span that dispatches it, which
    cannot be. The profiler aligns the two clocks only that well, so time
    moves between NEIGHBOURING spans by that much (here from dispatch into
    prepare, and out of fetch); sums over a step do not care."""
    rec = {"kind": "serve", "trace": {"host_spans": chip_reduction}}
    gaps = {g: _read(f"gap_{g}_ms_per_step", rec)
            for g in span_reduce.GROUPS}
    decode = span_reduce.classed(chip_reduction, "decode")
    assert _read("step_gap_ms_p50", rec) == pytest.approx(
        sorted(st["idle_s"] for st in decode)[1] * 1e3)
    assert _read("step_gap_ms_p50", rec) == pytest.approx(7.637406,
                                                          abs=1e-6)
    assert _read("step_host_ms_p50", rec) == pytest.approx(
        6.536701 - 2.119951, abs=1e-5)      # the second step's
    assert sum(gaps.values()) == pytest.approx(
        (7.76882 + 7.637406 + 5.408845 + 7.582428) / 4, abs=1e-5)
    # were the clocks one, `prepare` would hold the three 2 ms sleeps and
    # more (about 2.3 ms a step) and `fetch` next to nothing; the device's
    # clock being early moves 0.7-0.8 ms of each step's idle time from
    # prepare to fetch
    assert gaps["prepare"] == pytest.approx(1.476661, abs=1e-5)
    assert gaps["fetch"] == pytest.approx(2.057606, abs=1e-5)
    from harness import trace_reduce
    devices, _ = trace_reduce.read_planes(SPAN_TRACE)
    programs = sorted(s for _, s, _ in devices[0]["XLA Modules"])
    dispatch = sorted(s for n, s, _ in span_reduce.read_host_spans(
        SPAN_TRACE) if n in ("cb.decode.dispatch", "cb.prefill_chunk"))
    early = [d - p for d, p in zip(dispatch, programs)]
    assert len(early) == 4 and all(0.00068 < x < 0.00084 for x in early)
