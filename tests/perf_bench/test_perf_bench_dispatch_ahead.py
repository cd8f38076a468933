"""`dispatch_ahead_share` (PR 31): the reader of the engine's
`ahead.dispatched` counter over the window's steps, on made-up counter
samples, in BENCHMARK.json's tables, and in the CPU rehearsal of a
serving cell through perf/run.py. perf/rehearse.json is a file the
benchmark already had, which only a benchmark PR may touch, so the
rehearsal's manifest is made here in a temp file (as
test_span_reduce.py does): rehearse.json plus the new reader's entry in
the rehearsal's own serving cell.
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

from harness import counter_window, manifest  # noqa: E402

NAME = "dispatch_ahead_share"
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
# NOT `serve-moe-window-mixedlen`, though its engine keeps the same
# counter: test_perf_bench_hybrid_moe.py holds that cell's metric list
# equal to perf/rehearse_hybrid_moe.json's, and both are files only a
# benchmark PR may edit (PERF.md 7.8)
LISTED = ["serve-decode-saturated", "serve-mla-sparse-longdoc"]


def _read(rec):
    return manifest.load_plugin("layer_metrics", NAME).read(rec)


SAMPLES = {
    # every step but the window's first program ran ahead
    "steady": ({"steps": 100, "ahead.dispatched": 90},
               {"steps": 300, "ahead.dispatched": 289}, 199 / 200),
    # the resolve-first order: nothing is ever dispatched ahead
    "resolve_first": ({"steps": 10, "ahead.dispatched": 0},
                      {"steps": 50, "ahead.dispatched": 0}, 0.0),
    # the parent commit keeps no such counter: nothing, and no raise
    "parent": ({"steps": 10}, {"steps": 50}, None),
    # a window in which the engine did not step
    "no_steps": ({"steps": 10, "ahead.dispatched": 9},
                 {"steps": 10, "ahead.dispatched": 9}, None),
}


@pytest.mark.parametrize("case", SAMPLES)
def test_reader_on_counter_samples(monkeypatch, case):
    first, last, want = SAMPLES[case]
    import paddle_tpu.profiler as prof
    monkeypatch.setattr(prof, "counter_history",
                        lambda name: [(1.0, first), (2.0, last)])
    got = _read({"kind": "serve", "window": (1.0, 2.0)})
    assert got == want
    # a training record has no engine: nothing to read
    assert _read({"kind": "train", "window": (1.0, 2.0)}) is None
    assert counter_window.delta({"kind": "train",
                                 "window": (1.0, 2.0)}) is None


def test_manifest_entry_is_appended_and_names_its_cells():
    entry = BENCH["per_layer"][-1]
    assert entry == {
        "name": NAME, "unit": "fraction", "better": "higher",
        "source": "program_counter", "layer": "scheduler_host",
        "moves": "tpot_ms_p50", "workloads": LISTED}
    # every listed cell reports the end-to-end metric it moves
    moved = next(m for m in BENCH["end_to_end"] if m["name"] == "tpot_ms_p50")
    assert set(LISTED) <= set(moved["workloads"])
    assert os.path.isfile(os.path.join(PERF, "layer_metrics", NAME + ".py"))


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """`tiny-serve-closed --trace 1` through perf/run.py in this process
    (the conftest holds jax to the CPU): the result line."""
    with open(os.path.join(PERF, "rehearse.json")) as f:
        rehearse = json.load(f)
    rehearse["per_layer"].append(dict(
        BENCH["per_layer"][-1], workloads=["tiny-serve-closed"]))
    path = tmp_path_factory.mktemp("rehearse") / "rehearse-ahead.json"
    path.write_text(json.dumps(rehearse))
    spec = importlib.util.spec_from_file_location(
        "perf_run_ahead_test", os.path.join(PERF, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    out, err, old = io.StringIO(), io.StringIO(), sys.stderr
    sys.stderr = err
    try:
        rc = run.main(["--rehearse", str(path), "--workload",
                       "tiny-serve-closed", "--seed", "3000000311",
                       "--seconds", "0.5", "--trace", "1"], out=out)
    finally:
        sys.stderr = old
    assert rc == 0, err.getvalue()[-2000:]
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_rehearsal_runs_ahead_in_nearly_every_step(rehearsal):
    assert rehearsal["correct"] is True and rehearsal["failed"] == 0
    m = rehearsal["metrics"]
    assert m["compiles_in_window"]["value"] == 0
    assert m[NAME]["unit"] == "fraction"
    # greedy requests with a budget and nothing else: every program but
    # the ones dispatched into an empty engine runs ahead
    assert 0.9 < m[NAME]["value"] <= 1.0
