"""`flash_attn_device_share` (PR 33): the reader of the flash-attention
kernels' device time in a training cell's traced window, on made-up
kernel-time tables, in BENCHMARK.json's tables, and in the CPU rehearsal
of a training cell through perf/run.py. perf/rehearse.json is a file the
benchmark already had, which only a benchmark PR may touch, so the
rehearsal's manifest is made here in a temp file (as
test_perf_bench_dispatch_ahead.py does): rehearse.json plus the new
reader's entry in the rehearsal's own training cells.
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

from harness import manifest  # noqa: E402

NAME = "flash_attn_device_share"
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
LISTED = ["train-dense-1chip", "train-hybrid-4chip"]


def _read(rec):
    return manifest.load_plugin("layer_metrics", NAME).read(rec)


def _rec(seconds, kind="train"):
    """A record as perf/run.py hands it to a reader after a traced run,
    the kernel-time table already made (kernel_times.of keeps it)."""
    return {"kind": kind, "trace": {
        "kernel_times": None if seconds is None else {
            "runs": {m: 3 for m in seconds}, "seconds": seconds}}}


TABLES = {
    # both kernels under the trainer's executable, beside other families:
    # their share of all the self time counted there
    "named": ({"jit_step_fn": {"flash_attention_fwd": 0.4,
                               "flash_attention_bwd": 0.3,
                               "fusion.kOutput": 1.0, "reduce": 0.02}},
              0.7 / 1.72),
    # whatever the executables are called, the names decide
    "two_modules": ({"jit_step_fn": {"flash_attention_fwd": 0.4},
                     "jit_other": {"flash_attention_bwd": 0.2,
                                   "rms_norm": 0.1}}, 0.6 / 0.7),
    # the parent commit: the kernels run unnamed (`closed_call`, ...)
    "parent": ({"jit_step_fn": {"closed_call": 0.3, "checkpoint": 0.4,
                                "rematted_computation": 0.4}}, None),
    # a trace without a device plane or a window
    "no_table": (None, None),
}


@pytest.mark.parametrize("case", TABLES)
def test_reader_on_kernel_time_tables(case):
    seconds, want = TABLES[case]
    got = _read(_rec(seconds))
    assert got == (want if want is None else pytest.approx(want))
    # a serving record and an untraced run have nothing to read
    assert _read(_rec(seconds, kind="serve")) is None
    assert _read({"kind": "train", "trace": None}) is None


def test_manifest_entry_is_appended_and_names_its_cells():
    entry = next(m for m in BENCH["per_layer"] if m["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": "fraction", "better": "lower",
        "source": "device_trace", "layer": "training_step",
        "moves": "train_tokens_per_s_per_chip", "workloads": LISTED}
    # after everything the benchmark had, and in the cells that report
    # the end-to-end metric it moves
    assert BENCH["per_layer"].index(entry) > next(
        i for i, m in enumerate(BENCH["per_layer"])
        if m["name"] == "sparse_kv_decode_rows_per_step_mean")
    moved = next(m for m in BENCH["end_to_end"]
                 if m["name"] == entry["moves"])
    assert LISTED == moved["workloads"]
    assert os.path.isfile(os.path.join(PERF, "layer_metrics", NAME + ".py"))


def test_rehearsal_without_a_device_plane_leaves_the_metric_out(
        tmp_path):
    """`tiny-train --trace 1` through perf/run.py in this process (the
    conftest holds jax to the CPU): the reader runs, finds no device
    plane, returns nothing and the line leaves the metric out, which is
    what the parent commit's side of a traced run does too."""
    with open(os.path.join(PERF, "rehearse.json")) as f:
        rehearse = json.load(f)
    entry = next(m for m in BENCH["per_layer"] if m["name"] == NAME)
    rehearse["per_layer"].append(dict(
        entry, workloads=["tiny-train", "tiny-train-hybrid"]))
    path = tmp_path / "rehearse-flash.json"
    path.write_text(json.dumps(rehearse))
    spec = importlib.util.spec_from_file_location(
        "perf_run_flash_share_test", os.path.join(PERF, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    out, err, old = io.StringIO(), io.StringIO(), sys.stderr
    sys.stderr = err
    try:
        rc = run.main(["--rehearse", str(path), "--workload", "tiny-train",
                       "--seed", "3300000019", "--seconds", "0.5",
                       "--trace", "1"], out=out)
    finally:
        sys.stderr = old
    assert rc == 0, err.getvalue()[-2000:]
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert NAME not in line["metrics"]
    assert line["metrics"]["compiles_in_window"]["value"] == 0
