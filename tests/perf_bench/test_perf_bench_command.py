"""The benchmark's command end to end at tiny size on the CPU: every
generator and both system runners through `perf/run.py --rehearse`, the
last line held to the contract; the refusals (no accelerator, no program
beside the benchmark); and a cell, a configuration, a traffic mix and a
per-layer metric added as NEW FILES plus appended manifest entries, with
no existing file edited.
"""
import importlib.util
import io
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
REHEARSE = json.load(open(os.path.join(ROOT, "perf", "rehearse.json")))


def _load_run(root=ROOT):
    spec = importlib.util.spec_from_file_location(
        "perf_run_under_test", os.path.join(root, "perf", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(workload, trace, extra=()):
    """perf/run.py in THIS process (the conftest already holds jax to the
    CPU with eight devices); returns (everything printed, parsed line)."""
    out = io.StringIO()
    rc = _load_run().main(
        ["--rehearse", *extra, "--workload", workload, "--seed", "3",
         "--seconds", "0.5", "--trace", str(trace)], out=out)
    assert rc == 0
    lines = out.getvalue().strip().splitlines()
    return lines, json.loads(lines[-1])


# one run per cell: the closed loop and the one-chip trainer untraced, the
# open loop and the four-device mesh traced
CASES = {"tiny-train": 0, "tiny-train-hybrid": 1,
         "tiny-serve-closed": 0, "tiny-serve-open": 1}


@pytest.fixture(scope="module")
def results():
    return {name: _run(name, trace) for name, trace in CASES.items()}


@pytest.mark.parametrize("name", CASES)
def test_last_line_is_the_contract_and_nothing_else_is_printed(
        results, name):
    lines, line = results[name]
    assert len(lines) == 1                      # logs go to stderr
    want = {"correct", "attempted", "failed", "metrics", "device"}
    assert want <= set(line) <= want | {"breakdown"}
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    dev = line["device"]
    assert dev["platform"] == "cpu" and dev["count"] >= 1
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    for value in line["metrics"].values():
        assert set(value) == {"value", "unit"}
        assert isinstance(value["value"], (int, float))


@pytest.mark.parametrize("name", CASES)
def test_metrics_are_the_cells_own_for_the_mode(results, name):
    _, line = results[name]
    cell = next(w for w in REHEARSE["workloads"] if w["name"] == name)
    kind = "per_layer" if CASES[name] else "end_to_end"
    allowed = {m["name"] for m in REHEARSE[kind]
               if name in m.get("workloads", [name])}
    got = set(line["metrics"])
    assert got and got <= allowed, (cell, got - allowed)
    if CASES[name] == 0:
        assert got == allowed                   # every end-to-end metric
        assert all(v["value"] > 0 for v in line["metrics"].values())
    else:
        # the CPU has no device plane: trace-only readers return nothing,
        # counters and host clocks are there
        assert {"compile_s", "trace_lower_s", "cache_misses",
                "compiles_in_window"} <= got
        assert line["metrics"]["compiles_in_window"]["value"] == 0


def test_training_cells_count_whole_steps(results):
    for name in ("tiny-train", "tiny-train-hybrid"):
        _, line = results[name]
        assert line["attempted"] >= 3
    _, hybrid = results["tiny-train-hybrid"]
    assert hybrid["device"]["count"] >= 4


def test_open_loop_reports_the_generator_and_admission(results):
    _, line = results["tiny-serve-open"]
    got = set(line["metrics"])
    assert {"gen_late_ms_p99", "queue_wait_ms_p50", "ttft_ms_p90"} <= got
    # read from the device trace, which a CPU run does not have
    assert not {"prefill_step_ms_p50", "prefill_step_share",
                "decode_step_ms_p50", "decode_stream_share"} & got
    assert line["metrics"]["gen_late_ms_p99"]["value"] >= 0


def test_no_accelerator_is_an_error_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "perf/run.py", "--workload", "train-dense-1chip",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def _copy_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "perf"), tmp_path / "perf",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


def test_alone_in_a_directory_it_fails_and_prints_no_result(tmp_path):
    root = _copy_benchmark(tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "perf/run.py", "--workload", "train-dense-1chip",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "paddle_tpu" in p.stderr


def test_a_cell_is_added_as_new_files_and_appended_entries(tmp_path):
    """What a later PR does: a configuration, a traffic mix, a per-layer
    metric and a cell arrive as files that were not there, plus entries
    APPENDED to the manifest's lists. Nothing that exists is edited."""
    root = _copy_benchmark(tmp_path)
    before = {}
    for d, _, files in os.walk(root):
        for f in files:
            path = os.path.join(d, f)
            before[path] = open(path, "rb").read()

    cfg = json.load(open(root / "perf" / "configs" / "tiny-train.json"))
    cfg["num_hidden_layers"] = 1
    json.dump(cfg, open(root / "perf" / "configs" / "dummy-model.json", "w"))
    json.dump({"generator": "token_batches", "why": "dummy",
               "loss_rise_tol": 0.5, "loss_tol": 0.01, "params": {"batch": 2, "seq": 32}},
              open(root / "perf" / "traffic" / "dummy-mix.json", "w"))
    (root / "perf" / "layer_metrics" / "dummy_steps_per_s.py").write_text(
        '"""Steps a second: a reader a later PR brings with it."""\n\n\n'
        "def read(rec):\n"
        "    t0, t1 = rec['window']\n"
        "    return rec['n_steps'] / (t1 - t0)\n")
    (root / "perf" / "layer_metrics" / "dummy_nothing.py").write_text(
        "def read(rec):\n    return None\n")
    manifest = json.load(open(root / "BENCHMARK.json"))
    manifest["configs"].append(
        {"name": "dummy-model", "source": "none",
         "file": "perf/configs/dummy-model.json", "reduced": [], "why": "-"})
    manifest["workloads"].append(
        {"name": "dummy-cell", "config": "dummy-model",
         "traffic": "dummy-mix", "chips": 1, "why": "-"})
    for m in manifest["end_to_end"]:
        if m["name"] == "train_tokens_per_s_per_chip":
            m["workloads"].append("dummy-cell")
    for name in ("dummy_steps_per_s", "dummy_nothing"):
        manifest["per_layer"].append(
            {"name": name, "unit": "1/s", "better": "higher",
             "source": "host_clock", "layer": "training_step",
             "moves": "train_tokens_per_s_per_chip",
             "workloads": ["dummy-cell"]})
    json.dump(manifest, open(root / "BENCHMARK.json", "w"))

    out = io.StringIO()
    old_path = list(sys.path)
    for m in [m for m in sys.modules if m == "harness"
              or m.startswith("harness.")]:
        del sys.modules[m]          # the copy's harness, not the repo's
    try:
        sys.path.insert(0, str(root / "perf"))
        run = _load_run(str(root))
        assert run.ROOT == str(root)
        rc = run.main(["--rehearse", "BENCHMARK.json", "--workload",
                       "dummy-cell", "--seed", "1", "--seconds", "0.5",
                       "--trace", "1"], out=out)
    finally:
        sys.path[:] = old_path
        for m in [m for m in sys.modules if m == "harness"
                  or m.startswith("harness.")]:
            del sys.modules[m]
    assert rc == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] and line["attempted"] >= 1
    assert line["metrics"]["dummy_steps_per_s"]["value"] > 0
    assert "dummy_nothing" not in line["metrics"]   # nothing read: left out
    assert "mfu" not in line["metrics"]             # needs a chip's peak
    for path, content in before.items():
        if not path.endswith("BENCHMARK.json"):
            assert open(path, "rb").read() == content, path
