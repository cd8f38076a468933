"""Test configuration: an 8-device virtual CPU mesh, pinned BEFORE any jax
computation (SURVEY §4: the TPU analog of the reference's gloo/multi-process
CPU tests). Tests run on the CPU whatever the machine holds; the chip is
reached through chip_smoke.py, never through pytest.
"""
import os

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import time  # noqa: E402

import pytest  # noqa: E402

# tier-1 runtime guard: the driver runs the suite on six xdist workers
# (--dist loadfile) under `timeout -k 10 1470`; a run that is cut counts
# only the tests it reached. A whole run took 424 s at PR 27. Warn
# LOUDLY well before the limit so a PR adding slow tests sees it in the
# log (each worker keeps its own clock).
_DRIVER_TIMEOUT_S = 1470
_SUITE_BUDGET_WARN_S = 800
# per-test ENFORCEMENT (PR 6): any single non-`slow` test over this wall
# fails the run (exit status flipped in pytest_sessionfinish), listing
# offenders — under loadfile one hog file holds its worker while the
# others idle, and the mid-run warning above only fires after the
# damage is done.
_SINGLE_TEST_BUDGET_S = 15.0
# Tests already over the budget when the guard landed (measured on the
# PR-6 untimed full run: 15.4s-56.9s each) — grandfathered so the guard
# doesn't retroactively fail the suite, NOT endorsed: shrink or
# @pytest.mark.slow these instead of adding here. Matched by nodeid
# prefix so parametrized cases stay one entry.
_SINGLE_TEST_GRANDFATHERED = (
    "tests/test_acceptance_configs.py::test_config1_resnet_dygraph",
    "tests/test_cross_mesh_checkpoint.py::test_zero3_to_zero2_and_pipe",
    "tests/test_device_decode_loop.py::test_device_loop_eos_trims_like_host",
    "tests/test_pipeline_1f1b.py::TestOneFOneB::"
    "test_1f1b_memory_bounded_in_microbatches",
    "tests/test_ring_attention.py::test_ring_attention_grads",
    "tests/test_serving_weight_dtype.py::test_lazy_int8_matches_eager_int8",
    "tests/test_training_e2e.py::TestDygraphTraining::"
    "test_resnet18_forward_backward",
    # (The two test_multistep_decode.py entries that inherited the cb8
    # module fixture's compile bill at PR 10 — 22.2s/18.0s cold — are
    # GONE from this list: they now run on a small-geometry fixture
    # pair (2 layers, K=4, max_batch=2) that pins the same contracts
    # inside the budget; the K=8 full-geometry coverage stays on the
    # slow lane.)
    # (PR 7 moved the test_vision_models.py forward sweeps to slow;
    # PR 10 moved the 10 slowest remaining hogs — see
    # _PR10_RECLAIMED_S below. The entries still here all measured
    # UNDER the 15s budget solo and stay only as load-headroom: a
    # suite-contended run can push a 10-14s test past the boundary,
    # which is exactly the PR 8 prefix_share flake class.)
)

# The 10 slowest grandfathered tests, measured solo on this box at PR
# 10 and moved to @pytest.mark.slow — their tier-1 window seconds now
# run the new TP/handoff suites instead of re-proving long-stable
# coverage every run (the full suite still runs them on the slow lane).
_PR10_RECLAIMED_S = {
    "tests/test_elastic_resume.py::test_kill_watch_restart_resume": 107.7,
    "tests/test_namespace_tail.py::test_model_variant_factories": 70.9,
    "tests/test_flash_dropout.py::test_grad_matches_finite_difference":
        56.7,
    "tests/test_multistep_decode.py::TestFusedEquivalence::"
    "test_k8_matches_k1_on_ragged_stream": 40.2,
    "tests/test_sequence_parallel.py::test_sep2_dp2_matches_dense": 31.5,
    "tests/test_sequence_parallel.py::test_sep2_mp2_matches_dense": 31.0,
    "tests/test_sequence_parallel.py::test_sep2_matches_dense_long_seq":
        31.0,
    "tests/test_flash_dropout.py::test_mean_preserved_roughly": 23.3,
    "tests/test_fault_injection.py::TestServingFaultIsolation::"
    "test_decode_fault_retires_one_request": 18.5,
    "tests/test_spmd_trainer.py::test_parallel_configs_agree": 14.1,
}
_suite_t0 = [None]
_test_durations = []
_overbudget = []


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as paddle
    paddle.seed(2024)
    yield


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-process / long-running tests")
    config.addinivalue_line(
        "markers", "faults: fault-injection robustness tests "
        "(paddle_tpu.failsafe harness; see docs/robustness.md)")


def pytest_sessionstart(session):
    _suite_t0[0] = time.monotonic()


_budget_warned = [False]


def pytest_runtest_logreport(report):
    if report.when != "call":
        return
    _test_durations.append((report.duration, report.nodeid))
    if (report.duration > _SINGLE_TEST_BUDGET_S
            and "slow" not in report.keywords
            and not any(report.nodeid.startswith(g)
                        for g in _SINGLE_TEST_GRANDFATHERED)):
        _overbudget.append((report.duration, report.nodeid))
    # warn MID-RUN the moment the budget is crossed: when the driver's
    # `timeout` kills pytest, the terminal-summary hook below never
    # runs — an end-of-run warning cannot fire in exactly the scenario
    # it guards against
    if not _budget_warned[0] and _suite_t0[0] is not None and \
            time.monotonic() - _suite_t0[0] > _SUITE_BUDGET_WARN_S:
        _budget_warned[0] = True
        import sys
        print(f"\n!!! tier-1 guard: suite passed {_SUITE_BUDGET_WARN_S}s "
              f"at {report.nodeid} — the driver cuts the run at "
              f"{_DRIVER_TIMEOUT_S}s and counts only what it reached. "
              "Mark new long tests @pytest.mark.slow or shrink them.",
              file=sys.stderr, flush=True)


def pytest_sessionfinish(session, exitstatus):
    # fail-loud enforcement of the per-test budget: flipping
    # session.exitstatus here is what wrap_session returns to the shell,
    # so a hog that pytest itself counted as "passed" still turns the
    # run red (the offender list prints in the terminal summary below).
    if _overbudget and session.exitstatus == 0:
        session.exitstatus = 1


_LAST_WALL_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               ".tier1_last_wall.json")


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    if _suite_t0[0] is None:
        return
    total = time.monotonic() - _suite_t0[0]
    tr = terminalreporter
    tr.section("tier-1 runtime guard")
    tr.write_line(f"total wall time: {total:.1f}s "
                  f"(driver timeout {_DRIVER_TIMEOUT_S}s, warn at "
                  f"{_SUITE_BUDGET_WARN_S}s)")
    tr.write_line(
        f"PR 10 reclaimed {sum(_PR10_RECLAIMED_S.values()):.0f}s of "
        f"tier-1 wall ({len(_PR10_RECLAIMED_S)} grandfathered hogs "
        "moved to slow; solo-measured durations in conftest)")
    # delta vs the previous COMPLETED full-suite run (cacheprovider is
    # disabled in the tier-1 command, so the record lives in a sidecar
    # file; a run the driver kills never reaches this hook and leaves
    # the record untouched). The delta is what a PR review needs: did
    # THIS change add wall time that moves the run towards the limit?
    # Filtered/partial invocations (single files, -k) are
    # neither compared nor recorded — a 5s subset run must not poison
    # the baseline the guard measures against.
    import json
    full_suite = len(_test_durations) >= 200
    prev = None
    try:
        with open(_LAST_WALL_FILE) as f:
            prev = json.load(f)
    except (OSError, ValueError):
        pass
    # comparability gate: tier-1 (-m 'not slow') and the full suite both
    # clear the >=200 floor but differ by hundreds of tests — a delta
    # across selections is noise (and a negative one can mask a real
    # tier-1 regression). Compare only when the counts are within 10%;
    # the record below still refreshes, so the next same-selection run
    # compares again.
    comparable = (prev is not None
                  and isinstance(prev.get("total_wall_s"), (int, float))
                  and isinstance(prev.get("n_tests"), int)
                  and prev["n_tests"] > 0
                  and abs(len(_test_durations) - prev["n_tests"])
                  <= 0.1 * prev["n_tests"])
    if full_suite and prev and not comparable:
        tr.write_line(
            f"delta vs previous run: skipped — different selection "
            f"({prev.get('n_tests', '?')} tests then, "
            f"{len(_test_durations)} now)")
    if full_suite and comparable:
        delta = total - prev["total_wall_s"]
        tr.write_line(
            f"delta vs previous run: {delta:+.1f}s "
            f"(previous: {prev['total_wall_s']:.1f}s, "
            f"{prev.get('n_tests', '?')} tests; now {len(_test_durations)})")
        if delta > 30:
            tr.write_line(
                f"!!! this run is {delta:.0f}s slower than the previous "
                f"one (the driver cuts the run at {_DRIVER_TIMEOUT_S}s).",
                yellow=True, bold=True)
    if full_suite:
        try:
            with open(_LAST_WALL_FILE, "w") as f:
                json.dump({"total_wall_s": round(total, 1),
                           "n_tests": len(_test_durations)}, f)
        except OSError:
            pass
    for dur, nodeid in sorted(_test_durations, reverse=True)[:10]:
        tr.write_line(f"  {dur:7.2f}s  {nodeid}")
    if _overbudget:
        tr.write_line("")
        tr.write_line(
            f"!!! PER-TEST BUDGET: {len(_overbudget)} non-slow test(s) "
            f"exceeded {_SINGLE_TEST_BUDGET_S:.0f}s — the run is FAILED "
            "(exit status flipped). Mark them @pytest.mark.slow or "
            "shrink them:", red=True, bold=True)
        for dur, nodeid in sorted(_overbudget, reverse=True):
            tr.write_line(f"  {dur:7.2f}s  {nodeid}", red=True)
    if total > _SUITE_BUDGET_WARN_S:
        tr.write_line("")
        tr.write_line(
            f"!!! SUITE RUNTIME {total:.0f}s EXCEEDS THE "
            f"{_SUITE_BUDGET_WARN_S}s BUDGET — the driver cuts the run "
            f"at {_DRIVER_TIMEOUT_S}s and counts only what it reached. "
            "Mark new long tests @pytest.mark.slow or shrink them.",
            red=True, bold=True)
