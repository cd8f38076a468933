"""docs/probes/cb_schedule_model.py: the scheduler model PERF.md quotes
(section 6, PR 30) runs on the cell's own request stream and keeps the
engine's arithmetic where that can be said in closed form."""
import importlib.util
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def model():
    spec = importlib.util.spec_from_file_location(
        "cb_schedule_model",
        os.path.join(ROOT, "docs", "probes", "cb_schedule_model.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _traffic(prompt, output, clients=8):
    return {"warmup_s": 5, "params": {
        "arrival": {"process": "closed", "clients": clients},
        "prompt_len": {"dist": "fixed", "value": prompt},
        "output_len": {"dist": "fixed", "value": output},
        "max_total": 1 << 20}}


def test_a_decode_bound_loop_emits_slots_over_the_step(model):
    """One-chunk prompts and long outputs: every seat decodes nearly all
    the time, so tokens a second approach slots / (step + gap)."""
    got = model.simulate(_traffic(16, 2000), 1, 4, 512, 10.0, 17.0,
                         gap_decode_ms=3.0, window_s=20.0)
    assert abs(got["tokens_per_s"] - 4 / 0.020) < 0.02 * 200
    assert got["rows_per_step"] > 3.9


def test_while_prefill_waits_a_token_costs_a_chunk_and_a_step(model):
    """Prompts of many chunks for every seat: one chunk between every two
    decode steps, so a sequence's token gap is chunk + step + both gaps,
    and seats that still wait for their chunks decode nothing."""
    got = model.simulate(_traffic(512 * 80, 200, clients=16), 2, 8, 512,
                         40.0, 16.0, gap_prefill_ms=1.0, gap_decode_ms=3.0,
                         warm_s=20.0, window_s=20.0)
    assert abs(got["tpot_ms_p50"] - 60.0) < 0.5
    assert got["rows_per_step"] < 8
    assert abs(got["tokens_per_s"]
               - got["rows_per_step"] / 0.060) < 0.05 * got["tokens_per_s"]


@pytest.mark.parametrize("policy", ["slot", "fcfs", "shortest"])
def test_the_cell_is_not_steady_in_a_40_s_window_in_the_model(model, policy):
    """The finding PERF.md quotes: at the measured program times the
    latent cell's tokens a second differ between two seeds by more than
    the 3.5 % a new cell is admitted under, whatever the prefill order,
    while the token gap of a request is one cycle on both."""
    traffic = json.load(open(os.path.join(
        ROOT, "perf", "traffic", "longdoc-closed128.json")))
    runs = [model.simulate(traffic, seed, 64, 512, 48.5, 21.5,
                           policy=policy)
            for seed in (3000000083, 3000000097)]
    a, b = (r["tokens_per_s"] for r in runs)
    assert abs(a - b) / min(a, b) > 0.035
    for r in runs:
        assert abs(r["tpot_ms_p50"] - 74.0) < 0.5
        assert r["rows_per_step"] < 50


def test_spread_is_the_quartile_distance_over_the_median(model):
    assert abs(model.spread([1.0, 2.0, 3.0, 4.0, 5.0]) - 1.0) < 1e-12
