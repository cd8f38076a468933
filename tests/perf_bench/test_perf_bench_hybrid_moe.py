"""The configuration `mimo-v2.5-ep16-serve` held to a hand-written table
of the catalog row's widths, `flops_hybrid_moe.py`'s counts against hand
sums, and the cell `serve-moe-window-mixedlen` rehearsed on the CPU
through its own manifest (`perf/rehearse_hybrid_moe.json`, configuration
`tiny-mimo-serve`) with the same runner, generator and readers.
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

from harness import flops_hybrid_moe as fl  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CFG = json.load(open(os.path.join(
    PERF, "configs", "mimo-v2.5-ep16-serve.json")))
CELL = "serve-moe-window-mixedlen"
REHEARSE = "perf/rehearse_hybrid_moe.json"

# the catalog row MiMo-V2.5, written by hand: every width, never cut
WIDTHS = {
    "hidden_size": 4096, "intermediate_size": 16384,
    "moe_intermediate_size": 2048, "num_attention_heads": 64,
    "num_key_value_heads": 4, "swa_num_key_value_heads": 8,
    "swa_num_attention_heads": 64, "head_dim": 192, "swa_head_dim": 192,
    "v_head_dim": 128, "swa_v_head_dim": 128, "sliding_window": 128,
    "sliding_window_size": 128, "attention_chunk_size": 128,
    "num_experts_per_tok": 8, "n_group": 1, "topk_group": 1,
    "partial_rotary_factor": 0.334, "attention_value_scale": 0.707,
    "rope_theta": 10000000, "swa_rope_theta": 10000,
    "layernorm_epsilon": 1e-05, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "n_shared_experts": None, "routed_scaling_factor": None,
    "add_full_attention_sink_bias": False,
    "add_swa_attention_sink_bias": True, "attention_bias": False,
    "attention_projection_layout": "fused_qkv", "hidden_act": "silu",
    "tie_word_embeddings": False, "model_type": "mimo_v2"}
CUT = {"num_hidden_layers": (48, 7), "n_routed_experts": (256, 16),
       "vocab_size": (152576, 19072),
       "max_position_embeddings": (1048576, 4096)}


@pytest.mark.parametrize("key", sorted(WIDTHS))
def test_no_width_differs_from_the_catalog_row(key):
    assert CFG[key] == WIDTHS[key]


@pytest.mark.parametrize("key", sorted(CUT))
def test_each_cut_is_declared_with_the_published_value_beside_it(key):
    published, here = CUT[key]
    assert CFG[key] == here and CFG["published"][key] == published
    assert key in CFG["reduced"] and key in CFG["changed"]
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "mimo-v2.5-ep16-serve")
    assert sorted(entry["reduced"]) == sorted(CUT)
    assert entry["source"] == CFG["source"]


def test_the_patterns_are_the_published_ones_and_a_whole_period_is_kept():
    pattern = [0, 1, 1, 1, 1, 0] + [1, 1, 1, 1, 1, 0] * 7
    assert CFG["hybrid_layer_pattern"] == pattern
    assert CFG["moe_layer_freq"] == [0] + [1] * 47
    kept = CFG["layers_kept"]
    assert kept == [0, 6, 7, 8, 9, 10, 11] and len(kept) == 7
    assert [pattern[l] for l in kept] == [0, 1, 1, 1, 1, 1, 0]
    assert fl.layers(CFG) == [(False, False)] + [(True, True)] * 5 \
        + [(False, True)]
    assert CFG["held_experts"] == [0, 16]
    assert "16 chips share each layer" in CFG["deployment"]
    assert CFG["serving"]["engine"] == {
        "max_len": 4096, "page_size": 128, "max_batch": 128,
        "weight_dtype": "bfloat16", "prefill_chunk": 512,
        "prefix_cache": False}


def test_the_cell_and_its_traffic_are_the_issues():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mimo-v2.5-ep16-serve", "mixedlen-closed256", 1)
    mix = json.load(open(os.path.join(PERF, "traffic",
                                      "mixedlen-closed256.json")))
    assert mix["generator"] == "requests" and mix["warmup_s"] == 15
    assert mix["params"] == {
        "arrival": {"process": "closed", "clients": 256},
        "prompt_len": {"dist": "lognormal", "median": 320, "sigma": 1.2,
                       "min": 32, "max": 3072},
        "output_len": {"dist": "lognormal", "median": 384, "sigma": 0.6,
                       "min": 64, "max": 1024},
        "max_total": 4096, "stagger_first": True, "stratify": 16}
    ours = {m["name"] for m in BENCH["per_layer"]
            if m.get("workloads") == [CELL]}
    assert ours == {
        "hybrid_decode_stream_share", "expert_mm_roofline",
        "paged_attn_roofline", "expert_rows_per_step_mean",
        "expert_load_max_over_mean", "pages_used_share_full_mean",
        "pages_used_share_window_mean"}
    stream = next(m for m in BENCH["per_layer"]
                  if m["name"] == "decode_stream_share")
    assert CELL not in stream["workloads"]      # the dense int8 count


# ---- flops_hybrid_moe.py against hand sums (the issue's table) ----------
FULL_ATTN = 4096 * (12288 + 768 + 512) + 8192 * 4096        # 89.1 M
WIN_ATTN = 4096 * (12288 + 1536 + 1024) + 8192 * 4096       # 94.4 M
DENSE = 3 * 4096 * 16384                                    # 201.3 M
EXPERT = 3 * 4096 * 2048                                    # 25.2 M
ROUTER = 4096 * 256
HEAD = 4096 * 19072


@pytest.mark.parametrize("got,want", [
    (lambda: fl.attention_params(CFG, False), FULL_ATTN),
    (lambda: fl.attention_params(CFG, True), WIN_ATTN),
    (lambda: fl.dense_ffn_params(CFG), DENSE),
    (lambda: fl.expert_params(CFG), EXPERT),
    (lambda: fl.router_params(CFG), ROUTER),
    (lambda: fl.head_params(CFG), HEAD),
    (lambda: fl.resident_params(CFG),
     2 * FULL_ATTN + 5 * WIN_ATTN + DENSE + 6 * (16 * EXPERT + ROUTER)
     + 2 * HEAD),
    (lambda: fl.kv_bytes_per_token(CFG, False), 4 * 320 * 2),
    (lambda: fl.kv_bytes_per_token(CFG, True), 8 * 320 * 2),
    # one sequence of 1000 tokens: 2 full layers read all, 5 window
    # layers 128 of them
    (lambda: fl.kv_read_bytes(CFG, [1000]),
     2 * 1000 * 2560 + 5 * 128 * 5120),
    (lambda: fl.kv_read_bytes(CFG, [50, 50]),
     2 * 100 * 2560 + 5 * 100 * 5120),
    # 15 experts touched by 60 rows in one layer
    (lambda: fl.expert_mm_bytes(CFG, 15, 60),
     15 * EXPERT * 2 + 60 * (4096 + 4096 + 2048 + 4096) * 2),
    (lambda: fl.decode_weight_bytes(CFG, 16),
     2 * (2 * FULL_ATTN + 5 * WIN_ATTN + DENSE + 6 * 16 * EXPERT + HEAD)
     + 4 * 6 * ROUTER),
])
def test_counts_against_hand_sums(got, want):
    assert got() == want


def test_the_arithmetic_of_the_cut():
    """3.43 B parameters, 6.86 GB of bf16; a decode step that touches
    every held expert streams 6.7 GB."""
    assert abs(fl.resident_params(CFG) / 1e9 - 3.43) < 0.01
    assert abs(fl.decode_weight_bytes(CFG, 16) / 1e9 - 6.72) < 0.02
    full = 2 * 2560 * 128 * 4096
    assert abs(full / 1e9 - 2.68) < 0.01


# ---- the cell rehearsed on the CPU ------------------------------------
def _run(trace):
    spec = importlib.util.spec_from_file_location(
        "perf_run_hybrid_moe", os.path.join(PERF, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = io.StringIO()
    rc = mod.main(["--rehearse", REHEARSE, "--workload",
                   "tiny-mimo-serve-closed", "--seed", "2500000011",
                   "--seconds", "0.5", "--trace", str(trace)], out=out)
    assert rc == 0
    lines = out.getvalue().strip().splitlines()
    assert len(lines) == 1
    return json.loads(lines[0])


@pytest.fixture(scope="module")
def traced():
    return _run(1)


def test_rehearsal_is_correct_and_reports_the_program_counters(traced):
    assert traced["correct"] is True and traced["failed"] == 0
    assert traced["attempted"] > 0
    m = traced["metrics"]
    # a CPU rehearsal has no device plane: the trace-read metrics stay
    # silent, the program's counters speak
    for name in ("expert_rows_per_step_mean", "expert_load_max_over_mean",
                 "pages_used_share_full_mean",
                 "pages_used_share_window_mean", "compiles_in_window",
                 "batch_occupancy_mean", "pages_used_share_mean"):
        assert name in m, name
    assert m["compiles_in_window"]["value"] == 0
    # 4 slots x top 2 of 8 experts, 4 held: at most one row an expert
    assert 0.3 < m["expert_rows_per_step_mean"]["value"] <= 1.0
    assert m["expert_load_max_over_mean"]["value"] >= 1.0
    assert 0.0 < m["pages_used_share_window_mean"]["value"] <= 1.0
    assert 0.0 < m["pages_used_share_full_mean"]["value"] <= 1.0
    for name in ("expert_mm_roofline", "paged_attn_roofline",
                 "hybrid_decode_stream_share"):
        assert name not in m


def test_rehearsal_manifest_gives_the_cell_its_metric_tables():
    man = json.load(open(os.path.join(ROOT, REHEARSE)))
    tiny = "tiny-mimo-serve-closed"
    for kind in ("end_to_end", "per_layer"):
        want = {m["name"] for m in BENCH[kind]
                if CELL in m.get("workloads", [CELL])}
        got = {m["name"] for m in man[kind]
               if tiny in m.get("workloads", [tiny])}
        assert got == want, (kind, got ^ want)


@pytest.fixture(scope="module")
def untraced():
    return _run(0)


def test_untraced_rehearsal_reports_the_end_to_end_metrics(untraced):
    assert untraced["correct"] is True
    assert set(untraced["metrics"]) == {"setup_s", "serve_out_tokens_per_s",
                                    "tpot_ms_p50"}
