"""The configuration `keye-vl-2.0-30b-a3b-l5-serve` held to a hand-written
table of the catalog row, `flops_sparse_gqa.py`'s counts against hand sums,
this PR's readers on records made by hand, and the cell
`serve-sparse-gqa-longctx` rehearsed on the CPU through its own manifest
(`perf/rehearse_sparse_gqa.json`, configuration `tiny-keye-serve`) with the
same runner, generator and readers.
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
from harness import flops_sparse_gqa as fl  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = "keye-vl-2.0-30b-a3b-l5-serve"
CFG = json.load(open(os.path.join(PERF, "configs", NAME + ".json")))
CELL = "serve-sparse-gqa-longctx"
REHEARSE = "perf/rehearse_sparse_gqa.json"
TINY = "tiny-keye-serve-closed"

# the catalog row Keye-VL-2.0-30B-A3B, written by hand: every key that is
# not cut, widths first
ROW = {
    "hidden_size": 2048, "intermediate_size": 6144,
    "moe_intermediate_size": 768, "num_attention_heads": 32,
    "num_key_value_heads": 4, "head_dim": 128, "num_experts": 128,
    "num_local_experts": 128, "num_experts_per_tok": 8,
    "vocab_size": 151936, "norm_topk_prob": True,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "rope_scaling": {"mrope_section": [16, 24, 24],
                     "rope_type": "default", "type": "default"},
    "rope_theta": 10000000, "rms_norm_eps": 1e-06,
    "decoder_sparse_step": 1, "mlp_only_layers": [],
    "max_window_layers": 48, "sliding_window": None,
    "use_sliding_window": False, "attention_bias": False,
    "hidden_act": "silu", "tie_word_embeddings": False,
    "model_type": "KeyeVL2"}
CUT = {"num_hidden_layers": (48, 5),
       "max_position_embeddings": (262144, 16384)}


@pytest.mark.parametrize("key", sorted(ROW))
def test_no_key_differs_from_the_catalog_row(key):
    assert CFG[key] == ROW[key]


@pytest.mark.parametrize("key", sorted(CUT))
def test_each_cut_is_declared_with_the_published_value_beside_it(key):
    published, here = CUT[key]
    assert CFG[key] == here and CFG["published"][key] == published
    assert key in CFG["reduced"] and key in CFG["changed"]
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert sorted(entry["reduced"]) == sorted(CUT) == sorted(CFG["reduced"])
    assert entry["source"] == CFG["source"] == (
        "https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/"
        "config.json")
    assert entry["file"] == f"perf/configs/{NAME}.json"


def test_every_key_of_the_file_is_the_rows_a_cut_or_the_harnesss():
    ours = {"source", "catalog", "system", "reference", "held_experts",
            "published", "changed", "reduced", "assumed", "deployment",
            "serving"}
    assert set(CFG) == set(ROW) | set(CUT) | ours
    assert (CFG["system"], CFG["reference"]) == ("serve_engine", "keye_vl2")


def test_no_width_expert_or_vocabulary_row_is_cut_and_the_deployment_is_said():
    assert CFG["held_experts"] == [0, 128]      # every expert, on the chip
    assert CFG["num_hidden_layers"] >= 4        # the floor; all alike
    for said in ("holds its layers WHOLE", "all 128 experts", "16 sequences",
                 "1 row a held expert a decode step",
                 "exactly its share"):
        assert said in CFG["deployment"], said
    assumed = " ".join(CFG["assumed"])
    for said in ("(a) A per-head RMSNorm", "(b) The indexer", "NORMED HIDDEN "
                 "STATE", "(c) The router is a softmax", "(d) Rotate-half",
                 "tile sizes", "vision tower"):
        assert said in assumed, said
    assert CFG["serving"]["engine"] == {
        "max_len": 16384, "page_size": 128, "max_batch": 16,
        "weight_dtype": "bfloat16", "prefill_chunk": 512,
        "prefix_cache": False}
    # every scored token lies past the top-k: a real selection
    check = CFG["serving"]["check"]
    assert check == {"requests": 4, "prompt_min": 2304,
                     "prompt_max": 3072, "new_tokens": 16}
    assert check["prompt_min"] > CFG["sa_config"]["topk"]


OURS = ("sparse_kv_decode_stream_share", "sparse_kv_attend_roofline",
        "sparse_kv_index_scan_roofline", "sparse_kv_prefill_chunk_ms",
        "experts_touched_share_mean", "sparse_kv_decode_rows_per_step_mean")
SHARED = ("sparse_attn_decode_share", "sparse_selected_share_mean",
          "sparse_index_scan_live_share", "decode_step_ms_p50",
          "step_gap_ms_p50", "step_host_ms_p50", "setup_engine_build_s",
          "setup_first_calls_s")


def test_the_cell_and_its_traffic_are_the_issues():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "longctx-closed32", 1)
    mix = json.load(open(os.path.join(PERF, "traffic",
                                      "longctx-closed32.json")))
    assert mix["generator"] == "requests" and mix["warmup_s"] == 45
    assert mix["params"] == {
        "arrival": {"process": "closed", "clients": 32},
        "prompt_len": {"dist": "lognormal", "median": 6144, "sigma": 0.5,
                       "min": 2304, "max": 14336},
        "output_len": {"dist": "lognormal", "median": 512, "sigma": 0.5,
                       "min": 128, "max": 1536},
        "max_total": 16384, "stagger_first": True, "stratify": 16}
    # every prompt starts past the top-k and fits the engine with its answer
    p = mix["params"]
    assert p["prompt_len"]["min"] > CFG["sa_config"]["topk"]
    assert p["max_total"] == CFG["serving"]["engine"]["max_len"]


@pytest.mark.parametrize("name", OURS + SHARED)
def test_the_metric_lists_the_cell_and_has_a_reader(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert CELL in entry["workloads"]
    assert entry["moves"] == ("setup_s" if name.startswith("setup_")
                              else "tpot_ms_p50")
    assert hasattr(manifest.load_plugin("layer_metrics", name), "read")
    if name in OURS:        # new: this cell alone, appended at the end
        assert entry["workloads"] == [CELL]
        assert entry in BENCH["per_layer"][-len(OURS):]


def test_the_cell_reports_tpot_and_not_what_would_read_nothing():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert CELL in e2e["tpot_ms_p50"]["workloads"]
    assert CELL not in e2e["serve_out_tokens_per_s"]["workloads"]
    per_layer = {m["name"]: m for m in BENCH["per_layer"]}
    # their reader counts indexer layers from a `layer_types` list this
    # configuration has not
    for name in ("sparse_decode_rows_per_step_mean",
                 "sparse_decode_tokens_per_s"):
        assert CELL not in per_layer[name]["workloads"]
        assert "layer_types" not in CFG


# ---- flops_sparse_gqa.py against hand sums (the issue's table) ---------
ATTN = 2048 * (32 + 4 + 4) * 128 + 32 * 128 * 2048            # 18.87 M
INDEXER = 2048 * 16 * 64 + 2048 * 64 + 2048 * 16              # 2.26 M
ROUTER = 2048 * 128                                           # 0.26 M
EXPERT = 3 * 2048 * 768                                       # 4.72 M
HEAD = 2048 * 151936                                          # 311.2 M
LAYER = ATTN + INDEXER + ROUTER + 128 * EXPERT                # 625.4 M
PARAMS = 5 * LAYER + 2 * HEAD                                 # 3.75 B
ROW_B, IXK_B = 2 * 4 * 128 * 2, 64 * 2                        # 2048, 128


@pytest.mark.parametrize("got,want", [
    (lambda: fl.attention_params(CFG), ATTN),
    (lambda: fl.indexer_params(CFG), INDEXER),
    (lambda: fl.router_params(CFG), ROUTER),
    (lambda: fl.expert_params(CFG), EXPERT),
    (lambda: fl.head_params(CFG), HEAD),
    (lambda: fl.held(CFG), 128),
    (lambda: fl.resident_params(CFG), PARAMS),
    (lambda: fl.resident_weight_bytes(CFG),
     2 * PARAMS + 2 * 5 * (INDEXER + ROUTER)),
    (lambda: fl.row_bytes(CFG), ROW_B),
    (lambda: fl.index_key_bytes(CFG), IXK_B),
    (lambda: fl.token_cache_bytes(CFG), 10880),
    # one sequence of 7000 tokens: every layer reads all 7000 index keys
    # and 2048 selected rows
    (lambda: fl.cache_read_bytes(CFG, [7000]),
     5 * (7000 * IXK_B + 2048 * ROW_B)),
    # under the top-k everything is read
    (lambda: fl.cache_read_bytes(CFG, [300, 100]),
     5 * 400 * (IXK_B + ROW_B)),
    (lambda: fl.decode_weight_bytes(CFG, 81),
     2 * (HEAD + 5 * (ATTN + 81 * EXPERT)) + 4 * 5 * (INDEXER + ROUTER)),
    (lambda: fl.decode_step_bytes(CFG, [7000], 81),
     fl.decode_weight_bytes(CFG, 81) + fl.cache_read_bytes(CFG, [7000])),
])
def test_counts_against_hand_sums(got, want):
    assert got() == want


def test_the_arithmetic_of_the_cut():
    """3.75 B parameters, 7.52 GB as held; pools 2.85 GB; 65 % of the
    chip; a decode step's least bytes 5.09 GB (4.7 of weights)."""
    assert abs(LAYER / 1e6 - 625.4) < 0.05
    assert abs(fl.resident_params(CFG) / 1e9 - 3.749) < 0.001
    weights = fl.resident_weight_bytes(CFG)
    assert abs(weights / 1e9 - 7.52) < 0.01
    pools = 16 * 16384 * fl.token_cache_bytes(CFG)
    assert abs(pools / 1e9 - 2.85) < 0.01
    assert abs(16 * 16384 * 5 * IXK_B / 1e9 - 0.17) < 0.005
    assert 0.64 < (weights + pools) / 16e9 < 0.66
    need = fl.decode_step_bytes(CFG, [7200] * 16, 81)
    assert abs(fl.decode_weight_bytes(CFG, 81) / 1e9 - 4.69) < 0.01
    assert abs(need / 1e9 - 5.09) < 0.01


# ---- this PR's readers on records made by hand ------------------------
def _scope_key():
    share = manifest.load_plugin("layer_metrics", "sparse_attn_decode_share")
    return "scope_times:" + ",".join(share.SCOPES)


def _rec(step=None, prefill=None, model=CFG):
    seconds = {}
    if step:
        seconds["jit_step"] = step
    if prefill:
        seconds["jit_prefill"] = prefill
    return {"kind": "serve", "model": model, "window": (10.0, 50.0),
            "peaks": {"hbm_bytes_per_s": 819e9},
            "steps": [(0, 0, "decode", 16, 0, 0)] * 1000
            + [(0, 0, "prefill", 16, 0, 0)] * 400,
            "requests": [{"state": "done", "n_prompt": 6000, "n_out": 400},
                         {"state": "done", "n_prompt": 9000, "n_out": 800}],
            "trace": {"modules": {"jit_step": [0.025, 0.027]},
                      _scope_key(): {"runs": {"jit_step": 10,
                                              "jit_prefill": 4},
                                     "seconds": seconds}}}


# 1000 decode steps of 14 rows in 5 layers at a mean context of 7000
DELTA = {"experts.decode_steps": 1000,
         "experts.touched": [80000, 82000, 81000, 79000, 83000],
         "experts.rows": [[875] * 128] * 5,
         "sparse.decode_queries": 5 * 1000 * 14,
         "sparse.keys_visible": 5 * 1000 * 14 * 7000,
         "sparse.keys_attended": 5 * 1000 * 14 * 2048,
         "sparse.index_keys_scored": 5 * 1000 * 16 * 16384}


def _reader(name):
    return manifest.load_plugin("layer_metrics", name)


def test_the_two_rooflines_on_a_reduced_trace(monkeypatch):
    monkeypatch.setattr(counter_window, "delta", lambda rec: DELTA)
    attend = _reader("sparse_kv_attend_roofline")
    scan = _reader("sparse_kv_index_scan_roofline")
    rec = _rec(step={"sparse_attend": 0.060, "sparse_index_scores": 0.030,
                     "sparse_select": 0.050, "(other)": 0.12})
    # a step attends to 5 x 14 x 2048 rows of 2048 B in 6 ms of the scope
    want = 100 * (5 * 14 * 2048 * 2048) / 819e9 / 0.006
    assert abs(attend.read(rec) - want) < 1e-9 and 5 < want < 7
    # and scores 5 x 14 x 7000 visible keys of 128 B in 3 ms
    want = 100 * (5 * 14 * 7000 * 128) / 819e9 / 0.003
    assert abs(scan.read(rec) - want) < 1e-9 and 2 < want < 3
    # silent: no such scope in the step, no trace, no counters, no peaks,
    # another family's model
    assert attend.read(_rec(step={"(other)": 0.1})) is None
    assert scan.read(_rec(step={"sparse_attend": 0.1})) is None
    assert attend.read(dict(rec, trace=None)) is None
    assert attend.read(dict(rec, peaks=None)) is None
    assert attend.read(dict(rec, model={"index_topk": 2048})) is None
    monkeypatch.setattr(counter_window, "delta", lambda rec: None)
    assert attend.read(rec) is scan.read(rec) is None


def test_decode_stream_share_on_a_reduced_trace(monkeypatch):
    monkeypatch.setattr(counter_window, "delta", lambda rec: DELTA)
    share = _reader("sparse_kv_decode_stream_share")
    rec = _rec(step={"(other)": 0.26})
    per_seq = (fl.cache_read_bytes(CFG, [6200])
               + fl.cache_read_bytes(CFG, [9400])) / 2
    want = (fl.decode_weight_bytes(CFG, 81.0) + 16 * per_seq) \
        / 819e9 / 0.026
    assert abs(share.read(rec) - want) < 1e-12 and 0.2 < want < 0.3
    assert share.read(dict(rec, trace=None)) is None
    assert share.read(dict(rec, model={"index_topk": 2048})) is None
    monkeypatch.setattr(counter_window, "delta", lambda rec: None)
    assert share.read(rec) is None


def test_prefill_chunk_ms_on_a_reduced_trace():
    chunk = _reader("sparse_kv_prefill_chunk_ms")
    rec = _rec(prefill={"sparse_attend": 0.03, "sparse_select": 0.02,
                        "sparse_index_scores": 0.01, "(other)": 0.08})
    assert abs(chunk.read(rec) - 35.0) < 1e-9           # 0.14 s / 4 runs
    assert chunk.read(_rec(prefill={"(other)": 0.1})) is None
    assert chunk.read(_rec(step={"sparse_attend": 0.1})) is None
    assert chunk.read(dict(rec, model={"index_topk": 2048})) is None
    assert chunk.read(dict(rec, trace=None)) is None


def test_counter_readers_on_a_window_of_counters(monkeypatch):
    touched = _reader("experts_touched_share_mean")
    rows = _reader("sparse_kv_decode_rows_per_step_mean")
    rec = _rec()
    monkeypatch.setattr(counter_window, "delta", lambda rec: DELTA)
    assert abs(touched.read(rec) - 81 / 128) < 1e-12
    assert rows.read(rec) == 14.0
    # the latent family's row reader finds no `layer_types` here
    assert _reader("sparse_decode_rows_per_step_mean").read(rec) is None
    assert rows.read(dict(rec, model={"index_topk": 2048})) is None
    assert rows.read(dict(rec, steps=[])) is None
    monkeypatch.setattr(counter_window, "delta", lambda rec: None)
    assert touched.read(rec) is rows.read(rec) is None
    monkeypatch.setattr(counter_window, "delta",
                        lambda rec: {"steps": 3})
    assert touched.read(rec) is rows.read(rec) is None


# ---- the cell rehearsed on the CPU ------------------------------------
def _run(trace):
    spec = importlib.util.spec_from_file_location(
        "perf_run_sparse_gqa", os.path.join(PERF, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = io.StringIO()
    rc = mod.main(["--rehearse", REHEARSE, "--workload", TINY, "--seed",
                   "3200000011", "--seconds", "0.5", "--trace", str(trace)],
                  out=out)
    assert rc == 0
    lines = out.getvalue().strip().splitlines()
    assert len(lines) == 1
    return json.loads(lines[0])


@pytest.fixture(scope="module")
def traced():
    return _run(1)


@pytest.fixture(scope="module")
def untraced():
    return _run(0)


def test_untraced_rehearsal_reports_the_end_to_end_metrics(untraced):
    assert untraced["correct"] is True and untraced["failed"] == 0
    assert set(untraced["metrics"]) == {"setup_s", "tpot_ms_p50"}


def test_rehearsal_is_correct_and_reports_the_program_counters(traced):
    assert traced["correct"] is True and traced["failed"] == 0
    m = traced["metrics"]
    # a CPU rehearsal has no device plane: the trace-read metrics stay
    # silent, the program's counters speak
    for name in ("sparse_selected_share_mean", "compiles_in_window",
                 "setup_first_calls_s", "sparse_index_scan_live_share",
                 "experts_touched_share_mean",
                 "sparse_kv_decode_rows_per_step_mean"):
        assert name in m, name
    assert m["compiles_in_window"]["value"] == 0
    # contexts of 8-64 tokens against a top-k of 12
    assert 0.15 < m["sparse_selected_share_mean"]["value"] < 0.8
    # 4 slots; the scan reads all 128 positions of every slot of a bucket
    assert 0 < m["sparse_kv_decode_rows_per_step_mean"]["value"] <= 4
    assert 0 < m["sparse_index_scan_live_share"]["value"] < 64 / 128
    # up to 4 rows x 2 picks over 8 experts
    assert 0.125 <= m["experts_touched_share_mean"]["value"] <= 1.0
    for name in ("sparse_kv_decode_stream_share", "sparse_attn_decode_share",
                 "sparse_kv_attend_roofline", "sparse_kv_index_scan_roofline",
                 "sparse_kv_prefill_chunk_ms", "decode_step_ms_p50"):
        assert name not in m


def test_rehearsal_manifest_gives_the_cell_its_metric_tables():
    man = json.load(open(os.path.join(ROOT, REHEARSE)))
    for kind in ("end_to_end", "per_layer"):
        want = {m["name"] for m in BENCH[kind]
                if CELL in m.get("workloads", [CELL])}
        got = {m["name"] for m in man[kind]
               if TINY in m.get("workloads", [TINY])}
        assert got == want, (kind, got ^ want)
    assert set(OURS + SHARED) <= {m["name"] for m in man["per_layer"]}
