"""The configuration `command-a-plus-ep16-l4-serve` held to a hand-written
table of the catalog row, `flops_parallel_block.py`'s counts against hand
sums (the issue's numbers), this PR's readers on records made by hand,
and the cell `serve-parallel-window-ragdoc` rehearsed on the CPU through
its own manifest (`perf/rehearse_parallel_block.json`, configuration
`tiny-cohere2-moe-serve`) with the same runner, generator and readers.
Manifest entries are found BY NAME, never by their place in a list.
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
from harness import flops_parallel_block as fl  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = "command-a-plus-ep16-l4-serve"
CFG = json.load(open(os.path.join(PERF, "configs", NAME + ".json")))
CELL = "serve-parallel-window-ragdoc"
TRAFFIC = "ragdoc-closed96"
REHEARSE = "perf/rehearse_parallel_block.json"
TINY = "tiny-cohere2-moe-serve-closed"

# the catalog row command-a-plus-05-2026, written by hand: every key that
# is not cut, widths first
ROW = {
    "hidden_size": 4096, "intermediate_size": 4096,
    "num_attention_heads": 128, "num_key_value_heads": 8, "head_dim": 128,
    "num_experts_per_tok": 8, "num_shared_experts": 4,
    "sliding_window": 4096, "rope_theta": 50000, "rotary_pct": 1,
    "rope_parameters": {"rope_theta": 50000, "rope_type": "default"},
    "layer_norm_eps": 1e-05, "rms_norm_eps": None, "layer_switch": 4,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 8,
    "order_of_interleaved_layers": "local_attn_first",
    "position_embedding_type": "rope_gptj",
    "expert_selection_fn": "sigmoid", "norm_topk_prob": True,
    "shared_expert_combination_strategy": "average",
    "first_k_dense_replace": 0, "prefix_dense_intermediate_size": 16384,
    "prefix_dense_sliding_window_pattern": 1, "logit_scale": 1,
    "tie_word_embeddings": True, "use_embedding_sharing": True,
    "use_gated_activation": True, "use_parallel_block": True,
    "use_parallel_embedding": False, "use_qk_norm": False,
    "attention_bias": False, "hidden_act": "silu", "tf_legacy_loss": False,
    "model_type": "cohere2_moe"}
CUT = {"num_hidden_layers": (32, 4), "num_experts": (128, 8),
       "vocab_size": (262144, 32768),
       "max_position_embeddings": (200000, 16384)}


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
    assert sorted(CFG["changed"]) == sorted(CUT) == sorted(CFG["published"])
    assert entry["source"] == CFG["source"] == (
        "https://huggingface.co/CohereLabs/command-a-plus-05-2026/blob/"
        "main/config.json")
    assert entry["file"] == f"perf/configs/{NAME}.json"


def test_every_key_of_the_file_is_the_rows_a_cut_or_the_harnesss():
    ours = {"source", "catalog", "system", "reference", "layers_kept",
            "held_experts", "published", "changed", "reduced", "assumed",
            "deployment", "serving"}
    assert set(CFG) == set(ROW) | set(CUT) | ours
    assert (CFG["system"], CFG["reference"]) == ("serve_engine",
                                                 "cohere2_moe")


def test_no_width_is_cut_and_the_deployment_is_said():
    assert CFG["held_experts"] == [0, 8]        # the floor of 8 experts
    assert CFG["layers_kept"] == [0, 1, 2, 3]   # one whole period
    assert [CFG["layer_types"][l] for l in CFG["layers_kept"]] == \
        ["sliding_attention"] * 3 + ["full_attention"]
    assert CFG["vocab_size"] * 8 == CFG["published"]["vocab_size"]
    for said in ("16 chips share each layer", "8 of 128 here",
                 "32768 of 262144 rows", "3 rows a decode step",
                 "16 x their share", "6.51 GB", "3.22 GB", "2.58 GB"):
        assert said in CFG["deployment"], said
    assumed = " ".join(CFG["assumed"])
    for said in ("MEAN OVER THE FOUR SHARED EXPERTS", "(routed + shared) / 2",
                 "No router correction bias", "`intermediate_size` 4096",
                 "INTERLEAVED pairs", "position-free", "vision tower",
                 "ONE LayerNorm", "prefill_chunk 1024"):
        assert said in assumed, said
    assert CFG["serving"]["engine"] == {
        "max_len": 16384, "page_size": 128, "max_batch": 48,
        "weight_dtype": "bfloat16", "prefill_chunk": 1024,
        "prefix_cache": False}
    # the checked requests cross the window, so pages are freed behind it
    check = CFG["serving"]["check"]
    assert check == {"requests": 4, "prompt_min": 3584,
                     "prompt_max": 5632, "new_tokens": 16}
    assert check["prompt_min"] + check["new_tokens"] < CFG["sliding_window"] \
        < check["prompt_max"]


def test_the_cell_and_its_traffic_are_the_issues():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, TRAFFIC, 1)
    mix = json.load(open(os.path.join(PERF, "traffic", TRAFFIC + ".json")))
    assert mix["generator"] == "requests" and mix["warmup_s"] == 45
    assert mix["params"] == {
        "arrival": {"process": "closed", "clients": 96},
        "prompt_len": {"dist": "lognormal", "median": 4096, "sigma": 0.7,
                       "min": 768, "max": 13312},
        "output_len": {"dist": "lognormal", "median": 1024, "sigma": 0.5,
                       "min": 256, "max": 3072},
        "max_total": 16384, "stagger_first": True, "stratify": 16}
    p = mix["params"]
    assert p["arrival"]["clients"] == 2 * CFG["serving"]["engine"][
        "max_batch"]
    assert p["max_total"] == CFG["serving"]["engine"]["max_len"]
    # half the prompts end under the window and half over it
    assert p["prompt_len"]["median"] == CFG["sliding_window"]


OURS = ("parallel_block_decode_stream_share",
        "parallel_block_paged_attn_roofline",
        "parallel_block_expert_mm_roofline", "shared_expert_decode_ms",
        "window_kv_read_share_mean", "gqa_chunk_attn_roofline")
SHARED = tuple(f"{prog}_{what}" for prog in ("decode", "prefill")
               for what in ("attn_proj_ms", "kv_write_ms", "attend_ms",
                            "ffn_ms", "head_ms", "unphased_share")) + (
    "decode_step_ms_p50", "step_gap_ms_p50", "step_host_ms_p50",
    "setup_engine_build_s", "setup_first_calls_s", "dispatch_ahead_share",
    "experts_touched_share_mean")


@pytest.mark.parametrize("name", OURS + SHARED)
def test_the_metric_lists_the_cell_and_has_a_reader(name):
    found = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert len(found) == 1
    entry = found[0]
    assert CELL in entry["workloads"]
    assert entry["moves"] == ("setup_s" if name.startswith("setup_")
                              else "tpot_ms_p50")
    assert hasattr(manifest.load_plugin("layer_metrics", name), "read")
    if name in OURS:        # new: this cell alone
        assert entry["workloads"] == [CELL]
        assert entry["unit"] == ("%" if name.endswith("_roofline") else
                                 "ms" if name.endswith("_ms") else
                                 "fraction")


def test_the_cell_reports_tpot_and_no_reader_of_another_familys_keys():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert CELL in e2e["tpot_ms_p50"]["workloads"]
    per_layer = {m["name"]: m for m in BENCH["per_layer"]}
    # these read `moe_intermediate_size` / `hybrid_layer_pattern` /
    # `sa_config`, which this configuration has not
    for name in ("expert_mm_roofline", "paged_attn_roofline",
                 "hybrid_decode_stream_share", "decode_stream_share",
                 "sparse_kv_decode_stream_share"):
        assert CELL not in per_layer[name]["workloads"], name
    for key in ("moe_intermediate_size", "hybrid_layer_pattern",
                "sa_config"):
        assert key not in CFG
    # a metric lists the cell only if the cell reports what it moves
    mine = {m["name"] for m in e2e.values()
            if CELL in m.get("workloads", [CELL])}
    for m in BENCH["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["moves"] in mine, m["name"]


# ---- flops_parallel_block.py against hand sums (the issue's table) -----
ATTN = 4096 * (16384 + 2 * 1024) + 16384 * 4096               # 142.6 M
EXPERT = 3 * 4096 * 4096                                      # 50.33 M
SHARED_P = 4 * EXPERT                                         # 201.3 M
ROUTER = 4096 * 128                                           # 0.52 M
HEAD = 4096 * 32768                                           # 134.2 M
LAYER = ATTN + SHARED_P + ROUTER + 8 * EXPERT                 # 747.1 M
KV_B = 2 * 8 * 128 * 2                                        # 4096 B


@pytest.mark.parametrize("got,want", [
    (lambda: fl.layers(CFG), [True, True, True, False]),
    (lambda: fl.attention_params(CFG), ATTN),
    (lambda: fl.expert_params(CFG), EXPERT),
    (lambda: fl.shared_params(CFG), SHARED_P),
    (lambda: fl.router_params(CFG), ROUTER),
    (lambda: fl.head_params(CFG), HEAD),
    (lambda: fl.held(CFG), 8),
    (lambda: fl.layer_params(CFG), LAYER),
    (lambda: fl.resident_params(CFG, head_copy=False), 4 * LAYER + HEAD),
    (lambda: fl.resident_params(CFG), 4 * LAYER + 2 * HEAD),
    (lambda: fl.resident_weight_bytes(CFG),
     2 * (4 * LAYER + 2 * HEAD) + 2 * 4 * ROUTER),
    (lambda: fl.kv_bytes_per_token(CFG), KV_B),
    # one sequence under the window, one far over it
    (lambda: fl.kv_read_tokens(CFG, [3000, 9000]),
     (3 * (3000 + 4096), 3000 + 9000)),
    (lambda: fl.kv_read_bytes(CFG, [3000, 9000]),
     (3 * 7096 + 12000) * KV_B),
    (lambda: fl.expert_mm_bytes(CFG, 7.6, 144),
     7.6 * EXPERT * 2 + 144 * (4096 + 3 * 4096 + 4096) * 2),
    (lambda: fl.decode_weight_bytes(CFG, 7.6),
     2 * (HEAD + 4 * (ATTN + SHARED_P + 7.6 * EXPERT)) + 4 * 4 * ROUTER),
    (lambda: fl.decode_step_bytes(CFG, 700000, 7.6),
     fl.decode_weight_bytes(CFG, 7.6) + 700000 * KV_B),
    # a 1,024-token chunk at positions 4,096..5,119: every query of a
    # window layer sees 4,096 keys, of the full layer all before it
    (lambda: fl.chunk_attention_flops(
        CFG, 3 * 1024 * 4096 + sum(range(4097, 5121))),
     4 * 128 * 128 * (3 * 1024 * 4096 + (4097 + 5120) * 1024 // 2)),
])
def test_counts_against_hand_sums(got, want):
    assert got() == want


def test_the_arithmetic_of_the_cut():
    """The issue's numbers: a layer outside the routed experts 344.4 M;
    3.12 B parameters (6.25 GB; 6.51 with the head's copy); full group
    3.2 GB, window group 2.6 GB; resident about three quarters of the
    chip; a decode step NEEDS about 9.3 GB."""
    assert abs(ATTN / 1e6 - 142.6) < 0.05
    assert abs((ATTN + SHARED_P + ROUTER) / 1e6 - 344.4) < 0.1
    assert abs(fl.resident_params(CFG, head_copy=False) / 1e9 - 3.12) < 0.005
    weights = fl.resident_weight_bytes(CFG)
    assert abs(weights / 1e9 - 6.51) < 0.01
    eng = CFG["serving"]["engine"]
    pages_seq = eng["max_len"] // eng["page_size"]
    full = 1 * eng["max_batch"] * pages_seq * eng["page_size"] * KV_B
    bound1 = -(-(CFG["sliding_window"] + 1) // eng["page_size"]) + 1
    win = 3 * (eng["max_batch"] * bound1
               + eng["prefill_chunk"] // eng["page_size"]) \
        * eng["page_size"] * KV_B
    assert bound1 == 34
    assert abs(full / 1e9 - 3.22) < 0.01 and abs(win / 1e9 - 2.58) < 0.01
    assert 0.75 < (weights + full + win) / 16e9 < 0.78
    # 48 sequences at a mean context of 5.5k, 3.6k of it inside a window
    tokens = 48 * (3 * 3600 + 5500)
    need = fl.decode_step_bytes(CFG, tokens, 0.95 * 8)
    assert abs(fl.decode_weight_bytes(CFG, 0.95 * 8) / 1e9 - 6.09) < 0.02
    assert abs(need / 1e9 - 9.3) < 0.15
    assert abs(3 * 3600 / (3 * 3600 + 5500) - 0.66) < 0.01


# ---- this PR's readers on records made by hand ------------------------
def _reader(name):
    return manifest.load_plugin("layer_metrics", name)


def _rec(scope=None, kernels=None, model=CFG, prefill=None):
    shared = _reader("shared_expert_decode_ms")
    return {"kind": "serve", "model": model, "window": (10.0, 50.0),
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
            "steps": [(0, 0, "decode", 48, 0, 0)] * 1500,
            "requests": [],
            "trace": {"modules": {"jit_step": [0.020, 0.022]},
                      "scope_times:" + ",".join(shared.SCOPES): {
                          "runs": {"jit_step": 10},
                          "seconds": {"jit_step": scope or {}}},
                      "kernel_times": {
                          "runs": {"jit_step": 10, "jit_prefill": 4},
                          "seconds": {"jit_step": kernels or {},
                                      "jit_prefill": prefill or {}}}}}


# 1500 decode steps of 48 rows in 4 layers at a mean context of 5.5k, 3.6k
# of it inside the window; group 0 the window group (3 layers)
DELTA = {"steps": 1900, "prefill_steps": 400, "experts.decode_steps": 1500,
         # 400 chunks of 1,024 queries at a mean of 3,000 keys a query in
         # a window layer and 3,500 in the full one
         "group0.prefill_pairs": 400 * 3 * 1024 * 3000,
         "group1.prefill_pairs": 400 * 1024 * 3500,
         "experts.touched": [11400, 11250, 11550, 11400],
         "experts.rows": [[4500] * 8] * 4,
         "group0.window": 4096, "group1.window": 0,
         "group0.kv_tokens_read": 1500 * 48 * 3 * 3600,
         "group1.kv_tokens_read": 1500 * 48 * 5500,
         "group0.pages_total": 4920, "group1.pages_total": 6144}


def test_decode_stream_share_on_a_reduced_trace(monkeypatch):
    monkeypatch.setattr(counter_window, "delta", lambda rec: DELTA)
    share = _reader("parallel_block_decode_stream_share")
    rec = _rec()
    tokens = 48 * (3 * 3600 + 5500)
    want = fl.decode_step_bytes(CFG, tokens, 7.6) / 819e9 / 0.021
    assert abs(share.read(rec) - want) < 1e-12 and 0.5 < want < 0.6
    assert share.kv_tokens_per_step(DELTA) == (48 * 3 * 3600, 48 * 5500)
    # silent: no trace, no peaks, another family's model, no counters,
    # a program without the new counter (the parent commit)
    assert share.read(dict(rec, trace=None)) is None
    assert share.read(dict(rec, peaks=None)) is None
    assert share.read(dict(rec, model={"hybrid_layer_pattern": [0]})) is None
    old = {k: v for k, v in DELTA.items() if "kv_tokens_read" not in k}
    monkeypatch.setattr(counter_window, "delta", lambda rec: old)
    assert share.read(rec) is None
    monkeypatch.setattr(counter_window, "delta", lambda rec: None)
    assert share.read(rec) is None


def test_the_two_rooflines_on_a_reduced_trace(monkeypatch):
    monkeypatch.setattr(counter_window, "delta", lambda rec: DELTA)
    attn = _reader("parallel_block_paged_attn_roofline")
    mm = _reader("parallel_block_expert_mm_roofline")
    rec = _rec(kernels={"paged_attention_decode": 0.060,
                        "moe_grouped_matmul": 0.050, "fusion": 0.1})
    # a step reads 48 x (3 x 3600 + 5500) tokens of 4096 B in 6 ms
    want = 100 * 48 * (3 * 3600 + 5500) * 4096 / 819e9 / 0.006
    assert abs(attn.read(rec) - want) < 1e-9 and 60 < want < 70
    # and streams 7.6 touched experts a layer, 24 rows, in 5 ms
    want = 100 * 4 * fl.expert_mm_bytes(CFG, 7.6, 24) / 819e9 / 0.005
    assert abs(mm.read(rec) - want) < 1e-9 and 70 < want < 80
    none = _rec(kernels={"fusion": 0.1})
    assert attn.read(none) is None and mm.read(none) is None
    assert attn.read(dict(rec, peaks=None)) is None
    assert mm.read(dict(rec, model={"moe_intermediate_size": 2048})) is None
    monkeypatch.setattr(counter_window, "delta", lambda rec: None)
    assert attn.read(rec) is None and mm.read(rec) is None


def test_shared_expert_ms_and_window_share(monkeypatch):
    shared = _reader("shared_expert_decode_ms")
    win = _reader("window_kv_read_share_mean")
    rec = _rec(scope={"shared_expert": 0.021, "(other)": 0.2})
    assert abs(shared.read(rec) - 2.1) < 1e-9       # 21 ms / 10 runs
    assert shared.read(_rec(scope={"(other)": 0.2})) is None
    assert shared.read(dict(rec, trace=None)) is None
    assert shared.read(dict(rec, kind="train")) is None
    monkeypatch.setattr(counter_window, "delta", lambda rec: DELTA)
    want = 3 * 3600 / (3 * 3600 + 5500)
    assert abs(win.read(rec) - want) < 1e-12 and 0.5 < want < 0.8
    monkeypatch.setattr(counter_window, "delta", lambda rec: None)
    assert win.read(rec) is None


def test_chunk_attention_roofline_on_a_reduced_trace(monkeypatch):
    monkeypatch.setattr(counter_window, "delta", lambda rec: DELTA)
    roof = _reader("gqa_chunk_attn_roofline")
    rec = _rec(prefill={"paged_chunk_attention": 0.040, "fusion": 0.1})
    # a chunk covers 1,024 x (3 x 3,000 + 3,500) pairs in 10 ms of kernels
    want = 100 * 4 * 128 * 128 * 1024 * 12500 / 197e12 / 0.010
    assert abs(roof.read(rec) - want) < 1e-9 and 40 < want < 45
    # XLA key blocks ran the phase: no such kernel, nothing to read
    assert roof.read(_rec(prefill={"fusion": 0.1})) is None
    assert roof.read(dict(rec, peaks=None)) is None
    assert roof.read(dict(rec, model={"hybrid_layer_pattern": [0]})) is None
    old = {k: v for k, v in DELTA.items() if "prefill_pairs" not in k}
    monkeypatch.setattr(counter_window, "delta", lambda rec: old)
    assert roof.read(rec) is None
    monkeypatch.setattr(counter_window, "delta", lambda rec: None)
    assert roof.read(rec) is None


# ---- the cell rehearsed on the CPU ------------------------------------
def _run(trace):
    spec = importlib.util.spec_from_file_location(
        "perf_run_parallel_block", os.path.join(PERF, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = io.StringIO()
    rc = mod.main(["--rehearse", REHEARSE, "--workload", TINY, "--seed",
                   "3600000011", "--seconds", "0.5", "--trace", str(trace)],
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
    for name in ("compiles_in_window", "setup_first_calls_s",
                 "dispatch_ahead_share", "experts_touched_share_mean",
                 "window_kv_read_share_mean"):
        assert name in m, name
    assert m["compiles_in_window"]["value"] == 0
    assert m["dispatch_ahead_share"]["value"] > 0.9
    # contexts of 8-64 tokens, a window of 16, three window layers of four
    assert 0.4 < m["window_kv_read_share_mean"]["value"] <= 0.75
    # up to 4 rows x 2 picks over the 4 held of 8 experts
    assert 0.1 <= m["experts_touched_share_mean"]["value"] <= 1.0
    for name in ("parallel_block_decode_stream_share",
                 "parallel_block_paged_attn_roofline",
                 "parallel_block_expert_mm_roofline",
                 "shared_expert_decode_ms", "decode_step_ms_p50",
                 "decode_unphased_share", "gqa_chunk_attn_roofline"):
        assert name not in m


def test_rehearsal_manifest_gives_the_cell_its_metric_tables():
    """The rehearsal lists this PR's readers and the cell's end-to-end
    metrics under the names the manifest has; what a later PR appends the
    cell to is not pinned here."""
    man = json.load(open(os.path.join(ROOT, REHEARSE)))
    for kind, ours in (("end_to_end", {"setup_s", "tpot_ms_p50"}),
                       ("per_layer", set(OURS + SHARED))):
        known = {m["name"] for m in BENCH[kind]}
        got = {m["name"] for m in man[kind]
               if TINY in m.get("workloads", [TINY])}
        assert ours <= got <= known, (kind, ours - got, got - known)
