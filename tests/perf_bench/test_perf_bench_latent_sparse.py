"""The configuration `dots3-note-prev-ep16-serve` held to a hand-written
table of the catalog row, `flops_latent_sparse.py`'s counts against hand
sums, the trace's scope reader on the recorded fixture, and the cell
`serve-mla-sparse-longdoc` rehearsed on the CPU through its own manifest
(`perf/rehearse_latent_sparse.json`, configuration `tiny-dots3-serve`)
with the same runner, generator and readers.
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

from harness import flops_latent_sparse as fl  # noqa: E402
from harness import kernel_times, scope_times  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = "dots3-note-prev-ep16-serve"
CFG = json.load(open(os.path.join(PERF, "configs", NAME + ".json")))
CELL = "serve-mla-sparse-longdoc"
REHEARSE = "perf/rehearse_latent_sparse.json"
FIXTURE = os.path.join(ROOT, "tests", "perf_bench", "data",
                       "small_tpu_spans.xplane.pb")

PATTERN = ["full_attention"] + ["full_attention", "sliding_attention",
                                "sliding_attention",
                                "sliding_attention"] * 11 \
    + ["full_attention"]
# the catalog row dots3-note-prev, written by hand: every key that is
# not cut, widths first
ROW = {
    "hidden_size": 5120, "intermediate_size": 13824,
    "moe_intermediate_size": 1536, "num_attention_heads": 128,
    "num_key_value_heads": 128, "q_lora_rank": 1024, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "swa_num_attention_heads": 64, "swa_num_key_value_heads": 64,
    "swa_q_lora_rank": 1024, "swa_kv_lora_rank": 1024,
    "swa_qk_nope_head_dim": 192, "swa_qk_rope_head_dim": 64,
    "swa_v_head_dim": 128, "sliding_window_size": 513,
    "index_head_dim": 128, "index_n_heads": 64, "index_topk": 2048,
    "num_experts_per_tok": 8, "n_shared_experts": 1,
    "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "rope_theta": 80000000, "swa_rope_theta": 50000,
    "rope_scaling": None, "rms_norm_eps": 1e-05,
    "routed_scaling_factor": 1, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "apply_mla_qkv_lora_rescale": True, "attention_bias": False,
    "attention_gate_type": "headwise",
    "swa_attention_gate_type": "headwise", "hidden_act": "silu",
    "tie_word_embeddings": False, "model_type": "dots3_note",
    "layer_types": PATTERN}
CUT = {"num_hidden_layers": (46, 5), "n_routed_experts": (256, 16),
       "vocab_size": (152064, 19008),
       "max_position_embeddings": (524288, 18432)}


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
    assert entry["source"] == CFG["source"]
    assert entry["file"] == f"perf/configs/{NAME}.json"


def test_every_key_of_the_file_is_the_rows_a_cut_or_the_harnesss():
    ours = {"source", "catalog", "system", "reference", "layers_kept",
            "held_experts", "published", "changed", "reduced", "assumed",
            "deployment", "serving"}
    assert set(CFG) == set(ROW) | set(CUT) | ours
    assert len(PATTERN) == 46 and PATTERN.count("full_attention") == 13


def test_a_whole_period_is_kept_and_the_deployment_is_written_down():
    kept = CFG["layers_kept"]
    assert kept == [0, 1, 2, 3, 4]
    assert [PATTERN[l] for l in kept] == ["full_attention"] * 2 \
        + ["sliding_attention"] * 3
    # (windowed, routed): the dense full layer, then one period, routed
    assert fl.layers(CFG) == [(False, False), (False, True)] \
        + [(True, True)] * 3
    assert CFG["held_experts"] == [0, 16]
    for said in ("16 chips share each layer", "data-parallel",
                 "vocabulary-parallel 8 ways", "shared expert"):
        assert said in CFG["deployment"], said
    assumed = " ".join(CFG["assumed"])
    for said in ("(a) `apply_mla_qkv_lora_rescale`", "(b) The head-wise "
                 "gate", "(c) Rotate-half"):
        assert said in assumed, said
    assert CFG["serving"]["engine"] == {
        "max_len": 18432, "page_size": 128, "max_batch": 64,
        "weight_dtype": "bfloat16", "prefill_chunk": 512,
        "prefix_cache": False}
    # every scored token lies past the top-k and past the window
    check = CFG["serving"]["check"]
    assert check == {"requests": 4, "prompt_min": 2304,
                     "prompt_max": 3072, "new_tokens": 16}
    assert check["prompt_min"] > CFG["index_topk"] \
        > CFG["sliding_window_size"]


OURS = ("latent_decode_stream_share", "sparse_attn_decode_share",
        "sparse_selected_share_mean", "latent_prefill_chunk_ms",
        "latent_prefill_attn_share", "latent_prefill_device_share",
        "sparse_decode_rows_per_step_mean", "sparse_decode_tokens_per_s",
        "sparse_index_scan_live_share")


def test_the_cell_and_its_traffic_are_the_issues():
    """Presence only: the cell with the issue's configuration, traffic
    and chips, the issue's traffic parameters, and this PR's readers
    listing the cell. What else lists the cell is a later PR's to change
    (test_perf_bench_command.py holds entries to being appended)."""
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "longdoc-closed128", 1)
    mix = json.load(open(os.path.join(PERF, "traffic",
                                      "longdoc-closed128.json")))
    assert mix["generator"] == "requests" and 40 <= mix["warmup_s"] <= 60
    assert mix["params"] == {
        "arrival": {"process": "closed", "clients": 128},
        "prompt_len": {"dist": "lognormal", "median": 4096, "sigma": 0.7,
                       "min": 1024, "max": 16384},
        "output_len": {"dist": "lognormal", "median": 768, "sigma": 0.5,
                       "min": 256, "max": 2048},
        "max_total": 18432, "stagger_first": True, "stratify": 16}
    per_layer = {m["name"]: m for m in BENCH["per_layer"]}
    for name in OURS:
        assert CELL in per_layer[name]["workloads"], name
        assert os.path.exists(os.path.join(PERF, "layer_metrics",
                                           name + ".py")), name
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert CELL in e2e["tpot_ms_p50"]["workloads"]


# ---- flops_latent_sparse.py against hand sums (the issue's table) -------
FULL_ATTN = (5120 * 1024 + 1024 * 128 * 192 + 5120 * 576
             + 512 * 128 * 256 + 128 * 128 * 5120 + 5120 * 128)  # 134.7 M
INDEXER = 1024 * 64 * 128 + 5120 * 128 + 5120 * 64               # 9.4 M
WIN_ATTN = (5120 * 1024 + 1024 * 64 * 256 + 5120 * 1088
            + 1024 * 64 * 320 + 64 * 128 * 5120 + 5120 * 64)     # 90.8 M
DENSE = 3 * 5120 * 13824                                         # 212.3 M
EXPERT = 3 * 5120 * 1536                                         # 23.6 M
ROUTER = 5120 * 256
HEAD = 5120 * 19008
PARAMS = (2 * (FULL_ATTN + INDEXER) + 3 * WIN_ATTN + DENSE
          + 4 * (17 * EXPERT + ROUTER) + 2 * HEAD)


@pytest.mark.parametrize("got,want", [
    (lambda: fl.attention_params(CFG, False), FULL_ATTN),
    (lambda: fl.indexer_params(CFG), INDEXER),
    (lambda: fl.attention_params(CFG, True), WIN_ATTN),
    (lambda: fl.dense_ffn_params(CFG), DENSE),
    (lambda: fl.expert_params(CFG), EXPERT),
    (lambda: fl.shared_params(CFG), EXPERT),
    (lambda: fl.router_params(CFG), ROUTER),
    (lambda: fl.head_params(CFG), HEAD),
    (lambda: fl.resident_params(CFG), PARAMS),
    (lambda: fl.resident_weight_bytes(CFG),
     2 * PARAMS + 2 * (2 * INDEXER + 4 * ROUTER)),
    (lambda: fl.row_width(CFG, False), 576),
    (lambda: fl.row_width(CFG, True), 1088),
    # one sequence of 5000 tokens: a full layer reads 2048 selected rows
    # and all 5000 index keys, a window layer 513 rows
    (lambda: fl.cache_read_bytes(CFG, [5000]),
     2 * (2048 * 1152 + 5000 * 256) + 3 * 513 * 2176),
    # under the top-k and the window everything is read
    (lambda: fl.cache_read_bytes(CFG, [300, 100]),
     2 * 400 * (1152 + 256) + 3 * 400 * 2176),
    (lambda: fl.decode_weight_bytes(CFG, 14),
     2 * (2 * FULL_ATTN + 3 * WIN_ATTN + DENSE + 4 * 15 * EXPERT + HEAD)
     + 4 * (2 * INDEXER + 4 * ROUTER)),
    (lambda: fl.decode_step_bytes(CFG, [5000], 14),
     fl.decode_weight_bytes(CFG, 14) + fl.cache_read_bytes(CFG, [5000])),
])
def test_counts_against_hand_sums(got, want):
    assert got() == want


def test_the_arithmetic_of_the_cut():
    """2.58 B parameters, 5.2 GB as held; the pools as the engine lays
    them out (rows padded to the lanes) 3.97 GB; 57 % of the chip."""
    assert abs(fl.resident_params(CFG) / 1e6 - 2577.1) < 0.1
    assert abs((FULL_ATTN + INDEXER) / 1e6 - 144.05) < 0.01
    assert abs(WIN_ATTN / 1e6 - 90.83) < 0.01
    weights = fl.resident_weight_bytes(CFG)
    assert abs(weights / 1e9 - 5.20) < 0.01
    pages = 64 * 144
    window_pages = 64 * 6 + 4               # bound(1) = 6, one chunk more
    pools = (2 * pages * 128 * (640 + 128) * 2
             + 3 * window_pages * 128 * 1152 * 2)
    assert window_pages == 388 and abs(pools / 1e9 - 3.97) < 0.01
    assert 0.55 < (weights + pools) / 16e9 < 0.60
    # a decode step's least bytes: 14 of 16 experts touched, 64
    # sequences at 5,400 tokens
    need = fl.decode_step_bytes(CFG, [5400] * 64, 14)
    assert abs(need / 1e9 - 5.32) < 0.01


# ---- the scope reader on the recorded TPU trace ------------------------
def test_scope_times_reads_name_stacks_and_agrees_with_kernel_times():
    ops = scope_times.read_device_ops(FIXTURE)
    stacks = {stack for stack, _, _, _ in ops}
    assert "jit(small_step)/while/body/closed_call/dot_general:" in stacks
    got = scope_times.reduce_file(FIXTURE, ("closed_call", "no_such"))
    want = kernel_times.reduce_file(FIXTURE)
    assert got["runs"] == want["runs"] == {"jit_small_step": 4}
    sec = got["seconds"]["jit_small_step"]
    assert set(sec) == {"closed_call", "(other)"}
    # every operation's self time lands in exactly one bucket
    assert abs(sum(sec.values())
               - sum(want["seconds"]["jit_small_step"].values())) < 1e-7
    # the matrix multiplications are the ones traced under the call
    assert abs(sec["closed_call"] - want["seconds"]["jit_small_step"][
        "fusion.kOutput"]) < 1e-7


@pytest.mark.parametrize("buf,want", [
    (bytes([0x08, 0x96, 0x01]), [(1, 0, 150)]),
    (bytes([0x12, 0x03]) + b"abc", [(2, 2, b"abc")]),
    (bytes([0x0d, 1, 0, 0, 0, 0x11, 2, 0, 0, 0, 0, 0, 0, 0]),
     [(1, 5, 1), (2, 1, 2)]),
])
def test_wire_format_fields(buf, want):
    got = [(n, w, bytes(v) if w == 2 else v)
           for n, w, v in scope_times.fields(memoryview(buf))]
    assert got == want


def test_prefill_readers_on_a_reduced_trace():
    """The two prefill readers from scope seconds as `scope_times.of`
    keeps them in the record; silent without a latent scope."""
    from harness import manifest
    chunk = manifest.load_plugin("layer_metrics", "latent_prefill_chunk_ms")
    share = manifest.load_plugin("layer_metrics",
                                 "latent_prefill_attn_share")
    key = "scope_times:" + ",".join(chunk.SCOPES)

    def rec(seconds):
        return {"kind": "serve", "trace": {key: {
            "runs": {"jit_prefill": 4, "jit_step": 4},
            "seconds": {"jit_prefill": seconds}}}}

    got = rec({"sparse_attend": 0.06, "window_latent_attend": 0.02,
               "sparse_select": 0.016, "sparse_index_scores": 0.004,
               "(other)": 0.1})
    assert abs(chunk.read(got) - 50.0) < 1e-9           # 0.2 s / 4 runs
    assert abs(share.read(got) - 0.5) < 1e-12
    assert chunk.read(rec({"(other)": 0.1})) is None
    assert share.read(rec({"(other)": 0.1})) is None
    assert chunk.read({"kind": "serve", "trace": None}) is None


def test_counter_readers_on_a_window_of_counters(monkeypatch):
    """The three counter readers from two samples of the engine's
    counters; silent where the program keeps none (the parent)."""
    from harness import counter_window, manifest
    rows, rate, live = (manifest.load_plugin("layer_metrics", n) for n in (
        "sparse_decode_rows_per_step_mean", "sparse_decode_tokens_per_s",
        "sparse_index_scan_live_share"))
    rec = {"kind": "serve", "model": CFG, "window": (10.0, 50.0),
           "steps": [(0, 0, "decode", 64, 0, 0)] * 500
           + [(0, 0, "prefill", 64, 0, 0)] * 500}
    # 2 indexer layers x 500 steps x 40 rows; 64 slots x 18432 scanned
    delta = {"sparse.decode_queries": 2 * 500 * 40,
             "sparse.keys_visible": 2 * 500 * 40 * 6000,
             "sparse.index_keys_scored": 2 * 500 * 64 * 18432}
    monkeypatch.setattr(counter_window, "delta", lambda rec: delta)
    assert rows.read(rec) == 40.0
    assert rate.read(rec) == 500.0                  # 20,000 tokens / 40 s
    assert abs(live.read(rec) - 40 * 6000 / (64 * 18432)) < 1e-12
    monkeypatch.setattr(counter_window, "delta", lambda rec: None)
    assert rows.read(rec) is rate.read(rec) is live.read(rec) is None
    monkeypatch.setattr(counter_window, "delta",
                        lambda rec: {"experts.decode_steps": 3})
    assert rows.read(rec) is rate.read(rec) is live.read(rec) is None


def test_prefill_device_share_on_a_reduced_trace():
    from harness import manifest
    chunk = manifest.load_plugin("layer_metrics", "latent_prefill_chunk_ms")
    share = manifest.load_plugin("layer_metrics",
                                 "latent_prefill_device_share")
    key = "scope_times:" + ",".join(chunk.SCOPES)
    rec = {"kind": "serve", "trace": {key: {
        "runs": {"jit_prefill": 4, "jit_step": 4},
        "seconds": {"jit_prefill": {"sparse_attend": 0.1, "(other)": 0.1},
                    "jit_step": {"sparse_attend": 0.04, "(other)": 0.06}}}}}
    assert abs(share.read(rec) - 2 / 3) < 1e-12
    rec["trace"][key]["seconds"]["jit_prefill"] = {"(other)": 0.1}
    assert share.read(rec) is None
    assert share.read({"kind": "serve", "trace": None}) is None


# ---- the cell rehearsed on the CPU ------------------------------------
def _run(trace):
    spec = importlib.util.spec_from_file_location(
        "perf_run_latent_sparse", os.path.join(PERF, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = io.StringIO()
    rc = mod.main(["--rehearse", REHEARSE, "--workload",
                   "tiny-dots3-serve-closed", "--seed", "2500000011",
                   "--seconds", "0.5", "--trace", str(trace)], out=out)
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
    assert untraced["correct"] is True
    assert {"setup_s", "tpot_ms_p50"} <= set(untraced["metrics"])


def test_rehearsal_is_correct_and_reports_the_program_counters(traced):
    assert traced["correct"] is True and traced["failed"] == 0
    m = traced["metrics"]
    # a CPU rehearsal has no device plane: the trace-read metrics stay
    # silent, the program's counters speak
    for name in ("sparse_selected_share_mean", "compiles_in_window",
                 "setup_first_calls_s", "sparse_decode_rows_per_step_mean",
                 "sparse_decode_tokens_per_s",
                 "sparse_index_scan_live_share"):
        assert name in m, name
    assert m["compiles_in_window"]["value"] == 0
    # contexts of 20-64 tokens against a top-k of 12
    assert 0.15 < m["sparse_selected_share_mean"]["value"] < 0.7
    # 4 slots; the scan reads all 96 positions of every slot of a bucket
    assert 0 < m["sparse_decode_rows_per_step_mean"]["value"] <= 4
    assert 0 < m["sparse_index_scan_live_share"]["value"] < 64 / 96
    assert m["sparse_decode_tokens_per_s"]["value"] > 0
    for name in ("latent_decode_stream_share", "sparse_attn_decode_share",
                 "latent_prefill_chunk_ms", "latent_prefill_attn_share",
                 "latent_prefill_device_share"):
        assert name not in m


def test_rehearsal_manifest_gives_the_cell_its_metric_tables():
    """The rehearsal lists this PR's readers and the cell's end-to-end
    metrics under the names the manifest has; what a later PR appends the
    cell to is not pinned here."""
    man = json.load(open(os.path.join(ROOT, REHEARSE)))
    tiny = "tiny-dots3-serve-closed"
    for kind, ours in (("end_to_end", {"setup_s", "tpot_ms_p50"}),
                       ("per_layer", set(OURS))):
        known = {m["name"] for m in BENCH[kind]}
        got = {m["name"] for m in man[kind]
               if tiny in m.get("workloads", [tiny])}
        assert ours <= got <= known, (kind, ours - got, got - known)
