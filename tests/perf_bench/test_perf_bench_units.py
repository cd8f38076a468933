"""The benchmark's yardstick, piece by piece (CPU, next to no computation):
the manifest against its contract, the operation counts against hand
counts, the generators, the order statistics, the metric readers on
records made by hand, and the trace reduction on intervals made by hand.
"""
import json
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PERF = os.path.join(ROOT, "perf")
for _p in (ROOT, PERF):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from harness import flops, manifest, peaks, stats, trace_reduce  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
with open(os.path.join(PERF, "rehearse.json")) as _f:
    REHEARSE = json.load(_f)
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

# InternLM2's published sizes (config.json of internlm/internlm2-7b and
# internlm/internlm2-1_8b), written here a second time by hand: a width in
# a configuration file that differs from these is a cut, and no width is
# ever cut.
PUBLISHED = {
    "internlm2-7b": dict(hidden_size=4096, intermediate_size=14336,
                         num_attention_heads=32, num_key_value_heads=8,
                         vocab_size=92544, rms_norm_eps=1e-5,
                         rope_theta=1000000, num_hidden_layers=32,
                         tie_word_embeddings=False),
    "internlm2-1_8b": dict(hidden_size=2048, intermediate_size=8192,
                           num_attention_heads=16, num_key_value_heads=8,
                           vocab_size=92544, rms_norm_eps=1e-5,
                           rope_theta=1000000, num_hidden_layers=24,
                           tie_word_embeddings=False),
}


# ------------------------------------------------------------- manifest --
def test_manifest_has_exactly_the_contract_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "perf/run.py"]
    assert BENCH["paths"] == ["perf", "tests/perf_bench"]
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_run_seconds_fits_a_full_check_of_24_cells():
    rs = BENCH["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_every_name_is_plain_and_used_once():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))
    assert all(len(x["why"]) <= 200
               for k in ("configs", "workloads") for x in BENCH[k])


def test_cells_pair_config_and_traffic_once_and_chips_are_rationed():
    cells = BENCH["workloads"]
    assert 2 <= len(cells) <= 24
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(pairs) == len(set(pairs))
    assert all(w["chips"] in (1, 4) for w in cells)
    four = [w for w in cells if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    assert {w["config"] for w in cells} == {c["name"]
                                            for c in BENCH["configs"]}


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_keeps_every_published_width(cfg):
    assert cfg["file"].startswith("perf/configs/")
    with open(os.path.join(ROOT, cfg["file"])) as f:
        run = json.load(f)
    assert run["source"] == cfg["source"]
    published = PUBLISHED[re.search(r"internlm2-[^/]+", cfg["source"])[0]]
    differs = {k for k, v in published.items() if run[k] != v}
    assert differs <= {"num_hidden_layers"}          # depth only, never width
    assert differs <= set(cfg["reduced"]) == set(run["reduced"])
    assert set(run["changed"]) == set(run["reduced"])
    forbidden = re.compile(r"hidden_size|intermediate|_dim$|_rank$|head|"
                           r"latent|state|expert")
    assert not any(forbidden.search(k) for k in cfg["reduced"])
    # what the file names must exist
    for kind, key in (("systems", "system"), ("references", "reference")):
        assert os.path.isfile(os.path.join(PERF, kind, run[key] + ".py"))


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_reports_setup_another_end_to_end_and_a_layer_metric(cell):
    c = manifest.Cell(BENCH, cell["name"])
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer and all(m["moves"] in e2e for m in c.per_layer)
    gen = c.traffic["generator"]
    assert os.path.isfile(os.path.join(PERF, "generators", gen + ".py"))


@pytest.mark.parametrize(
    "kind,metric",
    [(k, m) for k in ("end_to_end", "per_layer") for m in BENCH[k]],
    ids=lambda x: x["name"] if isinstance(x, dict) else x)
def test_metric_entry_and_its_reader(kind, metric):
    assert metric["source"] in SOURCES
    assert metric["better"] in ("higher", "lower")
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    if kind == "end_to_end":
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
        folder = "end_to_end"
    else:
        assert "bound" not in metric and metric["layer"]
        assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
        folder = "layer_metrics"
    assert hasattr(manifest.load_plugin(folder, metric["name"]), "read")


def test_setup_s_has_the_bound_the_contract_gives_it():
    setup = next(m for m in BENCH["end_to_end"] if m["name"] == "setup_s")
    assert setup["bound"] == 0.1 and "workloads" not in setup


def test_rehearsal_manifest_covers_the_metric_tables():
    """Every metric of the benchmark is rehearsed; the rehearsal also keeps
    the open-loop cell and its readers, which the benchmark does not list
    yet (PERF.md, section 6)."""
    for kind in ("end_to_end", "per_layer"):
        assert {m["name"] for m in BENCH[kind]} <= \
            {m["name"] for m in REHEARSE[kind]}
        folder = "end_to_end" if kind == "end_to_end" else "layer_metrics"
        for m in REHEARSE[kind]:
            assert hasattr(manifest.load_plugin(folder, m["name"]), "read")
    for w in REHEARSE["workloads"]:
        manifest.Cell(REHEARSE, w["name"])      # every file it names exists


def test_unknown_plugin_and_unknown_cell_say_so():
    with pytest.raises(FileNotFoundError, match="no_such_reader"):
        manifest.load_plugin("layer_metrics", "no_such_reader")
    with pytest.raises(KeyError, match="no workload"):
        manifest.Cell(BENCH, "no-such-cell")


# ---------------------------------------------------- peaks and counting --
def test_peaks_of_the_v5e_and_an_unknown_kind_is_an_error():
    p = peaks.peaks_for("TPU v5 lite")
    assert (p["bf16_flops"], p["int8_ops"], p["hbm_bytes_per_s"],
            p["hbm_bytes"]) == (197e12, 393e12, 819e9, 16e9)
    with pytest.raises(LookupError, match="TPU v9"):
        peaks.peaks_for("TPU v9")
    with pytest.raises(LookupError):
        peaks.peaks_for("cpu")


@pytest.mark.parametrize("model,layers,seq,params,per_token", [
    # per layer at 1.8B: q,o 2*2048^2 + k,v 2*2048*1024 + mlp 3*2048*8192
    #   = 8388608 + 4194304 + 50331648 = 62914560; head 2048*92544
    ("internlm2-1_8b", 24, 4096, 24 * 62914560 + 189530112,
     6 * (24 * 62914560 + 189530112) + 6 * 24 * 2048 * 4096),
    ("internlm2-1_8b", 18, 4096, 18 * 62914560 + 189530112,
     6 * (18 * 62914560 + 189530112) + 6 * 18 * 2048 * 4096),
    # per layer at 7B: 2*4096^2 + 2*4096*1024 + 3*4096*14336 = 218103808
    ("internlm2-7b", 32, 1024, 32 * 218103808 + 379060224,
     6 * (32 * 218103808 + 379060224) + 6 * 32 * 4096 * 1024),
    ("internlm2-7b", 8, 4096, 8 * 218103808 + 379060224,
     6 * (8 * 218103808 + 379060224) + 6 * 8 * 4096 * 4096),
])
def test_flops_against_hand_counts(model, layers, seq, params, per_token):
    cfg = dict(PUBLISHED[model], num_hidden_layers=layers)
    assert flops.matmul_params(cfg) == params
    assert flops.train_flops_per_token(cfg, seq) == per_token


def test_decode_bytes_against_hand_counts():
    cfg = PUBLISHED["internlm2-7b"]
    assert flops.decode_weight_bytes(cfg) == 32 * 218103808 + 379060224
    # keys and values, 8 heads x 128, bf16, 32 layers = 131072 B a token
    assert flops.kv_bytes_per_token(cfg) == 2 * 8 * 128 * 2 * 32 == 131072
    assert flops.decode_step_bytes(cfg, 1000) == \
        flops.decode_weight_bytes(cfg) + 131072000
    # the input embedding (92544 x 4096) is what 6*n_params overcounts
    n_params = flops.matmul_params(cfg) + 92544 * 4096 + 65 * 4096
    assert 6 * n_params > 6 * flops.matmul_params(cfg)


def test_percentile_and_mean():
    assert stats.percentile([], 50) is None and stats.mean([]) is None
    assert stats.percentile([3.0, 1.0, 2.0], 50) == 2.0
    assert stats.percentile(range(101), 90) == 90.0
    assert stats.percentile([1.0, 2.0], 50) == 1.5
    assert stats.mean([1, 2, 6]) == 3.0


# ------------------------------------------------------------ generators --
CLOSED = {"arrival": {"process": "closed", "clients": 5},
          "prompt_len": {"dist": "lognormal", "median": 96, "sigma": 0.8,
                         "min": 16, "max": 384},
          "output_len": {"dist": "lognormal", "median": 320, "sigma": 0.5,
                         "min": 128, "max": 640},
          "max_total": 1024, "stagger_first": True}
OPEN = {"arrival": {"process": "open", "rate_per_s": 50.0, "cv": 1.0},
        "prompt_len": {"dist": "uniform", "min": 16, "max": 768},
        "output_len": {"dist": "fixed", "value": 300},
        "max_total": 1024}


def _requests_gen():
    return manifest.load_plugin("generators", "requests")


def _drain_open(stream, horizon):
    return stream.due(horizon)


def test_open_loop_is_reproducible_and_seed_dependent():
    g = _requests_gen()
    a = _drain_open(g.make(OPEN, 7, 92544), 20.0)
    b = _drain_open(g.make(OPEN, 7, 92544), 20.0)
    c = _drain_open(g.make(OPEN, 8, 92544), 20.0)
    assert [r.t_due for r in a] == [r.t_due for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
               for x, y in zip(a, b))
    assert [r.t_due for r in a] != [r.t_due for r in c]
    # asked in pieces or at once, the schedule is the same
    s = g.make(OPEN, 7, 92544)
    pieces = [r for t in np.arange(0.5, 20.01, 0.5) for r in s.due(t)]
    assert [r.t_due for r in pieces] == [r.t_due for r in a]


def test_open_loop_rate_lengths_and_total_cap():
    reqs = _drain_open(_requests_gen().make(OPEN, 3, 92544), 100.0)
    assert 4500 < len(reqs) < 5500                 # 50 a second for 100 s
    due = np.array([r.t_due for r in reqs])
    assert np.all(np.diff(due) >= 0) and due[-1] <= 100.0
    gaps = np.diff(due)
    assert abs(np.std(gaps) / np.mean(gaps) - 1.0) < 0.1     # Poisson: cv 1
    for r in reqs:
        assert 16 <= r.prompt.size <= 768
        assert r.prompt.size + r.max_new <= 1024 and r.max_new >= 1
        assert r.prompt.dtype == np.int64
        assert 0 <= r.prompt.min() and r.prompt.max() < 92544
    assert any(r.max_new < 300 for r in reqs)      # the cap did cut some


def test_burstier_arrivals_keep_the_rate_and_raise_the_spread():
    p = dict(OPEN, arrival={"process": "open", "rate_per_s": 50.0, "cv": 3.0})
    due = np.array([r.t_due for r in
                    _drain_open(_requests_gen().make(p, 3, 1000), 200.0)])
    gaps = np.diff(due)
    assert abs(len(due) / 200.0 - 50.0) < 5.0
    assert 2.5 < np.std(gaps) / np.mean(gaps) < 3.5


def test_closed_loop_sends_on_completion_only():
    s = _requests_gen().make(CLOSED, 11, 92544)
    assert s.closed
    first = s.due(0.0)
    assert len(first) == 5 and {r.client for r in first} == set(range(5))
    assert all(r.t_due == 0.0 for r in first)
    assert s.due(100.0) == [] and s.next_due() is None
    s.done(first[2], 3.5)
    assert s.next_due() == 3.5 and s.due(3.4) == []
    nxt = s.due(3.6)
    assert len(nxt) == 1 and nxt[0].client == 2 and nxt[0].t_due == 3.5
    assert nxt[0].index == 5
    for r in first + nxt:
        assert 16 <= r.prompt.size <= 384 and 1 <= r.max_new <= 640


def test_closed_loop_staggers_only_the_first_round():
    g = _requests_gen()
    plain = g.make(dict(CLOSED, stagger_first=False), 5, 92544).due(0.0)
    cut = g.make(CLOSED, 5, 92544).due(0.0)
    assert all(c.max_new <= p.max_new for c, p in zip(cut, plain))
    assert sum(c.max_new < p.max_new for c, p in zip(cut, plain)) >= 4
    assert all(np.array_equal(c.prompt, p.prompt)
               for c, p in zip(cut, plain))
    s = g.make(CLOSED, 5, 92544)
    s.due(0.0)
    s.done(cut[0], 1.0)
    assert s.due(2.0)[0].max_new >= 128            # later rounds are whole


def test_shared_prefixes_come_from_one_pool():
    p = dict(OPEN, prefix={"pool": 3, "share": 0.5,
                           "len": {"dist": "fixed", "value": 64}},
             prompt_len={"dist": "fixed", "value": 200})
    reqs = _drain_open(_requests_gen().make(p, 9, 92544), 10.0)
    heads = {tuple(r.prompt[:64]) for r in reqs}
    shared = [h for h in heads
              if sum(tuple(r.prompt[:64]) == h for r in reqs) > 1]
    assert len(shared) == 3
    n_shared = sum(tuple(r.prompt[:64]) in shared for r in reqs)
    assert 0.4 < n_shared / len(reqs) < 0.6
    tails = {tuple(r.prompt[64:]) for r in reqs}
    assert len(tails) == len(reqs)                 # own tokens follow


def test_stratified_draws_take_one_value_from_every_slice_per_block():
    g = _requests_gen()
    strata = g._Strata(np.random.default_rng(21), 8)
    blocks = [[strata.next() for _ in range(8)] for _ in range(20)]
    for block in blocks:
        assert sorted(int(u * 8) for u in block) == list(range(8))
    assert len({tuple(np.argsort(b)) for b in blocks}) > 10   # order drawn
    # lengths rise with the quantile, so a block of requests spans the
    # distribution: one short, one long, the rest between
    spec = {"dist": "lognormal", "median": 100, "sigma": 0.7, "min": 8,
            "max": 256}
    lens = [g._length(u, spec) for u in np.linspace(0.001, 0.999, 50)]
    assert lens == sorted(lens) and lens[0] == 11 and lens[-1] == 256
    assert g._length(0.5, spec) == 100
    assert [g._length(u, {"dist": "uniform", "min": 3, "max": 6})
            for u in (0.0, 0.26, 0.51, 0.76, 0.999)] == [3, 4, 5, 6, 6]
    p = dict(OPEN, stratify=8, output_len=spec)
    reqs = _drain_open(g.make(p, 21, 1000), 10.0)[:64]
    for b in range(0, 64, 8):
        outs = sorted(r.max_new for r in reqs[b:b + 8])
        assert outs[0] < 50 and outs[-1] > 200 and 70 < outs[3] < 130


def test_stratified_arrivals_offer_every_seed_nearly_the_same_work():
    def offered(stratify, seed):
        p = dict(OPEN, stratify=stratify,
                 arrival={"process": "open", "rate_per_s": 1.0, "cv": 1.0},
                 output_len={"dist": "lognormal", "median": 100,
                             "sigma": 0.7, "min": 8, "max": 256})
        reqs = _drain_open(_requests_gen().make(p, seed, 1000), 40.0)
        return len(reqs), sum(r.max_new for r in reqs)

    plain = np.array([offered(1, s) for s in range(40)])
    strat = np.array([offered(16, s) for s in range(40)])
    assert abs(plain[:, 0].mean() - 40) < 3 and abs(strat[:, 0].mean() - 40) < 2
    assert plain[:, 0].std() > 4.5              # Poisson: sqrt(40) = 6.3
    assert strat[:, 0].std() < 0.6 * plain[:, 0].std()
    assert strat[:, 1].std() < 0.6 * plain[:, 1].std()
    # the same distribution: the mean output is the lognormal's, clipped
    assert abs(strat[:, 1].sum() / strat[:, 0].sum()
               - plain[:, 1].sum() / plain[:, 0].sum()) < 8


def test_generator_rejects_what_it_does_not_know():
    g = _requests_gen()
    with pytest.raises(ValueError, match="arrival"):
        g.make(dict(OPEN, arrival={"process": "sometimes"}), 1, 100)
    with pytest.raises(ValueError, match="distribution"):
        g.make(dict(OPEN, prompt_len={"dist": "cauchy"}), 1, 100).due(1.0)


def test_token_batches_are_fresh_each_step_and_reproducible():
    g = manifest.load_plugin("generators", "token_batches")
    a, b = g.make({"batch": 2, "seq": 4096}, 4, 92544), \
        g.make({"batch": 2, "seq": 4096}, 4, 92544)
    ids, labels = a.batch(3)
    assert ids.shape == labels.shape == (2, 4096)
    assert a.tokens_per_step == 8192
    assert np.array_equal(ids, b.batch(3)[0])
    assert not np.array_equal(ids, a.batch(4)[0])
    assert not np.array_equal(
        ids, g.make({"batch": 2, "seq": 4096}, 5, 92544).batch(3)[0])
    assert np.array_equal(labels[:, :-1], ids[:, 1:])
    assert 0 <= ids.min() and ids.max() < 92544


# --------------------------------------------- readers on a made-up record --
def _serve_record():
    reqs = []
    for i in range(10):                  # due at 100+i, first token i*10 ms
        reqs.append({"t_due": 100.0 + i, "t_add": 100.0 + i + 0.001 * i,
                     "t_seat": 100.0 + i + 0.002,
                     "t_first": 100.0 + i + 0.010 * (i + 1),
                     "t_done": 100.0 + i + 0.010 * (i + 1) + 0.020 * 9,
                     "n_prompt": 100, "n_out": 10, "state": "done"})
    reqs.append({"t_due": 105.5, "t_add": 105.5, "t_seat": None,
                 "t_first": None, "t_done": None, "n_prompt": 50,
                 "n_out": 0, "state": "abandoned"})
    steps = [(100.0 + 0.03 * k, 100.0 + 0.03 * k + (0.02 if k % 3 else 0.03),
              "decode" if k % 3 else "prefill", 16, 0, 64)
             for k in range(300)]
    return {"kind": "serve", "window": (100.0, 110.0), "t_give_up": 120.0,
            "requests": reqs, "steps": steps, "tokens_in_window": 12345,
            "slots_total": 32, "pages_total": 256, "closed_loop": False,
            "model": PUBLISHED["internlm2-7b"], "t_process_start": 40.0,
            "device": {"memory_peak_bytes": 13_000_000_000},
            "peaks": peaks.peaks_for("TPU v5 lite"),
            "clock": {"compile_s_setup": 12.5, "trace_lower_s": 30.0,
                      "cache_misses": 0, "cache_hits": 9, "in_window": []},
            "trace": {"window_s": 5.0, "busy_s": 4.0, "collective_s": 0.0,
                      "modules": {"jit_step": [0.020, 0.022],
                                  "jit_prefill": [0.03]}}}


def _read(folder, name, rec):
    return manifest.load_plugin(folder, name).read(rec)


def test_serving_readers_on_a_made_up_record():
    rec = _serve_record()
    assert _read("end_to_end", "setup_s", rec) == 60.0
    assert _read("end_to_end", "serve_out_tokens_per_s", rec) == 1234.5
    assert _read("end_to_end", "tpot_ms_p50", rec) == pytest.approx(20.0)
    assert _read("end_to_end", "tpot_ms_mean", rec) == pytest.approx(20.0)
    # ten served at 10..100 ms and one abandoned, which counts from its
    # due time to the give-up: 14.5 s. p90 of 11 values = the 10th.
    assert _read("layer_metrics", "ttft_ms_p90", rec) == pytest.approx(100.0)
    assert _read("end_to_end", "ttft_ms_p50", rec) == pytest.approx(60.0)
    assert _read("layer_metrics", "queue_wait_ms_p50", rec) == \
        pytest.approx(2.0)
    assert _read("layer_metrics", "gen_late_ms_p99", rec) == \
        pytest.approx(8.9, abs=0.2)
    assert _read("layer_metrics", "batch_occupancy_mean", rec) == 0.5
    assert _read("layer_metrics", "pages_used_share_mean", rec) == 0.75
    # device times of the two step programs, from the traced modules
    assert _read("layer_metrics", "decode_step_ms_p50", rec) == \
        pytest.approx(21.0)
    assert _read("layer_metrics", "prefill_step_ms_p50", rec) == \
        pytest.approx(30.0)
    assert _read("layer_metrics", "prefill_step_share", rec) == \
        pytest.approx(0.03 / 5.0)
    assert _read("layer_metrics", "compile_s", rec) == 12.5
    assert _read("layer_metrics", "trace_lower_s", rec) == 30.0
    assert _read("layer_metrics", "cache_misses", rec) == 0
    assert _read("layer_metrics", "compiles_in_window", rec) == 0
    # 16 running x (100 + 10/2) tokens of context
    need = flops.decode_step_bytes(PUBLISHED["internlm2-7b"], 16 * 105)
    assert _read("layer_metrics", "decode_stream_share", rec) == \
        pytest.approx(need / 819e9 / 0.021)


def test_a_reader_with_nothing_to_read_returns_nothing():
    rec = _serve_record()
    rec["trace"] = None
    assert _read("layer_metrics", "decode_stream_share", rec) is None
    assert _read("layer_metrics", "decode_step_ms_p50", rec) is None
    assert _read("layer_metrics", "prefill_step_share", rec) is None
    assert _read("layer_metrics", "collective_share", rec) is None
    assert _read("layer_metrics", "mfu", rec) is None
    assert _read("end_to_end", "train_tokens_per_s_per_chip", rec) is None
    rec["closed_loop"] = True
    assert _read("layer_metrics", "gen_late_ms_p99", rec) is None
    rec["device"] = {"memory_peak_bytes": 0}
    assert _read("layer_metrics", "peak_hbm_gb", rec) is None


def test_training_readers_on_a_made_up_record():
    cfg = dict(PUBLISHED["internlm2-1_8b"], num_hidden_layers=18)
    rec = {"kind": "train", "window": (10.0, 40.0), "n_steps": 30,
           "tokens_per_step": 8192, "seq": 4096, "chips": 4, "model": cfg,
           "t_process_start": 0.0, "peaks": peaks.peaks_for("TPU v5 lite"),
           "device": {"memory_peak_bytes": 14_500_000_000},
           "trace": {"window_s": 3.0, "collective_s": 0.6, "modules": {}}}
    assert _read("end_to_end", "train_tokens_per_s_per_chip", rec) == 2048.0
    assert _read("layer_metrics", "mfu", rec) == pytest.approx(
        8192.0 * flops.train_flops_per_token(cfg, 4096) / (4 * 197e12))
    assert _read("layer_metrics", "peak_hbm_gb", rec) == 14.5
    assert _read("layer_metrics", "collective_share", rec) == \
        pytest.approx(0.2)
    assert _read("end_to_end", "tpot_ms_p50", rec) is None
    rec["chips"] = 1
    assert _read("layer_metrics", "collective_share", rec) is None


# ---------------------------------------- trace arithmetic, made by hand --
def test_union_clips_and_merges():
    assert trace_reduce.union([(0, 2), (1, 3), (5, 6), (9, 12)], 0.5, 10) == \
        [[0.5, 3], [5, 6], [9, 10]]
    assert trace_reduce.union([(4, 5)], 0, 3) == []


def test_self_time_goes_to_the_innermost_operation():
    events = [("while.1", 0.0, 10.0), ("fusion.2", 1.0, 4.0),
              ("fusion.3", 4.0, 9.0), ("copy.4", 12.0, 13.0),
              ("all-reduce.5", 5.0, 6.0)]
    own = dict(trace_reduce.self_times(events))
    assert own == {"while.1": pytest.approx(2.0), "fusion.2": 3.0,
                   "fusion.3": pytest.approx(4.0), "all-reduce.5": 1.0,
                   "copy.4": 1.0}
    assert sum(own.values()) == pytest.approx(11.0)   # the busy union


def test_operation_names_come_out_of_the_hlo_text():
    text = ("%negate_add_fusion.2 = (bf16[2,4096,8192]{2,1,0:T(8,128)(2,1)}) "
            "fusion(bf16[2,4096,8192] %p.1), kind=kLoop, calls=%fused.3")
    assert trace_reduce.op_name(text) == "negate_add_fusion.2"
    assert trace_reduce.op_name(
        "%fusion.362 = bf16[8] fusion(bf16[8] %x), kind=kOutput, "
        "calls=%f") == "fusion.kOutput.362"
    assert trace_reduce.op_family("fusion.kOutput.362") == "fusion.kOutput"
    assert trace_reduce.op_name("%all-reduce.5 = f32[4] all-reduce(%y)") == \
        "all-reduce.5"
    assert trace_reduce.op_name("bench.step") == "bench.step"
    psum = ("%psum.3 = bf16[4096]{0} all-reduce(bf16[4096]{0} %x), "
            "channel_id=1, replica_groups={{0,1}}")
    assert trace_reduce.op_name(psum) == "all-reduce.psum.3"
    assert trace_reduce.op_family("all-reduce.psum.3") == "all-reduce.psum"
    assert trace_reduce.op_name(
        "%ag.1 = bf16[8] all-gather-start(bf16[4] %y)") == "all-gather.ag.1"


def test_operation_families_and_module_names():
    assert trace_reduce.op_family("fusion.1234") == "fusion"
    assert trace_reduce.op_family("all-reduce-start.7.1") == \
        "all-reduce-start"
    assert trace_reduce.op_family("decode_layer_mk") == "decode_layer_mk"
    assert trace_reduce.module_name("jit_step(1234567890)") == "jit_step"


# ------------------------------- a trace recorded on the v5e (29 KB) --
FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "small_tpu.xplane.pb")


def test_recorded_tpu_trace_has_the_planes_and_lines_the_reduction_reads():
    """tests/perf_bench/record_trace_fixture.py on one v5e chip (PR 22):
    four runs of a small jitted program with a loop inside, the harness's
    span names round them, a 2 ms pause between."""
    devices, spans = trace_reduce.read_planes(FIXTURE)
    assert sorted(devices) == [0]
    assert {k: len(v) for k, v in devices[0].items()} == \
        {"XLA Modules": 4, "XLA Ops": 68}
    assert [s[0] for s in spans] == ["bench.step", "bench.wait",
                                     "bench.idle"] * 4
    families = {trace_reduce.op_family(e[0]) for e in devices[0]["XLA Ops"]}
    assert families == {"copy", "fusion.kOutput", "copy-start", "copy-done",
                        "while", "reduce"}
    assert all(trace_reduce.module_name(e[0]) == "jit_small_step"
               for e in devices[0]["XLA Modules"])


def test_recorded_tpu_trace_reduces_to_the_numbers_read_by_hand():
    r = trace_reduce.reduce_trace(FIXTURE, 1)
    assert r["window_s"] == pytest.approx(0.01307964, rel=1e-6)
    assert r["busy_s"] == pytest.approx(0.000321526, rel=1e-5)
    assert r["busy_s_per_chip"] == [r["busy_s"]]
    assert r["collective_s"] == 0.0
    # four module runs of about 80 microseconds are all the busy time, and
    # the operations' self times add up to it: nothing is counted twice
    runs = r["modules"]["jit_small_step"]
    assert len(runs) == 4 and all(7.9e-5 < d < 8.2e-5 for d in runs)
    assert sum(runs) == pytest.approx(r["busy_s"], rel=1e-3)
    assert sum(s for _, s in r["device_ops"]) == \
        pytest.approx(r["busy_s"], rel=1e-6)
    assert r["device_ops"][0][0] == "fusion.kOutput"     # the matmuls
    assert r["device_ops"][0][1] == pytest.approx(0.00027776, rel=1e-4)
    # the chip was idle nearly all the window, mostly under the pause
    idle = dict(r["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
    assert r["idle_gaps"][0][0] == "bench.idle"
    assert idle["bench.idle"] == pytest.approx(0.009582607, rel=1e-5)
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10


def test_a_trace_without_a_device_plane_reduces_to_nothing(tmp_path):
    import jax
    import jax.numpy as jnp
    import jax.profiler as jp
    jp.start_trace(str(tmp_path))
    with jp.TraceAnnotation("bench.step"):
        jax.jit(lambda x: x + 1)(jnp.ones(4)).block_until_ready()
    jp.stop_trace()
    import glob
    path = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))[0]
    assert trace_reduce.reduce_trace(path, 1) is None
    _, spans = trace_reduce.read_planes(path)
    assert [s[0] for s in spans] == ["bench.step"]
