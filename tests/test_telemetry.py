"""Serving telemetry plane (ISSUE 13): per-request lifecycle tracing,
latency histograms, fleet metrics export.

The acceptance contract: a seeded 20-request ragged run with telemetry
ON yields (a) greedy outputs BYTE-IDENTICAL to the telemetry-off run,
(b) a perfetto-loadable chrome trace where every retired request has a
complete span chain (admission -> TTFT -> decode -> retire, plus any
demote/handoff/failover legs), and (c) TTFT/TPOT histogram counts equal
to retired requests — fleet-wide through EngineRouter.metrics(). The
health() schema of engine and router is PINNED here (dashboards and the
registry's rate sampler consume it; a renamed counter used to fail
silently). Micro 1-layer geometry throughout — telemetry is
model-independent host work.
"""
import json

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import failsafe, profiler
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.inference.router import EngineRouter
from paddle_tpu.inference.scheduler import ContinuousBatchingEngine
from paddle_tpu.inference.telemetry import (DEFAULT_BUCKETS_MS,
                                            Histogram, MetricsRegistry,
                                            Telemetry, chrome_trace)


def _micro_cfg():
    return LlamaConfig.tiny(num_hidden_layers=1, hidden_size=32,
                            intermediate_size=64, num_attention_heads=2)


@pytest.fixture(scope="module")
def tiny():
    paddle.seed(3)
    cfg = _micro_cfg()
    return LlamaForCausalLM(cfg), cfg


ENGINE_KW = dict(max_len=64, page_size=8, max_batch=2, prefill_chunk=8)


def stream(cfg, n=20, seed=0):
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, cfg.vocab_size, (int(t),)).astype(np.int64)
               for t in rng.randint(4, 14, n)]
    budgets = [int(b) for b in rng.randint(3, 8, n)]
    return prompts, budgets


@pytest.fixture(scope="module")
def traced_run(tiny):
    """The acceptance run: 20 seeded ragged requests, decode_block=4,
    telemetry off (reference outputs) then on (same stream, same
    engine config). Shared by the byte-identity / span-chain /
    histogram-count / export assertions below."""
    model, cfg = tiny
    prompts, budgets = stream(cfg)
    kw = dict(ENGINE_KW, max_batch=4, decode_block=4)
    ref = ContinuousBatchingEngine(model, **kw).generate_many(
        prompts, max_new_tokens=budgets)
    tel = Telemetry()
    eng = ContinuousBatchingEngine(model, telemetry=tel, **kw)
    outs = eng.generate_many(prompts, max_new_tokens=budgets)
    return prompts, budgets, ref, outs, tel, eng


# -- units -------------------------------------------------------------------
class TestHistogram:
    def test_observe_and_percentiles(self):
        h = Histogram()
        for v in (0.15, 0.15, 3.0, 3.0, 3.0, 300.0):
            h.observe(v)
        assert h.count == 6
        assert h.vmin == 0.15 and h.vmax == 300.0
        # p50 lands in the (2, 5] bucket; p99+ in (200, 500]
        assert 2.0 <= h.percentile(50) <= 5.0
        assert 200.0 <= h.percentile(99) <= 500.0
        assert h.percentile(0) <= h.percentile(100)

    def test_overflow_bucket_reports_max(self):
        h = Histogram()
        h.observe(1e9)
        assert h.percentile(99) == 1e9

    def test_merge_adds(self):
        a, b = Histogram(), Histogram()
        a.observe(1.0)
        b.observe(100.0)
        b.observe(100.0)
        a.merge(b)
        assert a.count == 3
        assert a.vmax == 100.0 and a.vmin == 1.0
        with pytest.raises(ValueError):
            a.merge(Histogram(buckets=(1.0, 2.0)))

    def test_empty(self):
        h = Histogram()
        assert h.percentile(99) == 0.0
        assert h.snapshot() == {"count": 0}


class TestRegistry:
    def test_rates_from_counter_samples(self):
        reg = MetricsRegistry()
        assert reg.sample({"steps": 0, "name": "x"}) == {}
        rates = reg.sample({"steps": 50, "name": "x"})
        assert rates["steps_per_s"] > 0
        assert "name_per_s" not in rates       # non-numeric skipped

    def test_merged_fleet_view(self):
        a, b = MetricsRegistry(), MetricsRegistry()
        a.observe("ttft_ms", 10.0)
        a.count("requests_done")
        b.observe("ttft_ms", 20.0)
        b.count("requests_done", 2)
        fleet = MetricsRegistry.merged([a, b])
        assert fleet.hist["ttft_ms"].count == 2
        assert fleet.counters["requests_done"] == 3

    def test_prometheus_text(self):
        reg = MetricsRegistry()
        reg.observe("ttft_ms", 42.0)
        reg.count("requests_done", 7)
        text = reg.prometheus()
        assert "# TYPE paddle_tpu_ttft_ms histogram" in text
        assert 'paddle_tpu_ttft_ms_bucket{le="+Inf"} 1' in text
        assert "paddle_tpu_ttft_ms_count 1" in text
        assert "paddle_tpu_requests_done 7" in text


# -- windowed percentiles (PR 17 satellite: the autoscale controller
# -- reacts to CURRENT load, not lifetime aggregates) -------------------------
WINDOW_SNAPSHOT_KEYS = frozenset({
    "count", "sum_ms", "min_ms", "max_ms", "p50_ms", "p90_ms",
    "p95_ms", "p99_ms", "window_s",
})


class TestWindowedPercentiles:
    def test_window_reflects_recent_not_lifetime(self):
        reg = MetricsRegistry(window_s=10.0)
        reg.observe("ttft_ms", 100.0, now=0.0)
        reg.observe("ttft_ms", 100.0, now=3.0)
        reg.observe("ttft_ms", 500.0, now=20.0)
        assert reg.hist["ttft_ms"].count == 3       # lifetime keeps all
        w = reg.window_hist("ttft_ms", now=21.0)
        assert w.count == 1                         # window: recent only
        assert w.percentile(99) > 200.0
        # an old-only window reads empty, lifetime still answers
        assert reg.window_hist("ttft_ms", now=200.0).count == 0

    def test_window_snapshot_schema_pinned(self):
        reg = MetricsRegistry(window_s=10.0)
        reg.observe("queue_wait_ms", 5.0)
        snap = reg.window_snapshot()
        got = frozenset(snap["queue_wait_ms"])
        assert got == WINDOW_SNAPSHOT_KEYS, (
            f"window snapshot schema drifted: "
            f"added={sorted(got - WINDOW_SNAPSHOT_KEYS)} "
            f"removed={sorted(WINDOW_SNAPSHOT_KEYS - got)} — the "
            "autoscale controller and dashboards consume these keys; "
            "update docs/observability.md and this pin TOGETHER")
        # the registry snapshot carries the windows view alongside the
        # lifetime histograms under its own key
        assert "windows" in reg.snapshot()
        # an aged-out window degrades to the empty histogram shape
        empty = reg.window_snapshot(now=1e9)["queue_wait_ms"]
        assert frozenset(empty) == frozenset({"count", "window_s"})
        assert empty["count"] == 0

    def test_merge_aggregates_windows(self):
        a = MetricsRegistry(window_s=10.0)
        b = MetricsRegistry(window_s=10.0)
        a.observe("ttft_ms", 10.0, now=20.0)
        b.observe("ttft_ms", 30.0, now=20.5)
        b.merge(a)
        assert b.window_hist("ttft_ms", now=21.0).count == 2
        fleet = MetricsRegistry.merged([a, b])
        assert fleet.window_hist("ttft_ms", now=21.0).count >= 2

    def test_state_ships_ages_not_timestamps(self):
        # cross-process rule (same as relative deadline budgets):
        # monotonic clocks do not cross process boundaries, so the
        # shipped state carries slice AGES and install() rebases them
        # onto the local clock
        tel = Telemetry(name="w0")
        tel.registry.observe("tpot_ms", 7.0)
        state = tel.state()
        assert "win" in state
        from paddle_tpu.inference.telemetry import (
            ReplicaTelemetryMirror)
        mir = ReplicaTelemetryMirror("w0")
        mir.install_state(state)
        assert mir.registry.window_hist("tpot_ms").count == 1


# -- the pinned health() schemas (satellite: dashboards + the registry's
# -- rate sampler consume these keys; a rename must fail a test, not a
# -- dashboard) --------------------------------------------------------------
ENGINE_HEALTH_KEYS = frozenset({
    "queued", "running", "slots_total", "queue_limit", "pages_free",
    "pages_total", "page_groups", "experts", "sparse", "prefix_pages",
    "prefix_hits",
    "done", "failed",
    "cancelled", "steps", "prefill_steps", "decode_steps", "admissions",
    "failures", "deadline_expiries", "cow_copies", "decode_block",
    "fused_blocks", "chained_blocks",
    # the per-step path's run-ahead (PR 31): {"dispatched",
    # "resolved_first": {reason: n}, "overrun_rows"}
    "ahead",
    "megakernel",
    "megakernel_whole_step", "tp", "tp_mode", "tp_compress", "speculate",
    "drafter", "spec_passes", "spec_emitted", "spec_accept_rate",
    "spec_tokens_per_pass", "draft_errors",
    # on-device sampling v2 (PR 18: inference/sampling.py)
    "sampled_requests", "sample_k",
    "spec_sampled_accept_rate",
    "handoffs_out", "handoffs_in",
    "kv_tier", "demoted", "pages_demoted", "demotions", "restores",
    "restore_failures", "demote_errors", "tier", "index_publishes",
    "index_publish_errors", "prefix_exports", "prefix_imports",
    "adapters", "preemptions", "tenants",
    # PR 25: the weight matmuls' MXU operand type, static
    "mm_operand_dtype",
    # PR 27: the megakernel's tile plan, static (None on the op chain)
    "mk_tile_plan",
    # PR 37: what the paged decode attention kernel does a layer call,
    # static (None under the megakernel or where no layer calls it)
    "paged_decode",
    # what a latent layer's prefill chunk runs, static (None without a
    # latent layer)
    "latent_prefill",
})

ROUTER_HEALTH_KEYS = frozenset({
    "replicas", "held", "pending", "done", "failed", "steps",
    "failovers", "requeued", "duplicates_dropped", "probes", "hot_swaps",
    "swap_rollbacks", "topology", "kv_handoffs", "handoff_failures",
    "prefix_routing", "prefix_routed", "prefix_ships",
    "prefix_ship_failures", "prefix_index",
    # elastic fleet (PR 17: inference/autoscale.py)
    "crash_loops", "shedding", "shed_rejections", "adapter_affinity",
})

REPLICA_HEALTH_KEYS = frozenset({
    "state", "role", "breaker", "failures", "kills", "swaps",
    "last_error", "assigned",
    # headroom() keys merged for non-quarantined replicas
    "queued", "running", "slots_total", "pages_free", "pages_total",
    "pages_demoted", "demoted",
})


class TestHealthSchema:
    def test_engine_health_exact_keys(self, tiny):
        model, _ = tiny
        eng = ContinuousBatchingEngine(model, **ENGINE_KW)
        got = frozenset(eng.health())
        assert got == ENGINE_HEALTH_KEYS, (
            f"engine health() schema drifted: "
            f"added={sorted(got - ENGINE_HEALTH_KEYS)} "
            f"removed={sorted(ENGINE_HEALTH_KEYS - got)} — dashboards "
            "and the telemetry rate sampler consume these keys; update "
            "docs/observability.md and this pin TOGETHER")

    def test_router_health_exact_keys(self, tiny):
        model, _ = tiny
        router = EngineRouter(
            lambda: ContinuousBatchingEngine(model, **ENGINE_KW),
            replicas=1)
        h = router.health()
        got = frozenset(h)
        assert got == ROUTER_HEALTH_KEYS, (
            f"router health() schema drifted: "
            f"added={sorted(got - ROUTER_HEALTH_KEYS)} "
            f"removed={sorted(ROUTER_HEALTH_KEYS - got)}")
        rep = frozenset(h["replicas"]["r0"])
        assert rep == REPLICA_HEALTH_KEYS, (
            f"per-replica health entry drifted: "
            f"added={sorted(rep - REPLICA_HEALTH_KEYS)} "
            f"removed={sorted(REPLICA_HEALTH_KEYS - rep)}")


# -- the acceptance run ------------------------------------------------------
class TestTracedRun:
    def test_outputs_byte_identical_on_vs_off(self, traced_run):
        _, _, ref, outs, _, _ = traced_run
        for i, (a, b) in enumerate(zip(ref, outs)):
            assert a.shape == b.shape and (a == b).all(), (
                f"telemetry changed request {i}'s greedy output")

    def test_every_retired_request_has_complete_chain(self, traced_run):
        prompts, _, _, _, tel, _ = traced_run
        done = tel.done_traces()
        assert len(done) == len(prompts)
        for tr in done:
            assert tr.state == "done"
            assert tr.complete_chain(), (tr, tr.phases())
            # ordered: submit <= seat <= first token <= retire
            assert tr.t_submit <= tr.t_seat <= tr.t_first <= tr.t_done

    def test_histogram_counts_equal_retired_requests(self, traced_run):
        prompts, _, _, _, tel, _ = traced_run
        reg = tel.registry
        n = len(prompts)
        assert reg.hist["ttft_ms"].count == n
        assert reg.hist["tpot_ms"].count == n
        assert reg.hist["queue_wait_ms"].count == n
        assert reg.hist["e2e_ms"].count == n
        assert reg.counters["requests_done"] == n
        assert reg.hist["block_ms"].count == reg.counters["blocks"] > 0

    def test_chrome_trace_perfetto_loadable(self, traced_run, tmp_path):
        prompts, _, _, _, tel, _ = traced_run
        path = tel.export_chrome_trace(str(tmp_path / "trace.json"))
        with open(path) as f:
            data = json.load(f)            # parseable = loadable
        evs = data["traceEvents"]
        assert isinstance(evs, list) and evs
        for ev in evs:
            assert {"ph", "name", "pid", "tid"} <= set(ev)
        # every request shows the full queue/prefill/decode span chain
        for uid in range(len(prompts)):
            names = {e["name"] for e in evs
                     if e["tid"] == uid and e["ph"] == "X"}
            assert {"queue", "prefill", "decode"} <= names, (uid, names)
            assert any(e["name"] == "retire" for e in evs
                       if e["tid"] == uid)

    def test_tpot_is_not_e2e(self, traced_run):
        _, budgets, _, _, tel, _ = traced_run
        reg = tel.registry
        # per-token time must be well under end-to-end for multi-token
        # budgets (a regression here usually means tpot observed the
        # wrong reference point)
        assert reg.hist["tpot_ms"].percentile(50) < \
            reg.hist["e2e_ms"].percentile(50)

    def test_jsonl_export(self, traced_run, tmp_path):
        _, _, _, _, tel, _ = traced_run
        path = tel.export_jsonl(str(tmp_path / "events.jsonl"))
        with open(path) as f:
            lines = [json.loads(ln) for ln in f]
        assert lines
        assert all("t" in e and "ev" in e for e in lines)
        assert any(e["ev"] == "retire" for e in lines)


# -- lifecycle legs ----------------------------------------------------------
class TestLegs:
    def test_spec_pass_events_carry_accept_counts(self, tiny):
        model, cfg = tiny
        eng = ContinuousBatchingEngine(model, speculate=4,
                                       drafter="ngram", telemetry=True,
                                       **ENGINE_KW)
        rng = np.random.RandomState(5)
        motif = rng.randint(0, cfg.vocab_size, (4,)).astype(np.int64)
        u = eng.add_request(np.tile(motif, 4), max_new_tokens=8)
        eng.drain()
        tr = eng.telemetry.trace("engine", u)
        passes = [a for _, n, a in tr.events if n == "spec_pass"]
        assert passes, tr.phases()
        for a in passes:
            assert {"offered", "accepted", "emitted"} <= set(a)
        # the FIRST token comes from prefill, every later one from a
        # verify pass — so the passes account for n_tokens - 1
        assert sum(a["emitted"] for a in passes) == tr.n_tokens - 1

    def test_demote_restore_leg(self, tiny):
        model, cfg = tiny
        eng = ContinuousBatchingEngine(model, kv_tier="host",
                                       telemetry=True, **ENGINE_KW)
        rng = np.random.RandomState(7)
        p = rng.randint(0, cfg.vocab_size, (10,)).astype(np.int64)
        u = eng.add_request(p, max_new_tokens=6)
        while eng.status(u) != "decode":
            eng.step()
        eng.demote_request(u)
        eng.restore_request(u)
        eng.drain()
        tr = eng.telemetry.trace("engine", u)
        assert tr.complete_chain()
        phases = tr.phases()
        assert phases.index("demote") < phases.index("restore")
        assert eng.telemetry.registry.hist["restore_ms"].count == 1
        # the demoted leg renders as its own span
        d = eng.telemetry.chrome_trace()
        assert any(e["name"] == "demoted" for e in d["traceEvents"])

    def test_disagg_handoff_fleet_counts_and_chains(self, tiny):
        model, cfg = tiny
        router = EngineRouter(
            lambda: ContinuousBatchingEngine(model, **ENGINE_KW),
            topology={"prefill": 1, "decode": 1}, telemetry=True)
        prompts, budgets = stream(cfg, n=3, seed=11)
        uids = [router.add_request(p, max_new_tokens=b)
                for p, b in zip(prompts, budgets)]
        router.drain()
        assert router.kv_handoffs >= 1
        m = router.metrics()
        h = m["fleet"]["histograms"]
        # TTFT observed on prefill workers, TPOT on the decode workers
        # that retire DONE — fleet counts each equal retired requests,
        # and handoff_ms counts every migration
        assert h["ttft_ms"]["count"] == len(prompts)
        assert h["tpot_ms"]["count"] == len(prompts)
        # seat observes queue_wait on the PREFILL engine only — the
        # router's "route" and the decode worker's "import_seat" mark
        # span timestamps without double-counting the wait
        assert h["queue_wait_ms"]["count"] == len(prompts)
        assert h["handoff_ms"]["count"] == router.kv_handoffs
        # fleet counters stay engine-sourced: the router counts
        # deliveries under its own names
        c = m["fleet"]["counters"]
        assert c["requests_done"] == len(prompts)
        assert c["requests_delivered"] == len(prompts)
        src_tel = router._replicas[0].telemetry
        migrated = [t for t in src_tel.done_traces()
                    if t.state == "migrated"]
        assert migrated
        for tr in migrated:
            assert tr.complete_chain()
            assert "kv_export" in tr.phases()
        dst_tel = router._replicas[1].telemetry
        for tr in dst_tel.done_traces():
            if tr.state == "done":
                assert tr.imported() and tr.complete_chain()
        # router-level leg + fleet export round-trips
        rt = router.telemetry.trace("router", uids[0])
        assert "handoff" in rt.phases() and rt.state == "delivered"

    def test_failover_requeue_leg(self, tiny):
        model, cfg = tiny
        router = EngineRouter(
            lambda: ContinuousBatchingEngine(model, **ENGINE_KW),
            replicas=2, quarantine_threshold=3, telemetry=True)
        prompts, budgets = stream(cfg, n=4, seed=13)
        uids = [router.add_request(p, max_new_tokens=b)
                for p, b in zip(prompts, budgets)]
        with failsafe.inject("replica.step", nth=1):
            router.step()
        router.drain()
        assert router.failovers == 1
        assert all(router.status(u) == "done" for u in uids)
        requeued = [router.telemetry.trace("router", u) for u in uids]
        requeued = [t for t in requeued
                    if "requeue" in t.phases()]
        assert requeued, "no router trace recorded the failover leg"
        # the kill itself is in the same timeline (fault hook)
        assert any(e.get("ev") == "fault"
                   and e.get("point") == "replica.step"
                   for e in router.telemetry.log)
        # fleet export merges router + replica sources
        d = chrome_trace([router.telemetry]
                         + [r.telemetry for r in router._replicas])
        pids = {e["pid"] for e in d["traceEvents"]}
        assert len(pids) == 3

    def test_failover_after_first_token_keeps_counts(self, tiny):
        """A request that fails over AFTER its first token must not
        observe TTFT twice: the resumed continuation (folded prompt,
        "resume" marker from submit_resume) keeps its span timestamp
        but skips the histogram — fleet counts stay == retired."""
        model, cfg = tiny
        router = EngineRouter(
            lambda: ContinuousBatchingEngine(model, **ENGINE_KW),
            replicas=2, quarantine_threshold=3, telemetry=True)
        rng = np.random.RandomState(31)
        u = router.add_request(
            rng.randint(0, cfg.vocab_size, (6,)).astype(np.int64),
            max_new_tokens=8)
        r = None
        for _ in range(30):
            router.step()
            rr = router._reqs[u]
            if rr.replica is not None:
                r = router._by_name[rr.replica].engine._requests.get(
                    rr.engine_uid)
                if r is not None and r.out:
                    break
        assert r is not None and r.out, "no token before the kill"
        with failsafe.inject("replica.step", nth=1):
            router.step()
        router.drain()
        assert router.failovers == 1
        assert router.status(u) == "done"
        h = router.metrics()["fleet"]["histograms"]
        assert h["ttft_ms"]["count"] == 1, h["ttft_ms"]
        assert h["tpot_ms"]["count"] == 1, h["tpot_ms"]

    def test_fault_hook_records_engine_faults(self, tiny):
        model, cfg = tiny
        tel = Telemetry()
        eng = ContinuousBatchingEngine(model, telemetry=tel, **ENGINE_KW)
        rng = np.random.RandomState(17)
        u = eng.add_request(
            rng.randint(0, cfg.vocab_size, (6,)).astype(np.int64),
            max_new_tokens=4)
        with failsafe.inject("cb.decode", nth=1):
            eng.drain()
        faults = [e for e in tel.log if e.get("ev") == "fault"]
        assert faults and faults[0]["point"] == "cb.decode"
        tr = tel.trace("engine", u)
        assert tr.state == "failed" and tr.stage == "decode"
        tel.close()                       # detaches the weakref hook


# -- profiler + device attribution -------------------------------------------
class TestProfilerAndProbe:
    def test_traced_two_step_run_produces_parseable_trace(
            self, tiny, tmp_path):
        model, cfg = tiny
        eng = ContinuousBatchingEngine(model, **ENGINE_KW)
        rng = np.random.RandomState(19)
        eng.add_request(
            rng.randint(0, cfg.vocab_size, (6,)).astype(np.int64),
            max_new_tokens=4)
        out_dir = str(tmp_path / "prof")
        prof = profiler.Profiler(
            timer_only=True,              # spans only; no device trace
            on_trace_ready=profiler.export_chrome_tracing(
                out_dir, worker_name="w0"))
        with prof:
            eng.step()
            eng.step()
        # the export_chrome_tracing handler now actually writes a file
        path = f"{out_dir}/w0.json"
        with open(path) as f:
            data = json.load(f)
        names = {e["name"] for e in data["traceEvents"]}
        assert {"cb.prefill_chunk", "cb.decode_step"} & names, names
        for ev in data["traceEvents"]:
            assert ev["dur"] >= 0.0
        eng.drain()

    def test_profiler_sessions_do_not_leak_spans(self, tmp_path):
        """The global span buffer clears at session start — a second
        profiler's export must not contain the first's spans (invisible
        before the export path had a consumer)."""
        with profiler.Profiler(timer_only=True):
            with profiler.RecordEvent("tel_span_one"):
                pass
        p2 = profiler.Profiler(timer_only=True)
        with p2:
            with profiler.RecordEvent("tel_span_two"):
                pass
        path = str(tmp_path / "t.json")
        p2.export(path)
        with open(path) as f:
            names = {e["name"] for e in json.load(f)["traceEvents"]}
        assert "tel_span_two" in names
        assert "tel_span_one" not in names

    def test_span_totals_time_dispatch_and_fetch(self, tiny):
        """The always-on spans are the engine's only timers: with no
        profiler session, span_totals() holds (count, seconds) of every
        decode step's dispatch and fetch."""
        model, cfg = tiny
        eng = ContinuousBatchingEngine(model, **ENGINE_KW)
        rng = np.random.RandomState(23)
        p = rng.randint(0, cfg.vocab_size, (6,)).astype(np.int64)
        before = profiler.span_totals()
        eng.generate_many([p], max_new_tokens=3)
        after = profiler.span_totals()
        assert eng.decode_steps > 0
        for name in ("cb.decode.dispatch", "cb.decode.fetch"):
            n0, s0 = before.get(name, (0, 0.0))
            n1, s1 = after[name]
            assert n1 - n0 == eng.decode_steps
            assert s1 - s0 > 0

    def test_jsonl_streaming(self, tiny, tmp_path):
        model, cfg = tiny
        path = str(tmp_path / "stream.jsonl")
        tel = Telemetry(jsonl_path=path, flush_every=4)
        eng = ContinuousBatchingEngine(model, telemetry=tel, **ENGINE_KW)
        rng = np.random.RandomState(29)
        eng.generate_many(
            [rng.randint(0, cfg.vocab_size, (6,)).astype(np.int64)],
            max_new_tokens=3)
        tel.flush()
        with open(path) as f:
            lines = [json.loads(ln) for ln in f]
        assert any(e["ev"] == "submit" for e in lines)
        assert any(e["ev"] == "retire" for e in lines)


# -- the latent sparse path's names (PR 30): scopes, counters, health keys --
LATENT_SPARSE_NAMES = (
    "sparse_index_scores", "sparse_select", "sparse_attend",
    "window_latent_attend", "sparse.keys_visible", "sparse.keys_attended",
    "sparse.index_keys_scored", "sparse.decode_queries", "row_width",
    "index_width", "latent_prefill", "latent.prefill_live_steps")


@pytest.mark.parametrize("name", LATENT_SPARSE_NAMES)
def test_latent_sparse_names_are_in_the_docs_and_in_the_program(name):
    """Readers under perf/ find device time by these scope names and
    counters by these keys: a rename must fail here, with the docs."""
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    docs = open(os.path.join(root, "docs", "observability.md")).read()
    assert name in docs, f"{name} missing from docs/observability.md"
    code = "".join(open(os.path.join(root, "paddle_tpu", "inference",
                                     f)).read()
                   for f in ("latent.py", "scheduler.py"))
    assert name.split(".")[-1] in code
