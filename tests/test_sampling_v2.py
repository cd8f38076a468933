"""On-device sampling v2 (ISSUE 18): the in-kernel top-K fold, the
counter-based per-request key stream, and the logit-processor chain.

Pins, bottom-up:
  - fold bit-identity: per-request sampled streams identical across
    decode_block {1, 8} x megakernel {off, multi} x tp {1, 2} on the
    int8 engine geometry, and across the in-kernel fold vs the
    materialized arm (the "proc" program under a neutral processor
    chain) — lean cells tier-1, the full cross on the slow lane;
  - batch-composition invariance: a request's stream depends only on
    (seed, position), never on its batchmates — solo == batched, and
    greedy rows inside a mixed batch == the all-greedy engine;
  - resume carries sampling: export_request/submit_resume and
    export_kv_pages/import_kv_pages continue a sampled (and penalized)
    stream byte-identically, counts and all;
  - sampled speculation is honest: speculate=4 sampled output ==
    the unspeculated engine, token for token;
  - seeded chi-squared distribution pins: select_from_topk against its
    numpy mirror, rejection_sample's marginal against the target p;
  - the processor chain: penalties K1 == K8, neutral rows bit-exact
    passthrough, stop-sequence truncation mid-block, JSON-schema
    automaton validity of every emitted token;
  - the jaxpr assert: the sampled whole-step decode program contains
    NO [*, V] intermediate outside the kernel — the [w, V] logits row
    never reaches HBM — while the "proc" program of the same engine
    (the positive control) does.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.inference.router import EngineRouter
from paddle_tpu.inference.scheduler import ContinuousBatchingEngine
from paddle_tpu.inference.sampling import (
    SamplingParams, TokenMaskAutomaton, apply_penalties, fold_keys,
    json_schema_pattern, select_from_topk)
from paddle_tpu.inference.speculative import rejection_sample


# -- geometry ----------------------------------------------------------------
# V=50 is chosen so NO other array dimension equals it (hidden 32,
# inter 48, heads 4/2, hd 8, pages 8, block 8) — the jaxpr walker can
# recognize a vocab-width intermediate by its last axis alone.
V, H = 50, 32
ENGINE_KW = dict(max_len=48, page_size=8, max_batch=2, quant="int8",
                 slot_buckets=(2,))
NEW_TOKENS = 8

# chi-squared inverse CDF at p=0.001 by degrees of freedom — the pins
# are SEEDED (deterministic draws), so these act as regression bounds,
# not flaky statistical gates.
CHI2_999 = {1: 10.83, 2: 13.82, 3: 16.27, 4: 18.47, 5: 20.52, 6: 22.46,
            7: 24.32}


@pytest.fixture(scope="module")
def tiny():
    cfg = LlamaConfig(vocab_size=V, hidden_size=H,
                      intermediate_size=48, num_hidden_layers=1,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=64)
    paddle.seed(7)
    return LlamaForCausalLM(cfg), cfg


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.RandomState(3)
    return [rng.randint(0, V, n).astype(np.int64) for n in (5, 9, 12)]


def _sp(i, **over):
    kw = dict(do_sample=True, temperature=0.8, top_k=6, top_p=0.95,
              seed=100 + i)
    kw.update(over)
    return SamplingParams(**kw)


def _run(model, prompts, specs, **kw):
    eng = ContinuousBatchingEngine(model, **{**ENGINE_KW, **kw})
    uids = [eng.add_request(p, max_new_tokens=NEW_TOKENS, sampling=s)
            for p, s in zip(prompts, specs)]
    eng.drain()
    return [np.asarray(eng.result(u)) for u in uids], eng


@pytest.fixture(scope="module")
def ref_sampled(tiny, prompts):
    """The canonical sampled streams: decode_block=1, megakernel off —
    every other cell must reproduce these bits."""
    model, _ = tiny
    outs, _ = _run(model, prompts, [_sp(i) for i in range(3)],
                   megakernel=False, decode_block=1)
    return outs


@pytest.fixture(scope="module")
def ref_greedy(tiny, prompts):
    model, _ = tiny
    eng = ContinuousBatchingEngine(model, megakernel=False,
                                   **ENGINE_KW)
    return eng.generate_many(prompts, max_new_tokens=NEW_TOKENS)


def _assert_same(ref, outs, tag):
    for i, (a, b) in enumerate(zip(ref, outs)):
        assert a.shape == b.shape and (a == b).all(), (
            f"{tag}: sampled request {i} diverged from the K=1 "
            "unfused reference stream")


# -- fold bit-identity -------------------------------------------------------
class TestFoldBitIdentity:
    def test_k8_opchain(self, tiny, prompts, ref_sampled):
        model, _ = tiny
        outs, _ = _run(model, prompts, [_sp(i) for i in range(3)],
                       megakernel=False, decode_block=8)
        _assert_same(ref_sampled, outs, "off+K8")

    def test_k1_multi(self, tiny, prompts, ref_sampled):
        model, _ = tiny
        outs, _ = _run(model, prompts, [_sp(i) for i in range(3)],
                       megakernel="multi", decode_block=1)
        _assert_same(ref_sampled, outs, "multi+K1")

    def test_k8_multi(self, tiny, prompts, ref_sampled):
        model, _ = tiny
        outs, eng = _run(model, prompts, [_sp(i) for i in range(3)],
                         megakernel="multi", decode_block=8)
        _assert_same(ref_sampled, outs, "multi+K8")
        h = eng.health()
        assert h["sampled_requests"] == 3
        assert h["sample_k"] == 8

    def test_tp2_multi_k8(self, tiny, prompts, ref_sampled):
        model, _ = tiny
        outs, _ = _run(model, prompts, [_sp(i) for i in range(3)],
                       tp=2, megakernel="multi", decode_block=8)
        _assert_same(ref_sampled, outs, "tp2+multi+K8")

    def test_materialized_arm(self, tiny, prompts, ref_sampled):
        # a request that needs processors runs the "proc" program,
        # which keeps the [w, V] logits and selects on the materialized
        # row; under a NEUTRAL chain (no penalties, the always-allow
        # automaton) that is the same survivor set, the same key stream
        # and the same bits as the fold
        model, _ = tiny
        allow = TokenMaskAutomaton.trivial(V)
        specs = [_sp(i, grammar=allow) for i in range(3)]
        assert all(s.needs_processors for s in specs)
        outs, _ = _run(model, prompts, specs,
                       megakernel="multi", decode_block=8)
        _assert_same(ref_sampled, outs, "multi+K8+materialized")

    def test_mixed_greedy_sampled_batch(self, tiny, prompts,
                                        ref_sampled, ref_greedy):
        # greedy rows in a mixed batch cost nothing and change nothing:
        # they reproduce the all-greedy engine while the sampled row
        # reproduces the all-sampled reference
        model, _ = tiny
        specs = [None, _sp(1), None]
        outs, _ = _run(model, prompts, specs, megakernel="multi",
                       decode_block=8)
        assert (outs[0] == ref_greedy[0]).all()
        assert (outs[2] == ref_greedy[2]).all()
        assert (outs[1] == ref_sampled[1]).all()

    def test_solo_equals_batched(self, tiny, prompts, ref_sampled):
        # batch-composition invariance: the key stream is
        # (seed, position) — batchmates, slot order and admission
        # timing are invisible to it
        model, _ = tiny
        outs, _ = _run(model, prompts[2:], [_sp(2)],
                       megakernel="multi", decode_block=8)
        assert (outs[0] == ref_sampled[2]).all()

    @pytest.mark.slow
    def test_crossed_matrix(self, tiny, prompts, ref_sampled):
        # the full acceptance cross: decode_block {1, 8} x megakernel
        # {off, multi} x tp {1, 2}, all on the int8 geometry
        model, _ = tiny
        for mk in (False, "multi"):
            for K in (1, 8):
                for tp in (1, 2):
                    outs, _ = _run(model, prompts,
                                   [_sp(i) for i in range(3)],
                                   megakernel=mk, decode_block=K,
                                   tp=tp)
                    _assert_same(ref_sampled, outs,
                                 f"mk={mk} K={K} tp={tp}")


# -- resume carries sampling -------------------------------------------------
class TestResumeCarriesSampling:
    def test_kv_handoff_continues_stream(self, tiny, prompts,
                                         ref_sampled):
        # disaggregated handoff mid-decode: the page images move, the
        # SamplingParams ride the payload, and the decode-side tail is
        # byte-identical — the counter-based keys make the cut point
        # invisible
        model, _ = tiny
        A = ContinuousBatchingEngine(model, megakernel=False,
                                     decode_block=1, **ENGINE_KW)
        B = ContinuousBatchingEngine(model, megakernel=False,
                                     decode_block=1, **ENGINE_KW)
        ua = A.add_request(prompts[1], max_new_tokens=NEW_TOKENS,
                           sampling=_sp(1))
        while A.status(ua) != "decode":
            A.step()
        for _ in range(3):
            A.step()                      # a few sampled tokens on A
        ub = B.import_kv_pages(A.export_kv_pages(ua))
        A.release_handoff(ua)
        B.drain()
        assert np.array_equal(B.result(ub), ref_sampled[1])

    def test_export_resume_carries_processor_state(self, tiny,
                                                   prompts):
        # failover salvage of a PENALIZED sampled request: the resume
        # spec must carry counts (the folded prompt would otherwise
        # reclassify generated tokens as prompt for penalty purposes)
        # and the params — the resumed tail matches the uninterrupted
        # run bit for bit
        model, _ = tiny
        sp = SamplingParams(do_sample=True, temperature=0.9, seed=7,
                            repetition_penalty=1.3,
                            presence_penalty=0.2,
                            frequency_penalty=0.1)
        kw = dict(ENGINE_KW)
        ref_e = ContinuousBatchingEngine(model, megakernel=False,
                                         decode_block=1, **kw)
        u0 = ref_e.add_request(prompts[0], max_new_tokens=NEW_TOKENS,
                               sampling=sp)
        ref_e.drain()
        ref = np.asarray(ref_e.result(u0))

        A = ContinuousBatchingEngine(model, megakernel=False,
                                     decode_block=1, **kw)
        ua = A.add_request(prompts[0], max_new_tokens=NEW_TOKENS,
                           sampling=sp)
        while not (A.status(ua) == "decode"
                   and A.export_request(ua)["generated"] >= 3):
            A.step()
        spec = A.export_request(ua)
        assert spec["sampling"]["repetition_penalty"] == 1.3
        assert spec["counts"]                 # state, not just params
        B = ContinuousBatchingEngine(model, megakernel=False,
                                     decode_block=1, **kw)
        ub = B.submit_resume(spec)
        B.drain()
        assert np.array_equal(B.result(ub), ref)


# -- sampled speculation -----------------------------------------------------
class TestSpecSampled:
    def test_spec_sampled_byte_identity(self, tiny, prompts,
                                        ref_sampled):
        # sample-and-match acceptance: a speculative engine's sampled
        # stream is the unspeculated stream, token for token — the
        # drafts only change WHEN tokens appear, never WHICH
        model, _ = tiny
        outs, eng = _run(model, prompts, [_sp(i) for i in range(3)],
                         speculate=4)
        _assert_same(ref_sampled, outs, "spec4")
        assert eng.health()["spec_sampled_accept_rate"] is not None


# -- seeded distribution pins ------------------------------------------------
def _chi2(counts, probs):
    n = counts.sum()
    exp = probs * n
    m = exp > 0
    return float(((counts[m] - exp[m]) ** 2 / exp[m]).sum())


class TestDistributionPins:
    def test_select_from_topk_matches_mirror(self):
        # numpy mirror of the device rule (temperature -> top_k ->
        # exclusive-cumsum top_p -> categorical over the survivors);
        # 4000 seeded draws must track the analytic distribution
        N, K = 4000, 8
        row = np.array([2.0, 1.5, 1.2, 1.0, 0.5, 0.2, -0.3, -1.0],
                       np.float32)
        ids = np.array([7, 3, 19, 42, 1, 30, 11, 25], np.int32)
        temp, topk, topp = 0.7, 4, 0.85

        scaled = row.astype(np.float64) / temp
        keep = np.arange(K) < topk
        masked = np.where(keep, scaled, -1e30)
        p = np.exp(masked - masked.max())
        p /= p.sum()
        keep &= (np.cumsum(p) - p) < topp     # exclusive nucleus
        expected = np.where(keep, p, 0.0)
        expected /= expected.sum()
        kept = int(keep.sum())
        assert kept == 3                      # top_p drops the 4th

        keys = fold_keys(np.full(N, 123, np.uint32),
                         np.arange(N, dtype=np.int32))
        toks = select_from_topk(
            jnp.tile(jnp.asarray(row), (N, 1)),
            jnp.tile(jnp.asarray(ids), (N, 1)),
            keys, jnp.ones(N, bool),
            jnp.full(N, temp, jnp.float32),
            jnp.full(N, topk, jnp.int32),
            jnp.full(N, topp, jnp.float32),
            jnp.zeros(N, jnp.float32))
        toks = np.asarray(toks)
        counts = np.array([(toks == ids[j]).sum() for j in range(K)],
                          np.float64)
        assert counts[~keep].sum() == 0       # nothing outside nucleus
        assert _chi2(counts, expected) < CHI2_999[kept - 1]

    def test_select_greedy_rows_ignore_keys(self):
        row = jnp.asarray([[3.0, 2.0, 1.0]], jnp.float32)
        ids = jnp.asarray([[9, 4, 2]], jnp.int32)
        keys = fold_keys(np.array([5], np.uint32),
                         np.array([0], np.int32))
        tok = select_from_topk(row, ids, keys,
                               jnp.zeros(1, bool),
                               jnp.ones(1, jnp.float32),
                               jnp.zeros(1, jnp.int32),
                               jnp.ones(1, jnp.float32),
                               jnp.zeros(1, jnp.float32))
        assert int(tok[0]) == 9               # topi[:, 0], bit-exact

    def test_rejection_sample_marginal_is_p(self):
        # the distribution-preservation pin: for q = delta(draft), the
        # emitted marginal is EXACTLY p and the acceptance probability
        # is p[draft]
        p = np.array([0.05, 0.1, 0.4, 0.15, 0.2, 0.1], np.float32)
        q = np.zeros(6, np.float32)
        d = 2
        q[d] = 1.0
        N = 3000
        keys = fold_keys(np.full(N, 9, np.uint32),
                         np.arange(N, dtype=np.int32))
        acc, toks = jax.vmap(
            lambda k: rejection_sample(p, q, d, k))(keys)
        counts = np.bincount(np.asarray(toks), minlength=6).astype(
            np.float64)
        assert _chi2(counts, p.astype(np.float64)) < CHI2_999[5]
        rate = float(np.asarray(acc).mean())
        assert abs(rate - p[d]) < 0.05        # ~4 sigma at N=3000


# -- the processor chain -----------------------------------------------------
class TestProcessorChain:
    def test_penalties_k1_equals_k8(self, tiny, prompts):
        # the proc path runs K=1 selection host-side and the block
        # rhythm replays it — same counts evolution, same bits
        model, _ = tiny
        sp = SamplingParams(do_sample=True, temperature=0.9, seed=21,
                            repetition_penalty=1.3,
                            presence_penalty=0.2,
                            frequency_penalty=0.1)
        a, _ = _run(model, prompts[:2], [sp, sp],
                    megakernel=False, decode_block=1)
        b, _ = _run(model, prompts[:2], [sp, sp],
                    megakernel=False, decode_block=8)
        _assert_same(a, b, "proc K1 vs K8")

    def test_neutral_penalties_pass_through(self):
        rng = np.random.RandomState(11)
        logits = jnp.asarray(rng.randn(2, 16).astype(np.float32))
        counts = jnp.asarray(rng.randint(0, 3, (2, 16)), jnp.int32)
        out = apply_penalties(logits, counts,
                              jnp.ones(2, jnp.float32),
                              jnp.zeros(2, jnp.float32),
                              jnp.zeros(2, jnp.float32))
        assert (np.asarray(out) == np.asarray(logits)).all()

    def test_stop_sequence_truncates_mid_block(self, tiny, prompts,
                                               ref_greedy):
        # stop at the first greedy bigram: the request retires WITH the
        # stop sequence, and tokens the block computed past it are
        # discarded — exact truncation, decode_block=4
        model, _ = tiny
        plen = len(prompts[0])
        g = np.asarray(ref_greedy[0])[plen:]
        pair = (int(g[2]), int(g[3]))
        j = next(i for i in range(1, len(g))
                 if (int(g[i - 1]), int(g[i])) == pair)
        sp = SamplingParams(stop=(pair,))
        eng = ContinuousBatchingEngine(model, megakernel=False,
                                       decode_block=4, **ENGINE_KW)
        u = eng.add_request(prompts[0], max_new_tokens=NEW_TOKENS,
                            sampling=sp)
        eng.drain()
        out = np.asarray(eng.result(u))
        expect = np.concatenate([prompts[0], g[:j + 1]])
        assert np.array_equal(out, expect)

    def test_json_schema_grammar_walk(self, tiny, prompts):
        # a char-token vocabulary under {"type": "integer"}: every
        # emitted token must be mask-allowed from the authoritative
        # host state, and EOS may only arrive from an accept state —
        # so the decoded text is a complete integer literal
        model, _ = tiny
        token_strs = [""] * V
        for i in range(10):
            token_strs[i] = str(i)
        token_strs[10] = "-"
        eos = 11
        auto = TokenMaskAutomaton.from_json_schema(
            {"type": "integer"}, token_strs, eos_id=eos)
        sp = SamplingParams(do_sample=True, temperature=1.0, seed=5,
                            grammar=auto)
        eng = ContinuousBatchingEngine(model, megakernel=False,
                                       decode_block=1, **ENGINE_KW)
        u = eng.add_request(prompts[0], max_new_tokens=12,
                            eos_token_id=eos, sampling=sp)
        eng.drain()
        gen = np.asarray(eng.result(u))[len(prompts[0]):]
        assert gen.size > 0
        state = 0
        for t in gen:
            assert auto.mask[state, int(t)], (
                f"token {t} not allowed in automaton state {state}")
            if int(t) == eos:
                assert state in auto.accept_states
                break
            state = auto.advance(state, int(t))
        text = "".join(token_strs[int(t)] for t in gen
                       if int(t) != eos)
        if eos in gen:
            import re
            assert re.fullmatch(r"-?[0-9]+", text), text


# -- the jaxpr assert: no [*, V] in the folded sampled program ---------------
def _walk_jaxprs(jaxpr):
    """Yield this jaxpr and every sub-jaxpr (scan/cond/pjit bodies),
    EXCEPT pallas kernel internals — tile-resident [rows, tile] blocks
    inside the kernel are the point of the fold; the claim is that the
    full vocab row never exists in the XLA-level graph (HBM)."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        if "pallas" in eqn.primitive.name:
            continue
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from _walk_jaxprs(sub)


def _sub_jaxprs(v):
    if hasattr(v, "eqns"):                # raw Jaxpr
        yield v
    elif hasattr(v, "jaxpr"):             # ClosedJaxpr
        yield from _sub_jaxprs(v.jaxpr)
    elif isinstance(v, (list, tuple)):
        for x in v:
            yield from _sub_jaxprs(x)


def _vocab_intermediates(jaxpr):
    """Eqn outputs shaped [..., V] that are NOT weight-like ([H, V] is
    the lm head / its dequant): these are materialized logits rows."""
    bad = []
    for jx in _walk_jaxprs(jaxpr):
        for eqn in jx.eqns:
            for ov in eqn.outvars:
                shp = tuple(getattr(ov.aval, "shape", ()))
                if (len(shp) >= 2 and shp[-1] == V
                        and shp[-2] != H):
                    bad.append((eqn.primitive.name, shp))
    return bad


class TestNoMaterializedLogits:
    def test_sampled_decode_program_has_no_vocab_row(self, tiny,
                                                     prompts):
        # capture the REAL argument shapes of the decode-only sampled
        # fused program (donated buffers: shapes must be recorded
        # BEFORE the call), retrace it, and walk the jaxpr
        model, _ = tiny
        eng = ContinuousBatchingEngine(model, megakernel="multi",
                                       decode_block=8, **ENGINE_KW)
        seen = {}
        real = eng._get_fused

        def spy(w, hp, hd, ad, mode):
            fn = real(w, hp, hd, ad, mode)
            if mode != "sampled" or hp or not hd or ad:
                return fn

            def wrapped(*args):
                if "structs" not in seen:
                    # first arg is the weights PYTREE; leaves only
                    seen["structs"] = jax.tree.map(
                        lambda a: jax.ShapeDtypeStruct(
                            np.shape(a), np.result_type(a)), args)
                    seen["w"] = w
                return fn(*args)
            return wrapped

        eng._get_fused = spy
        for i, p in enumerate(prompts[:2]):
            eng.add_request(p, max_new_tokens=NEW_TOKENS,
                            sampling=_sp(i))
        eng.drain()
        assert "structs" in seen, "no decode-only sampled block ran"

        prog = eng._build_cb_fused(seen["w"], False, True, False,
                                   mode="sampled")
        jaxpr = jax.make_jaxpr(prog)(*seen["structs"]).jaxpr
        bad = _vocab_intermediates(jaxpr)
        assert not bad, (
            f"[*, {V}] logits materialized in the folded sampled "
            f"decode program: {bad}")

        # positive control — the walker is not blind: the "proc"
        # program of the same engine (the sampled inputs plus the
        # penalty / grammar state) must show the vocab row it keeps
        w = seen["w"]
        proc_extras = eng._row_params([None] * w, "proc")[6:]
        prog2 = eng._build_cb_fused(w, False, True, False, mode="proc")
        jaxpr2 = jax.make_jaxpr(prog2)(*seen["structs"],
                                       *proc_extras).jaxpr
        assert _vocab_intermediates(jaxpr2), (
            '"proc" program shows no vocab row — walker broken?')


# -- typed gates, deprecation, routing ---------------------------------------
class TestGatesAndRouting:
    @pytest.mark.parametrize("gone", [
        {"do_sample": True}, {"temperature": 0.8}, {"top_k": 4},
        {"top_p": 0.9}, {"seed": 11}, {"sample_fold": False}],
        ids=lambda kw: next(iter(kw)))
    def test_engine_level_sampling_options_refused(self, tiny, gone):
        # sampling is per request (add_request(sampling=...)); the
        # engine-level knobs are gone and fail as any unknown option
        model, _ = tiny
        with pytest.raises(TypeError):
            ContinuousBatchingEngine(model, **gone, **ENGINE_KW)

    def test_top_k_exceeding_sample_k_rejected(self, tiny, prompts):
        model, _ = tiny
        eng = ContinuousBatchingEngine(model, **ENGINE_KW)
        with pytest.raises(ValueError, match="sample_k"):
            eng.add_request(prompts[0], max_new_tokens=4,
                            sampling=_sp(0, top_k=16))

    def test_processors_refuse_speculation(self, tiny, prompts):
        model, _ = tiny
        eng = ContinuousBatchingEngine(model, speculate=4, **ENGINE_KW)
        with pytest.raises(ValueError, match="speculate"):
            eng.add_request(
                prompts[0], max_new_tokens=4,
                sampling=_sp(0, repetition_penalty=1.3))

    def test_grammar_vocab_mismatch_rejected(self, tiny, prompts):
        model, _ = tiny
        eng = ContinuousBatchingEngine(model, **ENGINE_KW)
        wrong = TokenMaskAutomaton.from_pattern(
            json_schema_pattern({"type": "boolean"}),
            ["true", "false", ""], eos_id=2)
        with pytest.raises(ValueError, match="vocab"):
            eng.add_request(
                prompts[0], max_new_tokens=4,
                sampling=SamplingParams(do_sample=True,
                                        temperature=1.0,
                                        grammar=wrong))

    def test_router_carries_sampling(self, tiny, prompts,
                                     ref_sampled):
        # the router's spec path: a to_spec() dict rides add_request ->
        # replica submit_resume and the replica's stream matches the
        # direct-engine reference
        model, _ = tiny

        def factory():
            return ContinuousBatchingEngine(
                model, megakernel=False, decode_block=1, **ENGINE_KW)

        router = EngineRouter(factory, replicas=1)
        u = router.add_request(prompts[0], NEW_TOKENS,
                               sampling=_sp(0).to_spec())
        router.drain()
        assert np.array_equal(router.result(u), ref_sampled[0])
