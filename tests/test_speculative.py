"""Speculative decoding (ISSUE 7): drafters, one-pass ragged
verification with accept/reject inside the device scan carries, adaptive
draft length, and the multi-tenant admission layer.

The load-bearing contract: GREEDY spec-decode output is BYTE-IDENTICAL
to the non-speculative engine — acceptance under greedy is deterministic
(the verify pass's logits rows are bit-equal to sequential decode steps
on the interpret path), asserted here across GQA, int8, and
decode_block in {1, 4, 8} like PR 6 did for the megakernel.

Tier-1 additions are lean (the suite is 870s-timeout-bound); the wide
fault/cancel/deadline soak and the acceptance-rate sweep are slow-marked.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import failsafe
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.inference.scheduler import (ContinuousBatchingEngine,
                                            PrefixCache)
from paddle_tpu.inference.speculative import (Drafter, ModelDrafter,
                                              NGramDrafter,
                                              PrefixCacheDrafter,
                                              resolve_drafter)
from paddle_tpu.ops.pallas.paged_attention import (
    paged_attention, spec_verify_attention)


@pytest.fixture(scope="module")
def gqa_tiny():
    # GQA (4 q heads over 2 kv heads) is the verify kernel's hard
    # layout; 2 layers keeps compiles cheap while crossing a layer
    # boundary
    paddle.seed(7)
    cfg = LlamaConfig.tiny(num_key_value_heads=2, num_hidden_layers=2)
    return LlamaForCausalLM(cfg), cfg


def mk(model, **kw):
    kw.setdefault("max_len", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_batch", 4)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("slot_buckets", (4,))   # one compiled width per engine
    return ContinuousBatchingEngine(model, **kw)


def spec_prompts(cfg, seed=0):
    """Ragged mix with a repetitive-suffix prompt (n-gram draftable), a
    short random one, and a prefix-sharing pair."""
    rng = np.random.RandomState(seed)
    motif = rng.randint(0, cfg.vocab_size, (4,))
    return [np.tile(motif, 5).astype(np.int64)[:18],
            rng.randint(0, cfg.vocab_size, (7,)).astype(np.int64),
            np.tile(motif, 4).astype(np.int64)[:13]]


def assert_no_leak(eng):
    held = 0 if eng._prefix is None else len(eng._prefix)
    assert eng.allocator.available == eng.allocator.n_pages - held


@pytest.fixture(scope="module")
def ref_outs(gqa_tiny):
    model, cfg = gqa_tiny
    eng = mk(model)
    outs = eng.generate_many(spec_prompts(cfg), max_new_tokens=14)
    assert_no_leak(eng)
    return outs


class TestDrafters:
    def test_ngram_repetition(self):
        d = NGramDrafter(n=3)
        ctx = np.array([5, 6, 7, 8, 5, 6, 7, 8, 5, 6], np.int64)
        np.testing.assert_array_equal(d.propose(ctx, 3), [7, 8, 5])
        # no earlier occurrence of any trailing n-gram -> empty
        assert d.propose(np.array([1, 2, 3, 4], np.int64), 3).size == 0
        assert d.propose(np.array([9], np.int64), 3).size == 0

    def test_ngram_prefers_longest_match(self):
        # trailing [2, 3] occurs earlier (continuation 4); trailing [3]
        # alone also occurs with a different continuation — the longer
        # pattern must win
        d = NGramDrafter(n=3)
        ctx = np.array([2, 3, 4, 3, 9, 2, 3], np.int64)
        np.testing.assert_array_equal(d.propose(ctx, 1), [4])

    def test_prefix_cache_continuation(self):
        cache = PrefixCache(page_size=4)

        class _Alloc:
            def share(self, p):
                return p

            def refcount(self, p):
                return 2

        a = _Alloc()
        seq = np.arange(100, 112, dtype=np.int64)       # 3 full pages
        key = ()
        for j, page in enumerate((0, 1, 2)):
            key = cache.insert(key, seq[j * 4:(j + 1) * 4], page, a)
        # mid-page context: the cached chain completes the page and
        # descends into the next one
        np.testing.assert_array_equal(
            cache.continuation(seq[:6], 4), seq[6:10])
        # full-page context walks straight down the chain
        np.testing.assert_array_equal(
            cache.continuation(seq[:4], 8), seq[4:12])
        # divergent context -> empty
        assert cache.continuation(
            np.array([1, 2, 3, 4, 5], np.int64), 4).size == 0
        d = PrefixCacheDrafter(cache)
        assert d.propose(seq[:6], 2).size == 2

    def test_model_drafter_matches_greedy(self, gqa_tiny):
        model, cfg = gqa_tiny
        rng = np.random.RandomState(3)
        ctx = rng.randint(0, cfg.vocab_size, (9,)).astype(np.int64)
        d = ModelDrafter(model, bucket=16)
        prop = d.propose(ctx, 2)
        assert prop.shape == (2,)
        # the drafter's first proposal IS the model's greedy next token
        from paddle_tpu.tensor.tensor import Tensor
        pad = np.zeros((1, 16), np.int64)
        pad[0, :ctx.size] = ctx
        logits = model(Tensor(pad)).data
        assert int(prop[0]) == int(np.argmax(
            np.asarray(logits)[0, ctx.size - 1]))

    def test_resolve(self):
        assert isinstance(resolve_drafter("ngram", None), NGramDrafter)
        with pytest.raises(ValueError, match="prefix_cache"):
            resolve_drafter("prefix", None)
        with pytest.raises(ValueError, match="drafter"):
            resolve_drafter("turbo", None)


class TestSpecByteIdentity:
    @pytest.mark.parametrize("width,db", [(4, 1), (4, 4), (4, 8),
                                          (2, 1), (8, 1)])
    def test_greedy_identity_across_decode_blocks(self, gqa_tiny,
                                                  ref_outs, width, db):
        # THE acceptance contract: spec output == non-spec output, byte
        # for byte, at decode_block 1 (one verify pass per dispatch), 4
        # and 8 (multi-pass blocks with optimistic draft slices), and at
        # the narrowest and a wide verify width; parametrized so each
        # compile stays inside the per-test budget
        model, cfg = gqa_tiny
        prompts = spec_prompts(cfg)
        eng = mk(model, speculate=width, decode_block=db)
        outs = eng.generate_many(prompts, max_new_tokens=14)
        for i, (a, b) in enumerate(zip(ref_outs, outs)):
            np.testing.assert_array_equal(
                a, b, err_msg=f"speculate={width} diverged at "
                f"decode_block={db} request {i}")
        h = eng.health()
        assert h["spec_passes"] > 0
        assert h["spec_emitted"] >= h["spec_passes"]
        assert_no_leak(eng)

    def test_greedy_identity_int8(self, gqa_tiny):
        # int8 x GQA at decode_block=1 with ONE short request (the
        # multi-pass decode_block sweep is the test above): int8
        # interpret matmuls dominate, and two engine compiles already
        # sit near the 15s per-test budget — keep the timed region to
        # the compiles plus a handful of verify passes
        model, cfg = gqa_tiny
        prompts = spec_prompts(cfg, seed=1)[:1]
        ref = mk(model, quant="int8").generate_many(prompts,
                                                    max_new_tokens=8)
        eng = mk(model, quant="int8", speculate=4)
        outs = eng.generate_many(prompts, max_new_tokens=8)
        for i, (a, b) in enumerate(zip(ref, outs)):
            np.testing.assert_array_equal(
                a, b, err_msg=f"int8 spec diverged at request {i}")
        assert eng.health()["spec_accept_rate"] > 0
        assert_no_leak(eng)

    def test_eos_mid_pass_matches(self, gqa_tiny, ref_outs):
        """A token that becomes EOS mid-verify-pass must retire exactly
        where the per-step engine would."""
        model, cfg = gqa_tiny
        prompts = spec_prompts(cfg)
        # an eos discovered from the free-running reference output
        eos = int(ref_outs[0][prompts[0].size + 3])
        ref = mk(model).generate_many(prompts, max_new_tokens=14,
                                      eos_token_id=eos)
        eng = mk(model, speculate=4)
        outs = eng.generate_many(prompts, max_new_tokens=14,
                                 eos_token_id=eos)
        for a, b in zip(ref, outs):
            np.testing.assert_array_equal(a, b)
        assert_no_leak(eng)

    def test_emits_more_than_one_token_per_pass(self, gqa_tiny):
        # the perf claim in miniature: on a repetitive suffix the n-gram
        # drafter's acceptances push tokens/pass above 1
        model, cfg = gqa_tiny
        rng = np.random.RandomState(11)
        motif = rng.randint(0, cfg.vocab_size, (4,))
        eng = mk(model, speculate=4)
        eng.generate_many([np.tile(motif, 6).astype(np.int64)[:22]],
                          max_new_tokens=24)
        h = eng.health()
        assert h["spec_tokens_per_pass"] > 1.0, h


class TestVerifyKernel:
    def test_verify_rows_match_sequential_decode(self):
        """spec_verify_attention row j == the decode kernel fed token j
        sequentially — bit-identical on the interpret path (the basis of
        the greedy byte-identity contract)."""
        rng = np.random.RandomState(0)
        b, h, hkv, d, p, npg, mp, K = 3, 4, 2, 16, 8, 12, 4, 4
        kp = jnp.asarray(rng.randn(npg, p, hkv, d).astype(np.float32))
        vp = jnp.asarray(rng.randn(npg, p, hkv, d).astype(np.float32))
        table = jnp.asarray(rng.permutation(npg)[:b * mp]
                            .reshape(b, mp).astype(np.int32))
        lens = np.array([5, 9, 13], np.int32)
        q = jnp.asarray(rng.randn(b, K, h, d).astype(np.float32))
        seq = jnp.stack([paged_attention(q[:, j], kp, vp, table,
                                         jnp.asarray(lens + j + 1),
                                         interpret=True)
                         for j in range(K)], axis=1)
        ver = spec_verify_attention(q, kp, vp, table, jnp.asarray(lens),
                                    interpret=True)
        np.testing.assert_array_equal(np.asarray(seq), np.asarray(ver))

    def test_verify_entry_under_outer_jit(self):
        """The PR 5/6 trap class: interpret-mode pallas_call re-
        discharges its jaxpr at OUTER-jit lowering, outside the
        enable_x64(False) window — a weak int literal anywhere in the
        kernel or its index maps re-canonicalizes to i64 and MLIR
        verification fails. The verify entry must lower clean."""
        rng = np.random.RandomState(1)
        b, h, hkv, d, p, npg, mp, K = 2, 4, 2, 16, 8, 8, 3, 3
        kp = jnp.asarray(rng.randn(npg, p, hkv, d).astype(np.float32))
        vp = jnp.asarray(rng.randn(npg, p, hkv, d).astype(np.float32))
        table = jnp.asarray(rng.randint(0, npg, (b, mp)).astype(np.int32))
        lens = jnp.asarray(np.array([4, 10], np.int32))
        q = jnp.asarray(rng.randn(b, K, h, d).astype(np.float32))

        @jax.jit
        def outer(q, kp, vp, table, lens):
            out = spec_verify_attention(q, kp, vp, table, lens,
                                        interpret=True)
            return out * 2.0           # make the jit non-trivial

        direct = spec_verify_attention(q, kp, vp, table, lens,
                                       interpret=True)
        np.testing.assert_allclose(np.asarray(outer(q, kp, vp, table,
                                                    lens)),
                                   2 * np.asarray(direct), rtol=0,
                                   atol=0)


class _OracleDrafter(Drafter):
    """Test drafter that knows the reference outputs: perfect drafts for
    any context that is a prefix of a known row."""

    name = "oracle"

    def __init__(self, rows):
        self.rows = [np.asarray(r) for r in rows]

    def propose(self, ctx, k):
        ctx = np.asarray(ctx)
        for row in self.rows:
            if row.size > ctx.size and (row[:ctx.size] == ctx).all():
                return row[ctx.size:ctx.size + k]
        return np.empty((0,), np.int64)


class _WrongDrafter(Drafter):
    """Always proposes a fixed (wrong) token."""

    name = "wrong"

    def __init__(self, token):
        self.token = int(token)

    def propose(self, ctx, k):
        return np.full(k, self.token, np.int64)


class TestAdaptiveK:
    def test_oracle_full_acceptance(self, gqa_tiny, ref_outs):
        model, cfg = gqa_tiny
        prompts = spec_prompts(cfg)
        eng = mk(model, speculate=4, drafter=_OracleDrafter(ref_outs))
        outs = eng.generate_many(prompts, max_new_tokens=14)
        for a, b in zip(ref_outs, outs):
            np.testing.assert_array_equal(a, b)
        h = eng.health()
        assert h["spec_accept_rate"] == 1.0, h
        # perfect drafts keep every request at the max draft length
        assert all(r.draft_k == 3 for r in eng._requests.values())

    def test_wrong_drafter_shrinks_draft_k(self, gqa_tiny, ref_outs):
        model, cfg = gqa_tiny
        prompts = spec_prompts(cfg)
        # a token none of the reference outputs ever emit: always rejects
        emitted = set(np.concatenate(ref_outs).tolist())
        bad = next(t for t in range(cfg.vocab_size) if t not in emitted)
        eng = mk(model, speculate=8, drafter=_WrongDrafter(bad))
        outs = eng.generate_many(spec_prompts(cfg), max_new_tokens=14)
        for a, b in zip(ref_outs, outs):
            np.testing.assert_array_equal(a, b)   # still byte-identical
        h = eng.health()
        assert h["spec_accept_rate"] == 0.0
        # zero-accept passes halve draft_k down to the floor of 1
        assert all(r.draft_k == 1 for r in eng._requests.values())

    def test_short_draft_k_stays_aligned_multi_pass(self, gqa_tiny,
                                                    ref_outs):
        """decode_block>1 with draft_k < T-1: the per-pass continuation
        slices must stride (want+1), so a perfect drafter keeps FULL
        acceptance in every pass — a T-stride would misalign passes
        1..K-1 even under perfect drafting."""
        model, cfg = gqa_tiny
        prompts = spec_prompts(cfg)
        eng = mk(model, speculate=8, decode_block=4,
                 drafter=_OracleDrafter(ref_outs), spec_adaptive=False)
        uids = [eng.add_request(p, max_new_tokens=14) for p in prompts]
        for u in uids:
            eng._requests[u].draft_k = 2
        eng.drain()
        for u, ref in zip(uids, ref_outs):
            np.testing.assert_array_equal(eng.result(u), ref)
        assert eng.health()["spec_accept_rate"] == 1.0, eng.health()

    def test_broken_drafter_degrades_not_fails(self, gqa_tiny, ref_outs):
        class _Boom(Drafter):
            name = "boom"

            def propose(self, ctx, k):
                raise RuntimeError("drafter crashed")

        model, cfg = gqa_tiny
        eng = mk(model, speculate=4, drafter=_Boom())
        outs = eng.generate_many(spec_prompts(cfg), max_new_tokens=14)
        for a, b in zip(ref_outs, outs):
            np.testing.assert_array_equal(a, b)
        assert eng.draft_errors > 0
        assert eng.health()["spec_accept_rate"] == 0.0


class TestSpecFaults:
    def test_draft_fault_retires_one_request(self, gqa_tiny):
        model, cfg = gqa_tiny
        eng = mk(model, speculate=4)
        rng = np.random.RandomState(5)
        with failsafe.inject("cb.draft", nth=1):
            lone = eng.add_request(
                rng.randint(0, cfg.vocab_size, (9,)).astype(np.int64),
                max_new_tokens=8)
            eng.drain()
        assert eng.status(lone) == "failed"
        assert eng.failures()[lone].stage == "draft"
        assert_no_leak(eng)
        # the engine keeps serving afterwards
        ok = eng.add_request(
            rng.randint(0, cfg.vocab_size, (5,)).astype(np.int64),
            max_new_tokens=4)
        eng.drain()
        assert eng.status(ok) == "done"

    def test_verify_fault_stage_decode(self, gqa_tiny):
        model, cfg = gqa_tiny
        eng = mk(model, speculate=4)
        rng = np.random.RandomState(6)
        with failsafe.inject("cb.verify", nth=1):
            lone = eng.add_request(
                rng.randint(0, cfg.vocab_size, (7,)).astype(np.int64),
                max_new_tokens=8)
            eng.drain()
        assert eng.failures()[lone].stage == "decode"
        assert_no_leak(eng)


class TestTenants:
    def test_priority_preempts_and_victim_output_intact(self, gqa_tiny):
        model, cfg = gqa_tiny
        rng = np.random.RandomState(9)
        p1 = rng.randint(0, cfg.vocab_size, (8,)).astype(np.int64)
        p2 = rng.randint(0, cfg.vocab_size, (9,)).astype(np.int64)
        ref = mk(model, max_batch=1).generate_many(
            [p1], max_new_tokens=24)[0]
        eng = mk(model, max_batch=1,
                 tenants={"gold": {"priority": 5},
                          "bulk": {"share": 1.0}})
        a = eng.add_request(p1, max_new_tokens=24, tenant="bulk")
        for _ in range(4):
            eng.step()
        b = eng.add_request(p2, max_new_tokens=4, tenant="gold")
        eng.drain()
        assert eng.preemptions == 1
        assert eng.status(a) == "done" and eng.status(b) == "done"
        # the victim's folded-and-resumed output is byte-identical to an
        # uninterrupted run
        np.testing.assert_array_equal(eng.result(a), ref)
        assert_no_leak(eng)

    def test_equal_priority_never_preempts(self, gqa_tiny):
        model, cfg = gqa_tiny
        rng = np.random.RandomState(10)
        eng = mk(model, max_batch=1)
        a = eng.add_request(
            rng.randint(0, cfg.vocab_size, (6,)).astype(np.int64),
            max_new_tokens=6)
        eng.step()
        eng.add_request(
            rng.randint(0, cfg.vocab_size, (6,)).astype(np.int64),
            max_new_tokens=4)
        eng.drain()
        assert eng.preemptions == 0
        assert eng.status(a) == "done"

    def test_fair_share_orders_admission(self, gqa_tiny):
        """Single slot, equal priority: stride scheduling by virtual
        time — the share-2 tenant gets two admissions for tenant a's
        one after a's first request charges its tokens."""
        model, cfg = gqa_tiny
        rng = np.random.RandomState(11)
        eng = mk(model, max_batch=1,
                 tenants={"a": {"share": 1.0}, "b": {"share": 2.0}})
        order = []
        uids = {}
        for name, tenant in (("a1", "a"), ("a2", "a"),
                             ("b1", "b"), ("b2", "b")):
            uids[name] = eng.add_request(
                rng.randint(0, cfg.vocab_size, (5,)).astype(np.int64),
                max_new_tokens=6, tenant=tenant)
        seen = set()
        while eng.step():
            for name, u in uids.items():
                if name not in seen and eng.status(u) != "queued":
                    order.append(name)
                    seen.add(name)
        # ties break by uid (a1 first); then vt steers: a charged 6
        # tokens at share 1 (vt 6), b runs twice (vt 3 then 6), a2 last
        assert order == ["a1", "b1", "b2", "a2"], order

    def test_health_reports_tenants(self, gqa_tiny):
        model, cfg = gqa_tiny
        eng = mk(model, tenants={"gold": {"share": 2.0, "priority": 1}})
        rng = np.random.RandomState(12)
        eng.generate_many([rng.randint(0, cfg.vocab_size, (5,))
                           .astype(np.int64)], max_new_tokens=4)
        h = eng.health()
        assert "default" in h["tenants"]
        assert h["tenants"]["default"]["tokens"] == 4
        assert h["tenants"]["gold"]["share"] == 2.0
        assert h["preemptions"] == 0


@pytest.mark.slow
class TestSpecSoak:
    def test_outcome_parity_under_faults_cancel_deadline(self, gqa_tiny):
        """Spec vs non-spec on a seeded ragged stream with TTLs and a
        cancel: identical completion/failure OUTCOME sets and
        byte-identical survivor outputs (fault counts differ per mode —
        TTLs tick verify passes — so only pass-deterministic knobs ride
        this soak)."""
        model, cfg = gqa_tiny
        rng = np.random.RandomState(42)
        lens = rng.randint(3, 18, 12)
        prompts = [rng.randint(0, cfg.vocab_size, (int(t),))
                   .astype(np.int64) for t in lens]
        budgets = [int(b) for b in rng.randint(3, 12, 12)]
        results = {}
        for spec in (0, 4):
            eng = mk(model, speculate=spec or None, decode_block=4)
            uids = [eng.add_request(p, max_new_tokens=b)
                    for p, b in zip(prompts, budgets)]
            for _ in range(2):
                eng.step()
            eng.cancel(uids[3])
            eng.drain()
            outs = {}
            for i, u in enumerate(uids):
                if u not in eng.failures():
                    outs[i] = eng.result(u)
            results[spec] = (outs, set(eng.failures()))
            assert_no_leak(eng)
        outs0, fails0 = results[0]
        outs4, fails4 = results[4]
        assert set(outs0) == set(outs4)
        for i in outs0:
            np.testing.assert_array_equal(
                outs0[i], outs4[i],
                err_msg=f"request {i} diverged spec vs non-spec")

    def test_acceptance_rate_sweep(self, gqa_tiny):
        """Repetitive workload: acceptance should not degrade as the
        verify width grows, and tokens/pass should exceed 1.3 by K=8
        (pinned deterministically)."""
        model, cfg = gqa_tiny
        rng = np.random.RandomState(13)
        motif = rng.randint(0, cfg.vocab_size, (4,))
        prompts = [np.tile(motif, 6).astype(np.int64)[:20 + i]
                   for i in range(3)]
        tps = {}
        for K in (2, 4, 8):
            eng = mk(model, speculate=K)
            eng.generate_many(prompts, max_new_tokens=24)
            tps[K] = eng.health()["spec_tokens_per_pass"]
        assert tps[8] > 1.3, tps
        assert tps[8] >= tps[2] - 0.2, tps

    def test_spec_with_prefix_drafter(self, gqa_tiny):
        """The prefix-cache-seeded drafter pays on REPLAYED traffic:
        request A's prompt is a previous greedy generation (prompt +
        continuation, e.g. a conversation turn resubmitted), request B
        arrives with just the original prompt — B's greedy continuation
        IS the cached chain's suffix, so the cache-walked drafts accept."""
        model, cfg = gqa_tiny
        rng = np.random.RandomState(14)
        seedp = rng.randint(0, cfg.vocab_size, (10,)).astype(np.int64)
        full = mk(model).generate_many([seedp], max_new_tokens=14)[0]
        assert full.size == 24          # 3 full pages at page_size 8
        eng = mk(model, speculate=4, drafter="prefix")
        uA = eng.add_request(full.copy(), max_new_tokens=4)
        eng.drain()                     # A publishes full's pages
        uB = eng.add_request(seedp.copy(), max_new_tokens=8)
        eng.drain()
        # B's output must match the original greedy continuation AND
        # the cache-seeded drafts must have accepted (B's context is a
        # prefix of the cached chain, whose suffix is B's own greedy
        # future by determinism)
        np.testing.assert_array_equal(eng.result(uB), full[:18])
        assert eng.spec_accepted_total > 0
        assert eng.status(uA) == "done"
        assert_no_leak(eng)
