"""Whole-step decode megakernel v2 (ISSUE 12): host-free decode blocks
that compose with speculation and tensor parallelism.

Pins, at kernel level and engine level:
  - the HEAD fold: final norm + lm_head vocab tiles + running argmax in
    the same invocation, tok/logits BIT-identical to the op-chain head
    (including jnp.argmax's first-max-wins tie rule);
  - the tq>1 verify variant: substituted block contents + the shared
    ragged causal mask == the unfused scatter-then-attend path;
  - the per-shard TP segments: qkv/tail/down compose to the full walk;
  - engine byte-identity: greedy outputs across unfused vs "layer" vs
    "multi" (whole-step) x decode_block {1,8} x speculate {off,4}
    x tp {1,2} on GQA int8 geometry — lean cells tier-1, the crossed
    matrix on the slow lane;
  - kill-at-block-boundary fault parity with the megakernel on;
  - the deleted speculate/tp rejection gates stay deleted (regression).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.inference.sampling import SamplingParams
from paddle_tpu.inference.scheduler import ContinuousBatchingEngine
from paddle_tpu.inference.serving import _mm, _rms
from paddle_tpu.ops.pallas.decode_megakernel import (
    decode_megakernel, pack_decode_layer, pack_lm_head, stack_packed)


# -- kernel-level fixtures ---------------------------------------------------
@pytest.fixture(scope="module")
def kstate():
    rng = np.random.RandomState(0)
    b, nh, nh_kv, hd, H, F, V, p, mp = 2, 4, 2, 8, 32, 48, 50, 8, 4
    n_pages = 8

    def w(k, n):
        return jnp.asarray(rng.randn(k, n).astype(np.float32) * 0.1)

    ws = dict(wq=w(H, nh * hd), wk=w(H, nh_kv * hd), wv=w(H, nh_kv * hd),
              wo=w(nh * hd, H), wg=w(H, F), wu=w(H, F), wd=w(F, H),
              ln1=jnp.asarray(rng.rand(H).astype(np.float32) + 0.5),
              ln2=jnp.asarray(rng.rand(H).astype(np.float32) + 0.5))
    head = w(H, V)
    norm = jnp.asarray(rng.rand(H).astype(np.float32) + 0.5)
    kpg = jnp.asarray(rng.randn(n_pages, p, nh_kv, hd).astype(np.float32))
    vpg = jnp.asarray(rng.randn(n_pages, p, nh_kv, hd).astype(np.float32))
    tbl = jnp.asarray(rng.choice(n_pages, (b, mp),
                                 replace=False).astype(np.int32))
    return dict(rng=rng, b=b, nh=nh, nh_kv=nh_kv, hd=hd, H=H, F=F, V=V,
                p=p, mp=mp, n_pages=n_pages, ws=ws, head=head, norm=norm,
                kpg=kpg, vpg=vpg, tbl=tbl,
                lens=jnp.asarray(np.array([5, 11], np.int32)),
                act=jnp.ones(b, jnp.int32), eps=1e-5,
                mk=pack_decode_layer(ws),
                hp=pack_lm_head(head, norm))


class TestWholeStepKernel:
    def _inputs(self, st, rows=None):
        rng = st["rng"]
        b = rows or st["b"]
        h = jnp.asarray(rng.randn(b, st["H"]).astype(np.float32))
        cos = jnp.asarray(rng.randn(b, st["hd"] // 2).astype(np.float32))
        sin = jnp.asarray(rng.randn(b, st["hd"] // 2).astype(np.float32))
        return h, cos, sin

    def _kw(self, st):
        return dict(nh=st["nh"], nh_kv=st["nh_kv"], hd=st["hd"],
                    eps=st["eps"], interpret=True)

    def test_head_fold_bitwise(self, kstate):
        st = kstate
        h, cos, sin = self._inputs(st)
        args = (h, st["mk"], st["kpg"], st["vpg"], st["tbl"], st["lens"],
                st["act"], cos, sin)
        ho, kn, vn = decode_megakernel(*args, **self._kw(st))
        ho2, kn2, vn2, tok, maxv, logits = decode_megakernel(
            *args, head=st["hp"], head_v=st["V"], **self._kw(st))
        # the head fold must not perturb the layer walk
        assert (ho == ho2).all() and (kn == kn2).all() and \
            (vn == vn2).all()
        ref = _mm(_rms(ho[:, None], st["norm"], st["eps"]),
                  st["head"], True)[:, 0]
        assert (np.asarray(logits) == np.asarray(ref)).all()
        assert (np.asarray(tok) == np.asarray(jnp.argmax(ref, -1))).all()
        assert (np.asarray(maxv) == np.asarray(ref).max(-1)).all()

    def test_head_argmax_tie_rule(self, kstate):
        # duplicate head columns produce EXACT logit ties; the running
        # argmax must keep the first index, like jnp.argmax
        st = kstate
        head = np.asarray(st["head"]).copy()
        head[:, 17] = head[:, 3]          # tie across tiles? V=50 < 512:
        head[:, 9] = head[:, 3]           # same tile — both directions
        head = jnp.asarray(head)
        hp = pack_lm_head(head, st["norm"])
        h, cos, sin = self._inputs(st)
        out = decode_megakernel(
            h, st["mk"], st["kpg"], st["vpg"], st["tbl"], st["lens"],
            st["act"], cos, sin, head=hp, head_v=st["V"], **self._kw(st))
        ho, kn, vn, tok, maxv, logits = out
        ref = jnp.argmax(logits, -1)
        assert (np.asarray(tok) == np.asarray(ref)).all()

    def test_segments_match_full(self, kstate):
        st = kstate
        h, cos, sin = self._inputs(st)
        kw = self._kw(st)
        ho, kn, vn, tok, maxv, logits = decode_megakernel(
            h, st["mk"], st["kpg"], st["vpg"], st["tbl"], st["lens"],
            st["act"], cos, sin, head=st["hp"], head_v=st["V"], **kw)
        attn, kn2, vn2 = decode_megakernel(
            h, st["mk"], st["kpg"], st["vpg"], st["tbl"], st["lens"],
            st["act"], cos, sin, seg="qkv", **kw)
        assert (kn2 == kn).all() and (vn2 == vn).all()
        h_mid, act = decode_megakernel(h, st["mk"], seg="tail",
                                       attn_in=attn, mlp_v=st["F"], **kw)
        ho2, tok2, maxv2, logits2 = decode_megakernel(
            h_mid, st["mk"], seg="down", act_in=act, head=st["hp"],
            head_v=st["V"], **kw)
        assert (ho2 == ho).all()
        assert (tok2 == tok).all() and (logits2 == logits).all()

    def test_tq_verify_matches_scatter_then_attend(self, kstate):
        # the spec-verify contract at kernel level: substitute-in-block
        # under the write mask == write-gated scatter then the ragged
        # verify kernel, bit for bit — INCLUDING an ungated (rejected-
        # budget) feed row reading the pool's stale bytes. Both sides
        # under jit (the engine's context; eager XLA fuses rope
        # differently).
        from paddle_tpu.ops.pallas.paged_attention import \
            spec_verify_attention
        st = kstate
        b, T, hd, H, p = st["b"], 3, st["hd"], st["H"], st["p"]
        R = b * T
        nh, nh_kv = st["nh"], st["nh_kv"]
        n_pages = st["n_pages"]
        ws, lens, tbl, act = st["ws"], st["lens"], st["tbl"], st["act"]
        eps = st["eps"]
        h, cos, sin = self._inputs(st, rows=R)
        wm = jnp.asarray(np.array([1, 1, 0, 1, 1, 1], np.int32))

        @jax.jit
        def ref(hT, kpg, vpg):
            h3 = hT.reshape(b, T, H)
            x = _rms(h3, ws["ln1"], eps)
            q = _mm(x, ws["wq"], True).reshape(b, T, -1, hd)
            k = _mm(x, ws["wk"], True).reshape(b, T, -1, hd)
            v = _mm(x, ws["wv"], True).reshape(b, T, -1, hd)
            c = cos.reshape(b, T, 1, hd // 2)
            s = sin.reshape(b, T, 1, hd // 2)
            d2 = hd // 2

            def rope(x_):
                x1, x2 = x_[..., :d2], x_[..., d2:]
                return jnp.concatenate(
                    [x1 * c - x2 * s, x2 * c + x1 * s], -1)

            q, k = rope(q), rope(k)
            pos = lens[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
            slots = tbl[jnp.arange(b)[:, None], pos // p] * p + pos % p
            slots = jnp.where(wm.reshape(b, T) > 0, slots,
                              jnp.int32(n_pages * p))
            kp2 = kpg.reshape(-1, nh_kv, hd).at[slots].set(
                k, mode="drop").reshape(n_pages, p, nh_kv, hd)
            vp2 = vpg.reshape(-1, nh_kv, hd).at[slots].set(
                v, mode="drop").reshape(n_pages, p, nh_kv, hd)
            attn = spec_verify_attention(q, kp2, vp2, tbl, lens,
                                         active=act, interpret=True)
            o = _mm(attn.reshape(b, T, -1), ws["wo"], True)
            h2 = h3 + o
            x2 = _rms(h2, ws["ln2"], eps)
            g_ = _mm(x2, ws["wg"], True)
            u_ = _mm(x2, ws["wu"], True)
            a_ = jax.nn.silu(g_.astype(jnp.float32)).astype(
                g_.dtype) * u_
            return h2 + _mm(a_, ws["wd"], True), k, v

        @jax.jit
        def run(hT, kpg, vpg):
            return decode_megakernel(
                hT, st["mk"], kpg, vpg, tbl, lens, act, cos, sin,
                tq=T, wmask=wm, **self._kw(st))

        h_ref, k_ref, v_ref = ref(h, st["kpg"], st["vpg"])
        ho, kn, vn = run(h, st["kpg"], st["vpg"])
        assert (np.asarray(kn).reshape(b, T, nh_kv, hd)
                == np.asarray(k_ref)).all()
        assert (np.asarray(vn).reshape(b, T, nh_kv, hd)
                == np.asarray(v_ref)).all()
        assert (np.asarray(ho) == np.asarray(h_ref).reshape(R, H)).all()


# -- engine-level matrix -----------------------------------------------------
ENGINE_KW = dict(max_len=48, page_size=8, max_batch=2, quant="int8",
                 slot_buckets=(2,))
NEW_TOKENS = 10


@pytest.fixture(scope="module")
def tiny():
    cfg = LlamaConfig(vocab_size=64, hidden_size=32,
                      intermediate_size=48, num_hidden_layers=1,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=64)
    paddle.seed(7)
    return LlamaForCausalLM(cfg), cfg


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.RandomState(3)
    return [rng.randint(0, 64, n).astype(np.int64) for n in (5, 9, 12)]


@pytest.fixture(scope="module")
def ref_outputs(tiny, prompts):
    model, cfg = tiny
    eng = ContinuousBatchingEngine(model, megakernel=False, **ENGINE_KW)
    return eng.generate_many(prompts, max_new_tokens=NEW_TOKENS)


def _assert_same(ref, outs, tag):
    for i, (a, b) in enumerate(zip(ref, outs)):
        assert a.shape == b.shape and (a == b).all(), (
            f"{tag}: request {i} diverged from the unfused engine")


class TestV2ByteIdentity:
    def test_wholestep_multi_k8(self, tiny, prompts, ref_outputs):
        model, _ = tiny
        eng = ContinuousBatchingEngine(model, megakernel="multi",
                                       decode_block=8, **ENGINE_KW)
        assert eng.health()["megakernel_whole_step"] is True
        outs = eng.generate_many(prompts, max_new_tokens=NEW_TOKENS)
        _assert_same(ref_outputs, outs, "multi+K8")

    def test_layer_mode_k1(self, tiny, prompts, ref_outputs):
        model, _ = tiny
        eng = ContinuousBatchingEngine(model, megakernel="layer",
                                       **ENGINE_KW)
        assert eng.health()["megakernel_whole_step"] is False
        outs = eng.generate_many(prompts, max_new_tokens=NEW_TOKENS)
        _assert_same(ref_outputs, outs, "layer+K1")

    def test_spec_rides_wholestep(self, tiny, prompts, ref_outputs):
        # the PR 6 gate is DELETED: speculate + megakernel composes and
        # greedy output stays byte-identical to the plain engine
        model, _ = tiny
        eng = ContinuousBatchingEngine(model, megakernel="multi",
                                       speculate=4, **ENGINE_KW)
        outs = eng.generate_many(prompts, max_new_tokens=NEW_TOKENS)
        _assert_same(ref_outputs, outs, "multi+spec4")
        assert eng.spec_passes > 0

    def test_tp2_wholestep_k8(self, tiny, prompts, ref_outputs):
        model, _ = tiny
        eng = ContinuousBatchingEngine(model, tp=2, megakernel="multi",
                                       decode_block=8, **ENGINE_KW)
        assert eng.health()["megakernel_whole_step"] is True
        outs = eng.generate_many(prompts, max_new_tokens=NEW_TOKENS)
        _assert_same(ref_outputs, outs, "tp2+multi+K8")

    def test_tp2_spec_wholestep_k8(self, tiny, prompts, ref_outputs):
        # every composition at once: the verify pass on the whole-step
        # kernel's tq>1 schedule, per-shard segments, fused blocks
        model, _ = tiny
        eng = ContinuousBatchingEngine(model, tp=2, megakernel="multi",
                                       speculate=4, decode_block=8,
                                       **ENGINE_KW)
        outs = eng.generate_many(prompts, max_new_tokens=NEW_TOKENS)
        _assert_same(ref_outputs, outs, "tp2+multi+spec4+K8")
        assert eng.spec_passes > 0

    @pytest.mark.slow
    def test_tp2_spec_layer(self, tiny, prompts, ref_outputs):
        # slow lane: the tier-1 tp cell is test_tp2_wholestep_k8; this
        # cell re-appears inside the crossed matrix below anyway
        model, _ = tiny
        eng = ContinuousBatchingEngine(model, tp=2, megakernel="layer",
                                       speculate=4, **ENGINE_KW)
        outs = eng.generate_many(prompts, max_new_tokens=NEW_TOKENS)
        _assert_same(ref_outputs, outs, "tp2+layer+spec4")


class TestFaultParity:
    def test_kill_at_block_boundary_parity(self, tiny, prompts):
        # an injected cb.decode fault at a block boundary must retire
        # the SAME request with the same stage whether the block math
        # runs the whole-step megakernel or the op chain, and the
        # survivors' outputs stay byte-identical
        from paddle_tpu.failsafe import inject
        model, _ = tiny
        two = prompts[:2]         # two engines compile in this test —
        #                           keep its tier-1 wall small

        def run(mk):
            eng = ContinuousBatchingEngine(model, megakernel=mk,
                                           decode_block=4, **ENGINE_KW)
            uids = [eng.add_request(p, max_new_tokens=NEW_TOKENS)
                    for p in two]
            with inject("cb.decode", nth=3):
                eng.drain()
            return eng, uids

        e0, u0 = run(False)
        e1, u1 = run("multi")
        s0 = [e0.status(u) for u in u0]
        s1 = [e1.status(u) for u in u1]
        assert s0 == s1
        f0 = {u: e0.failures()[u].stage for u in e0.failures()}
        f1 = {u: e1.failures()[u].stage for u in e1.failures()}
        assert f0 == f1 and f0          # at least one retirement
        for u_a, u_b, st in zip(u0, u1, s0):
            if st == "done":
                assert (e0.result(u_a) == e1.result(u_b)).all()


class TestTypedGates:
    def test_spec_gate_deleted(self, tiny):
        # regression for the PR 6 conflict error: forcing megakernel
        # with speculate= must construct, not raise
        model, _ = tiny
        eng = ContinuousBatchingEngine(model, megakernel="layer",
                                       speculate=4, **ENGINE_KW)
        assert eng.health()["megakernel"] == "layer"
        assert eng.health()["speculate"] == 4

    def test_tp_psum_rejected_typed(self, tiny):
        model, _ = tiny
        with pytest.raises(ValueError, match="exact"):
            ContinuousBatchingEngine(model, tp=2, tp_mode="psum",
                                     megakernel="multi", **ENGINE_KW)

    def test_tp_ffn_indivisible_rejected(self):
        # an ffn tp cannot divide is rejected with a ValueError before
        # any kernel runs — today at the base engine's column-parallel
        # weight placement (megakernel or not); _build_mk_pack keeps
        # its own typed check as a backstop should placement ever
        # loosen
        cfg = LlamaConfig(vocab_size=64, hidden_size=32,
                          intermediate_size=49, num_hidden_layers=1,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=64)
        paddle.seed(7)
        model = LlamaForCausalLM(cfg)
        with pytest.raises(ValueError):
            ContinuousBatchingEngine(model, tp=2, megakernel="layer",
                                     **ENGINE_KW)


@pytest.mark.slow
class TestV2Soak:
    def test_crossed_matrix_two_layers(self, prompts):
        # the full acceptance cross on a 2-layer GQA geometry:
        # mode {layer, multi} x decode_block {1, 8} x speculate {off, 4}
        # x tp {1, 2}, all byte-identical to the unfused tp=1 engine
        cfg = LlamaConfig(vocab_size=64, hidden_size=32,
                          intermediate_size=48, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=64)
        paddle.seed(7)
        model = LlamaForCausalLM(cfg)
        ref = ContinuousBatchingEngine(model, megakernel=False,
                                       **ENGINE_KW)
        ref_outs = ref.generate_many(prompts, max_new_tokens=NEW_TOKENS)
        for mode in ("layer", "multi"):
            for K in (1, 8):
                for spec in (None, 4):
                    for tp in (1, 2):
                        eng = ContinuousBatchingEngine(
                            model, megakernel=mode, decode_block=K,
                            speculate=spec, tp=tp, **ENGINE_KW)
                        outs = eng.generate_many(
                            prompts, max_new_tokens=NEW_TOKENS)
                        _assert_same(
                            ref_outs, outs,
                            f"mode={mode} K={K} spec={spec} tp={tp}")

    def test_sampled_mode_wholestep_identical_to_opchain(self, tiny,
                                                         prompts):
        # sampled outputs depend only on the logits bits + the
        # per-request (seed, position) key stream; the whole-step
        # kernel's logits are bit-identical to the op chain's, so the
        # SAME seeds must sample the SAME tokens
        model, _ = tiny

        def sampled(megakernel):
            eng = ContinuousBatchingEngine(
                model, megakernel=megakernel, decode_block=8,
                **ENGINE_KW)
            uids = [eng.add_request(
                        p, NEW_TOKENS, sampling=SamplingParams(
                            do_sample=True, temperature=0.8,
                            seed=11 + i))
                    for i, p in enumerate(prompts)]
            eng.drain()
            return [eng.result(u) for u in uids]

        _assert_same(sampled(False), sampled("multi"),
                     "sampled multi+K8")
