"""Decode/serving kernel tests (VERDICT round-1 #6): paged attention and
int8 weight-only matmul (interpret mode on CPU; native on TPU)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu  # noqa: F401
from paddle_tpu.ops.pallas.paged_attention import (
    paged_attention, paged_attention_reference, ragged_paged_attention,
    ragged_paged_attention_reference)
from paddle_tpu.ops.pallas.quantized_matmul import (dot_tile_f32,
                                                    mm_operand_dtype,
                                                    quantized_matmul,
                                                    quantize_weights)


class TestPagedAttention:
    def test_matches_reference_ragged_lens(self):
        rng = np.random.RandomState(0)
        b, h, d, p, n_pages, max_pages = 3, 4, 64, 128, 16, 4
        q = jnp.asarray(rng.randn(b, h, d), jnp.float32)
        kp = jnp.asarray(rng.randn(n_pages, p, h, d), jnp.float32)
        vp = jnp.asarray(rng.randn(n_pages, p, h, d), jnp.float32)
        table = jnp.asarray(
            rng.permutation(n_pages)[:b * max_pages].reshape(b, max_pages),
            jnp.int32)
        lens = jnp.asarray([500, 130, 37], jnp.int32)
        out = paged_attention(q, kp, vp, table, lens, interpret=True)
        ref = paged_attention_reference(q, kp, vp, table, lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)

    def test_single_token_seq(self):
        rng = np.random.RandomState(1)
        b, h, d, p, n_pages, max_pages = 1, 2, 32, 128, 4, 2
        q = jnp.asarray(rng.randn(b, h, d), jnp.float32)
        kp = jnp.asarray(rng.randn(n_pages, p, h, d), jnp.float32)
        vp = jnp.asarray(rng.randn(n_pages, p, h, d), jnp.float32)
        table = jnp.zeros((b, max_pages), jnp.int32)
        lens = jnp.asarray([1], jnp.int32)
        out = paged_attention(q, kp, vp, table, lens, interpret=True)
        ref = paged_attention_reference(q, kp, vp, table, lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)

    def test_bf16_pages(self):
        rng = np.random.RandomState(2)
        b, h, d, p, n_pages, max_pages = 2, 4, 64, 128, 8, 2
        q = jnp.asarray(rng.randn(b, h, d), jnp.bfloat16)
        kp = jnp.asarray(rng.randn(n_pages, p, h, d), jnp.bfloat16)
        vp = jnp.asarray(rng.randn(n_pages, p, h, d), jnp.bfloat16)
        table = jnp.asarray(rng.randint(0, n_pages, (b, max_pages)),
                            jnp.int32)
        lens = jnp.asarray([256, 100], jnp.int32)
        out = paged_attention(q, kp, vp, table, lens, interpret=True)
        ref = paged_attention_reference(q, kp, vp, table, lens)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=5e-2, atol=5e-2)


class TestRaggedPagedAttention:
    """ISSUE 4 ragged prefill fusion: one kernel invocation covers
    slots at DIFFERENT positions (per-slot q_start/ctx_len scalar
    prefetch), each attending its own pages causally."""

    def _rand(self, rng, b, tq, h, h_kv, d, p, n_pages, max_pages):
        q = jnp.asarray(rng.randn(b, tq, h, d) * 0.3, jnp.float32)
        kp = jnp.asarray(rng.randn(n_pages, p, h_kv, d) * 0.3, jnp.float32)
        vp = jnp.asarray(rng.randn(n_pages, p, h_kv, d) * 0.3, jnp.float32)
        table = jnp.asarray(rng.randint(0, n_pages, (b, max_pages)),
                            jnp.int32)
        return q, kp, vp, table

    def _check(self, q, kp, vp, table, ctx, starts, act=None, tol=2e-4):
        out = ragged_paged_attention(q, kp, vp, table, ctx, starts,
                                     active=act, interpret=True)
        ref = ragged_paged_attention_reference(q, kp, vp, table, ctx,
                                               starts, active=act)
        out, ref = np.asarray(out), np.asarray(ref)
        tq = q.shape[1]
        for i in range(q.shape[0]):
            if act is not None and not int(act[i]):
                assert np.all(out[i] == 0), "inactive slot must emit zeros"
                continue
            # rows past a slot's real chunk length are garbage by
            # contract — compare the valid rows only
            n_valid = max(0, min(tq, int(ctx[i]) - int(starts[i])))
            np.testing.assert_allclose(out[i, :n_valid], ref[i, :n_valid],
                                       rtol=tol, atol=tol,
                                       err_msg=f"slot {i}")

    def test_slots_at_different_offsets(self):
        rng = np.random.RandomState(0)
        b, tq, h, d, p, n_pages, mp = 4, 8, 4, 32, 8, 16, 6
        q, kp, vp, table = self._rand(rng, b, tq, h, h, d, p, n_pages, mp)
        starts = jnp.asarray([0, 5, 23, 11], jnp.int32)
        ctx = jnp.asarray([8, 13, 31, 19], jnp.int32)
        self._check(q, kp, vp, table, ctx, starts)

    def test_partial_chunk_and_active_mask(self):
        rng = np.random.RandomState(1)
        b, tq, h, d, p, n_pages, mp = 4, 4, 2, 32, 8, 8, 4
        q, kp, vp, table = self._rand(rng, b, tq, h, h, d, p, n_pages, mp)
        starts = jnp.asarray([0, 6, 2, 9], jnp.int32)
        # slot 1 ends mid-chunk (ctx < start + tq); slot 2 is inactive
        ctx = jnp.asarray([4, 8, 6, 13], jnp.int32)
        act = jnp.asarray([1, 1, 0, 1], jnp.int32)
        self._check(q, kp, vp, table, ctx, starts, act=act)

    def test_gqa_grouped_heads(self):
        rng = np.random.RandomState(2)
        b, tq, h, h_kv, d, p, n_pages, mp = 2, 4, 8, 2, 32, 8, 16, 4
        q, kp, vp, table = self._rand(rng, b, tq, h, h_kv, d, p,
                                      n_pages, mp)
        starts = jnp.asarray([3, 17], jnp.int32)
        ctx = jnp.asarray([7, 21], jnp.int32)
        self._check(q, kp, vp, table, ctx, starts, tol=2e-3)

    def test_decode_is_the_tq1_special_case(self):
        """tq=1 with q_start = ctx-1 must agree with the tuned decode
        kernel."""
        rng = np.random.RandomState(3)
        b, h, d, p, n_pages, mp = 3, 4, 32, 8, 16, 4
        q, kp, vp, table = self._rand(rng, b, 1, h, h, d, p, n_pages, mp)
        lens = jnp.asarray([3, 17, 30], jnp.int32)
        dec = paged_attention(q[:, 0], kp, vp, table, lens, interpret=True)
        rag = ragged_paged_attention(q, kp, vp, table, lens, lens - 1,
                                     interpret=True)[:, 0]
        np.testing.assert_allclose(np.asarray(dec), np.asarray(rag),
                                   rtol=2e-5, atol=2e-5)


class TestQuantizedMatmul:
    def test_matches_dequantized(self):
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(70, 300), jnp.float32)
        w = jnp.asarray(rng.randn(300, 130) * 0.1, jnp.float32)
        wq, sc = quantize_weights(w)
        out = quantized_matmul(x, wq, sc, bm=64, bn=128, bk=128,
                               interpret=True)
        ref = x @ (wq.astype(jnp.float32) * sc[None, :])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

    def test_quantization_error_small(self):
        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(16, 128), jnp.float32)
        w = jnp.asarray(rng.randn(128, 64) * 0.05, jnp.float32)
        wq, sc = quantize_weights(w)
        out = quantized_matmul(x, wq, sc, interpret=True)
        full = x @ w
        rel = float(jnp.max(jnp.abs(out - full)) / jnp.max(jnp.abs(full)))
        assert rel < 0.05, rel

    def test_bf16_activations(self):
        rng = np.random.RandomState(2)
        x = jnp.asarray(rng.randn(32, 256), jnp.bfloat16)
        w = jnp.asarray(rng.randn(256, 128) * 0.1, jnp.float32)
        wq, sc = quantize_weights(w)
        out = quantized_matmul(x, wq, sc, interpret=True)
        assert out.dtype == jnp.bfloat16
        ref = (x.astype(jnp.float32)
               @ (wq.astype(jnp.float32) * sc[None, :]))
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref), rtol=5e-2, atol=5e-1)


def _dot_generals(jaxpr):
    """Every dot_general equation of a jaxpr, through pallas_call
    bodies, pl.when branches and any other nested jaxpr."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append(eqn)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _dot_generals(sub)
    return found


def _operand_dtypes(eqns):
    return {(e.invars[0].aval.dtype.name, e.invars[1].aval.dtype.name,
             e.outvars[0].aval.dtype.name) for e in eqns}


# activations, weights -> the MXU operand type the ONE rule picks
OPERAND_RULE = [
    (jnp.bfloat16, jnp.int8, "bfloat16"),
    (jnp.bfloat16, jnp.bfloat16, "bfloat16"),
    (jnp.float32, jnp.int8, "float32"),
    (jnp.float32, jnp.float32, "float32"),
    (jnp.float16, jnp.int8, "float32"),
    (jnp.bfloat16, jnp.float32, "float32"),
]


class TestMatmulOperandDtype:
    """PR 25: bf16 activations against int8 or bf16 weights reach the
    MXU as bf16 x bf16 (one pass; every product exact in f32); anything
    else keeps the f32 x f32 product. One rule, shared by
    quantized_matmul and both megakernel call sites."""

    @pytest.mark.parametrize("xdt,wdt,want", OPERAND_RULE)
    def test_rule(self, xdt, wdt, want):
        assert jnp.dtype(mm_operand_dtype(xdt, wdt)).name == want

    @pytest.mark.parametrize("xdt,wdt,want", OPERAND_RULE[:4])
    def test_quantized_matmul_jaxpr(self, xdt, wdt, want):
        x = jax.ShapeDtypeStruct((16, 256), xdt)
        w = jax.ShapeDtypeStruct((256, 128), wdt)
        sc = jax.ShapeDtypeStruct((128,), jnp.float32)
        dots = _dot_generals(jax.make_jaxpr(
            lambda a, b, c: quantized_matmul(a, b, c, bk=128,
                                             interpret=True))(x, w, sc).jaxpr)
        assert _operand_dtypes(dots) == {(want, want, "float32")}

    @pytest.mark.parametrize("xdt,wdt,want", OPERAND_RULE[:4])
    def test_megakernel_layer_jaxpr(self, xdt, wdt, want):
        """The weight dots of a megakernel layer call: lhs [R, k-tile],
        the o-projection's read from the f32 attention scratch too (the
        attention phase's own small f32 dots have rep != R rows)."""
        from paddle_tpu.ops.pallas.decode_megakernel import (
            decode_megakernel, pack_decode_layer)
        R, nh, nh_kv, hd, H, F, p, mp = 3, 4, 2, 16, 64, 96, 8, 2

        def w(k, n):
            if wdt == jnp.int8:
                return (jnp.ones((k, n), jnp.int8),
                        jnp.ones((n,), jnp.float32))
            return jnp.ones((k, n), wdt)
        ws = dict(ln1=jnp.ones((H,), xdt), ln2=jnp.ones((H,), xdt),
                  wq=w(H, H), wk=w(H, nh_kv * hd), wv=w(H, nh_kv * hd),
                  wo=w(H, H), wg=w(H, F), wu=w(H, F), wd=w(F, H))
        mk = pack_decode_layer(ws, cdtype=xdt)
        pages = jax.ShapeDtypeStruct((R * mp, p, nh_kv, hd), xdt)
        rope = jax.ShapeDtypeStruct((R, hd // 2), jnp.float32)
        jaxpr = jax.make_jaxpr(
            lambda h, kp, vp, tbl, ln, c, s_: decode_megakernel(
                h, mk, kp, vp, tbl, ln, None, c, s_, nh=nh, nh_kv=nh_kv,
                hd=hd, eps=1e-6, interpret=True))(
            jax.ShapeDtypeStruct((R, H), xdt), pages, pages,
            jax.ShapeDtypeStruct((R, mp), jnp.int32),
            jax.ShapeDtypeStruct((R,), jnp.int32), rope, rope).jaxpr
        weight_dots = [e for e in _dot_generals(jaxpr)
                       if e.invars[0].aval.shape[0] == R]
        assert len(weight_dots) == 7          # q k v o gate up down
        assert _operand_dtypes(weight_dots) == {(want, want, "float32")}

    @pytest.mark.parametrize("wdt", [jnp.int8, jnp.bfloat16])
    def test_bf16_operands_exact(self, wdt):
        """Small-integer bf16 activations against int8 (or
        integer-valued bf16) weights: every product and partial sum is
        an integer below 2^24, exact in f32, so the bf16-operand tile
        equals the f32-operand tile bit for bit — the one pass is the
        six-pass product less the passes that multiplied zeros."""
        rng = np.random.RandomState(5)
        x = jnp.asarray(rng.randint(-8, 9, (32, 512)), jnp.bfloat16)
        w = jnp.asarray(rng.randint(-127, 128, (512, 256)), wdt)
        got = dot_tile_f32(x, w)
        ref = dot_tile_f32(x.astype(jnp.float32), w)
        want = (np.asarray(x, np.float64) @ np.asarray(w, np.float64))
        assert got.dtype == ref.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
        np.testing.assert_array_equal(np.asarray(got, np.float64), want)
        # an f32 container passed as the bf16 it holds (the megakernel's
        # attention scratch) takes the same branch
        np.testing.assert_array_equal(
            np.asarray(dot_tile_f32(x.astype(jnp.float32), w,
                                    jnp.bfloat16)), np.asarray(got))
        # and through the kernel, k-tiled
        out = quantized_matmul(x, w, jnp.ones((256,), jnp.float32),
                               out_dtype=jnp.float32, interpret=True)
        np.testing.assert_array_equal(np.asarray(out, np.float64), want)

    @pytest.mark.parametrize("wdt,want", [("bfloat16", "bfloat16"),
                                          (None, "float32")])
    @pytest.mark.parametrize("quant", ["int8", None])
    def test_engine_health_reports_it(self, wdt, want, quant):
        import paddle_tpu as paddle
        from paddle_tpu.inference.scheduler import ContinuousBatchingEngine
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        cfg = LlamaConfig(vocab_size=64, hidden_size=32,
                          intermediate_size=48, num_hidden_layers=1,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=64)
        paddle.seed(7)
        eng = ContinuousBatchingEngine(
            LlamaForCausalLM(cfg), max_len=48, page_size=8, max_batch=2,
            quant=quant, weight_dtype=wdt, slot_buckets=(2,))
        assert eng.health()["mm_operand_dtype"] == want


def test_paged_attention_gqa_native():
    """q heads attend their kv group without cache expansion."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_attention, paged_attention_reference)
    rng = np.random.RandomState(7)
    b, h, h_kv, d, p, n_pages, max_pages = 2, 8, 2, 32, 8, 16, 4
    q = jnp.asarray(rng.randn(b, h, d) * 0.3, jnp.float32)
    kp = jnp.asarray(rng.randn(n_pages, p, h_kv, d) * 0.3, jnp.float32)
    vp = jnp.asarray(rng.randn(n_pages, p, h_kv, d) * 0.3, jnp.float32)
    table = jnp.asarray(rng.permutation(n_pages)[:b * max_pages]
                        .reshape(b, max_pages), jnp.int32)
    lens = jnp.asarray([29, 17], jnp.int32)
    out = paged_attention(q, kp, vp, table, lens, interpret=True)
    ref = paged_attention_reference(q, kp, vp, table, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
