"""The decode megakernel's tile plan (ISSUE 27): how wide a weight block
one grid step of a matmul phase streams.

Pins:
  - `mm_tile_plan` as a pure function at the 7B serving cell's geometry
    (int8 and bf16), for tp = 2 shards and for the lm_head's N with a
    prime factor: the expected widths, bn | N and 128 | bn, the block
    within the budget, the pads the 512-column pack always produced;
  - the pack makes no second copy of an aligned weight and its shapes do
    not depend on the block budget;
  - a layer whose projections take SEVERAL n-tiles of MORE than one
    128-lane tile each (a small budget through the module constant — a
    test's handle, not an engine option) is byte-identical to the op
    chain, tq = 1 and tq > 1, and its segments compose to the full walk;
  - `health()["mk_tile_plan"]` reports the plan and the grid steps (since
    ISSUE 29 the attention phase's are the slots: its pages are a loop).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.inference.scheduler import ContinuousBatchingEngine
from paddle_tpu.inference.serving import _mm, _rms
from paddle_tpu.ops.pallas import decode_megakernel as dm
from paddle_tpu.ops.pallas.paged_attention import (paged_attention,
                                                   spec_verify_attention)
from paddle_tpu.ops.pallas.quantized_matmul import quantize_weights

MiB = 1 << 20
# internlm2-7b: hidden 4096, 32 q / 8 kv heads x 128, ffn 14336, vocab 92544
H7, KV7, F7, V7 = 4096, 1024, 14336, 92544
CELL = {"q": (H7, H7), "k": (H7, KV7), "v": (H7, KV7), "o": (H7, H7),
        "g": (H7, F7), "u": (H7, F7), "d": (F7, H7)}
# projection -> bn at 2 MiB, by weight item size
WANT = {1: {"q": 4096, "k": 1024, "v": 1024, "o": 4096, "g": 3584,
            "u": 3584, "d": 4096},
        2: {"q": 2048, "k": 1024, "v": 1024, "o": 2048, "g": 2048,
            "u": 2048, "d": 2048}}


def _parent_pad(dim):
    """The pad the fixed 512 x 512 tiles gave a dimension."""
    return dim if dim <= 512 else -(-dim // 512) * 512


class TestPlanRule:
    def test_budget_is_the_swept_constant(self):
        assert dm.MM_BK == 512 and dm.MM_BLOCK_BYTES == 2 * MiB

    @pytest.mark.parametrize("key", list(CELL))
    @pytest.mark.parametrize("itemsize", [1, 2], ids=["int8", "bf16"])
    def test_cell_geometry_widths(self, key, itemsize):
        k, n = CELL[key]
        bk, bn, k_pad, n_pad = dm.mm_tile_plan(k, n, itemsize)
        assert (bk, bn) == (512, WANT[itemsize][key])
        assert (k_pad, n_pad) == (k, n)          # nothing is padded
        assert n % bn == 0 and bn % 128 == 0
        assert bk * bn * itemsize <= dm.MM_BLOCK_BYTES

    @pytest.mark.parametrize("itemsize,want", [(1, 124), (2, 216)],
                             ids=["int8", "bf16"])
    def test_cell_geometry_steps_a_layer(self, itemsize, want):
        steps = 0
        for k, n in CELL.values():
            bk, bn, _, _ = dm.mm_tile_plan(k, n, itemsize)
            steps += (k // bk) * (n // bn)
        assert steps == want                      # the 512 columns: 832

    @pytest.mark.parametrize("budget,want", [
        (MiB // 4, 832), (1 * MiB, 216), (2 * MiB, 124), (4 * MiB, 92)])
    def test_cell_geometry_steps_by_budget(self, budget, want):
        steps = 0
        for k, n in CELL.values():
            bk, bn, _, _ = dm.mm_tile_plan(k, n, 1, budget)
            steps += (k // bk) * (n // bn)
        assert steps == want

    def test_tp2_shard_keeps_its_width_unpadded(self):
        # ffn / 2 = 7168 = 56 lanes: 28 of them fit 2 MiB of int8
        assert dm.mm_tile_plan(H7, F7 // 2, 1) == (512, 3584, H7, F7 // 2)
        assert dm.mm_tile_plan(H7, H7 // 2, 1) == (512, 2048, H7, H7 // 2)
        assert dm.mm_tile_plan(H7, KV7 // 2, 1) == (512, 512, H7, KV7 // 2)

    def test_head_with_a_prime_factor_keeps_512(self):
        # 92544 pads to 92672 = 181 x 512, 181 prime: the next divisor
        # up, 181 x 128 lanes, is 11 MiB a block
        assert dm.mm_tile_plan(H7, V7, 1) == (512, 512, H7, 92672)
        assert dm.mm_tile_plan(H7, V7, 2) == (512, 512, H7, 92672)

    @pytest.mark.parametrize("k,n", [
        (32, 48), (1000, 96), (96, 1000), (4096, 11008), (11008, 4096),
        (600, 64), (64, 600), (512, 512), (513, 513), (2048, 640)])
    @pytest.mark.parametrize("itemsize", [1, 2, 4])
    @pytest.mark.parametrize("budget", [64 << 10, 192 << 10, 2 * MiB,
                                        4 * MiB])
    def test_rule_properties(self, k, n, itemsize, budget):
        bk, bn, k_pad, n_pad = dm.mm_tile_plan(k, n, itemsize, budget)
        # the pads are the 512-column pack's, whatever the budget
        assert (k_pad, n_pad) == (_parent_pad(k), _parent_pad(n))
        assert bk == min(k, 512) and k_pad % bk == 0 and n_pad % bn == 0
        if n <= 512:
            assert bn == n            # one block: the whole matrix
        else:
            assert bn % 128 == 0
            assert bk * bn * itemsize <= max(budget, bk * 128 * itemsize)
            # no wider divisor of n_pad would have fitted
            wider = [d for d in range(bn + 128, n_pad + 1, 128)
                     if n_pad % d == 0 and bk * d * itemsize <= budget]
            assert not wider
        # the call sees the padded shape and draws the same tiles
        assert dm.mm_tile_plan(k_pad, n_pad, itemsize, budget) == (
            bk, bn, k_pad, n_pad)


def _abstract_layer(quant, tp=1):
    def w(k, n):
        if quant:
            return (jax.ShapeDtypeStruct((k, n), jnp.int8),
                    jax.ShapeDtypeStruct((n,), jnp.float32))
        return jax.ShapeDtypeStruct((k, n), jnp.bfloat16)

    ws = {"w" + key: w(*kn) for key, kn in CELL.items()}
    ws["ln1"] = ws["ln2"] = jax.ShapeDtypeStruct((H7,), jnp.bfloat16)
    return jax.eval_shape(
        lambda t: dm.pack_decode_layer(t, cdtype=jnp.bfloat16, tp=tp), ws)


class TestPack:
    @pytest.mark.parametrize("quant", [True, False], ids=["int8", "bf16"])
    @pytest.mark.parametrize("tp", [1, 2])
    def test_cell_pack_shapes_are_the_weights_own(self, quant, tp):
        # every N of the cell is a 512-multiple, per shard too: the pack
        # pads nothing, so it holds no second copy of any weight
        mk = _abstract_layer(quant, tp)
        for key, kn in CELL.items():
            assert mk["w" + key].shape == kn
            assert mk["s" + key].shape == (1, kn[1])
        assert mk["wq"].dtype == (jnp.int8 if quant else jnp.bfloat16)
        plan = dm.layer_tile_plan(mk, slots=32, tp=tp)
        want = dict(WANT[1 if quant else 2])
        if tp == 2 and quant:
            want.update(q=2048, k=512, v=512)     # g / u stay 3584
        elif tp == 2:
            want.update(k=512, v=512, g=1792, u=1792)
        assert plan["blocks"] == {k: [512, v] for k, v in want.items()}
        # one attention step a slot, whatever the table's width (PR 29)
        assert plan["layer_steps"]["attention"] == 32
        assert plan["attention_pages"] == "live"
        if quant and tp == 1:     # the dense serving cell's engine
            assert plan["layer_steps"] == {"matmul": 124, "attention": 32}

    def test_aligned_weights_are_not_copied(self, monkeypatch):
        rng = np.random.RandomState(0)
        wq = quantize_weights(jnp.asarray(rng.randn(512, 1024), jnp.float32))
        one = jnp.ones((512,), jnp.float32)
        ws = dict(wq=wq, wk=wq, wv=wq, wo=wq, wg=wq, wu=wq, wd=wq,
                  ln1=one, ln2=one)
        mk = dm.pack_decode_layer(ws)
        assert mk["wq"] is wq[0] and mk["wd"] is wq[0]
        # nor does the budget enter the pack
        monkeypatch.setattr(dm, "MM_BLOCK_BYTES", 64 << 10)
        small = dm.pack_decode_layer(ws)
        assert small["wq"] is wq[0]
        assert {k: v.shape for k, v in small.items()} == {
            k: v.shape for k, v in mk.items()}

    def test_head_pack_pads_as_before(self):
        hp = jax.eval_shape(
            lambda h, n: dm.pack_lm_head(h, n, cdtype=jnp.bfloat16),
            (jax.ShapeDtypeStruct((H7, V7), jnp.int8),
             jax.ShapeDtypeStruct((V7,), jnp.float32)),
            jax.ShapeDtypeStruct((H7,), jnp.bfloat16))
        assert hp["wh"].shape == (H7, 92672)
        assert hp["sh"].shape == (1, 92672)


# -- a layer wide enough for several n-tiles --------------------------------
WIDE = dict(b=2, nh=8, nh_kv=2, hd=128, H=1024, F=1536, p=8, mp=3,
            n_pages=8, eps=1e-5)
# 192 KiB of int8 = three 128-lane tiles a block at bk 512: hidden 1024
# (8 lanes) streams in 4 blocks of 256, ffn 1536 (12 lanes) in 4 of 384
WIDE_BUDGET = 192 << 10


@pytest.fixture(scope="module")
def wide():
    rng = np.random.RandomState(1)
    g = WIDE

    def w(k, n):
        return quantize_weights(
            jnp.asarray(rng.randn(k, n).astype(np.float32) * 0.05))

    H, F, NQ, NK = g["H"], g["F"], g["nh"] * g["hd"], g["nh_kv"] * g["hd"]
    ws = dict(wq=w(H, NQ), wk=w(H, NK), wv=w(H, NK), wo=w(NQ, H),
              wg=w(H, F), wu=w(H, F), wd=w(F, H),
              ln1=jnp.asarray(rng.rand(H).astype(np.float32) + 0.5),
              ln2=jnp.asarray(rng.rand(H).astype(np.float32) + 0.5))
    shape = (g["n_pages"], g["p"], g["nh_kv"], g["hd"])
    return dict(
        g, ws=ws, mk=dm.pack_decode_layer(ws),
        kpg=jnp.asarray(rng.randn(*shape).astype(np.float32)),
        vpg=jnp.asarray(rng.randn(*shape).astype(np.float32)),
        tbl=jnp.asarray(rng.choice(g["n_pages"], (g["b"], g["mp"]),
                                   replace=False).astype(np.int32)),
        lens=jnp.asarray(np.array([5, 11], np.int32)),
        act=jnp.ones(g["b"], jnp.int32), rng=rng)


def _op_chain_qkv(st, T, hT, cos, sin, wm):
    """The unfused engine path up to the attention output, on [b*T, H]
    feed rows: quantized_matmul projections, rope, write-gated scatter,
    then the decode (T = 1) or verify (T > 1) attention kernel."""
    b, hd, H, p = st["b"], st["hd"], st["H"], st["p"]
    nh_kv, n_pages = st["nh_kv"], st["n_pages"]
    ws, lens, tbl, act = st["ws"], st["lens"], st["tbl"], st["act"]
    x = _rms(hT.reshape(b, T, H), ws["ln1"], st["eps"])
    q = _mm(x, ws["wq"], True).reshape(b, T, -1, hd)
    k = _mm(x, ws["wk"], True).reshape(b, T, -1, hd)
    v = _mm(x, ws["wv"], True).reshape(b, T, -1, hd)
    c = cos.reshape(b, T, 1, hd // 2)
    s = sin.reshape(b, T, 1, hd // 2)
    d2 = hd // 2

    def rope(x_):
        x1, x2 = x_[..., :d2], x_[..., d2:]
        return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)

    q, k = rope(q), rope(k)
    pos = lens[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    slots = tbl[jnp.arange(b)[:, None], pos // p] * p + pos % p
    slots = jnp.where(wm.reshape(b, T) > 0, slots, jnp.int32(n_pages * p))
    kp2 = st["kpg"].reshape(-1, nh_kv, hd).at[slots].set(
        k, mode="drop").reshape(n_pages, p, nh_kv, hd)
    vp2 = st["vpg"].reshape(-1, nh_kv, hd).at[slots].set(
        v, mode="drop").reshape(n_pages, p, nh_kv, hd)
    if T == 1:
        attn = paged_attention(q[:, 0], kp2, vp2, tbl, lens + 1,
                               active=act, interpret=True)
    else:
        attn = spec_verify_attention(q, kp2, vp2, tbl, lens, active=act,
                                     interpret=True)
    return attn.reshape(b * T, -1), k, v


def _op_chain_mlp(st, h2):
    x2 = _rms(h2, st["ws"]["ln2"], st["eps"])
    g_ = _mm(x2, st["ws"]["wg"], True)
    return jax.nn.silu(g_.astype(jnp.float32)).astype(g_.dtype) \
        * _mm(x2, st["ws"]["wu"], True)


def _same(a, b):
    return (np.asarray(a) == np.asarray(b)).all()


class TestWideMultiTileEmission:
    def _rows(self, st, T):
        rng, R = st["rng"], st["b"] * T
        return (jnp.asarray(rng.randn(R, st["H"]).astype(np.float32)),
                jnp.asarray(rng.randn(R, st["hd"] // 2).astype(np.float32)),
                jnp.asarray(rng.randn(R, st["hd"] // 2).astype(np.float32)))

    def _kw(self, st):
        return dict(nh=st["nh"], nh_kv=st["nh_kv"], hd=st["hd"],
                    eps=st["eps"], interpret=True)

    def test_the_small_budget_gives_several_wide_tiles(self, wide,
                                                       monkeypatch):
        monkeypatch.setattr(dm, "MM_BLOCK_BYTES", WIDE_BUDGET)
        plan = dm.layer_tile_plan(wide["mk"], wide["b"])
        assert plan["blocks"] == {
            "q": [512, 256], "k": [512, 256], "v": [512, 256],
            "o": [512, 256], "g": [512, 384], "u": [512, 384],
            "d": [512, 256]}
        # q 2x4, k and v 2x1, o 2x4, gate and up 2x4, down 3x4
        assert plan["layer_steps"] == {"matmul": 48,
                                       "attention": wide["b"]}

    @pytest.mark.parametrize("T", [1, 3], ids=["tq1", "tq3"])
    def test_byte_identical_to_the_op_chain(self, wide, monkeypatch, T):
        """Stage by stage, each against quantized_matmul's own tiles
        (bn 256): the column-parallel segment whole; the two projections
        that ADD into the residual with a zero residual, because the CPU
        backend contracts the kernel's `h + acc * scale` into one fused
        multiply-add where the op chain rounds twice (at the parent
        commit too; a bf16 stream rounds between the two and is not
        affected), and 0 + x is x either way; then the full walk against
        the segments composed, residual and all."""
        monkeypatch.setattr(dm, "MM_BLOCK_BYTES", WIDE_BUDGET)
        st = wide
        b, nh_kv, hd = st["b"], st["nh_kv"], st["hd"]
        h, cos, sin = self._rows(st, T)
        wm = (jnp.ones(b, jnp.int32) if T == 1 else
              jnp.asarray(np.array([1, 1, 0, 1, 1, 1], np.int32)))
        kw = self._kw(st)
        args = (st["kpg"], st["vpg"], st["tbl"], st["lens"], st["act"],
                cos, sin)

        @jax.jit
        def run(hT):
            z = jnp.zeros_like(hT)
            full = dm.decode_megakernel(hT, st["mk"], *args, tq=T,
                                        wmask=wm, **kw)
            attn, kn, vn = dm.decode_megakernel(
                hT, st["mk"], *args, seg="qkv", tq=T, wmask=wm, **kw)
            o0, act0 = dm.decode_megakernel(
                z, st["mk"], seg="tail", attn_in=attn, mlp_v=st["F"], **kw)
            d0 = dm.decode_megakernel(z, st["mk"], seg="down",
                                      act_in=act0, **kw)
            h_mid, act = dm.decode_megakernel(
                hT, st["mk"], seg="tail", attn_in=attn, mlp_v=st["F"],
                **kw)
            ho = dm.decode_megakernel(h_mid, st["mk"], seg="down",
                                      act_in=act, **kw)
            return full, (ho, kn, vn), attn, o0, act0, d0

        @jax.jit
        def ref(hT):
            attn, k, v = _op_chain_qkv(st, T, hT, cos, sin, wm)
            o = _mm(attn, st["ws"]["wo"], True)
            act = _op_chain_mlp(st, o)
            return attn, k, v, o, act, _mm(act, st["ws"]["wd"], True)

        full, segs, attn, o0, act0, d0 = run(h)
        attn_r, k_r, v_r, o_r, act_r, d_r = ref(h)
        assert _same(attn, attn_r)
        assert _same(np.asarray(segs[1]).reshape(b, T, nh_kv, hd), k_r)
        assert _same(np.asarray(segs[2]).reshape(b, T, nh_kv, hd), v_r)
        assert _same(o0, o_r) and _same(act0, act_r) and _same(d0, d_r)
        for a, c in zip(full, segs):
            assert _same(a, c)

    def test_the_plan_does_not_enter_the_bits(self, wide, monkeypatch):
        # one block a projection (the default budget holds these whole),
        # the 512 columns this kernel always had (256 KiB of int8), and
        # several blocks wider than a lane tile: the same outputs
        st = wide
        h, cos, sin = self._rows(st, 1)
        args = (st["kpg"], st["vpg"], st["tbl"], st["lens"], st["act"],
                cos, sin)
        outs = []
        for budget in (dm.MM_BLOCK_BYTES, 256 << 10, WIDE_BUDGET):
            monkeypatch.setattr(dm, "MM_BLOCK_BYTES", budget)
            outs.append(jax.jit(lambda hT: dm.decode_megakernel(
                hT, st["mk"], *args, **self._kw(st)))(h))
        assert dm.layer_tile_plan(st["mk"], 2)["blocks"]["g"] == [
            512, 384]
        for got in outs[1:]:
            for a, c in zip(outs[0], got):
                assert _same(a, c)


class TestEngineReportsThePlan:
    @pytest.fixture(scope="class")
    def tiny(self):
        cfg = LlamaConfig(vocab_size=64, hidden_size=32,
                          intermediate_size=48, num_hidden_layers=1,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=64)
        paddle.seed(7)
        return LlamaForCausalLM(cfg)

    @pytest.mark.parametrize("mode", ["layer", "multi"])
    def test_health_mk_tile_plan(self, tiny, mode):
        eng = ContinuousBatchingEngine(
            tiny, max_len=48, page_size=8, max_batch=2, quant="int8",
            slot_buckets=(2,), megakernel=mode)
        plan = eng.health()["mk_tile_plan"]
        # test widths are under 512: a block is the whole matrix
        assert plan["blocks"] == {
            "q": [32, 32], "k": [32, 16], "v": [32, 16], "o": [32, 32],
            "g": [32, 48], "u": [32, 48], "d": [48, 32]}
        # 2 slots: a step each, not one per column of the 6-wide table
        assert plan["layer_steps"] == {"matmul": 7, "attention": 2}
        assert plan["attention_pages"] == "live"
        assert eng.health()["paged_decode"] is None

    def test_op_chain_reports_none(self, tiny):
        eng = ContinuousBatchingEngine(
            tiny, max_len=48, page_size=8, max_batch=2, quant="int8",
            slot_buckets=(2,), megakernel=False)
        h = eng.health()
        assert h["mk_tile_plan"] is None
        # the op chain's twin of the plan (PR 37): the paged decode
        # kernel takes a grid step a slot and walks its live pages
        assert h["paged_decode"] == {
            "grid_steps_per_layer": 2, "pages": "live",
            "mm_operand_dtype": "float32"}
