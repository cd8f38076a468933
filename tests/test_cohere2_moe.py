"""The parallel block (one LayerNorm, attention and FFN from the same
normed input, one residual add; 16:1 grouped queries; window layers that
rotate in interleaved pairs beside position-free full layers; sigmoid
router without a bias; four averaged shared experts; tied head) end to
end at tiny widths on the CPU: model against the plain reference, the
serving engine through its cache against the reference's full forward on
LOGITS, the layouts the engine is handed, the chunk attention in key
blocks, the page groups and counters, and the typed refusals.
"""
import dataclasses
import importlib.util
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.inference import sparse_heads
from paddle_tpu.inference.description import (AttentionSpec, IndexerSpec,
                                              UnsupportedByDescription,
                                              describe)
from paddle_tpu.inference.sampling import (SamplingParams,
                                           TokenMaskAutomaton)
from paddle_tpu.models import Cohere2MoeConfig, Cohere2MoeForCausalLM
from paddle_tpu.models import cohere2_moe as C
from paddle_tpu.ops import latent_attention as la
from paddle_tpu.ops.moe import routed_experts
from paddle_tpu.ops.pallas import chunk_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    spec = importlib.util.spec_from_file_location(
        "perf_reference_cohere2_moe",
        os.path.join(ROOT, "perf", "references", "cohere2_moe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()

# the tiny configuration as a configuration FILE's keys (what the
# reference reads): two periods of [sliding x 3, full], window 8, the
# experts all held
CFG = {
    "hidden_size": 64, "intermediate_size": 32, "num_hidden_layers": 8,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 2,
    "sliding_window": 8, "rope_theta": 50000, "rotary_pct": 1,
    "layer_norm_eps": 1e-5, "num_experts": 8, "num_experts_per_tok": 2,
    "num_shared_experts": 4, "norm_topk_prob": True, "logit_scale": 1,
    "tie_word_embeddings": True, "use_parallel_block": True,
    "use_qk_norm": False, "first_k_dense_replace": 0, "vocab_size": 96,
    "max_position_embeddings": 128}


@pytest.fixture(scope="module")
def model():
    paddle.seed(11)
    m = Cohere2MoeForCausalLM(REF.model_config(CFG))
    # norm weights away from one, so that a norm left out or applied
    # twice shows
    rng = np.random.default_rng(12)
    for norm in [m.norm] + [l.input_layernorm for l in m.layers]:
        norm.weight.data = jnp.asarray(
            1.0 + 0.3 * rng.standard_normal(64), jnp.float32)
    m.eval()
    return m


def _engine_layout(model):
    """The model's parameters as the ENGINE is handed them, float32."""
    params = model.serving_parameters()

    def arr(p):
        return jnp.asarray(p.data, jnp.float32)

    return {"emb": arr(params["emb"]), "norm": arr(params["norm"]),
            "head": arr(params["head"]),
            "layers": [{k: arr(v) for k, v in layer.items()}
                       for layer in params["layers"]]}


def _weights(model):
    """... and in the reference's (the published) layout."""
    return REF.published_weights(_engine_layout(model), CFG)


@jax.jit
def ref_forward(weights, ids):
    return REF.forward(weights, ids, CFG)


@pytest.fixture(scope="module")
def eager(model):
    """(the model's own eager logits, the reference's) of one sequence
    that runs past the window."""
    ids = np.random.default_rng(0).integers(0, 96, (1, 24))
    return (model(paddle.to_tensor(ids)).numpy(),
            np.asarray(ref_forward(_weights(model), ids)))


def test_model_matches_the_reference_logits(eager):
    got, want = eager
    # both float32 at "highest": only the order of float32 sums differs
    # (measured 2e-5 on logits of size ~40)
    np.testing.assert_allclose(got, want, rtol=0, atol=3e-4)


def test_the_layouts_handed_to_the_engine_are_undone_exactly(model):
    """`published_layer` gives back the stored parameters bit for bit:
    interleaved columns, four shared experts apart, no 1/4."""
    pub = _weights(model)
    for layer, w in zip(model.layers, pub["layers"]):
        a, f = layer.self_attn, layer.mlp
        for name, p in (("wq", a.q_proj), ("wk", a.k_proj),
                        ("wv", a.v_proj), ("wo", a.o_proj),
                        ("sh_g", f.shared_gate), ("sh_u", f.shared_up),
                        ("sh_d", f.shared_down), ("router", f.router)):
            np.testing.assert_array_equal(np.asarray(w[name]),
                                          np.asarray(p.data), err_msg=name)
    eng_w = _engine_layout(model)
    np.testing.assert_array_equal(np.asarray(eng_w["head"]),
                                  np.asarray(model.embed_tokens.data).T)
    # a rotating layer's wq really is permuted, a full layer's is not
    assert not np.array_equal(np.asarray(eng_w["layers"][0]["wq"]),
                              np.asarray(model.layers[0].self_attn
                                         .q_proj.data))
    np.testing.assert_array_equal(
        np.asarray(eng_w["layers"][3]["wq"]),
        np.asarray(model.layers[3].self_attn.q_proj.data))
    assert "ln2" not in eng_w["layers"][0]
    assert "router_bias" not in eng_w["layers"][0]


def test_interleaved_rotation_is_half_split_on_deinterleaved_weights():
    rng = np.random.default_rng(1)
    s, h, nh, nkv, d = 12, 32, 4, 2, 16
    x = jnp.asarray(rng.normal(size=(s, h)), jnp.float32)
    wq = jnp.asarray(rng.normal(size=(h, nh * d)), jnp.float32)
    wk = jnp.asarray(rng.normal(size=(h, nkv * d)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        q = REF.rope_interleaved((x @ wq).reshape(s, nh, d), 50000.0)
        k = REF.rope_interleaved((x @ wk).reshape(s, nkv, d), 50000.0)
        want = jnp.einsum("qhd,khd->hqk", q, jnp.repeat(k, 2, 1))
        # the engine's rotation (sparse_attention.rope_half pairs dim i
        # with i + d/2) on the de-interleaved projections
        from paddle_tpu.ops.sparse_attention import rope_half
        cos, sin = _tables(s, d, 50000.0)
        q2 = rope_half((x @ C.deinterleave(wq, nh, d)).reshape(s, nh, d),
                       cos[:, None], sin[:, None])
        k2 = rope_half((x @ C.deinterleave(wk, nkv, d)).reshape(s, nkv, d),
                       cos[:, None], sin[:, None])
        got = jnp.einsum("qhd,khd->hqk", q2, jnp.repeat(k2, 2, 1))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=1e-4)
    # and the inverse is the inverse
    np.testing.assert_array_equal(
        np.asarray(REF.interleave(C.deinterleave(wq, nh, d), nh, d)),
        np.asarray(wq))


def _tables(s, d, theta):
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.outer(np.arange(s, dtype=np.float64), inv)
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def test_the_folded_shared_expert_is_the_mean_of_four(model):
    f = model.layers[0].mlp
    x = jnp.asarray(np.random.default_rng(2).normal(size=(10, 64)),
                    jnp.float32)
    w = {k: v.data for k, v in f.serving_weights().items()}
    assert w["ws_g"].shape == (64, 4 * 32) and w["ws_d"].shape == (128, 64)
    with jax.default_matmul_precision("highest"):
        got = la.swiglu(x, w["ws_g"], w["ws_u"], w["ws_d"])
        want = REF.shared(x, {"sh_g": f.shared_gate.data,
                              "sh_u": f.shared_up.data,
                              "sh_d": f.shared_down.data})
        one = REF.swiglu(x, f.shared_gate.data[0], f.shared_up.data[0],
                         f.shared_down.data[0])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=2e-5)
    assert float(jnp.max(jnp.abs(want - one))) > 0.05   # a mean, not one


@pytest.fixture(scope="module")
def served(model):
    """Four prompts through the engine (chunks of 8 over pages of 4, a
    window of 8): one ends under the window, one crosses it inside its
    second chunk, two run far past it (pages freed behind it, five and
    ten of them); every logits row the engine selected a token from
    captured with its request and position."""
    eng = ContinuousBatchingEngine(model, max_len=64, page_size=4,
                                   max_batch=4, prefill_chunk=8,
                                   prefix_cache=False)
    seen = []
    select = eng._select_tokens

    def spy(rows, positions, mode, logits=None, **kw):
        for i, r in enumerate(rows):
            if r is not None:
                seen.append((r.uid, int(positions[i]) - 1,
                             np.asarray(logits[i], np.float32)))
        return select(rows, positions, mode, logits=logits, **kw)

    eng._select_tokens = spy
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 96, n) for n in (5, 13, 29, 50)]
    anything = SamplingParams(grammar=TokenMaskAutomaton.trivial(96))
    uids = [eng.add_request(p, max_new_tokens=8, sampling=anything)
            for p in prompts]
    eng.drain()
    return eng, {u: eng.result(u) for u in uids}, seen


# float32 engine against float32 reference: the same products in another
# order of sums (paged online softmax and key blocks against a dense
# softmax, a grouped product against a loop over experts, one folded
# shared SwiGLU against four). Measured worst 4e-5 on logits of size ~40;
# 1e-3 leaves room and is far under what a sequential wiring (~10), a
# rotated full layer (~1) or a bf16 router (a changed expert: ~1) moves.
TOL = 1e-3


def test_engine_through_the_cache_matches_the_reference(model, served):
    eng, results, seen = served
    assert len(seen) == 4 * 8
    ids = np.zeros((len(results), 58), np.int64)
    for row, full in enumerate(results.values()):
        ids[row, :full.size] = full
    want = np.asarray(ref_forward(_weights(model), ids))
    row_of = {uid: row for row, uid in enumerate(results)}
    worst = max(float(np.max(np.abs(got - want[row_of[uid], pos])))
                for uid, pos, got in seen)
    assert worst < TOL, worst
    # greedy under the neutral chain: the tokens are the reference's too
    for uid, pos, got in seen:
        assert int(np.argmax(got)) == int(
            np.argmax(want[row_of[uid], pos]))


@pytest.fixture(scope="module")
def greedy(model, served):
    """Two of the probed prompts again as plain greedy requests."""
    _, results, _ = served
    eng = ContinuousBatchingEngine(model, max_len=64, page_size=4,
                                   max_batch=2, prefill_chunk=8,
                                   prefix_cache=False)
    fulls = list(results.values())[1:3]
    uids = [eng.add_request(full[:full.size - 8], max_new_tokens=8)
            for full in fulls]
    eng.drain()
    return eng, fulls, [eng.result(u) for u in uids]


def test_a_greedy_stream_equals_the_probed_one(greedy):
    """The greedy step program (token on the device, dispatch ahead)
    gives the stream the materializing arm gave."""
    eng, fulls, got = greedy
    for full, mine in zip(fulls, got):
        np.testing.assert_array_equal(mine, full)
    assert eng.health()["ahead"]["dispatched"] > 0


@pytest.mark.parametrize("fault", ["sequential_block", "rotated_full_layer",
                                   "half_sum_shared", "bf16_router",
                                   "rms_norm"])
def test_the_tolerance_catches_a_fault(model, fault, monkeypatch):
    """A forward pass wired sequentially, with the full layers rotated,
    with the other reading of "average", with the router on bf16-rounded
    operands or with an RMSNorm parts from the true one by more than TOL:
    the comparison above would fail on each."""
    weights = _weights(model)
    ids = np.random.default_rng(9).integers(0, 96, (1, 56))
    true = np.asarray(jax.jit(lambda w: REF.forward(w, ids, CFG))(weights))
    cfg = CFG
    if fault == "sequential_block":
        def block(h, w, cfg, kind, choices=False):
            # x + a, then the FFN on LayerNorm(x + a): the wrong wiring
            zero = dict(w, w_gu=jnp.zeros_like(w["w_gu"]),
                        sh_g=jnp.zeros_like(w["sh_g"]))
            mid = real_block(h, zero, cfg, kind)            # x + a
            n = REF.layer_norm(mid, w["ln1"], cfg["layer_norm_eps"])
            y, _ = REF.routed(n, w, REF.held_experts(cfg),
                              cfg["num_experts_per_tok"])
            return mid + y + REF.shared(n, w)
        real_block = REF.block
        monkeypatch.setattr(REF, "block", block)
    elif fault == "rotated_full_layer":
        cfg = dict(CFG, layer_types=["sliding_attention"] * 8,
                   sliding_window=10 ** 6)
    elif fault == "half_sum_shared":
        shared, routed = REF.shared, REF.routed
        monkeypatch.setattr(REF, "routed", lambda *a: (
            routed(*a)[0] / 2, routed(*a)[1]))
        monkeypatch.setattr(REF, "shared", lambda x, w: shared(x, w) / 2)
    elif fault == "bf16_router":
        router = REF.router

        def rounded(a):
            return a.astype(jnp.bfloat16).astype(jnp.float32)

        monkeypatch.setattr(REF, "router", lambda x, w, k: router(
            rounded(x), rounded(w), k))
    else:
        monkeypatch.setattr(REF, "layer_norm", lambda x, w, eps: (
            x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * w))
    bad = np.asarray(jax.jit(lambda w: REF.forward(w, ids, cfg))(weights))
    assert float(np.max(np.abs(bad - true))) > 2 * TOL


def test_a_position_free_layer_ignores_positions(model, served):
    eng = served[0]
    W = eng.weights
    h = jnp.asarray(np.random.default_rng(3).normal(size=(1, 6, 64)),
                    jnp.float32)
    near = jnp.arange(6)[None, :]
    far = near + 40
    q0, k0, _ = eng._layer_qkv(W, W["layers"][3], h, near, li=3)
    q1, k1, _ = eng._layer_qkv(W, W["layers"][3], h, far, li=3)
    np.testing.assert_array_equal(np.asarray(q0), np.asarray(q1))
    np.testing.assert_array_equal(np.asarray(k0), np.asarray(k1))
    q0, k0, _ = eng._layer_qkv(W, W["layers"][0], h, near, li=0)
    q1, k1, _ = eng._layer_qkv(W, W["layers"][0], h, far, li=0)
    assert float(jnp.max(jnp.abs(q0 - q1))) > 0.1
    # one rope table for the whole model: the full layers have none
    assert "rope" not in W and W["cos"].shape == (64, 8)
    assert eng._layer_rope == (0, 0, 0, None) * 2


@pytest.fixture(scope="module")
def shares(model):
    """(x + attention + shared experts, counted once; the sum of the four
    chips' expert shares; their row counts; the uncut layer)."""
    w = _weights(model)["layers"][1]
    x = jnp.asarray(np.random.default_rng(3).normal(size=(20, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut = REF.block(x, w, CFG, "sliding_attention")   # all 8 held
        none = dict(w, w_gu=jnp.zeros_like(w["w_gu"]))
        once = REF.block(x, none, CFG, "sliding_attention")  # x + a + shared
        n = REF.layer_norm(x, w["ln1"], 1e-5)
    parts, rows = 0, []
    for lo in range(0, 8, 2):                       # four chips' shares
        y, r = routed_experts(n, w["router"], None, w["w_gu"][lo:lo + 2],
                              w["w_d"][lo:lo + 2], (lo, lo + 2), 2,
                              interpret=True)
        parts = parts + y
        rows.append(r)
    return once, parts, rows, uncut


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer(shares):
    """The guide's share test: what all 4 shares of the experts give,
    with attention and the shared experts counted ONCE, is the uncut
    reference's whole layer."""
    once, parts, rows, uncut = shares
    np.testing.assert_allclose(np.asarray(once + parts), np.asarray(uncut),
                               rtol=0, atol=2e-5)
    assert float(jnp.max(jnp.abs(parts))) > 0.1
    assert int(sum(map(jnp.sum, rows))) == 20 * 2   # every row, once


def _dense_chunk(q, k_all, v_all, pos, a, sink):
    """[chunk, H, dv] by one dense softmax over every key (float64)."""
    q, k_all, v_all = (np.asarray(t, np.float64) for t in (q, k_all, v_all))
    rep = a.n_heads // a.n_kv_heads
    k_all, v_all = np.repeat(k_all, rep, 1), np.repeat(v_all, rep, 1)
    lg = np.einsum("qhd,khd->hqk", q, k_all) / np.sqrt(a.qk_dim)
    kpos = np.arange(k_all.shape[0])[None, None, :]
    qpos = np.asarray(pos)[None, :, None]
    seen = kpos <= qpos
    if a.window is not None:
        seen &= kpos > qpos - a.window
    lg = np.where(seen, lg, -np.inf)
    m = lg.max(-1, keepdims=True)
    if sink is not None:
        m = np.maximum(m, np.asarray(sink)[:, None, None])
    e = np.exp(lg - m)
    den = e.sum(-1, keepdims=True)
    if sink is not None:
        den = den + np.exp(np.asarray(sink)[:, None, None] - m)
    return np.einsum("hqk,khd->qhd", e / den, v_all)


@pytest.mark.parametrize("window,sink,flat,t_start", [
    (None, False, False, 0), (None, False, False, 40), (8, False, False, 40),
    (8, True, True, 24), (20, True, False, 40), (None, True, True, 16),
    (4096, False, False, 40)])
def test_chunk_attention_in_key_blocks(window, sink, flat, t_start):
    """`attend_chunk_blocks` against one dense softmax: full and window
    layers, a sink, flat keys of a width that is no value width, a window
    wider than the context; the pages behind a window are never read."""
    rng = np.random.default_rng(4)
    H, G, dk, dv, p, mp, chunk = 8, 2, 24, 16, 4, 16, 8
    a = AttentionSpec(H, G, dk, dv, dk, 1e4, window=window, sink=sink)
    t_end = t_start + chunk - 2             # two padded rows
    k_all = rng.normal(size=(mp * p, G, dk))
    v_all = rng.normal(size=(mp * p, G, dv))
    tab = rng.permutation(mp + 3)[:mp]
    kp = np.zeros(((mp + 3), p, G, dk))
    vp = np.zeros(((mp + 3), p, G, dv))
    kp[tab] = k_all.reshape(mp, p, G, dk)
    vp[tab] = v_all.reshape(mp, p, G, dv)
    if window is not None and t_start - window >= p:   # freed, reused
        dead = tab[:(t_start - window + 1) // p]
        kp[dead] = np.nan
        vp[dead] = np.nan
    if flat:
        kp = kp.reshape(mp + 3, p, G * dk)
    q = jnp.asarray(rng.normal(size=(chunk, H, dk)), jnp.float32)
    pos = t_start + jnp.arange(chunk)
    sk = jnp.asarray(rng.normal(size=(H,)), jnp.float32) if sink else None
    got = jax.jit(lambda *t: sparse_heads.attend_chunk_blocks(
        *t, a, p, sk))(q, jnp.asarray(kp, jnp.float32),
                       jnp.asarray(vp, jnp.float32),
                       jnp.asarray(tab, jnp.int32), pos, jnp.int32(t_end))
    want = _dense_chunk(q, k_all, v_all, pos, a, sk)
    real = t_end - t_start
    np.testing.assert_allclose(np.asarray(got)[:real], want[:real],
                               rtol=0, atol=2e-5)
    assert np.all(np.isfinite(np.asarray(got)))


@pytest.mark.parametrize("window,sink,t_start,chunk,rows", [
    (None, False, 0, 16, 8192), (None, False, 40, 16, 8192),
    (8, False, 40, 16, 8192), (20, True, 32, 32, 8192),
    (None, True, 16, 16, 8192), (4096, False, 40, 16, 8192),
    # several query blocks a chunk (tq 8): each walks to ITS last page
    (None, False, 24, 32, 32), (12, True, 40, 32, 32)])
def test_chunk_attention_kernel(window, sink, t_start, chunk, rows,
                                monkeypatch):
    """The Pallas kernel (interpreted) against one dense softmax, at a
    head width that fills the lanes: what `attend_chunk` hands it."""
    monkeypatch.setattr(chunk_attention, "ROWS", rows)
    rng = np.random.default_rng(4)
    H, G, d, p, mp = 4, 2, 128, 8, 12
    a = AttentionSpec(H, G, d, d, d, 1e4, window=window, sink=sink)
    t_end = t_start + chunk - 3             # three padded rows
    k_all = rng.normal(size=(mp * p, G, d))
    v_all = rng.normal(size=(mp * p, G, d))
    tab = rng.permutation(mp + 3)[:mp]
    kp = np.zeros((mp + 3, p, G, d))
    vp = np.zeros((mp + 3, p, G, d))
    kp[tab] = k_all.reshape(mp, p, G, d)
    vp[tab] = v_all.reshape(mp, p, G, d)
    if window is not None and t_start - window >= p:   # freed, reused
        dead = tab[:(t_start - window + 1) // p]
        kp[dead] = np.nan
        vp[dead] = np.nan
    q = jnp.asarray(rng.normal(size=(chunk, H, d)), jnp.float32)
    pos = t_start + jnp.arange(chunk)
    sk = jnp.asarray(rng.normal(size=(H,)), jnp.float32) if sink else None
    assert chunk_attention.query_block(chunk, H) == min(chunk, rows // H)
    got = jax.jit(lambda *t: sparse_heads.attend_chunk(
        *t, a, p, sk, interpret=True))(
            q, jnp.asarray(kp, jnp.float32), jnp.asarray(vp, jnp.float32),
            jnp.asarray(tab, jnp.int32), pos, jnp.int32(t_end))
    want = _dense_chunk(q, k_all, v_all, pos, a, sk)
    real = t_end - t_start
    np.testing.assert_allclose(np.asarray(got)[:real], want[:real],
                               rtol=0, atol=2e-5)
    assert np.all(np.isfinite(np.asarray(got)))


def test_which_chunk_attention_runs_is_read_off_the_shapes(monkeypatch):
    """Pools by head with lane-filling widths take the kernel; flat keys,
    a narrow head or a chunk no query block divides take the XLA blocks."""
    calls = []
    monkeypatch.setattr(sparse_heads, "paged_chunk_attention",
                        lambda q, *t, **kw: calls.append("kernel") or q)
    monkeypatch.setattr(sparse_heads, "attend_chunk_blocks",
                        lambda q, *t: calls.append("blocks") or q)
    for d, flat, chunk in ((128, False, 16), (128, True, 16),
                           (16, False, 16), (128, False, 12)):
        a = AttentionSpec(4, 2, d, d, d, 1e4)
        kp = jnp.zeros((3, 8, 2 * d) if flat else (3, 8, 2, d))
        sparse_heads.attend_chunk(
            jnp.zeros((chunk, 4, d)), kp, jnp.zeros((3, 8, 2, d)),
            jnp.zeros(3, jnp.int32), jnp.arange(chunk), 5, a, 8)
    assert calls == ["kernel", "blocks", "blocks", "blocks"]


@pytest.fixture(scope="module")
def wide_heads():
    """One period at head width 128 (pools by head, not flat: the chunk
    kernel's shape) through the engine: (results, logits rows seen, the
    reference's logits, health)."""
    cfg = dict(CFG, num_hidden_layers=4, num_attention_heads=4,
               num_key_value_heads=2, head_dim=128,
               layer_types=CFG["layer_types"][:4])
    paddle.seed(21)
    m = Cohere2MoeForCausalLM(REF.model_config(cfg))
    m.eval()
    eng = ContinuousBatchingEngine(m, max_len=64, page_size=4, max_batch=2,
                                   prefill_chunk=8, prefix_cache=False)
    assert not any(g.k_flat for g in eng.groups)
    seen = []
    select = eng._select_tokens

    def spy(rows, positions, mode, logits=None, **kw):
        for i, r in enumerate(rows):
            if r is not None:
                seen.append((r.uid, int(positions[i]) - 1,
                             np.asarray(logits[i], np.float32)))
        return select(rows, positions, mode, logits=logits, **kw)

    eng._select_tokens = spy
    rng = np.random.default_rng(8)
    anything = SamplingParams(grammar=TokenMaskAutomaton.trivial(96))
    prompts = [rng.integers(0, 96, n) for n in (11, 37)]
    uids = [eng.add_request(p, max_new_tokens=4, sampling=anything)
            for p in prompts]
    eng.drain()
    results = {u: eng.result(u) for u in uids}
    ids = np.zeros((2, 41), np.int64)
    for row, full in enumerate(results.values()):
        ids[row, :full.size] = full
    weights = REF.published_weights(_engine_layout(m), cfg)
    want = np.asarray(jax.jit(
        lambda w: REF.forward(w, ids, cfg))(weights))
    return results, seen, want, eng.health()


def test_the_engine_through_the_chunk_kernel_matches_the_reference(
        wide_heads):
    results, seen, want, h = wide_heads
    assert len(seen) == 2 * 4
    row_of = {uid: row for row, uid in enumerate(results)}
    worst = max(float(np.max(np.abs(got - want[row_of[uid], pos])))
                for uid, pos, got in seen)
    assert worst < TOL, worst
    # the chunks' (query, key) pairs x layers, as the program counted them
    win, full = h["page_groups"]
    assert full["prefill_pairs"] == sum(n * (n + 1) // 2 for n in (11, 37))
    assert win["prefill_pairs"] == 3 * sum(
        min(i, 8) for n in (11, 37) for i in range(1, n + 1))


def _all_avals(jaxpr):
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield v.aval
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _all_avals(sub)


def test_the_chunk_prefill_repeats_nothing_and_holds_no_whole_context(
        model):
    """The engine's chunk program of this model: no value in it is as
    large as [heads, chunk, max_len] logits, and none has K or V at the
    query head count over the context."""
    eng = ContinuousBatchingEngine(model, max_len=256, page_size=4,
                                   max_batch=2, prefill_chunk=8,
                                   prefix_cache=False)
    fn = eng._build_cb_prefill(8)
    args = (eng.weights, jnp.zeros((1, 8), jnp.int64), eng.k_pages,
            eng.v_pages, jnp.zeros((1, eng.max_pages_per_seq), jnp.int32),
            jnp.int32(0), jnp.int32(8))
    jaxpr = jax.make_jaxpr(lambda *t: fn(*t))(*args)
    heads, chunk, keys = 8, 8, 256
    pools = {tuple(k.shape) for k in eng.k_pages + eng.v_pages}
    big = [a for a in _all_avals(jaxpr.jaxpr)
           if hasattr(a, "shape") and tuple(a.shape) not in pools
           and int(np.prod(a.shape)) >= heads * chunk * keys // 2]
    # what is that large: the weights' own shapes and pool reshapes only
    assert all(a.shape[-1] in (64, 96, 16, 2 * 16) or a.ndim <= 2
               for a in big), [a.shape for a in big]
    assert not [a for a in _all_avals(jaxpr.jaxpr)
                if hasattr(a, "shape") and a.ndim >= 3
                and heads in a.shape and keys in a.shape]


@pytest.fixture(scope="module")
def streamed(model):
    """Three ragged requests through a two-slot engine: (the engine, the
    most window pages any sequence held, the most the group had in use,
    its health afterwards)."""
    eng = ContinuousBatchingEngine(model, max_len=64, page_size=4,
                                   max_batch=2, prefill_chunk=8,
                                   prefix_cache=False)
    g_win = eng.groups[0]
    rng = np.random.default_rng(6)
    for n in (40, 9, 25):
        eng.add_request(rng.integers(0, 96, n), max_new_tokens=6)
    held_most = used_most = 0
    while eng.step():
        for r in eng._slots:
            if r is not None:
                held_most = max(held_most,
                                len(r.more_pages.get(g_win.index, {})))
        used_most = max(used_most, g_win.used)
    return eng, held_most, used_most, eng.health()


def test_two_groups_of_one_head_shape_that_differ_only_in_window(
        model, streamed):
    desc = describe(model)
    assert desc.norm == "layer" and not desc.plain
    assert all(l.parallel for l in desc.layers)
    win, full = desc.groups
    assert win._replace(window=None) == full and win.window == 8
    assert desc.layer_group == (0, 0, 0, 1) * 2
    eng, held_most, used_most, h = streamed
    g_win, g_full = eng.groups
    assert g_win.n_pages == 2 * g_win.bound(1) + 2 and g_win.bound(1) == 4
    assert g_full.n_pages == 2 * 16
    # a sequence never holds more than the window and one chunk need
    assert 3 <= held_most <= g_win.bound(8) and used_most <= g_win.n_pages
    assert h["pages_free"] == h["pages_total"]
    hw, hf = h["page_groups"]
    assert hw["freed_behind_window"] > 0 and hf["freed_behind_window"] == 0
    # cached tokens the decode queries read: 6 window layers see at most
    # 8 tokens a query, 2 full layers the whole context
    steps = 3 * 6 - 3           # a request's first token is its prefill's
    assert hw["kv_tokens_read"] == 6 * 8 * steps
    ctxs = [n + j for n in (40, 9, 25) for j in range(1, 6)]
    assert hf["kv_tokens_read"] == 2 * sum(ctxs)
    # pages the paged decode kernel walked (pages of 4): a query's live
    # pages [first, last) a layer, from the same lengths; beside the
    # tokens read they give the walk's overhead in page bytes
    assert hf["kv_pages_walked"] == 2 * sum(-(-c // 4) for c in ctxs)
    assert hw["kv_pages_walked"] == 6 * sum(
        -(-c // 4) - (c - 8) // 4 for c in ctxs)
    eng.health()                # samples the engine's counters
    sample = profiler.counter_history("engine")[-1][1]
    assert sample["group0.kv_pages_walked"] == hw["kv_pages_walked"]
    assert sample["group1.kv_pages_walked"] == hf["kv_pages_walked"]
    assert h["paged_decode"] == {
        "grid_steps_per_layer": 2, "pages": "live",
        "mm_operand_dtype": "float32"}
    assert h["experts"]["decode_steps"] == eng.decode_steps


@pytest.mark.parametrize("kw,what", [
    ({"prefix_cache": True}, "prefix_cache"),
    ({"speculate": 4}, "speculate"),
    ({"kv_tier": "host"}, "kv_tier"),
    ({"tp": 2}, "tp"),
    ({"adapters": True}, "adapters"),
    ({"decode_block": 4}, "decode_block"),
    ({"megakernel": True}, "megakernel"),
    ({"quant": "int8"}, "quant"),
])
def test_each_unsupported_combination_raises_its_typed_error(model, kw,
                                                             what):
    base = dict(max_len=64, page_size=4, max_batch=2, prefix_cache=False)
    base.update(kw)
    with pytest.raises(UnsupportedByDescription, match=what):
        ContinuousBatchingEngine(model, **base)


class _Described:
    """A model that only changes what it says of itself."""

    def __init__(self, model, change):
        self._m, self._change = model, change
        self.config = model.config

    def eval(self):
        return self

    def serving_description(self):
        return self._change(self._m.serving_description())

    def serving_parameters(self):
        return self._m.serving_parameters()


@pytest.mark.parametrize("change,match", [
    (lambda d: dataclasses.replace(d, norm="batch"), "norm"),
    (lambda d: dataclasses.replace(d, layers=tuple(
        dataclasses.replace(l, attn=dataclasses.replace(
            l.attn, indexer=IndexerSpec(2, 8, 8, 4))) for l in d.layers)),
     "indexer"),
], ids=["unknown_norm", "parallel_block_with_an_indexer"])
def test_what_the_description_cannot_have_is_refused_typed(model, change,
                                                           match):
    with pytest.raises(UnsupportedByDescription, match=match):
        ContinuousBatchingEngine(_Described(model, change), max_len=64,
                                 page_size=4, max_batch=2,
                                 prefix_cache=False)


def test_the_config_refuses_what_is_not_built():
    for kw in ({"use_parallel_block": False}, {"use_qk_norm": True},
               {"first_k_dense_replace": 1}, {"rotary_pct": 0.5},
               {"tie_word_embeddings": False}):
        with pytest.raises(ValueError, match="not built"):
            Cohere2MoeConfig.tiny(**kw)
    cfg = Cohere2MoeConfig.tiny()
    assert cfg.layer_types == (["sliding_attention"] * 3
                               + ["full_attention"]) * 2
