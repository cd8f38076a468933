"""The hybrid block (window + full attention, key width != value width,
partial rotary, sinks, routed experts held by share) end to end at tiny
widths on the CPU: model against the plain reference, the serving engine
through its cache against the reference's full forward, the kernels
against their XLA references, the page groups, and the typed refusals.
"""
import importlib.util
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.inference.sampling import (SamplingParams,
                                           TokenMaskAutomaton)
from paddle_tpu.inference.description import (UnsupportedByDescription,
                                              describe)
from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM, MiMoV2Config,
                               MiMoV2ForCausalLM)
from paddle_tpu.ops.moe import routed_experts
from paddle_tpu.ops.pallas.grouped_matmul import (grouped_matmul,
                                                  grouped_matmul_reference)
from paddle_tpu.ops.pallas.paged_attention import (
    paged_attention, paged_attention_reference, ragged_paged_attention,
    ragged_paged_attention_reference)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    spec = importlib.util.spec_from_file_location(
        "perf_reference_mimo_v2",
        os.path.join(ROOT, "perf", "references", "mimo_v2.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()

# the tiny configuration as a configuration FILE's keys (what the
# reference reads), the experts all held
CFG = {
    "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 1,
    "swa_num_key_value_heads": 2, "head_dim": 24, "v_head_dim": 16,
    "partial_rotary_factor": 0.334, "rope_theta": 1e7,
    "swa_rope_theta": 1e4, "sliding_window": 8,
    "attention_value_scale": 0.707,
    "add_full_attention_sink_bias": False,
    "add_swa_attention_sink_bias": True,
    "hybrid_layer_pattern": [0, 1, 1, 0], "moe_layer_freq": [0, 1, 1, 1],
    "n_routed_experts": 8, "num_experts_per_tok": 2,
    "layernorm_epsilon": 1e-5, "vocab_size": 96,
    "max_position_embeddings": 128}


@pytest.fixture(scope="module")
def model():
    paddle.seed(11)
    m = MiMoV2ForCausalLM(REF.model_config(CFG))
    m.eval()
    return m


def _weights(model):
    """The model's parameters in the reference's layout, float32."""
    params = model.serving_parameters()

    def arr(p):
        return jnp.asarray(p.data, jnp.float32)

    return {"emb": arr(params["emb"]), "norm": arr(params["norm"]),
            "head": arr(params["head"]),
            "layers": [{k: arr(v) for k, v in layer.items()}
                       for layer in params["layers"]]}


@jax.jit
def ref_forward(weights, ids):
    return REF.forward(weights, ids, CFG)


def test_model_matches_the_reference_logits(model):
    ids = np.random.default_rng(0).integers(0, 96, (1, 16))
    got = model(paddle.to_tensor(ids)).numpy()
    want = np.asarray(ref_forward(_weights(model), ids))
    # both float32; the model multiplies at the package's "highest" too:
    # only the order of float32 sums differs
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-4)


@pytest.fixture(scope="module")
def served(model):
    """Four prompts through the engine (chunks of 16 over pages of 8: a
    chunk crosses the window of 8 and two pages; the longest prompt five
    pages), every logits row the engine selected a token from captured
    with its request and position."""
    eng = ContinuousBatchingEngine(model, max_len=96, page_size=8,
                                   max_batch=4, prefill_chunk=16,
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
    prompts = [rng.integers(0, 96, n) for n in (7, 19, 42, 30)]
    uids = [eng.add_request(p, max_new_tokens=12) for p in prompts]
    eng.drain()
    results = {u: eng.result(u) for u in uids}
    # a greedy step program keeps its logits on the device (PR 31): the
    # rows come from serving the prompts again under a neutral processor
    # chain, the arm that materializes them, token for token the same
    assert not seen
    anything = SamplingParams(grammar=TokenMaskAutomaton.trivial(96))
    again = [eng.add_request(p, max_new_tokens=12, sampling=anything)
             for p in prompts]
    eng.drain()
    for u, v in zip(uids, again):
        np.testing.assert_array_equal(results[u], eng.result(v))
    seen[:] = [(uids[again.index(v)], pos, row) for v, pos, row in seen]
    return eng, results, seen


# float32 engine against float32 reference: the same products in another
# order of sums (paged online softmax against a dense one, a grouped
# product against a loop over experts). Measured worst 3e-5 on logits of
# size ~2; 5e-4 leaves room and is still far under what a dropped sink
# (~0.1) or a bf16 router (a changed expert: ~0.3) moves a logit by
TOL = 5e-4


def test_engine_through_the_cache_matches_the_reference(model, served):
    eng, results, seen = served
    assert len(seen) == 4 * 12
    # the reference is causal: one padded batch serves every request
    ids = np.zeros((len(results), 56), np.int64)
    for row, full in enumerate(results.values()):
        ids[row, :full.size] = full
    want = np.asarray(ref_forward(_weights(model), ids))
    row_of = {uid: row for row, uid in enumerate(results)}
    worst = max(float(np.max(np.abs(got - want[row_of[uid], pos])))
                for uid, pos, got in seen)
    assert worst < TOL, worst


@pytest.mark.parametrize("fault", ["dropped_sink", "bf16_router"])
def test_the_tolerance_catches_a_fault(model, fault, monkeypatch):
    """A forward pass with the sink dropped, or with the router computed
    on bf16-rounded operands, parts from the true one by more than TOL:
    the comparison above would fail on either."""
    weights = _weights(model)
    ids = np.random.default_rng(9).integers(0, 96, (1, 120))
    true = np.asarray(jax.jit(lambda w: REF.forward(w, ids, CFG))(weights))
    if fault == "dropped_sink":
        weights = dict(weights, layers=[
            dict(w, sink=jnp.full_like(w["sink"], -1e9)) if "sink" in w
            else w for w in weights["layers"]])
    else:
        router = REF.router

        def rounded(a):
            return a.astype(jnp.bfloat16).astype(jnp.float32)

        monkeypatch.setattr(REF, "router", lambda x, w, top_k: router(
            rounded(x), dict(w, router=rounded(w["router"])), top_k))
    bad = np.asarray(jax.jit(lambda w: REF.forward(w, ids, CFG))(weights))
    assert float(np.max(np.abs(bad - true))) > 2 * TOL


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer():
    rng = np.random.default_rng(3)
    t, h, e, f, k = 24, 64, 16, 32, 4
    x = jnp.asarray(rng.normal(size=(t, h)), jnp.float32)
    w = {"router": jnp.asarray(rng.normal(size=(h, e)) / 8, jnp.float32),
         "router_bias": jnp.asarray(rng.normal(size=(e,)) * 0.1,
                                    jnp.float32),
         "w_gu": jnp.asarray(rng.normal(size=(e, h, 2 * f)) / 8,
                             jnp.float32),
         "w_d": jnp.asarray(rng.normal(size=(e, f, h)) / 6, jnp.float32)}
    with jax.default_matmul_precision("highest"):
        uncut, _ = REF.routed(x, w, (0, e), k)      # the whole layer
    parts = 0
    rows = []
    for lo in range(0, e, 4):                       # four chips' shares
        y, r = routed_experts(
            x, w["router"], w["router_bias"], w["w_gu"][lo:lo + 4],
            w["w_d"][lo:lo + 4], (lo, lo + 4), k, interpret=True)
        parts = parts + y
        rows.append(r)
    np.testing.assert_allclose(np.asarray(parts), np.asarray(uncut),
                               rtol=0, atol=1e-5)
    assert int(sum(map(jnp.sum, rows))) == t * k    # every row, once


def _pools(rng, n_pages, p, h_kv, dk, dv):
    return (jnp.asarray(rng.normal(size=(n_pages, p, h_kv, dk)),
                        jnp.float32),
            jnp.asarray(rng.normal(size=(n_pages, p, h_kv, dv)),
                        jnp.float32))


@pytest.mark.parametrize("flat", [False, True], ids=["heads", "flat"])
@pytest.mark.parametrize("window,sink", [(None, False), (8, False),
                                         (8, True), (20, True),
                                         (None, True)])
def test_decode_kernel_key_width_window_and_sink(window, sink, flat):
    rng = np.random.default_rng(1)
    b, h, h_kv, dk, dv, p, mp = 3, 4, 2, 24, 16, 8, 6
    kp, vp = _pools(rng, b * mp, p, h_kv, dk, dv)
    if flat:        # a page [p, h_kv * d]: how the engine keeps a key
        kp = kp.reshape(b * mp, p, h_kv * dk)   # width that is no 128s
    q = jnp.asarray(rng.normal(size=(b, h, dk)), jnp.float32)
    table = jnp.asarray(rng.permutation(b * mp).reshape(b, mp), jnp.int32)
    lens = jnp.asarray([5, 48, 23], jnp.int32)
    sinks = jnp.asarray(rng.normal(size=(h,)), jnp.float32) if sink \
        else None
    got = paged_attention(q, kp, vp, table, lens, interpret=True,
                          window=window, sinks=sinks, k_flat=flat)
    want = paged_attention_reference(q, kp, vp, table, lens,
                                     window=window, sinks=sinks,
                                     k_flat=flat)
    assert got.shape == (b, h, dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=2e-5)
    if window is not None:
        # pages behind the window are never read: poison them
        dead = np.asarray(table)[1, :(48 - window) // p]
        kp2 = kp.at[dead].set(jnp.nan)
        got2 = paged_attention(q, kp2, vp, table, lens, interpret=True,
                               window=window, sinks=sinks, k_flat=flat)
        np.testing.assert_array_equal(np.asarray(got2), np.asarray(got))


@pytest.mark.parametrize("window,sink", [(None, False), (8, True),
                                         (20, False), (None, True)])
def test_ragged_kernel_key_width_window_and_sink(window, sink):
    rng = np.random.default_rng(2)
    b, tq, h, h_kv, dk, dv, p, mp = 2, 4, 4, 2, 24, 16, 8, 6
    kp, vp = _pools(rng, b * mp, p, h_kv, dk, dv)
    q = jnp.asarray(rng.normal(size=(b, tq, h, dk)), jnp.float32)
    table = jnp.asarray(rng.permutation(b * mp).reshape(b, mp), jnp.int32)
    starts = jnp.asarray([3, 37], jnp.int32)
    ctx = starts + tq
    sinks = jnp.asarray(rng.normal(size=(h,)), jnp.float32) if sink \
        else None
    got = ragged_paged_attention(q, kp, vp, table, ctx, starts,
                                 interpret=True, window=window,
                                 sinks=sinks)
    want = ragged_paged_attention_reference(q, kp, vp, table, ctx, starts,
                                            window=window, sinks=sinks)
    assert got.shape == (b, tq, h, dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=2e-5)


@pytest.mark.parametrize("sizes,m", [([3, 0, 5, 0, 0, 7], 40),
                                     ([0, 0, 0], 16), ([40, 0, 24], 64),
                                     ([1, 1, 1, 1], 8), ([0, 33], 48)])
def test_grouped_product_against_an_einsum_with_empty_groups(sizes, m):
    rng = np.random.default_rng(4)
    lhs = jnp.asarray(rng.normal(size=(m, 256)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(len(sizes), 256, 384)), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    got = grouped_matmul(lhs, rhs, gs, tm=16, tn=128, tk=128,
                         interpret=True)
    want = grouped_matmul_reference(lhs, rhs, gs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=2e-4)
    assert not np.any(np.asarray(got[sum(sizes):]))     # no group: zeros


def test_window_group_frees_pages_keeps_its_bound_and_leaks_none(model):
    eng = ContinuousBatchingEngine(model, max_len=64, page_size=8,
                                   max_batch=2, prefill_chunk=16,
                                   prefix_cache=False)
    full, win = eng.groups
    assert (full.window, win.window) == (None, 8)
    assert len(full.layers) == 2 and len(win.layers) == 2
    assert win.n_pages == 2 * win.bound(1) + 2
    rng = np.random.default_rng(6)
    for n in (40, 9, 25):
        eng.add_request(rng.integers(0, 96, n), max_new_tokens=6)
    most = 0
    while eng.step():
        for r in eng._slots:
            if r is not None:
                held = r.more_pages.get(win.index, {})
                most = max(most, len(held))
                # at rest a sequence holds what ONE more token needs
                assert len(held) <= win.bound(1)
        assert win.used <= win.n_pages
    assert most >= 2
    h = eng.health()
    assert h["pages_free"] == h["pages_total"]
    assert h["pages_total"] == full.n_pages + win.n_pages
    g_full, g_win = h["page_groups"]
    assert g_full["freed_behind_window"] == 0
    assert g_win["freed_behind_window"] > 0 and g_win["pages_used"] == 0
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
    base = dict(max_len=64, page_size=8, max_batch=2, prefix_cache=False)
    base.update(kw)
    with pytest.raises(UnsupportedByDescription, match=what):
        ContinuousBatchingEngine(model, **base)


@pytest.mark.parametrize("call", ["generate", "export_kv_pages",
                                  "export_prefix_pages"])
def test_plain_only_calls_raise_typed(model, call):
    eng = ContinuousBatchingEngine(model, max_len=64, page_size=8,
                                   max_batch=2, prefix_cache=False)
    args = {"generate": (np.zeros((1, 4), np.int64),),
            "export_kv_pages": (0,),
            "export_prefix_pages": ([1, 2, 3],)}[call]
    with pytest.raises(UnsupportedByDescription):
        getattr(eng, call)(*args)


def test_the_description_is_the_seam():
    llama = LlamaForCausalLM(LlamaConfig.tiny())
    desc = describe(llama)
    assert desc.plain and len(desc.groups) == 1
    mimo = describe(MiMoV2ForCausalLM(MiMoV2Config.tiny()))
    assert not mimo.plain and mimo.has_experts
    assert mimo.layer_group == (0, 1, 1, 0)
    assert [g.window for g in mimo.groups] == [None, 8]
    with pytest.raises(TypeError, match="serving_description"):
        ContinuousBatchingEngine(object())
    # no check on a model's class is left in the engine
    for name in ("serving.py", "scheduler.py"):
        text = open(os.path.join(ROOT, "paddle_tpu", "inference",
                                 name)).read()
        assert "isinstance(model" not in text
        assert "LlamaForCausalLM" not in text
