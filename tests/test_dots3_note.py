"""The latent sparse block (latent attention whose full layers attend to a
learned top-k selection, latent window layers, a head-wise gate, routed
experts held by share plus a shared expert) end to end at tiny widths on
the CPU: model against the plain reference, the serving engine through its
cache against the reference's full forward, the absorbed form against the
expanded one, the selection against an argsort, the page groups, the
shares, and the typed refusals. The top-k (12) and the window (13, no
multiple of the page of 8) lie far below the contexts served.
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
from paddle_tpu.models import Dots3NoteConfig, Dots3NoteForCausalLM
from paddle_tpu.models.mimo_v2 import rope_tables
from paddle_tpu.ops import latent_attention as la
from paddle_tpu.ops import sparse_attention as sa
from paddle_tpu.ops.moe import routed_experts

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    spec = importlib.util.spec_from_file_location(
        "perf_reference_dots3_note",
        os.path.join(ROOT, "perf", "references", "dots3_note.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()

# the tiny configuration as a configuration FILE's keys (what the
# reference reads), the experts all held
CFG = {
    "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 4,
    "layer_types": ["full_attention", "full_attention",
                    "sliding_attention", "sliding_attention"],
    "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 16,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rope_theta": 8e7, "swa_num_attention_heads": 2,
    "swa_q_lora_rank": 24, "swa_kv_lora_rank": 32,
    "swa_qk_nope_head_dim": 24, "swa_qk_rope_head_dim": 8,
    "swa_v_head_dim": 16, "swa_rope_theta": 5e4,
    "sliding_window_size": 13, "index_n_heads": 4, "index_head_dim": 16,
    "index_topk": 12, "apply_mla_qkv_lora_rescale": True,
    "attention_gate_type": "headwise",
    "swa_attention_gate_type": "headwise", "first_k_dense_replace": 1,
    "n_routed_experts": 8, "n_shared_experts": 1,
    "num_experts_per_tok": 2, "routed_scaling_factor": 1,
    "rms_norm_eps": 1e-5, "vocab_size": 96,
    "max_position_embeddings": 128}


@pytest.fixture(scope="module")
def model():
    paddle.seed(11)
    m = Dots3NoteForCausalLM(REF.model_config(CFG))
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
    ids = np.random.default_rng(0).integers(0, 96, (1, 24))
    with paddle.no_grad():      # inference: nothing is linearized
        got = model(paddle.to_tensor(ids)).numpy()
    want = np.asarray(ref_forward(_weights(model), ids))
    # both float32 at "highest": only the order of float32 sums differs
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-4)


@pytest.fixture(scope="module")
def served(model):
    """Four prompts through the engine (chunks of 16 over pages of 8, a
    window of 13 and a top-k of 12: every chunk crosses pages, the longer
    prompts cross the window, the selection and several chunks), every
    logits row the engine selected a token from captured with its
    request and position."""
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
    prompts = [rng.integers(0, 96, n) for n in (7, 19, 42, 61)]
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
# order of sums (absorbed against expanded, a blocked online softmax
# against a dense one, a grouped product against a loop over experts).
# 5e-4 on logits of size ~3 leaves room over the measured worst and is
# far under what any of the four faults below moves a logit by
TOL = 5e-4


def test_engine_through_the_cache_matches_the_reference(model, served):
    eng, results, seen = served
    assert len(seen) == 4 * 12
    # the reference is causal: one padded batch serves every request
    ids = np.zeros((len(results), 80), np.int64)
    for row, full in enumerate(results.values()):
        ids[row, :full.size] = full
    want = np.asarray(ref_forward(_weights(model), ids))
    row_of = {uid: row for row, uid in enumerate(results)}
    worst = max(float(np.max(np.abs(got - want[row_of[uid], pos])))
                for uid, pos, got in seen)
    assert worst < TOL, worst


def test_engine_counts_the_selection_and_leaks_no_page(served):
    eng, results, _ = served
    h = eng.health()
    full, win = h["page_groups"]
    assert (full["kind"], full["row_width"], full["index_width"],
            full["window"]) == ("latent", 24, 16, None)
    assert (win["kind"], win["row_width"], win["index_width"],
            win["window"]) == ("latent", 40, 0, 13)
    assert h["pages_free"] == h["pages_total"]
    assert win["freed_behind_window"] > 0
    # no layer runs the paged decode kernel: nothing walked, no plan
    assert h["paged_decode"] is None
    assert full["kv_pages_walked"] == win["kv_pages_walked"] == 0
    # decode queries of the two full layers: position t sees t + 1 keys
    # and attends to min(t + 1, 12) of them
    visible = attended = queries = 0
    for full_ids in results.values():
        for t in range(full_ids.size - 12, full_ids.size - 1):
            visible += 2 * (t + 1)
            attended += 2 * min(t + 1, 12)
            queries += 2
    # (the fixture serves every prompt twice)
    visible, attended, queries = 2 * visible, 2 * attended, 2 * queries
    scored = h["sparse"].pop("index_keys_scored")
    assert h["sparse"] == {"keys_visible": visible,
                           "keys_attended": attended,
                           "decode_queries": queries}
    # the scan scores every table page (96 positions) of every slot of
    # the step's bucket (1 to 4 slots wide), live or not, in both layers
    assert scored % (2 * 96) == 0 and scored > visible
    assert eng.decode_steps <= scored // (2 * 96) <= 4 * eng.decode_steps
    assert h["experts"]["decode_steps"] == eng.decode_steps


@pytest.mark.parametrize("variant", ["no_selection", "no_gate",
                                     "no_rescale", "window_off_by_one"])
def test_the_tolerance_catches_a_wrong_variant(model, variant):
    """A forward pass that skips the selection, the gate or the rescale,
    or whose window is one short, parts from the true one by more than
    the tolerance: the comparison above would fail on each."""
    weights = _weights(model)
    ids = np.random.default_rng(9).integers(0, 96, (1, 60))
    true = np.asarray(ref_forward(weights, ids))
    bad = np.asarray(jax.jit(lambda w: REF.forward(
        w, ids, CFG, variant=variant))(weights))
    assert float(np.max(np.abs(bad - true))) > 2 * TOL


def _layer(model, li, s=48, seed=2):
    """(x [1, s, hidden] normed-like input, the layer's float32 weights,
    its AttentionSpec, cos, sin) for op-level checks."""
    w = _weights(model)["layers"][li]
    a = model.serving_description().layers[li].attn
    x = jnp.asarray(np.random.default_rng(seed).normal(size=(1, s, 64)),
                    jnp.float32)
    cos, sin = rope_tables(s, a.rope_dim, a.rope_theta)
    return x, w, a, cos, sin


@pytest.mark.parametrize("li", [1, 2])
def test_absorbed_attention_equals_the_expanded_form(model, li):
    """Queries carried into the latent space, scores against the cached
    rows, W_uv after the sum == per-head keys and values expanded from
    the rows (both over all causal keys of the last query)."""
    x, w, a, cos, sin = _layer(model, li)
    r = a.latent.kv_rank
    q_n, q_r, row, _ = la.latent_qkv(x, w, a, 1e-5, cos, sin)
    t = x.shape[1] - 1                           # the last query
    q_abs = la.absorb_query(q_n[:, t], q_r[:, t], w["w_uk"], a)
    valid = jnp.ones((1, t + 1), bool)
    o_lat = la.attend_rows(q_abs, row, valid, r, la.softmax_scale(a))
    got = la.expand_values(o_lat, w["w_uv"], a)[0]
    k_n = (row[0, :, :r] @ w["w_uk"]).reshape(t + 1, a.n_heads, -1)
    v = (row[0, :, :r] @ w["w_uv"]).reshape(t + 1, a.n_heads, a.v_dim)
    logits = (jnp.einsum("hd,khd->hk", q_n[0, t], k_n)
              + jnp.einsum("hd,kd->hk", q_r[0, t], row[0, :, r:])) \
        * la.softmax_scale(a)
    want = jnp.einsum("hk,khd->hd", jax.nn.softmax(logits, -1), v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=2e-5)


@pytest.mark.parametrize("how", ["top_k", "threshold"])
def test_selection_equals_an_argsort_of_the_reference_scores(model, how):
    """The decode path's lax.top_k and the prefill path's radix-select
    threshold both pick exactly the set a stable argsort of the
    reference's index scores picks, for every query of a sequence."""
    x, w, a, cos, sin = _layer(model, 1, s=56)
    kind = REF.layer_kinds(CFG)[1]
    with jax.default_matmul_precision("highest"):
        c_q = REF.rms_norm(x[0] @ w["wq_a"], w["q_norm"], 1e-5) \
            * a.latent.q_scale
        ref_scores = REF.index_scores(x[0], c_q, w, kind)
    s = x.shape[1]
    seen = jnp.asarray(np.tril(np.ones((s, s), bool)))
    want = np.asarray(REF.selection(ref_scores, seen, 12))
    _, _, _, c_q2 = la.latent_qkv(x, w, a, 1e-5, cos, sin)
    q_i, k_i, w_i = sa.index_qkw(x, c_q2, w, a.indexer, cos, sin)
    scores = sa.index_scores(q_i, k_i, w_i)[0]
    np.testing.assert_allclose(np.asarray(scores), np.asarray(ref_scores),
                               rtol=1e-5, atol=1e-4)
    if how == "top_k":
        idx, valid = (np.asarray(v) for v in sa.select_top(scores, seen, 12))
        got = np.zeros((s, s), bool)
        for t in range(s):
            got[t, idx[t][valid[t]]] = True
    else:
        got = np.asarray(seen & sa.top_mask(
            jnp.where(seen, scores, -jnp.inf), 12))
    assert want.sum() == sum(min(t + 1, 12) for t in range(s))
    assert np.array_equal(got, want)
    # exact zeros (every head's relu at rest) are ties: the 4 index
    # heads of this size make them common, and they break by position
    assert float(jnp.mean(scores == 0)) > 0.01


@pytest.mark.parametrize("n,s,k", [(3, 40, 7), (5, 64, 64), (2, 9, 12),
                                   (4, 33, 1)])
def test_kth_largest_is_the_sorted_kth(n, s, k):
    rng = np.random.default_rng(n * s + k)
    x = rng.normal(size=(n, s)).astype(np.float32) * 100
    x[:, ::5] = -np.inf                         # not visible
    x[0, 3] = x[0, 4] = 0.0                     # signed zero, a tie
    got = np.asarray(sa.kth_largest(jnp.asarray(x), k))
    want = np.sort(x, axis=1)[:, ::-1][:, min(k, s) - 1]
    assert np.array_equal(got, want)


def test_window_group_frees_pages_behind_an_odd_window(model):
    # one slot: one prefill and one step program to compile
    eng = ContinuousBatchingEngine(model, max_len=64, page_size=8,
                                   max_batch=1, prefill_chunk=16,
                                   prefix_cache=False)
    full, win = eng.groups
    assert (full.window, win.window) == (None, 13)
    assert full.latent and win.latent
    assert len(full.layers) == 2 and len(win.layers) == 2
    assert win.bound(1) == 3                 # ceil((13 + 1) / 8) + 1
    assert win.n_pages == 1 * win.bound(1) + 2  # slots x bound + a chunk
    # one row a token, padded to the lanes; the index keys beside the
    # full group's rows and nothing beside the window group's
    assert full.pool_shapes() == ((full.n_pages, 8, 128),
                                  (full.n_pages, 8, 16))
    assert win.pool_shapes() == ((win.n_pages, 8, 128), (0,))
    rng = np.random.default_rng(6)
    for n in (40, 9, 25):
        eng.add_request(rng.integers(0, 96, n), max_new_tokens=6)
    most = 0
    while eng.step():
        for r in eng._slots:
            if r is not None:
                held = r.more_pages.get(win.index, {})
                most = max(most, len(held))
                assert len(held) <= win.bound(1)
        assert win.used <= win.n_pages
    assert most >= 2
    h = eng.health()
    assert h["pages_free"] == h["pages_total"]
    assert h["page_groups"][1]["freed_behind_window"] > 0


def test_the_key_blocks_serve_where_the_kernel_cannot_tile(model, served,
                                                          monkeypatch):
    """Where Mosaic cannot tile a shape, the XLA key blocks run a chunk's
    attention (forced here): the fixture's prompts give the kernel's
    tokens."""
    from paddle_tpu.inference import latent
    _, results, _ = served
    monkeypatch.setattr(latent, "prefill_plan", lambda *a: None)
    eng = ContinuousBatchingEngine(model, max_len=96, page_size=8,
                                   max_batch=4, prefill_chunk=16,
                                   prefix_cache=False)
    assert {v["kernel"] for v in eng.health()["latent_prefill"].values()} \
        == {"attend_key_blocks"}
    rng = np.random.default_rng(5)
    uids = [eng.add_request(rng.integers(0, 96, n), max_new_tokens=12)
            for n in (7, 19, 42, 61)]
    eng.drain()
    for u, want in zip(uids, results.values()):
        np.testing.assert_array_equal(eng.result(u), want)
    assert eng.latent_prefill_live_steps == 0


def test_the_chunk_kernel_is_named_and_its_live_steps_counted(model):
    """`health()["latent_prefill"]` names the Pallas chunk kernel for both
    geometries, and `latent.prefill_live_steps` counts what the host books
    for a known prompt: 40 tokens in chunks of 16 over pages of 8."""
    from paddle_tpu import profiler
    eng = ContinuousBatchingEngine(model, max_len=96, page_size=8,
                                   max_batch=1, prefill_chunk=16,
                                   prefix_cache=False)
    facts = eng.health()["latent_prefill"]
    assert {k: (v["kernel"], v["tq"], v["pages_per_step"])
            for k, v in facts.items()} == {
        "full": ("paged_latent_chunk_attention", 16, 4),
        "window": ("paged_latent_chunk_attention", 16, 5)}
    eng.add_request(np.arange(40) % 96, max_new_tokens=1)
    eng.drain()
    eng.health()
    # one query block a chunk. Full layers walk from page 0 to the page
    # of the chunk's last position (1, 3, 4) four pages a step: 1 + 1 + 2
    # steps. Window layers (13) cover the pages of a block's window in
    # ONE step: 1 + 1 + 1. Two layers of each
    assert profiler.counter_history("engine")[-1][1][
        "latent.prefill_live_steps"] == 2 * 4 + 2 * 3


def test_the_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """Four chips hold two experts each: the routed parts of all four plus
    the shared expert counted ONCE give the reference's uncut layer."""
    rng = np.random.default_rng(3)
    t, h, e, f, k = 24, 64, 8, 32, 2

    def rand(*shape):
        return jnp.asarray(rng.normal(size=shape) / 8, jnp.float32)

    x = jnp.asarray(rng.normal(size=(t, h)), jnp.float32)
    w = {"router": rand(h, e), "router_bias": rand(e),
         "w_gu": rand(e, h, 2 * f), "w_d": rand(e, f, h),
         "ws_g": rand(h, f), "ws_u": rand(h, f), "ws_d": rand(f, h)}
    cfg = dict(CFG, n_routed_experts=e)
    kind = REF.layer_kinds(CFG)[1]
    with jax.default_matmul_precision("highest"):
        whole = REF.ffn(x, w, cfg, kind)
        total = la.swiglu(x, w["ws_g"], w["ws_u"], w["ws_d"])
        for lo in range(0, e, 2):
            part, rows = routed_experts(
                x, w["router"], w["router_bias"], w["w_gu"][lo:lo + 2],
                w["w_d"][lo:lo + 2], (lo, lo + 2), k, interpret=True)
            total = total + part
            # and one share alone is the reference's share, less the
            # shared expert
            want = REF.ffn(x, dict(w, w_gu=w["w_gu"][lo:lo + 2],
                                   w_d=w["w_d"][lo:lo + 2]),
                           dict(cfg, held_experts=[lo, lo + 2]), kind,
                           shared=False)
            np.testing.assert_allclose(np.asarray(part), np.asarray(want),
                                       rtol=0, atol=2e-5)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=0, atol=5e-5)


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


def test_the_description_is_the_seam(model):
    desc = describe(model)
    assert not desc.plain and desc.has_experts and desc.has_indexer
    assert desc.layer_group == (0, 0, 1, 1)
    # (kind, 1, row width, no value width, window, index key width)
    assert desc.groups == (("latent", 1, 24, 0, None, 16),
                           ("latent", 1, 40, 0, 13, 0))
    full, win = desc.layers[1].attn, desc.layers[2].attn
    assert (full.n_heads, win.n_heads) == (4, 2)    # heads differ by kind
    assert full.gate and win.gate and win.indexer is None
    assert full.indexer.top_k == 12
    assert abs(full.latent.kv_scale - (64 / 16) ** 0.5) < 1e-12
    assert [l.ffn.kind for l in desc.layers] == ["dense"] + ["experts"] * 3
    assert desc.layers[1].ffn.shared_width == 32
    # the published depth is cut by `layers_kept`, the list stays whole
    cut = Dots3NoteConfig.tiny(num_hidden_layers=2, layers_kept=[0, 3])
    assert [cut.layer_spec(l).attn.window for l in range(2)] == [None, 13]
    assert cut.layer_spec(1).ffn.kind == "experts"
    # no check on a model's class is in the engine
    for name in ("serving.py", "scheduler.py", "latent.py"):
        text = open(os.path.join(ROOT, "paddle_tpu", "inference",
                                 name)).read()
        assert "isinstance(model" not in text
        assert "Dots3Note" not in text and "dots3" not in text
