"""The per-head sparse block (grouped-query attention whose EVERY layer
attends to a learned top-k selection of its cached K and V, a per-head q/k
norm, multi-component rotary, routed experts under a softmax router) end
to end at tiny widths on the CPU: model against the plain reference, the
serving engine through its cache against the reference's full forward, the
selection against an argsort under ties and zeros, the [K ; V] rows against
dense attention, the router against a hand computation, the page group
with its index keys, the counters, and the typed refusals. The top-k (8),
a page (4), a chunk (8) and a key block (16) all lie inside the contexts
served (3 to 52).
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
from paddle_tpu.inference.description import (GroupKey,
                                              UnsupportedByDescription,
                                              describe)
from paddle_tpu.models import KeyeVL2Config, KeyeVL2ForCausalLM
from paddle_tpu.models.mimo_v2 import rope_tables
from paddle_tpu.ops import sparse_attention as sa
from paddle_tpu.ops.moe import route, routed_experts

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    spec = importlib.util.spec_from_file_location(
        "perf_reference_keye_vl2",
        os.path.join(ROOT, "perf", "references", "keye_vl2.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()

# the tiny configuration as a configuration FILE's keys (what the
# reference reads), the experts all held
CFG = {
    "hidden_size": 64, "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "rope_theta": 1e7,
    "rope_scaling": {"mrope_section": [2, 3, 3]},
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4,
                  "indexer_num_kv_heads": 1, "topk": 8},
    "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "rms_norm_eps": 1e-6, "vocab_size": 96, "max_position_embeddings": 128}
NEW = 12                    # tokens a served request decodes
PROMPTS = (3, 7, 19, 40)    # under the top-k, across it, across a key
#                             block (16), across several chunks and blocks


@pytest.fixture(scope="module")
def model():
    paddle.seed(13)
    m = KeyeVL2ForCausalLM(REF.model_config(CFG))
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


# float32 model and engine against the float32 reference: the same
# products in another order of sums (a blocked online softmax against a
# dense one, a gather of selected rows against a mask, a grouped product
# against a loop over experts). 5e-4 on logits of size ~3 leaves room over
# the measured worst (6e-6) and is far under what any of the five faults
# below moves a logit by (0.05 to 3)
TOL = 5e-4


def test_model_matches_the_reference_logits(model):
    ids = np.random.default_rng(0).integers(0, 96, (2, 40))
    with paddle.no_grad():      # inference: nothing is linearized
        got = model(paddle.to_tensor(ids)).numpy()
    want = np.asarray(ref_forward(_weights(model), ids))
    np.testing.assert_allclose(got, want, rtol=0, atol=TOL)


@pytest.fixture(scope="module")
def served(model):
    """Four prompts through the engine (chunks of 8 over pages of 4, key
    blocks of 16, a top-k of 8), every logits row the engine selected a
    token from captured with its request and position."""
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
    prompts = [rng.integers(0, 96, n) for n in PROMPTS]
    uids = [eng.add_request(p, max_new_tokens=NEW) for p in prompts]
    eng.drain()
    results = {u: eng.result(u) for u in uids}
    # a greedy step program keeps its logits on the device (PR 31): the
    # rows come from serving the prompts again under a neutral processor
    # chain, the arm that materializes them, token for token the same
    assert not seen
    anything = SamplingParams(grammar=TokenMaskAutomaton.trivial(96))
    again = [eng.add_request(p, max_new_tokens=NEW, sampling=anything)
             for p in prompts]
    eng.drain()
    for u, v in zip(uids, again):
        np.testing.assert_array_equal(results[u], eng.result(v))
    seen[:] = [(uids[again.index(v)], pos, row) for v, pos, row in seen]
    return eng, results, seen


@pytest.mark.parametrize("which", range(len(PROMPTS)),
                         ids=[f"prompt{n}" for n in PROMPTS])
def test_engine_through_the_cache_matches_the_reference(model, served,
                                                        which):
    """Prefill in chunks, then decode through the cache (ONE decode
    transport exists: the gather of the selected rows), logits against the
    reference's full forward."""
    eng, results, seen = served
    uid, full = list(results.items())[which]
    want = np.asarray(ref_forward(_weights(model), full[None]))[0]
    rows = [(pos, got) for u, pos, got in seen if u == uid]
    assert [pos for pos, _ in rows] == list(
        range(PROMPTS[which] - 1, PROMPTS[which] - 1 + NEW))
    worst = max(float(np.max(np.abs(got - want[pos]))) for pos, got in rows)
    assert worst < TOL, worst
    # greedy: the tokens are the reference's own argmax
    assert np.array_equal(full[PROMPTS[which]:],
                          want[PROMPTS[which] - 1:-1].argmax(-1))


def test_engine_counts_the_selection_and_leaks_no_page(served):
    eng, results, _ = served
    h = eng.health()
    (group,) = h["page_groups"]
    assert (group["kind"], group["kv_heads"], group["row_width"],
            group["index_width"], group["window"], group["layers"]) == (
                "heads", 2, 64, 8, None, 3)
    assert h["pages_free"] == h["pages_total"]
    # decode queries of the three layers: position t sees t + 1 keys and
    # attends to min(t + 1, 8) of them
    visible = attended = queries = 0
    for full_ids in results.values():
        for t in range(full_ids.size - NEW, full_ids.size - 1):
            visible += 3 * (t + 1)
            attended += 3 * min(t + 1, 8)
            queries += 3
    # (the fixture serves every prompt twice)
    visible, attended, queries = 2 * visible, 2 * attended, 2 * queries
    scored = h["sparse"].pop("index_keys_scored")
    assert h["sparse"] == {"keys_visible": visible,
                           "keys_attended": attended,
                           "decode_queries": queries}
    # the scan scores every table page (64 positions) of every slot of the
    # step's bucket (1 to 4 slots wide), live or not, in all three layers
    assert scored % (3 * 64) == 0 and scored > visible
    assert eng.decode_steps <= scored // (3 * 64) <= 4 * eng.decode_steps
    assert h["experts"]["decode_steps"] == eng.decode_steps
    assert np.asarray(h["experts"]["rows"]).shape == (3, 8)


@pytest.mark.parametrize("variant", ["no_selection", "bf16_indexer",
                                     "no_qk_norm", "sigmoid_router",
                                     "float8_kv"])
def test_the_tolerance_catches_a_wrong_variant(model, variant):
    """A forward pass without the selection or the q/k norm, with the
    indexer on bf16 operands, under the sigmoid router or on float8 K and
    V parts from the true one by more than the tolerance: the comparisons
    above would fail on each."""
    weights = _weights(model)
    ids = np.random.default_rng(9).integers(0, 96, (3, 60))
    true = np.asarray(ref_forward(weights, ids))
    bad = np.asarray(jax.jit(lambda w: REF.forward(
        w, ids, CFG, variant=variant))(weights))
    assert float(np.max(np.abs(bad - true))) > 2 * TOL


# ----------------------------------------------------- the selection --
def _stable_top(scores, seen, k):
    """Each row's k best seen positions by a stable argsort: a mask."""
    order = np.argsort(np.where(seen, -scores, np.inf), axis=-1,
                       kind="stable")
    want = np.zeros(scores.shape, bool)
    for r in range(scores.shape[0]):
        want[r, order[r, :min(k, int(seen[r].sum()))]] = True
    return want


@pytest.mark.parametrize("case", ["ties", "zeros", "all_equal", "few_seen",
                                  "negative"])
@pytest.mark.parametrize("form", ["list", "mask"])
def test_selection_is_exact_under_ties_and_zeros(case, form):
    """Both forms of the exact top-k pick what a stable argsort picks:
    ties at the k-th value go to the lowest positions, and +0.0 / -0.0
    (`index_scores` writes one zero) are one value."""
    rng = np.random.default_rng(4)
    n, s, k = 6, 40, 8
    scores = {
        "ties": rng.integers(0, 4, (n, s)).astype(np.float32),
        "zeros": np.where(rng.random((n, s)) < 0.7, 0.0,
                          rng.normal(size=(n, s))).astype(np.float32),
        "all_equal": np.full((n, s), 2.5, np.float32),
        "few_seen": rng.normal(size=(n, s)).astype(np.float32),
        "negative": -np.abs(rng.integers(0, 3, (n, s))).astype(np.float32),
    }[case]
    seen = np.arange(s)[None, :] <= np.array(
        [[3], [7], [8], [20], [39], [39]] if case == "few_seen"
        else [[39]] * n)
    want = _stable_top(scores, seen, k)
    if form == "list":
        idx, valid = (np.asarray(v) for v in sa.select_top(
            jnp.asarray(scores), jnp.asarray(seen), k))
        got = np.zeros_like(want)
        for r in range(n):
            got[r, idx[r][valid[r]]] = True
        assert valid.sum(1).tolist() == want.sum(1).tolist()
    else:
        got = np.asarray(seen & sa.top_mask(
            jnp.where(seen, scores, -jnp.inf), k))
    assert np.array_equal(got, want)


def test_index_scores_write_one_zero(model):
    """Scores that are exact zeros (every head's relu at rest) come out
    as +0.0 whatever the sign of the head weights: a tie by position."""
    q = jnp.zeros((1, 5, 4, 8), jnp.float32).at[0, :, 0, 0].set(-1.0)
    k = jnp.ones((1, 7, 8), jnp.float32)
    wt = -jnp.ones((1, 5, 4), jnp.float32)
    got = np.asarray(sa.index_scores(q, k, wt))
    assert np.all(got == 0) and not np.any(np.signbit(got))


# ------------------------------------------------ rows of [K ; V] --
def _spec():
    return KeyeVL2Config.tiny().layer_spec().attn


def _dense_gqa(q, k, v, seen, a):
    """q [t, H, d], k [n, G, d], v [n, G, dv], seen [t, n] -> [t, H, dv]."""
    rep = a.n_heads // a.n_kv_heads
    lg = np.einsum("thd,nhd->htn", q, np.repeat(k, rep, 1)) \
        / np.sqrt(a.qk_dim)
    lg = np.where(seen[None], lg, -np.inf)
    p = np.exp(lg - lg.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("htn,nhd->thd", p, np.repeat(v, rep, 1))


def test_attention_over_rows_equals_dense_grouped_attention():
    """One [K ; V] row a token: the decode form (one query a sequence over
    its gathered rows) and the prefill form (a chunk over key blocks under
    a mask) both equal dense grouped-query attention."""
    a = _spec()
    rng = np.random.default_rng(8)
    t, n = 6, 32
    q = rng.normal(size=(t, a.n_heads, a.qk_dim)).astype(np.float32)
    k = rng.normal(size=(n, a.n_kv_heads, a.qk_dim)).astype(np.float32)
    v = rng.normal(size=(n, a.n_kv_heads, a.v_dim)).astype(np.float32)
    seen = rng.random((t, n)) < 0.5
    seen[:, 0] = True
    want = _dense_gqa(q, k, v, seen, a)
    rows = sa.kv_row(jnp.asarray(k), jnp.asarray(v))
    assert rows.shape == (n, sa.kv_row_width(a)) == (n, 64)
    got = sa.attend_selected(
        jnp.asarray(q), jnp.broadcast_to(rows, (t, n, 64)),
        jnp.asarray(seen), a)
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=2e-5)

    def block(j):
        return (jax.lax.dynamic_slice(rows, (j * 8, 0), (8, 64)),
                jax.lax.dynamic_slice(jnp.asarray(seen), (0, j * 8),
                                      (t, 8)))

    got = sa.attend_kv_blocks(jnp.asarray(q), block, 0, n // 8, a)
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=2e-5)


# ------------------------------------------------------------ mrope --
def test_mrope_with_equal_components_is_plain_rotary(model):
    """A text token's three position components are equal: the reference
    under explicit [3, t] position ids equals its text path bit for bit,
    and with unequal components (an image patch) it differs."""
    weights = _weights(model)
    ids = np.random.default_rng(2).integers(0, 96, (1, 30))
    def under(positions):
        return np.asarray(jax.jit(lambda w: REF.forward(
            w, ids, CFG, positions=positions))(weights))

    text = np.asarray(ref_forward(weights, ids))
    assert np.array_equal(under(REF.text_positions(30)), text)
    other = under(np.stack([np.arange(30), np.arange(30) // 6,
                            np.arange(30) % 6]))
    assert float(np.max(np.abs(other - text))) > 100 * TOL


def test_mrope_sections_assign_pairs_to_components():
    """Pair i of a head turns by component c(i): with only the height
    component moved, the temporal and width pairs stay put."""
    x = jnp.ones((1, 1, 16), jnp.float32)
    base = np.asarray(REF.rope(x, np.array([[5], [5], [5]]), 1e4, (2, 3, 3)))
    moved = np.asarray(REF.rope(x, np.array([[5], [9], [5]]), 1e4, (2, 3, 3)))
    changed = np.abs(moved - base)[0, 0] > 1e-6
    # dims i and i + 8 belong to pair i: pairs 2, 3, 4 are the height's
    assert changed.tolist() == ([False] * 2 + [True] * 3 + [False] * 3) * 2
    # and the program's tables (rotate-half, all dims) are the text path
    cos, sin = rope_tables(7, 16, 1e4)
    got = sa.rope_half(jnp.ones((7, 16)), cos, sin)
    want = REF.rope(jnp.ones((7, 16)), REF.text_positions(7), 1e4, (2, 3, 3))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


# ----------------------------------------------------------- router --
def test_softmax_router_against_a_hand_computation():
    rng = np.random.default_rng(1)
    x = rng.normal(size=(5, 16)).astype(np.float32)
    w_r = rng.normal(size=(16, 6)).astype(np.float32)
    idx, wts = (np.asarray(v) for v in route(
        jnp.asarray(x), jnp.asarray(w_r), None, 2, "softmax"))
    logits = x.astype(np.float64) @ w_r.astype(np.float64)
    p = np.exp(logits - logits.max(1, keepdims=True))
    p /= p.sum(1, keepdims=True)
    for t in range(5):
        best = np.argsort(-p[t], kind="stable")[:2]
        assert idx[t].tolist() == best.tolist()
        np.testing.assert_allclose(wts[t], p[t, best] / p[t, best].sum(),
                                   rtol=1e-5)
    # and it is NOT the sigmoid router's weighting
    _, sig = route(jnp.asarray(x), jnp.asarray(w_r), jnp.zeros(6), 2)
    assert float(np.max(np.abs(np.asarray(sig) - wts))) > 1e-2


@pytest.mark.parametrize("shares", [1, 2, 4, 8])
def test_the_shares_add_up_to_the_uncut_layer_under_the_softmax_router(
        shares):
    """8 experts over `shares` chips: every `held` range's part of the
    result sums to the reference's uncut layer, and one share alone is
    the reference's share."""
    rng = np.random.default_rng(3)
    t, h, e, f, k = 24, 64, 8, 32, 2

    def rand(*shape):
        return jnp.asarray(rng.normal(size=shape) / 8, jnp.float32)

    x = jnp.asarray(rng.normal(size=(t, h)), jnp.float32)
    w = {"router": rand(h, e), "w_gu": rand(e, h, 2 * f),
         "w_d": rand(e, f, h)}
    n = e // shares
    with jax.default_matmul_precision("highest"):
        whole = REF.routed(x, w, (0, e), k)
        total = jnp.zeros_like(x)
        for lo in range(0, e, n):
            part, rows = routed_experts(
                x, w["router"], None, w["w_gu"][lo:lo + n],
                w["w_d"][lo:lo + n], (lo, lo + n), k, interpret=True,
                score="softmax")
            assert rows.shape == (n,)
            total = total + part
            want = REF.routed(x, dict(w, w_gu=w["w_gu"][lo:lo + n],
                                      w_d=w["w_d"][lo:lo + n]),
                              (lo, lo + n), k)
            np.testing.assert_allclose(np.asarray(part), np.asarray(want),
                                       rtol=0, atol=2e-5)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=0, atol=5e-5)


# ------------------------------------------- page group, description --
def test_page_group_keeps_rows_and_index_keys_on_one_table(model):
    eng = ContinuousBatchingEngine(model, max_len=64, page_size=4,
                                   max_batch=2, prefill_chunk=8,
                                   prefix_cache=False)
    (g,) = eng.groups
    assert (g.kind, g.latent, g.window) == ("heads", False, None)
    # a token's row is [K ; V] of both KV heads, padded to the lanes; its
    # index key sits in the second pool, page for page
    assert (g.row_width, g.row_pad, g.index_width) == (64, 128, 8)
    assert g.n_pages == 2 * 16 and not g.k_flat
    assert g.pool_shapes() == ((32, 4, 128), (32, 4, 8))
    assert [tuple(p.shape) for p in eng.k_pages] == [(32, 4, 128)] * 3
    assert [tuple(p.shape) for p in eng.v_pages] == [(32, 4, 8)] * 3
    assert eng.max_pages_per_seq == 16      # one table for both pools
    uid = eng.add_request(np.arange(1, 10), max_new_tokens=3)
    eng.step()
    assert g.used == 3                      # ceil((9 + 3) / 4), at admission
    eng.drain()
    assert len(eng.result(uid)) == 12 and g.used == 0


def test_the_description_is_the_seam(model):
    desc = describe(model)
    assert not desc.plain and desc.has_experts and desc.has_indexer
    assert desc.layer_group == (0, 0, 0)
    assert desc.groups == (GroupKey("heads", 2, 16, 16, None, 8),)
    assert desc.groups[0].index_width == 8
    layer = desc.layers[0]
    assert all(other == layer for other in desc.layers)
    a = layer.attn
    assert a.qk_norm and a.latent is None and a.window is None
    assert (a.indexer.n_heads, a.indexer.dim, a.indexer.rope_dim,
            a.indexer.top_k) == (4, 8, 8, 8)
    assert (layer.ffn.kind, layer.ffn.score, layer.ffn.held,
            layer.ffn.shared_width) == ("experts", "softmax", (0, 8), 0)
    names = set(model.serving_parameters()["layers"][0])
    assert "router_bias" not in names and {"q_hn", "k_hn", "ix_wq"} <= names
    # the index query reads the normed hidden state: [hidden, heads x dim]
    assert model.serving_parameters()["layers"][0]["ix_wq"].shape == [64, 32]
    with pytest.raises(ValueError, match="mrope_section"):
        KeyeVL2Config.tiny(mrope_section=(2, 2, 2))
    with pytest.raises(ValueError, match="held_experts"):
        KeyeVL2Config.tiny(held_experts=(4, 12))


def test_held_range_reaches_the_engine(model):
    """A share of the experts serves through the same engine: the model
    holding experts [2, 6) emits what its own eager forward says."""
    paddle.seed(13)
    part = KeyeVL2ForCausalLM(KeyeVL2Config.tiny(held_experts=(2, 6)))
    part.eval()
    eng = ContinuousBatchingEngine(part, max_len=32, page_size=4,
                                   max_batch=1, prefill_chunk=8,
                                   prefix_cache=False)
    prompt = np.random.default_rng(7).integers(0, 96, 11)
    uid = eng.add_request(prompt, max_new_tokens=4)
    eng.drain()
    full = np.asarray(eng.result(uid))
    with paddle.no_grad():
        want = part(paddle.to_tensor(full[None, :-1])).numpy()[0]
    assert np.array_equal(full[11:], want[10:].argmax(-1))
    assert np.asarray(eng.health()["experts"]["rows"]).shape == (3, 4)


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


@pytest.mark.parametrize("call", ["generate", "export_kv_pages",
                                  "export_prefix_pages"])
def test_plain_only_calls_raise_typed(model, call):
    eng = ContinuousBatchingEngine(model, max_len=64, page_size=4,
                                   max_batch=2, prefix_cache=False)
    args = {"generate": (np.zeros((1, 4), np.int64),),
            "export_kv_pages": (0,),
            "export_prefix_pages": ([1, 2, 3],)}[call]
    with pytest.raises(UnsupportedByDescription):
        getattr(eng, call)(*args)

