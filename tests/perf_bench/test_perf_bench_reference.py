"""The plain InternLM2 reference against the program, at tiny size on the
CPU: the trainer's loss AND gradients against jax.grad of the reference
(on the chip the benchmark can only compare losses, and with random
tokens that is weak at catching a wrong attention — this is where a wrong
one is caught), the serving engine's tokens against the reference's
logits, and the comparison itself against a reference fed wrong weights.
"""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PERF = os.path.join(ROOT, "perf")
for _p in (ROOT, PERF):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from harness import manifest  # noqa: E402

TINY_TRAIN = manifest.load_json("perf/configs/tiny-train.json")
TINY_SERVE = manifest.load_json("perf/configs/tiny-serve.json")


@pytest.fixture(scope="module")
def family():
    return manifest.load_plugin("references", "internlm2")


# ------------------------------------------------ the mathematics itself --
def test_grouped_attention_equals_a_per_head_loop(family):
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    s, heads, kv, d = 37, 4, 2, 8
    q, k, v = (jnp.asarray(rng.standard_normal((s, n, d)), jnp.float32)
               for n in (heads, kv, kv))
    got = np.asarray(family.attention(q, k, v, q_block=16))
    for h in range(heads):
        g = h // (heads // kv)
        scores = np.asarray(q[:, h]) @ np.asarray(k[:, g]).T / np.sqrt(d)
        scores[np.triu_indices(s, 1)] = -np.inf
        p = np.exp(scores - scores.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        np.testing.assert_allclose(got[:, h], p @ np.asarray(v[:, g]),
                                   rtol=2e-5, atol=2e-6)


def test_rope_is_a_rotation_that_depends_on_relative_position(family):
    import jax.numpy as jnp
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((6, 1, 16)), jnp.float32)
    r = np.asarray(family.rope(x, 10000.0))
    np.testing.assert_allclose(np.linalg.norm(r, axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1),
                               rtol=1e-5)
    np.testing.assert_allclose(r[0], np.asarray(x)[0], rtol=1e-6)
    same = jnp.tile(x[:1], (6, 1, 1))
    rs = np.asarray(family.rope(same, 10000.0))[:, 0]
    np.testing.assert_allclose(rs[1] @ rs[3], rs[2] @ rs[4], rtol=1e-4)
    assert abs(rs[1] @ rs[3] - rs[1] @ rs[4]) > 1e-3


def test_vocabulary_slices_cover_it_and_score_rows_agree(family):
    import jax.numpy as jnp
    for vocab in (128, 92544, 32000, 8193):
        chunks = family._vocab_chunks(vocab)
        assert chunks[0][0] == 0 and chunks[-1][1] == vocab
        assert all(a[1] == b[0] for a, b in zip(chunks, chunks[1:]))
        assert max(b - a for a, b in chunks) <= 8192
    cfg = dict(TINY_SERVE, vocab_size=20000)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((5, 64)), jnp.float32)
    head = jnp.asarray(rng.standard_normal((64, 20000)), jnp.float32)
    tokens = np.array([0, 7, 9999, 10000, 19999])
    lse, top, picked = family.Reference(cfg).score({"head": head}, x, tokens)
    logits = np.asarray(x, np.float64) @ np.asarray(head, np.float64)
    np.testing.assert_allclose(top, logits.max(-1), rtol=1e-5)
    np.testing.assert_allclose(picked, logits[np.arange(5), tokens],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        lse, np.log(np.exp(logits - logits.max(-1, keepdims=True)).sum(-1))
        + logits.max(-1), rtol=1e-5)


def test_int8_weights_are_read_with_their_scales(family):
    import jax.numpy as jnp
    q = jnp.asarray([[1, -2], [3, 4]], jnp.int8)
    got = np.asarray(family.as_f32((q, jnp.asarray([0.5, 2.0]))))
    np.testing.assert_array_equal(got, [[0.5, -4.0], [1.5, 8.0]])
    assert family.as_f32(jnp.ones(3, jnp.bfloat16)).dtype == jnp.float32


# ------------------------------------------------- against the trainer --
@pytest.fixture(scope="module")
def trained_one_step(family):
    """The tiny configuration through SpmdTrainer in float32 for one step,
    with what the reference needs to say what that step should have seen."""
    import jax
    from paddle_tpu.distributed.mesh import build_mesh, set_global_mesh
    from paddle_tpu.models.train_step import SpmdTrainer
    cfg = TINY_TRAIN
    mesh = build_mesh({"data": 1, "pipe": 1, "sharding": 1, "model": 1},
                      devices=jax.devices()[:1])
    set_global_mesh(mesh)
    kw = dict(cfg["training"]["trainer"], param_dtype="float32",
              moment_dtype="float32")
    trainer = SpmdTrainer(family.build_model(cfg, 3), mesh, **kw)
    state = trainer.init_state()
    raw = family.weights_from_trainer(trainer, state)
    weights = {"emb": family.as_f32(raw["emb"]),
               "norm": family.as_f32(raw["norm"]),
               "head": family.as_f32(raw["head"]),
               "layers": [{k: family.as_f32(v)
                           for k, v in raw["layers"][i].items()}
                          for i in range(cfg["num_hidden_layers"])]}
    batches = manifest.load_plugin("generators", "token_batches").make(
        {"batch": 2, "seq": 48}, 3, cfg["vocab_size"])
    ids, labels = batches.batch(0)
    want_loss, want_grads = jax.value_and_grad(family.loss)(
        weights, ids, labels, cfg)
    layerwise = family.Reference(cfg).loss(raw, ids, labels)
    state, loss = trainer.step(state, ids, labels)
    canon = trainer.canonical_state(state)
    return {"trainer": trainer, "loss": float(loss), "canon": canon,
            "want_loss": float(want_loss), "want_grads": want_grads,
            "layerwise": layerwise}


def test_trainer_loss_equals_the_reference(trained_one_step):
    t = trained_one_step
    assert t["loss"] == pytest.approx(t["want_loss"], rel=2e-6)
    # the layer-by-layer driver the chip run uses is the same mathematics
    assert t["layerwise"] == pytest.approx(t["want_loss"], rel=2e-6)


@pytest.mark.parametrize("which", ["emb", "norm", "head", "ln1", "wq", "wk",
                                   "wv", "wo", "ln2", "wg", "wu", "wd"])
def test_trainer_gradients_equal_jax_grad_of_the_reference(
        trained_one_step, family, which):
    """After ONE AdamW step from zero moments, m = (1 - beta1) * gradient
    (weight decay is decoupled), so the trainer's own state gives back the
    gradient its step computed."""
    t = trained_one_step
    trainer, opt = t["trainer"], t["canon"]["opt"]
    scale = 1.0 - trainer.b1
    if which in ("emb", "norm", "head"):
        got = np.asarray(opt["outer"][("emb", "norm", "head").index(which)]
                         ["m"]) / scale
        want = np.asarray(t["want_grads"][which])
    else:
        name = {v: k for k, v in family._TRAINER_NAMES.items()}[which]
        got = np.asarray(opt["stacked"][
            trainer.layer_param_names.index(name)]["m"]) / scale
        want = np.stack([np.asarray(lw[which])
                         for lw in t["want_grads"]["layers"]])
    assert got.shape == want.shape
    assert np.abs(want).max() > 0
    np.testing.assert_allclose(got, want, rtol=2e-4,
                               atol=2e-6 * np.abs(want).max())


# -------------------------------------------------- against the engine --
class _Ctx:
    seed, rehearse = 5, True

    def __init__(self, cell):
        self.cell = cell

    @staticmethod
    def log(msg):
        pass


@pytest.fixture(scope="module")
def served(family):
    rehearse = manifest.load_json("perf/rehearse.json")
    runner = manifest.load_plugin("systems", "serve_engine")
    ctx = _Ctx(manifest.Cell(rehearse, "tiny-serve-closed"))
    eng, fam = runner.build(ctx)
    return runner, ctx, eng, fam


def test_engine_tokens_sit_at_the_reference_top(served):
    runner, ctx, eng, fam = served
    check = runner.check_against_reference(ctx, eng, fam)
    assert check["ok"] and check["tokens_scored"] == 12
    # float32 engine on the CPU: the token IS the reference's top, to rounding
    assert check["worst_margin"] < 1e-3


@pytest.mark.parametrize("broken", ["swap_k_and_v", "drop_a_layer",
                                    "rope_theta"])
def test_a_wrong_model_fails_the_comparison(served, broken, monkeypatch):
    """The tolerance has teeth: the same engine tokens scored by a
    reference that differs in one place are no near-ties."""
    runner, ctx, eng, fam = served
    real = fam.weights_from_engine

    def wrong(engine):
        w = dict(real(engine))
        layers = [dict(x) for x in w["layers"]]
        if broken == "swap_k_and_v":
            layers[1]["wk"], layers[1]["wv"] = layers[1]["wv"], layers[1]["wk"]
        elif broken == "drop_a_layer":
            layers[1] = layers[0]
        w["layers"] = layers
        return w

    monkeypatch.setattr(fam, "weights_from_engine", wrong)
    if broken == "rope_theta":
        monkeypatch.setitem(ctx.cell.config, "rope_theta", 100.0)
    check = runner.check_against_reference(ctx, eng, fam)
    assert not check["ok"] and check["worst_margin"] > runner.TIE_TOL
