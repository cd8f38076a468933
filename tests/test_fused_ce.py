"""Fused chunked lm-head+CE (ops/fused_ce.py): numeric + grad parity with
the naive logits path, unsharded and vocab-parallel, incl. padding and
ignore_index; and trainer-level fused-vs-unfused equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from paddle_tpu.ops.fused_ce import fused_linear_ce, vocab_parallel_ce_rows


def _ref_loss(h, w, lab, ignore_index=-100):
    logits = (h.astype(jnp.float32) @ w.astype(jnp.float32))
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    safe = jnp.clip(lab, 0, w.shape[1] - 1)
    picked = jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0]
    per = jnp.where(lab != ignore_index, lse - picked, 0.0)
    return jnp.sum(per), jnp.sum((lab != ignore_index).astype(jnp.float32))


@pytest.mark.parametrize("n,chunk", [(32, 8), (30, 8), (16, 64)])
def test_fused_matches_reference(n, chunk):
    rng = np.random.RandomState(0)
    h = jnp.asarray(rng.randn(n, 16), jnp.float32)
    w = jnp.asarray(rng.randn(16, 24) * 0.3, jnp.float32)
    lab = np.asarray(rng.randint(0, 24, (n,)))
    lab[::5] = -100  # sprinkle ignored rows
    lab = jnp.asarray(lab)

    tot0, cnt0 = _ref_loss(h, w, lab)
    (tot1, cnt1) = fused_linear_ce(h, w, lab, chunk=chunk)
    np.testing.assert_allclose(float(tot0), float(tot1), rtol=1e-5)
    assert float(cnt0) == float(cnt1)

    g0 = jax.grad(lambda h, w: _ref_loss(h, w, lab)[0], argnums=(0, 1))(h, w)
    g1 = jax.grad(lambda h, w: fused_linear_ce(h, w, lab, chunk=chunk)[0],
                  argnums=(0, 1))(h, w)
    np.testing.assert_allclose(np.asarray(g0[0]), np.asarray(g1[0]),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(g0[1]), np.asarray(g1[1]),
                               atol=1e-5)


def test_fused_vocab_parallel_matches_unsharded():
    rng = np.random.RandomState(1)
    n, hdim, v = 32, 16, 64
    h = jnp.asarray(rng.randn(n, hdim), jnp.float32)
    w = jnp.asarray(rng.randn(hdim, v) * 0.3, jnp.float32)
    lab = np.asarray(rng.randint(0, v, (n,)))
    lab[3] = -100
    lab = jnp.asarray(lab)
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), ("model",))

    def sharded(h, w):
        def f(h, w):
            tot, cnt = fused_linear_ce(h, w, lab, axis="model", chunk=8)
            return tot / cnt
        return shard_map(f, mesh=mesh, in_specs=(P(), P(None, "model")),
                         out_specs=P(), check_vma=False)(h, w)

    tot0, cnt0 = _ref_loss(h, w, lab)
    l0 = tot0 / cnt0
    l1 = sharded(h, w)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-5)
    g0 = jax.grad(lambda h, w: _ref_loss(h, w, lab)[0] / cnt0,
                  argnums=(0, 1))(h, w)
    g1 = jax.grad(sharded, argnums=(0, 1))(h, w)
    np.testing.assert_allclose(np.asarray(g0[0]), np.asarray(g1[0]),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(g0[1]), np.asarray(g1[1]),
                               atol=1e-5)


def test_scalar_scan_carry_grad_under_shard_map():
    """Regression pin for the fused_ce vocab-parallel grad failure (the
    pre-existing tier-1 break since PR 6): on the 0.4.x stack a RANK-0
    lax.scan carry inside shard_map kills jax.grad with _SpecError —
    the scalar carry becomes a partial-eval residual that dodges
    _promote_scalar_residuals, so the transpose binds a rank-0 aval to
    {0: axis} out-names. fused_linear_ce now carries rank-1 [1]
    accumulators (squeezed at the return); this test pins BOTH that the
    fused path differentiates under shard_map and that the rank-1-carry
    shape of the same scan does (the trap-class witness), without
    depending on the CE math."""
    from jax import lax

    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), ("model",))
    xs = jnp.asarray(np.random.RandomState(3).randn(4, 8), jnp.float32)

    def f(x):
        def body(c, row):
            return c + lax.psum(jnp.sum(row, keepdims=True), "model"), None
        body = jax.checkpoint(body)
        tot, _ = lax.scan(body, jnp.zeros((1,), jnp.float32), x)
        return tot[0]

    g = jax.grad(lambda x: shard_map(f, mesh=mesh,
                                     in_specs=(P(None, "model"),),
                                     out_specs=P(), check_vma=False)(x))(xs)
    np.testing.assert_allclose(np.asarray(g), 1.0, rtol=1e-6)


def test_ce_rows_ignore_index_zeroes_loss_and_grad():
    rng = np.random.RandomState(2)
    logits = jnp.asarray(rng.randn(6, 10), jnp.float32)
    lab = jnp.asarray([1, -100, 3, -100, 5, 0])

    def f(lg):
        loss, _, _ = vocab_parallel_ce_rows(lg, lab)
        return jnp.sum(loss)

    loss, _, _ = vocab_parallel_ce_rows(logits, lab)
    assert float(loss[1]) == 0.0 and float(loss[3]) == 0.0
    g = jax.grad(f)(logits)
    np.testing.assert_allclose(np.asarray(g)[1], 0.0, atol=1e-7)
    np.testing.assert_allclose(np.asarray(g)[3], 0.0, atol=1e-7)


def test_trainer_fused_matches_unfused():
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.train_step import SpmdTrainer
    from paddle_tpu.distributed.mesh import build_mesh, set_global_mesh

    cfg = LlamaConfig.tiny()
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (4, 64)).astype(np.int64)
    labels = np.roll(ids, -1, axis=1)

    def traj(fused):
        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
        mesh = build_mesh({"data": 1, "pipe": 1, "sharding": 1, "model": 1})
        set_global_mesh(mesh)
        tr = SpmdTrainer(model, mesh, lr=1e-2, fuse_head_ce=fused,
                         ce_chunk=64)
        st = tr.init_state()
        out = []
        for i in range(3):
            st, loss = tr.step(st, ids, labels, key=jax.random.key(i))
            out.append(float(loss))
        return out

    np.testing.assert_allclose(traj(True), traj(False), rtol=2e-5)
