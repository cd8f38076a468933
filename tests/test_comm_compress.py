"""Quantized gradient collectives (comm_compress) + compress= wiring.

Tier-1 tests stay cheap: tiny arrays, a handful of shard_map compiles.
Multi-step trainer convergence rides the `slow` marker (the tier-1 suite
is timeout-bound — see conftest's runtime guard).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
from jax import shard_map
from paddle_tpu.distributed.mesh import build_mesh, set_global_mesh, \
    spmd_axes
from paddle_tpu.distributed import comm_compress as cc


class TestQuantize:
    def test_roundtrip_bounded_by_chunk_scale(self):
        rng = np.random.RandomState(0)
        # heavy-tailed values: per-chunk scales must isolate the outlier
        x = (rng.randn(1000) * np.exp(2 * rng.randn(1000))).astype(
            np.float32)
        x[100] = 1e4  # outlier in chunk 1
        q, s, size = cc.quantize_int8(x, chunk=64)
        back = np.asarray(cc.dequantize_int8(q, s, size, x.shape))
        s_np = np.asarray(s)
        for ci in range(s_np.shape[0]):
            sl = slice(ci * 64, min((ci + 1) * 64, 1000))
            # symmetric rounding: error <= scale/2 per element
            assert np.max(np.abs(back[sl] - x[sl])) <= s_np[ci] * 0.5 + 1e-7
        # the outlier flattens ONLY its own chunk's resolution
        other = np.abs(back[:64] - x[:64]).max()
        assert other < 1.0, other

    def test_all_zero_chunk_exact(self):
        x = np.zeros(130, np.float32)
        q, s, size = cc.quantize_int8(x, chunk=64)
        assert np.all(np.asarray(s) == 1.0)  # no div-by-zero sentinel
        np.testing.assert_array_equal(
            np.asarray(cc.dequantize_int8(q, s, size, x.shape)), x)


class TestQuantizedPsum:
    def test_psum_and_scatter_with_ef_identity(self):
        mesh = build_mesh({"data": 4})
        rng = np.random.RandomState(1)
        x = (rng.randn(4, 500) * np.exp(rng.randn(4, 500))).astype(
            np.float32)

        def inner(xs):
            y, err = cc.quantized_psum(xs[0], "data", axis_size=4, chunk=64)
            ys, errs = cc.quantized_psum_scatter(
                xs[0][:400], "data", axis_size=4, chunk=64)
            return y[None], err[None], ys[None], errs[None]

        f = jax.jit(shard_map(inner, mesh=mesh, in_specs=P("data"),
                              out_specs=P("data"), check_vma=False))
        y, err, ys, errs = (np.asarray(a) for a in f(x))
        exact = x.sum(0)
        # every rank decodes the same allreduce result
        assert np.all(y == y[0:1])
        # approximation is chunked-int8-grade
        rel = np.abs(y[0] - exact) / (np.abs(exact) + 1e-3)
        assert np.median(rel) < 0.05, np.median(rel)
        # the EF contract, exactly: psum(x) == y + psum(err)
        np.testing.assert_allclose(y[0] + err.sum(0), exact,
                                   rtol=1e-5, atol=1e-4)
        # reduce-scatter: rank r's shard + scattered residuals == exact
        exact_rs = x[:, :400].sum(0).reshape(4, 100)
        for r in range(4):
            np.testing.assert_allclose(
                ys[r] + errs[:, r * 100:(r + 1) * 100].sum(0), exact_rs[r],
                rtol=1e-5, atol=1e-4)

    def test_axis_size_one_is_identity(self):
        x = jnp.asarray(np.random.RandomState(2).randn(37).astype(
            np.float32))
        y, err = cc.quantized_psum(x, "nope", axis_size=1)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
        assert not np.any(np.asarray(err))


class TestAllReduceCompressAPI:
    def _run_program(self):
        from paddle_tpu.distributed.collective import (all_reduce, new_group,
                                                       ReduceOp)
        from paddle_tpu.tensor.tensor import Tensor

        mesh = build_mesh({"model": 4})
        set_global_mesh(mesh)
        g = new_group(list(range(4)), axis_name="model")

        def inner(x):
            with spmd_axes(("model",)):
                t_def = Tensor(x)
                all_reduce(t_def, group=g)          # default: exact
                ref = lax.psum(x, "model")          # the prior lowering
                t_q = Tensor(x)
                all_reduce(t_q, group=g, compress="int8",
                           compress_chunk=64)
                t_p = Tensor(x)
                all_reduce(t_p, op=ReduceOp.PROD, group=g)
                return t_def.data, ref, t_q.data, t_p.data

        f = shard_map(inner, mesh=mesh, in_specs=P("model"),
                      out_specs=P("model"), check_vma=False)
        # includes zeros and negatives (the PROD regression surface)
        x = np.asarray([2.0, -3.0, 0.0, 1.5, -1.0, 4.0, -2.0, 0.5],
                       np.float32)
        return x, [np.asarray(a) for a in jax.jit(f)(jnp.asarray(x))]

    def test_default_byte_identical_and_int8_close(self):
        x, (t_def, ref, t_q, _) = self._run_program()
        # compress=None must be bit-for-bit the old lax.psum lowering
        np.testing.assert_array_equal(t_def, ref)
        exact = x.reshape(4, 2).sum(0)
        np.testing.assert_allclose(t_q.reshape(4, 2),
                                   np.tile(exact, (4, 1)),
                                   rtol=0.05, atol=0.05)

    def test_prod_handles_zero_and_negative(self):
        # regression: exp(psum(log)) NaN'd on zero/negative inputs
        x, (_, _, _, t_p) = self._run_program()
        expect = x.reshape(4, 2).prod(0)  # [(2)(0)(-1)(-2), (-3)(1.5)(4)(.5)]
        got = t_p.reshape(4, 2)
        assert np.all(np.isfinite(got))
        np.testing.assert_allclose(got, np.tile(expect, (4, 1)),
                                   rtol=1e-5, atol=1e-6)

    def test_prod_integer_dtype_exact(self):
        # regression: exp(psum(log)) reconstructs 42 as 41.99999x; the
        # cast back to the input's int dtype must round, not truncate
        from paddle_tpu.distributed.collective import (all_reduce,
                                                       new_group, ReduceOp)
        from paddle_tpu.tensor.tensor import Tensor

        mesh = build_mesh({"model": 4})
        set_global_mesh(mesh)
        g = new_group(list(range(4)), axis_name="model")

        def inner(x):
            with spmd_axes(("model",)):
                t = Tensor(x)
                all_reduce(t, op=ReduceOp.PROD, group=g)
                return t.data

        f = shard_map(inner, mesh=mesh, in_specs=P("model"),
                      out_specs=P("model"), check_vma=False)
        x = np.asarray([2, 3, 1, 1, 3, 1, 7, 2], np.int32)
        out = np.asarray(jax.jit(f)(jnp.asarray(x)))
        expect = x.reshape(4, 2).prod(0)  # [42, 6]
        np.testing.assert_array_equal(out.reshape(4, 2),
                                      np.tile(expect, (4, 1)))

    def test_bad_compress_value_raises(self):
        from paddle_tpu.distributed.collective import all_reduce, ReduceOp
        from paddle_tpu.tensor.tensor import Tensor
        t = Tensor(jnp.ones(4))
        with pytest.raises(ValueError, match="compress"):
            all_reduce(t, compress="int4")
        with pytest.raises(ValueError, match="SUM/AVG"):
            all_reduce(t, op=ReduceOp.MAX, compress="int8")


def _build_trainer(axes, **kw):
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.train_step import SpmdTrainer
    from paddle_tpu.distributed import fleet

    full = {"data": 1, "pipe": 1, "sharding": 1, "model": 1}
    full.update(axes)
    mesh = build_mesh(full)
    set_global_mesh(mesh)
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {
        "dp_degree": full["data"], "mp_degree": full["model"],
        "pp_degree": full["pipe"], "sharding_degree": full["sharding"]}
    fleet.init(is_collective=True, strategy=strategy)
    paddle.seed(11)
    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg)
    return SpmdTrainer(model, mesh, lr=1e-2, **kw), cfg


class TestTrainerKnobs:
    def test_validation(self):
        with pytest.raises(ValueError, match="grad_compress"):
            _build_trainer({"data": 2}, grad_compress="int4")
        with pytest.raises(ValueError, match="grad_accum"):
            _build_trainer({"data": 2}, grad_accum=0)
        with pytest.raises(ValueError, match="grad_accum"):
            _build_trainer({"data": 2, "pipe": 2}, grad_accum=2,
                           micro_batch_size=2)

    def test_ef_state_presence(self):
        tr, _ = _build_trainer({"data": 2, "sharding": 2})
        assert "ef" not in tr.abstract_state()  # default: untouched layout
        tr8, _ = _build_trainer({"data": 2, "sharding": 2},
                                grad_compress="int8")
        ab = tr8.abstract_state()
        assert set(ab["ef"]) == {"outer", "stacked"}
        for kind in ("outer", "stacked"):
            for e, p in zip(ab["ef"][kind], ab["params"][kind]):
                assert e.shape == p.shape and e.dtype == jnp.float32
        state = tr8.init_state()
        flat = jax.tree_util.tree_leaves(state["ef"])
        assert all(not np.any(np.asarray(l)) for l in flat)


@pytest.mark.slow
class TestConvergenceGuard:
    """int8+error-feedback training must track the exact-f32 trajectory
    (the EQuARX claim: compression costs wire bytes, not quality)."""

    def test_int8_ef_and_accum_track_exact(self):
        rng = np.random.RandomState(0)
        ids = rng.randint(0, 128, (8, 16)).astype(np.int64)
        labels = np.roll(ids, -1, axis=1)
        key = jax.random.PRNGKey(3)
        finals = {}
        for name, axes, kw in [
            ("exact", {"data": 2, "sharding": 2}, {}),
            ("int8", {"data": 2, "sharding": 2},
             {"grad_compress": "int8"}),
            ("int8_s3", {"data": 2, "sharding": 2},
             {"grad_compress": "int8", "sharding_stage": 3}),
            ("accum2", {"data": 2, "sharding": 2}, {"grad_accum": 2}),
        ]:
            tr, _ = _build_trainer(axes, **kw)
            state = tr.init_state()
            losses = []
            for _ in range(6):
                state, loss = tr.step(state, ids, labels, key=key)
                losses.append(float(loss))
            assert all(np.isfinite(losses)) and losses[-1] < losses[0], \
                (name, losses)
            finals[name] = losses[-1]
        # deferred sync is a reduction reorder, not an approximation
        assert abs(finals["accum2"] - finals["exact"]) < 1e-3 \
            + 0.01 * abs(finals["exact"]), finals
        # compressed trajectories within 5% of exact after 6 steps
        for name in ("int8", "int8_s3"):
            rel = abs(finals[name] - finals["exact"]) / abs(finals["exact"])
            assert rel < 0.05, (name, finals)

    def test_checkpoint_roundtrip_drops_and_rezeros_ef(self, tmp_path):
        """EF residuals are transient: canonical checkpoints drop them;
        restore re-zeros them — across meshes, sharding stages, and
        compressed<->exact trainer configs."""
        rng = np.random.RandomState(0)
        ids = rng.randint(0, 128, (8, 16)).astype(np.int64)
        labels = np.roll(ids, -1, axis=1)
        key = jax.random.PRNGKey(3)
        tr, _ = _build_trainer({"data": 2, "sharding": 2},
                               grad_compress="int8")
        state = tr.init_state()
        state, _ = tr.step(state, ids, labels, key=key)
        tr.save_checkpoint(state, str(tmp_path), step=1)
        # restore onto a different mesh + compressed stage-3 trainer
        tr2, _ = _build_trainer({"data": 4, "sharding": 2},
                                grad_compress="int8", sharding_stage=3)
        state2, _ = tr2.load_checkpoint(str(tmp_path))
        assert "ef" in state2 and int(state2["step"]) == 1
        assert all(not np.any(np.asarray(x))
                   for x in jax.tree_util.tree_leaves(state2["ef"]))
        state2, l2 = tr2.step(state2, ids, labels, key=key)
        # and onto an exact trainer: no ef key at all
        tr3, _ = _build_trainer({"data": 2, "sharding": 2})
        state3, _ = tr3.load_checkpoint(str(tmp_path))
        assert "ef" not in state3
        state3, l3 = tr3.step(state3, ids, labels, key=key)
        assert np.isfinite(l2) and np.isfinite(l3)
        assert abs(float(l2) - float(l3)) < 0.02
