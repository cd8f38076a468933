"""Auto-parallel end-to-end (VERDICT r3 next #5): a once-annotated
program is completed (Completer), planned against a cluster bandwidth
table (Planner cost rule), partitioned onto the mesh with explicit
reshard chains (Partitioner), and executed — pinned to the dense
single-device trajectory.
ref: auto_parallel/partitioner.py:38, reshard.py:1007, cost/base_cost.py.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.distributed.auto_parallel import (
    Engine, ProcessMesh, Strategy, shard_tensor)
from paddle_tpu.distributed.auto_parallel.partitioner import (
    Cluster, Partitioner, Planner)


def _mesh2d():
    devs = np.array(jax.devices()[:4]).reshape(2, 2)
    return Mesh(devs, ("data", "model"))


class MLP(nn.Layer):
    def __init__(self, h=8, ff=16):
        super().__init__()
        self.fc1 = nn.Linear(h, ff, bias_attr=False)
        self.fc2 = nn.Linear(ff, h, bias_attr=False)

    def forward(self, x):
        return self.fc2(paddle.nn.functional.relu(self.fc1(x)))


def _loss(out, y):
    return ((out - y) ** 2).mean()


def _make_data(n=8, h=8, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, h).astype(np.float32)
    y = rng.randn(n, h).astype(np.float32)
    return x, y


class _OneBatch:
    def __init__(self, x, y, repeats=1):
        self.x, self.y, self.repeats = x, y, repeats

    def __iter__(self):
        from paddle_tpu.tensor.tensor import Tensor
        for _ in range(self.repeats):
            yield (Tensor(jnp.asarray(self.x)), Tensor(jnp.asarray(self.y)))


def _dense_sgd_traj(x, y, steps=3, lr=1e-2, seed=7):
    paddle.seed(seed)
    model = MLP()
    params = [p.data for p in model.parameters()]

    def loss_fn(parrs, xx, yy):
        for p, a in zip(model.parameters(), parrs):
            p.data = a
        from paddle_tpu.tensor.tensor import Tensor
        from paddle_tpu.autograd import tape
        with tape.no_grad():
            out = model(Tensor(xx))
            return _loss(out, Tensor(yy)).data

    traj = []
    for _ in range(steps):
        lv, g = jax.value_and_grad(loss_fn)(params, x, y)
        params = [a - lr * gg for a, gg in zip(params, g)]
        traj.append(float(lv))
    return traj


class _SGD:
    def __init__(self, lr):
        self.lr = lr

    def get_lr(self):
        return self.lr


def test_full_auto_engine_matches_dense():
    """Annotate ONLY fc1 column-parallel + batch data-parallel; the
    Completer infers fc2 row-parallel, the Partitioner inserts the psum
    chain, and the full-auto trajectory pins to dense SGD."""
    x, y = _make_data()
    dense = _dense_sgd_traj(x, y, steps=3)

    paddle.seed(7)
    model = MLP()
    pm = ProcessMesh(np.arange(4).reshape(2, 2),
                     ["data", "model"])
    # one annotation: fc1 weight [h, ff] sharded on ff over 'model'
    model.fc1.weight.dist_attr = (None, "model")
    strat = Strategy()
    strat.auto_mode = "full"
    eng = Engine(model=model, loss=_loss, optimizer=_SGD(1e-2),
                 strategy=strat)
    eng.prepare(input_placements=[("data", None), ("data", None)],
                process_mesh=pm)
    hist = []
    for _ in range(3):
        hist += eng.fit(_OneBatch(x, y), epochs=1, verbose=0)
    np.testing.assert_allclose(hist, dense, rtol=2e-4,
                               err_msg=f"full-auto {hist} vs dense {dense}")
    # the completer must have INFERRED fc2's row sharding from the one
    # fc1 annotation
    fc2_spec = eng.completed_param_specs[
        [id(p) for p in model.parameters()].index(id(model.fc2.weight))]
    assert fc2_spec is not None and "model" in tuple(fc2_spec), fc2_spec


def test_partitioner_inserts_expected_collectives():
    """The explicit chain for the Megatron pair: ONE psum-class collective
    for the contraction (no gather of the big activations)."""
    x, y = _make_data()
    paddle.seed(7)
    model = MLP()
    pm = ProcessMesh(np.arange(4).reshape(2, 2),
                     ["data", "model"])
    model.fc1.weight.dist_attr = (None, "model")
    strat = Strategy()
    strat.auto_mode = "full"
    eng = Engine(model=model, loss=_loss, optimizer=_SGD(1e-2),
                 strategy=strat)
    eng.prepare(input_placements=[("data", None), ("data", None)],
                process_mesh=pm)
    eng.fit(_OneBatch(x, y), epochs=1, verbose=0)
    ops = [r["op"] for r in eng.partitioner.record]
    assert any(op in ("psum", "psum_scatter") for op in ops), ops
    # Megatron pairing: the hidden activations must NOT be all_gathered
    assert "fallback_replicated" not in ops, ops


def test_planner_prefers_fast_axis_mover():
    """Cluster bandwidth steers the cost rule: with equal byte counts the
    operand whose reshard rides the faster link moves."""
    mesh = _mesh2d()
    fast = Planner(mesh, Cluster({"data": 100.0, "model": 100.0}))
    # a is bigger -> b moves
    assert fast.choose_mover((1024, 64), ("data", None),
                             (64, 64), (None, "model")) == "b"
    # same shapes, but b's move crosses a 100x slower link -> a moves
    slow_b = Planner(mesh, Cluster({"data": 1.0, "model": 100.0}))
    a_cost = slow_b.move_seconds((256, 64), "float32", ("model", None),
                                 ("data", None))
    b_cost = slow_b.move_seconds((256, 64), "float32", ("data", None),
                                 ("model", None))
    assert b_cost > a_cost  # moving the data-sharded operand is slower


def test_unknown_primitive_falls_back_replicated():
    """A primitive without a partition rule (sort) degrades to
    gather -> replicated execution — correct, recorded."""
    mesh = Mesh(np.array(jax.devices()[:2]), ("x",))

    def f(a, b):
        return jnp.sort(a + b, axis=0).sum()

    part = Partitioner(mesh)
    a = np.arange(8, dtype=np.float32)[::-1].copy()
    b = np.ones(8, np.float32)
    local = part.partition(f, [a, b], [("x",), ("x",)])
    out = shard_map(local, mesh=mesh, in_specs=(P("x"), P("x")),
                    out_specs=P(), check_vma=False)(a, b)
    np.testing.assert_allclose(float(out), float(np.sort(a + b).sum()))
    assert any(r["op"] == "fallback_replicated"
               for r in part.record), part.record


def test_conflict_reshard_chain_row_to_col():
    """Producer row-sharded, consumer needs column-sharded: the
    partitioner routes through its reshard chain and stays exact."""
    mesh = Mesh(np.array(jax.devices()[:2]), ("x",))
    rng = np.random.RandomState(0)
    a = rng.randn(8, 8).astype(np.float32)
    w = rng.randn(8, 6).astype(np.float32)

    def f(a, w):
        h = a * 2.0          # stays row-sharded
        return (h @ w).sum()  # contraction over the full dim

    part = Partitioner(mesh)
    local = part.partition(f, [a, w], [("x", None), (None, None)])
    out = shard_map(local, mesh=mesh, in_specs=(P("x", None), P()),
                    out_specs=P(), check_vma=False)(a, w)
    np.testing.assert_allclose(float(out), float((a * 2.0 @ w).sum()),
                               rtol=1e-5)


def test_full_mode_without_prepare_raises_clearly():
    strat = Strategy()
    strat.auto_mode = "full"
    x, y = _make_data()
    eng = Engine(model=MLP(), loss=_loss, optimizer=_SGD(1e-2),
                 strategy=strat)
    with pytest.raises(ValueError, match="process_mesh"):
        eng.fit(_OneBatch(x, y), epochs=1, verbose=0)


def test_full_mode_step_threads_rng_key():
    """The partitioned step takes a fresh key per step (a baked trace-time
    key would freeze dropout masks)."""

    class DropNet(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(8, 8, bias_attr=False)
            self.drop = nn.Dropout(0.5)

        def forward(self, x):
            return self.drop(self.fc(x))

    x, y = _make_data()
    paddle.seed(3)
    model = DropNet()
    model.train()
    pm = ProcessMesh(np.arange(4).reshape(2, 2), ["data", "model"])
    model.fc.weight.dist_attr = (None, "model")
    strat = Strategy()
    strat.auto_mode = "full"
    eng = Engine(model=model, loss=_loss, optimizer=_SGD(0.0),
                 strategy=strat)
    eng.prepare(input_placements=[("data", None), ("data", None)],
                process_mesh=pm)
    eng.fit(_OneBatch(x, y), epochs=1, verbose=0)
    params = [p.data for p in model.parameters()]
    import paddle_tpu.framework.random as frnd
    l1 = eng._jitted(params, x, y, jax.random.key(1))[1]
    l2 = eng._jitted(params, x, y, jax.random.key(2))[1]
    assert float(l1) != float(l2), (l1, l2)


def test_partial_aligned_to_sharded_operand_grads():
    """ADVICE r4 medium #1: a partial dot output aligned by _elementwise
    to a 'model'-sharded operand must route through ONE psum_scatter
    (transpose: all_gather). The former untied-psum + slice chain
    zero-padded per-rank cotangents outside the local slice, silently
    dropping the other ranks' contributions from upstream grads."""
    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, ("model",))
    B, K, M = 4, 8, 16
    rng = np.random.RandomState(0)
    x = rng.randn(B, K).astype(np.float32)
    w = rng.randn(K, M).astype(np.float32)
    b2 = rng.randn(B, M).astype(np.float32)

    def fn(w_, b2_, x_):
        h = x_ @ w_          # contraction sharded both sides -> partial
        return (h * b2_).sum()

    part = Partitioner(mesh)
    specs = [("model", None), (None, "model"), (None, "model")]
    local = part.partition(fn, (w, b2, x), specs)

    def step(w_, b2_, x_):
        return jax.value_and_grad(local, argnums=(0, 1, 2))(w_, b2_, x_)

    smapped = shard_map(
        step, mesh=mesh,
        in_specs=(P("model", None), P(None, "model"), P(None, "model")),
        out_specs=(P(), (P("model", None), P(None, "model"),
                         P(None, "model"))),
        check_vma=False)
    lv, grads = jax.jit(smapped)(w, b2, x)

    want_l, want_g = jax.value_and_grad(fn, argnums=(0, 1, 2))(w, b2, x)
    np.testing.assert_allclose(float(lv), float(want_l), rtol=1e-5)
    for g, wg in zip(grads, want_g):
        np.testing.assert_allclose(np.asarray(g), np.asarray(wg),
                                   rtol=1e-4, atol=1e-5)
    # and the reshard record shows the scatter, not psum + slice
    ops = [r["op"] for r in part.record]
    assert "psum_scatter" in ops, ops


def test_broadcast_in_dim_sharded_local_size_one():
    """ADVICE r4 medium #2: a dim sharded down to LOCAL size 1 (global
    size == mesh axis size) must not be misclassified as a size-1
    broadcast dim — its sharding was dropped and each rank broadcast its
    own single element to the full dim, replicated-marked but diverging
    across ranks."""
    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, ("model",))
    v = np.arange(4, dtype=np.float32) + 1.0  # global size == mesh size

    def fn(v_):
        return jax.lax.broadcast_in_dim(v_, (4, 8), (0,)).sum()

    part = Partitioner(mesh)
    local = part.partition(fn, (v,), [("model",)])
    smapped = shard_map(local, mesh=mesh, in_specs=(P("model"),),
                        out_specs=P(), check_vma=False)
    got = float(jax.jit(smapped)(v))
    assert got == float(fn(v)), (got, float(fn(v)))
