"""Distributed tests on the 8-device virtual CPU mesh (SURVEY §4: analog of
the reference's hybrid_parallel_* tests under TestMultipleGpus; here SPMD
replaces multi-process)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
import paddle_tpu.distributed as dist
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed.fleet import meta_parallel as mpu


def _init_fleet(dp=1, mp=1, pp=1, sharding=1):
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": dp, "mp_degree": mp,
                               "pp_degree": pp, "sharding_degree": sharding}
    fleet.init(is_collective=True, strategy=strategy)
    return fleet.get_hybrid_communicate_group()


class TestTopology:
    """ref: unittests/collective/fleet/hybrid_parallel_communicate_group.py"""

    def test_coordinate_math(self):
        from paddle_tpu.distributed.topology import CommunicateTopology
        topo = CommunicateTopology(["data", "pipe", "sharding", "model"],
                                   [2, 2, 1, 2])
        assert topo.world_size() == 8
        assert topo.get_rank(data=0, pipe=0, sharding=0, model=0) == 0
        assert topo.get_rank(data=1, pipe=1, sharding=0, model=1) == 7
        coord = topo.get_coord(5)
        assert (coord.data, coord.pipe, coord.sharding, coord.model) == (1, 0, 0, 1)
        # model-axis groups: consecutive ranks
        assert topo.get_comm_list("model")[0] == [0, 1]
        assert topo.get_comm_list("data")[0] == [0, 4]
        assert topo.get_axis_list("pipe", 0) == [0, 1, 4, 5]

    def test_hcg_groups(self):
        hcg = _init_fleet(dp=2, mp=2, pp=2)
        assert hcg.get_model_parallel_world_size() == 2
        assert hcg.get_pipe_parallel_world_size() == 2
        assert hcg.get_data_parallel_world_size() == 2
        assert hcg.get_parallel_mode() == "pipeline_parallel"
        assert hcg.get_model_parallel_group().axis_name == "model"

    def test_fleet_builds_mesh(self):
        _init_fleet(dp=2, mp=4)
        mesh = fleet.fleet_instance.mesh
        assert mesh.shape["data"] == 2
        assert mesh.shape["model"] == 4


class TestCollectivesSPMD:
    """Collectives lower to lax ops inside shard_map regions."""

    def test_allreduce_inside_shard_map(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from paddle_tpu.distributed.mesh import spmd_axes, set_global_mesh, build_mesh
        from paddle_tpu.distributed.collective import all_reduce, new_group
        from paddle_tpu.tensor.tensor import Tensor

        mesh = build_mesh({"model": 4})
        set_global_mesh(mesh)
        g = new_group(list(range(4)), axis_name="model")

        def inner(x):
            with spmd_axes(("model",)):
                t = Tensor(x)
                all_reduce(t, group=g)
                return t.data

        f = shard_map(inner, mesh=mesh, in_specs=P("model"),
                      out_specs=P("model"), check_vma=False)
        x = jnp.arange(8, dtype=jnp.float32)
        out = f(x)
        # each shard holds 2 elems; psum sums across 4 shards elementwise
        shard_sum = x.reshape(4, 2).sum(0)
        np.testing.assert_allclose(np.asarray(out).reshape(4, 2),
                                   np.tile(shard_sum, (4, 1)))


class TestBatchIsendIrecv:
    """ref: unittests/collective/test_communication_api_base — matched
    isend/irecv pairs lower to one ppermute over the mesh axis."""

    def test_shift_by_one_ring(self):
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from paddle_tpu.distributed.mesh import spmd_axes, set_global_mesh, \
            build_mesh
        from paddle_tpu.distributed.collective import (P2POp, isend, irecv,
                                                       batch_isend_irecv,
                                                       new_group)
        from paddle_tpu.tensor.tensor import Tensor

        mesh = build_mesh({"pipe": 4})
        set_global_mesh(mesh)
        g = new_group(list(range(4)), axis_name="pipe")

        def inner(x):
            with spmd_axes(("pipe",)):
                src = Tensor(x)
                dst = Tensor(jnp.zeros_like(x))
                ops = [P2POp(isend, src, 1, group=g),
                       P2POp(irecv, dst, 3, group=g)]  # recv from rank-1
                tasks = batch_isend_irecv(ops)
                tasks[0].wait()
                return dst.data

        f = shard_map(inner, mesh=mesh, in_specs=P("pipe"),
                      out_specs=P("pipe"), check_vma=False)
        x = jnp.arange(8, dtype=jnp.float32)
        out = np.asarray(f(x)).reshape(4, 2)
        expect = np.asarray(x).reshape(4, 2)[[3, 0, 1, 2]]  # ring shift +1
        np.testing.assert_allclose(out, expect)

    def test_shift_with_global_rank_peers(self):
        # peers are global ranks; non-identity groups must translate to
        # group-local coordinates before computing the ring offset
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from paddle_tpu.distributed.mesh import spmd_axes, set_global_mesh, \
            build_mesh
        from paddle_tpu.distributed.collective import (P2POp, isend, irecv,
                                                       batch_isend_irecv,
                                                       new_group)
        from paddle_tpu.tensor.tensor import Tensor

        mesh = build_mesh({"pipe": 4})
        set_global_mesh(mesh)
        # group over global ranks [0,2,4,6]: '+1 neighbor' of rank 0 is 2
        g = new_group([0, 2, 4, 6], axis_name="pipe")

        def inner(x):
            with spmd_axes(("pipe",)):
                src = Tensor(x)
                dst = Tensor(jnp.zeros_like(x))
                ops = [P2POp(isend, src, 2, group=g),
                       P2POp(irecv, dst, 6, group=g)]
                batch_isend_irecv(ops)
                return dst.data

        f = shard_map(inner, mesh=mesh, in_specs=P("pipe"),
                      out_specs=P("pipe"), check_vma=False)
        x = jnp.arange(8, dtype=jnp.float32)
        out = np.asarray(f(x)).reshape(4, 2)
        expect = np.asarray(x).reshape(4, 2)[[3, 0, 1, 2]]  # shift by ONE
        np.testing.assert_allclose(out, expect)

    def test_object_scatter_single(self):
        from paddle_tpu.distributed.collective import scatter_object_list
        out = []
        scatter_object_list(out, [{"a": 1}], src=0)
        assert out == [{"a": 1}]


class TestTensorParallel:
    """ref: unittests/collective/fleet/hybrid_parallel_mp_layers.py — TP
    layers vs dense reference."""

    def setup_method(self, m):
        self.hcg = _init_fleet(mp=4)

    def test_column_row_parallel_matches_dense(self):
        rng = np.random.RandomState(0)
        x = rng.randn(2, 8).astype(np.float32)
        w1 = rng.randn(8, 16).astype(np.float32)
        w2 = rng.randn(16, 8).astype(np.float32)

        col = mpu.ColumnParallelLinear(8, 16, gather_output=False,
                                       has_bias=False)
        row = mpu.RowParallelLinear(16, 8, input_is_parallel=True,
                                    has_bias=False)
        col.weight.set_value(paddle.to_tensor(w1))
        row.weight.set_value(paddle.to_tensor(w2))

        class Block(nn.Layer):
            def __init__(self):
                super().__init__()
                self.col = col
                self.row = row

            def forward(self, t):
                return self.row(self.col(t))

        model = fleet.distributed_model(Block())
        out = model(paddle.to_tensor(x))
        np.testing.assert_allclose(out.numpy(), x @ w1 @ w2, rtol=1e-4,
                                   atol=1e-5)

    def test_tp_backward_matches_dense(self):
        rng = np.random.RandomState(1)
        x = rng.randn(2, 8).astype(np.float32)
        w1 = rng.randn(8, 16).astype(np.float32)
        w2 = rng.randn(16, 8).astype(np.float32)

        col = mpu.ColumnParallelLinear(8, 16, gather_output=False,
                                       has_bias=False)
        row = mpu.RowParallelLinear(16, 8, input_is_parallel=True,
                                    has_bias=False)
        col.weight.set_value(paddle.to_tensor(w1))
        row.weight.set_value(paddle.to_tensor(w2))

        class Block(nn.Layer):
            def __init__(self):
                super().__init__()
                self.col = col
                self.row = row

            def forward(self, t):
                return self.row(self.col(t))

        model = fleet.distributed_model(Block())
        out = model(paddle.to_tensor(x))
        loss = paddle.sum(out)
        loss.backward()

        # dense reference grads
        gout = np.ones((2, 8), np.float32)
        g_w2 = (x @ w1).T @ gout
        g_w1 = x.T @ (gout @ w2.T)
        np.testing.assert_allclose(row.weight.grad.numpy(), g_w2, rtol=1e-4,
                                   atol=1e-4)
        np.testing.assert_allclose(col.weight.grad.numpy(), g_w1, rtol=1e-4,
                                   atol=1e-4)

    def test_vocab_parallel_embedding(self):
        rng = np.random.RandomState(2)
        w = rng.randn(16, 6).astype(np.float32)
        emb = mpu.VocabParallelEmbedding(16, 6)
        emb.weight.set_value(paddle.to_tensor(w))
        ids = np.asarray([[0, 5, 15], [7, 3, 9]])

        class M(nn.Layer):
            def __init__(self):
                super().__init__()
                self.emb = emb

            def forward(self, t):
                return self.emb(t)

        model = fleet.distributed_model(M())
        out = model(paddle.to_tensor(ids))
        np.testing.assert_allclose(out.numpy(), w[ids], rtol=1e-5)

    def test_parallel_cross_entropy(self):
        rng = np.random.RandomState(3)
        logits = rng.randn(4, 16).astype(np.float32)
        labels = np.asarray([0, 5, 11, 15], np.int64)

        class M(nn.Layer):
            def __init__(self):
                super().__init__()
                self.head = mpu.ColumnParallelLinear(8, 16,
                                                     gather_output=False,
                                                     has_bias=False)
                self.ce = mpu.ParallelCrossEntropy()

            def forward(self, t, lab):
                return paddle.mean(self.ce(self.head(t), lab))

        m = M()
        w = rng.randn(8, 16).astype(np.float32)
        m.head.weight.set_value(paddle.to_tensor(w))
        x = rng.randn(4, 8).astype(np.float32)
        model = fleet.distributed_model(m)
        loss = model(paddle.to_tensor(x), paddle.to_tensor(labels))
        # dense reference
        lg = x @ w
        lse = np.log(np.exp(lg - lg.max(-1, keepdims=True)).sum(-1)) + \
            lg.max(-1)
        expect = (lse - lg[np.arange(4), labels]).mean()
        np.testing.assert_allclose(loss.numpy().reshape(()), expect, rtol=1e-4)

    def test_rng_tracker_determinism(self):
        tracker = mpu.get_rng_state_tracker()
        tracker.reset()
        mpu.model_parallel_random_seed(1234)
        with tracker.rng_state("global_seed"):
            a = paddle.randn([4]).numpy()
        mpu.model_parallel_random_seed(1234)
        with tracker.rng_state("global_seed"):
            b = paddle.randn([4]).numpy()
        np.testing.assert_array_equal(a, b)


class TestDataParallelWrapper:
    def test_dp_identity_single_controller(self):
        _init_fleet(dp=8)
        net = nn.Linear(4, 4)
        model = fleet.distributed_model(net)
        x = paddle.randn([2, 4])
        out = model(x)
        loss = paddle.sum(out)
        loss.backward()
        assert net.weight.grad is not None
        with model.no_sync():
            assert not model._grad_sync_enabled


class TestShardingPlacement:
    def test_group_sharded_api(self):
        _init_fleet(sharding=8)
        net = nn.Linear(16, 16)
        opt = paddle.optimizer.Adam(0.01, parameters=net.parameters())
        model, opt, scaler = dist.sharding.group_sharded_parallel(
            net, opt, level="os_g")
        x = paddle.randn([4, 16])
        loss = paddle.sum(model(x))
        loss.backward()
        opt.step()
        opt.clear_grad()
        # optimizer state exists and step worked
        state = opt._optim._accumulators["__state__"]
        assert len(state) == 2
        # sharded placement over the sharding axis (dim0=16 divisible by 8)
        key = net.weight.name or str(id(net.weight))
        m1 = state[key]["moment1"]
        assert m1.sharding is not None

    def test_stage3_param_placement(self):
        _init_fleet(sharding=8)
        net = nn.Linear(16, 16)
        opt = paddle.optimizer.Adam(0.01, parameters=net.parameters())
        model, opt, _ = dist.sharding.group_sharded_parallel(net, opt,
                                                             level="p_g_os")
        out = model(paddle.randn([2, 16]))
        assert out.shape == [2, 16]
