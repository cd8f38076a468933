"""Sequence/context parallelism tests (green-field per SURVEY §5.7)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

import paddle_tpu  # noqa: F401
from paddle_tpu.distributed.mesh import build_mesh, spmd_axes
from paddle_tpu.distributed.fleet.meta_parallel.parallel_layers.ring_attention \
    import ring_attention
from paddle_tpu.ops.pallas.flash_attention import _xla_ref


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_dense(causal):
    mesh = build_mesh({"sep": 4})
    rng = np.random.RandomState(0)
    b, s, h, d = 2, 32, 2, 16
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    scale = 1.0 / np.sqrt(d)

    def inner(qq, kk, vv):
        with spmd_axes(("sep",)):
            return ring_attention(qq, kk, vv, "sep", causal=causal,
                                  scale=scale)

    f = shard_map(inner, mesh=mesh,
                  in_specs=(P(None, "sep"), P(None, "sep"), P(None, "sep")),
                  out_specs=P(None, "sep"), check_vma=False)
    out = np.asarray(f(q, k, v))
    ref = np.asarray(_xla_ref(q, k, v, causal, scale))
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_ring_attention_grads():
    mesh = build_mesh({"sep": 4})
    rng = np.random.RandomState(1)
    b, s, h, d = 1, 16, 1, 8
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    scale = 1.0 / np.sqrt(d)

    def inner(qq, kk, vv):
        with spmd_axes(("sep",)):
            o = ring_attention(qq, kk, vv, "sep", causal=True, scale=scale)
        return jax.lax.psum(jnp.sum(o * o), "sep")

    f = shard_map(inner, mesh=mesh,
                  in_specs=(P(None, "sep"), P(None, "sep"), P(None, "sep")),
                  out_specs=P(), check_vma=True)
    g = jax.grad(lambda a, b_, c: f(a, b_, c), argnums=(0, 1, 2))(q, k, v)

    def ref_loss(a, b_, c):
        return jnp.sum(_xla_ref(a, b_, c, True, scale) ** 2)

    gr = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for x, y in zip(g, gr):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-3,
                                   atol=1e-4)
