"""Tensor-parallel collective primitives.

ref: python/paddle/distributed/fleet/layers/mpu/mp_ops.py —
_c_identity:27 (fwd identity / bwd allreduce), _c_concat:91, _c_split:153,
_mp_allreduce:219 (fwd allreduce / bwd identity),
_c_softmax_with_cross_entropy:375, split:653.

Each primitive is a jax.custom_vjp over the 'model' mesh axis, applied
through the tape so eager autograd and compiled SPMD agree. Outside an SPMD
region (mp degree 1) they are passthrough.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

from .....ops import apply
from .....tensor.tensor import Tensor
from ....mesh import in_spmd_region
from jax.lax import axis_size as _axis_size


@functools.lru_cache(maxsize=None)
def _identity_fn(axis):
    @jax.custom_vjp
    def f(x):
        return x

    def fwd(x):
        return x, None

    def bwd(_, g):
        return (lax.psum(g, axis),)

    f.defvjp(fwd, bwd)
    return f


@functools.lru_cache(maxsize=None)
def _allreduce_fn(axis):
    @jax.custom_vjp
    def f(x):
        return lax.psum(x, axis)

    def fwd(x):
        return lax.psum(x, axis), None

    def bwd(_, g):
        # the primal is per-shard (varying over `axis`); the psum's
        # cotangent arrives invariant, and shard_map(check_vma=True)
        # type-checks the two — same values, cast to varying
        return (lax.pcast(g, axis, to="varying"),)

    f.defvjp(fwd, bwd)
    return f


def _c_identity(tensor, group=None, skip_c_identity_dynamic=False):
    """fwd identity, bwd allreduce (column-parallel input)."""
    axis = group.axis_name if group is not None else "model"
    if not in_spmd_region(axis):
        return tensor
    return apply(_identity_fn(axis), tensor, name="c_identity")


def _mp_allreduce(tensor, op=None, group=None, use_calc_stream=True,
                  use_model_parallel=True):
    """fwd allreduce, bwd identity (row-parallel output)."""
    axis = group.axis_name if group is not None else "model"
    if not in_spmd_region(axis):
        return tensor
    return apply(_allreduce_fn(axis), tensor, name="mp_allreduce")


def _c_concat(tensor, group=None):
    """all_gather along last dim (ref: mp_ops.py:91)."""
    axis = group.axis_name if group is not None else "model"
    if not in_spmd_region(axis):
        return tensor
    return apply(lambda a: lax.all_gather(a, axis, axis=a.ndim - 1, tiled=True),
                 tensor, name="c_concat")


def _c_split(tensor, group=None):
    """keep local slice of last dim (ref: mp_ops.py:153)."""
    axis = group.axis_name if group is not None else "model"
    if not in_spmd_region(axis):
        return tensor

    def fn(a):
        n = _axis_size(axis)
        idx = lax.axis_index(axis)
        sz = a.shape[-1] // n
        return lax.dynamic_slice_in_dim(a, idx * sz, sz, axis=a.ndim - 1)

    return apply(fn, tensor, name="c_split")


def _c_softmax_with_cross_entropy(logits, label, group=None,
                                  return_softmax=False,
                                  ignore_index=-100):
    """Vocab-parallel softmax CE (ref: mp_ops.py:375 + C++
    c_softmax_with_cross_entropy_op). logits sharded on last (vocab) dim."""
    axis = group.axis_name if group is not None else "model"
    lab = label.data if isinstance(label, Tensor) else jnp.asarray(label)

    if not in_spmd_region(axis):
        from .....nn.functional.loss import cross_entropy
        loss = cross_entropy(logits, label, reduction="none",
                             ignore_index=ignore_index)
        if loss.ndim < logits.ndim:
            from .....tensor.manipulation import unsqueeze
            loss = unsqueeze(loss, -1)
        if return_softmax:
            from .....nn.functional import softmax
            return loss, softmax(logits)
        return loss

    def fn(lg):
        # shared shard-CE core (ops/fused_ce.py) — one implementation of
        # the global-max/psum/picked-logit math for both this op and the
        # trainer's fused chunked head+CE
        from .....ops.fused_ce import vocab_parallel_ce_rows
        lab_ = lab
        if lab_.ndim == lg.ndim:
            lab_ = jnp.squeeze(lab_, -1)
        loss, shifted, gsum = vocab_parallel_ce_rows(
            lg, lab_, axis=axis, ignore_index=ignore_index)
        sm = jnp.exp(shifted) / gsum
        return loss[..., None], sm

    loss, sm = apply(fn, logits, n_outputs=2, name="c_softmax_ce")
    if return_softmax:
        return loss, sm
    return loss


def split(x, size, operation, axis=0, num_partitions=1, gather_out=True,
          weight_attr=None, bias_attr=None, name=None):
    """Tensor-split helper API (ref: mp_ops.py:653). Builds the matching
    parallel layer."""
    from .mp_layers import (ColumnParallelLinear, RowParallelLinear,
                            VocabParallelEmbedding)
    if operation == "linear":
        if axis == 0:
            layer = RowParallelLinear(size[0], size[1],
                                      weight_attr=weight_attr,
                                      has_bias=bias_attr is not False,
                                      input_is_parallel=False)
        else:
            layer = ColumnParallelLinear(size[0], size[1],
                                         weight_attr=weight_attr,
                                         has_bias=bias_attr is not False,
                                         gather_output=gather_out)
        return layer(x)
    if operation == "embedding":
        layer = VocabParallelEmbedding(size[0], size[1],
                                       weight_attr=weight_attr)
        return layer(x)
    raise ValueError(f"unsupported split operation {operation}")
