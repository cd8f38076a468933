"""Megatron-style sequence parallelism utilities.

Green-field per SURVEY §5.7 (SP is absent from the reference snapshot;
the design follows the upstream-Paddle/Megatron AllGatherOp /
ReduceScatterOp, ColumnSequenceParallelLinear, RowSequenceParallelLinear,
mark_as_sequence_parallel_parameter surface) — the OTHER half of §5.7's
SP plan, complementing ring attention (CP):
between TP regions the activations live SEQUENCE-SHARDED over the
'model' axis, so the norms/residual/dropout of every layer touch only
s/mp tokens per device. The collective pair replacing the classic
_c_identity/_mp_allreduce (mp_ops.py:27,219) is

  entry (column-parallel in):  all_gather(seq)     [bwd: reduce_scatter]
  exit  (row-parallel out):    reduce_scatter(seq) [bwd: all_gather]

— the same total bytes as the allreduce it replaces, but the activation
tensors BETWEEN the collectives shrink by 1/mp.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

from ....ops import apply
from jax.lax import axis_size as _axis_size
from ...mesh import in_spmd_region


@functools.lru_cache(maxsize=None)
def _allgather_seq_fn(axis, seq_axis):
    @jax.custom_vjp
    def f(x):
        return lax.all_gather(x, axis, axis=seq_axis, tiled=True)

    def fwd(x):
        return f(x), None

    def bwd(_, g):
        # transpose of tiled all_gather: reduce-scatter back to the shard
        return (lax.psum_scatter(g, axis, scatter_dimension=seq_axis,
                                 tiled=True),)

    f.defvjp(fwd, bwd)
    return f


@functools.lru_cache(maxsize=None)
def _allgather_seq_slice_grad_fn(axis, seq_axis):
    """all_gather whose TRANSPOSE is a plain slice: use when the gathered
    tensor feeds REPLICATED computation (e.g. the pre-lm-head gather), so
    every rank's cotangent is identical — a psum_scatter there would
    overcount by the group size (Megatron's
    gather_from_sequence_parallel_region(tensor_parallel_output_grad=
    False))."""
    @jax.custom_vjp
    def f(x):
        return lax.all_gather(x, axis, axis=seq_axis, tiled=True)

    def fwd(x):
        return f(x), None

    def bwd(_, g):
        n = _axis_size(axis)
        idx = lax.axis_index(axis)
        sz = g.shape[seq_axis] // n
        return (lax.dynamic_slice_in_dim(g, idx * sz, sz, axis=seq_axis),)

    f.defvjp(fwd, bwd)
    return f


@functools.lru_cache(maxsize=None)
def _scatter_seq_fn(axis, seq_axis):
    """ScatterOp: replicated full sequence -> this rank's shard (fwd
    slice); transpose all_gathers the per-rank shard cotangents (each
    position's cotangent lives on exactly one rank)."""
    @jax.custom_vjp
    def f(x):
        n = _axis_size(axis)
        idx = lax.axis_index(axis)
        sz = x.shape[seq_axis] // n
        return lax.dynamic_slice_in_dim(x, idx * sz, sz, axis=seq_axis)

    def fwd(x):
        return f(x), None

    def bwd(_, g):
        return (lax.all_gather(g, axis, axis=seq_axis, tiled=True),)

    f.defvjp(fwd, bwd)
    return f


@functools.lru_cache(maxsize=None)
def _reduce_scatter_seq_fn(axis, seq_axis):
    @jax.custom_vjp
    def f(x):
        return lax.psum_scatter(x, axis, scatter_dimension=seq_axis,
                                tiled=True)

    def fwd(x):
        return f(x), None

    def bwd(_, g):
        return (lax.all_gather(g, axis, axis=seq_axis, tiled=True),)

    f.defvjp(fwd, bwd)
    return f


def all_gather_sp(x, axis_name="model", seq_axis=1, grad_mode="reduce_scatter"):
    """AllGatherOp: sequence-sharded -> full sequence (fwd).

    grad_mode="reduce_scatter" (default): transpose sums every rank's
    distinct cotangent — correct when downstream is tensor-parallel.
    grad_mode="slice": transpose takes this rank's slice — correct when
    downstream is replicated (identical cotangents per rank)."""
    if not in_spmd_region(axis_name):
        return x
    fn = (_allgather_seq_fn(axis_name, seq_axis)
          if grad_mode == "reduce_scatter"
          else _allgather_seq_slice_grad_fn(axis_name, seq_axis))
    return apply(fn, x, name="sp_allgather")


def scatter_sp(x, axis_name="model", seq_axis=1):
    """ScatterOp: replicated full sequence -> per-rank shard (fwd slice,
    bwd all_gather)."""
    if not in_spmd_region(axis_name):
        return x
    return apply(_scatter_seq_fn(axis_name, seq_axis), x, name="sp_scatter")


def reduce_scatter_sp(x, axis_name="model", seq_axis=1):
    """ReduceScatterOp: partial full-sequence -> reduced sequence shard."""
    if not in_spmd_region(axis_name):
        return x
    return apply(_reduce_scatter_seq_fn(axis_name, seq_axis), x,
                 name="sp_reduce_scatter")


class ColumnSequenceParallelLinear:
    """Mixin-style wrapper: a ColumnParallelLinear whose input arrives
    sequence-sharded (upstream-Paddle/Megatron
    ColumnSequenceParallelLinear; SURVEY §5.7). Implemented as a thin
    module over the existing layer to keep one Linear implementation.

    gather_input=False: the caller already all_gather_sp'd the sequence
    (one shared gather per block feeds q/k/v or gate/up, so the backward
    emits ONE reduce-scatter on the SUMMED cotangents instead of one per
    linear — Megatron's fused-qkv collective volume with separate
    weights)."""

    def __new__(cls, in_features, out_features, gather_input=True, **kw):
        from ..meta_parallel import ColumnParallelLinear
        from ..meta_parallel.parallel_layers import mp_ops

        class _Col(ColumnParallelLinear):
            def forward(self, x):
                from ....nn import functional as F
                from ....tensor.tensor import Tensor
                if not isinstance(x, Tensor):
                    x = Tensor(jnp.asarray(x))
                # the gather's reduce-scatter transpose REPLACES
                # _c_identity's psum — stacking both would overcount dh
                # by the TP degree
                full = all_gather_sp(x) if self._sp_gather_input else x
                out = F.linear(full, self.weight, self.bias)
                if self.gather_output:
                    out = mp_ops._c_concat(out, group=self.group)
                return out

        kw.setdefault("gather_output", False)
        inst = _Col(in_features, out_features, **kw)
        inst._sp_gather_input = gather_input
        if inst.bias is not None:
            # column bias is output-sharded over 'model' (complete per
            # rank) — no marking needed
            pass
        return inst


class RowSequenceParallelLinear:
    """RowParallelLinear whose output is reduce-SCATTERED over the
    sequence dim instead of allreduced (upstream-Paddle/Megatron
    RowSequenceParallelLinear; SURVEY §5.7)."""

    def __new__(cls, in_features, out_features, **kw):
        from ..meta_parallel import RowParallelLinear
        from ..meta_parallel.parallel_layers import mp_ops

        class _Row(RowParallelLinear):
            def forward(self, x):
                from ....nn import functional as F
                if not self.input_is_parallel:
                    x = mp_ops._c_split(x, group=self.group)
                out = F.linear(x, self.weight)
                out = reduce_scatter_sp(out)
                if self.bias is not None:
                    out = out + self.bias
                return out

        kw.setdefault("input_is_parallel", True)
        inst = _Row(in_features, out_features, **kw)
        if inst.bias is not None:
            # the bias is added AFTER the sequence reduce-scatter: it acts
            # on this rank's s/mp tokens only, so its grad is partial over
            # 'model' — tag it for the trainer/hybrid grad sync psum
            mark_as_sequence_parallel_parameter(inst.bias)
        return inst


def mark_as_sequence_parallel_parameter(param):
    """ref: mark_as_sequence_parallel_parameter — tags params whose grads
    are partial over the TP group because they act on sequence shards
    (norm weights between TP regions); hybrid grad sync psums them."""
    param.sequence_parallel = True
    return param
