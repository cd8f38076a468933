"""SPMD execution engine.

This is where eager Layers meet the mesh: `spmd_apply` runs a Layer's
forward inside jax.shard_map over the global mesh, threading parameters as
explicit inputs with PartitionSpecs derived from each Parameter's
`dist_attr`. Because the whole SPMD forward is recorded as ONE tape op (via
ops.apply), `loss.backward()` differentiates straight through the collectives
— shard_map's AD inserts the mirrored collectives — and parameter grads land
on `param.grad` like any eager op.

This replaces the reference's per-rank eager execution + ProcessGroupNCCL
(SURVEY §7 "ProcessGroupXLA-equivalent").
"""
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from ....autograd import tape
from ....framework import random as frnd
from ....tensor.tensor import Tensor
from ....ops import apply
from ...mesh import global_mesh, spmd_axes


def param_spec(p):
    """PartitionSpec from a Parameter's dist_attr (default replicated)."""
    da = getattr(p, "dist_attr", None)
    if da is None:
        return P()
    return P(*da)


def collect_params(layer):
    """Stable (names, tensors, specs) triple for a layer tree."""
    names, tensors, specs = [], [], []
    for n, p in layer.named_parameters():
        names.append(n)
        tensors.append(p)
        specs.append(param_spec(p))
    for n, b in layer.named_buffers():
        names.append("buffer:" + n)
        tensors.append(b)
        specs.append(param_spec(b))
    return names, tensors, specs


class _Swap:
    """Temporarily substitute tensor .data with traced arrays."""

    def __init__(self, tensors, arrays):
        self.tensors = tensors
        self.arrays = arrays

    def __enter__(self):
        self.saved = [t.data for t in self.tensors]
        for t, a in zip(self.tensors, self.arrays):
            t.data = a

    def __exit__(self, *exc):
        for t, s in zip(self.tensors, self.saved):
            t.data = s
        return False


def spmd_forward(layer, inputs, in_specs=None, out_spec=None, mesh=None,
                 data_axis=None):
    """Run layer(*inputs) as one SPMD region over the mesh, recorded as a
    single tape node (so backward works eagerly).

    inputs: list of Tensors (replicated unless in_specs given, or sharded on
    batch over `data_axis`).
    """
    mesh = mesh or global_mesh()
    names, ptensors, pspecs = collect_params(layer)
    n_params = len(ptensors)
    if in_specs is None:
        if data_axis and data_axis in mesh.axis_names \
                and mesh.shape[data_axis] > 1:
            in_specs = [P(data_axis) for _ in inputs]
        else:
            in_specs = [P() for _ in inputs]
    out_spec = out_spec if out_spec is not None else P()
    axis_names = tuple(mesh.axis_names)

    def inner(key, *arrays):
        parrs = arrays[:n_params]
        iarrs = arrays[n_params:]
        with spmd_axes(axis_names), _Swap(ptensors, list(parrs)), \
                frnd.key_scope(key), tape.no_grad():
            wrapped = [Tensor(a) for a in iarrs]
            out = layer(*wrapped)
        if isinstance(out, (list, tuple)):
            return tuple(o.data if isinstance(o, Tensor) else o for o in out)
        return out.data if isinstance(out, Tensor) else out

    smapped = shard_map(
        inner, mesh=mesh,
        in_specs=(P(),) + tuple(pspecs) + tuple(in_specs),
        out_specs=out_spec,
        check_vma=True,
    )
    key = frnd.next_key()
    return apply(lambda *arrs: smapped(key, *arrs), *ptensors, *inputs,
                 name="spmd_forward")


def functional_loss_fn(layer, loss_builder):
    """Build pure fn(params_arrays, key, *input_arrays) -> scalar loss for use
    with jax.value_and_grad in compiled train steps. loss_builder(outputs,
    *inputs) -> Tensor."""
    names, ptensors, pspecs = collect_params(layer)

    def fn(parrs, key, *iarrs):
        with _Swap(ptensors, list(parrs)), frnd.key_scope(key), tape.no_grad():
            wrapped = [Tensor(a) for a in iarrs]
            out = loss_builder(layer, *wrapped)
        return out.data if isinstance(out, Tensor) else out

    return fn, names, ptensors, pspecs
