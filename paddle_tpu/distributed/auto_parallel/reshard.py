"""Resharder — insert the communication that converts one sharding into
another.

ref: python/paddle/distributed/auto_parallel/reshard.py:1007 (Resharder:
2964 LoC of slice/concat/send/recv insertion over ProgramDesc). The
TPU-native version is a CHAIN OF XLA COLLECTIVES applied inside the SPMD
region — per mesh axis, the movement of that axis between tensor dims
decides the primitive:

  src dim == dst dim      -> nothing
  moved between dims      -> lax.all_to_all   (keeps memory flat: each
                             device exchanges only 1/n of its shard)
  sharded -> unsharded    -> lax.all_gather
  unsharded -> sharded    -> local slice at axis_index (free: drops data)
  Partial -> replicated   -> lax.psum
  Partial -> sharded      -> lax.psum_scatter (reduce straight to owner)

`plan_conflict` is the cost rule the reference's planner applies op-level:
when two operands disagree, reshard the one that moves fewer bytes —
"prefer keeping the larger operand in place".
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from jax.lax import axis_size as _axis_size

PARTIAL = "__partial__"  # pseudo entry: spec[0] may carry ("partial", axis)


@functools.lru_cache(maxsize=None)
def _psum_untied_fn(axis):
    """psum whose TRANSPOSE is identity: resolving a partial sum into a
    replicated value whose downstream consumers are replicated. lax.psum
    transposes to psum, which double-counts when the caller separately
    completes parameter grads with an explicit psum (the auto-parallel
    Partitioner's contract)."""
    @jax.custom_vjp
    def f(x):
        return lax.psum(x, axis)

    def fwd(x):
        return f(x), None

    def bwd(_, g):
        return (g,)

    f.defvjp(fwd, bwd)
    return f


class ReshardRecord(list):
    """Collects the collective ops a reshard emitted (test/introspection)."""

    def op(self, name, axis, **kw):
        self.append({"op": name, "axis": axis, **kw})


def _axis_dim(spec, axis):
    """Which tensor dim `axis` shards in `spec` (None if absent)."""
    if spec is None:
        return None
    for d, a in enumerate(spec):
        if a == axis:
            return d
        if isinstance(a, tuple) and axis in a:
            return d
    return None


def _entry_axes(e):
    """Axes of one spec entry (None -> (), 'x' -> ('x',), tuple as-is)."""
    if e is None:
        return ()
    return e if isinstance(e, tuple) else (e,)


def _axes_of(spec):
    out = []
    if spec is None:
        return out
    for a in spec:
        if a is None:
            continue
        for x in (a if isinstance(a, tuple) else (a,)):
            out.append(x)
    return out


def reshard_spec(x, src, dst, partial_axes=(), record=None,
                 untied_grad=False):
    """Convert array `x` (local shard, inside shard_map) from sharding
    `src` to `dst`. specs: tuple(axis-name-or-None per dim). partial_axes:
    mesh axes over which x is a PARTIAL sum (pending reduction).
    untied_grad: resolve partials with the identity-transpose psum (see
    _psum_untied_fn — for callers that complete param grads themselves).
    Returns the resharded local array."""
    rec = record if record is not None else ReshardRecord()
    ndim = x.ndim
    src = tuple(src) if src is not None else (None,) * ndim
    dst = tuple(dst) if dst is not None else (None,) * ndim

    # 1. pending partial sums: reduce straight to the destination owner
    for axis in partial_axes:
        ddim = _axis_dim(dst, axis)
        sdim = _axis_dim(src, axis)
        if sdim is not None:
            raise ValueError(
                f"axis {axis!r} cannot be both partial and sharded in src")
        if ddim is not None:
            x = lax.psum_scatter(x, axis, scatter_dimension=ddim, tiled=True)
            rec.op("psum_scatter", axis, dim=ddim)
            # merge into (not overwrite) the dim's existing sharding: the
            # scatter tiles WITHIN each existing block, so `axis` lands as
            # the innermost entry
            lst = list(src)
            prev = _entry_axes(lst[ddim])
            lst[ddim] = axis if not prev else prev + (axis,)
            src = tuple(lst)
        else:
            x = (_psum_untied_fn(axis)(x) if untied_grad
                 else lax.psum(x, axis))
            rec.op("psum", axis)

    # Multi-axis tuple entries (a dim sharded by several mesh axes at
    # once): the optimal move/gather chains below assume one axis per
    # dim — partial moves out of a tuple entry reorder the nested tiling
    # and corrupt both data and bookkeeping. Fall back to the always-
    # correct canonical chain: gather every sharded dim (innermost axis
    # first, preserving tile order), then re-slice to dst (outer axis
    # first). Bandwidth-suboptimal, never wrong.
    if any(isinstance(e, tuple) for e in src + dst):
        for d, e in enumerate(src):
            for axis in reversed(_entry_axes(e)):  # innermost first
                x = lax.all_gather(x, axis, axis=d, tiled=True)
                rec.op("all_gather", axis, dim=d)
        src = (None,) * ndim
        for d, e in enumerate(dst):
            for axis in _entry_axes(e):  # outer first: nested block order
                n = _axis_size(axis)
                idx = lax.axis_index(axis)
                sz = x.shape[d] // n
                x = lax.dynamic_slice_in_dim(x, idx * sz, sz, axis=d)
                rec.op("slice", axis, dim=d)
        return x

    # 2. axis moves between dims: all_to_all. A move may only execute when
    # its destination dim is not still sharded by a DIFFERENT axis (else
    # the spec bookkeeping would clobber that axis and emit a wrong
    # chain). Moves are drained in any safe order; a cycle (e.g. the dim
    # swap ('x','y') -> ('y','x')) has no safe order, so one blocking
    # axis is all_gathered to break it — step 4 re-shards the gathered
    # axis with a free local slice.
    while True:
        moves = []
        for axis in _axes_of(src):
            sdim = _axis_dim(src, axis)
            ddim = _axis_dim(dst, axis)
            if ddim is not None and ddim != sdim:
                moves.append((axis, sdim, ddim))
        if not moves:
            break
        safe = next(((a, s, d) for a, s, d in moves
                     if src[d] is None or src[d] == a), None)
        if safe is None:
            # cycle: gather whatever shards the first move's destination
            _, _, ddim = moves[0]
            blockers = src[ddim]
            for bx in (blockers if isinstance(blockers, tuple)
                       else (blockers,)):
                x = lax.all_gather(x, bx, axis=ddim, tiled=True)
                rec.op("all_gather", bx, dim=ddim)
            lst = list(src)
            lst[ddim] = None
            src = tuple(lst)
            continue
        axis, sdim, ddim = safe
        x = lax.all_to_all(x, axis, split_axis=ddim, concat_axis=sdim,
                           tiled=True)
        rec.op("all_to_all", axis, src_dim=sdim, dst_dim=ddim)
        lst = list(src)
        lst[sdim] = None
        lst[ddim] = axis
        src = tuple(lst)

    # 3. sharded -> unsharded: all_gather
    for axis in _axes_of(src):
        if _axis_dim(dst, axis) is None:
            sdim = _axis_dim(src, axis)
            x = lax.all_gather(x, axis, axis=sdim, tiled=True)
            rec.op("all_gather", axis, dim=sdim)
            lst = list(src)
            lst[sdim] = None
            src = tuple(lst)

    # 4. unsharded -> sharded: free local slice
    for axis in _axes_of(dst):
        if _axis_dim(src, axis) is None:
            ddim = _axis_dim(dst, axis)
            n = _axis_size(axis)
            idx = lax.axis_index(axis)
            sz = x.shape[ddim] // n
            x = lax.dynamic_slice_in_dim(x, idx * sz, sz, axis=ddim)
            rec.op("slice", axis, dim=ddim)
    return x


def comm_bytes(shape, dtype, src, dst, mesh_shape):
    """Approximate per-device bytes moved by reshard_spec(src -> dst)
    (all_to_all ~ local bytes; all_gather ~ (n-1)/n of global bytes;
    slice free)."""
    item = jnp.dtype(dtype).itemsize
    local = int(np.prod(shape)) * item
    for a in _axes_of(src):
        local //= int(mesh_shape.get(a, 1))
    total = 0
    src_t = tuple(src) if src is not None else (None,) * len(shape)
    dst_t = tuple(dst) if dst is not None else (None,) * len(shape)
    for axis in set(_axes_of(src_t)):
        sdim, ddim = _axis_dim(src_t, axis), _axis_dim(dst_t, axis)
        n = int(mesh_shape.get(axis, 1))
        if ddim is not None and ddim != sdim:
            total += local  # all_to_all: exchange ~its whole local shard
        elif ddim is None:
            total += local * (n - 1)  # all_gather
    return total


def plan_conflict(shape_a, spec_a, shape_b, spec_b, dtype="float32",
                  mesh_shape=None):
    """Which operand should move when two disagree? The one whose reshard
    moves fewer bytes — i.e. keep the LARGER operand in place
    (ref: auto_parallel/cost_model + reshard planning). Returns "a" or
    "b" (the operand to reshard, toward the other's sharding)."""
    mesh_shape = mesh_shape or {}
    cost_a = comm_bytes(shape_a, dtype, spec_a, spec_b, mesh_shape)
    cost_b = comm_bytes(shape_b, dtype, spec_b, spec_a, mesh_shape)
    return "a" if cost_a <= cost_b else "b"
