#!/usr/bin/env python3
"""What the AdamW update of ONE parameter tensor costs the TPU compiler,
flat (the trainer before PR 35: `reshape(-1).astype(float32)`, rank-1
moments) against shaped (PR 35: the block's own shape, the owned slice
along one axis of it), on one chip and on a 2 x 2 mesh ('sharding' 2 x
'model' 2, the four-chip cell's).

  here:  JAX_PLATFORMS=cpu python3 docs/probes/adamw_layout_probe.py

A probe, run by hand: no benchmark cell runs it, no test imports it.
NOTHING RUNS: every program is compiled for a DESCRIBED v5e:2x2 (as
`perf/aot_check.py` does it) and the compiler's own accounting is read:
`cost_analysis()` bytes accessed and `optimal_seconds`,
`memory_analysis()` temporaries, and the collectives and whole-tensor
copies of the compiled text by name and shape. A compile is not a time:
the chip's numbers are the benchmark's (`train_optimizer_ms`, PERF.md 5).
The update is `train_step._adamw_core`'s arithmetic, written here again
so that the probe measures the LAYOUT and nothing of the trainer.

Cases (bf16 parameters, gradients and moments, as both cells hold them):
the dense cell's w1 stack `[18, 2048, 8192]` on one chip; on the mesh the
four-chip cell's local blocks, w1 `[8, 4096, 7168]` (axis 0: layers; the
updated slices back by `all_gather`, and IN PLACE as the trainer does
it) and the vocabulary-parallel embedding `[46272, 4096]` along axis 0
(what `moment_axis` picks: vocabulary rows) and along axis 1 (hidden).

Output, one JSON line a case (jax 0.9.0, libtpu 0.0.34; `ms` =
optimal_seconds x 1e3), as a table:

  1chip [18,2048,8192] flat              15.10 GB 47.7 ms temp 3.62 GB  no collective | whole copies: 2 x f32[4608,64,8,128]
  1chip [18,2048,8192] shaped             4.23 GB 13.3 ms temp 0.00 GB  no collective | none
  2x2   [8,4096,7168]  flat              13.15 GB 41.5 ms temp 2.82 GB  all-reduce f32[234881024]; all-gather bf16[234881024] | 2 x f32[4096,56,8,128]
  2x2   [8,4096,7168]  axis 0             7.28 GB 23.0 ms temp 1.41 GB  reduce-scatter f32[4,4096,7168]; all-gather bf16[8,4096,7168] | 2 x bf16[8,4096,7168]
  2x2   [8,4096,7168]  axis 0, in place   6.58 GB 20.8 ms temp 1.41 GB  reduce-scatter f32[4,4096,7168]; collective-permute bf16[4,4096,7168] | none
  2x2   [46272,4096]   flat              10.61 GB 33.5 ms temp 2.27 GB  all-reduce f32[189530112]; all-gather bf16[189530112] | 2 x f32[5784,32,8,128]
  2x2   [46272,4096]   axis 0             6.66 GB 21.0 ms temp 1.52 GB  all-reduce f32[46512,4096]; collective-permute f32[120,4096]; all-gather bf16[46272,4096] | 2 x bf16[46272,4096]
  2x2   [46272,4096]   axis 1             5.88 GB 18.5 ms temp 1.14 GB  reduce-scatter f32[46272,2048]; all-gather bf16[46272,4096] | 2 x bf16[46272,4096]

What it showed. (1) A rank-1 `psum_scatter` is what the compiler rewrites
as an all-reduce of the whole tensor, between two float32 relayout copies;
along the LEADING axis of a rank-3 block, or the minor axis of a rank-2
one, it stays a reduce-scatter of half the bytes. (2) Along the rows of a
rank-2 block (its second-minor, sublane, dimension) it is rewritten all
the same, at every row count tried (46,272, 46,080, 32,768, 23,136): a
`pad`, one all-reduce and a small permute; on the chip that embedding's
reduction took 11.4 ms beside the equally large head's true
reduce-scatter at 10.4 (PERF.md 6, PR 35), so the rule (first axis that
divides) was left alone. (3) The result of a whole-block `all_gather`
that leaves the program is copied once, and the donated block it
replaces once more on its way in: two whole-tensor bf16 copies a tensor
that the in-place form (dynamic_update_slice of the own slice,
collective-permute of the other) does not have.
"""
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax, shard_map  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

B1, B2, EPS, WD = 0.9, 0.95, 1e-8, 0.01
BF16, F32 = jnp.bfloat16, jnp.float32
FLAT = "flat"


def adamw(pl, gl, m, v, step, lr):
    """train_step._adamw_core: float32 inside, bf16 moments out."""
    m = B1 * m.astype(F32) + (1 - B1) * gl
    v = B2 * v.astype(F32) + (1 - B2) * gl * gl
    t = step.astype(F32)
    pl = pl * (1 - lr * WD) - lr * (m / (1 - B1 ** t)) / (
        jnp.sqrt(v / (1 - B2 ** t)) + EPS)
    return pl, m.astype(BF16), v.astype(BF16)


def flat_update(S):
    """The parent's `_update12_scaffold`: a flattened float32 copy of the
    gradient and of the parameter, rank-1 moments."""
    def f(p, g, m, v, step, lr):
        gf = g.reshape(-1).astype(F32)
        pf = p.reshape(-1).astype(F32)
        if S > 1:
            gl = lax.psum_scatter(gf, "sharding", scatter_dimension=0,
                                  tiled=True)
            chunk = gf.shape[0] // S
            pl = lax.dynamic_slice_in_dim(
                pf, lax.axis_index("sharding") * chunk, chunk)
        else:
            gl, pl = gf, pf
        pl, m, v = adamw(pl, gl, m, v, step, lr)
        if S > 1:
            pl = lax.all_gather(pl, "sharding", axis=0, tiled=True)
        return pl.reshape(p.shape).astype(p.dtype), m, v
    return f


def shaped_update(S, k, in_place=False):
    """PR 35's: the block's own shape, the owned slice along axis k; the
    updated slices back by all_gather, or (`in_place`, what the trainer
    does) written into the donated block: this rank's by
    dynamic_update_slice, the others' as they arrive by permute."""
    def f(p, g, m, v, step, lr):
        if S == 1:
            pl, m, v = adamw(p.astype(F32), g.astype(F32), m, v, step, lr)
            return pl.astype(p.dtype), m, v
        gl = lax.psum_scatter(g.astype(F32), "sharding",
                              scatter_dimension=k, tiled=True)
        chunk = p.shape[k] // S
        pl = lax.dynamic_slice_in_dim(
            p, lax.axis_index("sharding") * chunk, chunk, axis=k)
        pl, m, v = adamw(pl.astype(F32), gl, m, v, step, lr)
        pl = pl.astype(p.dtype)
        if not in_place:
            return lax.all_gather(pl, "sharding", axis=k, tiled=True), m, v
        r = lax.axis_index("sharding")
        out = lax.dynamic_update_slice_in_dim(p, pl, r * chunk, axis=k)
        for j in range(1, S):
            got = lax.ppermute(pl, "sharding",
                               [(i, (i + j) % S) for i in range(S)])
            out = lax.dynamic_update_slice_in_dim(
                out, got, ((r - j) % S) * chunk, axis=k)
        return out, m, v
    return f


def compile_case(topo, chips, shape, how):
    devs = list(topo.devices)[:chips]
    S = 2 if chips == 4 else 1
    mesh = Mesh(np.array(devs).reshape((S, chips // S)),
                ("sharding", "model"))
    n = int(np.prod(shape))
    if how == FLAT:
        fn, mshape = flat_update(S), (n,)
        mspec = P("sharding") if S > 1 else P()
    else:
        k, in_place = how if isinstance(how, tuple) else (how, False)
        fn, mshape = shaped_update(S, k, in_place), shape
        mspec = (P(*[None] * k, "sharding") if S > 1 else P())
    rep = P()
    sm = shard_map(fn, mesh=mesh,
                   in_specs=(rep, rep, mspec, mspec, rep, rep),
                   out_specs=(rep, mspec, mspec), check_vma=False)

    def sds(s, dt, spec):
        return jax.ShapeDtypeStruct(s, dt, sharding=NamedSharding(mesh, spec))
    args = (sds(shape, BF16, rep), sds(shape, BF16, rep),
            sds(mshape, BF16, mspec), sds(mshape, BF16, mspec),
            sds((), jnp.int32, rep), sds((), F32, rep))
    compiled = jax.jit(sm, donate_argnums=(0, 2, 3)).lower(*args).compile()
    cost = compiled.cost_analysis()
    text = compiled.as_text()
    coll, copies = {}, {}
    for line in text.split("\n"):
        op = re.search(r" (all-reduce|all-gather|reduce-scatter|"
                       r"collective-permute|copy)(?:-start)?\(", line)
        shp = re.search(r"= \(?((?:f32|bf16)\[[0-9,]*\])", line)
        if not (op and shp) or shp.start() > op.start():
            continue
        op, shp = op.group(1), shp.group(1)
        if op == "copy":        # whole-tensor copies, either type
            if _elems(shp) >= n:
                copies[shp] = copies.get(shp, 0) + 1
        elif _elems(shp) > 1:
            coll[f"{op} {shp}"] = coll.get(f"{op} {shp}", 0) + 1
    return {"chips": chips, "shape": list(shape),
            "how": how if how == FLAT else "shaped" if S == 1
            else f"axis {k}" + (", in place" if in_place else ""),
            "bytes_accessed_gb": cost.get("bytes accessed", 0) / 1e9,
            "optimal_ms": cost.get("optimal_seconds", 0) * 1e3,
            "temp_gb": compiled.memory_analysis().temp_size_in_bytes / 1e9,
            "collectives": coll, "whole_copies": copies}


def _elems(shp):
    dims = shp[shp.index("[") + 1:-1]
    return int(np.prod([int(d) for d in dims.split(",") if d])) if dims else 1


def main():
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    cases = [(1, (18, 2048, 8192), FLAT), (1, (18, 2048, 8192), 0),
             (4, (8, 4096, 7168), FLAT), (4, (8, 4096, 7168), 0),
             (4, (8, 4096, 7168), (0, True)),
             (4, (46272, 4096), FLAT), (4, (46272, 4096), 0),
             (4, (46272, 4096), 1)]
    for chips, shape, how in cases:
        print(json.dumps(compile_case(topo, chips, shape, how)), flush=True)


if __name__ == "__main__":
    sys.exit(main())
