"""Pallas grouped matrix product (TPU): the routed-expert layer's two
matmuls over the experts THIS chip holds.

  lhs         : [m, k]      rows SORTED BY GROUP (expert); rows past
                            sum(group_sizes) belong to no group
  rhs         : [g, k, n]   one [k, n] matrix per group
  group_sizes : [g] int32   rows per group, in order; EMPTY groups allowed
  out         : [m, n]      out[r] = lhs[r] @ rhs[group of r]; rows that
                            belong to no group come out as ZEROS

The walk (the megablocks / MegaBlox idea, written for this repo's shapes):
the m rows are cut into tiles of `tm`; a tile that holds rows of several
groups is VISITED once per group, each visit multiplying the whole tile
by that group's matrix and storing only the rows that are the group's.
The list of visits (tile, group) is computed outside the kernel from
group_sizes alone and scalar-prefetched; its length is static
(tiles + groups - 1, the worst case) and the visits past the real count
are pinned to the last real one, so they fetch nothing and compute
nothing. An expert nobody routed to is never fetched: a decode step
streams exactly the weights of the experts its rows touch, once per row
tile they span.

Grid (n tiles, visits, k tiles), k innermost: the float32 accumulator
lives in VMEM scratch across k, and consecutive visits of one row tile
keep the output block resident (same block index), which is what lets a
visit store its own rows and leave the others' in place.

Operands reach the MXU in the dtype the caller hands over (bf16 on the
chip) at Precision.DEFAULT — named here, because the package-wide
"highest" default reaches into kernels (PERF.md, PR 25) — with float32
accumulation.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import vmem_limit

KERNEL_NAME = "moe_grouped_matmul"


def visit_plan(group_sizes, m, tm):
    """(offsets [g+1], visit_group [v], visit_tile [v], n_visits [1]) for
    rows sorted by group and cut into ceil(m / tm) tiles; v = tiles + g - 1
    is static. Visits at and past n_visits repeat the last real one."""
    sizes = group_sizes.astype(jnp.int32)
    g = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    v_end = jnp.cumsum(tiles)
    v_start = v_end - tiles
    n_visits = v_end[-1]
    v = jnp.minimum(jnp.arange(-(-m // tm) + g - 1, dtype=jnp.int32),
                    jnp.maximum(n_visits - 1, 0))
    vg = jnp.minimum(jnp.searchsorted(v_end, v, side="right"),
                     g - 1).astype(jnp.int32)
    vt = (first[vg] + v - v_start[vg]).astype(jnp.int32)
    # no visit at all (nobody routed here): tile 0, group 0, nothing runs
    vt = jnp.where(n_visits > 0, vt, 0)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, vg, vt, n_visits.reshape(1).astype(jnp.int32)


def _kernel(off_ref, vg_ref, vt_ref, nv_ref, lhs_ref, rhs_ref, o_ref,
            acc_scr, *, tm, nk):
    v = pl.program_id(1)
    kk = pl.program_id(2)
    live = v < nv_ref[0]

    @pl.when(kk == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(live)
    def _mul():
        acc_scr[...] += jax.lax.dot_general(
            lhs_ref[...], rhs_ref[0], (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(live, kk == nk - 1))
    def _store():
        g = vg_ref[v]
        row = vt_ref[v] * tm + jax.lax.broadcasted_iota(
            jnp.int32, acc_scr.shape, 0)
        mine = jnp.logical_and(row >= off_ref[g], row < off_ref[g + 1])
        o_ref[...] = jnp.where(mine, acc_scr[...].astype(o_ref.dtype),
                               o_ref[...])


def _tile(n, want):
    """The largest multiple of 128 that divides n and is <= want (n
    itself when it is no multiple of 128: one tile)."""
    if n % 128:
        return n
    t = min(want, n) // 128 * 128
    while n % t:
        t -= 128
    return t


def grouped_matmul(lhs, rhs, group_sizes, tm=128, tn=None, tk=4096,
                   interpret=False):
    """See the module docstring. Tiles (measured on the v5e at 16 experts
    of 4096 x 4096 and 2048 x 4096, 4 to 16 rows each; PERF.md, PR 26):
    the whole contraction in one tile where k <= 4096, and tn so that an
    expert's [tk, tn] block is 4 MiB of bf16 — 0.80 and 0.43 ms, 82 % and
    76 % of the weights' time at the HBM peak. (Two k tiles cost 2.2 ms
    before the skipped visits pinned their k index too: every skipped
    grid step re-fetched a 4 MiB block.)"""
    m, k = lhs.shape
    g, k2, n = rhs.shape
    assert k == k2 and group_sizes.shape == (g,), (lhs.shape, rhs.shape,
                                                   group_sizes.shape)
    m_pad = -(-m // tm) * tm
    if m_pad != m:
        lhs = jnp.pad(lhs, ((0, m_pad - m), (0, 0)))
    tk = _tile(k, tk)
    if tn is None:
        tn = max(128, (4 << 20) // (tk * jnp.dtype(rhs.dtype).itemsize))
    tn = _tile(n, tn)
    nk = k // tk
    offsets, vg, vt, nv = visit_plan(group_sizes, m_pad, tm)
    n_visits = vg.shape[0]

    def k_of(v, kk, nv):
        # a skipped visit repeats the last real one's LAST k tile too:
        # nothing it names changes, so nothing is fetched
        return jnp.where(v < nv[0], kk, nk - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n // tn, n_visits, nk),
        in_specs=[
            pl.BlockSpec((tm, tk), lambda j, v, kk, off, vg, vt, nv:
                         (vt[v], k_of(v, kk, nv))),
            pl.BlockSpec((1, tk, tn), lambda j, v, kk, off, vg, vt, nv:
                         (vg[v], k_of(v, kk, nv), j)),
        ],
        out_specs=pl.BlockSpec(
            (tm, tn), lambda j, v, kk, off, vg, vt, nv: (vt[v], j)),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
    )
    limit = vmem_limit(
        blocks=[((tm, tk), lhs.dtype), ((tk, tn), rhs.dtype),
                ((tm, tn), lhs.dtype)],
        scratch=[((tm, tn), jnp.float32)],
        temps=[((tm, tn), jnp.float32)] * 2)
    with jax.enable_x64(False):
        out = pl.pallas_call(
            functools.partial(_kernel, tm=tm, nk=nk),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((m_pad, n), lhs.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary"),
                vmem_limit_bytes=limit),
            interpret=interpret,
            name=KERNEL_NAME,
        )(offsets, vg, vt, nv, lhs, rhs)
    # tiles no visit reached were never written, and a visited tile's
    # rows past the last group hold whatever was there: zeros, by rule
    rows = jnp.arange(m_pad, dtype=jnp.int32)[:, None]
    return jnp.where(rows < offsets[-1], out, 0)[:m]


def grouped_matmul_reference(lhs, rhs, group_sizes):
    """The same product as one einsum over a one-hot of each row's group
    (tests): float32, precision "highest"."""
    m = lhs.shape[0]
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    row = jnp.arange(m, dtype=jnp.int32)
    group = jnp.searchsorted(ends, row, side="right")
    onehot = (group[:, None] == jnp.arange(rhs.shape[0])[None, :]) \
        & (row < ends[-1])[:, None]
    with jax.default_matmul_precision("highest"):
        return jnp.einsum("mk,gkn,mg->mn", lhs.astype(jnp.float32),
                          rhs.astype(jnp.float32),
                          onehot.astype(jnp.float32))
