"""Chunk attention through a page table: ONE sequence's prefill chunk
against its own cached keys and values, grouped queries kept together.

The continuous-batching engine prefills a prompt a chunk at a time: the
chunk's K and V are written into the sequence's pages, then its queries
attend to every cached key they may see, the chunk's own included. With
H query heads over G KV heads, the H / G query heads of a KV head are
rows of ONE product against that head's keys as they are cached: K and V
are never repeated to the query head count, and a block of logits lives
in VMEM only ([H / G x tq, page] float32 a KV head), never in HBM. A grid
step is (a block of tq queries, one page of the walk); the running
maximum, sum and weighted values of all H x tq rows stay in scratch
across the walk (the online softmax of `paged_attention._ragged_kernel`,
whose queries are a few tokens of MANY slots; this one is many tokens of
one slot, so the query block, not the slot, is the parallel grid axis).

The walk: a full layer from page 0, a window layer from the page of the
first query's oldest key (the pages behind it are freed and their table
entries dead: never fetched), both to the page of the chunk's last real
position; the index map clamps to the pages a query block can see, so a
grid step past them fetches nothing new and computes nothing.

Operands reach the MXU in the pools' dtype (bf16 on the chip: one pass,
named because the package-wide "highest" reaches into kernels), sums are
float32.
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import NEG_INF, _sink_finish, vmem_limit

ROWS = 8192     # most query rows (heads x tq) a grid step carries


def query_block(chunk, n_heads):
    """Queries a grid step: the largest power of two that divides the
    chunk and keeps heads x tq within `ROWS` (64 at 128 heads), at least
    8."""
    tq = 8
    while tq * 2 <= chunk and chunk % (tq * 2) == 0 \
            and n_heads * tq * 2 <= ROWS:
        tq *= 2
    return tq


def _kernel(tab_ref, sc_ref, q_ref, k_ref, v_ref, *rest, p, tq, chunk, n_kv,
            rep, n_walk, scale, window, has_sink):
    sink_ref = rest[0] if has_sink else None
    o_ref, m_scr, l_scr, acc_scr = rest[-4:]
    qi = pl.program_id(0)
    pi = pl.program_id(1)

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start, t_end = sc_ref[0], sc_ref[1]
    # (every integer literal pinned to int32: under interpret inside an
    # outer jit the body is lowered again outside the x64 window)
    i32 = jnp.int32
    q_lo = q_start + qi * i32(tq)               # this block's first query
    first = _first_page(q_start, window, p)
    page_start = (first + pi) * i32(p)
    # the page holds a key some query of the block may see: not past the
    # block's last query, not wholly behind its first query's window
    run = page_start <= q_lo + i32(tq - 1)
    if window is not None:
        run = jnp.logical_and(run,
                              page_start + i32(p) > q_lo - i32(window - 1))
    run = jnp.logical_and(run, page_start < jnp.minimum(
        q_start + i32(chunk), t_end))
    rows = rep * tq                             # a KV head's query rows

    @pl.when(run)
    def _compute():
        mxu = k_ref.dtype
        # row r of a KV head's block is query (r % tq) of its head r // tq
        qpos = q_lo + jax.lax.broadcasted_iota(i32, (rows, p), 0) % i32(tq)
        kpos = page_start + jax.lax.broadcasted_iota(i32, (rows, p), 1)
        ok = kpos <= qpos
        if window is not None:
            ok = jnp.logical_and(ok, kpos > qpos - i32(window))
        for g in range(n_kv):
            sl = slice(g * rows, (g + 1) * rows)
            logits = jax.lax.dot_general(
                q_ref[0, sl, :], k_ref[0, :, g, :].astype(mxu),
                (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.DEFAULT,
                preferred_element_type=jnp.float32) * jnp.float32(scale)
            logits = jnp.where(ok, logits, jnp.float32(NEG_INF))
            m_prev = m_scr[sl, :1]
            m_new = jnp.maximum(m_prev,
                                jnp.max(logits, axis=-1, keepdims=True))
            w = jnp.where(ok, jnp.exp(logits - m_new), jnp.float32(0))
            alpha = jnp.exp(m_prev - m_new)
            l_scr[sl, :] = jnp.broadcast_to(
                alpha * l_scr[sl, :1] + jnp.sum(w, axis=-1, keepdims=True),
                (rows, l_scr.shape[1]))
            acc_scr[sl, :] = alpha * acc_scr[sl, :] + jax.lax.dot_general(
                w.astype(mxu), v_ref[0, :, g, :].astype(mxu),
                (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.DEFAULT,
                preferred_element_type=jnp.float32)
            m_scr[sl, :] = jnp.broadcast_to(m_new, (rows, m_scr.shape[1]))

    @pl.when(pi == n_walk - 1)
    def _emit():
        acc, l_fin = acc_scr[...], l_scr[:, :1]
        if has_sink:
            acc, l_fin = _sink_finish(m_scr[:, :1], l_fin, acc,
                                      sink_ref[:, :1])
        l_fin = jnp.maximum(l_fin, jnp.float32(1e-30))
        o_ref[0] = (acc / l_fin).astype(o_ref.dtype)


def _first_page(q_start, window, p):
    """Logical page of the walk's first key: 0, or the page of the first
    query's oldest visible key."""
    if window is None:
        return jnp.int32(0)
    return jnp.maximum(q_start - jnp.int32(window - 1),
                       jnp.int32(0)) // jnp.int32(p)


def paged_chunk_attention(q, k_pages, v_pages, table, q_start, t_end,
                          window=None, sinks=None, scale=None,
                          interpret=False):
    """q [chunk, H, d]: the queries of ONE sequence at positions q_start +
    [0, chunk) (those at or past t_end are padding: their rows are
    garbage by contract); k_pages [n_pages, p, G, d], v_pages [n_pages,
    p, G, dv] with the chunk's own K and V already written; table
    [pages_per_seq] the sequence's pages of this layer's group. Query i
    sees keys j <= pos[i], and j > pos[i] - window in a window layer.
    sinks [H]: a learned logit a head in the softmax's denominator only.
    Returns [chunk, H, dv] in q's dtype."""
    chunk, H, d = q.shape
    n_pages, p, n_kv, dd = k_pages.shape
    dv = v_pages.shape[-1]
    assert dd == d and H % n_kv == 0, (q.shape, k_pages.shape)
    rep = H // n_kv
    mp = table.shape[0]
    tq = query_block(chunk, H)
    assert chunk % tq == 0, (chunk, tq)
    n_q = chunk // tq
    n_walk = mp if window is None else \
        min(mp, -(-(int(window) + chunk - 1) // p) + 1)
    s = scale if scale is not None else 1.0 / math.sqrt(d)

    # rows of a query block: (KV head, its query heads, the block's tokens)
    qr = q.reshape(n_q, tq, n_kv, rep, d).transpose(0, 2, 3, 1, 4).reshape(
        n_q, H * tq, d).astype(k_pages.dtype)
    tab = jnp.clip(table.astype(jnp.int32), 0, n_pages - 1)
    sc = jnp.stack([jnp.asarray(q_start, jnp.int32),
                    jnp.asarray(t_end, jnp.int32)])

    def page_of(qi, pi, tbl, sc_):
        # past the last page this block can see the index stays put: no
        # new fetch for a step that computes nothing
        i32 = jnp.int32
        last = (jnp.minimum(jnp.minimum(sc_[0] + i32(chunk), sc_[1]),
                            sc_[0] + (qi + i32(1)) * i32(tq))
                - i32(1)) // i32(p)
        page = jnp.minimum(_first_page(sc_[0], window, p) + pi,
                           jnp.maximum(last, i32(0)))
        return (tbl[jnp.minimum(page, i32(mp - 1))], 0, 0, 0)

    kernel = functools.partial(
        _kernel, p=p, tq=tq, chunk=chunk, n_kv=n_kv, rep=rep, n_walk=n_walk,
        scale=s, window=window, has_sink=sinks is not None)
    in_specs = [
        pl.BlockSpec((1, H * tq, d), lambda qi, pi, tbl, sc_: (qi, 0, 0)),
        pl.BlockSpec((1, p, n_kv, d), page_of),
        pl.BlockSpec((1, p, n_kv, dv), page_of),
    ]
    args = [tab, sc, qr, k_pages, v_pages]
    if sinks is not None:
        in_specs.append(pl.BlockSpec(
            (H * tq, 128), lambda qi, pi, tbl, sc_: (0, 0)))
        # row order (head, token), heads in order: a head's sink tq times
        args.append(jnp.broadcast_to(
            jnp.repeat(sinks.astype(jnp.float32), tq)[:, None],
            (H * tq, 128)))
    f32 = jnp.float32
    rows = rep * tq
    limit = vmem_limit(
        blocks=[((H * tq, d), k_pages.dtype), ((H * tq, dv), q.dtype),
                ((p, n_kv, d), k_pages.dtype),
                ((p, n_kv, dv), v_pages.dtype)],
        scratch=[((H * tq, 128), f32)] * 2 + [((H * tq, dv), f32)],
        # a KV head's logits, weights and masks, its product's result
        temps=[((rows, p), f32)] * 4 + [((rows, dv), f32)] * 2)
    with jax.enable_x64(False):
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(n_q, n_walk),
                in_specs=in_specs,
                out_specs=pl.BlockSpec((1, H * tq, dv),
                                       lambda qi, pi, tbl, sc_: (qi, 0, 0)),
                scratch_shapes=[
                    pltpu.VMEM((H * tq, 128), f32),
                    pltpu.VMEM((H * tq, 128), f32),
                    pltpu.VMEM((H * tq, dv), f32),
                ]),
            out_shape=jax.ShapeDtypeStruct((n_q, H * tq, dv), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=limit),
            interpret=interpret,
            name="paged_chunk_attention",
        )(*args)
    return out.reshape(n_q, n_kv, rep, tq, dv).transpose(0, 3, 1, 2, 4) \
        .reshape(chunk, H, dv)
