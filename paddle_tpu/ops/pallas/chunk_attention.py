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

`paged_latent_chunk_attention` (below) is the latent layers' twin: ONE
cached row a token for all heads, the values a column slice of the keys,
a selection mask beside a full layer's causal one. It shares only the
walk's first page, the VMEM accounting and NEG_INF with the kernel
above: one row for all heads against a loop over KV heads, and a mask
operand, would put branches into programs that gain nothing from them.
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import NEG_INF, _sink_finish, mxu_operands, vmem_limit

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


# ---------------------------------------------------------------------------
# Latent rows: ONE cached row a token for all heads (inference/latent.py).
#
# The absorbed queries of a chunk score the rows [c_kv ; k_r] directly and
# the softmax-weighted sum runs over the row's leading kv_rank columns: the
# values are a column slice of the SAME block, so a page is fetched once for
# both products. A grid step is (a block of tq queries, `kp` pages of the
# block's walk); its logits [tq x heads, kp x page] float32 and the running
# maximum, sum and weighted latents [tq x heads, kv_rank] never leave VMEM.
# Query rows are ordered (token, head): the chunk's [chunk, H, row] queries
# and [chunk, H, kv_rank] outputs are the kernel's operands as they lie.
#
# The walk of a query block: a full layer from page 0, a window layer from
# the page of the block's first query's oldest visible key, both to the
# page of the block's last real query; a step past it fetches nothing new
# and computes nothing, and a block wholly past the prompt's end computes
# nothing (its rows are padding: garbage by contract, zeros here). A full
# layer masks by the selection, `chosen` [chunk, width] (each query's top-k
# of the index scores), as well as by causality.

LATENT_VMEM_LIMIT = 72 << 20    # the most VMEM a grid step may ask for
                                # (`vmem_limit`: 48 MiB of blocks, scratch
                                # and temporaries; a v5e core has 128)
LATENT_STEP_KEYS = 512          # keys a full layer's grid step covers
_MASKED = 2 * NEG_INF           # a masked logit: below the running maximum's
                                # floor, so its weight is exactly zero even in
                                # a row that has seen no key yet


def _latent_tileable(p, row, kv_rank, n_heads, dtype):
    """Can Mosaic tile the latent kernel's blocks at this shape? Rows and
    their value columns whole 128-lane registers, a page whole sublane
    tiles of the pool's type, the heads of a token whole float32 sublane
    tiles (the (token, head) rows reshape to [tq, H, keys] in place)."""
    pack = 8 * 4 // jnp.dtype(dtype).itemsize
    return (row % 128 == 0 and kv_rank % 128 == 0 and kv_rank <= row
            and p % pack == 0 and n_heads % 8 == 0)


def _latent_vmem(tq, kp, n_heads, row, kv_rank, p, dtype, masked):
    """(blocks, scratch, temps) of one grid step, as `vmem_limit` counts
    them."""
    f32 = jnp.float32
    rows, keys = tq * n_heads, kp * p
    blocks = [((rows, row), dtype), ((rows, kv_rank), dtype)] \
        + [((p, row), dtype)] * kp
    if masked:
        blocks.append(((tq, keys), _mask_dtype(tq)))
    scratch = [((rows, 128), f32)] * 2 + [((rows, kv_rank), f32)]
    # logits, their exponentials (and bf16 copy), the pages side by side,
    # the weighted sum's product
    temps = [((rows, keys), f32)] * 3 + [((keys, row), dtype),
                                         ((rows, kv_rank), f32)]
    return blocks, scratch, temps


def _mask_dtype(tq):
    """The selection's type in a [tq, keys] block: bf16 tiles 16 rows."""
    return jnp.bfloat16 if tq % 16 == 0 else jnp.int32


def latent_plan(chunk, n_heads, row, kv_rank, p, dtype, window=None,
                width=None, interpret=False):
    """How `paged_latent_chunk_attention` runs a chunk of this shape:
    {"tq": queries a block, "pages_per_step": kp, "vmem_limit_bytes"},
    or None where Mosaic cannot tile it (the caller then runs the XLA key
    blocks, `latent_attention.attend_key_blocks`). Read off the shape
    alone. A full layer's step covers `LATENT_STEP_KEYS` keys (a
    selection `width` wide must hold whole steps); a window layer's every
    page its query block's window touches, so a block takes ONE step. Of
    the query blocks that divide the chunk (at least 8, 16 with a
    selection) the largest whose step fits `LATENT_VMEM_LIMIT`. On the
    v5e (PERF.md 6, the latent chunk probe) 128 heads x 640 take 16
    queries x 4 pages and 64 heads x 1,152 behind 513 keys 16 queries x 6
    pages, at 160-176 and 171 TFLOP/s of products over the keys walked."""
    masked = width is not None
    if not interpret and not _latent_tileable(p, row, kv_rank, n_heads,
                                              dtype):
        return None
    kp = max(1, LATENT_STEP_KEYS // p)
    while masked and kp > 1 and width % (kp * p):
        kp //= 2
    if masked and (width % (kp * p)
                   or not (interpret or (kp * p) % 128 == 0)):
        return None
    plan = None
    t = 1 if interpret else (16 if masked else 8)
    while t <= chunk:
        if window is not None:
            kp = latent_walk_pages(p, t, window)
        limit = vmem_limit(*_latent_vmem(t, kp, n_heads, row, kv_rank, p,
                                         dtype, masked))
        if chunk % t == 0 and limit <= LATENT_VMEM_LIMIT:
            plan = {"tq": t, "pages_per_step": kp, "vmem_limit_bytes": limit}
        t *= 2
    return plan


def _latent_walk(q_lo, q_start, t_end, chunk, tq, p, window):
    """(first page, last key) of a query block's walk: the logical page of
    its first query's oldest visible key (0 in a full layer), and the
    last key any of its real queries sees."""
    i32 = jnp.int32
    first = _first_page(q_lo, window, p)
    last = jnp.minimum(q_lo + i32(tq - 1),
                       jnp.minimum(q_start + i32(chunk), t_end) - i32(1))
    return first, last


def latent_walk_pages(p, tq, window, mp=None):
    """Pages a query block's walk may cover: the table's mp in a full
    layer, at most (window + tq - 2) // page + 2 behind a window."""
    if window is None:
        return mp
    pages = (window + tq - 2) // p + 2
    return pages if mp is None else min(mp, pages)


def latent_walk_steps(p, tq, kp, window, mp):
    """Grid steps along a query block's walk, kp pages a step."""
    return -(-latent_walk_pages(p, tq, window, mp) // kp)


def latent_live_steps(q_start, t_end, chunk, p, tq, kp, window):
    """Grid steps that compute, for one chunk at q_start of a prompt that
    ends at t_end: the kernel's own walk, counted on the host."""
    n = 0
    for q_lo in range(q_start, q_start + chunk, tq):
        if q_lo >= t_end:
            break
        first = 0 if window is None else max(q_lo - window + 1, 0) // p
        last = min(q_lo + tq - 1, q_start + chunk - 1, t_end - 1)
        n += (last // p - first) // kp + 1
    return n


def _latent_kernel(tab_ref, sc_ref, q_ref, *rest, p, tq, kp, n_heads,
                   chunk, kv_rank, n_walk, scale, window, masked, precision):
    k_refs = rest[:kp]
    sel_ref = rest[kp] if masked else None
    o_ref, m_scr, l_scr, acc_scr = rest[-4:]
    qi = pl.program_id(0)
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    i32, f32 = jnp.int32, jnp.float32
    q_start, t_end = sc_ref[0], sc_ref[1]
    q_lo = q_start + qi * i32(tq)
    first, last = _latent_walk(q_lo, q_start, t_end, chunk, tq, p, window)
    key0 = (first + si * i32(kp)) * i32(p)      # this step's first key
    keys = kp * p
    run = jnp.logical_and(key0 <= last, q_lo < t_end)

    @pl.when(run)
    def _compute():
        mxu = k_refs[0].dtype
        k = k_refs[0][0] if kp == 1 else jnp.concatenate(
            [r[0] for r in k_refs], axis=0)             # [keys, row]
        logits = jax.lax.dot_general(
            q_ref[...], k, (((1,), (1,)), ((), ())), precision=precision,
            preferred_element_type=f32) * f32(scale)    # [tq x H, keys]
        qpos = q_lo + jax.lax.broadcasted_iota(i32, (tq, keys), 0)
        kpos = key0 + jax.lax.broadcasted_iota(i32, (tq, keys), 1)
        ok = kpos <= qpos
        if window is not None:
            ok = jnp.logical_and(ok, kpos > qpos - i32(window))
        if masked:
            ok = jnp.logical_and(ok, sel_ref[...].astype(f32) > 0)
        # one [tq, keys] mask for all heads of a token: added, not selected
        bias = jnp.where(ok, f32(0), f32(_MASKED))
        logits = (logits.reshape(tq, n_heads, keys) + bias[:, None, :]) \
            .reshape(tq * n_heads, keys)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        w = jnp.exp(logits - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = jnp.broadcast_to(
            alpha * l_scr[:, :1] + jnp.sum(w, axis=-1, keepdims=True),
            l_scr.shape)
        # the values are the rows' leading kv_rank columns
        acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
            w.astype(mxu), k[:, :kv_rank], (((1,), (0,)), ((), ())),
            precision=precision, preferred_element_type=f32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(si == n_walk - 1)
    def _emit():
        l_fin = jnp.maximum(l_scr[:, :1], f32(1e-30))
        o_ref[...] = (acc_scr[...] / l_fin).astype(o_ref.dtype)


def paged_latent_chunk_attention(q_abs, rows_pool, table, q_start, t_end,
                                 kv_rank, scale, window=None, chosen=None,
                                 plan=None, interpret=False):
    """q_abs [chunk, H, row]: the absorbed queries of ONE sequence at
    positions q_start + [0, chunk) (those at or past t_end are padding:
    garbage by contract); rows_pool [n_pages, p, row] with the chunk's
    rows already written; table [pages_per_seq] the sequence's pages of
    this layer's group. Query i sees keys j <= pos[i], j > pos[i] - window
    in a window layer, and in a full layer with a selection only those
    with chosen[i, j] (chosen [chunk, width >= pages_per_seq x p]).
    Returns the weighted sums of the latents [chunk, H, kv_rank] in the
    pool's dtype. `plan` is `latent_plan`'s (drawn here if None)."""
    chunk, n_heads, row = q_abs.shape
    n_pages, p, rr = rows_pool.shape
    assert rr == row, (q_abs.shape, rows_pool.shape)
    mp = table.shape[0]
    width = None if chosen is None else chosen.shape[1]
    if plan is None:
        plan = latent_plan(chunk, n_heads, row, kv_rank, p, rows_pool.dtype,
                           window, width, interpret)
    assert plan is not None, "no tiling of this shape: attend_key_blocks"
    tq, kp = plan["tq"], plan["pages_per_step"]
    assert chunk % tq == 0, (chunk, tq)
    n_q = chunk // tq
    n_walk = latent_walk_steps(p, tq, kp, window, mp)
    mxu, precision = mxu_operands(rows_pool.dtype)
    rows = tq * n_heads

    def step_of(qi, si, sc_):
        # past the block's last page the step stays put: no new fetch
        i32 = jnp.int32
        q_lo = sc_[0] + qi * i32(tq)
        first, last = _latent_walk(q_lo, sc_[0], sc_[1], chunk, tq, p,
                                   window)
        hi = jnp.maximum(last, first * i32(p)) // i32(p)   # a page
        return first, jnp.minimum(si, (hi - first) // i32(kp))

    def page_map(j):
        def page_of(qi, si, tbl, sc_):
            first, s = step_of(qi, si, sc_)
            page = first + s * jnp.int32(kp) + jnp.int32(j)
            return (tbl[jnp.minimum(page, jnp.int32(mp - 1))], 0, 0)
        return page_of

    def sel_of(qi, si, tbl, sc_):
        return (qi, step_of(qi, si, sc_)[1])

    in_specs = [pl.BlockSpec((rows, row), lambda qi, si, tbl, sc_: (qi, 0))]
    in_specs += [pl.BlockSpec((1, p, row), page_map(j)) for j in range(kp)]
    tab = jnp.clip(table.astype(jnp.int32), 0, n_pages - 1)
    sc = jnp.stack([jnp.asarray(q_start, jnp.int32),
                    jnp.asarray(t_end, jnp.int32)])
    args = [tab, sc, q_abs.reshape(chunk * n_heads, row).astype(mxu)] \
        + [rows_pool] * kp
    if chosen is not None:
        in_specs.append(pl.BlockSpec((tq, kp * p), sel_of))
        args.append(chosen.astype(_mask_dtype(tq)))
    f32 = jnp.float32
    kernel = functools.partial(
        _latent_kernel, p=p, tq=tq, kp=kp, n_heads=n_heads, chunk=chunk,
        kv_rank=kv_rank, n_walk=n_walk, scale=scale, window=window,
        masked=chosen is not None, precision=precision)
    with jax.enable_x64(False):
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(n_q, n_walk),
                in_specs=in_specs,
                out_specs=pl.BlockSpec((rows, kv_rank),
                                       lambda qi, si, tbl, sc_: (qi, 0)),
                scratch_shapes=[
                    pltpu.VMEM((rows, 128), f32),
                    pltpu.VMEM((rows, 128), f32),
                    pltpu.VMEM((rows, kv_rank), f32),
                ]),
            out_shape=jax.ShapeDtypeStruct((chunk * n_heads, kv_rank),
                                           rows_pool.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=plan["vmem_limit_bytes"]),
            interpret=interpret,
            name="paged_latent_chunk_attention",
        )(*args)
    return out.reshape(chunk, n_heads, kv_rank)
