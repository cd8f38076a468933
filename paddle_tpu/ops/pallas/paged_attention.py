"""Pallas paged-attention decode kernel (TPU).

The serving-path attention core: single-token queries attend over a PAGED
KV cache — the TPU-native answer to the reference's inline-KV-cache masked
MHA (ref: paddle/fluid/operators/fused/fused_multi_transformer_op.cu.h:13
masked_multihead_attention; PAPERS.md ragged paged attention).

Layout:
  q          : [b, h, d]            (one decode token per sequence)
  k_pages    : [n_pages, p, h_kv, d]  (p = page_size tokens per page;
                                       h_kv <= h for GQA — the cache is
                                       stored at the checkpoint's kv
                                       head count, q head i attends kv
                                       head i // (h // h_kv))
  v_pages    : [n_pages, p, h_kv, d]
  page_table : [b, max_pages] int32 (physical page id per logical page;
                                     entries past the sequence are ignored)
  seq_lens   : [b] int32            (tokens filled per sequence)

Grid (b,): ONE grid step a slot. The pools stay in HBM
(`memory_space=pl.ANY`); inside the step a loop walks the slot's LIVE
logical pages only — [first, last), `last` = ceil(seq_len / p) and
`first` = the page of the oldest key a window still shows (0 without a
window), no trip at all for an inactive or empty slot — and the kernel
makes its own copies. `live_walk` lays the live pages of ALL slots out as
one list of page ids in scalar memory; the loop trip of live page i
starts the copy of page i + 2 of that list (`COPIES_AHEAD`; three VMEM
buffers a pool, taken in turn along the list), waits for page i and
multiplies it, so two copies are in flight at any time, over the step
boundaries too: the copy engine never waits for a slot to finish. A
table entry outside a slot's [first, last) is never dereferenced and a
page outside the live set never fetched. KV for a sequence is gathered
page by page under an online softmax in VMEM scratch (float32), never
materialized contiguously: the kernel's time follows the bytes of the
pages the slots hold (about 740 GB/s of them at 128 tokens x 8 heads x
128 on a v5e), not the table's width. A pool whose page Mosaic cannot
slice out of HBM (`_copyable`: a 64-wide head, a lone bf16 KV head)
walks the same live pages a grid step a page through the pipelined index
map, under the same softmax.

Operands reach the MXU in the POOL's type (`mxu_operands`): bf16 pools,
as on the chip, give one MXU pass a product (the package-wide matmul
precision "highest" reaches into kernels and would make six of a float32
copy); float32 pools keep float32 operands at full precision, which is
what every bit-identity test on the interpret path runs. A head's [p, d]
matrix of a page comes out of the buffer by ONE strided load
(`_head_matrices`), not by slicing the loaded page: a token's heads sit
in the sublanes of one tile, and parting them as values cost the chip
more than the page's copy.
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def vmem_limit(blocks, scratch=(), temps=()):
    """vmem_limit_bytes for a pallas_call whose footprint grows with the
    model's width: Mosaic's default scoped limit is 16 MiB on v5e, which
    the 7B geometry passes (a [128 x 32 x 128] page block alone is 1 MiB
    and its f32 copy 2). Counts every pipelined in/out block twice
    (double buffering), the scratch, and the body's large temporaries —
    each a (shape, dtype) pair — adds half again for what the compiler
    keeps beside them, and stays between the default and 100 MiB (a v5e
    core holds 128)."""
    def nbytes(shape, dtype):
        return math.prod(shape) * jnp.dtype(dtype).itemsize

    need = (2 * sum(nbytes(*b) for b in blocks)
            + sum(nbytes(*x) for x in scratch)
            + sum(nbytes(*t) for t in temps))
    return int(min(max(need * 3 // 2, 16 << 20), 100 << 20))


def window_first_page(first_key, p):
    """Logical page that holds the oldest key a windowed query may see
    (`first_key` = query position - window + 1, clamped at 0). ONE
    definition for the index maps and the kernel bodies."""
    return jnp.maximum(first_key, 0) // p


def _sink_finish(m, l, acc, sink):
    """Close an online softmax whose denominator also holds a learned
    per-row sink logit: out = acc / (l + exp(sink - m)), computed at
    m' = max(m, sink) so neither exponent can overflow. A fully masked
    row (m = NEG_INF, l = 0, acc = 0) comes out as exact zeros."""
    m_fin = jnp.maximum(m, sink)
    beta = jnp.exp(m - m_fin)
    return acc * beta, l * beta + jnp.exp(sink - m_fin)


def mxu_operands(pool_dtype):
    """(operand dtype, precision) of the decode kernel's products, ONE
    rule for per-head and flat keys: K and V reach the MXU with the bits
    they are stored in. A bf16 pool: bf16 operands in one pass (named,
    because the package-wide "highest" reaches into kernels and a float32
    copy of a bf16 page takes six passes and changes no value of it);
    what is rounded anew is q * scale and the softmax weights, as in
    flash attention and `paged_chunk_attention`. Any other pool: float32
    operands at full precision. Sums are float32 either way."""
    if jnp.dtype(pool_dtype) == jnp.bfloat16:
        return jnp.bfloat16, jax.lax.Precision.DEFAULT
    return jnp.float32, jax.lax.Precision.HIGHEST


def _head_matrices(buf_ref, buf, n_kv):
    """The page in buffer `buf` of a [buffers, p, n_kv, width] ref as n_kv
    [p, width] matrices, with NO shuffle of its rows. A token's heads sit
    in the sublanes of one tile, so a per-head slice of the page is a row
    out of every tile: sliced as a value, the chip spends thousands of
    rotates and selects a page on it (PERF.md 6, PR 37: 1.3 us of a 1.37
    us loop trip). Read instead through the buffer's 2-D view
    [p * n_kv, width], where head g is the rows g, g + n_kv, ...: ONE
    strided load. A bf16 buffer packs two heads into a 32-bit sublane
    (heads 2j and 2j + 1 are the low and high halves of row j of a
    token), so its view is uint32 [p * n_kv / 2, width] and a load brings
    a PAIR of heads, parted by a shift and a mask: a bf16 is the high
    half of its float32, so both come out float32 with exactly the bits
    they were stored with."""
    _, p, _, width = buf_ref.shape
    packed = buf_ref.dtype == jnp.bfloat16
    if width % 128 or (packed and n_kv % 2) or \
            not (packed or buf_ref.dtype == jnp.float32):
        # shapes Mosaic's strided load does not take (a width that is no
        # multiple of the lanes, a lone packed head): slices of the
        # loaded page, as the table walk took them
        page = buf_ref[buf]
        return [page[:, g, :] for g in range(n_kv)]
    if not packed:
        rows = buf_ref.reshape(buf_ref.shape[0], p * n_kv, width)
        return [rows[buf, pl.ds(g, p, stride=n_kv), :] for g in range(n_kv)]
    words = buf_ref.bitcast(jnp.uint32).reshape(
        buf_ref.shape[0], p * n_kv // 2, width)
    out = []
    for j in range(n_kv // 2):
        pair = words[buf, pl.ds(j, p, stride=n_kv // 2), :]
        out += [jax.lax.bitcast_convert_type(pair << 16, jnp.float32),
                jax.lax.bitcast_convert_type(
                    pair & jnp.uint32(0xffff0000), jnp.float32)]
    return out


COPIES_AHEAD = 2    # page copies in flight while a page is multiplied
#                     (one more buffer a pool than that): with one, the
#                     copy engine idles from a copy's end to the next
#                     trip's start, 610 against 738 GB/s of live pages at
#                     the parallel block's shapes; three gave no more
#                     (PERF.md 6, PR 37)


def _slot_softmax(q_ref, sink_ref, o_ref, m_scr, l_scr, acc_scr, pool_dtype,
                  n_kv, *, p, scale, rep, window, k_flat):
    """(clear, query, fold, emit): one slot's online softmax over its
    pages, the arithmetic both decode kernels share. `clear()` resets the
    running maximum, sum and weighted values; `query()` is the query
    operand; `fold(q, k_ref, v_ref, buf, page, seq_len)` folds logical
    page `page`, held in buffer `buf` of the two refs, into them;
    `emit()` closes the softmax (with the sink's logit in the
    denominator) and writes the slot's rows."""
    # NOTE: every integer the body compares or selects with is a typed
    # int32 (interpret mode re-discharges the kernel under an outer jit,
    # outside the enable_x64(False) window, where a weak python literal
    # becomes int64: decode_megakernel.py has the long form)
    i32 = jnp.int32
    mxu, precision = mxu_operands(pool_dtype)

    def clear():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def query():
        # the scale applied in float32, the operand cast once
        q = q_ref[0].astype(jnp.float32) * jnp.float32(scale)
        if k_flat:
            # flat keys: spread the rows over the h_kv * d lanes of a
            # flat page, head i's d values in its kv head's stretch and
            # zeros elsewhere, so that ONE product gives every head's
            # logits with no per-head slice at a lane offset that is no
            # multiple of 128 (built here: spread by XLA the query is
            # h_kv times the bytes, 196 KB a slot a layer at 8 kv heads
            # x 192)
            zeros = jnp.zeros((rep, q.shape[1]), jnp.float32)
            q = jnp.concatenate([
                jnp.concatenate([q[g * rep:(g + 1) * rep] if col == g
                                 else zeros for col in range(n_kv)], axis=1)
                for g in range(n_kv)], axis=0)             # [h, h_kv * d]
        return q.astype(mxu)

    def product(a, b, contract):
        return jax.lax.dot_general(
            a, b.astype(mxu), (contract, ((), ())),
            precision=precision, preferred_element_type=jnp.float32)

    def fold(q, k_ref, v_ref, buf, page, seq_len):
        if k_flat:
            logits = product(q, k_ref[buf], ((1,), (1,)))      # [h, p]
        else:
            # per-head contraction over d as unrolled 2-D dots (Mosaic's
            # dot lowering rejects BATCHED dimension numbers). GQA-
            # native: the rep query heads of kv head g share ONE
            # [rep, d] x [d, p] dot against that head's keys
            logits = jnp.concatenate([
                product(q[g * rep:(g + 1) * rep], k_g, ((1,), (1,)))
                for g, k_g in enumerate(_head_matrices(k_ref, buf, n_kv))],
                axis=0)                                        # [h, p]
        # mask positions past seq_len and behind the window (keys j with
        # qpos - window < j <= qpos), fold into the running max and sum
        pos = jax.lax.broadcasted_iota(i32, logits.shape, 1) + page * i32(p)
        ok = pos < seq_len
        if window is not None:
            ok = jnp.logical_and(ok, pos >= seq_len - i32(window))
        logits = jnp.where(ok, logits, jnp.float32(NEG_INF))
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        w = jnp.exp(logits - m_new)                            # [h, p]
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = jnp.broadcast_to(
            alpha * l_prev + jnp.sum(w, axis=-1, keepdims=True), l_scr.shape)
        # [h, dv] accumulation: sum_p w[h, p] * v[p, kv head of h, dv]
        w = w.astype(mxu)
        acc_scr[...] = alpha * acc_scr[...] + jnp.concatenate([
            product(w[g * rep:(g + 1) * rep], v_g, ((1,), (0,)))
            for g, v_g in enumerate(_head_matrices(v_ref, buf, n_kv))],
            axis=0)                                            # [h, dv]
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    def emit():
        # a slot with no live page leaves l = 0, acc = 0: exact zeros
        acc, l_fin = acc_scr[...], l_scr[:, :1]
        if sink_ref is not None:
            acc, l_fin = _sink_finish(m_scr[:, :1], l_fin, acc,
                                      sink_ref[:, :1])
        l_fin = jnp.maximum(l_fin, jnp.float32(1e-30))
        o_ref[0] = (acc / l_fin).astype(o_ref.dtype)

    return clear, query, fold, emit


def _decode_kernel(pages_ref, walk_ref, q_ref, k_hbm, v_hbm, *rest,
                   has_sink=False, **how):
    """One grid step a slot, a loop over its live pages, the kernel's
    own copies from the pools in HBM (the module's docstring)."""
    sink_ref = rest[0] if has_sink else None
    o_ref, m_scr, l_scr, acc_scr, kbuf, vbuf, sem = rest[-7:]
    i32 = jnp.int32
    slot = pl.program_id(0)
    n_buf = kbuf.shape[0]
    clear, query, fold, emit = _slot_softmax(
        q_ref, sink_ref, o_ref, m_scr, l_scr, acc_scr, kbuf.dtype,
        vbuf.shape[2], **how)
    # the slot's column of `live_walk`: its live logical pages are
    # [first, last), walked in ascending order, and its first page is
    # number `start` of the `total` live pages of all slots
    seq_len, first, last, start, total = (
        walk_ref[i, slot] for i in range(5))

    def page_copies(index):
        # live page number `index` of the whole walk, pool (HBM) -> the
        # buffer its number gives it: the buffers of a pool are taken in
        # turn along the walk, whatever slot a page belongs to. The SAME
        # descriptors start a copy and wait on it
        pg = pages_ref[index]
        buf = jax.lax.rem(index, i32(n_buf))
        return (pltpu.make_async_copy(k_hbm.at[pg], kbuf.at[buf],
                                      sem.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[pg], vbuf.at[buf],
                                      sem.at[1, buf]))

    def start_page(index):
        @pl.when(index < total)
        def _():
            for cp in page_copies(index):
                cp.start()

    # the walk's first pages have no trip before them to start their
    # copies: the step of the first slot that has a page does
    @pl.when(jnp.logical_and(start == 0, first < last))
    def _():
        for ahead in range(n_buf - 1):
            start_page(i32(ahead))

    clear()
    q = query()

    def _page(page, carry):
        index = start + page - first
        # the copies of the NEXT live pages of the walk fly while this
        # one is multiplied, this slot's or a later slot's alike: copies
        # are in flight over every step boundary
        start_page(index + i32(n_buf - 1))
        for cp in page_copies(index):
            cp.wait()
        fold(q, kbuf, vbuf, jax.lax.rem(index, i32(n_buf)), page, seq_len)
        return carry

    jax.lax.fori_loop(first, last, _page, i32(0))
    emit()


def _decode_kernel_blocks(table_ref, walk_ref, q_ref, k_ref, v_ref, *rest,
                          n_grid, has_sink=False, **how):
    """The same walk for pools whose pages the kernel cannot copy itself
    (`_copyable`): a grid step a (slot, page of its walk), the page
    brought by Pallas' own pipeline through the index map. The steps
    past a slot's last live page compute nothing and, their index map
    staying on that page, fetch nothing new."""
    sink_ref = rest[0] if has_sink else None
    o_ref, m_scr, l_scr, acc_scr = rest[-4:]
    slot, pi = pl.program_id(0), pl.program_id(1)
    clear, query, fold, emit = _slot_softmax(
        q_ref, sink_ref, o_ref, m_scr, l_scr, acc_scr, k_ref.dtype,
        v_ref.shape[2], **how)
    seq_len, first, last = (walk_ref[i, slot] for i in range(3))

    @pl.when(pi == 0)
    def _():
        clear()

    @pl.when(first + pi < last)
    def _():
        fold(query(), k_ref, v_ref, 0, first + pi, seq_len)

    @pl.when(pi == n_grid - 1)
    def _():
        emit()


def live_walk(page_table, seq_lens, active, window, p):
    """What the decode kernel reads of the walk, two int32 arrays for its
    scalar memory.

    `walk` [5, b], a column a slot: (seq_len, first, last, start, total).
    The slot's live logical pages are [first, last): `last` =
    ceil(seq_len / p), `first` the page of the oldest key a window still
    shows (0 without one); none for a retired slot of a continuous-
    batching step (active == 0: seq_len 0) or an empty one. `start` is
    the number of the slot's first page in the walk of ALL slots' live
    pages, in slot order, `total` their count.

    `pages` [b * max_pages]: the page id of live page number i of that
    walk, which is all the kernel reads of the table: it copies page
    i + 2 while it multiplies page i without asking which slot either
    belongs to. Entries from `total` on are never read by the kernel,
    and no table entry outside a slot's [first, last) reaches an entry
    before it."""
    b, max_pages = page_table.shape
    seq_len = seq_lens.astype(jnp.int32)
    if active is not None:
        seq_len = jnp.where(active.astype(jnp.int32) > 0, seq_len, 0)
    last = jnp.minimum(-(-seq_len // p), max_pages)
    first = jnp.zeros_like(last) if window is None else \
        window_first_page(seq_len - window, p)
    n_live = jnp.maximum(last - first, 0)
    end = jnp.cumsum(n_live, dtype=jnp.int32)
    start = end - n_live
    number = jnp.arange(b * max_pages, dtype=jnp.int32)
    # the slot of live page i: how many slots end at or before i
    slot = jnp.minimum(jnp.sum(number[:, None] >= end[None, :], axis=1,
                               dtype=jnp.int32), b - 1)
    column = jnp.clip(first[slot] + number - start[slot], 0, max_pages - 1)
    pages = page_table.astype(jnp.int32)[slot, column]
    walk = jnp.stack([seq_len, first, last, start,
                      jnp.broadcast_to(end[-1], (b,))])
    return pages, walk.astype(jnp.int32)


def wv_diag(w, v, d, rep=1):
    """sum_p w[r,p] * v[p,h_kv,d] -> [r*h_kv... ,d] without the
    cross-head product. `rep` is the number of w ROWS per kv head: rows
    [g*rep, (g+1)*rep) read kv head g — plain GQA decode passes the
    query-head replication factor; the ragged chunk kernel passes
    rep*tq (its rows are (head, query-token) pairs, head-major). One
    [rep, p] x [p, d] dot per kv head. Unrolled 2-D dots (Mosaic
    rejects batched dot_general — see _decode_kernel), per-head slices
    (Mosaic also rejects the 3-D transpose on older toolchains)."""
    return jnp.concatenate([
        jax.lax.dot_general(
            w[g * rep:(g + 1) * rep], v[:, g, :], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)     # [rep, d]
        for g in range(v.shape[1])], axis=0)        # [rows, d]


def expand_kv_heads(x, h_q):
    """[..., h_kv, d] -> [..., h_q, d] by repeating each kv head over its
    query group (jnp.repeat semantics — THE head-grouping convention all
    GQA paths share: this kernel's i // rep mapping, the engine's dense
    prefill, models/generation.py). Identity when heads already match."""
    h_kv = x.shape[-2]
    if h_kv == h_q:
        return x
    assert h_q % h_kv == 0, (x.shape, h_q)
    return jnp.repeat(x, h_q // h_kv, axis=-2)


def _sink_rows(sinks, h, tq=1):
    """[h] learned sink logits -> the [h*tq, 128] float32 block the
    kernels read (row = head-major (head, token), every lane alike)."""
    rows = jnp.repeat(sinks.astype(jnp.float32).reshape(h), tq)
    return jnp.broadcast_to(rows[:, None], (h * tq, 128))


def paged_attention(q, k_pages, v_pages, page_table, seq_lens, scale=None,
                    interpret=False, active=None, window=None, sinks=None,
                    k_flat=False):
    """q: [b, h, d]; k_pages: [n_pages, p, h_kv, d], v_pages: [n_pages,
    p, h_kv, dv] with h % h_kv == 0 (GQA: q head i attends kv head
    i // (h // h_kv) — the cache is kept at the CHECKPOINT's kv head
    count, ref GQA repeat_kv removed); the value width dv may differ from
    the key width d. page_table: [b, max_pages] int32; seq_lens: [b]
    int32.

    One grid step a slot; inside it a loop over the slot's LIVE pages
    [first, last) with the kernel's own copies from the pools in HBM,
    two in flight (the module's docstring): table entries outside that
    range are never dereferenced and may hold anything.

    active: optional [b] mask (bool/int) for continuous batching — slots
    whose request has retired stay in the batch shape, walk no page and
    fetch none, so a mostly-drained decode batch costs its live rows.
    None means all slots live. Inactive and empty rows emit exact zeros.

    window: None = every key up to the query (causal); W = a sliding
    window, the query at position seq_len - 1 sees keys j with
    qpos - W < j <= qpos: the walk starts at the page of key
    seq_len - W, and table entries behind the window are never read (the
    engine frees those pages).
    sinks: optional [h] learned per-head sink logits, added to the
    softmax's denominator only (out = sum_j e^{l_j} v_j / (sum_j e^{l_j}
    + e^{sink})).
    k_flat: k_pages is [n_pages, p, h_kv * d], a token's keys of all kv
    heads side by side. The engine keeps a key width that is no multiple
    of the 128 lanes this way (PERF.md, PR 26: with [.., h_kv, 192] XLA
    gives the step's pool parameter one layout and its pool result
    another and copies the whole pool between them every step). The
    query rows are spread over the same lanes here, zeros outside their
    kv head's stretch, and one product gives all heads' logits.

    Returns [b, h, dv]."""
    b, h, d = q.shape
    dv = v_pages.shape[-1]
    h_kv = v_pages.shape[2]
    if k_flat:
        n_pages, p, flat = k_pages.shape
        assert flat == h_kv * d, (q.shape, k_pages.shape, v_pages.shape)
        dd = d
    else:
        n_pages, p, _, dd = k_pages.shape
        assert k_pages.shape[2] == h_kv, (k_pages.shape, v_pages.shape)
    assert dd == d and h % h_kv == 0, (q.shape, k_pages.shape)
    assert v_pages.shape[:3] == (n_pages, p, h_kv), (k_pages.shape,
                                                     v_pages.shape)
    rep = h // h_kv
    max_pages = page_table.shape[1]
    how = dict(p=p, scale=scale if scale is not None else 1.0 / math.sqrt(d),
               rep=rep, window=window, k_flat=k_flat,
               has_sink=sinks is not None)
    pages, walk = live_walk(page_table, seq_lens, active, window, p)
    k_page, v_page = k_pages.shape[1:], v_pages.shape[1:]
    f32 = jnp.float32
    softmax_scratch = [((h, 128), f32), ((h, 128), f32), ((h, dv), f32)]
    # the body's [h, p] logits, weights and mask, a head's matrix of the
    # page in float32 and in the operand type, the spread query
    temps = [((h, p), f32)] * 4 + [((p, max(d, dv)), f32)] * 3 \
        + [((h, h_kv * d), f32)] * 2 * bool(k_flat)
    if _copyable(k_pages, interpret) and _copyable(v_pages, interpret):
        kernel = functools.partial(_decode_kernel, **how)
        grid = (b,)
        # the pools stay in HBM: the kernel copies the live pages itself
        pools = [pl.BlockSpec(memory_space=pl.ANY)] * 2
        scalars = [pages, walk]
        scratch = softmax_scratch + [
            ((COPIES_AHEAD + 1,) + k_page, k_pages.dtype),
            ((COPIES_AHEAD + 1,) + v_page, v_pages.dtype)]
        semaphores = [pltpu.SemaphoreType.DMA((2, COPIES_AHEAD + 1))]
        # the page buffers of a pool are scratch: counted once
        limit = vmem_limit(blocks=[((h, d), q.dtype), ((h, dv), q.dtype)],
                           scratch=scratch, temps=temps)
        # in order: a slot's trips start the next slots' copies
        semantics = ("arbitrary",)
    else:
        n_grid = max_pages if window is None else \
            min(max_pages, -(-int(window) // p) + 1)
        kernel = functools.partial(_decode_kernel_blocks, n_grid=n_grid,
                                   **how)
        grid = (b, n_grid)

        def page_of(bb, pi, tbl, wk):
            # the slot's live page first + pi; past its last one the
            # index stays put (no new fetch). The table is clipped: the
            # column of a slot without a page may name none
            column = jnp.minimum(wk[1, bb] + pi,
                                 jnp.maximum(wk[2, bb] - 1, wk[1, bb]))
            return (tbl[bb, jnp.minimum(column, max_pages - 1)], 0, 0, 0)

        pools = [pl.BlockSpec((1,) + k_page,
                              (lambda *a: page_of(*a)[:3]) if k_flat
                              else page_of),
                 pl.BlockSpec((1,) + v_page, page_of)]
        scalars = [jnp.clip(page_table.astype(jnp.int32), 0, n_pages - 1),
                   walk]
        scratch, semaphores = softmax_scratch, []
        limit = vmem_limit(
            blocks=[((h, d), q.dtype), ((h, dv), q.dtype),
                    (k_page, k_pages.dtype), (v_page, v_pages.dtype)],
            scratch=scratch,
            temps=temps + [(k_page, f32), (v_page, f32)])
        semantics = ("parallel", "arbitrary")
    at_slot = lambda bb, *_: (bb, 0, 0)                 # noqa: E731
    in_specs = [pl.BlockSpec((1, h, d), at_slot)] + pools
    args = scalars + [q, k_pages, v_pages]
    if sinks is not None:
        in_specs.append(pl.BlockSpec((h, 128), lambda *a: (0, 0)))
        args.append(_sink_rows(sinks, h))
    with jax.enable_x64(False):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=grid,
                in_specs=in_specs,
                out_specs=pl.BlockSpec((1, h, dv), at_slot),
                scratch_shapes=[pltpu.VMEM(*x) for x in scratch]
                + semaphores),
            out_shape=jax.ShapeDtypeStruct((b, h, dv), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=semantics, vmem_limit_bytes=limit),
            interpret=interpret,
            name="paged_attention_decode",
        )(*args)


def _copyable(pool, interpret):
    """Can a kernel copy ONE page of this pool out of HBM by itself?
    Mosaic takes a dynamic slice of a ref only in whole tiles: the minor
    dimension a multiple of the 128 lanes, and of a 16-bit pool an even
    number of rows in the dimension before it (two share a sublane). A
    64-wide head or a lone bf16 KV head (multi-query attention, or one
    head a shard under tp) is not: such a pool's pages come through the
    pipelined index map (`_decode_kernel_blocks`). Under interpret every
    pool is."""
    return interpret or (pool.shape[-1] % 128 == 0 and (
        pool.dtype.itemsize >= 4 or pool.shape[-2] % 2 == 0))


def ragged_causal_mask(shape, tq, q_start, page_start, ctx_len,
                       window=None):
    """The ragged multi-token-q causal mask over a [rows, p] logits
    block whose rows are (head, token)-flattened with token MINOR (row r
    is chunk offset r % tq): key column c (global position page_start +
    c) is visible to row r iff it is causally at-or-before the row's own
    global position q_start + r % tq AND inside the context. ONE
    definition shared by _ragged_kernel and the decode megakernel's
    tq>1 verify phase — the spec-verify byte-identity contract rests on
    the two kernels computing this mask identically. window=W adds the
    sliding window's lower bound: kpos > qpos - W."""
    qpos = q_start + jax.lax.rem(
        jax.lax.broadcasted_iota(jnp.int32, shape, 0), jnp.int32(tq))
    kpos = jax.lax.broadcasted_iota(jnp.int32, shape, 1) + page_start
    ok = jnp.logical_and(kpos <= qpos, kpos < ctx_len)
    if window is not None:
        ok = jnp.logical_and(ok, kpos > qpos - window)
    return ok


def _ragged_kernel(page_table_ref, ctx_lens_ref, q_starts_ref, active_ref,
                   q_ref, k_ref, v_ref, *rest, p, d, tq, n_pages_max, scale,
                   rep=1, window=None, has_sink=False):
    """Chunked (multi-token-q) variant of _decode_kernel: slot b carries
    tq query tokens at GLOBAL positions q_starts[b] + [0, tq); its keys
    are the slot's own pages, causally masked per query token. Query
    rows arrive (head, token)-flattened HEAD-MAJOR — row g*rep*tq + j*tq
    + qi is q head g*rep+j at chunk offset qi — so each kv head's rows
    are one contiguous [rep*tq, d] slice (same Mosaic-friendly unrolled
    2-D dots as decode). window / has_sink: as in _decode_kernel, the
    first query of the chunk deciding the first page walked."""
    sink_ref = rest[0] if has_sink else None
    o_ref, m_scr, l_scr, acc_scr = rest[-4:]
    b = pl.program_id(0)
    pi = pl.program_id(1)

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    ctx_len = ctx_lens_ref[b]
    q_start = q_starts_ref[b]
    if window is None:
        page_start = pi * p
    else:
        page_start = (window_first_page(q_start - window + 1, p) + pi) * p
    # queries attend kpos <= q_start + qi < ctx_len: pages at/after the
    # context end contribute nothing — skip compute (an inactive slot's
    # index map additionally pins its page DMA to block 0)
    run = jnp.logical_and(active_ref[b] > 0, page_start < ctx_len)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * jnp.float32(scale)  # [h*tq, d]
        k = k_ref[0].astype(jnp.float32)                       # [p, h_kv, d]
        v = v_ref[0].astype(jnp.float32)
        h_kv = k.shape[1]
        rows = rep * tq                       # q rows per kv head
        logits = jnp.concatenate([
            jax.lax.dot_general(
                q[g * rows:(g + 1) * rows], k[:, g, :],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)     # [rep*tq, p]
            for g in range(h_kv)], axis=0)              # [h*tq, p]
        # causal + length mask at GLOBAL positions (shared helper — the
        # megakernel's verify phase applies the identical mask)
        ok = ragged_causal_mask(logits.shape, tq, q_start, page_start,
                                ctx_len, window=window)
        logits = jnp.where(ok, logits, jnp.float32(NEG_INF))

        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        w = jnp.exp(logits - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = jnp.broadcast_to(
            alpha * l_prev + jnp.sum(w, axis=-1, keepdims=True), l_scr.shape)
        acc_scr[...] = alpha * acc_scr[...] + wv_diag(w, v, d, rep=rows)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(pi == n_pages_max - 1)
    def _emit():
        # fully-masked rows (padded chunk tail, inactive slots) have
        # l == 0 and acc == 0: the clamp emits exact zeros, never NaN
        acc, l_fin = acc_scr[...], l_scr[:, :1]
        if has_sink:
            acc, l_fin = _sink_finish(m_scr[:, :1], l_fin, acc,
                                      sink_ref[:, :1])
        l_fin = jnp.maximum(l_fin, jnp.float32(1e-30))
        o_ref[0] = (acc / l_fin).astype(o_ref.dtype)


def ragged_paged_attention(q, k_pages, v_pages, page_table, ctx_lens,
                           q_starts, active=None, scale=None,
                           interpret=False, window=None, sinks=None):
    """Ragged-chunk paged attention: ONE kernel invocation covers slots
    sitting at DIFFERENT positions — each slot b contributes tq query
    tokens at global positions q_starts[b] + [0, tq), attending its own
    pages causally up to ctx_lens[b]. This is what lets chunked prefill
    (slots mid-prompt at arbitrary offsets) ride inside the same fused
    serving step as decode instead of a separate dispatch (PAPERS.md
    ragged paged attention; decode is the tq == 1 special case of this
    masking, kept on its own tuned kernel).

    q          : [b, tq, h, d]   (tq chunk tokens per slot)
    k/v_pages  : [n_pages, p, h_kv, d]   (GQA: h % h_kv == 0)
    page_table : [b, max_pages] int32
    ctx_lens   : [b] int32  — tokens in cache AFTER this chunk's write
                  (i.e. the chunk's end position); keys at/after it mask
    q_starts   : [b] int32  — global position of each slot's first
                  chunk token (ragged: per-slot, scalar-prefetched)
    active     : optional [b] mask; inactive slots skip compute AND page
                  DMA (index map pins their fetches to block 0) and emit
                  zeros.

    window / sinks: the sliding window's lower bound and the learned
    per-head sink of the softmax's denominator, as in paged_attention;
    v_pages may be narrower than k_pages ([..., dv]).

    Returns [b, tq, h, dv]. Rows past a slot's real chunk length are
    garbage (they attend whatever the causal window holds) — callers
    index the rows they wrote, exactly like the padded dense prefill."""
    b, tq, h, d = q.shape
    n_pages, p, h_kv, dd = k_pages.shape
    dv = v_pages.shape[-1]
    assert dd == d and h % h_kv == 0, (q.shape, k_pages.shape)
    rep = h // h_kv
    max_pages = page_table.shape[1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    n_grid = max_pages if window is None else \
        min(max_pages, -(-(int(window) + tq - 1) // p) + 1)

    # rows head-major [(h, tq) -> h*tq, d]: each kv head's rep*tq query
    # rows form one contiguous slice (see _ragged_kernel)
    qr = jnp.swapaxes(q, 1, 2).reshape(b, h * tq, d)
    table = jnp.clip(page_table.astype(jnp.int32), 0, n_pages - 1)
    lens = ctx_lens.astype(jnp.int32)
    starts = q_starts.astype(jnp.int32)
    if active is None:
        act = jnp.ones((b,), jnp.int32)
    else:
        act = active.astype(jnp.int32)

    def page_of(bb, pi, tbl, ln, st, ac):
        if window is not None:
            pi = jnp.minimum(
                window_first_page(st[bb] - window + 1, p) + pi,
                max_pages - 1)
        return (tbl[bb, pi] * ac[bb], 0, 0, 0)

    kernel = functools.partial(_ragged_kernel, p=p, d=d, tq=tq,
                               n_pages_max=n_grid, scale=s, rep=rep,
                               window=window, has_sink=sinks is not None)
    in_specs = [
        pl.BlockSpec((1, h * tq, d),
                     lambda bb, pi, tbl, ln, st, ac: (bb, 0, 0)),
        pl.BlockSpec((1, p, h_kv, d), page_of),
        pl.BlockSpec((1, p, h_kv, dv), page_of),
    ]
    args = [table, lens, starts, act, qr, k_pages, v_pages]
    if sinks is not None:
        in_specs.append(pl.BlockSpec(
            (h * tq, 128), lambda bb, pi, tbl, ln, st, ac: (0, 0)))
        args.append(_sink_rows(sinks, h, tq))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, n_grid),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h * tq, dv),
                               lambda bb, pi, tbl, ln, st, ac: (bb, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h * tq, 128), jnp.float32),
            pltpu.VMEM((h * tq, 128), jnp.float32),
            pltpu.VMEM((h * tq, dv), jnp.float32),
        ],
    )
    f32 = jnp.float32
    limit = vmem_limit(
        blocks=[((h * tq, d), q.dtype), ((h * tq, dv), q.dtype),
                ((p, h_kv, d), k_pages.dtype),
                ((p, h_kv, dv), v_pages.dtype)],
        scratch=[((h * tq, 128), f32)] * 2 + [((h * tq, dv), f32)],
        # the body's f32 copies: q, k, v, and the [rows, p] logits,
        # weights and mask
        temps=[((h * tq, d), f32), ((p, h_kv, d), f32),
               ((p, h_kv, dv), f32)] + [((h * tq, p), f32)] * 3)
    with jax.enable_x64(False):
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, h * tq, dv), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=limit),
            interpret=interpret,
            name="paged_attention_ragged",
        )(*args)
    return jnp.swapaxes(out.reshape(b, h, tq, dv), 1, 2)


def spec_verify_attention(q, k_pages, v_pages, page_table, lens,
                          active=None, scale=None, interpret=False):
    """Speculative-decode VERIFY entry: score K draft tokens per slot in
    ONE ragged-paged-attention invocation (ISSUE 7 / ROADMAP item 3).

    Slot b holds `lens[b]` committed tokens; its K feed tokens (the
    pending token + K-1 drafts) sit at global positions lens[b] + [0, K)
    and their k/v were scattered into the slot's pages BEFORE this call
    (length-gated, so rejected drafts need no scrub — `lens` simply does
    not advance over them). Each query row attends causally up to its
    own position, which is exactly the mask the sequential decode kernel
    applies one token at a time: on the interpret path the two kernels
    share the same per-page online-softmax trajectory, so verify logits
    are BIT-IDENTICAL to K sequential decode steps — the property the
    engine's greedy byte-identity contract rests on.

    q: [b, K, h, d]; pages [n_pages, p, h_kv, d]; page_table [b, mp];
    lens [b] committed lengths (i32-pinned here, as are the ragged
    kernel's index maps — the PR 5/6 weak-literal traps). Returns
    [b, K, h, d]."""
    K = q.shape[1]
    lens = lens.astype(jnp.int32)
    # ctx covers every feed position; per-row causality is the binding
    # mask (kpos <= qpos), so unwritten positions past a row's own
    # write gate are never attended by rows the engine keeps
    ctx = lens + jnp.int32(K)
    return ragged_paged_attention(q, k_pages, v_pages, page_table, ctx,
                                  lens, active=active, scale=scale,
                                  interpret=interpret)


def _sink_softmax(logits, sinks):
    """softmax over the last axis of [h, ..., k] logits with the learned
    per-head sink in the denominator only."""
    if sinks is None:
        return jax.nn.softmax(logits, axis=-1)
    s = sinks.astype(jnp.float32).reshape((-1,) + (1,) * (logits.ndim - 1))
    m = jnp.maximum(jnp.max(logits, -1, keepdims=True), s)
    e = jnp.exp(logits - m)
    return e / (jnp.sum(e, -1, keepdims=True) + jnp.exp(s - m))


def ragged_paged_attention_reference(q, k_pages, v_pages, page_table,
                                     ctx_lens, q_starts, active=None,
                                     scale=None, window=None, sinks=None):
    """XLA reference for tests: per-slot gather + dense causal softmax
    at the slot's global offset (GQA kv heads repeated)."""
    b, tq, h, d = q.shape
    dv = v_pages.shape[-1]
    n_pages, p, h_kv, _ = k_pages.shape
    max_pages = page_table.shape[1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    outs = []
    for i in range(b):
        if active is not None and not int(active[i]):
            outs.append(jnp.zeros((tq, h, dv), q.dtype))
            continue
        ks = k_pages[page_table[i]].reshape(max_pages * p, h_kv, d)
        vs = v_pages[page_table[i]].reshape(max_pages * p, h_kv, dv)
        if h_kv != h:
            ks = jnp.repeat(ks, h // h_kv, axis=1)
            vs = jnp.repeat(vs, h // h_kv, axis=1)
        logits = jnp.einsum("qhd,khd->hqk", q[i].astype(jnp.float32),
                            ks.astype(jnp.float32)) * s
        kpos = jnp.arange(max_pages * p)[None, None, :]
        qpos = (int(q_starts[i]) + jnp.arange(tq))[None, :, None]
        ok = (kpos <= qpos) & (kpos < int(ctx_lens[i]))
        if window is not None:
            ok = ok & (kpos > qpos - window)
        logits = jnp.where(ok, logits, NEG_INF)
        w = _sink_softmax(logits, sinks)
        # fully-masked rows: renormalize the uniform softmax to zero out
        any_ok = ok.any(-1)
        w = jnp.where(any_ok[..., None], w, 0.0)
        outs.append(jnp.einsum("hqk,khd->qhd", w,
                               vs.astype(jnp.float32)).astype(q.dtype))
    return jnp.stack(outs)


def paged_attention_reference(q, k_pages, v_pages, page_table, seq_lens,
                              scale=None, window=None, sinks=None,
                              k_flat=False):
    """XLA reference for tests: gather pages then plain softmax attention
    (GQA: kv heads repeated up to the q head count)."""
    if k_flat:          # [n, p, h_kv * d] -> [n, p, h_kv, d]
        k_pages = k_pages.reshape(k_pages.shape[:2] + (v_pages.shape[2],
                                                       -1))
    b, h, d = q.shape
    dv = v_pages.shape[-1]
    n_pages, p, h_kv, _ = k_pages.shape
    max_pages = page_table.shape[1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    outs = []
    for i in range(b):
        ks = k_pages[page_table[i]].reshape(max_pages * p, h_kv, d)
        vs = v_pages[page_table[i]].reshape(max_pages * p, h_kv, dv)
        if h_kv != h:
            ks = jnp.repeat(ks, h // h_kv, axis=1)
            vs = jnp.repeat(vs, h // h_kv, axis=1)
        L = int(seq_lens[i])
        lo = 0 if window is None else max(L - int(window), 0)
        ks, vs = ks[lo:L], vs[lo:L]
        logits = jnp.einsum("hd,khd->hk", q[i].astype(jnp.float32),
                            ks.astype(jnp.float32)) * s
        w = _sink_softmax(logits, sinks)
        outs.append(jnp.einsum("hk,khd->hd", w, vs.astype(jnp.float32)))
    return jnp.stack(outs).astype(q.dtype)


def paged_attention_dense(q, k_cache, v_cache, seq_len, scale=None,
                          page_size=None, interpret=None):
    """Decode attention over a DENSE per-sequence cache in one launch:
    the [b, L, h, d] cache is VIEWED as identity-tabled pages (a free
    reshape) and run through the paged kernel — inline-KV masked MHA as
    a single kernel, the TPU analog of the reference's
    fused_multi_transformer masked-MHA core
    (ref: fused_multi_transformer_op.cu.h:13 — one launch per layer).

    q: [b, h, d]; caches: [b, L, h, d]; seq_len: scalar or [b] filled
    length (keys < seq_len attend). Returns [b, h, d]."""
    b, L, h, d = k_cache.shape
    if page_size is None:
        page_size = 128
        while L % page_size:
            page_size //= 2
    p = page_size
    kp = k_cache.reshape(b * (L // p), p, h, d)
    vp = v_cache.reshape(b * (L // p), p, h, d)
    table = jnp.arange(b * (L // p), dtype=jnp.int32).reshape(b, L // p)
    lens = jnp.broadcast_to(jnp.asarray(seq_len, jnp.int32), (b,))
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    return paged_attention(q, kp, vp, table, lens, scale=scale,
                           interpret=interpret)
