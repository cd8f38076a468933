"""A latent attention layer inside the continuous-batching engine's step
programs: the row pool and the index-key pool written and read through a
page table (ops/latent_attention.py has the mathematics, serving.py the
pool shapes, docs/serving.md "Latent page groups" the design).

Both run the ABSORBED form: the query carried into the latent space
scores the cached rows directly, W_uv and the gate come after the sum.

  decode_layer   one token a slot. A FULL layer scores every visible
                 index key of every slot, selects exactly the top-k
                 positions, gathers THOSE rows by page table and attends
                 to them; a WINDOW layer gathers the pages its window
                 touches.
  prefill_layer  one chunk of one sequence over the sequence's LIVE
                 pages (online softmax): the Pallas kernel
                 `paged_latent_chunk_attention`, or where Mosaic cannot
                 tile the shape the XLA key blocks (`key_blocks`); no
                 tensor against all `pages_per_seq` pages exists. A full
                 layer first fills a [chunk, max_len] float32 buffer of
                 index scores block by block and marks each query's top-k
                 in it (a radix select for the k-th value, no sort).

Every operation sits under a model phase (`profiler.phase`; the name
stack reaches the profiler's event metadata; docs/observability.md
"Device phases"): `attn_proj` the projections before and after the sum,
`kv_write` the rows and index keys into the pools, `attend` the rest.
Inside `attend` four plain `jax.named_scope`s split the attention:
sparse_index_scores, sparse_select, sparse_attend, window_latent_attend.

`decode_selection`, `prefill_selection` and `block_pages` (the index scan
and the exact selection through a page table) know nothing latent: a
per-head layer with an indexer (inference/sparse_heads.py) calls them too.
"""
import jax
import jax.numpy as jnp

from ..ops import latent_attention as la
from ..ops import sparse_attention as sa
from ..ops.pallas.chunk_attention import (latent_plan,
                                          paged_latent_chunk_attention)
from ..ops.sparse_attention import SPARSE_COUNTS
from ..profiler import phase
from .serving import _rms

KEY_BLOCK_PAGES = 4     # pages a prefill key block gathers


def _write(pool, slots, values):
    """values [n, width] into the flat token slots of pool [pages, p,
    width]; a slot at or past the pool's end is dropped."""
    flat = pool.reshape(-1, pool.shape[-1])
    return flat.at[slots].set(values.astype(pool.dtype),
                              mode="drop").reshape(pool.shape)


def block_pages(tab, j, p, nb=KEY_BLOCK_PAGES, first=0):
    """(pool pages [nb], key positions [kb]) of key block j (nb pages,
    counted from logical page `first`) of a sequence whose table is tab
    [pages_per_seq]; a page past the table reads its last entry and is
    masked by position."""
    mp = tab.shape[0]
    page_ix = first + j * nb + jnp.arange(nb)
    kpos = (page_ix[:, None] * p + jnp.arange(p)[None, :]).reshape(nb * p)
    return tab[jnp.minimum(page_ix, mp - 1)], \
        jnp.where(jnp.repeat(page_ix < mp, p), kpos, mp * p)


def decode_selection(ix_pool, tab, q_i, w_i, n_vis, active, ix, p):
    """One decode query a slot (q_i [w, 1, Hi, di], w_i [w, 1, Hi]) over
    its slot's index keys: (idx [w, top_k] the selected POSITIONS, valid
    [w, top_k], `SPARSE_COUNTS` int32). n_vis [w]: positions visible (0
    for an inactive slot)."""
    w, mp = tab.shape
    with jax.named_scope("sparse_index_scores"):
        keys = ix_pool[tab].reshape(w, mp * p, ix.dim)
        scores = sa.index_scores(q_i, keys, w_i)[:, 0]
    with jax.named_scope("sparse_select"):
        visible = jnp.arange(mp * p)[None, :] < n_vis[:, None]
        idx, valid = sa.select_top(scores, visible, ix.top_k)
    # the scan reads every table page of every slot of the bucket, live
    # or not: what it scores is w x mp x p, not what is visible
    counts = (jnp.sum(n_vis, dtype=jnp.int32),
              jnp.sum(valid, dtype=jnp.int32),
              jnp.int32(w * mp * p),
              jnp.sum(active, dtype=jnp.int32))
    return idx, valid, counts


def selection_width(mp, p):
    """Columns of a chunk's selection for a table of mp pages of p: whole
    key blocks."""
    return -(-mp // KEY_BLOCK_PAGES) * KEY_BLOCK_PAGES * p


def prefill_plan(eng, li, chunk):
    """What runs the chunk attention of latent layer li: the Pallas
    kernel's plan (`chunk_attention.latent_plan`), or None where it cannot
    tile the shape and the XLA key blocks run. Read off the shapes."""
    a = eng.desc.layers[li].attn
    g = eng.groups[eng.desc.layer_group[li]]
    masked = a.indexer is not None
    return latent_plan(
        chunk, a.n_heads, g.row_pad, a.latent.kv_rank, eng.page_size,
        eng.kv_dtype, None if masked else a.window,
        selection_width(eng.pages_per_seq, eng.page_size) if masked
        else None, eng.interpret)


def prefill_plans(eng):
    """{page group index: `prefill_plan` of its layers} over the latent
    groups (a group's layers share their shape)."""
    return {g.index: prefill_plan(eng, g.layers[0], eng.prefill_chunk)
            for g in eng.groups if g.latent}


def prefill_facts(eng, plans):
    """{"full" | "window": what a chunk of the group's layers runs: the
    kernel or the fallback, its query block, the pages a grid step covers
    and its VMEM limit}, None without a latent layer (static;
    `health()["latent_prefill"]`). plans: `prefill_plans(eng)`."""
    return {"full" if eng.groups[i].window is None else "window": {
        "kernel": "attend_key_blocks" if plan is None
        else "paged_latent_chunk_attention", **(plan or {})}
        for i, plan in plans.items()} or None


def prefill_selection(ix_pool, tab, q_i, w_i, qpos, hi_blk, ix, p):
    """A chunk's queries (q_i [chunk, Hi, di], w_i [chunk, Hi], at qpos
    [chunk, 1]) over the sequence's LIVE index-key blocks 0..hi_blk-1:
    chosen [chunk, width] bool, each query's top-k among the positions
    it sees. The [chunk, width] float32 score buffer is filled block by
    block; a radix select marks the k-th value, no sort."""
    chunk, kb = q_i.shape[0], KEY_BLOCK_PAGES * p
    with jax.named_scope("sparse_index_scores"):
        def score_block(j, buf):
            pages, kpos = block_pages(tab, j, p)
            keys = ix_pool[pages].reshape(kb, ix.dim)
            sc = sa.index_scores(q_i, keys, w_i)
            sc = jnp.where(kpos[None, :] <= qpos, sc, -jnp.inf)
            return jax.lax.dynamic_update_slice(
                buf, sc, (jnp.zeros((), j.dtype), j * kb))

        # columns past the live blocks stay -inf: not visible
        width = selection_width(tab.shape[0], p)
        buf = jax.lax.fori_loop(
            0, hi_blk, score_block,
            jnp.full((chunk, width), -jnp.inf, jnp.float32))
    with jax.named_scope("sparse_select"):
        return sa.top_mask(buf, ix.top_k)


def key_blocks(rows_pool, tab, pos, t_end, p, window=None, chosen=None):
    """(block, lo, hi) for `la.attend_key_blocks`: a chunk at positions pos
    [chunk] over key blocks of the sequence's live pages, from the block
    the first query's window starts in (0 in a full layer) to that of the
    chunk's last real position, masked by causality and the window or the
    selection chosen [chunk, width]. The fallback of the Pallas kernel,
    and its reference in tests."""
    chunk, kb = pos.shape[0], KEY_BLOCK_PAGES * p
    qpos = pos[:, None]
    last = jnp.minimum(pos[0] + chunk, t_end) - 1       # a real position

    def block(j):
        pages, kpos = block_pages(tab, j, p)
        seen = kpos[None, :] <= qpos
        if window is not None:
            seen = seen & (kpos[None, :] > qpos - window)
        if chosen is not None:
            seen = seen & jax.lax.dynamic_slice(
                chosen, (jnp.zeros((), j.dtype), j * kb), (chunk, kb))
        return rows_pool[pages].reshape(kb, -1), seen

    lo = 0 if window is None else jnp.maximum(pos[0] - window + 1, 0) // kb
    return block, lo, last // kb + 1


def _front(eng, W, wset, h, pos_ids, li):
    """A latent layer up to its attention, for tokens h [b, t, hidden] at
    pos_ids [b, t]: (q_n [b, t, H, no-position] and q_r [b, t, H, rotary]
    float32; row [b, t, padded row] the tokens' cache rows, zero in the
    padding; gate [b, t, H] float32 or None; the indexer's (q^I, k^I, w)
    float32 or None). Products take bf16 operands on the chip, their sums
    and the norms between them are float32."""
    with phase("attn_proj"):
        a = eng.desc.layers[li].attn
        g = eng.groups[eng.desc.layer_group[li]]
        cos, sin = eng._rope_of(W, li)
        cos, sin = cos[pos_ids], sin[pos_ids]
        x = _rms(h, wset["ln1"], W["eps"])
        q_n, q_r, row, c_q = la.latent_qkv(x, wset, a, W["eps"], cos, sin)
        row = jnp.pad(row, [(0, 0)] * 2 + [(0, g.row_pad - g.row_width)])
        gate = la.head_gate(x, wset["w_gate"]) if a.gate else None
        ix = sa.index_qkw(x, c_q, wset, a.indexer, cos, sin) \
            if a.indexer is not None else None
        return q_n, q_r, row.astype(eng.kv_dtype), gate, ix


def _gated(o, gate, dtype):
    """The heads' outputs [..., H, value width] under the head-wise gate,
    in the dtype the output projection takes."""
    return (o if gate is None else o * gate[..., None]).astype(dtype)


def _absorbed_query(eng, wset, q_n, q_r, a, g):
    """The query carried into the latent space, zero over the row's
    padding, in the cache's dtype."""
    with phase("attn_proj"):
        q_abs = la.absorb_query(q_n, q_r, wset["w_uk"], a)
        pad = [(0, 0)] * (q_abs.ndim - 1) + [(0, g.row_pad - g.row_width)]
        return jnp.pad(q_abs, pad).astype(eng.kv_dtype)


def decode_layer(eng, W, wset, h, rows_pool, ix_pool, tab, lens, active, li):
    """h [w, 1, hidden] -> (heads' outputs [w, 1, H, value width], the two
    pools, `SPARSE_COUNTS` int32 of this layer's decode queries, zeros
    for a window layer). tab [w, pages_per_seq] is the layer's group's
    part of the page table."""
    a = eng.desc.layers[li].attn
    g = eng.groups[eng.desc.layer_group[li]]
    p, mp, w = eng.page_size, eng.pages_per_seq, lens.shape[0]
    r, scale = a.latent.kv_rank, la.softmax_scale(a)
    q_n, q_r, row, gate, ix = _front(eng, W, wset, h, lens[:, None], li)
    q_abs = _absorbed_query(eng, wset, q_n, q_r, a, g)
    with phase("kv_write"):
        slots = jnp.where(active,
                          tab[jnp.arange(w), lens // p] * p + lens % p,
                          g.n_pages * p)
        rows_pool = _write(rows_pool, slots, row[:, 0])
        if a.indexer is not None:
            ix_pool = _write(ix_pool, slots, ix[1][:, 0])
    with phase("attend"):
        flat = rows_pool.reshape(-1, g.row_pad)
        n_vis = jnp.where(active, lens + 1, 0)
        if a.indexer is not None:
            q_i, _, w_i = ix
            idx, valid, counts = decode_selection(
                ix_pool, tab, q_i, w_i, n_vis, active, a.indexer, p)
            with jax.named_scope("sparse_attend"):
                sel = jnp.take_along_axis(tab, idx // p, axis=1) * p \
                    + idx % p
                o_lat = la.attend_rows(q_abs[:, 0], flat[sel], valid, r,
                                       scale)
        else:
            with jax.named_scope("window_latent_attend"):
                # the pages the window of the query at `lens` touches
                n_ctx = min(mp, g.bound(1))
                first = jnp.maximum(lens - a.window + 1, 0) // p
                page_ix = first[:, None] + jnp.arange(n_ctx)[None, :]
                pages = jnp.take_along_axis(
                    tab, jnp.minimum(page_ix, mp - 1), axis=1)
                kpos = (page_ix[:, :, None] * p
                        + jnp.arange(p)[None, None, :]).reshape(
                            w, n_ctx * p)
                qpos = lens[:, None]
                valid = (kpos <= qpos) & (kpos > qpos - a.window) \
                    & active[:, None]
                o_lat = la.attend_rows(
                    q_abs[:, 0],
                    rows_pool[pages].reshape(w, n_ctx * p, -1),
                    valid, r, scale)
            counts = (jnp.int32(0),) * len(SPARSE_COUNTS)
    with phase("attn_proj"):
        o = la.expand_values(o_lat, wset["w_uv"], a)    # W_uv after the sum
        return (_gated(o[:, None], gate, eng.kv_dtype), rows_pool, ix_pool,
                counts)


def prefill_layer(eng, W, wset, h, rows_pool, ix_pool, tab, pos, t_end, li):
    """One chunk of one sequence: h [1, chunk, hidden] at positions pos
    [chunk] (those >= t_end are padding and write nothing) -> (heads'
    outputs [1, chunk, H, value width], the two pools). tab [pages_per_
    seq]."""
    a = eng.desc.layers[li].attn
    g = eng.groups[eng.desc.layer_group[li]]
    p, chunk = eng.page_size, pos.shape[0]
    q_n, q_r, row, gate, ix = _front(eng, W, wset, h, pos[None, :], li)
    q_abs = _absorbed_query(eng, wset, q_n, q_r, a, g)
    with phase("kv_write"):
        slots = jnp.where(pos < t_end, tab[pos // p] * p + pos % p,
                          g.n_pages * p)
        rows_pool = _write(rows_pool, slots, row[0])
        if a.indexer is not None:
            ix_pool = _write(ix_pool, slots, ix[1][0])
    with phase("attend"):
        plan = prefill_plan(eng, li, chunk)
        chosen = None
        if a.indexer is not None:
            q_i, _, w_i = ix
            last = jnp.minimum(pos[0] + chunk, t_end) - 1  # a real position
            chosen = prefill_selection(
                ix_pool, tab, q_i[0], w_i[0], pos[:, None],
                last // (KEY_BLOCK_PAGES * p) + 1, a.indexer, p)
        window = None if chosen is not None else a.window
        with jax.named_scope("sparse_attend" if chosen is not None
                             else "window_latent_attend"):
            if plan is not None:
                # the chunk's live pages straight out of the pool, logits
                # and the running sum in VMEM
                o_lat = paged_latent_chunk_attention(
                    q_abs[0], rows_pool, tab, pos[0], t_end,
                    a.latent.kv_rank, la.softmax_scale(a), window=window,
                    chosen=chosen, plan=plan, interpret=eng.interpret)
            else:
                o_lat = la.attend_key_blocks(
                    q_abs[0], *key_blocks(rows_pool, tab, pos, t_end, p,
                                          window, chosen),
                    a.latent.kv_rank, la.softmax_scale(a))
    with phase("attn_proj"):
        o = la.expand_values(o_lat[None], wset["w_uv"], a)
        return _gated(o, gate, eng.kv_dtype), rows_pool, ix_pool
