"""Per-head (grouped-query) attention inside the continuous-batching
engine's step programs where the dense gather does not do. A layer with
an indexer: the group's [K ; V] row pool and its index-key pool written
and read through a page table (ops/sparse_attention.py has the
mathematics, serving.PageGroup the pool shapes, docs/serving.md
"Per-head groups with index keys" the design).

  decode_layer   one token a slot: score every visible index key of every
                 slot, select exactly the top-k positions, GATHER those
                 tokens' rows by page table (one row brings a token's K
                 and V of every KV head) and attend to them.
  prefill_layer  one chunk of one sequence over key blocks of the
                 sequence's LIVE pages under the selection mask (online
                 softmax): no tensor against all `pages_per_seq` pages
                 exists.

A layer WITHOUT an indexer (separate K and V pools, window or full):

  attend_chunk   one chunk of one sequence over the pages the chunk can
                 SEE (a window layer: from the page of the first query's
                 oldest key; a full layer: the live pages) under a running
                 softmax: K and V are never repeated to the query head
                 count and no [heads, chunk, max_len] logits exist
                 (docs/serving.md "Chunk attention through the page
                 table"). Lane-aligned heads take the Pallas kernel
                 (ops/pallas/chunk_attention.py: a block's logits never
                 leave VMEM), any other shape `attend_chunk_blocks`, the
                 same walk as XLA operations over key blocks.

The index scan and the selection are inference/latent.py's, the model
phases (`attn_proj`, `kv_write`, `attend`), the scopes inside `attend`
(sparse_index_scores, sparse_select, sparse_attend) and the counters
(`SPARSE_COUNTS`) the latent layers' own, so one set of readers serves
both layer kinds.
"""
import jax
import jax.numpy as jnp

from ..ops import sparse_attention as sa
from ..ops.pallas.chunk_attention import paged_chunk_attention
from ..profiler import phase
from .latent import (KEY_BLOCK_PAGES, _write, block_pages, decode_selection,
                     prefill_selection)
from .serving import _rms


def _front(eng, W, wset, h, pos_ids, li):
    """The layer up to its attention for tokens h [b, t, hidden] at
    pos_ids [b, t]: (q [b, t, H, d] normed and rotated; the tokens' cache
    rows [b, t, padded row] in the cache's dtype; the indexer's (q^I, k^I, w)
    float32, its query a product of the normed hidden state)."""
    a = eng.desc.layers[li].attn
    g = eng.groups[eng.desc.layer_group[li]]
    q, k, v = eng._layer_qkv(W, wset, h, pos_ids, li=li)
    with phase("attn_proj"):
        row = jnp.pad(sa.kv_row(k, v),
                      [(0, 0)] * 2 + [(0, g.row_pad - g.row_width)])
        cos, sin = eng._rope_of(W, li, indexer=True)
        x = _rms(h, wset["ln1"], W["eps"])
        ix = sa.index_qkw(x, x, wset, a.indexer, cos[pos_ids],
                          sin[pos_ids])
        return q, row.astype(eng.kv_dtype), ix


def decode_layer(eng, W, wset, h, rows_pool, ix_pool, tab, lens, active, li):
    """h [w, 1, hidden] -> (heads' outputs [w, 1, H, value width], the two
    pools, `SPARSE_COUNTS` int32 of this layer's decode queries). tab [w,
    pages_per_seq] is the layer's group's part of the page table."""
    a = eng.desc.layers[li].attn
    g = eng.groups[eng.desc.layer_group[li]]
    p, w = eng.page_size, lens.shape[0]
    q, row, (q_i, k_i, w_i) = _front(eng, W, wset, h, lens[:, None], li)
    with phase("kv_write"):
        slots = jnp.where(active,
                          tab[jnp.arange(w), lens // p] * p + lens % p,
                          g.n_pages * p)
        rows_pool = _write(rows_pool, slots, row[:, 0])
        ix_pool = _write(ix_pool, slots, k_i[:, 0])
    with phase("attend"):
        idx, valid, counts = decode_selection(
            ix_pool, tab, q_i, w_i, jnp.where(active, lens + 1, 0), active,
            a.indexer, p)
        with jax.named_scope("sparse_attend"):
            sel = jnp.take_along_axis(tab, idx // p, axis=1) * p + idx % p
            o = sa.attend_selected(
                q[:, 0], rows_pool.reshape(-1, g.row_pad)[sel], valid, a)
        return o[:, None].astype(eng.kv_dtype), rows_pool, ix_pool, counts


def prefill_layer(eng, W, wset, h, rows_pool, ix_pool, tab, pos, t_end, li):
    """One chunk of one sequence: h [1, chunk, hidden] at positions pos
    [chunk] (those >= t_end are padding and write nothing) -> (heads'
    outputs [1, chunk, H, value width], the two pools). tab [pages_per_
    seq]."""
    a = eng.desc.layers[li].attn
    g = eng.groups[eng.desc.layer_group[li]]
    p, chunk = eng.page_size, pos.shape[0]
    q, row, (q_i, k_i, w_i) = _front(eng, W, wset, h, pos[None, :], li)
    with phase("kv_write"):
        slots = jnp.where(pos < t_end, tab[pos // p] * p + pos % p,
                          g.n_pages * p)
        rows_pool = _write(rows_pool, slots, row[0])
        ix_pool = _write(ix_pool, slots, k_i[0])
    with phase("attend"):
        kb = KEY_BLOCK_PAGES * p
        qpos = pos[:, None]
        hi_blk = (jnp.minimum(pos[0] + chunk, t_end) - 1) // kb + 1
        chosen = prefill_selection(ix_pool, tab, q_i[0], w_i[0], qpos,
                                   hi_blk, a.indexer, p)

        def block(j):
            pages, kpos = block_pages(tab, j, p)
            sel = jax.lax.dynamic_slice(
                chosen, (jnp.zeros((), j.dtype), j * kb), (chunk, kb))
            return rows_pool[pages].reshape(kb, -1), \
                sel & (kpos[None, :] <= qpos)

        with jax.named_scope("sparse_attend"):
            o = sa.attend_kv_blocks(q[0], block, 0, hi_blk, a)
        return o[None].astype(eng.kv_dtype), rows_pool, ix_pool


def attend_chunk(q, k_pool, v_pool, tab, pos, t_end, a, p, sink=None,
                 interpret=False):
    """One chunk of one sequence of a per-head layer without an indexer,
    after its K and V were written (arguments as `attend_chunk_blocks`).
    Which implementation runs is read off the shapes: pools by head
    ([pages, p, kv heads, d], not flat) whose key and value widths fill
    whole 128-lane registers, and a chunk the kernel's query blocks
    divide, take the Pallas kernel; the rest the XLA key blocks."""
    if k_pool.ndim == 4 and a.qk_dim % 128 == 0 and a.v_dim % 128 == 0 \
            and pos.shape[0] % 8 == 0:
        return paged_chunk_attention(
            q, k_pool, v_pool, tab, pos[0], t_end, window=a.window,
            sinks=sink, interpret=interpret).astype(k_pool.dtype)
    return attend_chunk_blocks(q, k_pool, v_pool, tab, pos, t_end, a, p,
                               sink)


def attend_chunk_blocks(q, k_pool, v_pool, tab, pos, t_end, a, p, sink=None):
    """One chunk of one sequence of a per-head layer without an indexer,
    after its K and V were written: q [chunk, H, d] at positions pos
    [chunk] against the pools ([pages, p, kv heads, d] or flat [pages, p,
    kv heads * d]; values [pages, p, kv heads, dv]) through tab
    [pages_per_seq] -> [chunk, H, dv] in the pools' dtype. Query i sees
    keys j <= pos[i], and j > pos[i] - window in a window layer: the walk
    starts at the PAGE of the first query's oldest key (the pages behind
    it are freed, their table entries dead, and are never read) and ends
    at the block of the chunk's last real position."""
    chunk, nb = pos.shape[0], KEY_BLOCK_PAGES
    kb = nb * p
    qpos = pos[:, None]
    first = 0 if a.window is None else \
        jnp.maximum(pos[0] - a.window + 1, 0) // p
    last = (jnp.minimum(pos[0] + chunk, t_end) - 1) // p    # a page
    n_blk = (last - first) // nb + 1

    def block(j):
        pages, kpos = block_pages(tab, j, p, nb, first)
        seen = kpos[None, :] <= qpos
        if a.window is not None:
            seen = seen & (kpos[None, :] > qpos - a.window)
        return sa.kv_row(
            k_pool[pages].reshape(kb, a.n_kv_heads, a.qk_dim),
            v_pool[pages].reshape(kb, a.n_kv_heads, a.v_dim)), seen

    with jax.named_scope("chunk_attend_blocks"):
        o = sa.attend_kv_blocks(q, block, 0, n_blk, a, sink)
    return o.astype(k_pool.dtype)
