"""Learned sparse attention: the indexer, the exact top-k selection, and
attention of per-head (grouped-query) K and V under that selection, as op
chains. Both layer kinds that select call this module: latent layers
(ops/latent_attention.py, inference/latent.py) for the indexer and the
selection, per-head layers (inference/sparse_heads.py, the model's eager
forward) for all of it. Nothing here knows a cache or a page table.

Indexer (`ix` an IndexerSpec): q^I_j = q_src W^I_q (q_src the query
latent of a latent layer, the normed hidden state of a per-head one),
k^I = LayerNorm(x W^I_k), both rotated on their leading `ix.rope_dim`
dims, w = x W^I_w;  I(t, s) = sum_j w_j(t) relu(q^I_j(t) . k^I(s)),
float32 at precision "highest" like the router; a query attends to the
top_k visible positions by I (all while fewer are visible). The selection
is exact, in the form its consumer takes: `select_top` (lax.top_k) gives
decode the LIST of positions its row gather needs, `top_mask` (a radix
select on the float's bits, `kth_largest`, then one cumulative count)
gives prefill the MASK its walk over key blocks needs. Turning one into
the other is a sort or a scatter of [queries, max_len] on this chip,
dearer than either; both send ties at the k-th value to the lowest
positions, and one test holds both to a stable argsort of the
reference's scores.

Per-head attention under a selection (`a` an AttentionSpec without
`latent`): a token's cache ROW is [K ; V] = its `n_kv_heads` keys side by
side and then its values, so ONE gathered row a selected token brings
both; query head h reads kv head h // (heads / kv heads).
"""
import math

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
_NEG = -1e30

# what a layer with an indexer adds to the engine's device counters in a
# decode step: index keys visible to its queries, cache rows they
# attended to, index keys the scan scored (dead pages included), and the
# queries themselves
SPARSE_COUNTS = ("visible", "attended", "scored", "queries")


def rms(x, w, eps):
    """RMSNorm over the last dim (a hidden state's, a latent's, a head's
    width), float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def rope_half(x, cos, sin):
    """Rotate-half over ALL of x's last dim; cos/sin broadcast to
    [..., d / 2]."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def index_qkw(x, q_src, w, ix, cos, sin):
    """(q^I [..., Hi, di], k^I [..., di], w [..., Hi]) float32; q_src is
    what `ix_wq` multiplies ([..., ix_wq rows]); cos/sin [...,
    ix.rope_dim / 2]."""
    x, q_src = x.astype(jnp.float32), q_src.astype(jnp.float32)
    q = jnp.dot(q_src, w["ix_wq"], precision=_HI).reshape(
        *x.shape[:-1], ix.n_heads, ix.dim)
    k = jnp.dot(x, w["ix_wk"], precision=_HI)
    mu = jnp.mean(k, -1, keepdims=True)
    var = jnp.mean(jnp.square(k - mu), -1, keepdims=True)
    k = (k - mu) * jax.lax.rsqrt(var + ix.eps) * w["ix_kn_w"] + w["ix_kn_b"]
    rd = ix.rope_dim
    q = jnp.concatenate([rope_half(q[..., :rd], cos[..., None, :],
                                   sin[..., None, :]), q[..., rd:]], -1)
    k = jnp.concatenate([rope_half(k[..., :rd], cos, sin), k[..., rd:]], -1)
    return q, k, jnp.dot(x, w["ix_ww"], precision=_HI)


def index_scores(q, k, wt):
    """I(t, s): q [..., t, Hi, di], k [..., s, di], wt [..., t, Hi] ->
    [..., t, s] float32."""
    s = jnp.einsum("...thd,...sd->...ths", q, k.astype(jnp.float32),
                   precision=_HI)
    s = jnp.sum(jax.nn.relu(s) * wt[..., None], axis=-2)
    return jnp.where(s == 0, 0.0, s)    # one zero: ties break by position


def select_top(scores, visible, k):
    """The k visible positions with the largest score: (idx [..., k]
    int32, valid [..., k] bool). Fewer visible: all of them, the rest
    invalid."""
    vals, idx = jax.lax.top_k(jnp.where(visible, scores, -jnp.inf),
                              min(k, scores.shape[-1]))
    return idx.astype(jnp.int32), vals > -jnp.inf


def kth_largest(scores, k):
    """The k-th largest of each row of scores [n, s] float32 (-inf where
    the row has fewer than k finite entries among -inf padding): a radix
    select over the bits, 32 counting passes, no sort."""
    k = min(k, scores.shape[-1])
    u = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    u = jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(1 << 31))  # monotone

    def body(i, prefix):
        cand = prefix | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(
            jnp.uint32)))
        cnt = jnp.sum(u >= cand[:, None], axis=1)
        return jnp.where(cnt >= k, cand, prefix)

    p = jax.lax.fori_loop(0, 32, body, jnp.zeros(scores.shape[0],
                                                 jnp.uint32))
    p = jnp.where(p >> 31 == 1, p & jnp.uint32((1 << 31) - 1), ~p)
    return jax.lax.bitcast_convert_type(p, jnp.float32)


def top_mask(scores, k):
    """[n, s] bool: each row's k largest entries, ties at the k-th value
    going to the lowest positions (what lax.top_k and a stable argsort
    pick), from `kth_largest` and one cumulative count."""
    thr = kth_largest(scores, k)[:, None]
    above, ties = scores > thr, scores == thr
    room = min(k, scores.shape[-1]) - jnp.sum(
        above, axis=1, dtype=jnp.int32, keepdims=True)
    return above | (ties & (jnp.cumsum(ties, axis=1, dtype=jnp.int32)
                            <= room))


# ------------------------------------------ per-head K and V, selected --
def kv_row_width(a):
    """Values of one token's [K ; V] row."""
    return a.n_kv_heads * (a.qk_dim + a.v_dim)


def kv_row(k, v):
    """k [..., G, d], v [..., G, dv] -> the tokens' rows [..., G * (d +
    dv)]: the keys side by side, then the values."""
    lead = k.shape[:-2]
    return jnp.concatenate([k.reshape(*lead, -1), v.reshape(*lead, -1)], -1)


def _kv_of(rows, g, a):
    """KV head g's keys [..., d] and values [..., dv] out of rows: slices
    at multiples of the head widths (the 128 lanes at published sizes), no
    relayout of the gathered rows."""
    k0 = g * a.qk_dim
    v0 = a.n_kv_heads * a.qk_dim + g * a.v_dim
    return rows[..., k0:k0 + a.qk_dim], rows[..., v0:v0 + a.v_dim]


def attend_selected(q, rows, valid, a):
    """ONE query a sequence over ITS OWN selected rows: q [b, H, d], rows
    [b, n, row] (the selected tokens' [K ; V]), valid [b, n] -> the
    heads' outputs [b, H, dv] float32."""
    rep = a.n_heads // a.n_kv_heads
    scale = 1.0 / math.sqrt(a.qk_dim)
    outs = []
    for g in range(a.n_kv_heads):
        k, v = _kv_of(rows, g, a)
        lg = jnp.einsum("bhd,bnd->bhn",
                        q[:, g * rep:(g + 1) * rep].astype(rows.dtype), k,
                        preferred_element_type=jnp.float32) * scale
        lg = jnp.where(valid[:, None, :], lg, _NEG)
        p = jax.nn.softmax(lg, -1).astype(rows.dtype)
        outs.append(jnp.einsum("bhn,bnd->bhd", p, v,
                               preferred_element_type=jnp.float32))
    return jnp.concatenate(outs, 1)


def attend_kv_blocks(q, block, lo, hi, a, sink=None):
    """t queries of one sequence over the key blocks lo..hi-1 that all of
    them share, one block at a time (online softmax): q [t, H, d];
    block(j) -> (rows [n, row], seen [t, n]). A KV head's query heads
    meet its keys as they are cached: nothing is repeated to the query
    head count, and no [H, t, all keys] tensor exists. sink [H]: a
    learned logit a head, in the softmax's denominator only. Returns the
    heads' outputs [t, H, dv] float32."""
    t, G = q.shape[0], a.n_kv_heads
    rep = a.n_heads // G
    scale = 1.0 / math.sqrt(a.qk_dim)
    nk = G * a.qk_dim

    def body(j, carry):
        m, l, acc = carry
        rows, seen = block(j)
        # ONE product over the KV heads (a batch dimension), each head's
        # rep query heads against its own keys; no concatenation of
        # per-head results (on the v5e those were whole-tensor copies,
        # 14 of a 63 ms chunk attention: PERF.md 6, PR 36)
        k = rows[:, :nk].reshape(-1, G, a.qk_dim)
        v = rows[:, nk:nk + G * a.v_dim].reshape(-1, G, a.v_dim)
        qg = q.astype(rows.dtype).reshape(t, G, rep, a.qk_dim)
        lg = jnp.einsum("tgrd,ngd->grtn", qg, k,
                        preferred_element_type=jnp.float32) * scale
        ok = seen[None, None]
        lg = jnp.where(ok, lg, _NEG)
        m_new = jnp.maximum(m, jnp.max(lg, -1))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(ok, jnp.exp(lg - m_new[..., None]), 0.0)
        pv = jnp.einsum("grtn,ngd->grtd", p.astype(rows.dtype), v,
                        preferred_element_type=jnp.float32)
        return m_new, l * alpha + jnp.sum(p, -1), acc * alpha[..., None] + pv

    init = (jnp.full((G, rep, t), _NEG, jnp.float32),
            jnp.zeros((G, rep, t), jnp.float32),
            jnp.zeros((G, rep, t, a.v_dim), jnp.float32))
    m, l, acc = jax.lax.fori_loop(lo, hi, body, init)
    if sink is not None:
        sk = sink.astype(jnp.float32).reshape(G, rep, 1)
        m_fin = jnp.maximum(m, sk)
        beta = jnp.exp(m - m_fin)
        acc, l = acc * beta[..., None], l * beta + jnp.exp(sk - m_fin)
    out = acc / jnp.maximum(l, 1e-30)[..., None]            # [G, rep, t, dv]
    return jnp.moveaxis(out, 2, 0).reshape(t, a.n_heads, a.v_dim)


def sparse_gqa_attention_dense(x, w, a, eps, cos, sin, ix_cos, ix_sin):
    """One layer's attention on x [b, s, hidden] (normed), dense masks:
    what the model's eager forward runs. cos/sin [s, qk_dim / 2] of the
    layer's base, ix_cos/ix_sin [s, ix.rope_dim / 2]. `w`: wq wk wv wo,
    q_hn k_hn where `a.qk_norm`, the indexer's ix_*. Returns [b, s,
    hidden]."""
    b, s, _ = x.shape

    def dot(x_, w_):
        return jnp.dot(x_.astype(w_.dtype), w_,
                       preferred_element_type=jnp.float32)

    q = dot(x, w["wq"]).reshape(b, s, a.n_heads, a.qk_dim)
    k = dot(x, w["wk"]).reshape(b, s, a.n_kv_heads, a.qk_dim)
    v = dot(x, w["wv"]).reshape(b, s, a.n_kv_heads, a.v_dim)
    if a.qk_norm:
        q, k = rms(q, w["q_hn"], eps), rms(k, w["k_hn"], eps)
    q = rope_half(q, cos[:, None, :], sin[:, None, :])
    k = rope_half(k, cos[:, None, :], sin[:, None, :])
    rep = a.n_heads // a.n_kv_heads
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, rep, 2),
                        precision=_HI) / math.sqrt(a.qk_dim)
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    seen = jnp.broadcast_to(j <= i, (b, s, s))
    if a.indexer is not None:
        qi, ki, wi = index_qkw(x, x, w, a.indexer, ix_cos, ix_sin)
        idx, valid = select_top(index_scores(qi, ki, wi), seen,
                                a.indexer.top_k)
        seen = jnp.put_along_axis(jnp.zeros_like(seen), idx, valid,
                                  axis=-1, inplace=False)
    logits = jnp.where(seen[:, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, -1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, jnp.repeat(v, rep, 2),
                   precision=_HI)
    return dot(o.reshape(b, s, -1), w["wo"])
