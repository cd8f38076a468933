"""Latent attention with a learned sparse selection, as op chains.

The model's eager forward and the serving engine's step programs both
call these functions; nothing here knows a cache or a page table.

Latent attention (per layer; `a` an AttentionSpec with `a.latent`):
  c_q = RMSNorm(x W_dq) * q_scale;  q_h = c_q W_uq,h = [q_n,h ; q_r,h],
  q_r rotated;  [c_kv ; k_r] = x W_dkv,  c_kv = RMSNorm(c_kv) * kv_scale,
  k_r rotated, ONE per token for all heads: the token's cache ROW;
  [k_n,h ; v_h] = c_kv [W_uk,h ; W_uv,h];
  logit = (q_n,h . k_n,h + q_r,h . k_r) / sqrt(no-position + rotary width).
Absorbed form (what the engine runs, decode and prefill): q~_h = [q_n,h
W_uk,h^T ; q_r,h] scores the rows directly, the weighted sum runs over
c_kv, and W_uv,h comes after it.

Indexer (`a.indexer`): q^I_j = c_q W^I_q, k^I = LayerNorm(x W^I_k), both
rotated on their leading dims, w = x W^I_w;  I(t, s) = sum_j w_j(t)
relu(q^I_j(t) . k^I(s)), float32 at precision "highest" like the router;
a query attends to the top_k visible positions by I (all while fewer are
visible). The selection is exact, in the form its consumer takes:
`select_top` (lax.top_k) gives decode the LIST of positions its row
gather needs, `top_mask` (a radix select on the float's bits,
`kth_largest`, then one cumulative count) gives prefill the MASK its
walk over key blocks needs. Turning one into the other is a sort or a
scatter of [queries, max_len] on this chip, dearer than either; both
send ties at the k-th value to the lowest positions, and one test holds
both to a stable argsort of the reference's scores.

Products take their operands in the WEIGHTS' dtype with float32 sums.
"""
import math

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
_NEG = -1e30


def _dot(x, w):
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def _rms(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def rope_half(x, cos, sin):
    """Rotate-half over ALL of x's last dim; cos/sin broadcast to
    [..., d / 2]."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def softmax_scale(a):
    return 1.0 / math.sqrt(a.qk_dim)


def latent_qkv(x, w, a, eps, cos, sin):
    """x [..., hidden] (normed); cos/sin [..., rotary / 2] at each token's
    position. Returns float32 (q_n [..., H, no-position], q_r [..., H,
    rotary] rotated, row [..., latent rank + rotary], c_q [..., q rank])."""
    lat = a.latent
    c_q = _rms(_dot(x, w["wq_a"]), w["q_norm"], eps) * lat.q_scale
    q = _dot(c_q, w["wq_b"]).reshape(*x.shape[:-1], a.n_heads, a.qk_dim)
    dn = a.qk_dim - a.rope_dim
    q_r = rope_half(q[..., dn:], cos[..., None, :], sin[..., None, :])
    kv = _dot(x, w["wkv_a"])
    c_kv = _rms(kv[..., :lat.kv_rank], w["kv_norm"], eps) * lat.kv_scale
    k_r = rope_half(kv[..., lat.kv_rank:], cos, sin)
    return q[..., :dn], q_r, jnp.concatenate([c_kv, k_r], -1), c_q


def absorb_query(q_n, q_r, w_uk, a):
    """q~ [..., H, latent rank + rotary] float32: the query carried into
    the latent space."""
    r = a.latent.kv_rank
    uk = w_uk.reshape(r, a.n_heads, a.qk_dim - a.rope_dim)
    q_lat = jnp.einsum("...hd,rhd->...hr", q_n.astype(uk.dtype), uk,
                       preferred_element_type=jnp.float32)
    return jnp.concatenate([q_lat, q_r], -1)


def expand_values(o_lat, w_uv, a):
    """[..., H, latent rank] -> [..., H, value width] float32: W_uv after
    the sum."""
    uv = w_uv.reshape(a.latent.kv_rank, a.n_heads, a.v_dim)
    return jnp.einsum("...hr,rhd->...hd", o_lat.astype(uv.dtype), uv,
                      preferred_element_type=jnp.float32)


def head_gate(x, w_gate):
    """sigmoid(x W_g) [..., H] float32."""
    return jax.nn.sigmoid(_dot(x, w_gate))


def index_qkw(x, c_q, w, ix, cos, sin):
    """(q^I [..., Hi, di], k^I [..., di], w [..., Hi]) float32; cos/sin
    [..., ix.rope_dim / 2]."""
    x, c_q = x.astype(jnp.float32), c_q.astype(jnp.float32)
    q = jnp.dot(c_q, w["ix_wq"], precision=_HI).reshape(
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


def attend_rows(q_abs, rows, valid, kv_rank, scale):
    """Absorbed attention of ONE query a sequence over ITS OWN rows:
    q_abs [b, H, row], rows [b, n, row], valid [b, n] -> the weighted sum
    of the latents [b, H, kv_rank] float32."""
    lg = jnp.einsum("bhc,bnc->bhn", q_abs, rows,
                    preferred_element_type=jnp.float32) * scale
    lg = jnp.where(valid[:, None, :], lg, _NEG)
    p = jax.nn.softmax(lg, -1).astype(rows.dtype)
    return jnp.einsum("bhn,bnr->bhr", p, rows[..., :kv_rank],
                      preferred_element_type=jnp.float32)


def attend_key_blocks(q_abs, block, lo, hi, kv_rank, scale):
    """Absorbed attention of t queries of one sequence over the key
    blocks lo..hi-1 that all of them share, one block at a time (online
    softmax): q_abs [t, H, row]; block(j) -> (rows [n, row], seen [t,
    n]). Returns the weighted sums of the latents [t, H, kv_rank]
    float32. No [H, t, all keys] tensor exists. (The expanded form, keys
    and values per head once a block, was measured SLOWER at 512 queries
    on the v5e, 21.4 against 15.3 ms a chunk: its products contract over
    64 and 128-192 wide heads, these over the 640-wide row; PERF.md,
    PR 30.)"""
    t, n_heads, _ = q_abs.shape

    def body(j, carry):
        m, l, acc = carry
        rows, seen = block(j)
        lg = jnp.einsum("thc,nc->htn", q_abs, rows,
                        preferred_element_type=jnp.float32) * scale
        lg = jnp.where(seen[None], lg, _NEG)
        m_new = jnp.maximum(m, jnp.max(lg, -1))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(seen[None], jnp.exp(lg - m_new[..., None]), 0.0)
        acc = acc * alpha[..., None] + jnp.einsum(
            "htn,nr->htr", p.astype(rows.dtype), rows[:, :kv_rank],
            preferred_element_type=jnp.float32)
        return m_new, l * alpha + jnp.sum(p, -1), acc

    init = (jnp.full((n_heads, t), _NEG, jnp.float32),
            jnp.zeros((n_heads, t), jnp.float32),
            jnp.zeros((n_heads, t, kv_rank), jnp.float32))
    _, l, acc = jax.lax.fori_loop(lo, hi, body, init)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.swapaxes(out, 0, 1)


def latent_attention_dense(x, w, a, eps, cos, sin):
    """One layer's attention on x [b, s, hidden] (normed), the EXPANDED
    form with dense masks: what the model's eager forward runs. cos/sin
    [s, rotary / 2] of the layer's base. Returns [b, s, hidden]."""
    b, s, _ = x.shape
    r = a.latent.kv_rank
    q_n, q_r, row, c_q = latent_qkv(x, w, a, eps, cos, sin)
    c_kv, k_r = row[..., :r], row[..., r:]
    k_n = _dot(c_kv, w["w_uk"]).reshape(b, s, a.n_heads, -1)
    v = _dot(c_kv, w["w_uv"]).reshape(b, s, a.n_heads, a.v_dim)
    logits = (jnp.einsum("bqhd,bkhd->bhqk", q_n, k_n, precision=_HI)
              + jnp.einsum("bqhd,bkd->bhqk", q_r, k_r, precision=_HI)) \
        * softmax_scale(a)
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    seen = jnp.broadcast_to(j <= i, (b, s, s))
    if a.window is not None:
        seen = seen & (j > i - a.window)
    if a.indexer is not None:
        qi, ki, wi = index_qkw(x, c_q, w, a.indexer, cos, sin)
        idx, valid = select_top(index_scores(qi, ki, wi), seen,
                                a.indexer.top_k)
        seen = jnp.put_along_axis(jnp.zeros_like(seen), idx, valid,
                                  axis=-1, inplace=False)
    logits = jnp.where(seen[:, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, -1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=_HI)
    if a.gate:
        o = o * head_gate(x, w["w_gate"])[..., None]
    return _dot(o.reshape(b, s, -1), w["wo"])


def swiglu(x, wg, wu, wd):
    """(silu(x G) * (x U)) D, float32 result."""
    g, u = _dot(x, wg), _dot(x, wu)
    return _dot(jax.nn.silu(g) * u, wd)
