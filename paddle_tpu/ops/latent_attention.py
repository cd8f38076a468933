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

Indexer (`a.indexer`): its query comes from the query latent, q^I_j = c_q
W^I_q; the index score, the exact top-k selection (both forms) and the
selection counters are ops/sparse_attention.py's, which per-head layers
with an indexer call too.

Products take their operands in the WEIGHTS' dtype with float32 sums.
"""
import math

import jax
import jax.numpy as jnp

from .sparse_attention import (index_qkw, index_scores, rope_half,
                               select_top)
from .sparse_attention import rms as _rms

_HI = jax.lax.Precision.HIGHEST
_NEG = -1e30


def _dot(x, w):
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


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
