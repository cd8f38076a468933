"""Ring attention — sequence/context parallelism over the 'sep' mesh axis.

ABSENT from the reference (SURVEY §5.7: "SP/CP is green-field"); designed
TPU-first per §5.7's plan: blockwise attention with KV chunks rotated around
the ICI ring via lax.ppermute, online-softmax merge keeps O(s/N) memory per
chip. Causality is handled by rank-offset masking (each rank owns a
contiguous sequence shard).

Works inside any shard_map region that binds the 'sep' axis; composes with
TP ('model' axis shards heads) and DP.
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from .....ops import apply
from .....tensor.tensor import Tensor
from ....mesh import in_spmd_region
from jax.lax import axis_size as _axis_size

NEG_INF = -1e30


def _block_attn(q, k, v, scale, mask, dropout_p=0.0, drop_key=None):
    """q:[b,sq,h,d] k,v:[b,sk,h_kv,d] (h_kv divides h — GQA expands
    here, at compute time, so the RING rotates the small h_kv buffers);
    mask:[sq,sk] bool or None.

    Attention dropout (drop_key set): drops NORMALIZED probabilities —
    the accumulator `o` uses the dropped/inverted-scaled weights while
    the normalizer `l` keeps the full softmax sum, exactly
    dropout(softmax(logits)) @ v once the online merge divides by l.
    Returns (out_unnormalized [b,sq,h,d], m [b,sq,h,1], l [b,sq,h,1])."""
    if k.shape[2] != q.shape[2]:
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if mask is not None:
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)           # b h q 1
    p = jnp.exp(logits - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    if dropout_p and drop_key is not None:
        keep = jax.random.bernoulli(drop_key, 1.0 - dropout_p, p.shape)
        p_o = jnp.where(keep, p / (1.0 - dropout_p),
                        jnp.zeros((), p.dtype))
    else:
        p_o = p
    o = jnp.einsum("bhqk,bkhd->bqhd", p_o, v)
    # to b q h 1 layout
    m = jnp.transpose(m, (0, 2, 1, 3))
    l = jnp.transpose(l, (0, 2, 1, 3))
    return o, m, l


def ring_attention(q, k, v, axis_name="sep", causal=True, scale=None,
                   dropout_p=0.0):
    """Sequence-sharded attention. q,k,v: local [b, s_loc, h, d] jnp arrays
    inside an SPMD region with `axis_name` bound. dropout_p: in-ring
    attention-probability dropout (framework RNG stream; each (rank,
    chunk) pair draws an independent mask)."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    scale = jnp.float32(scale)
    n = _axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    b, s_loc, h, d = q.shape

    rows = jax.lax.broadcasted_iota(jnp.int32, (s_loc, s_loc), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (s_loc, s_loc), 1)

    if dropout_p:
        from .....framework import random as frnd
        base_key = jax.random.fold_in(frnd.next_key(), rank)
    else:
        base_key = None

    def step(carry, i):
        k_cur, v_cur, acc, m, l = carry
        # k_cur currently holds the chunk of rank (rank - i) mod n
        src = (rank - i) % n
        if causal:
            # my global rows: rank*s_loc + r ; chunk cols: src*s_loc + c
            full = src < rank
            none = src > rank
            diag_mask = rows >= cols
            mask = jnp.where(full, jnp.ones_like(diag_mask),
                             jnp.where(none, jnp.zeros_like(diag_mask),
                                       diag_mask))
        else:
            mask = None
        dk = (jax.random.fold_in(base_key, i) if base_key is not None
              else None)
        o_i, m_i, l_i = _block_attn(q, k_cur, v_cur, scale, mask,
                                    dropout_p=dropout_p, drop_key=dk)
        if causal:
            # fully-masked chunks produce m=-inf rows; guard merge
            m_i = jnp.where(l_i > 0, m_i, NEG_INF)
        m_new = jnp.maximum(m, m_i)
        a1 = jnp.exp(m - m_new)
        a2 = jnp.exp(m_i - m_new)
        acc = acc * a1 + o_i * a2
        l = l * a1 + l_i * a2
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (k_nxt, v_nxt, acc, m_new, l), None

    acc0 = jnp.zeros((b, s_loc, h, d), jnp.float32)
    m0 = jnp.full((b, s_loc, h, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, s_loc, h, 1), jnp.float32)
    try:  # mark device-varying for VMA-checked shard_map regions
        # (pcast(..., to='varying') — lax.pvary is deprecated)
        acc0 = lax.pcast(acc0, (axis_name,), to="varying")
        m0 = lax.pcast(m0, (axis_name,), to="varying")
        l0 = lax.pcast(l0, (axis_name,), to="varying")
    except Exception:
        pass
    (k_f, v_f, acc, m, l), _ = lax.scan(
        step, (k.astype(jnp.float32), v.astype(jnp.float32), acc0, m0, l0),
        jnp.arange(n))
    out = acc / jnp.maximum(l, 1e-30)
    return out.astype(q.dtype)


def sep_split(x, axis_name="sep", seq_axis=1):
    """Scatter the sequence dim across the sep axis (fwd slice, bwd gather)."""
    if not in_spmd_region(axis_name):
        return x

    def fn(a):
        n = _axis_size(axis_name)
        idx = lax.axis_index(axis_name)
        sz = a.shape[seq_axis] // n
        return lax.dynamic_slice_in_dim(a, idx * sz, sz, axis=seq_axis)

    return apply(fn, x, name="sep_split")


def sep_concat(x, axis_name="sep", seq_axis=1):
    """Gather sequence shards (fwd all_gather, bwd slice)."""
    if not in_spmd_region(axis_name):
        return x
    return apply(lambda a: lax.all_gather(a, axis_name, axis=seq_axis,
                                          tiled=True),
                 x, name="sep_concat")


class RingFlashAttention:
    """Module-style wrapper usable from Layer.forward: inputs [b, s_loc, h, d]
    Tensors. For the 'sep' axis this is a trivial delegate —
    scaled_dot_product_attention is the SINGLE dispatch point (ring when
    'sep' is live, plain sdpa/Pallas otherwise); other axis names keep a
    direct ring path."""

    def __init__(self, axis_name="sep", causal=True, dropout_p=0.0):
        self.axis_name = axis_name
        self.causal = causal
        self.dropout_p = dropout_p

    def __call__(self, q, k, v):
        if self.axis_name == "sep":
            from .....nn.functional.attention import (
                scaled_dot_product_attention)
            return scaled_dot_product_attention(
                q, k, v, is_causal=self.causal, dropout_p=self.dropout_p)
        if in_spmd_region(self.axis_name):
            # GQA: KV stays at h_kv heads ON THE WIRE (the ring's
            # bandwidth saving); _block_attn expands at compute time
            if q.shape[2] % k.shape[2]:
                raise ValueError(
                    f"query heads {q.shape[2]} must be a multiple of kv "
                    f"heads {k.shape[2]}")
            return apply(functools.partial(ring_attention,
                                           axis_name=self.axis_name,
                                           causal=self.causal,
                                           dropout_p=self.dropout_p),
                         q, k, v, name="ring_attention")
        from .....nn.functional.attention import scaled_dot_product_attention
        return scaled_dot_product_attention(q, k, v, is_causal=self.causal,
                                            dropout_p=self.dropout_p)
