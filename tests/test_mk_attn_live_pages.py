"""The megakernel's attention phase, one grid step a slot (ISSUE 29).

The phase no longer walks the page TABLE (slot x column, live or not):
a slot's step loops over its LIVE pages, ceil(seq_len / page) of them,
copied from the pools left in HBM by the kernel itself. What must hold
is what held before: the layer's output is BIT-identical to the unfused
scatter-then-attend path (write-gated page scatter, then
`paged_attention` / `spec_verify_attention`), wherever a slot's length
puts the loop's bound and the current tokens' page —

  lengths  inactive slot | 1 token | p - 1 | exactly p (the current token
           opens a new page) | p + 1 | all mp pages full | mixed
  x  T     1 | 4 with a partial write mask
  x  mode  per-layer | stacked "multi" (5-D pools, 2 layers) |
           seg="qkv" under tp 2 (shard_map, per-shard heads and pools)

— and a slot with no live page emits exact zeros. One compiled pair
(kernel, reference) per (T, mode); lengths, activity and write mask are
run-time arguments, as they are in the engine.
"""
import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from paddle_tpu.inference.serving import _mm, _rms
from paddle_tpu.ops.pallas.decode_megakernel import (
    decode_megakernel, pack_decode_layer, stack_packed)
from paddle_tpu.ops.pallas.paged_attention import (paged_attention,
                                                   spec_verify_attention)

B, NH, NKV, HD, H, F, PG, MP, NPAGES, L = 4, 4, 2, 8, 32, 48, 8, 3, 16, 2
EPS = 1e-5


def _lens_cases(T):
    full = MP * PG - T           # the feed tokens fill the last page
    return {
        "inactive_slot": ([PG + 3, 5, 2 * PG, 1], [0, 1, 0, 1]),
        "one_token": ([0] * B, [1] * B),
        "p_minus_1": ([PG - 1] * B, [1] * B),
        "exactly_p": ([PG] * B, [1] * B),
        "p_plus_1": ([PG + 1] * B, [1] * B),
        "all_pages_full": ([full] * B, [1] * B),
        "mixed": ([PG + 1, 0, PG, full], [0, 1, 1, 1]),
    }


# the engine's write_ok is a prefix of each slot's feed row, none for an
# inactive slot; T = 1 has no mask
_WM4 = np.array([[1, 1, 0, 0], [1, 1, 1, 1], [1, 0, 0, 0], [1, 1, 1, 0]],
                np.int32)


@functools.lru_cache(maxsize=None)
def _state():
    rng = np.random.RandomState(29)

    def w(k, n):
        return jnp.asarray(rng.randn(k, n).astype(np.float32) * 0.1)

    layers = [dict(wq=w(H, NH * HD), wk=w(H, NKV * HD), wv=w(H, NKV * HD),
                   wo=w(NH * HD, H), wg=w(H, F), wu=w(H, F), wd=w(F, H),
                   ln1=jnp.asarray(rng.rand(H).astype(np.float32) + 0.5),
                   ln2=jnp.asarray(rng.rand(H).astype(np.float32) + 0.5))
              for _ in range(L)]
    pools = [jnp.asarray(rng.randn(L, NPAGES, PG, NKV, HD)
                         .astype(np.float32)) for _ in range(2)]
    tbl = jnp.asarray(rng.permutation(NPAGES)[:B * MP]
                      .reshape(B, MP).astype(np.int32))
    return dict(layers=layers, kp=pools[0], vp=pools[1], tbl=tbl)


def _ref_attn(ws, h3, kpg, vpg, tbl, lens, act, wm, cos, sin, nh, nkv):
    """The unfused path of ONE layer's attention block over [b, T, H]
    rows: projections, rope, write-gated scatter, then the paged
    kernel. -> (attn [b, T, nh, hd], k, v [b, T, nkv, hd])."""
    b, T, _ = h3.shape
    x = _rms(h3, ws["ln1"], EPS)
    q = _mm(x, ws["wq"], True).reshape(b, T, nh, HD)
    k = _mm(x, ws["wk"], True).reshape(b, T, nkv, HD)
    v = _mm(x, ws["wv"], True).reshape(b, T, nkv, HD)
    c = cos.reshape(b, T, 1, HD // 2)
    s = sin.reshape(b, T, 1, HD // 2)

    def rope(t):
        t1, t2 = t[..., :HD // 2], t[..., HD // 2:]
        return jnp.concatenate([t1 * c - t2 * s, t2 * c + t1 * s], -1)

    q, k = rope(q), rope(k)
    pos = lens[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    slots = tbl[jnp.arange(b)[:, None], pos // PG] * PG + pos % PG
    slots = jnp.where(wm.reshape(b, T) > 0, slots, jnp.int32(NPAGES * PG))
    kp2 = kpg.reshape(-1, nkv, HD).at[slots].set(k, mode="drop") \
        .reshape(kpg.shape)
    vp2 = vpg.reshape(-1, nkv, HD).at[slots].set(v, mode="drop") \
        .reshape(vpg.shape)
    if T == 1:
        attn = paged_attention(q[:, 0], kp2, vp2, tbl,
                               jnp.where(act > 0, lens + 1, 0),
                               interpret=True, active=act)[:, None]
    else:
        attn = spec_verify_attention(q, kp2, vp2, tbl, lens, active=act,
                                     interpret=True)
    return attn, k, v


def _ref_layer(ws, h3, *a):
    attn, k, v = _ref_attn(ws, h3, *a, NH, NKV)
    b, T, _ = h3.shape
    h2 = h3 + _mm(attn.reshape(b, T, -1), ws["wo"], True)
    x2 = _rms(h2, ws["ln2"], EPS)
    g = _mm(x2, ws["wg"], True)
    u = _mm(x2, ws["wu"], True)
    act = jax.nn.silu(g.astype(jnp.float32)).astype(g.dtype) * u
    return h2 + _mm(act, ws["wd"], True), k, v


@functools.lru_cache(maxsize=None)
def _pair(T, mode):
    """(kernel, reference): jitted (h [R, H], lens, act, wm [R], cos,
    sin) -> the same tuple of arrays from both."""
    st = _state()
    R = B * T
    layers, tbl = st["layers"], st["tbl"]
    kw = dict(eps=EPS, interpret=True, tq=T)

    if mode == "layer":
        mk = pack_decode_layer(layers[0])
        kpg, vpg = st["kp"][0], st["vp"][0]

        def kern(h, lens, act, wm, cos, sin):
            return decode_megakernel(h, mk, kpg, vpg, tbl, lens, act, cos,
                                     sin, nh=NH, nh_kv=NKV, hd=HD,
                                     wmask=wm, **kw)

        def ref(h, lens, act, wm, cos, sin):
            ho, k, v = _ref_layer(layers[0], h.reshape(B, T, H), kpg, vpg,
                                  tbl, lens, act, wm, cos, sin)
            return (ho.reshape(R, H), k.reshape(R, -1), v.reshape(R, -1))

    elif mode == "multi":
        mk = stack_packed([pack_decode_layer(ws) for ws in layers])

        def kern(h, lens, act, wm, cos, sin):
            return decode_megakernel(h, mk, st["kp"], st["vp"], tbl, lens,
                                     act, cos, sin, nh=NH, nh_kv=NKV,
                                     hd=HD, wmask=wm, **kw)

        def ref(h, lens, act, wm, cos, sin):
            h3, ks, vs = h.reshape(B, T, H), [], []
            for li, ws in enumerate(layers):
                h3, k, v = _ref_layer(ws, h3, st["kp"][li], st["vp"][li],
                                      tbl, lens, act, wm, cos, sin)
                ks.append(k.reshape(R, -1))
                vs.append(v.reshape(R, -1))
            return h3.reshape(R, H), jnp.stack(ks), jnp.stack(vs)

    else:                       # seg="qkv" per shard of a tp=2 mesh
        mesh = Mesh(np.array(jax.devices()[:2]), ("mp",))
        col, rep, pool = P(None, "mp"), P(), P(None, None, "mp", None)
        ws = layers[0]
        full = pack_decode_layer(ws, tp=2)
        mk = {k: full[k] for k in ("wq", "sq", "wk", "sk", "wv", "sv",
                                   "ln1", "ln2")}
        mk_specs = {k: (rep if k[0] == "l" else col) for k in mk}
        raw = {k: ws[k] for k in ("wq", "wk", "wv", "ln1")}
        raw_specs = {k: (rep if k[0] == "l" else col) for k in raw}
        kpg, vpg = st["kp"][0], st["vp"][0]

        def kern_shard(h, lens, act, wm, cos, sin, mk_l, kp_l, vp_l):
            return decode_megakernel(h, mk_l, kp_l, vp_l, tbl, lens, act,
                                     cos, sin, seg="qkv", nh=NH // 2,
                                     nh_kv=NKV // 2, hd=HD, wmask=wm, **kw)

        def ref_shard(h, lens, act, wm, cos, sin, ws_l, kp_l, vp_l):
            attn, k, v = _ref_attn(ws_l, h.reshape(B, T, H), kp_l, vp_l,
                                   tbl, lens, act, wm, cos, sin,
                                   NH // 2, NKV // 2)
            return (attn.reshape(R, -1), k.reshape(R, -1),
                    v.reshape(R, -1))

        def wrap(fn, w, w_specs):
            sm = shard_map(fn, mesh=mesh,
                           in_specs=(rep,) * 6 + (w_specs, pool, pool),
                           out_specs=(col, col, col), check_vma=False)
            return lambda *a: sm(*a, w, kpg, vpg)

        kern, ref = wrap(kern_shard, mk, mk_specs), wrap(ref_shard, raw,
                                                         raw_specs)
    return jax.jit(kern), jax.jit(ref)


_CASES = [(T, mode, name) for T in (1, 4)
          for mode in ("layer", "multi", "qkv_tp2")
          for name in _lens_cases(T)]


@pytest.mark.parametrize("T,mode,name", _CASES,
                         ids=[f"T{T}-{m}-{n}" for T, m, n in _CASES])
def test_attention_phase_matches_scatter_then_attend(T, mode, name):
    rng = np.random.RandomState(len(name) * 7 + T)
    lens, act = (np.asarray(a, np.int32) for a in _lens_cases(T)[name])
    R = B * T
    wm = (_WM4 if T == 4 else np.ones((B, 1), np.int32)) * act[:, None]
    args = (jnp.asarray(rng.randn(R, H).astype(np.float32)),
            jnp.asarray(lens), jnp.asarray(act),
            jnp.asarray(wm.reshape(R)),
            jnp.asarray(rng.randn(R, HD // 2).astype(np.float32)),
            jnp.asarray(rng.randn(R, HD // 2).astype(np.float32)))
    kern, ref = _pair(T, mode)
    got, want = kern(*args), ref(*args)
    rows = np.repeat(act > 0, T)
    for tag, g, w in zip(("out", "k_new", "v_new"), got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.shape == w.shape, (tag, g.shape, w.shape)
        if tag == "out":
            # an inactive slot's rows: the engine discards them, and its
            # unfused attention leaves them to the kernel's skip
            g, w = g[rows], w[rows]
        assert (g == w).all(), (
            f"{tag} parts from the unfused path: "
            f"{np.abs(g - w).max()} at T={T} {mode} {name}")
    if mode == "qkv_tp2":
        # no live page -> the step's emission is exact zeros
        attn = np.asarray(got[0])
        assert (attn[~rows] == 0).all()
