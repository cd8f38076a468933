#!/usr/bin/env python
"""On-chip kernel x shape validation sweep.

Every Pallas kernel the training step and the serving engine can reach,
compiled by libtpu's Mosaic on the TPU and compared with an XLA
reference on the same device: flash fwd+bwd (d=64 fallback and d=128
layouts, masked, native dropout), paged-attention decode (MHA, GQA,
dense-cache), the int8 weight-only matmul, rms_norm fwd+bwd — and the
serving kernels at the 1.3B (h2048/ffn5504) and 7B (h4096/ffn11008)
geometries, d=128, page 128, slot widths 1/2/4/8:
ragged_paged_attention (chunk 128), spec_verify_attention (T=4), and
decode_megakernel per-layer ("layer") and stacked with the head fold
("multi", K=1 and K=8), dense bf16 / dense f32 / int8, tq=4, and the
three tensor-parallel segments at the tp=2 shard shapes.

Prints one table row per case and a final JSON line stamped with the
device; exits non-zero if any case fails. No TPU is a failure, not a
skip. The layout/VMEM checks this sweep exists for run inside libtpu
when XLA compiles, so only a chip run proves anything;
tests/test_mosaic_lowering.py pins the cheaper TPU-dialect lowering.

One process holds the chip: the parent stays off jax and runs the cases
in a child that logs each case's start and end to
chiprun_out/kernel_sweep.jsonl. A compiler crash that kills the child
is recorded as that case's failure and the remaining cases run in a new
child.

  python benchmarks/kernel_sweep.py [--only SUBSTR[,SUBSTR...]]
  python benchmarks/kernel_sweep.py --interpret   # CPU dev run: tiny
      geometry, interpret mode, checks the sweep's own plumbing only
"""
import argparse
import json
import os
import subprocess
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOG = os.path.join(ROOT, "chiprun_out", "kernel_sweep.jsonl")
CHILD_TIMEOUT_S = 1500
PAGE = 128
MAX_PAGES = 8             # max_len 1024 / page 128: the smoke's pool
GEOMS = {
    "7b": dict(H=4096, nh=32, hd=128, ffn=11008, V=32000),
    "1p3b": dict(H=2048, nh=16, hd=128, ffn=5504, V=32000),
}
TINY = dict(H=256, nh=2, hd=128, ffn=384, V=640)   # --interpret only
REL_TOL = 3e-2   # relative Frobenius error vs the XLA reference: bf16
#                  activations recast at ~8 points a layer measure
#                  ~1e-2; a layout or indexing bug measures O(1)


def _cases(interpret):
    """name -> zero-arg callable, built lazily (imports jax)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.flash_attention import (make_flash_attention,
                                                       _xla_ref)
    from paddle_tpu.ops.pallas.rms_norm import make_rms_norm
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_attention, paged_attention_dense, paged_attention_reference,
        ragged_paged_attention, ragged_paged_attention_reference,
        spec_verify_attention)
    from paddle_tpu.ops.pallas.chunk_attention import paged_chunk_attention
    from paddle_tpu.ops.pallas.quantized_matmul import (quantized_matmul,
                                                        quantize_weights)
    from paddle_tpu.ops.pallas.decode_megakernel import (
        decode_megakernel, pack_decode_layer, pack_lm_head, stack_packed)

    f32 = jnp.float32
    rng = np.random.RandomState(0)
    cases = {}
    geoms = {"tiny": TINY} if interpret else GEOMS
    page = 16 if interpret else PAGE

    def mk(b, s, h, d, dtype=jnp.bfloat16, scale=0.3):
        return tuple(jnp.asarray(rng.randn(b, s, h, d) * scale, dtype)
                     for _ in range(3))

    def check(a, b, tol):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=tol, atol=tol)

    def rel_err(a, b):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-6))

    def close(name, a, b, tol=REL_TOL):
        e = rel_err(a, b)
        assert np.isfinite(e) and e < tol, f"{name} rel err {e:.3e}"
        return e

    # ---- flash attention fwd+bwd, both layouts -------------------------
    def flash_case(d, dtype, tol):
        def run():
            q, k, v = mk(2, 512, 4, d, dtype)
            flash = make_flash_attention(interpret=interpret)
            sc = 1.0 / np.sqrt(d)
            out = jax.jit(lambda *a: flash(*a, True, sc))(q, k, v)
            ref = _xla_ref(q.astype(f32), k.astype(f32), v.astype(f32),
                           True, sc)
            check(out, ref, tol)
            gf = jax.jit(jax.grad(lambda a, b_, c: jnp.sum(
                flash(a, b_, c, True, sc).astype(f32) ** 2),
                argnums=(0, 1, 2)))(q, k, v)
            gr = jax.grad(lambda a, b_, c: jnp.sum(
                _xla_ref(a, b_, c, True, sc) ** 2), argnums=(0, 1, 2))(
                q.astype(f32), k.astype(f32), v.astype(f32))
            for x, y in zip(gf, gr):
                check(x, y, max(tol, 5e-2 if dtype == jnp.bfloat16
                                else tol))
        return run

    cases["flash_fwd_bwd_d64_bf16_fallback"] = flash_case(
        64, jnp.bfloat16, 5e-2)
    cases["flash_fwd_bwd_d128_bf16_fastpath"] = flash_case(
        128, jnp.bfloat16, 5e-2)
    cases["flash_fwd_bwd_d128_f32_vmem_shrink"] = flash_case(
        128, jnp.float32, 2e-3)

    def masked_case():
        q, k, v = mk(2, 512, 4, 128)
        m = jnp.asarray(rng.randn(2, 4, 512, 512) * 0.5, f32)
        flash = make_flash_attention(interpret=interpret)
        sc = 1.0 / np.sqrt(128)
        out = jax.jit(lambda *a: flash.masked(*a, False, sc))(q, k, v, m)
        ref = _xla_ref(q.astype(f32), k.astype(f32), v.astype(f32),
                       False, sc, mask=m)
        check(out, ref, 5e-2)
    cases["flash_masked_per_head_d128"] = masked_case

    def dropout_case():
        q, k, v = mk(2, 512, 4, 128)
        flash = make_flash_attention(interpret=interpret, dropout_p=0.2)
        sc = 1.0 / np.sqrt(128)
        f = jax.jit(lambda *a: flash.dropout(*a, True, sc))
        o1 = f(q, k, v, jnp.int32(7))
        o2 = f(q, k, v, jnp.int32(7))
        o3 = f(q, k, v, jnp.int32(8))
        np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
        assert np.abs(np.asarray(o1, np.float32)
                      - np.asarray(o3, np.float32)).max() > 1e-4
        g = jax.jit(jax.grad(lambda a, b_, c: jnp.sum(
            flash.dropout(a, b_, c, jnp.int32(7), True, sc
                          ).astype(f32) ** 2)))(q, k, v)
        assert np.isfinite(np.asarray(g, np.float32)).all()
    cases["flash_native_dropout_fwd_bwd"] = dropout_case

    # ---- paged decode ---------------------------------------------------
    def paged_case(h, h_kv):
        def run():
            b, d, p, n_pages, max_pages = 4, 128, 16, 64, 8
            q = jnp.asarray(rng.randn(b, h, d) * 0.3, jnp.bfloat16)
            kp = jnp.asarray(rng.randn(n_pages, p, h_kv, d) * 0.3,
                             jnp.bfloat16)
            vp = jnp.asarray(rng.randn(n_pages, p, h_kv, d) * 0.3,
                             jnp.bfloat16)
            table = jnp.asarray(
                rng.permutation(n_pages)[:b * max_pages].reshape(
                    b, max_pages), jnp.int32)
            lens = jnp.asarray([120, 77, 33, 128], jnp.int32)
            out = jax.jit(lambda *a: paged_attention(
                *a, interpret=interpret))(q, kp, vp, table, lens)
            ref = paged_attention_reference(q, kp, vp, table, lens)
            check(out, ref, 5e-2)
        return run
    cases["paged_attention_decode"] = paged_case(8, 8)
    cases["paged_attention_gqa_native_cache"] = paged_case(32, 4)

    def paged_dense_case():
        b, L, h, d = 2, 256, 8, 128
        q = jnp.asarray(rng.randn(b, h, d) * 0.3, jnp.bfloat16)
        kc = jnp.asarray(rng.randn(b, L, h, d) * 0.3, jnp.bfloat16)
        vc = jnp.asarray(rng.randn(b, L, h, d) * 0.3, jnp.bfloat16)
        out = jax.jit(lambda *a: paged_attention_dense(
            *a, 97, interpret=interpret))(q, kc, vc)
        lg = jnp.einsum("bhd,bkhd->bhk", q.astype(f32),
                        kc.astype(f32))[..., :97] / np.sqrt(d)
        w = jax.nn.softmax(lg, -1)
        ref = jnp.einsum("bhk,bkhd->bhd", w, vc.astype(f32)[:, :97])
        check(out, ref, 5e-2)
    cases["fused_mha_decode_dense_cache"] = paged_dense_case

    # ---- int8 weight-only matmul ---------------------------------------
    def qmm_case():
        x = jnp.asarray(rng.randn(256, 512) * 0.3, jnp.bfloat16)
        w = jnp.asarray(rng.randn(512, 1024) * 0.3, f32)
        wq, sc = quantize_weights(w)
        out = jax.jit(lambda *a: quantized_matmul(
            *a, interpret=interpret))(x, wq, sc)
        ref = x.astype(f32) @ w
        rel = (np.abs(np.asarray(out, np.float32) - np.asarray(ref))
               / (np.abs(np.asarray(ref)) + 1.0)).max()
        # bound: per-column int8 quantization (max|w|/127 per element,
        # ~sqrt(K)-accumulated) + bf16 activations — measured ~0.064 at
        # K=512 on random normals; 0.1 flags real lowering bugs only
        assert rel < 0.1, f"int8 matmul rel err {rel}"
    cases["quantized_matmul_int8"] = qmm_case

    # ---- rms_norm -------------------------------------------------------
    def rms_case():
        x = jnp.asarray(rng.randn(512, 1024), f32)
        w = jnp.asarray(rng.randn(1024), f32)
        rms = make_rms_norm(interpret=interpret)
        out = jax.jit(lambda *a: rms(*a, 1e-6))(x, w)
        var = np.mean(np.asarray(x) ** 2, -1, keepdims=True)
        ref = np.asarray(x) / np.sqrt(var + 1e-6) * np.asarray(w)
        check(out, ref, 1e-3)
        g = jax.jit(jax.grad(lambda a, b_: jnp.sum(rms(a, b_, 1e-6) ** 2),
                             argnums=(0, 1)))(x, w)
        assert np.isfinite(np.asarray(g[0])).all()
    cases["rms_norm_fwd_bwd"] = rms_case

    # ---- the serving kernels at the serving geometries -----------------
    def pool(w, nh_kv, hd, dtype, L=None):
        """A [n_pages, p, h_kv, d] pool (or [L, ...] stacked) with each
        slot's pages a random permutation, plus the table."""
        n_pages = w * MAX_PAGES
        shape = (n_pages, page, nh_kv, hd)
        if L is not None:
            shape = (L,) + shape
        kp = jnp.asarray(rng.randn(*shape) * 0.5, dtype)
        vp = jnp.asarray(rng.randn(*shape) * 0.5, dtype)
        table = jnp.asarray(rng.permutation(n_pages).reshape(w, MAX_PAGES),
                            jnp.int32)
        return kp, vp, table

    def slot_lens(w, room):
        """Per-slot committed lengths straddling page boundaries, each
        leaving `room` positions free below max_len."""
        top = MAX_PAGES * page - room
        base = [page - 1, page, 3 * page + 5, top, 1, 2 * page - 3,
                5 * page, page + 1]
        return np.minimum(np.asarray(base[:w], np.int32), top)

    def ragged_case(g, w, tq, verify):
        def run():
            nh, hd = g["nh"], g["hd"]
            kp, vp, table = pool(w, nh, hd, jnp.bfloat16)
            q = jnp.asarray(rng.randn(w, tq, nh, hd) * 0.3, jnp.bfloat16)
            starts = jnp.asarray(slot_lens(w, tq))
            if verify:
                out = jax.jit(lambda *a: spec_verify_attention(
                    *a, interpret=interpret))(q, kp, vp, table, starts)
            else:
                out = jax.jit(lambda *a: ragged_paged_attention(
                    *a, interpret=interpret))(q, kp, vp, table,
                                              starts + tq, starts)
            ref = ragged_paged_attention_reference(
                q, kp, vp, np.asarray(table), np.asarray(starts) + tq,
                np.asarray(starts))
            return close("attn", out, ref)
        return run

    def paged_geom_case(g, w):
        """The decode kernel at the serving pool's geometry (what the
        megakernel-off engine runs every step)."""
        def run():
            nh, hd = g["nh"], g["hd"]
            kp, vp, table = pool(w, nh, hd, jnp.bfloat16)
            q = jnp.asarray(rng.randn(w, nh, hd) * 0.3, jnp.bfloat16)
            lens = jnp.asarray(slot_lens(w, 1)) + 1
            out = jax.jit(lambda *a: paged_attention(
                *a, interpret=interpret))(q, kp, vp, table, lens)
            return close("attn", out, paged_attention_reference(
                q, kp, vp, np.asarray(table), np.asarray(lens)))
        return run

    def qmm_geom_case(m, k, n):
        """int8 matmul at engine shapes: m = slot width (decode) or the
        prefill chunk, [k, n] a projection of the geometry."""
        def run():
            x = jnp.asarray(rng.randn(m, k), jnp.bfloat16)
            wt = quantize_weights(jnp.asarray(
                rng.randn(k, n) / np.sqrt(k), f32))
            out = jax.jit(lambda *a: quantized_matmul(
                *a, interpret=interpret))(x, *wt)
            return close("out", out, ref_mm(x, wt, jnp.bfloat16))
        return run

    for gname, g in geoms.items():
        for w in (1, 8):
            cases[f"paged_attention_{gname}_page{page}_w{w}"] = \
                paged_geom_case(g, w)
        for m in (1, 8, page):
            cases[f"quantized_matmul_{gname}_gate_m{m}"] = qmm_geom_case(
                m, g["H"], g["ffn"])
            cases[f"quantized_matmul_{gname}_down_m{m}"] = qmm_geom_case(
                m, g["ffn"], g["H"])
        for w in (1, 2, 4, 8):
            cases[f"ragged_paged_attention_{gname}_chunk{page}_w{w}"] = \
                ragged_case(g, w, page, False)
            cases[f"spec_verify_attention_{gname}_T4_w{w}"] = \
                ragged_case(g, w, 4, True)

    def chunk_case(nh, nh_kv, chunk, start, window):
        """The K=1 prefill's chunk attention of ONE sequence through its
        page table (what a non-plain description with lane-aligned heads
        runs a chunk), grouped queries nh : nh_kv, against the ragged
        kernel's XLA reference at one slot."""
        def run():
            kp, vp, table = pool(1, nh_kv, 128, jnp.bfloat16)
            q = jnp.asarray(rng.randn(chunk, nh, 128) * 0.3, jnp.bfloat16)
            end = start + chunk - 3         # three padded rows
            out = jax.jit(lambda *a: paged_chunk_attention(
                *a, window=window, interpret=interpret))(
                    q, kp, vp, table[0], jnp.int32(start), jnp.int32(end))
            ref = ragged_paged_attention_reference(
                q[None], kp, vp, np.asarray(table), [start + chunk],
                [start], window=window)[0]
            return close("attn", out[:chunk - 3], ref[:chunk - 3])
        return run

    # 128 query heads over 8 KV heads x 128 (the parallel-block cell), a
    # chunk deep in the context and one at its start, full and windowed
    cq, ck = (8, 2) if interpret else (128, 8)
    for start in (0, 3 * page + 5):
        for window in (None, 2 * page):
            cases[f"paged_chunk_attention_{cq}q{ck}kv_chunk{2 * page}"
                  f"_start{start}_w{window}"] = chunk_case(
                      cq, ck, 2 * page, start, window)

    def make_layer(g, kind, nh_l=None, ffn_l=None):
        """One decoder layer's weights, unit-variance activations:
        kind 'bf16' / 'f32' dense, 'int8' (values, scales) pairs.
        nh_l / ffn_l give a tensor-parallel shard's local q/k/v/gate/up
        widths (o and down stay full, as in exact mode)."""
        H, hd = g["H"], g["hd"]
        nq = (nh_l or g["nh"]) * hd
        fl = ffn_l or g["ffn"]
        dt = {"bf16": jnp.bfloat16, "f32": f32, "int8": f32}[kind]

        def w(k, n):
            a = jnp.asarray(rng.randn(k, n) / np.sqrt(k), dt)
            return quantize_weights(a) if kind == "int8" else a
        return dict(ln1=jnp.asarray(1 + 0.1 * rng.randn(H), dt),
                    ln2=jnp.asarray(1 + 0.1 * rng.randn(H), dt),
                    wq=w(H, nq), wk=w(H, nq), wv=w(H, nq),
                    wo=w(g["nh"] * hd, H), wg=w(H, fl), wu=w(H, fl),
                    wd=w(g["ffn"], H))

    eps = 1e-6

    def rope_rows(pos, hd, cd):
        inv = 1.0 / (10000.0 ** (np.arange(0, hd, 2) / hd))
        ang = np.asarray(pos, np.float64)[:, None] * inv[None]
        return (jnp.asarray(np.cos(ang), cd), jnp.asarray(np.sin(ang), cd))

    def ref_mm(x, wt, cd):
        vals, sc = wt if isinstance(wt, tuple) else (wt, None)
        acc = jnp.dot(x.astype(f32), vals.astype(f32),
                      precision=jax.lax.Precision.HIGHEST)
        if sc is not None:
            acc = acc * sc[None, :]
        return acc.astype(cd)

    def ref_rms(x, wrow, cd):
        x32 = x.astype(f32)
        var = jnp.mean(x32 * x32, -1, keepdims=True)
        return (x32 * jax.lax.rsqrt(var + eps)).astype(cd) * wrow.astype(cd)

    def ref_qkv(h, ws, cos, sin, hd):
        """norm1 + q/k/v projections + rope, casting where the engine's
        op chain casts. Returns q/k/v as [R, heads, hd]."""
        cd = h.dtype
        x = ref_rms(h, ws["ln1"], cd)
        q, k, v = (ref_mm(x, ws[n], cd).reshape(h.shape[0], -1, hd)
                   for n in ("wq", "wk", "wv"))
        c, s = cos[:, None, :], sin[:, None, :]

        def rope(t):
            t1, t2 = t[..., :hd // 2], t[..., hd // 2:]
            return jnp.concatenate([t1 * c - t2 * s, t2 * c + t1 * s], -1)
        return rope(q), rope(k), v

    def ref_attn(q, k, v, kp, vp, table, lens, T):
        """Row (b, j) attends slot b's pages with the T feed tokens'
        k/v written at positions lens[b] + [0, T), causally."""
        cd = q.dtype
        hd = q.shape[-1]
        table, lens = np.asarray(table), np.asarray(lens)
        outs = []
        for b in range(table.shape[0]):
            ks = kp[table[b]].reshape(-1, *kp.shape[2:]).astype(f32)
            vs = vp[table[b]].reshape(-1, *vp.shape[2:]).astype(f32)
            sl = slice(b * T, (b + 1) * T)
            ks = ks.at[lens[b]:lens[b] + T].set(k[sl].astype(f32))
            vs = vs.at[lens[b]:lens[b] + T].set(v[sl].astype(f32))
            lg = jnp.einsum("qhd,khd->hqk", q[sl].astype(f32), ks,
                            precision=jax.lax.Precision.HIGHEST) \
                / np.sqrt(hd)
            kpos = np.arange(ks.shape[0])[None, None, :]
            qpos = (lens[b] + np.arange(T))[None, :, None]
            lg = jnp.where(kpos <= qpos, lg, -1e30)
            wts = jax.nn.softmax(lg, -1)
            outs.append(jnp.einsum(
                "hqk,khd->qhd", wts, vs,
                precision=jax.lax.Precision.HIGHEST).astype(cd))
        return jnp.concatenate(outs, 0)               # [R, nh, hd]

    def ref_tail(h, ws, attn_flat):
        cd = h.dtype
        h1 = h + ref_mm(attn_flat, ws["wo"], cd)
        x = ref_rms(h1, ws["ln2"], cd)
        gate = ref_mm(x, ws["wg"], cd)
        up = ref_mm(x, ws["wu"], cd)
        act = jax.nn.silu(gate.astype(f32)).astype(cd) * up
        return h1, act

    def ref_layer(h, ws, kp, vp, table, lens, cos, sin, hd, T):
        q, k, v = ref_qkv(h, ws, cos, sin, hd)
        attn = ref_attn(q, k, v, kp, vp, table, lens, T)
        h1, act = ref_tail(h, ws, attn.reshape(h.shape[0], -1))
        h2 = h1 + ref_mm(act, ws["wd"], h.dtype)
        flat = (h.shape[0], -1)
        return h2, k.reshape(flat), v.reshape(flat)

    def mk_inputs(g, w, T, cd, L=None):
        kp, vp, table = pool(w, g["nh"], g["hd"], cd, L=L)
        lens = slot_lens(w, T)
        R = w * T
        h = jnp.asarray(rng.randn(R, g["H"]), cd)
        pos = np.repeat(lens, T) + np.tile(np.arange(T), w)
        cos, sin = rope_rows(pos, g["hd"], cd)
        # the last slot of the widest bucket rides INACTIVE, as in any
        # engine step whose bucket is wider than its live requests
        active = np.ones(w, np.int32)
        if w == 8:
            active[-1] = 0
        live = np.repeat(active, T).astype(bool)
        return h, kp, vp, table, jnp.asarray(lens), cos, sin, active, live

    def mk_kw(g, nh=None):
        n = nh or g["nh"]
        return dict(nh=n, nh_kv=n, hd=g["hd"], eps=eps, interpret=interpret)

    def mk_layer_case(g, kind, w, T=1):
        def run():
            cd = f32 if kind == "f32" else jnp.bfloat16
            ws = make_layer(g, kind)
            h, kp, vp, table, lens, cos, sin, active, live = mk_inputs(
                g, w, T, cd)
            pack = pack_decode_layer(ws, cdtype=cd)
            fn = jax.jit(lambda h_, pk, kp_, vp_: decode_megakernel(
                h_, pk, kp_, vp_, table, lens, jnp.asarray(active), cos,
                sin, tq=T, **mk_kw(g)))
            ho, kn, vn = fn(h, pack, kp, vp)
            rh, rk, rv = ref_layer(h, ws, kp, vp, table, lens, cos, sin,
                                   g["hd"], T)
            errs = [close("h", ho[live], rh[live]),
                    close("k", kn[live], rk[live]),
                    close("v", vn[live], rv[live])]
            return max(errs)
        return run

    def mk_multi_case(g, kind, w, K):
        def run():
            cd = jnp.bfloat16
            L = 2
            layers = [make_layer(g, kind) for _ in range(L)]
            h, kp, vp, table, lens, cos, sin, active, live = mk_inputs(
                g, w, 1, cd, L=L)
            V = g["V"]
            head = jnp.asarray(rng.randn(g["H"], V) / np.sqrt(g["H"]),
                               f32)
            head = quantize_weights(head) if kind == "int8" \
                else head.astype(cd)
            nf = jnp.asarray(1 + 0.1 * rng.randn(g["H"]), cd)
            pack = stack_packed([pack_decode_layer(ws, cdtype=cd)
                                 for ws in layers])
            hpack = pack_lm_head(head, nf, cdtype=cd)
            fn = jax.jit(lambda h_, pk, hp, kp_, vp_: decode_megakernel(
                h_, pk, kp_, vp_, table, lens, jnp.asarray(active), cos,
                sin, head=hp, head_v=V, head_k=K if K > 1 else None,
                **mk_kw(g)))
            out = fn(h, pack, hpack, kp, vp)
            rh = h
            for li, ws in enumerate(layers):
                rh, _, _ = ref_layer(rh, ws, kp[li], vp[li], table, lens,
                                     cos, sin, g["hd"], 1)
            rlog = np.asarray(ref_mm(ref_rms(rh, nf, cd), head, cd),
                              np.float32)[live]
            err = close("h", out[0][live], rh[live])
            # selection is compared by VALUE under the reference's
            # logits: a near-tie may pick another id, a wrong id cannot
            # sit within tolerance of the reference's top-K values
            tol = REL_TOL * np.abs(rlog).max()
            top = -np.sort(-rlog, axis=1)[:, :K]
            ids = np.asarray(out[3])[live].reshape(-1, K)
            got = np.take_along_axis(rlog, ids, axis=1)
            assert np.abs(got - top).max() < tol, \
                f"top-{K} values off by {np.abs(got - top).max():.3e}"
            if K == 1:
                err = max(err, close("logits", np.asarray(out[5])[live],
                                     rlog))
            return err
        return run

    def mk_seg_case(g, seg, w):
        """The tp=2 shard's view: local q/k/v/gate/up widths, full o and
        down — each segment against the same slice of the reference."""
        def run():
            cd = jnp.bfloat16
            nh_l, ffn_l = g["nh"] // 2, g["ffn"] // 2
            gl = dict(g, nh=nh_l)
            ws = make_layer(g, "int8", nh_l=nh_l, ffn_l=ffn_l)
            h, kp, vp, table, lens, cos, sin, active, live = mk_inputs(
                gl, w, 1, cd)
            pack = pack_decode_layer(ws, cdtype=cd)
            kw = mk_kw(g, nh=nh_l)
            R = h.shape[0]
            if seg == "qkv":
                fn = jax.jit(lambda h_, pk, kp_, vp_: decode_megakernel(
                    h_, pk, kp_, vp_, table, lens, jnp.asarray(active),
                    cos, sin, seg="qkv", **kw))
                attn, kn, vn = fn(h, pack, kp, vp)
                q, k, v = ref_qkv(h, ws, cos, sin, g["hd"])
                ra = ref_attn(q, k, v, kp, vp, table, lens, 1)
                return max(close("attn", attn[live],
                                 ra.reshape(R, -1)[live]),
                           close("k", kn[live], k.reshape(R, -1)[live]))
            if seg == "tail":
                attn_f = jnp.asarray(rng.randn(R, g["nh"] * g["hd"]), cd)
                fn = jax.jit(lambda h_, pk, a_: decode_megakernel(
                    h_, pk, seg="tail", attn_in=a_, mlp_v=ffn_l, **kw))
                h1, act = fn(h, pack, attn_f)
                r1, ract = ref_tail(h, ws, attn_f)
                return max(close("h", h1, r1), close("act", act, ract))
            act_f = jnp.asarray(rng.randn(R, g["ffn"]) * 0.5, cd)
            fn = jax.jit(lambda h_, pk, a_: decode_megakernel(
                h_, pk, seg="down", act_in=a_, **kw))
            return close("h", fn(h, pack, act_f),
                         h + ref_mm(act_f, ws["wd"], cd))
        return run

    for gname, g in geoms.items():
        for kind in ("int8", "bf16"):
            for w in (1, 2, 4, 8):
                cases[f"megakernel_layer_{gname}_{kind}_w{w}"] = \
                    mk_layer_case(g, kind, w)
        for w in (1, 8):
            cases[f"megakernel_layer_{gname}_f32_w{w}"] = \
                mk_layer_case(g, "f32", w)
        for K in (1, 8):
            cases[f"megakernel_multi_head_{gname}_int8_K{K}_w8"] = \
                mk_multi_case(g, "int8", 8, K)
    g7 = geoms.get("7b", TINY)
    cases["megakernel_multi_head_bf16_K1_w4"] = mk_multi_case(
        g7, "bf16", 4, 1)
    for w in (2, 8):
        cases[f"megakernel_layer_int8_tq4_w{w}"] = mk_layer_case(
            g7, "int8", w, T=4)
    for seg in ("qkv", "tail", "down"):
        cases[f"megakernel_seg_{seg}_tp2_int8_w8"] = mk_seg_case(
            g7, seg, 8)
    return cases


def _append(rec):
    with open(LOG, "a") as f:
        f.write(json.dumps(rec) + "\n")


def _read_log():
    if not os.path.exists(LOG):
        return []
    with open(LOG) as f:
        return [json.loads(line) for line in f if line.strip()]


def _child(args):
    sys.path.insert(0, ROOT)
    from paddle_tpu.chip import (device_stamp, enable_compile_cache,
                                 require_tpu)
    enable_compile_cache()
    stamp = device_stamp() if args.interpret else require_tpu()
    _append({"stamp": stamp, "interpret": args.interpret})
    seen = {r["case"] for r in _read_log() if "case" in r}
    for name, fn in _cases(args.interpret).items():
        if name in seen or (args.only and not any(
                tok in name for tok in args.only.split(","))):
            continue
        _append({"case": name, "status": "START"})
        t0 = time.perf_counter()
        try:
            err = fn()
            rec = {"status": "PASS", "rel_err": err}
        except Exception as e:  # noqa: BLE001 — record, keep sweeping
            traceback.print_exc()
            rec = {"status": "FAIL", "error": f"{type(e).__name__}: {e}"}
        _append({"case": name, "seconds": time.perf_counter() - t0, **rec})
    _append({"complete": True})


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--interpret", action="store_true")
    ap.add_argument("--child", action="store_true")
    args = ap.parse_args()
    if args.child:
        return _child(args)

    os.makedirs(os.path.dirname(LOG), exist_ok=True)
    if os.path.exists(LOG):
        os.remove(LOG)
    cmd = [sys.executable, os.path.abspath(__file__), "--child"] \
        + (["--only", args.only] if args.only else []) \
        + (["--interpret"] if args.interpret else [])
    crashes = 0
    while True:
        try:
            rc = subprocess.run(cmd, timeout=CHILD_TIMEOUT_S).returncode
        except subprocess.TimeoutExpired:
            rc = "timeout"
        log = _read_log()
        if any(r.get("complete") for r in log):
            break
        ended = {r["case"] for r in log
                 if "case" in r and r["status"] != "START"}
        open_ = [r["case"] for r in log
                 if "case" in r and r["status"] == "START"
                 and r["case"] not in ended]
        if not open_:
            print(f"[kernel_sweep] child exited rc={rc} before its first "
                  "case", file=sys.stderr)
            sys.exit(1)
        crashes += 1
        _append({"case": open_[-1], "status": "FAIL",
                 "error": f"child died (rc={rc}) inside this case"})
        if crashes > 8:
            print("[kernel_sweep] too many child crashes", file=sys.stderr)
            sys.exit(1)

    log = _read_log()
    stamp = next(r["stamp"] for r in log if "stamp" in r)
    results = [r for r in log if "case" in r and r["status"] != "START"]
    width = max(len(r["case"]) for r in results)
    for r in results:
        err = r.get("rel_err")
        tail = (f"rel_err {err:.2e}" if isinstance(err, float)
                else r.get("error", ""))
        print(f"{r['case']:<{width}}  {r['status']}  "
              f"{r.get('seconds', 0.0):6.1f}s  {tail[:300]}")
    n_fail = sum(1 for r in results if r["status"] != "PASS")
    print(json.dumps({
        "kernel_sweep_cases": len(results), "failed": n_fail,
        "interpret": args.interpret, "device": stamp,
        "cases": {r["case"]: r["status"] for r in results},
    }))
    sys.exit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
