#!/usr/bin/env python3
"""What do the pieces of a per-head sparse layer cost at the sizes of
`serve-sparse-gqa-longctx` (16 slots x 128 pages of 128, rows of 1024 bf16,
index keys of 64, top 2048, 128 experts x 768 top 8, hidden 2048)?

  chip:  chiprun -- python3 docs/probes/sparse_gqa_primitives.py
  here:  JAX_PLATFORMS=cpu python3 docs/probes/sparse_gqa_primitives.py --tiny

A probe, run by hand before the cell's first run (PERF.md, PR 32: the
prediction was written from these numbers): no cell runs it, no test
imports it. Each line is the median of 20 calls of one jitted piece, ms.
The two decode transports the issue asks to choose between are here side
by side: the GATHER of the selected rows (`rows_gather`, then
`attend_selected`) and a WALK of every slot's pages under the selection's
mask (`walk_masked`, at the mean context `--ctx`).
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--ctx", type=int, default=7168)
    args = ap.parse_args(argv)
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.inference import latent
    from paddle_tpu.inference.description import AttentionSpec, IndexerSpec
    from paddle_tpu.ops import sparse_attention as sa
    from paddle_tpu.ops.moe import routed_experts

    if args.tiny:
        w, mp, p, hid, vocab, nexp, width, ctx = 4, 8, 16, 64, 512, 8, 32, 70
        a = AttentionSpec(4, 2, 16, 16, 16, 1e7, qk_norm=True,
                          indexer=IndexerSpec(4, 8, 8, 12))
    else:
        w, mp, p, hid, vocab, nexp, width = 16, 128, 128, 2048, 151936, \
            128, 768
        ctx = args.ctx
        a = AttentionSpec(32, 4, 128, 128, 128, 1e7, qk_norm=True,
                          indexer=IndexerSpec(16, 64, 64, 2048))
    ix, row = a.indexer, sa.kv_row_width(a)
    n_pages, top = w * mp, min(ix.top_k, mp * p)
    interpret = jax.default_backend() == "cpu"
    dt = jnp.float32 if interpret else jnp.bfloat16
    rng = np.random.default_rng(0)
    key = jax.random.key(0)

    def rnd(i, shape, dtype=dt):
        return jax.random.normal(jax.random.fold_in(key, i), shape, dtype)

    rows_pool = rnd(1, (n_pages, p, row))
    k_pool, v_pool = rnd(2, (n_pages, p, row // 2)), \
        rnd(3, (n_pages, p, row // 2))
    ix_pool = rnd(4, (n_pages, p, ix.dim))
    tab = jnp.asarray(rng.permutation(n_pages).reshape(w, mp), jnp.int32)
    lens = jnp.asarray(rng.integers(ctx // 2, min(2 * ctx, mp * p - 1), w),
                       jnp.int32)
    active = jnp.ones((w,), bool)
    q = rnd(5, (w, a.n_heads, a.qk_dim))
    q_i = rnd(6, (w, 1, ix.n_heads, ix.dim), jnp.float32)
    w_i = rnd(7, (w, 1, ix.n_heads), jnp.float32)
    scores = rnd(8, (w, mp * p), jnp.float32)
    visible = jnp.arange(mp * p)[None, :] <= lens[:, None]
    idx = jnp.asarray(np.stack([rng.permutation(int(n) + 1)[:top] if n + 1 >=
                                top else np.resize(np.arange(n + 1), top)
                                for n in np.asarray(lens)]), jnp.int32)
    sel = jnp.take_along_axis(tab, idx // p, axis=1) * p + idx % p
    valid = jnp.ones((w, top), bool)
    x = rnd(9, (w, hid), jnp.float32)
    router = rnd(10, (hid, nexp), jnp.float32)
    w_gu, w_d = rnd(11, (nexp, hid, 2 * width)), rnd(12, (nexp, width, hid))
    head = rnd(13, (hid, vocab))
    # prefill: one sequence of `ctx` tokens, its last chunk of 4 pages
    chunk = 4 * p
    tab1 = tab[0]
    pos = (ctx - chunk) + jnp.arange(chunk, dtype=jnp.int32)
    qp = rnd(14, (chunk, a.n_heads, a.qk_dim))
    q_ip = rnd(15, (chunk, ix.n_heads, ix.dim), jnp.float32)
    w_ip = rnd(16, (chunk, ix.n_heads), jnp.float32)
    hi_blk = -(-ctx // (latent.KEY_BLOCK_PAGES * p))

    n = -(-(int(np.max(np.asarray(lens))) + 1) // p)   # pages walked

    def walk_masked(rows_pool, tab, q, mask):
        """Every slot's pages up to the longest context, under a mask."""
        rows = rows_pool[tab[:, :n]].reshape(w, n * p, row)
        return sa.attend_selected(q, rows, mask[:, :n * p], a)

    def prefill_attend(rows_pool, ix_pool, tab1, qp, q_ip, w_ip):
        kb = latent.KEY_BLOCK_PAGES * p
        chosen = latent.prefill_selection(
            ix_pool, tab1, q_ip, w_ip, pos[:, None], hi_blk, ix, p)

        def block(j):
            pages, kpos = latent.block_pages(tab1, j, p)
            s = jax.lax.dynamic_slice(
                chosen, (jnp.zeros((), j.dtype), j * kb), (chunk, kb))
            return rows_pool[pages].reshape(kb, -1), \
                s & (kpos[None, :] <= pos[:, None])

        return sa.attend_kv_blocks(qp, block, 0, hi_blk, a)

    def select_only(ix_pool, tab1, q_ip, w_ip):
        return latent.prefill_selection(
            ix_pool, tab1, q_ip, w_ip, pos[:, None], hi_blk, ix, p)

    pieces = {
        "rows_gather_1024": (lambda pool, s: pool.reshape(-1, row)[s],
                             (rows_pool, sel)),
        "kv_gather_2x512": (lambda kp, vp, s: (
            kp.reshape(-1, row // 2)[s], vp.reshape(-1, row // 2)[s]),
            (k_pool, v_pool, sel)),
        "attend_selected": (lambda pool, s, q: sa.attend_selected(
            q, pool.reshape(-1, row)[s], valid, a), (rows_pool, sel, q)),
        "walk_masked": (walk_masked, (rows_pool, tab, q, visible)),
        "index_scan_select": (lambda ixp, t, qi, wi: latent.decode_selection(
            ixp, t, qi, wi, lens + 1, active, ix, p)[:2],
            (ix_pool, tab, q_i, w_i)),
        "top_k_list": (lambda s: sa.select_top(s, visible, ix.top_k),
                       (scores,)),
        "top_k_mask": (lambda s: sa.top_mask(
            jnp.where(visible, s, -jnp.inf), ix.top_k), (scores,)),
        "experts_16rows": (lambda x, r, g, d: routed_experts(
            x, r, None, g, d, (0, nexp), 8, interpret=interpret,
            score="softmax")[0], (x, router, w_gu, w_d)),
        "head": (lambda x, h: jnp.dot(x.astype(h.dtype), h,
                                      preferred_element_type=jnp.float32),
                 (x, head)),
        "prefill_select": (select_only, (ix_pool, tab1, q_ip, w_ip)),
        "prefill_select_attend": (prefill_attend, (rows_pool, ix_pool, tab1,
                                                   qp, q_ip, w_ip)),
    }
    xp = rnd(17, (chunk, hid), jnp.float32)
    pieces["experts_chunk_rows"] = (pieces["experts_16rows"][0],
                                    (xp, router, w_gu, w_d))
    out = {"device": jax.devices()[0].device_kind, "ctx": ctx, "slots": w}
    for name, (fn, fn_args) in pieces.items():
        f = jax.jit(fn)
        jax.block_until_ready(f(*fn_args))
        times = []
        for _ in range(3 if interpret else 20):
            t0 = time.perf_counter()
            jax.block_until_ready(f(*fn_args))
            times.append(time.perf_counter() - t0)
        out[name + "_ms"] = round(1e3 * float(np.median(times)), 4)
        print(name, out[name + "_ms"], flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "sparse_gqa_primitives.json"), "a") as f:
        f.write(json.dumps(out) + "\n")
    print(json.dumps(out))


if __name__ == "__main__":
    main()
