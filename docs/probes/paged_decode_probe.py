#!/usr/bin/env python3
"""What the paged decode attention kernel (`ops/pallas/paged_attention.
paged_attention`, `paged_attention_decode` in a trace) costs a LAYER CALL
at the geometries and length mixes of the two cells that serve per-head
paged KV on the op chain, and what each half of PR 37 gave:

  walk      "table": one grid step a (slot, table column), the index map
            fetching the column's page (the kernel before PR 37, kept in
            THIS file so one tree measures all variants);
            "live":  one grid step a slot, a loop over the slot's live
            pages with the kernel's own copies, two in flight (the tree's)
  operands  "float32": a float32 copy of the page, six MXU passes under
            the package-wide "highest" (per-head keys before PR 37);
            "pool":    the pool's bf16, one pass (`mxu_operands`)

Per-head keys ran (table, float32) before and run (live, pool) now; flat
192-wide keys have fed the MXU bf16 since PR 26, so they have the two
walks only.

  chip:  chiprun -- python3 docs/probes/paged_decode_probe.py
  here:  JAX_PLATFORMS=cpu python3 docs/probes/paged_decode_probe.py --aot

A probe, run by hand: no benchmark cell runs it, no test imports it.
`--aot` compiles every variant for a DESCRIBED v5e and runs nothing
(what Mosaic refuses here it refuses on the chip). On the chip each
variant is a scan of CALLS kernel calls in ONE jitted program (the
query of a call depends on the call before it), timed around
`block_until_ready`, best of REPS: ms a call, GB/s of live page bytes
(K and V of the pages a slot holds inside its window), and the largest
difference from the variant the parent ran. Contexts are drawn from the
cells' traffic parameters (`perf/traffic/*.json`) as a seat in mid-life
sees them: the prompt and a uniform share of the output. Lines go to
stdout as JSON and to chiprun_out/paged_decode_probe.json.
"""
import functools
import json
import math
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

import paddle_tpu  # noqa: E402,F401
from paddle_tpu import chip  # noqa: E402
from paddle_tpu.ops.pallas import paged_attention as pa  # noqa: E402

CALLS, REPS = 32, 5
bf16, f32 = jnp.bfloat16, jnp.float32
HIGHEST, DEFAULT = jax.lax.Precision.HIGHEST, jax.lax.Precision.DEFAULT
P = 128

# (name, slots, table columns, q heads, kv heads, key width, value width,
#  flat keys, window, sinks, pool pages, (prompt median, sigma, min, max),
#  (output median, sigma, min, max))
RAGDOC = ((4096, 0.7, 768, 13312), (1024, 0.5, 256, 3072))
MIXED = ((320, 1.2, 32, 3072), (384, 0.6, 64, 1024))
GEOMETRIES = [
    ("ragdoc.full", 48, 128, 128, 8, 128, 128, False, None, False,
     48 * 128) + RAGDOC,
    ("ragdoc.window4096", 48, 128, 128, 8, 128, 128, False, 4096, False,
     48 * 34 + 8) + RAGDOC,
    ("mixedlen.full", 128, 32, 64, 4, 192, 128, True, None, False,
     128 * 32) + MIXED,
    ("mixedlen.window128", 128, 32, 64, 8, 192, 128, True, 128, True,
     388) + MIXED,
]


def contexts(rng, n, prompt, output, most):
    def draw(median, sigma, lo, hi):
        return np.clip(np.exp(rng.normal(math.log(median), sigma, n)),
                       lo, hi)
    return np.minimum(draw(*prompt) + rng.uniform(0, 1, n) * draw(*output),
                      most).astype(np.int32)


def live_range(ctx, window):
    first = 0 if window is None else max(ctx - window, 0) // P
    return first, -(-ctx // P)


# ---- the walk before PR 37 (paged_attention.py at PR 36), operands by
# ---- argument: one grid step a table column, pages by the index map
def _table_kernel(tbl_ref, len_ref, act_ref, q_ref, k_ref, v_ref, *rest, p,
                  n_grid, scale, rep, window, has_sink, k_flat, pool_ops):
    sink_ref = rest[0] if has_sink else None
    o_ref, m_scr, l_scr, acc_scr = rest[-4:]
    b, pi = pl.program_id(0), pl.program_id(1)

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, pa.NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    seq_len = len_ref[b]
    if window is None:
        page_start = pi * p
    else:
        page_start = (pa.window_first_page(seq_len - window, p) + pi) * p
    run = jnp.logical_and(act_ref[b] > 0, page_start < seq_len)
    mxu, prec = (bf16, DEFAULT) if pool_ops else (f32, HIGHEST)

    def dot(a, b_, contract):
        return jax.lax.dot_general(
            a.astype(mxu), b_.astype(mxu), (contract, ((), ())),
            precision=prec, preferred_element_type=f32)

    @pl.when(run)
    def _compute():
        q = (q_ref[0].astype(f32) * f32(scale)).astype(mxu)
        k, v = k_ref[0], v_ref[0]
        n_kv = v.shape[1]
        if k_flat:
            logits = dot(q, k, ((1,), (1,)))
        else:
            k = k.astype(mxu)
            logits = jnp.concatenate([
                dot(q[g * rep:(g + 1) * rep], k[:, g, :], ((1,), (1,)))
                for g in range(n_kv)], axis=0)
        pos = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1) \
            + page_start
        ok = pos < seq_len
        if window is not None:
            ok = jnp.logical_and(ok, pos >= seq_len - window)
        logits = jnp.where(ok, logits, f32(pa.NEG_INF))
        m_prev, l_prev = m_scr[:, :1], l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        w = jnp.exp(logits - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = jnp.broadcast_to(
            alpha * l_prev + jnp.sum(w, axis=-1, keepdims=True), l_scr.shape)
        v = v.astype(mxu)
        acc_scr[...] = alpha * acc_scr[...] + jnp.concatenate([
            dot(w[g * rep:(g + 1) * rep], v[:, g, :], ((1,), (0,)))
            for g in range(n_kv)], axis=0)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(pi == n_grid - 1)
    def _emit():
        acc, l_fin = acc_scr[...], l_scr[:, :1]
        if has_sink:
            acc, l_fin = pa._sink_finish(m_scr[:, :1], l_fin, acc,
                                         sink_ref[:, :1])
        o_ref[0] = (acc / jnp.maximum(l_fin, f32(1e-30))).astype(o_ref.dtype)


def table_walk(q, k_pages, v_pages, page_table, seq_lens, active, window,
               sinks, k_flat, pool_ops):
    b, h, d = q.shape
    n_pages, p, h_kv, dv = v_pages.shape
    rep, max_pages = h // h_kv, page_table.shape[1]
    n_grid = max_pages if window is None else \
        min(max_pages, -(-int(window) // p) + 1)
    table = jnp.clip(page_table.astype(jnp.int32), 0, n_pages - 1)

    def page_of(bb, pi, tbl, ln, ac):
        if window is not None:
            pi = jnp.minimum(pa.window_first_page(ln[bb] - window, p) + pi,
                             max_pages - 1)
        return (tbl[bb, pi] * ac[bb], 0, 0, 0)

    if k_flat:
        onehot = (jnp.arange(h)[:, None] // rep
                  == jnp.arange(h_kv)[None, :]).astype(q.dtype)
        q = (q[:, :, None, :] * onehot[None, :, :, None]).reshape(
            b, h, h_kv * d)
        k_block, k_map = (1, p, h_kv * d), lambda *a: page_of(*a)[:3]
    else:
        k_block, k_map = (1, p, h_kv, d), page_of
    in_specs = [pl.BlockSpec((1,) + q.shape[1:],
                             lambda bb, pi, tbl, ln, ac: (bb, 0, 0)),
                pl.BlockSpec(k_block, k_map),
                pl.BlockSpec((1, p, h_kv, dv), page_of)]
    args = [table, seq_lens, active, q, k_pages, v_pages]
    if sinks is not None:
        in_specs.append(pl.BlockSpec(
            (h, 128), lambda bb, pi, tbl, ln, ac: (0, 0)))
        args.append(pa._sink_rows(sinks, h))
    limit = pa.vmem_limit(
        blocks=[(q.shape[1:], q.dtype), ((h, dv), q.dtype),
                ((p, h_kv, d), k_pages.dtype),
                ((p, h_kv, dv), v_pages.dtype)],
        scratch=[((h, 128), f32)] * 2 + [((h, dv), f32)],
        temps=[((p, h_kv, d), f32), ((p, h_kv, dv), f32)])
    with jax.enable_x64(False):
        return pl.pallas_call(
            functools.partial(
                _table_kernel, p=p, n_grid=n_grid, scale=1 / math.sqrt(d),
                rep=rep, window=window, has_sink=sinks is not None,
                k_flat=k_flat, pool_ops=pool_ops),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=(b, n_grid), in_specs=in_specs,
                out_specs=pl.BlockSpec(
                    (1, h, dv), lambda bb, pi, tbl, ln, ac: (bb, 0, 0)),
                scratch_shapes=[pltpu.VMEM((h, 128), f32),
                                pltpu.VMEM((h, 128), f32),
                                pltpu.VMEM((h, dv), f32)]),
            out_shape=jax.ShapeDtypeStruct((b, h, dv), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=limit),
            name="paged_attention_decode_table_walk")(*args)


def live_walk(q, k_pages, v_pages, page_table, seq_lens, active, window,
              sinks, k_flat, pool_ops):
    # the tree's kernel; "float32" operands by swapping the module's rule
    # while the call is traced
    rule = pa.mxu_operands
    if not pool_ops:
        pa.mxu_operands = lambda dtype: (f32, HIGHEST)
    try:
        return pa.paged_attention(q, k_pages, v_pages, page_table, seq_lens,
                                  active=active, window=window, sinks=sinks,
                                  k_flat=k_flat)
    finally:
        pa.mxu_operands = rule


def main():
    aot = "--aot" in sys.argv
    if aot:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        one = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
    else:
        chip.require_tpu()
    rng = np.random.default_rng(37)
    key = jax.random.PRNGKey(37)
    recs = []
    for (name, b, cols, h, h_kv, d, dv, k_flat, window, sink, n_pages,
         prompt, output) in GEOMETRIES:
        ctx = contexts(rng, b, prompt, output, cols * P)
        ranges = [live_range(int(c), window) for c in ctx]
        live = sum(hi - lo for lo, hi in ranges)
        assert live <= n_pages, (name, live, n_pages)
        # live columns hold distinct pages scattered over the pool, every
        # other column an id OUTSIDE it (the live walk never reads those;
        # the table walk clips them, as the parent did)
        table = np.full((b, cols), n_pages + 7, np.int32)
        ids = iter(rng.permutation(n_pages)[:live])
        for s, (lo, hi) in enumerate(ranges):
            table[s, lo:hi] = [next(ids) for _ in range(lo, hi)]
        k_shape = (n_pages, P, h_kv * d) if k_flat else (n_pages, P, h_kv, d)
        shapes = dict(q=((b, h, d), bf16), k=(k_shape, bf16),
                      v=((n_pages, P, h_kv, dv), bf16))
        page_bytes = 2 * P * h_kv * (d + dv)
        token_bytes = 2 * h_kv * (d + dv) * int(sum(
            c if window is None else min(c, window) for c in ctx))
        variants = [("table", True), ("live", True)] if k_flat else \
            [("table", False), ("table", True), ("live", False),
             ("live", True)]
        outs = {}
        for walk, pool_ops in variants:
            fn = table_walk if walk == "table" else live_walk

            @jax.jit
            def f(q, k, v, tbl, lens, act, sinks):
                def body(q, _):
                    o = fn(q, k, v, tbl, lens, act, window,
                           sinks if sink else None, k_flat, pool_ops)
                    # the next call's query depends on this call's output
                    return q + (jnp.sum(o) * 0).astype(q.dtype), o
                q, os_ = jax.lax.scan(body, q, None, length=CALLS)
                return os_[0]

            rec = {"geometry": name, "walk": walk,
                   "operands": "pool" if pool_ops else "float32",
                   "slots": b, "table_columns": cols,
                   "grid_steps": b * (1 if walk == "live" else (
                       cols if window is None
                       else min(cols, -(-window // P) + 1))),
                   "live_pages": live, "live_page_mb": live * page_bytes / 1e6,
                   "walk_overhead": live * page_bytes / token_bytes}
            try:
                if aot:
                    sds = lambda s, t: jax.ShapeDtypeStruct(  # noqa: E731
                        s, t, sharding=one)
                    f.lower(sds(*shapes["q"]), sds(*shapes["k"]),
                            sds(*shapes["v"]), sds((b, cols), jnp.int32),
                            sds((b,), jnp.int32), sds((b,), jnp.int32),
                            sds((h,), f32)).compile()
                    rec["aot"] = "ok"
                else:
                    if "args" not in outs:
                        kq, kk, kv = jax.random.split(
                            jax.random.fold_in(key, len(recs)), 3)
                        outs["args"] = (
                            jax.random.normal(kq, *shapes["q"]),
                            jax.random.normal(kk, *shapes["k"]),
                            jax.random.normal(kv, *shapes["v"]),
                            jnp.asarray(table), jnp.asarray(ctx),
                            jnp.ones((b,), jnp.int32),
                            jnp.linspace(-1.0, 1.0, h, dtype=f32))
                    out = jax.block_until_ready(f(*outs["args"]))
                    ts = []
                    for _ in range(REPS):
                        t = time.perf_counter()
                        jax.block_until_ready(f(*outs["args"]))
                        ts.append((time.perf_counter() - t) / CALLS * 1e3)
                    out = np.asarray(out.astype(f32))
                    base = outs.setdefault("parent", out)
                    rec.update(
                        ms_a_call=min(ts), ms_a_call_all=ts,
                        gb_s_live_pages=live * page_bytes / min(ts) / 1e6,
                        max_abs_diff_from_parent=float(
                            np.max(np.abs(out - base))),
                        bits_equal_parent=bool((out == base).all()))
            except Exception as e:   # a variant Mosaic refuses is a finding
                rec["error"] = repr(e)[:800]
            print(json.dumps(rec), flush=True)
            recs.append(rec)
    if not aot:
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/paged_decode_probe.json", "w") as fh:
            json.dump(recs, fh, indent=1)


if __name__ == "__main__":
    main()
