#!/usr/bin/env python3
"""What one latent layer's chunk attention costs, the XLA key blocks
(`latent_attention.attend_key_blocks` over `latent.key_blocks`, what the
engine ran before `paged_latent_chunk_attention`) against the Pallas
kernel at several geometries (queries a block x pages a grid step), at
the two layer geometries of `serve-mla-sparse-longdoc`:

  full      128 heads, rows 640 (576 stored padded), values the leading
            512, pages of 128, 144 table columns, a chunk of 512 whose
            queries each keep the top 2,048 of the keys they see (a
            random selection through `sparse_attention.top_mask`), the
            chunk ENDING at a context of 1,024, 4,096 and 12,288
  window    64 heads, rows 1,152, values the leading 1,024, a window of
            513 (the walk does not depend on the context past it)

  on a TPU host:  python3 docs/probes/latent_chunk_probe.py
  without one:    JAX_PLATFORMS=cpu python3 docs/probes/latent_chunk_probe.py --aot

A probe, run by hand: no benchmark cell runs it, no test imports it.
`--aot` compiles every variant for a DESCRIBED v5e and runs nothing (what
Mosaic refuses here it refuses on the chip). On the chip each variant is
a scan of CALLS calls in ONE jitted program (the queries of a call depend
on the call before), run once to warm up and once under the profiler;
the times are device 0's self time off that trace
(`perf/harness/trace_reduce`), never the host's clock: `ms` all of the
program's operations a call, `kernel_ms` the kernel's own, `us_a_live_
step` the kernel's time over the grid steps that compute
(`chunk_attention.latent_live_steps`), `tflops` the products the live
steps must do (2 x rows x keys x (row + kv_rank) over the keys each
query block's walk covers, masked or not) over `kernel_ms`, and
`rel_err` the kernel's distance from the key blocks (norm of the
difference over the norm, the real rows). One JSON line a variant and
context goes to stdout; the profiler writes under .perf_trace/.
"""
import collections
import glob
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..")
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "perf"))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import paddle_tpu  # noqa: E402,F401
from paddle_tpu import chip  # noqa: E402
from paddle_tpu.inference import latent  # noqa: E402
from paddle_tpu.ops import latent_attention as la  # noqa: E402
from paddle_tpu.ops import sparse_attention as sa  # noqa: E402
from paddle_tpu.ops.pallas import chunk_attention as ca  # noqa: E402

CALLS = 8
P, CHUNK, MP, TOP_K = 128, 512, 144, 2048
bf16 = jnp.bfloat16
# (name, heads, row, kv_rank, window, pool pages, contexts the chunk ends
#  at, (queries a block, pages a step) of the kernel)
GEOMETRIES = [
    ("full", 128, 640, 512, None, 64 * MP, (1024, 4096, 12288),
     [(8, 4), (16, 1), (16, 2), (16, 4), (32, 2)]),
    ("window513", 64, 1152, 1024, 513, 388, (4096,),
     [(8, 3), (16, 1), (16, 2), (16, 3), (16, 6), (32, 2), (32, 3)]),
]


def device_ms(trace_dir, calls):
    """(ms a call of all device-0 operations, ms a call by family) in the
    newest trace under trace_dir."""
    from harness import trace_reduce
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    devices, _ = trace_reduce.read_planes(path)
    ops = devices[min(devices)].get(trace_reduce.OPS_LINE, [])
    by = collections.Counter()
    for name, own in trace_reduce.self_times(ops):
        by[trace_reduce.op_family(name)] += own
    fams = {f: t * 1e3 / calls for f, t in by.items() if f != "while"}
    return sum(fams.values()), fams


def flops(q_start, t_end, tq, kp, window, heads, row, rank):
    """Products of the steps that compute: each covers kp pages for tq x
    heads rows, both products."""
    steps = ca.latent_live_steps(q_start, t_end, CHUNK, P, tq, kp, window)
    return steps, 2.0 * steps * tq * heads * kp * P * (row + rank)


def main():
    aot = "--aot" in sys.argv
    if aot:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        one = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
    else:
        chip.require_tpu()
    trace_dir = os.path.join(ROOT, ".perf_trace", "latent_chunk_probe")
    rng = np.random.default_rng(40)
    for name, heads, row, rank, window, n_pages, ctxs, geos in GEOMETRIES:
        masked = window is None
        width = latent.selection_width(MP, P)
        scale = float(1.0 / np.sqrt(192 if masked else 256))

        def kernel_call(q, rows, tab, sc, chosen, tq, kp):
            plan = {"tq": tq, "pages_per_step": kp, "vmem_limit_bytes":
                    ca.vmem_limit(*ca._latent_vmem(
                        tq, kp, heads, row, rank, P, bf16, masked))}
            return ca.paged_latent_chunk_attention(
                q, rows, tab, sc[0], sc[1], rank, scale, window=window,
                chosen=chosen, plan=plan)

        def blocks_call(q, rows, tab, sc, chosen):
            pos = sc[0] + jnp.arange(CHUNK, dtype=jnp.int32)
            block, lo, hi = latent.key_blocks(rows, tab, pos, sc[1], P,
                                              window, chosen)
            return la.attend_key_blocks(q, block, lo, hi, rank, scale)

        def program(fn):
            @jax.jit
            def f(q, rows, tab, sc, chosen):
                def body(q, _):
                    o = fn(q, rows, tab, sc, chosen)
                    # the next call's queries depend on this call's output
                    return q + (jnp.sum(o) * 0).astype(q.dtype), None
                q, _ = jax.lax.scan(body, q, None, length=CALLS)
                return fn(q, rows, tab, sc, chosen)
            return f

        # one program a variant, compiled once for every context
        variants = [("key_blocks", None, program(blocks_call))] + [
            (f"kernel_tq{tq}_kp{kp}", (tq, kp), program(
                (lambda tq, kp: lambda *a: kernel_call(*a, tq, kp))(tq, kp)))
            for tq, kp in geos]
        for ctx in (ctxs[:1] if aot else ctxs):
            q_start, t_end = ctx - CHUNK, ctx
            if not aot:
                k1, k2, k3 = jax.random.split(jax.random.PRNGKey(ctx), 3)
                rows = jax.random.normal(k1, (n_pages, P, row), bf16)
                q = (jax.random.normal(k2, (CHUNK, heads, row), bf16)
                     * 0.3).astype(bf16)
                held = -(-t_end // P)
                tab = np.full(MP, n_pages + 3, np.int32)
                tab[:held] = rng.permutation(n_pages)[:held]
                pos = q_start + jnp.arange(CHUNK)
                chosen = None
                if masked:
                    kpos = jnp.arange(width)
                    scores = jnp.where(
                        kpos[None, :] <= pos[:, None],
                        jax.random.normal(k3, (CHUNK, width), jnp.float32),
                        -jnp.inf)
                    chosen = sa.top_mask(scores, TOP_K)
                args = (q, rows, jnp.asarray(tab),
                        jnp.asarray([q_start, t_end], jnp.int32), chosen)
            ref = None
            for vname, geo, f in variants:
                rec = {"geometry": name, "context": ctx, "variant": vname}
                try:
                    if aot:
                        sds = lambda s, t: jax.ShapeDtypeStruct(  # noqa
                            s, t, sharding=one)
                        f.lower(sds((CHUNK, heads, row), bf16),
                                sds((n_pages, P, row), bf16),
                                sds((MP,), jnp.int32), sds((2,), jnp.int32),
                                sds((CHUNK, width), jnp.bool_) if masked
                                else None).compile()
                        rec["aot"] = "ok"
                    else:
                        out = jax.block_until_ready(f(*args))
                        jax.profiler.start_trace(trace_dir)
                        jax.block_until_ready(f(*args))
                        jax.profiler.stop_trace()
                        ms, fams = device_ms(trace_dir, CALLS + 1)
                        rec["ms"] = round(ms, 4)
                        rec["top"] = {k: round(v, 4) for k, v in sorted(
                            fams.items(), key=lambda kv: -kv[1])[:5]}
                        out = np.asarray(out, np.float32)[:t_end - q_start]
                        if ref is None:
                            ref = out
                        num = np.linalg.norm(out - ref)
                        rec["rel_err"] = float(num / np.linalg.norm(ref))
                        if geo is not None:
                            km = fams.get("paged_latent_chunk_attention", 0)
                            steps, fl = flops(q_start, t_end, *geo, window,
                                              heads, row, rank)
                            rec.update(
                                kernel_ms=round(km, 4), live_steps=steps,
                                grid_steps=(CHUNK // geo[0])
                                * ca.latent_walk_steps(P, *geo, window,
                                                       MP),
                                us_a_live_step=round(km * 1e3 / steps, 3),
                                tflops=round(fl / km / 1e9, 1))
                except Exception as e:  # a refused variant is a finding
                    rec["error"] = repr(e)[:800]
                print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
