#!/usr/bin/env python3
"""What ONE flash-attention call of the training cells costs by block
geometry: forward, fused backward and the XLA sum of the backward's dq
partials at (2, 4096, 16, 128) bf16 causal, no mask, no dropout (both
training cells run the kernel at this shape, 18 and 8 layers a step).

  chip:  chiprun -- python3 docs/probes/flash_train_probe.py
  here:  JAX_PLATFORMS=cpu python3 docs/probes/flash_train_probe.py --aot

A probe, run by hand: no benchmark cell runs it, no test imports it. Per
geometry (bq, bk) of {256, 512, 1024}^2 it builds two programs, CALLS
forward calls and CALLS backward calls in ONE jitted `fori_loop` each (a
call's output is the next call's q / dO, so nothing is hoisted), and
reports

  - `fwd_ms` / `bwd_ms`: host clock around `block_until_ready` of the
    whole loop, best of REPS, over CALLS (the backward's includes delta
    and the partial sum);
  - `trace_ms`: device-0 self time a call by operation family, read off a
    profiler trace of one more execution of each program
    (`perf/harness/trace_reduce`): `flash_attention_fwd`,
    `flash_attention_bwd`, and what XLA runs beside them (the partial
    sum is the `reduce` / `convert_reduce` family of the backward).

`err_vs_xla_f32` holds every geometry's o, dq, dk, dv (batch slice 0) to
XLA's attention in float32 on the same bf16 inputs: largest difference
over the largest reference element.
`--shape b,s,h,d` sweeps another shape (`512,1024,1,64` is the fallback
layout of (32, 1024, 16, 64): heads folded into the batch), `--fwd-only`
leaves the backward out (the serving prefill has none), `--geometries
512x1024,...` narrows the list, `--nb-max n` caps the batch slices a grid
step, `--rule` adds the geometry `flash_geometry` draws by itself. A
geometry the VMEM budget does not hold is fitted down, as a caller's
would be: the line reports what ran beside what was `asked`.
`--aot` compiles every geometry for a DESCRIBED v5e and runs nothing.
`--aot --classes` leaves the sweep and compiles, through the public
wrapper at the geometry `flash_geometry` draws by itself, forward and
backward of every CLASS of call the package can make of the kernels
(`CLASSES`: operand type, head dim, mask kind, dropout, padding), and
says for each what `_step_vmem_bytes` estimates beside what the compiler
needs: `need_mib` is the least `vmem_limit_bytes` (to 0.25 MiB, found by
bisection, `least_limit`) at which both kernels still compile. The
default limit is 16 MiB; `ok` says the class compiles under it. Plain
`--aot` says the same of each geometry of the sweep, a kernel at a time.
Lines go to stdout as JSON and to chiprun_out/flash_train_probe.json.
"""
import argparse
import collections
import glob
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..")
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "perf"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import paddle_tpu  # noqa: E402,F401
from paddle_tpu import chip  # noqa: E402
from paddle_tpu.ops.pallas import flash_attention as fa  # noqa: E402

CALLS, REPS = 16, 3
BLOCKS = (256, 512, 1024)
MIB = 2 ** 20

# name: (q shape [b, s, h, d], operand type, mask shape, causal, dropout)
CLASSES = {
    "train_cells": ((2, 4096, 16, 128), "bfloat16", None, True, 0.0),
    "noncausal": ((2, 4096, 16, 128), "bfloat16", None, False, 0.0),
    "float32": ((2, 4096, 16, 128), "float32", None, True, 0.0),
    "shared_mask": ((2, 4096, 16, 128), "bfloat16", (1, 1, 4096, 4096),
                    False, 0.0),
    "batched_mask": ((2, 4096, 16, 128), "bfloat16", (2, 1, 4096, 4096),
                     True, 0.0),
    "per_head_mask": ((2, 2048, 16, 128), "bfloat16", (2, 16, 2048, 2048),
                      False, 0.0),
    "per_head_mask_f32": ((2, 2048, 16, 128), "float32",
                          (2, 16, 2048, 2048), True, 0.0),
    "dropout": ((2, 4096, 16, 128), "bfloat16", None, True, 0.1),
    "dropout_f32": ((2, 2048, 16, 128), "float32", None, True, 0.1),
    "masked_dropout": ((2, 4096, 16, 128), "bfloat16", (2, 1, 4096, 4096),
                       True, 0.1),
    # the fallback layout: heads fold into the batch
    "d64": ((8, 2048, 16, 64), "bfloat16", None, True, 0.0),
    "d64_per_head_mask": ((2, 2048, 16, 64), "bfloat16",
                          (1, 16, 2048, 2048), False, 0.0),
    "d64_f32_dropout": ((8, 1024, 16, 64), "float32", None, True, 0.1),
    "d256": ((2, 4096, 8, 256), "bfloat16", None, True, 0.0),
    "uneven_1100": ((1, 1100, 32, 128), "bfloat16", None, True, 0.0),
    "big_batch_s256": ((64, 256, 16, 128), "bfloat16", None, True, 0.0),
    "prefill_2048": ((1, 2048, 32, 128), "bfloat16", None, True, 0.0),
}


def class_program(name):
    """(fn, argument structs, record) of one class: forward and gradients
    through the wrapper a caller uses, nothing chosen by hand."""
    shape, dtype, mask_shape, causal, dropout = CLASSES[name]
    b, s, h, d = shape
    dtype = jnp.dtype(dtype)
    flash = fa.make_flash_attention(dropout_p=dropout)
    x = jax.ShapeDtypeStruct(shape, dtype)
    args = [x, x, x]
    if mask_shape is not None:
        args.append(jax.ShapeDtypeStruct(mask_shape, jnp.float32))
    if dropout:
        args.append(jax.ShapeDtypeStruct((), jnp.int32))
    entry = {(False, False): flash, (True, False): flash.masked,
             (False, True): getattr(flash, "dropout", None),
             (True, True): getattr(flash, "masked_dropout", None)}[
                 mask_shape is not None, bool(dropout)]

    def fn(q, k, v, *rest):
        def loss(q_, k_, v_):
            return jnp.sum(entry(q_, k_, v_, *rest, causal, d ** -0.5)
                           .astype(jnp.float32))
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    bq, bk, nb, s_pad = fa.flash_geometry(shape, dtype, mask_shape,
                                          dropout=bool(dropout))
    fast = d % 128 == 0
    B, hk = (b, h) if fast else (b * h, 1)
    batched = mask_shape is not None and fa._mask_group(
        fa._mask_rows(mask_shape, b, h, fast), B, hk) == 1
    rec = {"class": name, "shape": list(shape), "dtype": dtype.name,
           "mask": mask_shape and list(mask_shape), "causal": causal,
           "dropout": dropout, "bq": bq, "bk": bk, "nb": nb,
           "s_pad": s_pad, "estimate_mib": round(fa._step_vmem_bytes(
               nb, bq, bk, d, dtype.itemsize, mask_shape is not None,
               batched, bool(dropout)) / MIB, 2)}
    return fn, args, rec


def least_limit(fn, args):
    """(compiles at the default limit, least `vmem_limit_bytes` in MiB at
    which it compiles, None above 48). The probe hands the limit to
    `pltpu.CompilerParams`: what a probe may do and a caller may not."""
    real = fa.pltpu.CompilerParams
    limit = [None]

    def params(**kw):
        if limit[0] is not None:
            kw["vmem_limit_bytes"] = limit[0]
        return real(**kw)

    def compiles(at):
        limit[0] = at
        jax.clear_caches()
        try:
            jax.jit(fn).lower(*args).compile()
            return True
        except Exception as e:
            if "vmem" not in str(e):
                raise
            return False

    fa.pltpu.CompilerParams = params
    try:
        ok = compiles(None)
        lo, hi = 4, 4 * 48      # quarters of a MiB: refused, compiles
        if not compiles(hi * MIB // 4):
            return ok, None
        while hi - lo > 1:
            mid = (lo + hi) // 2
            if compiles(mid * MIB // 4):
                hi = mid
            else:
                lo = mid
        return ok, hi / 4
    finally:
        fa.pltpu.CompilerParams = real


def aot_classes(sharding, names):
    """One line a class: the geometry drawn, the estimate, whether it
    compiles at the default limit and the least limit at which it does."""
    for name in names:
        fn, args, rec = class_program(name)
        args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
                for a in args]
        rec["ok"], rec["need_mib"] = least_limit(fn, args)
        print(json.dumps(rec), flush=True)


def programs(shape, bq, bk, nb, fwd_only):
    """{"fwd": (fn, args), "bwd": (fn, args)} on the kernels' own
    [B, s, h*d] layout (d is a lane multiple here: no transposes)."""
    b, s, h, d = shape
    scale = d ** -0.5

    def fwd_call(q, k, v):
        return fa._flash_fwd(q, k, v, None, h, True, scale, bq, bk, nb, s,
                             False)

    def fwd_loop(q, k, v):
        return jax.lax.fori_loop(
            0, CALLS, lambda _, x: fwd_call(x, k, v)[0], q)

    def bwd_loop(q, k, v, o, lse, do):
        def body(_, g):
            return fa._flash_bwd(q, k, v, o, lse, g, None, h, True, scale,
                                 bq, bk, nb, s, False)[0]
        return jax.lax.fori_loop(0, CALLS, body, do)

    x = jax.ShapeDtypeStruct((b, s, h * d), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((b, h, s, fa.ROW_LANES), jnp.float32)
    out = {"fwd": (fwd_loop, (x, x, x))}
    if not fwd_only:
        out["bwd"] = (bwd_loop, (x, x, x, x, lse, x))
    out["_fwd_call"] = fwd_call
    return out


def reference(shape, q, k, v, do):
    """XLA's attention in float32 on batch slice 0 of the bf16 inputs:
    (o, dq, dk, dv) in the kernels' [1, s, h*d] layout."""
    _, s, h, d = shape

    def f(q_, k_, v_):
        x = (t[:1].astype(jnp.float32).reshape(1, s, h, d)
             for t in (q_, k_, v_))
        return fa._xla_ref(*x, True, d ** -0.5).reshape(1, s, h * d)
    o, vjp = jax.vjp(f, q, k, v)
    return (o,) + tuple(g[:1] for g in vjp(do[:1].astype(jnp.float32)))


def worst_error(got, ref):
    """Largest |got - ref| over the largest |ref|, per output."""
    return [round(float(jnp.max(jnp.abs(g[:1].astype(jnp.float32) - r))
                        / jnp.max(jnp.abs(r))), 5)
            for g, r in zip(got, ref)]


def device_ms(trace_dir):
    """Device-0 self milliseconds by operation family in the newest
    trace under trace_dir."""
    from harness import trace_reduce
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    devices, _ = trace_reduce.read_planes(paths[-1])
    ops = devices[min(devices)].get(trace_reduce.OPS_LINE, [])
    by = collections.Counter()
    for name, own in trace_reduce.self_times(ops):
        by[trace_reduce.op_family(name)] += own
    return {f: round(t * 1e3 / CALLS, 4) for f, t in by.most_common(6)
            if f != "while"}


def measure(fn, args, trace_dir):
    jitted = jax.jit(fn)
    jax.block_until_ready(jitted(*args))
    best = float("inf")
    for _ in range(REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(jitted(*args))
        best = min(best, time.perf_counter() - t0)
    jax.profiler.start_trace(trace_dir)
    jax.block_until_ready(jitted(*args))
    jax.profiler.stop_trace()
    return best * 1e3 / CALLS, device_ms(trace_dir)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--aot", action="store_true")
    ap.add_argument("--shape", default="2,4096,16,128")
    ap.add_argument("--fwd-only", action="store_true")
    ap.add_argument("--geometries", default=None)
    ap.add_argument("--rule", action="store_true")
    ap.add_argument("--classes", nargs="?", const=",".join(CLASSES),
                    default=None)
    ap.add_argument("--nb-max", type=int, default=8)
    args = ap.parse_args()
    shape = tuple(int(x) for x in args.shape.split(","))
    b, s, h, d = shape
    geos = ([tuple(int(x) for x in g.split("x"))
             for g in args.geometries.split(",")] if args.geometries
            else [(q, k) for q in BLOCKS for k in BLOCKS])
    if args.rule:
        geos.append(None)

    sharding = None
    if args.aot:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        sharding = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
    else:
        chip.require_tpu()
        chip.enable_compile_cache()
    if args.classes:
        if not args.aot:
            sys.exit("--classes compiles and runs nothing: give --aot too")
        return aot_classes(sharding, args.classes.split(","))
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    key = jax.random.PRNGKey(0)
    lines, ref = [], None
    for geo in geos:
        bq, bk, nb, s_pad = fa.flash_geometry(
            (b, s, h, d), jnp.bfloat16, bq=geo and geo[0],
            bk=geo and geo[1], nb_max=args.nb_max)
        rec = {"shape": list(shape), "bq": bq, "bk": bk, "nb": nb,
               "asked": geo and list(geo),
               "steps_fwd": (b // nb) * h * (s_pad // bq) * (s_pad // bk),
               "estimate_mib": round(fa._step_vmem_bytes(
                   nb, bq, bk, d, 2, False, False) / MIB, 2)}
        if s_pad != s or (geo and (bq, bk) != geo):
            rec["note"] = "fitted below what was asked, or padded"
        jax.clear_caches()
        progs = programs((b, s_pad, h, d), bq, bk, nb, args.fwd_only)
        fwd_call = progs.pop("_fwd_call")
        try:
            if args.aot:
                for name, (fn, structs) in progs.items():
                    structs = [jax.ShapeDtypeStruct(
                        x.shape, x.dtype, sharding=sharding)
                        for x in structs]
                    rec[name + "_ok"], rec[name + "_need_mib"] = \
                        least_limit(fn, structs)
            else:
                q, k, v, do = (jax.random.normal(
                    kk, (b, s_pad, h * d), jnp.bfloat16)
                    for kk in jax.random.split(key, 4))
                o, lse = jax.jit(fwd_call)(q, k, v)
                feeds = {"fwd": (q, k, v), "bwd": (q, k, v, o, lse, do)}
                once = [o]
                if not args.fwd_only:
                    once += jax.jit(lambda *a: fa._flash_bwd(
                        *a, None, h, True, d ** -0.5, bq, bk, nb, s_pad,
                        False))(q, k, v, o, lse, do)
                if ref is None and s_pad == s:
                    ref = jax.jit(lambda *a: reference(shape, *a))(
                        q, k, v, do)
                if ref is not None:     # o, then dq, dk, dv
                    rec["err_vs_xla_f32"] = worst_error(once, ref)
                del once
                for name, (fn, _) in progs.items():
                    ms, dev = measure(fn, feeds[name], os.path.join(
                        ROOT, ".perf_trace", "flash_probe"))
                    rec[name + "_ms"] = round(ms, 4)
                    rec[name + "_trace_ms"] = dev
        except Exception as e:  # the compiler's refusal is the finding
            rec["error"] = f"{type(e).__name__}: {str(e)[:600]}"
        print(json.dumps(rec), flush=True)
        lines.append(rec)
    if not args.aot:
        with open(os.path.join(out_dir, "flash_train_probe.json"), "a") as f:
            for rec in lines:
                f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    main()
