#!/usr/bin/env python
"""Micro-benchmark: flash attention (Pallas) vs XLA attention, fwd+bwd.

Serial-chain timing: each measurement chains the computation (output
feeds next input), fetches one scalar at the end (a hard sync), and
reports the SLOPE between two chain lengths — dispatch constants cancel.

Prints one JSON line per (impl, shape) with ms/iter and achieved TFLOP/s.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def attn_flops(b, s, h, d, causal):
    f = 2 * 2 * b * h * s * s * d
    return f // 2 if causal else f


def bench_chain(step, x0, n1=20, n2=80):
    """step: x -> x (same shape/dtype). Returns seconds per iteration."""

    def run(n):
        x = x0
        t0 = time.perf_counter()
        for i in range(n):
            x = step(x, jnp.float32(i))
        float(jnp.sum(x[:1, :1].astype(jnp.float32)))  # hard sync
        return time.perf_counter() - t0

    run(3)  # warmup/compile
    t1 = run(n1)
    t2 = run(n2)
    return (t2 - t1) / (n2 - n1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, nargs="+", default=[1024, 2048])
    ap.add_argument("--bs", type=int, default=8)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--bq", type=int, default=None)
    ap.add_argument("--bk", type=int, default=None)
    def _pow2(v):
        n = int(v)
        if n < 1 or n & (n - 1):
            raise argparse.ArgumentTypeError(f"--nb must be a positive "
                                             f"power of two, got {v}")
        return n
    ap.add_argument("--nb", type=_pow2, default=8)
    ap.add_argument("--impls", nargs="+",
                    default=["pallas_fwd", "xla_fwd", "pallas_fwdbwd",
                             "xla_fwdbwd"],
                    help="also available: pallas_dropout_fwdbwd (native "
                         "in-kernel attention dropout)")
    args = ap.parse_args()

    from paddle_tpu.chip import enable_compile_cache, require_tpu
    enable_compile_cache()
    stamp = require_tpu()     # compiled kernels, achieved TFLOP/s: chip only
    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.nn.functional.attention import _sdpa_xla

    if args.bq or args.bk or args.nb != 8:
        # partial overrides fall back to the kernel's real defaults (256)
        flash = fa.make_flash_attention(bq=args.bq or 256, bk=args.bk or 256,
                                        nb_max=args.nb)
    else:
        flash = fa.make_flash_attention()

    b, h, d = args.bs, args.heads, args.dim
    for s in args.seq:
        rng = np.random.RandomState(0)
        shape = (b, s, h, d)
        q = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
        k = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
        v = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
        scale = 1.0 / np.sqrt(d)
        fl = attn_flops(b, s, h, d, True)

        @jax.jit
        def fwd_pallas(x, i):
            return flash(x + i.astype(x.dtype) * 1e-6, k, v, True, scale)

        @jax.jit
        def fwd_xla(x, i):
            return _sdpa_xla(x + i.astype(x.dtype) * 1e-6, k, v, None,
                             causal=True, scale=scale)

        def loss_p(q, k, v):
            return jnp.sum(flash(q, k, v, True, scale).astype(jnp.float32))

        def loss_x(q, k, v):
            return jnp.sum(_sdpa_xla(q, k, v, None, causal=True,
                                     scale=scale).astype(jnp.float32))

        gp = jax.grad(loss_p, argnums=(0, 1, 2))
        gx = jax.grad(loss_x, argnums=(0, 1, 2))

        @jax.jit
        def fb_pallas(x, i):
            dq, dk, dv = gp(x + i.astype(x.dtype) * 1e-6, k, v)
            return dq + 1e-6 * (dk + dv)

        @jax.jit
        def fb_xla(x, i):
            dq, dk, dv = gx(x + i.astype(x.dtype) * 1e-6, k, v)
            return dq + 1e-6 * (dk + dv)

        flash_do = fa.make_flash_attention(bq=args.bq or 256,
                                           bk=args.bk or 256,
                                           nb_max=args.nb, dropout_p=0.1)

        def loss_do(q, k, v):
            return jnp.sum(flash_do.dropout(
                q, k, v, jnp.int32(7), True, scale).astype(jnp.float32))

        gdo = jax.grad(loss_do, argnums=(0, 1, 2))

        @jax.jit
        def fb_dropout(x, i):
            dq, dk, dv = gdo(x + i.astype(x.dtype) * 1e-6, k, v)
            return dq + 1e-6 * (dk + dv)

        impls = {"pallas_fwd": (fwd_pallas, 1), "xla_fwd": (fwd_xla, 1),
                 "pallas_fwdbwd": (fb_pallas, 3.5), "xla_fwdbwd": (fb_xla, 3.5),
                 "pallas_dropout_fwdbwd": (fb_dropout, 3.5)}
        for name in args.impls:
            fn, mult = impls[name]
            try:
                dt = bench_chain(fn, q)
                print(json.dumps({
                    "impl": name, "b": b, "s": s, "h": h, "d": d,
                    "bq": args.bq, "bk": args.bk, "nb": args.nb,
                    "ms": round(dt * 1e3, 3),
                    "tflops": round(fl * mult / dt / 1e12, 2),
                    "device": stamp,
                }), flush=True)
            except Exception as e:
                print(json.dumps({"impl": name, "s": s,
                                  "error": f"{type(e).__name__}: {e}"[:300]}),
                      flush=True)


if __name__ == "__main__":
    main()
