#!/usr/bin/env python
"""VPU transcendental probe: is exp2 cheaper than exp on this chip?

Decision input for the flash-attention softmax (ops/pallas/
flash_attention.py): at d=64 the kernels looked exp-bound (builder-
reported on an older stack; ROADMAP S6). The classic CUDA flash trick
folds log2(e)
into the logit scale and uses exp2; whether that pays on the TPU VPU is
an empirical question this probe answers in one live window.

Prints one JSON line per measurement. Interpreting:
  - ratio ~1.0       -> XLA already lowers exp via the same unit; the
                        kernel rewrite would buy nothing — do not do it.
  - ratio >~1.15     -> exp2 is genuinely cheaper; the base-2 softmax
                        rewrite (scale' = scale*log2e, lse converted at
                        emit) is worth the change for d=64 shapes.
The compute-bound variant chains dependent exps so HBM streaming cannot
hide the VPU latency the way the single-pass variant lets it.
"""
import json
import os
import sys
import time

import numpy as np


def bench(f, x, n=50):
    import jax
    y = f(x)
    jax.block_until_ready(y)
    t0 = time.perf_counter()
    for _ in range(n):
        y = f(x)
    jax.block_until_ready(y)
    return (time.perf_counter() - t0) / n * 1e3


def main():
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from paddle_tpu.chip import enable_compile_cache, require_tpu
    enable_compile_cache()
    stamp = require_tpu()      # a VPU probe off the chip measures nothing
    import jax
    import jax.numpy as jnp

    x = jnp.asarray(np.random.RandomState(0)
                    .randn(8 * 1024 * 1024).astype(np.float32)) * 0.1

    cases = {
        "exp_single": jax.jit(lambda v: jnp.exp(v)),
        "exp2_single": jax.jit(lambda v: jnp.exp2(v)),
        # dependent chains: 8 serial transcendentals per element — the
        # VPU-bound regime the flash inner loop lives in
        "exp_chain8": jax.jit(lambda v: _chain(jnp.exp, v)),
        "exp2_chain8": jax.jit(lambda v: _chain(jnp.exp2, v)),
    }
    out = {"device": stamp}
    for name, f in cases.items():
        out[name + "_ms"] = round(bench(f, x), 4)
    out["single_ratio"] = round(out["exp_single_ms"]
                                / max(out["exp2_single_ms"], 1e-9), 3)
    out["chain_ratio"] = round(out["exp_chain8_ms"]
                               / max(out["exp2_chain8_ms"], 1e-9), 3)
    print(json.dumps(out))
    sys.stdout.flush()


def _chain(op, v):
    import jax.numpy as jnp
    y = v
    for _ in range(8):
        y = op(y) * jnp.float32(1e-3)  # keep values bounded
    return y


if __name__ == "__main__":
    main()
