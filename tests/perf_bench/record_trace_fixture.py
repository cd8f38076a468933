#!/usr/bin/env python3
"""Record the small profiler trace the trace-reduction test reads
(tests/perf_bench/data/small_tpu.xplane.pb). Run ON THE CHIP, by hand,
when the profiler's format changes:

  python3 tests/perf_bench/record_trace_fixture.py chiprun_out/fixture

A few steps of a small jitted program with a loop inside it (so the
`XLA Ops` line has operations nested in a `while`), a pause between
steps (so the device has idle gaps under a known host span), traced
through perf/run.py's own Tracer with the harness's own span names.
Prints what the test then pins: the spans' count and the device plane's
line names.
"""
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "perf")]


def main(out_dir):
    import jax
    import jax.numpy as jnp
    import run as bench
    from harness import trace_reduce

    @jax.jit
    def small_step(x):
        def body(_, h):
            return jnp.tanh(h @ x) * 0.5
        return jax.lax.fori_loop(0, 6, body, x).sum()

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    small_step(x).block_until_ready()
    tracer = bench.Tracer(True, out_dir)
    tracer.start()
    for _ in range(4):
        with tracer.span("bench.step"):
            y = small_step(x)
        with tracer.span("bench.wait"):
            y.block_until_ready()
        with tracer.span("bench.idle"):
            time.sleep(0.002)
    tracer.stop()
    path = tracer.xplane()
    print(path, os.path.getsize(path), "bytes on", jax.devices()[0])
    devices, spans = trace_reduce.read_planes(path)
    print({d: {k: len(v) for k, v in lines.items()}
           for d, lines in devices.items()}, len(spans))
    print(trace_reduce.reduce_trace(path, 1))


if __name__ == "__main__":
    main(sys.argv[1])
