#!/usr/bin/env python3
"""Record the small profiler trace the span-reduction test reads
(tests/perf_bench/data/small_tpu_spans.xplane.pb). Run ON THE CHIP, by
hand, when the profiler's format or the engine's span names change:

  python3 tests/perf_bench/record_span_fixture.py chiprun_out/span_fixture

Five made-up engine steps around a small jitted program, each under the
harness's `bench.step` and the program's own `cb.*` spans emitted through
`paddle_tpu.profiler.RecordEvent` (the engine's span call), with pauses
of known length where the device must sit idle under a known span:
decode, decode, prefill (a last chunk), a step with nothing to do, decode.
Prints what the test then pins: the host spans read back and the
reduction.
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "perf")]

PREPARE_S, PUSH_S, POLL_S = 0.002, 0.001, 0.0015


def main(out_dir):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import run as bench
    from harness import span_reduce
    from paddle_tpu.profiler import RecordEvent as span

    @jax.jit
    def small_step(x):
        def body(_, h):
            return jnp.tanh(h @ x) * 0.5
        return jax.lax.fori_loop(0, 12, body, x).sum()

    x = jnp.ones((2048, 2048), jnp.bfloat16)
    np.asarray(small_step(x))
    tracer = bench.Tracer(True, out_dir)

    def decode(i):
        with span("cb.step", step=i):
            with span("cb.admit"):
                pass
            with span("cb.decode.prepare"):
                time.sleep(PREPARE_S)
            with span("cb.decode_step"):
                with span("cb.decode.dispatch"):
                    y = small_step(x)
                with span("cb.decode.fetch"):
                    np.asarray(y)
            with span("cb.decode.push"):
                time.sleep(PUSH_S)

    def prefill(i):
        with span("cb.step", step=i):
            with span("cb.admit"):
                pass
            with span("cb.prefill.prepare"):
                time.sleep(PREPARE_S)
            with span("cb.prefill_chunk"):
                y = small_step(x)
            with span("cb.prefill.first_token"):
                np.asarray(y)

    def nothing(i):
        with span("cb.step", step=i):
            with span("cb.admit"):
                pass

    tracer.start()
    for i, step in enumerate((decode, decode, prefill, nothing, decode)):
        with tracer.span("bench.step"):
            step(i)
        with tracer.span("bench.poll"):
            time.sleep(POLL_S)
    tracer.stop()
    path = tracer.xplane()
    print(path, os.path.getsize(path), "bytes on", jax.devices()[0])
    for sp in sorted(span_reduce.read_host_spans(path),
                     key=lambda sp: (sp[1], -sp[2])):
        print(sp)
    print(json.dumps(span_reduce.reduce_file(path), indent=1))


if __name__ == "__main__":
    main(sys.argv[1])
