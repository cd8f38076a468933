#!/usr/bin/env python3
"""Record the small profiler trace the phase reader's test reads
(tests/perf_bench/data/small_tpu_phases.xplane.pb). Run ON THE CHIP, by
hand, when the profiler's format or jax's name stacks change:

  python3 tests/perf_bench/record_phase_fixture.py chiprun_out/phase_fixture

A few steps of a small jitted training step that opens its phases through
`paddle_tpu.profiler.phase` and differentiates a `jax.checkpoint`ed layer,
so forward, recompute and backward are all in it, with an update under
`optimizer`; traced through perf/run.py's own Tracer with the harness's
own span names (record_trace_fixture.py's method). Writes into the
directory, for tests/perf_bench/data/: `small_tpu_phases.xplane.pb`, the
trace WITHOUT its `/host:metadata` plane (the programs' HLO protos, two
thirds of the file, which no reader opens), and `small_tpu_phases.json`,
what the test pins: executions, and self seconds by phase and pass as
`harness/phase_times.py` reads them from the kept file.
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "perf")]


def without_metadata_plane(path):
    """The XSpace at `path` less its `/host:metadata` plane, as bytes:
    planes are the top-level field 1, every field is copied whole."""
    from harness import scope_times

    def varint(n):
        out = bytearray()
        while n >= 0x80:
            out.append(n & 0x7F | 0x80)
            n >>= 7
        return bytes(out + bytes([n]))

    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = bytearray()
    for num, wt, val in scope_times.fields(space):
        assert wt == 2, (num, wt)   # planes, errors, warnings, hostnames
        name = next((bytes(v) for n, w, v in scope_times.fields(val)
                     if n == 2 and w == 2), b"") if num == 1 else b""
        if name != b"/host:metadata":
            out += varint(num << 3 | 2) + varint(len(val)) + bytes(val)
    return bytes(out)


def main(out_dir):
    import jax
    import jax.numpy as jnp
    import run as bench
    from harness import phase_times
    from paddle_tpu.profiler import phase

    def layer(h, w):
        with phase("attn_proj"):
            q = jnp.tanh(h @ w["a"])
        with phase("attend"):
            p = jax.nn.softmax((q @ q.T).astype(jnp.float32) / 32.0, -1)
            s = p.astype(q.dtype) @ q
        with phase("ffn"):
            return h + jnp.tanh(s @ w["f"])

    def loss_fn(w, x):
        with phase("embed"):
            h = x * w["e"]
        h = jax.checkpoint(layer)(h, w)
        with phase("loss"):     # a head product, so it is no epilogue
            return jnp.mean(jnp.square((h @ w["h"]).astype(jnp.float32)))

    @jax.jit
    def small_train(w, x):
        loss, grads = jax.value_and_grad(loss_fn)(w, x)
        with phase("optimizer"):
            w = jax.tree_util.tree_map(
                lambda p, g: p - jnp.asarray(1e-3, p.dtype) * g, w, grads)
        return w, loss

    n = 1024
    x = jnp.ones((n, n), jnp.bfloat16) * 0.01
    w = {"a": jnp.eye(n, dtype=jnp.bfloat16),
         "f": jnp.eye(n, dtype=jnp.bfloat16) * 0.5,
         "h": jnp.eye(n, dtype=jnp.bfloat16) * 0.25,
         "e": jnp.ones((n,), jnp.bfloat16)}
    w, loss = small_train(w, x)
    loss.block_until_ready()
    tracer = bench.Tracer(True, out_dir)
    tracer.start()
    for _ in range(5):
        with tracer.span("bench.step"):
            w, loss = small_train(w, x)
        with tracer.span("bench.wait"):
            loss.block_until_ready()
    tracer.stop()
    kept = os.path.join(out_dir, "small_tpu_phases.xplane.pb")
    with open(kept, "wb") as f:
        f.write(without_metadata_plane(tracer.xplane()))
    print(kept, os.path.getsize(kept), "bytes of",
          os.path.getsize(tracer.xplane()), "on", jax.devices()[0])
    pt = phase_times.reduce_file(kept)
    if pt is None:
        print("no device plane: not a chip")
        return
    pinned = json.dumps({"runs": pt["runs"], "seconds": pt["seconds"]},
                        indent=1)
    with open(os.path.join(out_dir, "small_tpu_phases.json"), "w") as f:
        f.write(pinned + "\n")
    print(pinned)


if __name__ == "__main__":
    main(sys.argv[1])
