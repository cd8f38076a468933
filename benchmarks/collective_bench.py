#!/usr/bin/env python
"""Gradient-collective benchmark: exact f32 vs chunked-int8 allreduce.

Measures the comm layer the training step rides (docs/distributed_perf.md):
  - step-time + effective wire bandwidth for lax.psum vs
    comm_compress.quantized_psum (EQuARX-style two-stage int8) at several
    gradient sizes, on a multi-device mesh — the 8-device virtual CPU
    mesh under JAX_PLATFORMS=cpu (jax_num_cpu_devices), the real
    chips otherwise;
  - the same for the ZeRO reduce-to-owner pattern (psum_scatter);
  - a convergence guard: a tiny model trained N steps with exact vs
    int8+error-feedback gradient sync — final losses must agree within
    tolerance (the claim that compression costs wire bytes, not quality).

Prints one JSON line per metric, e.g.:
  {"metric": "allreduce_gbps_exact", "size_mb": 16.0, "value": ...}
  {"metric": "allreduce_gbps_int8", "size_mb": 16.0, "value": ...}
  {"metric": "collective_convergence", "pass": true, ...}

Wire bytes are the analytic ring-collective volume per rank
(comm_compress.wire_bytes): on a virtual CPU mesh nothing crosses a real
wire, so gbps is a dispatch+compute proxy there — the BYTES column is the
hardware-independent claim, the TPU run gives the physical bandwidth.
"""
import json
import os
import sys
import time

import numpy as np

# runnable from anywhere: the script dir (benchmarks/) is what lands on
# sys.path, not the repo root
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_CPU_DEVICES = 8


def _emit(payload):
    print(json.dumps(payload))
    sys.stdout.flush()


def _bench_collectives(on_tpu):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from paddle_tpu.distributed.mesh import build_mesh
    from paddle_tpu.distributed import comm_compress as cc

    n = len(jax.devices())
    mesh = build_mesh({"data": n})
    chunk = cc.DEFAULT_CHUNK
    # per-rank gradient sizes (elements); bucket-scale payloads
    sizes = [1 << 20, 1 << 22] if not on_tpu else [1 << 22, 1 << 24]
    calib_rows = []   # the cost_model.Calibration table (--calib-out)

    def timed(fn, x, iters=20):
        y = jax.block_until_ready(fn(x))  # compile + warm
        t0 = time.perf_counter()
        for _ in range(iters):
            y = fn(x)
        jax.block_until_ready(y)
        return (time.perf_counter() - t0) / iters

    for size in sizes:
        x = jnp.asarray(
            np.random.RandomState(0).randn(n * size).astype(np.float32))

        def exact(xs):
            return lax.psum(xs, "data")

        def int8(xs):
            y, _err = cc.quantized_psum(xs, "data", axis_size=n,
                                        chunk=chunk)
            return y

        def exact_rs(xs):
            return lax.psum_scatter(xs, "data", scatter_dimension=0,
                                    tiled=True)

        def int8_rs(xs):
            y, _err = cc.quantized_psum_scatter(xs, "data", axis_size=n,
                                                chunk=chunk)
            return y

        variants = {
            ("allreduce", "exact"): (exact, False, False),
            ("allreduce", "int8"): (int8, True, False),
            ("reducescatter", "exact"): (exact_rs, False, True),
            ("reducescatter", "int8"): (int8_rs, True, True),
        }
        for (verb, kind), (fn, compressed, scatter) in variants.items():
            f = jax.jit(shard_map(fn, mesh=mesh, in_specs=P("data"),
                                  out_specs=P("data"), check_vma=False))
            dt = timed(f, x)
            wire = cc.wire_bytes(size, n, chunk=chunk,
                                 compressed=compressed,
                                 scatter_only=scatter)
            gbps = wire / max(dt, 1e-9) / 1e9
            metric = (f"allreduce_gbps_{kind}" if verb == "allreduce"
                      else f"reducescatter_gbps_{kind}")
            _emit({
                "metric": metric,
                "size_mb": round(size * 4 / 1e6, 2),
                "devices": n,
                "step_time_ms": round(dt * 1e3, 3),
                "wire_mb_per_rank": round(wire / 1e6, 3),
                "value": round(gbps, 3),
                "unit": "GB/s",
                "backend": jax.default_backend(),
            })
            calib_rows.append({
                "verb": verb, "kind": kind,
                "size_bytes": int(wire), "gbps": round(gbps, 4),
                "devices": n,
                "step_time_ms": round(dt * 1e3, 4),
            })
    return calib_rows


def _convergence_guard(steps=8, rtol=0.05):
    """Tiny model, N steps, exact vs int8+EF gradient sync: the final
    losses must agree within rtol. Returns True on pass."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.train_step import SpmdTrainer
    from paddle_tpu.distributed.mesh import build_mesh, set_global_mesh
    from paddle_tpu.distributed import fleet

    n = len(jax.devices())
    axes = {"data": 2 if n >= 2 else 1, "pipe": 1,
            "sharding": 2 if n >= 4 else 1, "model": 1}
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 128, (8, 16)).astype(np.int64)
    labels = np.roll(ids, -1, axis=1)
    key = jax.random.PRNGKey(7)

    finals = {}
    for name, kw in [("exact", {}), ("int8", {"grad_compress": "int8"})]:
        mesh = build_mesh(axes)
        set_global_mesh(mesh)
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {
            "dp_degree": axes["data"], "mp_degree": axes["model"],
            "pp_degree": axes["pipe"], "sharding_degree": axes["sharding"]}
        fleet.init(is_collective=True, strategy=strategy)
        paddle.seed(11)
        model = LlamaForCausalLM(LlamaConfig.tiny())
        trainer = SpmdTrainer(model, mesh, lr=1e-2, **kw)
        state = trainer.init_state()
        loss = None
        for _ in range(steps):
            state, loss = trainer.step(state, ids, labels, key=key)
        finals[name] = float(loss)

    rel = abs(finals["int8"] - finals["exact"]) / max(
        abs(finals["exact"]), 1e-9)
    ok = bool(rel < rtol)
    _emit({
        "metric": "collective_convergence",
        "steps": steps,
        "exact_loss": round(finals["exact"], 6),
        "int8_loss": round(finals["int8"], 6),
        "rel_diff": round(rel, 6),
        "rtol": rtol,
        "pass": ok,
        "backend": jax.default_backend(),
    })
    return ok


def _write_calib(path, rows, backend):
    """The machine-readable calibration file cost_model.Calibration
    loads (benchmarks/calib/collectives.json by default) — the GB/s
    table plus the backend it was measured on.  CPU-measured numbers
    are a dispatch+compute proxy, which is exactly what the planner
    needs there: predictions stay in the units the machine actually
    exhibits."""
    import platform
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump({"backend": backend,
                   "hostname": platform.node(),
                   "source": "collective_bench.py --calib-out",
                   "collectives": rows}, f, indent=1, sort_keys=True)
    _emit({"metric": "calibration_written", "path": path,
           "rows": len(rows), "backend": backend})


def main():
    calib_out = None
    if "--calib-out" in sys.argv:
        i = sys.argv.index("--calib-out")
        calib_out = (sys.argv[i + 1] if i + 1 < len(sys.argv) else None)
        if not calib_out or calib_out.startswith("-"):
            # default destination: the checked-in fallback the planner
            # loads when nothing fresher exists
            calib_out = os.path.join(os.path.dirname(
                os.path.abspath(__file__)), "calib", "collectives.json")
    # the virtual multi-device CPU mesh must be pinned BEFORE the jax
    # backend initializes
    import jax
    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        jax.config.update("jax_num_cpu_devices", N_CPU_DEVICES)
    from paddle_tpu.chip import enable_compile_cache
    enable_compile_cache()

    on_tpu = jax.default_backend() not in ("cpu",)
    rows = _bench_collectives(on_tpu)
    if calib_out:
        _write_calib(calib_out, rows, jax.default_backend())
    if "--skip-convergence" not in sys.argv:
        ok = _convergence_guard()
        if not ok:
            sys.exit(1)


if __name__ == "__main__":
    main()
