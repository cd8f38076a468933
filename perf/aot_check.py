#!/usr/bin/env python3
"""Compile every step program a cell uses, at its real shapes, for a
DESCRIBED v5e (no chip attached), and read memory_analysis().

  JAX_PLATFORMS=cpu python3 perf/aot_check.py [--layers N] <workload> ...

`--layers N` tries another depth than the configuration file's (how the
depths in the files were found; see PERF.md).

A scratch script, run by hand in the sandbox before chip time is spent
(on-chip-measurement guide, section 2); not a test and not part of a
benchmark run. What the TPU compiler refuses here it would refuse on the
chip, and costs nothing to find. A compile that passes is not a chip run:
nothing executes, so this says nothing about results or times, and it
counts one program at a time, not what else the process keeps on the
device. The program's own code asks `jax.default_backend()` and would
take its CPU branch here, so this script — and only this script —
answers "tpu" for it while it builds and lowers.
"""
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
PERF_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(PERF_DIR), PERF_DIR]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from harness import manifest  # noqa: E402

HBM = 15.75 * 2 ** 30      # what the TPU compiler says a v5e chip offers


def report(name, compiled):
    ma = compiled.memory_analysis()
    peak = ma.peak_memory_in_bytes
    print(f"  {name}: arguments {ma.argument_size_in_bytes / 1e9:.3f} GB "
          f"(aliased to outputs {ma.alias_size_in_bytes / 1e9:.3f}), "
          f"temporaries {ma.temp_size_in_bytes / 1e9:.3f} GB, PEAK "
          f"{peak / 1e9:.3f} GB per device; slack under the usable "
          f"{HBM / 1e9:.2f} GB: {(HBM - peak) / 1e9:.3f} GB", flush=True)
    return peak


def check_train(cell, topo):
    from paddle_tpu.distributed.mesh import build_mesh, set_global_mesh
    from paddle_tpu.models.train_step import SpmdTrainer
    cfg = cell.config
    family = manifest.load_plugin("references", cfg["reference"])
    mesh = build_mesh(cfg["training"]["mesh"],
                      devices=list(topo.devices)[:cell.chips])
    set_global_mesh(mesh)
    trainer = SpmdTrainer(family.build_model(cfg, 0), mesh,
                          **cfg["training"]["trainer"])
    p = cell.traffic["params"]
    shape = (int(p["batch"]), int(p["seq"]))
    step = trainer._build(shape)            # what trainer.step() jits
    batch_spec = P(tuple(a for a in ("data", "sharding")
                         if mesh.shape[a] > 1) or None)
    ids = jax.ShapeDtypeStruct(shape, jnp.int32,
                               sharding=NamedSharding(mesh, batch_spec))
    rep = NamedSharding(mesh, P())
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=rep)
    lr = jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)
    t0 = time.time()
    compiled = step.lower(trainer.abstract_state(), ids, ids, key,
                          lr).compile()
    print(f"  compiled in {time.time() - t0:.0f} s")
    report("train step", compiled)
    text = compiled.as_text()
    found = {k: text.count(k + "(") + text.count(k + "-start(") for k in
             ("all-reduce", "all-gather", "reduce-scatter",
              "collective-permute", "all-to-all")}
    print(f"  collectives in the program: {found}; Mosaic kernels: "
          f"{text.count('tpu_custom_call')}")


def check_serve(cell, topo):
    """Builds the real engine (weights materialize on the host: about
    8 GB and a minute at 7B), then lowers its own jitted programs with
    every array replaced by its shape on the described chip."""
    from paddle_tpu.inference import ContinuousBatchingEngine
    cfg = cell.config
    family = manifest.load_plugin("references", cfg["reference"])
    runner = manifest.load_plugin("systems", cfg["system"])
    t0 = time.time()
    eng = ContinuousBatchingEngine(family.build_model(cfg, 0),
                                   **runner.engine_kwargs(cfg))
    print(f"  engine built on the host in {time.time() - t0:.0f} s: "
          f"megakernel={eng.health()['megakernel']} "
          f"interpret={eng.interpret} kv={eng.kv_dtype}")
    chip = SingleDeviceSharding(topo.devices[0])

    def shape_of(a):
        a = a if hasattr(a, "dtype") else np.asarray(a)
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)

    seen, weight_bytes = set(), 0
    for leaf in jax.tree_util.tree_leaves(eng.weights):
        if hasattr(leaf, "unsafe_buffer_pointer"):
            ptr = leaf.unsafe_buffer_pointer()
            if ptr not in seen:
                seen.add(ptr)
                weight_bytes += leaf.nbytes
    pool_bytes = sum(a.nbytes for a in eng.k_pages + eng.v_pages)
    print(f"  resident on the chip: weights {weight_bytes / 1e9:.3f} GB "
          f"(distinct buffers of engine.weights, megakernel pack "
          f"included) + KV pool {pool_bytes / 1e9:.3f} GB = "
          f"{(weight_bytes + pool_bytes) / 1e9:.3f} GB")

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda a: shape_of(a) if hasattr(a, "shape") else a, tree)

    W, kp, vp = sds(eng.weights), sds(eng.k_pages), sds(eng.v_pages)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=chip)  # noqa: E731
    mp = eng.max_pages_per_seq
    t0 = time.time()
    pre = eng._build_cb_prefill(eng.prefill_chunk)
    c = pre.lower(W, i32(1, eng.prefill_chunk), kp, vp, i32(1, mp),
                  i32(), i32()).compile()
    print(f"  prefill chunk {eng.prefill_chunk} compiled in "
          f"{time.time() - t0:.0f} s")
    worst = report("prefill", c)
    for w in eng._slot_buckets:
        t0 = time.time()
        fn = eng._build_cb_step(w)
        c = fn.lower(W, i32(w), kp, vp, i32(w, mp), i32(w),
                     jax.ShapeDtypeStruct((w,), jnp.bool_, sharding=chip)
                     ).compile()
        print(f"  decode step, {w} slots, compiled in "
              f"{time.time() - t0:.0f} s; Mosaic kernels: "
              f"{c.as_text().count('tpu_custom_call')}")
        worst = max(worst, report(f"decode w={w}", c))
    print(f"  largest program peak {worst / 1e9:.3f} GB")


def main():
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    jax.default_backend = lambda: "tpu"     # see the module docstring
    bench = manifest.load_json("BENCHMARK.json")
    argv = sys.argv[1:]
    layers = int(argv[1]) if argv[:1] == ["--layers"] else None
    for name in argv[2:] if layers else argv:
        cell = manifest.Cell(bench, name)
        if layers:
            cell.config["num_hidden_layers"] = layers
        print(f"{name}: {cell.config_name} x {cell.traffic_name}, "
              f"{cell.chips} chip(s)", flush=True)
        kind = cell.config["system"]
        {"spmd_trainer": check_train, "serve_engine": check_serve}[kind](
            cell, topo)


if __name__ == "__main__":
    main()
