#!/usr/bin/env python
"""Pretrain a LLaMA-family model with hybrid parallelism.

The flagship user journey: pick a mesh (data x pipe x sharding x model
[x sep]), build the model, hand both to SpmdTrainer — ONE compiled SPMD
program per step covers TP collectives, pipeline microbatching (GPipe /
1F1B / interleaved), ZeRO 1-3, recompute, and context parallelism.

Run on any host (CPU smoke):
    python examples/pretrain_llama_hybrid.py --cpu --devices 8
On TPU chips the same code runs unchanged: the mesh is derived from the
devices jax reports (the four-chip host trains pp2 x mp2) and the
collectives ride ICI.
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# North-star hybrid recipes (BASELINE.md workloads 3/4). The v5p-128
# 13B recipe lists ONE dp replica group's mesh — per-device memory is
# dp-invariant, so an 8-device AOT compile certifies the 128-chip
# placement (dp16 x mp2 x pp2 x sharding2).
RECIPES = {
    "7b": dict(
        cfg=dict(vocab_size=32000, hidden_size=4096,
                 intermediate_size=11008, num_hidden_layers=32,
                 num_attention_heads=32, max_position_embeddings=2048),
        mesh={"data": 1, "pipe": 1, "sharding": 8, "model": 1},
        trainer=dict(param_dtype="bfloat16", moment_dtype="float32",
                     recompute=True, sharding_stage=2),
        batch=(8, 2048), target="v5p-8 (95 GB HBM/chip)"),
    "13b": dict(
        cfg=dict(vocab_size=32000, hidden_size=5120,
                 intermediate_size=13824, num_hidden_layers=40,
                 num_attention_heads=40, max_position_embeddings=2048),
        mesh={"data": 1, "pipe": 2, "sharding": 2, "model": 2},
        trainer=dict(param_dtype="bfloat16", moment_dtype="float32",
                     recompute=True, sharding_stage=2,
                     micro_batch_size=2, pp_schedule="1f1b"),
        batch=(8, 2048), target="v5p-128 = dp16 x this replica group"),
}


def aot_memory_report(name):
    """AOT per-device memory accounting of a north-star recipe — built
    under LazyGuard (meta init), so no parameter is ever materialized:
    runs on any small host. Returns the memory_analysis dict."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.distributed.mesh import build_mesh, set_global_mesh
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.train_step import SpmdTrainer

    r = RECIPES[name]
    mesh = build_mesh(r["mesh"])
    set_global_mesh(mesh)
    with paddle.LazyGuard():
        model = LlamaForCausalLM(LlamaConfig(**r["cfg"]))
    trainer = SpmdTrainer(model, mesh, lr=1e-4, **r["trainer"])
    bs, seq = r["batch"]
    ids = jax.ShapeDtypeStruct((bs, seq), np.int64)
    return trainer.memory_analysis(trainer.abstract_state(), ids, ids)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--cpu", action="store_true",
                    help="run on N virtual CPU devices")
    ap.add_argument("--aot_memory", choices=sorted(RECIPES),
                    help="AOT-compile a north-star recipe (7b/13b) and "
                         "print its per-device memory accounting instead "
                         "of training")
    ap.add_argument("--grad-compress", choices=["none", "int8"],
                    default="none", dest="grad_compress",
                    help="int8: gradient collectives ride the chunked "
                         "int8 allreduce with error feedback "
                         "(docs/distributed_perf.md)")
    args = ap.parse_args()

    import jax
    if args.aot_memory or args.cpu:
        # pin BEFORE any backend query
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.devices)
    from paddle_tpu.chip import enable_compile_cache
    enable_compile_cache()

    if args.aot_memory:
        ma = aot_memory_report(args.aot_memory)
        r = RECIPES[args.aot_memory]
        print(f"{args.aot_memory} on {r['target']}: mesh={r['mesh']}")
        for k, v in ma.items():
            print(f"  {k}: {v / 1e9:.2f} GB")
        return

    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.mesh import build_mesh, set_global_mesh
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.train_step import SpmdTrainer

    # 1. strategy + mesh (the reference's fleet.init + hybrid_configs)
    # mesh from the devices jax reports: model and pipe take a factor
    # of two each while one is left, data takes the rest (8 devices ->
    # dp2 x pp2 x mp2, the four-chip host -> pp2 x mp2)
    n = len(jax.devices())
    mp = 2 if n % 2 == 0 else 1
    pp = 2 if (n // mp) % 2 == 0 else 1
    degrees = {"data": n // (mp * pp), "pipe": pp, "sharding": 1,
               "model": mp}
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": degrees["data"],
                               "mp_degree": mp, "pp_degree": pp,
                               "sharding_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    mesh = build_mesh(degrees)
    set_global_mesh(mesh)
    print(f"platform={jax.devices()[0].platform} devices={n} "
          f"mesh={degrees}")

    # 2. model + trainer (bf16 params, 1F1B schedule, fused head+CE)
    paddle.seed(0)
    cfg = LlamaConfig.tiny(num_hidden_layers=4)
    model = LlamaForCausalLM(cfg)
    trainer = SpmdTrainer(model, mesh, lr=1e-3, micro_batch_size=2,
                          pp_schedule="1f1b", recompute=True,
                          grad_compress=(None if args.grad_compress == "none"
                                         else args.grad_compress))
    state = trainer.init_state()

    # 3. train
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (8, args.seq)).astype(np.int64)
    labels = np.roll(ids, -1, axis=1)
    for step in range(args.steps):
        state, loss = trainer.step(state, ids, labels)
        print(f"step {step}: loss {float(loss):.4f}")

    # 4. sharded checkpoint + write back into the eager model
    from paddle_tpu.distributed import checkpoint as ckpt
    ckpt.save_state(state, "/tmp/llama_ckpt", step=args.steps)
    trainer.sync_to_model(state)
    print("checkpoint saved; eager model synced")


if __name__ == "__main__":
    main()
