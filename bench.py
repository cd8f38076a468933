#!/usr/bin/env python
"""LLaMA pretraining step on one TPU chip: tokens/s and model-FLOP
utilisation for the two configurations the repo has always timed.

  llama350m  h1024 L16 16x64   ffn2816, bf16 params + f32 moments,
             recompute, batch 32 x 1024
  llama1p3b  h2048 L24 16x128 ffn5504, bf16 params + bf16 moments, full
             recompute, ce_chunk 2048, LazyGuard, batch 8 x 1024

The parent stays off jax and runs each configuration in a child process
of its own, one at a time: a chip belongs to one process, and a fresh
process starts each measurement with an empty device. Every child
requires a TPU and stamps its line with the device; there is no CPU
path. Exits non-zero when any configuration fails, whatever the reason.

This is not the benchmark ROADMAP S1 asks for (no workloads table, no
serving cell, no regression bounds); it is what is left of the old one
after everything that hid the device was taken out.
"""
import json
import os
import subprocess
import sys
import time

CONFIGS = ("llama350m", "llama1p3b")
# Published bf16 peak per chip by device_kind (Google Cloud "TPU v5e"
# documentation: 197 TFLOP/s). A device that is not here is an error.
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12, "TPU v5e": 197e12}


def _measure(cfg, bs, seq, steps, warmup, moment_dtype="float32",
             lazy=False, **trainer_kw):
    import jax
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.models.train_step import SpmdTrainer
    from paddle_tpu.distributed.mesh import build_mesh, set_global_mesh

    mesh = build_mesh({"data": 1, "pipe": 1, "sharding": 1, "model": 1})
    set_global_mesh(mesh)
    paddle.seed(0)
    if lazy:
        # meta init: init_state materializes leaves straight to bf16 — an
        # eager f32 1.3B model (5.4 GB) beside the bf16 state and the
        # step's temporaries does not fit 16 GB
        with paddle.LazyGuard():
            model = LlamaForCausalLM(cfg)
    else:
        model = LlamaForCausalLM(cfg)
    trainer = SpmdTrainer(model, mesh, lr=1e-4, param_dtype="bfloat16",
                          recompute=True, moment_dtype=moment_dtype,
                          **trainer_kw)
    state = trainer.init_state()

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (bs, seq)).astype(np.int64)
    labels = np.roll(ids, -1, axis=1)

    for _ in range(warmup):
        state, loss = trainer.step(state, ids, labels)
    jax.block_until_ready(loss)

    t0 = time.perf_counter()
    for _ in range(steps):
        state, loss = trainer.step(state, ids, labels)
    loss = float(jax.block_until_ready(loss))
    dt = time.perf_counter() - t0
    if not np.isfinite(loss):
        raise RuntimeError(f"non-finite loss {loss}")

    tokens_per_sec = bs * seq * steps / dt
    # model FLOPs: 6N dense + causal attention 12*L*h*s/2; recomputed
    # operations do not count
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    attn = 12 * cfg.num_hidden_layers * cfg.hidden_size * seq // 2
    return tokens_per_sec, (6 * n_params + attn), n_params, loss


def _run_config(which):
    """Run ONE config in THIS process and print its result line."""
    from paddle_tpu.chip import enable_compile_cache, require_tpu
    enable_compile_cache()
    stamp = require_tpu()
    if stamp["kind"] not in PEAK_BF16_FLOPS:
        raise RuntimeError(
            f"no bf16 peak on record for device_kind {stamp['kind']!r}")
    from paddle_tpu.models import LlamaConfig
    from paddle_tpu.distributed import fleet

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1,
                               "pp_degree": 1, "sharding_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)

    if which == "llama350m":
        cfg = LlamaConfig(vocab_size=32000, hidden_size=1024,
                          intermediate_size=2816, num_hidden_layers=16,
                          num_attention_heads=16,
                          max_position_embeddings=1024)
        bs = 32
        tok, fpt, n, loss = _measure(cfg, bs, 1024, 20, 3)
    elif which == "llama1p3b":
        cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                          intermediate_size=5504, num_hidden_layers=24,
                          num_attention_heads=16,
                          max_position_embeddings=1024)
        bs = 8
        tok, fpt, n, loss = _measure(cfg, bs, 1024, 10, 2,
                                     moment_dtype="bfloat16",
                                     recompute_policy="full",
                                     ce_chunk=2048, lazy=True)
    else:
        raise ValueError(f"unknown config {which!r}")
    mfu = tok * fpt / PEAK_BF16_FLOPS[stamp["kind"]]
    print(json.dumps({"config": which, "tokens_per_sec_per_chip":
                      round(tok, 2), "mfu": round(mfu, 4),
                      "batch_size": bs, "n_params": n,
                      "final_loss": round(loss, 4), "device": stamp}),
          flush=True)


def main():
    if "--config" in sys.argv:
        return _run_config(sys.argv[sys.argv.index("--config") + 1])
    failed = []
    for which in CONFIGS:
        rc = subprocess.run([sys.executable, os.path.abspath(__file__),
                             "--config", which], timeout=1800).returncode
        if rc != 0:
            print(f"[bench] config {which} failed (rc={rc})",
                  file=sys.stderr)
            failed.append(which)
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
