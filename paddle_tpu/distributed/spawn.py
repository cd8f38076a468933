"""paddle.distributed.spawn (ref: python/paddle/distributed/spawn.py:472).

Single-controller JAX note: one process can drive all local TPU chips, so
the common reason to spawn (1 proc/GPU) doesn't apply to training.
Multi-host jobs use the launcher (paddle_tpu.distributed.launch). spawn
serves one-engine-per-chip replica fleets, CPU-process tests and API
parity.

A chip belongs to one process: with more than one child, child `rank` is
bound to local chip `rank` (chip.chip_env, set before the child touches
jax), and the parent must itself stay off the jax backend.
"""
import multiprocessing
import os

from ..chip import chip_env


def spawn(func, args=(), nprocs=-1, join=True, daemon=False, **options):
    if nprocs == -1:
        nprocs = 1
    ctx = multiprocessing.get_context("spawn")
    procs = []
    for rank in range(nprocs):
        env = {"PADDLE_TRAINER_ID": str(rank),
               "PADDLE_TRAINERS_NUM": str(nprocs)}
        if nprocs > 1:
            env.update(chip_env(rank))
        p = ctx.Process(target=_wrap, args=(func, args, env), daemon=daemon)
        p.start()
        procs.append(p)
    if join:
        for p in procs:
            p.join()
    return procs


def _wrap(func, args, env):
    os.environ.update(env)
    func(*args)
