"""Worker for the elastic restart-from-checkpoint test
(tests/test_elastic_resume.py).

Phase 1: 2-rank eager DataParallel training (stride-sharded batch) with
an ElasticManager heartbeat over the shared TCPStore; after 3 steps rank
0 checkpoints, then both ranks park in a heartbeat-alive wait loop — the
test SIGKILLs rank 1 there (its lease expires -> the observer's watch()
flips to RESTART) and releases rank 0 via the exit file.

Phase 2 (the elastic relaunch, world rewritten to 1): restores the
checkpoint and continues steps 3..5 on the FULL batch — DP equivalence
makes the whole trajectory match an uninterrupted 1-proc run.

ref: python/paddle/distributed/fleet/elastic/manager.py:126,243 (watch ->
endpoint rewrite -> restart; training resumes from user checkpoints).
"""
import os
import sys
import time

if __name__ == "__main__":
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 1)

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.nn as nn  # noqa: E402
import paddle_tpu.nn.functional as F  # noqa: E402
from paddle_tpu import optimizer  # noqa: E402
import paddle_tpu.distributed as dist  # noqa: E402


def build_model():
    paddle.seed(42)
    return nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))


def batch():
    rng = np.random.RandomState(7)
    return (rng.randn(8, 8).astype(np.float32),
            rng.randn(8, 4).astype(np.float32))


def train_steps(model, opt, X, Y, rank, world, lo, hi):
    xs = paddle.to_tensor(X[rank::world])
    ys = paddle.to_tensor(Y[rank::world])
    losses = []
    for _ in range(lo, hi):
        out = model(xs)
        loss = F.mse_loss(out, ys)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(np.asarray(loss.data)))
    return losses


def main():
    phase = os.environ["ELASTIC_PHASE"]
    ckpt = os.environ["ELASTIC_CKPT"]
    wait_dir = os.environ["ELASTIC_WAIT_DIR"]
    rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    world = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    X, Y = batch()

    # register with the elastic store (lease + heartbeat)
    from paddle_tpu.distributed.fleet.elastic import ElasticManager
    from paddle_tpu.distributed.fleet.elastic.tcp_store_backend import (
        TCPStoreElasticStore)
    store = TCPStoreElasticStore(
        "127.0.0.1", int(os.environ["ELASTIC_STORE_PORT"]),
        is_master=False, poll_interval=0.5)
    mgr = ElasticManager(f"127.0.0.1:{9000 + rank}",
                         job_id=os.environ["ELASTIC_JOB"], np=world,
                         min_np=1, store=store,
                         heartbeat_interval=0.5, lease_ttl=2)
    mgr.register()

    if phase == "1":
        env = dist.init_parallel_env()
        assert env.world_size == world == 2
        model = paddle.DataParallel(build_model())
        opt = optimizer.SGD(learning_rate=0.1,
                            parameters=model.parameters())
        losses = train_steps(model, opt, X, Y, rank, world, 0, 3)
        if rank == 0:
            params = {k: np.asarray(v.data)
                      for k, v in model.state_dict().items()}
            np.savez(ckpt, step=3, losses=np.asarray(losses), **params)
            os.replace(ckpt + ".npz", ckpt + ".ok.npz")
        open(os.path.join(wait_dir, f"done1.{rank}"), "w").write("ok")
        # park (heartbeats continue) until the controller releases us —
        # rank 1 is SIGKILLed here
        while not os.path.exists(os.path.join(wait_dir, "exit_ok")):
            time.sleep(0.2)
        return

    # phase 2: relaunched with the REWRITTEN world (1 rank); restore and
    # continue on the full batch
    assert world == 1
    data = np.load(ckpt + ".ok.npz")
    assert int(data["step"]) == 3
    model = build_model()
    sd = model.state_dict()
    model.set_state_dict({k: paddle.to_tensor(data[k]) for k in sd})
    opt = optimizer.SGD(learning_rate=0.1, parameters=model.parameters())
    losses = train_steps(model, opt, X, Y, 0, 1, 3, 6)
    np.savez(os.environ["ELASTIC_OUT"],
             phase1=data["losses"], phase2=np.asarray(losses))
    os.replace(os.environ["ELASTIC_OUT"] + ".npz",
               os.environ["ELASTIC_OUT"] + ".ok.npz")
    mgr.exit(completed=True)


if __name__ == "__main__":
    main()
