"""Worker for the 2-process pipeline p2p test: rank0 owns stage0
(Linear 8->16 + ReLU), rank1 owns stage1 (Linear 16->4 + MSE). Forward
activations ride send_forward/recv_forward; the boundary gradient rides
send_backward/recv_backward. Rank0 dumps its final params; the test
compares against single-process training of the full net."""
import os
import sys

if __name__ == "__main__":
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 1)

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.nn as nn  # noqa: E402
import paddle_tpu.distributed as dist  # noqa: E402
from paddle_tpu import optimizer  # noqa: E402
from paddle_tpu.distributed.fleet.meta_parallel.pp_utils import (  # noqa: E402
    SendRecvMeta, recv_backward, recv_forward, send_backward, send_forward)


def main():
    out_path = sys.argv[1]
    env = dist.init_parallel_env()
    rank = env.rank
    assert env.world_size == 2

    rng = np.random.RandomState(7)
    X = rng.randn(4, 8).astype(np.float32)
    Y = rng.randn(4, 4).astype(np.float32)

    paddle.seed(42)  # BOTH ranks build the full net => identical init
    full = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    stage0 = nn.Sequential(full[0], full[1])
    stage1 = full[2]

    if rank == 0:
        opt = optimizer.SGD(learning_rate=0.1,
                            parameters=stage0.parameters())
        for _ in range(3):
            act = stage0(paddle.to_tensor(X))
            send_forward(act, dst=1)
            g = recv_backward(SendRecvMeta(tuple(act.shape), "float32"),
                              src=1)
            act.backward(g)
            opt.step()
            opt.clear_grad()
        np.savez(out_path,
                 w=np.asarray(stage0[0].weight.data),
                 b=np.asarray(stage0[0].bias.data))
    else:
        opt = optimizer.SGD(learning_rate=0.1,
                            parameters=stage1.parameters())
        for _ in range(3):
            act = recv_forward(SendRecvMeta((4, 16), "float32"), src=0)
            act.stop_gradient = False
            out = stage1(act)
            loss = ((out - paddle.to_tensor(Y)) ** 2).mean()
            loss.backward()
            send_backward(act.grad, dst=0)
            opt.step()
            opt.clear_grad()
    print(f"rank {rank}: pipeline p2p steps done")


if __name__ == "__main__":
    main()
