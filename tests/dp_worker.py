"""Worker script for the 2-process eager DataParallel test
(launched by tests/test_eager_multiprocess.py; the reference analog is
unittests/test_parallel_dygraph_dataparallel.py worker scripts).

Trains a small MLP on this rank's HALF of a fixed batch; EagerReducer
averages gradients across the two processes, so the result must equal a
single-process run over the full batch. Rank 0 dumps final params.
"""
import os
import sys

if __name__ == "__main__":
    # worker-process jax config; must NOT run when the test process
    # imports this module for build_model (its backend is already live)
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


def main():
    out_path = sys.argv[1]
    env = dist.init_parallel_env()
    rank, world = env.rank, env.world_size
    assert world == 2, world

    model = build_model()
    model = paddle.DataParallel(model)
    opt = optimizer.SGD(learning_rate=0.1, parameters=model.parameters())

    rng = np.random.RandomState(7)
    X = rng.randn(8, 8).astype(np.float32)
    Y = rng.randn(8, 4).astype(np.float32)
    half = X.shape[0] // world
    xs = paddle.to_tensor(X[rank * half:(rank + 1) * half])
    ys = paddle.to_tensor(Y[rank * half:(rank + 1) * half])

    for step in range(5):
        out = model(xs)
        loss = F.mse_loss(out, ys)
        loss.backward()
        opt.step()
        opt.clear_grad()

    if rank == 0:
        params = {k: np.asarray(v.data)
                  for k, v in model.state_dict().items()}
        np.savez(out_path, **params)
    print(f"rank {rank} done", flush=True)


if __name__ == "__main__":
    main()
