"""Worker for the 2-process eager collective-verb tests
(tests/test_eager_collectives.py). Drives every cross-process verb against
its known expected value; any mismatch raises -> nonzero exit."""
import os
import sys

if __name__ == "__main__":
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 1)

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.distributed as dist  # noqa: E402


def main():
    env = dist.init_parallel_env()
    rank, world = env.rank, env.world_size
    assert world == 2, world

    # reduce_scatter: ranks contribute [r+1]*4 -> sum [3,3,3,3]; rank r
    # owns rows [2r:2r+2]
    out = paddle.to_tensor(np.zeros(2, np.float32))
    inp = paddle.to_tensor(np.full(4, rank + 1, np.float32))
    dist.reduce_scatter(out, inp)
    np.testing.assert_allclose(np.asarray(out.data), [3.0, 3.0])

    # alltoall: rank r sends [r*10+j] to peer j
    ins = [paddle.to_tensor(np.array([rank * 10 + j], np.float32))
           for j in range(2)]
    outs = []
    dist.alltoall(outs, ins)
    np.testing.assert_allclose(
        [float(t.data[0]) for t in outs], [0 * 10 + rank, 1 * 10 + rank])

    # all_to_all_single
    out_s = paddle.to_tensor(np.zeros(2, np.float32))
    in_s = paddle.to_tensor(np.array([rank * 10, rank * 10 + 1], np.float32))
    dist.all_to_all_single(out_s, in_s)
    np.testing.assert_allclose(np.asarray(out_s.data),
                               [rank, 10 + rank])

    # broadcast from src=1
    t = paddle.to_tensor(np.full(3, float(rank), np.float32))
    dist.broadcast(t, src=1)
    np.testing.assert_allclose(np.asarray(t.data), [1.0, 1.0, 1.0])

    # scatter from src=0 (non-src passes no list)
    tgt = paddle.to_tensor(np.zeros(2, np.float32))
    if rank == 0:
        dist.scatter(tgt, [paddle.to_tensor(np.array([5.0, 5.0], np.float32)),
                           paddle.to_tensor(np.array([7.0, 7.0], np.float32))],
                     src=0)
        np.testing.assert_allclose(np.asarray(tgt.data), [5.0, 5.0])
    else:
        dist.scatter(tgt, src=0)
        np.testing.assert_allclose(np.asarray(tgt.data), [7.0, 7.0])

    # send/recv: 0 -> 1
    if rank == 0:
        dist.send(paddle.to_tensor(np.array([42.0], np.float32)), dst=1)
    else:
        buf = paddle.to_tensor(np.zeros(1, np.float32))
        dist.recv(buf, src=0)
        np.testing.assert_allclose(np.asarray(buf.data), [42.0])

    # batch_isend_irecv ring: each sends its rank to the other
    sbuf = paddle.to_tensor(np.array([float(rank)], np.float32))
    rbuf = paddle.to_tensor(np.zeros(1, np.float32))
    ops = [dist.P2POp(dist.isend, sbuf, (rank + 1) % 2),
           dist.P2POp(dist.irecv, rbuf, (rank + 1) % 2)]
    dist.batch_isend_irecv(ops)
    np.testing.assert_allclose(np.asarray(rbuf.data), [(rank + 1) % 2])

    # object collectives
    objs = []
    dist.all_gather_object(objs, {"rank": rank, "tag": "x" * (rank + 1)})
    assert objs == [{"rank": 0, "tag": "x"}, {"rank": 1, "tag": "xx"}], objs

    lst = [{"seed": 123, "rank": rank}] if rank == 0 else [None]
    dist.broadcast_object_list(lst, src=0)
    assert lst == [{"seed": 123, "rank": 0}], lst

    outl = []
    dist.scatter_object_list(
        outl, [f"part{j}" for j in range(2)] if rank == 0 else None, src=0)
    assert outl == [f"part{rank}"], outl

    print(f"rank {rank}: all eager cross-process verbs OK")


if __name__ == "__main__":
    main()
