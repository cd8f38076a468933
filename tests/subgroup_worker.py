"""Worker for the 3-process SUBGROUP collective tests (VERDICT r3 next
#10): eager cross-process collectives over a strict subgroup ({0,2} of a
3-rank world) ride the store transport — non-members are unaffected —
and heterogeneous all_to_all_single split tables are honored."""
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
    assert world == 3, world

    sub = dist.new_group([0, 2])

    if rank in (0, 2):
        # subgroup all_reduce: members contribute rank+1 -> 1+3 = 4
        t = paddle.to_tensor(np.full(3, float(rank + 1), np.float32))
        dist.all_reduce(t, group=sub)
        np.testing.assert_allclose(np.asarray(t.data), [4.0, 4.0, 4.0])

        # subgroup all_gather
        lst = []
        dist.all_gather(lst, paddle.to_tensor(
            np.array([rank * 100.0], np.float32)), group=sub)
        np.testing.assert_allclose(
            [float(x.data[0]) for x in lst], [0.0, 200.0])

        # subgroup broadcast from world-rank 2
        b = paddle.to_tensor(np.full(2, float(rank), np.float32))
        dist.broadcast(b, src=2, group=sub)
        np.testing.assert_allclose(np.asarray(b.data), [2.0, 2.0])

        # subgroup object collective
        objs = []
        dist.all_gather_object(objs, {"r": rank}, group=sub)
        assert objs == [{"r": 0}, {"r": 2}], objs

        # non-member calling the subgroup verb must raise
    else:
        import pytest  # noqa: F401
        try:
            dist.all_reduce(paddle.to_tensor(np.zeros(1, np.float32)),
                            group=sub)
        except ValueError as e:
            assert "not a member" in str(e)
        else:
            raise AssertionError("non-member subgroup call did not raise")

    # heterogeneous all_to_all_single over the world: rank r's buffer has
    # 3*(r+1) rows (r+1 rows per destination), value = r*10 + dest
    per = rank + 1
    buf = np.concatenate([np.full(per, rank * 10 + d, np.float32)
                          for d in range(3)])
    in_splits = [per, per, per]
    # this rank receives s+1 rows from each source s -> 1+2+3 = 6 rows
    expect = np.concatenate([np.full(s + 1, s * 10 + rank, np.float32)
                             for s in range(3)])
    out = paddle.to_tensor(np.zeros(6, np.float32))
    dist.all_to_all_single(out, paddle.to_tensor(buf),
                           in_split_sizes=in_splits)
    np.testing.assert_allclose(np.asarray(out.data), expect)

    # a world object collective AFTER the subgroup traffic: per-group
    # generations must not have desynced the world keys
    objs = []
    dist.all_gather_object(objs, rank)
    assert objs == [0, 1, 2], objs

    print(f"rank {rank}: subgroup + heterogeneous verbs OK")


if __name__ == "__main__":
    main()
