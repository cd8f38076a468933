#!/usr/bin/env python
"""CTR training through the parameter server — the reference fork's
specialty workflow: slot-format files -> InMemoryDataset -> CTR-accessor
sparse table (embedx dormant until the show/click score crosses the
threshold) -> pooled embeddings -> dense tower.

    python examples/ctr_ps_training.py
"""
import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax
    jax.config.update("jax_platforms", "cpu")  # PS demo: tables live on
    #                                            the server, not the chip

    from paddle_tpu.chip import enable_compile_cache
    enable_compile_cache()
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu import optimizer
    from paddle_tpu.distributed import fleet, ps

    # 1. a slot-format file: "<n> label <n> feasigns... <n> feasigns..."
    rng = np.random.RandomState(0)
    lines = []
    for _ in range(64):
        click = rng.randint(0, 2)
        feas = rng.randint(0, 1000, rng.randint(1, 5))
        lines.append(" ".join(["1", str(click), str(len(feas))]
                              + [str(f) for f in feas]))
    f = tempfile.NamedTemporaryFile("w", suffix=".txt", delete=False)
    f.write("\n".join(lines))
    f.close()

    ds = fleet.InMemoryDataset()
    ds.init(batch_size=8, use_var=["click", "6"])
    ds.set_float_slots(["click"])
    ds.set_filelist([f.name])
    ds.load_into_memory()
    ds.local_shuffle()

    # 2. PS cluster + CTR sparse table + dense tower
    servers, cluster = ps.local_cluster(n_servers=2)
    emb = ps.DistributedEmbedding(8, cluster, optimizer="adagrad", lr=0.05,
                                  accessor="ctr", embedx_threshold=5.0)
    paddle.seed(0)
    tower = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 1))
    opt = optimizer.Adam(1e-3, parameters=tower.parameters())

    # 3. epochs over the in-memory data
    for epoch in range(2):
        for batch in ds:
            vals, lod = batch["6"]
            clicks, _ = batch["click"]
            pooled = []
            for i in range(len(lod) - 1):
                seg = vals[lod[i]:lod[i + 1]].astype(np.int64)
                vecs = emb(paddle.to_tensor(seg))   # PS pull (+push in bwd)
                pooled.append(vecs.mean(0))
            x = paddle.stack(pooled)
            y = paddle.to_tensor(clicks.reshape(-1, 1))
            loss = nn.functional.binary_cross_entropy_with_logits(tower(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
        print(f"epoch {epoch}: loss {float(loss):.4f}, "
              f"table rows {cluster.stat(0)['rows'] if hasattr(cluster, 'stat') else '?'}")

    cluster.close()
    for s in servers:
        s.stop()
    os.unlink(f.name)
    print("done")


if __name__ == "__main__":
    main()
