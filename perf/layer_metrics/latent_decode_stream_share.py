"""Serving kernels: how close a decode step of the latent sparse block
runs to streaming its bytes at the HBM peak — (weights a step must read:
attention with its indexer, the dense FFN, the shared experts, the
routers, the TOUCHED held experts, the sliced head + cache: per sequence
and full layer min(context, index_topk) latent rows and `context` index
keys, per window layer min(context, window) rows; from shapes,
`flops_latent_sparse.decode_step_bytes`) / published bytes per second /
device time of one decode step program (traced, device 0, the module the
engine names `step`): the share of the WHOLE step, and it cannot pass 1.

Experts touched per routed layer per step come from the program's routing
counters over the window; the contexts from the harness's own records:
slots running (mean over the window's decode steps) sequences, the cache
bytes of one being the mean over the requests counted of the bytes at the
request's mean context over its decode life (prompt + half its output):
the min() is taken per request, not of the mean."""
from harness import counter_window, flops_latent_sparse, stats


def read(rec):
    tr = rec.get("trace")
    if not tr or rec["kind"] != "serve" or rec.get("peaks") is None \
            or "index_topk" not in rec["model"]:
        return None
    times = tr["modules"].get("jit_step", [])
    done = [r for r in rec["requests"] if r["state"] == "done"]
    running = [s[3] for s in rec["steps"] if s[2] == "decode"]
    d = counter_window.delta(rec)
    if not times or not done or not running or not d \
            or not d.get("experts.decode_steps"):
        return None
    cfg = rec["model"]
    touched = stats.mean(d["experts.touched"]) / d["experts.decode_steps"]
    per_seq = stats.mean(flops_latent_sparse.cache_read_bytes(
        cfg, [r["n_prompt"] + r["n_out"] / 2]) for r in done)
    need = flops_latent_sparse.decode_weight_bytes(cfg, touched) \
        + round(stats.mean(running)) * per_seq
    return need / rec["peaks"]["hbm_bytes_per_s"] / stats.mean(times)
