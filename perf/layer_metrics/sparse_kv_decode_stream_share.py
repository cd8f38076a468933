"""Serving kernels: how close a decode step of the per-head sparse block
runs to streaming its bytes at the HBM peak — (weights a step must read:
attention, the float32 indexers and routers, the TOUCHED experts, the
head + cache: per sequence and layer `context` index keys and
min(context, topk) selected [K ; V] rows; from shapes,
`flops_sparse_gqa.decode_step_bytes`) / published bytes per second /
device time of one decode step program (traced, device 0, the module the
engine names `step`): the share of the WHOLE step, and it cannot pass 1.

Experts touched per layer per step come from the program's routing
counters over the window; the contexts from the harness's own records:
slots running (mean over the window's decode steps) sequences, the cache
bytes of one being the mean over the requests counted of the bytes at the
request's mean context over its decode life (prompt + half its output):
the min() is taken per request, not of the mean."""
from harness import counter_window, flops_sparse_gqa, stats


def read(rec):
    tr = rec.get("trace")
    if not tr or rec["kind"] != "serve" or rec.get("peaks") is None \
            or "sa_config" not in rec["model"]:
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
    per_seq = stats.mean(flops_sparse_gqa.cache_read_bytes(
        cfg, [r["n_prompt"] + r["n_out"] / 2]) for r in done)
    need = flops_sparse_gqa.decode_weight_bytes(cfg, touched) \
        + round(stats.mean(running)) * per_seq
    return need / rec["peaks"]["hbm_bytes_per_s"] / stats.mean(times)
