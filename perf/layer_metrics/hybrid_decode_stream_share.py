"""Serving kernels: how close a decode step of the hybrid block runs to
streaming its bytes at the HBM peak — (weights a step must read:
attention, the dense FFN, the routers, the TOUCHED held experts, the
sliced head + cached keys and values: full layers the whole context,
window layers at most `sliding_window` tokens; from shapes) / published
bytes per second / device time of one decode step program (traced,
device 0, the module the engine names `step`): the share of the WHOLE
step.

Experts touched per routed layer per step come from the program's routing
counters over the window; the context from the harness's own records,
as `decode_stream_share` estimates it: slots running (mean over the
window's decode steps) sequences, each at the mean context of a request
over its decode life (prompt + half its output)."""
from harness import counter_window, flops_hybrid_moe, stats


def read(rec):
    tr = rec.get("trace")
    if not tr or rec["kind"] != "serve" or rec.get("peaks") is None:
        return None
    times = tr["modules"].get("jit_step", [])
    done = [r for r in rec["requests"] if r["state"] == "done"]
    running = [s[3] for s in rec["steps"] if s[2] == "decode"]
    d = counter_window.delta(rec)
    if not times or not done or not running or not d \
            or not d.get("experts.decode_steps"):
        return None
    touched = stats.mean(d["experts.touched"]) / d["experts.decode_steps"]
    context = stats.mean(r["n_prompt"] + r["n_out"] / 2 for r in done)
    contexts = [context] * round(stats.mean(running))
    need = flops_hybrid_moe.decode_step_bytes(rec["model"], contexts,
                                              touched)
    return need / rec["peaks"]["hbm_bytes_per_s"] / stats.mean(times)
