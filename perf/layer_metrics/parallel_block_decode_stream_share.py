"""Serving kernels: how close a decode step of the parallel block runs to
streaming its bytes at the HBM peak — (weights a step must read:
attention, the shared experts, the float32 routers, the TOUCHED held
experts, the head + cached keys and values: full layers the whole
context, window layers at most `sliding_window` tokens; from shapes,
`flops_parallel_block.decode_step_bytes`) / published bytes per second /
device time of one decode step program (traced, device 0, the module the
engine names `step`): the share of the WHOLE step, and it cannot pass 1.

Experts touched per layer per step and the cached tokens the step's
queries read, layer by layer, both come from the program's own counters
over the window (`experts.touched`, `group<i>.kv_tokens_read`: counted
from every sequence's own length, not estimated from a mean)."""
from harness import counter_window, flops_parallel_block, stats


def kv_tokens_per_step(d):
    """(window groups', full groups') cached tokens x layers read per
    decode step over the window; None without the counters."""
    if not d or not d.get("experts.decode_steps"):
        return None
    win = full = 0
    i = 0
    while f"group{i}.window" in d:
        n = d.get(f"group{i}.kv_tokens_read")
        if n is None:
            return None
        if d[f"group{i}.window"]:
            win += n
        else:
            full += n
        i += 1
    steps = d["experts.decode_steps"]
    return win / steps, full / steps


def read(rec):
    tr = rec.get("trace")
    if not tr or rec["kind"] != "serve" or rec.get("peaks") is None \
            or "use_parallel_block" not in rec["model"]:
        return None
    times = tr["modules"].get("jit_step", [])
    d = counter_window.delta(rec)
    tokens = kv_tokens_per_step(d)
    if not times or tokens is None:
        return None
    touched = stats.mean(d["experts.touched"]) / d["experts.decode_steps"]
    need = flops_parallel_block.decode_step_bytes(
        rec["model"], sum(tokens), touched)
    return need / rec["peaks"]["hbm_bytes_per_s"] / stats.mean(times)
