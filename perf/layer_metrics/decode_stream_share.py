"""Serving kernels: how close a decode step runs to streaming its bytes at
the HBM peak — (int8 weight bytes + cached key/value bytes a decode step
must read, from shapes) / published bytes per second / device time of one
decode step program (traced, device 0, the module the engine names
`step`).

The context a step reads is estimated from the harness's own records:
slots running (mean over the window's decode steps) times the mean
context of a request over its decode life (prompt + half its output, over
the requests counted). The weights are three quarters and more of the
bytes, so the estimate moves the share by a few percent at most."""
from harness import flops, stats


def read(rec):
    tr = rec.get("trace")
    if not tr or rec["kind"] != "serve" or rec.get("peaks") is None:
        return None
    times = tr["modules"].get("jit_step", [])
    done = [r for r in rec["requests"] if r["state"] == "done"]
    running = [s[3] for s in rec["steps"] if s[2] == "decode"]
    if not times or not done or not running:
        return None
    context = stats.mean(running) * stats.mean(
        r["n_prompt"] + r["n_out"] / 2 for r in done)
    need = flops.decode_step_bytes(rec["model"], context)
    return need / rec["peaks"]["hbm_bytes_per_s"] / stats.mean(times)
