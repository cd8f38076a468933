"""Mean gap between output tokens over every token the counted requests
were given: sum of (t_done - t_first) over sum of (tokens - 1). Where a
window holds some sixty requests this is steadier than the median of
per-request means, which moves with which request happens to be the
middle one."""


def read(rec):
    if rec["kind"] != "serve":
        return None
    done = [r for r in rec["requests"]
            if r["state"] == "done" and r["n_out"] > 1]
    if not done:
        return None
    return (sum(r["t_done"] - r["t_first"] for r in done)
            / sum(r["n_out"] - 1 for r in done) * 1e3)
