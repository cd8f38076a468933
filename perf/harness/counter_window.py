"""The program's always-on counters over the benchmark's window.

`paddle_tpu.profiler.counter_history("engine")` keeps a timestamped
sample of the engine's cumulative counters every time `health()` is
called; the serving runner calls it as the window opens and as it closes
(its emitted-token counter is `health()`'s). The difference of those two
samples is what happened in the window. None where the program keeps no
such history (the parent commit) or the window has no two samples.
"""


def delta(rec):
    """{name: value at the window's end - value at its start} (lists
    element-wise, nested one level), plus the samples' constants under
    their own names; None with nothing to read."""
    try:
        from paddle_tpu.profiler import counter_history
    except ImportError:
        return None
    if rec.get("kind") != "serve":
        return None
    t_ws, t_we = rec["window"]
    samples = counter_history("engine")
    first = next((v for t, v in samples if t >= t_ws), None)
    last = next((v for t, v in samples if t >= t_we), None)
    if first is None or last is None or first is last:
        return None

    def sub(a, b):
        if isinstance(a, list):
            return [sub(x, y) for x, y in zip(a, b)]
        return a - b

    return {k: (last[k] if k.endswith((".pages_total", ".window"))
                else sub(last[k], first[k]))
            for k in last if k in first}


def group_used_share(rec, windowed):
    """Mean over the window's steps of pages in use / pages there are,
    over the page groups that are (not) windowed; None without them."""
    d = delta(rec)
    if not d or not d.get("steps"):
        return None
    used = total = 0
    i = 0
    while f"group{i}.window" in d:
        if bool(d[f"group{i}.window"]) == windowed:
            used += d[f"group{i}.used_page_steps"]
            total += d[f"group{i}.pages_total"]
        i += 1
    return used / d["steps"] / total if total else None
