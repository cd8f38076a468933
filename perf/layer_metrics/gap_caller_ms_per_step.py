"""Scheduler host loop: device-0 idle time per step outside any `cb.*`
span: the caller's loop between two `eng.step()`s, here the harness's
poll and inject (traced; mean over steps)."""
from harness import span_reduce


def read(rec):
    return span_reduce.gap_ms_per_step(rec, "caller")
