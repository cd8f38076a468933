"""Scheduler host loop: device-0 idle time per step inside
`cb.decode.push`, the token bookkeeping and retirement (traced; mean
over steps)."""
from harness import span_reduce


def read(rec):
    return span_reduce.gap_ms_per_step(rec, "push")
