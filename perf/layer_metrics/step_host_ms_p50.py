"""Scheduler host loop: median over decode steps of `cb.step` less
`cb.decode.fetch` (where the host is blocked on the device): the host's
own work in a step, the floor of a step on an infinitely fast chip."""
from harness import span_reduce, stats


def read(rec):
    return stats.percentile(
        [(st["dur_s"] - st["blocked_s"]) * 1e3
         for st in span_reduce.steps_of(rec, "decode")], 50)
