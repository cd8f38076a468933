"""Scheduler host loop: median over decode steps of the time device 0
sat idle between one `cb.step`'s start and the next one's (traced): what
a step costs beyond the device's own work, whoever is to blame."""
from harness import span_reduce, stats


def read(rec):
    return stats.percentile(
        [st["idle_s"] * 1e3 for st in span_reduce.steps_of(rec, "decode")],
        50)
