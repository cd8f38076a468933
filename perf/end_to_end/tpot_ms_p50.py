"""Median over completed requests of the mean gap between their output
tokens."""
from harness import serving_times, stats


def read(rec):
    if rec["kind"] != "serve":
        return None
    return stats.percentile(serving_times.tpot_ms(rec), 50)
