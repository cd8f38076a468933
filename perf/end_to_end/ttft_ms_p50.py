"""Median time to first token, from the scheduled due time (so queueing
and a late generator count). The 90th percentile is a per-layer metric:
at the rates this system sustains a window holds some forty requests, and
the fourth-largest of forty readings is no steady number (PERF.md)."""
from harness import serving_times, stats


def read(rec):
    if rec["kind"] != "serve":
        return None
    return stats.percentile(serving_times.ttft_ms(rec), 50)
