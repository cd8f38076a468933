"""Admission: 90th percentile of time to first token from the scheduled
due time; a request that failed or never finished counts from its due
time to the moment the harness gave up."""
from harness import serving_times, stats


def read(rec):
    if rec["kind"] != "serve":
        return None
    return stats.percentile(serving_times.ttft_ms(rec), 90)
