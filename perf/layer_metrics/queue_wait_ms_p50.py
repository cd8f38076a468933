"""Admission: median time from a request's due time until it left the
queue for a slot (read by polling status after every step)."""
from harness import serving_times, stats


def read(rec):
    if rec["kind"] != "serve":
        return None
    return stats.percentile(serving_times.queue_wait_ms(rec), 50)
