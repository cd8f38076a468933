"""Entry: how late the load generator ran — 99th percentile of the time
from a request's scheduled due time to its add_request. The loop is
single-threaded, so a request can only be added between engine steps; a
large value says the tails were the generator's, not only the server's."""
from harness import serving_times, stats


def read(rec):
    if rec["kind"] != "serve" or rec["closed_loop"]:
        return None
    return stats.percentile(serving_times.generator_late_ms(rec), 99)
