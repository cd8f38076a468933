"""Per-request times from the serving runner's records, in milliseconds.

All stamps are the harness's own `time.monotonic()` readings; `t_due`
comes from the seeded schedule, so queueing and a late generator count.
"""


def ttft_ms(rec):
    """Time to first token of every counted request. One that failed or
    never produced a token counts from its due time to the moment the
    harness gave up on it — over any value a served request shows."""
    return [((r["t_first"] if r["t_first"] is not None
              and r["state"] == "done" else rec["t_give_up"])
             - r["t_due"]) * 1e3 for r in rec["requests"]]


def tpot_ms(rec):
    """Mean gap between output tokens of every completed request with at
    least two tokens: (t_done - t_first) / (tokens - 1)."""
    return [(r["t_done"] - r["t_first"]) / (r["n_out"] - 1) * 1e3
            for r in rec["requests"]
            if r["state"] == "done" and r["n_out"] > 1]


def queue_wait_ms(rec):
    """Due time to the moment the request left the queue for a slot."""
    return [(r["t_seat"] - r["t_due"]) * 1e3 for r in rec["requests"]
            if r["t_seat"] is not None]


def generator_late_ms(rec):
    """How long after it was due each request reached add_request."""
    return [(r["t_add"] - r["t_due"]) * 1e3 for r in rec["requests"]]
