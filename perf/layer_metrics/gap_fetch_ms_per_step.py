"""Scheduler host loop: device-0 idle time per step inside
`cb.decode.fetch`, `cb.prefill.first_token` and `cb.decode_step`'s own
time: the chip has finished and the result is on its way to the host
(traced; mean over steps)."""
from harness import span_reduce


def read(rec):
    return span_reduce.gap_ms_per_step(rec, "fetch")
