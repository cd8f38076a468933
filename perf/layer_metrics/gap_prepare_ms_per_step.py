"""Scheduler host loop: device-0 idle time per step while the engine got
the next program going: `cb.admit`, `cb.*.prepare`, `cb.decode.dispatch`,
`cb.prefill_chunk` and `cb.step`'s own time (traced; mean over steps)."""
from harness import span_reduce


def read(rec):
    return span_reduce.gap_ms_per_step(rec, "prepare")
