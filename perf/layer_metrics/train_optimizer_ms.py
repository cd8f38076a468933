"""Training step: device time, ms, that one run of the training executable
spends in the `optimizer` phase (the AdamW update of every parameter,
its flatten, gather and casts) — device 0"s self time of the operations
under any model phase in that pass, over the whole steps inside the
traced window (`harness/phase_times.py`; the executable is the module
with most device time). With `train_unphased_share` x the step, the four
passes sum to the step. None where no operation carries a phase."""
from harness import phase_times


def read(rec):
    return phase_times.ms(rec, phase_times.train_module(rec),
                          phase_times.PHASES, ("optimizer",))
