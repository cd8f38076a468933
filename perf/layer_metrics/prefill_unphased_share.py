"""Step programs: the share of a prefill-chunk program (`jit_prefill`)'s
device time that no model phase owns — device 0's self time of the
operations whose name stack holds no phase of
`paddle_tpu.profiler.PHASES` / all of the program's, over the whole runs
inside the traced window (`harness/phase_times.py`). Lower is better:
what is left here is what the per-phase metrics cannot place (a fusion
whose root sits outside every phase). 1.0 for a program from before the
phases; None without a device plane."""
from harness import phase_times


def read(rec):
    return phase_times.unphased_share(rec, "jit_prefill")
