"""Step programs: mean device time, ms, that one run of the decode-step
program (`jit_step`) spends under the model phase `embed` + `head` (the
token embedding, the final norm, the head product and the greedy token /
top-k fold / write into the token vector) — device 0"s self time of the
operations whose name stack holds the phase, over the whole runs inside
the traced window (`harness/phase_times.py`). None where no operation
carries the phase: a CPU rehearsal, a program from before the phases."""
from harness import phase_times


def read(rec):
    return phase_times.ms(rec, "jit_step", ("embed", "head"))
