"""Step programs: mean device time, ms, that one run of the decode-step
program (`jit_step`) spends under the model phase `ffn` (the post-
attention norm and the dense SwiGLU, or the router, the routed experts"
permutation + grouped products + combine and the shared expert) — device
0"s self time of the operations whose name stack holds the phase, over
the whole runs inside the traced window (`harness/phase_times.py`). None
where no operation carries the phase: a CPU rehearsal, a program from
before the phases."""
from harness import phase_times


def read(rec):
    return phase_times.ms(rec, "jit_step", ("ffn",))
