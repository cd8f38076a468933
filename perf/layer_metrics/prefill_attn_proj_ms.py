"""Step programs: mean device time, ms, that one run of the prefill-chunk
program (`jit_prefill`) spends under the model phase `attn_proj` (the
pre-attention norm, the q/k/v (or latent and indexer) projections, per-
head norms, rotary, and the output projection with its residual add) —
device 0"s self time of the operations whose name stack holds the phase,
over the whole runs inside the traced window (`harness/phase_times.py`).
None where no operation carries the phase: a CPU rehearsal, a program
from before the phases."""
from harness import phase_times


def read(rec):
    return phase_times.ms(rec, "jit_prefill", ("attn_proj",))
