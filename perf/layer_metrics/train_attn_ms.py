"""Training step: device time, ms, that one run of the training executable
spends in the attention block (`attn_proj`: norm, projections, rotary,
output projection; `attend`: the flash kernels), over forward, recompute
and backward together — device 0"s self time of the operations under
those phases, over the whole steps inside the traced window
(`harness/phase_times.py`). None where no operation carries them."""
from harness import phase_times


def read(rec):
    return phase_times.ms(rec, phase_times.train_module(rec),
                          ("attn_proj", "attend"))
