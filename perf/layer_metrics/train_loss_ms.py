"""Training step: device time, ms, that one run of the training executable
spends in the ends of the model (`embed`: the token embedding and its
scatter-add backward; `loss`: final norm, chunked head and cross-
entropy), over forward, recompute and backward together — device 0"s
self time of the operations under those phases, over the whole steps
inside the traced window (`harness/phase_times.py`). None where no
operation carries them."""
from harness import phase_times


def read(rec):
    return phase_times.ms(rec, phase_times.train_module(rec),
                          ("embed", "loss"))
