"""Training step: the share of the training executable's device time that
no model phase owns — device 0's self time of the operations whose name
stack holds no phase of `paddle_tpu.profiler.PHASES` / all of the
executable's, over the whole steps inside the traced window
(`harness/phase_times.py`). Lower is better. 1.0 for a program from
before the phases; None without a device plane."""
from harness import phase_times


def read(rec):
    return phase_times.unphased_share(rec, phase_times.train_module(rec))
