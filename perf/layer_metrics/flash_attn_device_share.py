"""Training step: the share of a step's device time that the
flash-attention kernels take — device 0's self time of the Pallas calls
named `flash_attention_fwd` (run twice a layer under full recompute) and
`flash_attention_bwd` / its self time of ALL operations, both over the
executables that ran whole inside the traced window
(`harness/kernel_times.py`; the window's edges cut a step, and a share
with the cut step in one half only would read low). The XLA sum of the
backward's dq partials is not in it (the `reduce` family of the
breakdown). None where no kernel of that name ran: a serving cell, a CPU
rehearsal, a commit from before the kernels had names."""
from harness import kernel_times

FAMILIES = ("flash_attention_fwd", "flash_attention_bwd")


def read(rec):
    if not rec.get("trace") or rec["kind"] != "train":
        return None
    kt = kernel_times.of(rec)
    if not kt:
        return None
    tables = kt["seconds"].values()
    flash = sum(t.get(f, 0.0) for t in tables for f in FAMILIES)
    return flash / sum(sum(t.values()) for t in tables) if flash else None
