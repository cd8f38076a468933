"""Step programs: mean device time, ms, that one run of the decode-step
program (`jit_step`) spends in the shared experts (the one SwiGLU of
`num_shared_experts` x the width that every token takes, the plain scope
`shared_expert` inside the `ffn` phase) — device 0's self time of the
operations whose name stack holds the scope, over the `jit_step`
executions of the traced window (`harness/scope_times.py`). None where no
operation carries the scope: a CPU rehearsal, a program without shared
experts."""
from harness import scope_times

SCOPES = ("shared_expert",)


def read(rec):
    if rec.get("kind") != "serve":
        return None
    st = scope_times.of(rec, SCOPES)
    if not st:
        return None
    seconds = st["seconds"].get("jit_step", {}).get(SCOPES[0])
    runs = st["runs"].get("jit_step")
    return seconds / runs * 1e3 if seconds and runs else None
