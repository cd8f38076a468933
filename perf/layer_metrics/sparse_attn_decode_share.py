"""Serving kernels: the share of a decode step's device time that the
learned sparse attention takes — self time of the operations the program
traced under its `sparse_index_scores`, `sparse_select` and
`sparse_attend` scopes (index scoring, the exact top-k selection, the
gather of the selected rows and the attention over them) inside the
`jit_step` executions of the traced window / all device time inside them
(`harness/scope_times.py`, device 0). None where the trace holds no such
scope (a program without an indexer)."""
from harness import scope_times

SCOPES = ("sparse_index_scores", "sparse_select", "sparse_attend")


def read(rec):
    if rec.get("kind") != "serve":
        return None
    st = scope_times.of(rec, SCOPES)
    if not st:
        return None
    seconds = st["seconds"].get("jit_step")
    if not seconds or not any(s in seconds for s in SCOPES):
        return None
    return sum(seconds.get(s, 0.0) for s in SCOPES) / sum(seconds.values())
