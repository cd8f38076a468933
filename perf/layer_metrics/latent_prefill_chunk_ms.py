"""Step programs: mean device time of one prefill-chunk program of the
latent sparse block, ms — all self time of device 0's operations inside
the `jit_prefill` executions of the traced window / their count
(`harness/scope_times.py`). In a cell whose scheduler runs one chunk
between every two decode steps this is the larger part of the gap between
a sequence's tokens. None where the trace holds none of the latent
scopes (a program without latent layers)."""
from harness import scope_times

SCOPES = ("sparse_index_scores", "sparse_select", "sparse_attend",
          "window_latent_attend")


def chunk_seconds(rec):
    """{scope: self seconds} and executions of `jit_prefill`, or None."""
    if rec.get("kind") != "serve":
        return None
    st = scope_times.of(rec, SCOPES)
    if not st:
        return None
    seconds, runs = st["seconds"].get("jit_prefill"), \
        st["runs"].get("jit_prefill")
    if not seconds or not runs or not any(s in seconds for s in SCOPES):
        return None
    return seconds, runs


def read(rec):
    got = chunk_seconds(rec)
    if got is None:
        return None
    seconds, runs = got
    return 1e3 * sum(seconds.values()) / runs
