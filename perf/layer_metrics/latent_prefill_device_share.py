"""Step programs: the share of the traced window's program time that the
latent prefill chunks take — all self time of device 0's operations
inside the `jit_prefill` executions / that plus the same of the
`jit_step` executions (`harness/scope_times.py`). The scheduler runs one
chunk between every two decode steps while any seat waits for prefill, so
at two thirds a sequence's token gap is mostly another sequence's
prompt. None where the trace holds none of the latent scopes."""
from harness import manifest, scope_times

_chunk = manifest.load_plugin("layer_metrics", "latent_prefill_chunk_ms")


def read(rec):
    got = _chunk.chunk_seconds(rec)
    if got is None:
        return None
    prefill = sum(got[0].values())
    step = scope_times.of(rec, _chunk.SCOPES)["seconds"].get("jit_step", {})
    return prefill / (prefill + sum(step.values()))
