"""Serving kernels: the share of a prefill chunk's device time that the
latent attention takes — self time of the operations traced under
`sparse_index_scores`, `sparse_select`, `sparse_attend` and
`window_latent_attend` (index scoring, the radix-select top-k mask, the
blocked absorbed attention of full and window layers) inside the
`jit_prefill` executions of the traced window / all device time inside
them (`harness/scope_times.py`, device 0)."""
from harness import manifest

_chunk = manifest.load_plugin("layer_metrics", "latent_prefill_chunk_ms")


def read(rec):
    got = _chunk.chunk_seconds(rec)
    if got is None:
        return None
    seconds, _ = got
    return sum(seconds.get(s, 0.0) for s in _chunk.SCOPES) \
        / sum(seconds.values())
