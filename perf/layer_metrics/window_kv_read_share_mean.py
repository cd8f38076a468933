"""Cache manager: the share of the cached tokens a decode step's queries
read that the WINDOW layers read — over the window's decode steps, the
window page groups' `kv_tokens_read` / all groups' (the program's
per-group counters: per sequence and layer min(context, window) tokens in
a window group, the whole context in a full one). Three window layers of
4096 beside one full layer read two thirds at a mean context of 6k, and
all of it while every context is under the window. None where the program
keeps no such counter (the parent commit)."""
from harness import counter_window, manifest

_stream = manifest.load_plugin("layer_metrics",
                               "parallel_block_decode_stream_share")


def read(rec):
    tokens = _stream.kv_tokens_per_step(counter_window.delta(rec))
    if tokens is None or not sum(tokens):
        return None
    return tokens[0] / sum(tokens)
