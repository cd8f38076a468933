"""Serving kernels: the paged decode attention's share of its roofline in
the parallel block's decode steps, percent — cached key and value bytes
one step's queries must read over all layers (16 query heads share a KV
head's bytes; full layers the whole context, window layers at most
`sliding_window` tokens: the program's `group<i>.kv_tokens_read` over the
window x one layer's bytes a token / the window's decode steps) /
published bytes per second / device time of the `paged_attention_decode`
kernels in one `jit_step` execution (traced, device 0)."""
from harness import counter_window, flops_parallel_block, kernel_times, \
    manifest

_stream = manifest.load_plugin("layer_metrics",
                               "parallel_block_decode_stream_share")


def read(rec):
    if rec.get("kind") != "serve" or rec.get("peaks") is None \
            or "use_parallel_block" not in rec.get("model", {}):
        return None
    seconds = kernel_times.per_run(rec, "jit_step",
                                   "paged_attention_decode")
    tokens = _stream.kv_tokens_per_step(counter_window.delta(rec))
    if not seconds or tokens is None:
        return None
    need = sum(tokens) * flops_parallel_block.kv_bytes_per_token(
        rec["model"])
    return 100.0 * need / rec["peaks"]["hbm_bytes_per_s"] / seconds
