"""Serving kernels: the paged decode attention's share of its roofline,
percent — cached key and value bytes one step's attention must read over
all layers (full layers the whole context, window layers at most
`sliding_window` tokens; `flops_hybrid_moe.kv_read_bytes`) / published
bytes per second / device time of the `paged_attention_decode` kernels
in one `jit_step` execution (traced, device 0). The context is estimated
as `hybrid_decode_stream_share` does."""
from harness import flops_hybrid_moe, kernel_times, stats


def read(rec):
    if rec.get("kind") != "serve" or rec.get("peaks") is None \
            or "hybrid_layer_pattern" not in rec["model"]:
        return None
    seconds = kernel_times.per_run(rec, "jit_step",
                                   "paged_attention_decode")
    done = [r for r in rec["requests"] if r["state"] == "done"]
    running = [s[3] for s in rec["steps"] if s[2] == "decode"]
    if not seconds or not done or not running:
        return None
    context = stats.mean(r["n_prompt"] + r["n_out"] / 2 for r in done)
    need = flops_hybrid_moe.kv_read_bytes(
        rec["model"], [context] * round(stats.mean(running)))
    return 100.0 * need / rec["peaks"]["hbm_bytes_per_s"] / seconds
