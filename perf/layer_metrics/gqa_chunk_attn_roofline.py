"""Serving kernels: the chunk attention kernel's share of its roofline
in prefill chunks, percent — operations the attention of one chunk must
do over all layers ((query, key) pairs a causal query block covers, a
window layer at most `sliding_window` keys a query: the program's
`group<i>.prefill_pairs` over the window / its prefill chunks, x 4 x
query heads x head width; `flops_parallel_block.chunk_attention_flops`) /
published bf16 operations per second / device time of the
`paged_chunk_attention` kernels in one `jit_prefill` execution (traced,
device 0). Compute-bound: 16 query heads share every key and value byte
and a query block re-reads a page once, so the bytes' time is a
hundredth of the operations'. None where the trace holds no such kernel
(XLA key blocks ran the phase) or the program keeps no such counter."""
from harness import counter_window, flops_parallel_block, kernel_times


def read(rec):
    if rec.get("kind") != "serve" or rec.get("peaks") is None \
            or "use_parallel_block" not in rec.get("model", {}):
        return None
    seconds = kernel_times.per_run(rec, "jit_prefill",
                                   "paged_chunk_attention")
    d = counter_window.delta(rec)
    if not seconds or not d or not d.get("prefill_steps"):
        return None
    pairs, i = 0, 0
    while f"group{i}.window" in d:
        if d.get(f"group{i}.prefill_pairs") is None:
            return None
        pairs += d[f"group{i}.prefill_pairs"]
        i += 1
    need = flops_parallel_block.chunk_attention_flops(
        rec["model"], pairs / d["prefill_steps"])
    return 100.0 * need / rec["peaks"]["bf16_flops"] / seconds
