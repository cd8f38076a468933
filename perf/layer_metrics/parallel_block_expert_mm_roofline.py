"""Serving kernels: the grouped expert product's share of its roofline in
the parallel block's decode steps, percent — bytes the two products of
every layer's routed experts must move in one step (the touched experts'
weights once, the rows' activations in and out;
`flops_parallel_block.expert_mm_bytes` on the routing counters' means
over the window) / published bytes per second / device time of the
`moe_grouped_matmul` kernels in one `jit_step` execution (traced, device
0). Memory-bound: 3 rows an expert use a thousandth of the MXU."""
from harness import counter_window, flops_parallel_block, kernel_times


def read(rec):
    if rec.get("kind") != "serve" or rec.get("peaks") is None \
            or "use_parallel_block" not in rec.get("model", {}):
        return None
    seconds = kernel_times.per_run(rec, "jit_step", "moe_grouped_matmul")
    d = counter_window.delta(rec)
    if not seconds or not d or not d.get("experts.decode_steps"):
        return None
    steps = d["experts.decode_steps"]
    need = sum(flops_parallel_block.expert_mm_bytes(
        rec["model"], touched / steps, sum(rows) / steps)
        for touched, rows in zip(d["experts.touched"], d["experts.rows"]))
    return 100.0 * need / rec["peaks"]["hbm_bytes_per_s"] / seconds
