"""Serving kernels: the attention over the SELECTED rows as a share of its
roofline in decode steps, percent — bytes of the [K ; V] rows a step's
queries attended to (the program's `sparse.keys_attended` over the window
x one layer's row bytes / the window's decode steps; a row is read once
for all 32 heads) / published bytes per second / device self time under
the `sparse_attend` scope in one `jit_step` execution (traced, device 0,
`harness/scope_times.py`). The count of work is the rows', whatever runs
the phase: today an XLA gather of the rows by page table and two batched
products, so most of the time is the gather's row-by-row movement."""
from harness import counter_window, flops_sparse_gqa, manifest, scope_times

_share = manifest.load_plugin("layer_metrics", "sparse_attn_decode_share")


def scope_roofline(rec, scope, counter, unit_bytes):
    """100 x (window total of `counter` x unit_bytes / decode steps) /
    HBM peak / (`scope`'s self seconds a `jit_step` run); None without
    the counters, the trace or the scope."""
    if rec.get("kind") != "serve" or rec.get("peaks") is None \
            or "sa_config" not in rec.get("model", {}):
        return None
    st = scope_times.of(rec, _share.SCOPES)
    d = counter_window.delta(rec)
    if not st or not d or not d.get("experts.decode_steps") \
            or not d.get(counter):
        return None
    seconds = st["seconds"].get("jit_step", {}).get(scope)
    runs = st["runs"].get("jit_step")
    if not seconds or not runs:
        return None
    need = d[counter] * unit_bytes(rec["model"]) / d["experts.decode_steps"]
    return 100.0 * need / rec["peaks"]["hbm_bytes_per_s"] / (seconds / runs)


def read(rec):
    return scope_roofline(rec, "sparse_attend", "sparse.keys_attended",
                          flops_sparse_gqa.row_bytes)
