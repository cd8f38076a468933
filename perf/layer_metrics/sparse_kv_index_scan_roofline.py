"""Serving kernels: the index scan as a share of its roofline in decode
steps, percent — bytes of the index keys VISIBLE to a step's queries (the
program's `sparse.keys_visible` over the window x one key's bytes / the
window's decode steps) / published bytes per second / device self time
under the `sparse_index_scores` scope in one `jit_step` execution (traced,
device 0). Dead keys the scan also reads (`sparse_index_scan_live_share`)
are no work: they lower this share."""
from harness import flops_sparse_gqa, manifest

_attend = manifest.load_plugin("layer_metrics", "sparse_kv_attend_roofline")


def read(rec):
    return _attend.scope_roofline(rec, "sparse_index_scores",
                                  "sparse.keys_visible",
                                  flops_sparse_gqa.index_key_bytes)
