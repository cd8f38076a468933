"""Step programs: mean device time of one prefill-chunk program of the
per-head sparse block, ms — all self time of device 0's operations inside
the `jit_prefill` executions of the traced window / their count
(`harness/scope_times.py`). None for another model family or where the
trace holds none of the sparse scopes."""
from harness import manifest, scope_times

_share = manifest.load_plugin("layer_metrics", "sparse_attn_decode_share")


def read(rec):
    if rec.get("kind") != "serve" \
            or "sa_config" not in rec.get("model", {}):
        return None
    st = scope_times.of(rec, _share.SCOPES)
    if not st:
        return None
    seconds, runs = st["seconds"].get("jit_prefill"), \
        st["runs"].get("jit_prefill")
    if not seconds or not runs \
            or not any(s in seconds for s in _share.SCOPES):
        return None
    return 1e3 * sum(seconds.values()) / runs
