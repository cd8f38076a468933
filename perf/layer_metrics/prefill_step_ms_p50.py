"""Step programs: median device time of one prefill-chunk program (the
module the engine names `prefill`, traced, device 0). The host's wall of
such a step says little: a chunk that is not a prompt's last is dispatched
without waiting for it."""
from harness import stats


def read(rec):
    tr = rec.get("trace")
    if not tr or rec["kind"] != "serve":
        return None
    runs = tr["modules"].get("jit_prefill", [])
    return stats.percentile([d * 1e3 for d in runs], 50)
