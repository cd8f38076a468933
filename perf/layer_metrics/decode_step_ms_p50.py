"""Step programs: median device time of one decode-step program (the
module the engine names `step`, traced, device 0)."""
from harness import stats


def read(rec):
    tr = rec.get("trace")
    if not tr or rec["kind"] != "serve":
        return None
    runs = tr["modules"].get("jit_step", [])
    return stats.percentile([d * 1e3 for d in runs], 50)
