"""Cache manager: mean over the window's steps of KV pages not free
(reserved by running requests or held by the prefix cache) over pages
there are."""
from harness import stats


def read(rec):
    if rec["kind"] != "serve":
        return None
    return stats.mean(1.0 - s[5] / rec["pages_total"] for s in rec["steps"])
