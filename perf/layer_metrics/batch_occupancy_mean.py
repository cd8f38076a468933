"""Cache manager: mean over the window's steps of slots running over
slots there are (headroom() after every step)."""
from harness import stats


def read(rec):
    if rec["kind"] != "serve":
        return None
    return stats.mean(s[3] / rec["slots_total"] for s in rec["steps"])
