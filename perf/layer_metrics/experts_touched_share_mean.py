"""Experts: the share of the held experts a decode step touches — distinct
held experts with at least one (token, choice) row, per decode step and
expert layer, over the window / experts held (the program's routing
counters, from the `rows` the grouped product returns, accumulated on the
device inside the step program). A decode step streams the weights of
the touched experts only: with 16 x 8 picks over 128 experts about 0.63
when routing is even."""
from harness import counter_window, stats


def read(rec):
    d = counter_window.delta(rec)
    if not d or not d.get("experts.decode_steps"):
        return None
    held = len(d["experts.rows"][0])
    return stats.mean(d["experts.touched"]) / d["experts.decode_steps"] / held
