"""Experts: rows a held expert receives in a decode step — (token,
choice) rows routed to the experts held here, per decode step, per routed
layer, per held expert, over the window (the program's routing counters,
accumulated on the device inside the step program). slots x top_k /
experts when routing is even: 4 at 128 slots."""
from harness import counter_window


def read(rec):
    d = counter_window.delta(rec)
    if not d or not d.get("experts.decode_steps"):
        return None
    rows = d["experts.rows"]
    return sum(map(sum, rows)) / d["experts.decode_steps"] \
        / sum(map(len, rows))
