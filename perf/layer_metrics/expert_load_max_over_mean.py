"""Experts: how uneven the routing is — over the window's decode steps,
the busiest held expert's rows over the mean held expert's, per routed
layer, averaged over the layers (the program's routing counters). 1.0 is
perfectly even; the grouped product's time follows the experts TOUCHED,
the straggler chip of a deployment follows this."""
from harness import counter_window, stats


def read(rec):
    d = counter_window.delta(rec)
    if not d or not d.get("experts.decode_steps"):
        return None
    per_layer = [max(rows) / stats.mean(rows)
                 for rows in d["experts.rows"] if sum(rows)]
    return stats.mean(per_layer) if per_layer else None
